"""Host time per completed request: the window's wall time outside the
executor's device calls (scheduler, simulator, harness), per request."""
from metrics._common import device_s


def read(ctx):
    return 1e3 * (ctx["window_s"] - device_s(ctx)) / ctx["completed"]
