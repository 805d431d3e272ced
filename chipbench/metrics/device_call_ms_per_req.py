"""Wall time inside the executor's device calls (each ends in
``block_until_ready``) per completed request."""
from metrics._common import device_s


def read(ctx):
    return 1e3 * device_s(ctx) / ctx["completed"]
