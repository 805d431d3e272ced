"""Share of its roofline the decode_attention kernel reached in the traced part of the
window: the least time the chip needs for the work of every call the
traced batches made, counted from their shapes, over the kernel's device
time."""
from metrics._common import roofline


def read(ctx):
    return roofline(ctx, "decode_attention")
