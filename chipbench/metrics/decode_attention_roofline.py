"""Share of its roofline the decode_attention kernel reached in the traced
part of the window: the least time the chip needs for the work of every
call the traced batches made, counted from their shapes and from the
operands that cross HBM, over the device time of the kernel and of the
ops that move those operands (``metrics._common.roofline``)."""
from metrics._common import roofline

# the custom call's operands, inputs then outputs, named by the parts of
# the yardstick's cost (None: the valid lengths, which it does not count)
OPERANDS = ((None, "q", "k", "v"), ("o",))


def read(ctx):
    return roofline(ctx, "decode_attention", OPERANDS)
