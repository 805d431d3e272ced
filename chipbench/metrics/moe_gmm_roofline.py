"""Share of its roofline the moe_gmm kernel reached in the traced part of
the window (``metrics._common.roofline``).  Its work depends on the
routing, so each traced batch's bound comes from that batch's own
counters (``metrics._moe``): 6 d f FLOPs per row, and the bytes of every
held expert fetched (gate, up and down) and of the rows read and
written (``arch.moe_gmm_work``)."""
from metrics._common import roofline
from metrics._moe import batch_counters

# the custom call's operands, inputs then outputs, named by the parts of
# the cost (None: the layer index and the schedule the group sizes make)
OPERANDS = ((None,) * 6 + ("x", "w_gate", "w_up", "w_down"), ("o",))


class _PerBatch:
    """The architecture's side with, for each traced batch in turn, that
    batch's expert work (``roofline`` asks once per traced batch, in
    order)."""

    def __init__(self, works):
        self._works = iter(works)

    def kernel_work(self, c, prompt, gen, bucket):
        return {"moe_gmm": next(self._works)}


def read(ctx):
    counts = batch_counters(ctx)
    if counts is None:
        return None
    arch, gen = ctx["arch"], ctx["traffic"]["gen_len"]
    works = [arch.moe_gmm_work(
        ctx["config"], gen, a["moe_prefill_rows"] + a["moe_decode_rows"],
        a["moe_prefill_fetches"] + a["moe_decode_fetches"]) for a in counts]
    return roofline(dict(ctx, arch=_PerBatch(works)), "moe_gmm", OPERANDS)
