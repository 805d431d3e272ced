"""Rows an expert computes per fetch of its weights in the decode steps of
the traced batches: rows that fell on held experts over held experts with
rows, from the program's counters (``metrics._moe``).  At one row per
fetch every row pays for a whole expert's weights; batching raises it."""
from metrics._moe import batch_counters


def read(ctx):
    counts = batch_counters(ctx)
    if counts is None:
        return None
    fetches = sum(a["moe_decode_fetches"] for a in counts)
    rows = sum(a["moe_decode_rows"] for a in counts)
    return rows / fetches if fetches else None
