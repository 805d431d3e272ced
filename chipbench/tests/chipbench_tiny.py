"""Tiny bundles of the benchmark's cells for CPU tests: the published
configuration and traffic files with the widths, depth and lengths cut so
that a run takes seconds on a CPU, and with a limit of their own."""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "phi3": ("phi3-mini-3.8b", "phi3-chat",
             dict(hidden_size=128, intermediate_size=256,
                  num_attention_heads=4, num_key_value_heads=4,
                  num_hidden_layers=2, vocab_size=500,
                  initializer_range=0.2)),
    "mamba2": ("mamba2-370m", "mamba2-sparse",
               dict(d_model=64, n_layer=2, vocab_size=4000, d_state=16,
                    headdim=16, chunk_size=8, initializer_range=0.02)),
}


def bundle(kind: str, limit: float = 0.05, **traffic) -> dict:
    cfg_name, traffic_name, cut = TINY[kind]
    cfg = json.loads((BENCH / "configs" / f"{cfg_name}.json").read_text())
    cfg.update(cut)
    tr = json.loads((BENCH / "traffic" / f"{traffic_name}.json").read_text())
    tr.update(prompt_len=16, gen_len=8, max_batch=4, segment_s=1.0)
    tr["arrivals"]["rps"] = 40.0          # so that batches form
    tr.update(traffic)
    tr["check"] = dict(requests=8, rows=8, max_logit_gap=limit)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return dict(cell={"name": f"tiny-{kind}", "chips": 1}, config=cfg,
                traffic=tr, end_to_end=bench["end_to_end"],
                per_layer=bench["per_layer"])
