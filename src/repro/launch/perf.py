import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Performance hillclimbing (§Perf): hypothesis -> change -> measure ->
validate, on the three chosen (arch x shape) pairs.

Pairs (from the baseline roofline table):
  A. mixtral-8x22b  x train_4k    -- worst roofline fraction (useful 5.7%,
                                     collective 636 s vs compute 86 s)
  B. minicpm-2b     x decode_32k  -- most collective-bound serving shape
                                     (X/C = 5500x; GSPMD full-remat of the
                                     hd-sharded KV cache at the RoPE split)
  C. llama4-scout   x decode_32k  -- most representative of the paper's
                                     technique (MoE decode = the serving
                                     workload Archipelago schedules)

Each variant is measured with the same probe-compose methodology as
repro.launch.roofline; results land in results/perf/<pair>__<variant>.json.
"""
import argparse
import json
from typing import Any, Dict, Optional

import jax

from ..configs import get_config
from .mesh import V5E, make_production_mesh, peak_rates
from .roofline import (_compose, _measure, _probe_cfg, _type_key,
                       model_flops)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "perf")


def make_expert_mesh():
    """256 chips as (data=16, expert=8, tp=2): experts whole on chips."""
    return jax.make_mesh((16, 8, 2), ("data", "expert", "tp"))


def measure_variant(arch: str, shape: str, *, mesh=None,
                    cfg_transform=None, fsdp: Optional[bool] = None
                    ) -> Dict[str, float]:
    cfg = get_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    mesh = mesh or make_production_mesh(multi_pod=False)
    if fsdp is None:
        # decided by the FULL model's size, not the probes'
        from ..models.sharding import needs_fsdp
        fsdp = needs_fsdp(cfg, mesh)
    groups = cfg.groups()
    types: Dict[Any, list] = {}
    for i, g in enumerate(groups):
        types.setdefault(_type_key(g), []).append(i)
    base_counts = [1] * len(groups)
    base = _measure(arch, shape, mesh, _probe_cfg(cfg, base_counts),
                    fsdp=fsdp)
    deltas = []
    for key, idxs in types.items():
        full_layers = sum(groups[i].count for i in idxs)
        extra = full_layers - len(idxs)
        if extra == 0:
            continue
        counts = list(base_counts)
        for i in idxs:
            counts[i] = 2
        probe = _measure(arch, shape, mesh, _probe_cfg(cfg, counts),
                         fsdp=fsdp)
        deltas.append((
            {"flops": (probe["flops"] - base["flops"]) / len(idxs),
             "bytes": (probe["bytes"] - base["bytes"]) / len(idxs),
             "coll": {k: (probe["coll"][k] - base["coll"][k]) / len(idxs)
                      for k in probe["coll"]}}, extra))
    tot = _compose(base, deltas)
    peak = peak_rates(V5E)
    out = {
        "compute_s": tot["flops"] / peak.flops_bf16,
        "memory_s": tot["bytes"] / peak.hbm_bw,
        "collective_s": tot["coll_bytes"] / peak.ici_bw,
        "hlo_flops_dev": tot["flops"],
        "hlo_bytes_dev": tot["bytes"],
        "coll_bytes_dev": tot["coll_bytes"],
        "coll_by_kind": tot["coll_by_kind"],
    }
    out["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: out[k])
    mf = model_flops(cfg, shape) / mesh.devices.size
    out["useful_ratio"] = mf / max(tot["flops"], 1.0)
    return out


VARIANTS = {
    # -- pair A: mixtral train --------------------------------------------------
    ("mixtral-8x22b", "train_4k"): {
        "baseline": {},
        # H-A1: experts live whole on chips (expert axis 8 x tp 2); MoE
        # traffic becomes all-to-all over 8 instead of full-f tensor shards
        "expert_mesh": {"mesh": "expert"},
        # H-A2: capacity factor 1.25 -> 1.0 shrinks every dispatch/expert
        # buffer by 20% (slight routing-drop quality trade, documented)
        "expert_mesh_cap1": {
            "mesh": "expert",
            "cfg_transform": lambda c: c.with_(capacity_factor=1.0)},
    },
    # -- pair B: minicpm decode -------------------------------------------------
    ("minicpm-2b", "decode_32k"): {
        "baseline": {},
        # H-B1: pad 36 heads -> 48 so the cache shards by kv head; removes
        # the RoPE-split full-remat at +33% attention FLOPs
        "pad_heads": {"cfg_transform": lambda c: c.pad_heads(16)},
    },
    # -- pair C: llama4 decode --------------------------------------------------
    ("llama4-scout-17b-a16e", "decode_32k"): {
        "baseline": {},
        # H-C1: decode is weight-stationary; FSDP all-gathers every weight
        # every token.  Model-axis-only sharding (13.6GB/dev) drops that.
        "no_fsdp": {"fsdp": False},
        # H-C2: pad kv heads 8 -> 16 so the cache shards by head
        "pad_heads": {"cfg_transform": lambda c: c.pad_heads(16)},
        # H-C3: both
        "pad_heads_no_fsdp": {"cfg_transform": lambda c: c.pad_heads(16),
                              "fsdp": False},
    },
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", default=None,
                    help="arch:shape filter, e.g. minicpm-2b:decode_32k")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    os.makedirs(RESULTS_DIR, exist_ok=True)
    for (arch, shape), variants in VARIANTS.items():
        if args.pair and args.pair != f"{arch}:{shape}":
            continue
        for vname, opts in variants.items():
            tag = f"{arch}__{shape}__{vname}"
            path = os.path.join(RESULTS_DIR, tag + ".json")
            if os.path.exists(path) and not args.force:
                with open(path) as f:
                    r = json.load(f)
            else:
                mesh = make_expert_mesh() if opts.get("mesh") == "expert" \
                    else None
                r = measure_variant(arch, shape, mesh=mesh,
                                    cfg_transform=opts.get("cfg_transform"),
                                    fsdp=opts.get("fsdp"))
                with open(path, "w") as f:
                    json.dump(r, f, indent=1)
            print(f"{tag:60s} C={r['compute_s']*1e3:10.3f}ms "
                  f"M={r['memory_s']*1e3:10.3f}ms "
                  f"X={r['collective_s']*1e3:10.3f}ms dom={r['bottleneck']}")


if __name__ == "__main__":
    main()
