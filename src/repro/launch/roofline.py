import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch x shape), single-pod mesh:

    compute term    = HLO_FLOPs / peak_FLOP/s          (per device)
    memory term     = HLO_bytes / HBM_bw               (per device)
    collective term = collective_wire_bytes / ICI_bw   (per device)

Methodology — the while-loop problem.  XLA's ``cost_analysis`` counts a
while body ONCE regardless of trip count, and scan-over-layers puts every
layer inside a while loop.  We therefore compile *probe* configurations with
one layer per group and two layers per distinct group type — with scans
fully unrolled so the HLO is straight-line — and compose:

    total(metric) = probe_base + sum_T  delta_T * (layers_T - groups_T)

where delta_T is the exact per-layer cost of group type T (difference of two
straight-line compiles).  Collective bytes are read from the probes' HLO by
summing operand/result sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, converted to wire bytes with ring-algorithm
factors.  Memory analysis comes from the FULL compile (the real artifact).
"""
import argparse
import json
import re
from typing import Any, Dict, List, Optional, Tuple

from ..configs import ARCH_IDS, INPUT_SHAPES, get_config, shape_applicable
from ..models.config import LayerGroup, ModelConfig
from .mesh import V5E, make_production_mesh, peak_rates

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16, "s4": 1, "u4": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """bytes of an HLO type string like 'bf16[8,128,2304]{2,1,0}' or a
    tuple '(f32[4], bf16[8,16])'."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo: str, default_group: int) -> Dict[str, float]:
    """Sum *wire* bytes per collective kind from (straight-line) HLO text.

    Ring-algorithm factors per participating device:
      all-gather      (g-1)/g * result
      reduce-scatter  (g-1)/g * operand
      all-reduce      2 (g-1)/g * operand
      all-to-all      (g-1)/g * operand
      collective-permute   1  * operand
    """
    # symbol table: instruction name -> result bytes
    sizes: Dict[str, int] = {}
    for m in re.finditer(
            r"%?([\w.\-]+) = (\([^=]*?\)|\S+?\[[^\]]*\]\S*)\s", hlo):
        sizes[m.group(1)] = _shape_bytes(m.group(2))

    out = {k: 0.0 for k in _COLLECTIVES}
    pat = re.compile(
        r"%?([\w.\-]+) = (\([^=]*?\)|\S+?\[[^\]]*\]\S*)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(([^)]*)\)(.*)")
    for line in hlo.splitlines():
        line = line.strip()
        m = pat.match(line)
        if not m:
            continue
        name, rtype, kind, operands, rest = m.groups()
        if ".clone" in name and False:
            continue
        result_b = _shape_bytes(rtype)
        operand_b = sum(sizes.get(o.strip().lstrip("%"), 0)
                        for o in operands.split(",") if o.strip())
        # group size from replica_groups
        g = default_group
        gm = re.search(r"replica_groups=\{\{([\d,]+)\}", rest)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gm = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
            if gm:
                g = int(gm.group(2))
        g = max(g, 1)
        ring = (g - 1) / g
        if kind == "all-gather":
            out[kind] += ring * result_b
        elif kind == "all-reduce":
            out[kind] += 2 * ring * operand_b
        elif kind == "reduce-scatter":
            out[kind] += ring * operand_b
        elif kind == "all-to-all":
            out[kind] += ring * operand_b
        else:   # collective-permute
            out[kind] += operand_b
    return out


# ---------------------------------------------------------------------------
# probe configurations
# ---------------------------------------------------------------------------


def _type_key(g: LayerGroup) -> Tuple:
    return (g.kind, g.window, g.moe, g.cross_attn)


def _probe_cfg(cfg: ModelConfig, counts: List[int]) -> ModelConfig:
    groups = cfg.groups()
    new = tuple(LayerGroup(g.kind, c, window=g.window,
                           cross_attn=g.cross_attn, moe=g.moe)
                for g, c in zip(groups, counts) if c > 0)
    return cfg.with_(override_groups=new, scan_unroll=True)


def _measure(arch: str, shape: str, mesh, cfg: ModelConfig,
             fsdp: Optional[bool] = None) -> Dict[str, Any]:
    from .dryrun import lower_one
    compiled, info = lower_one(arch, shape, mesh, cfg=cfg, fsdp=fsdp)
    hlo = compiled.as_text()
    n_while = hlo.count(" while(")
    coll = parse_collective_bytes(hlo, default_group=mesh.devices.size)
    return {"flops": info["flops"], "bytes": info["bytes_accessed"],
            "coll": coll, "n_while": n_while}


def _compose(base: Dict, deltas: List[Tuple[Dict, int]]) -> Dict[str, float]:
    """total = base + sum(delta * extra_layers)."""
    tot = {"flops": base["flops"], "bytes": base["bytes"],
           "coll_bytes": sum(base["coll"].values())}
    coll_by_kind = dict(base["coll"])
    for d, extra in deltas:
        tot["flops"] += d["flops"] * extra
        tot["bytes"] += d["bytes"] * extra
        for k, v in d["coll"].items():
            coll_by_kind[k] = coll_by_kind.get(k, 0.0) + v * extra
    tot["coll_bytes"] = sum(coll_by_kind.values())
    tot["coll_by_kind"] = coll_by_kind
    return tot


def model_flops(cfg: ModelConfig, shape: str) -> float:
    """Analytic MODEL_FLOPS (global): 6*N*D train, 2*N*D inference, with
    N = active params (MoE: routed experts only).  Attention's quadratic
    term is excluded by convention — the useful-compute yardstick."""
    seq, batch, kind = INPUT_SHAPES[shape]
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * batch * seq
    if kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch          # decode: one token per sequence


def analyze(arch: str, shape: str, use_cache: bool = True) -> Dict[str, Any]:
    res_dir = os.path.join(RESULTS_DIR, "roofline")
    os.makedirs(res_dir, exist_ok=True)
    out_path = os.path.join(res_dir, f"{arch}__{shape}.json")
    if use_cache and os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=False)
    # FSDP decision must come from the FULL model's size, not the probes'
    from ..models.sharding import needs_fsdp
    fsdp = needs_fsdp(cfg, mesh)
    groups = cfg.groups()

    # distinct group types and their multiplicities
    types: Dict[Tuple, List[int]] = {}
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
        delta = {"flops": (probe["flops"] - base["flops"]) / len(idxs),
                 "bytes": (probe["bytes"] - base["bytes"]) / len(idxs),
                 "coll": {k: (probe["coll"][k] - base["coll"][k]) / len(idxs)
                          for k in probe["coll"]}}
        deltas.append((delta, extra))

    tot = _compose(base, deltas)
    n_dev = mesh.devices.size

    peak = peak_rates(V5E)
    compute_s = tot["flops"] / peak.flops_bf16
    memory_s = tot["bytes"] / peak.hbm_bw
    coll_s = tot["coll_bytes"] / peak.ici_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    bottleneck = max(terms, key=terms.get)

    mf_global = model_flops(cfg, shape)
    mf_dev = mf_global / n_dev
    result = {
        "arch": arch, "shape": shape, "mesh": "16x16", "n_devices": n_dev,
        "hlo_flops_dev": tot["flops"], "hlo_bytes_dev": tot["bytes"],
        "coll_bytes_dev": tot["coll_bytes"],
        "coll_by_kind": tot["coll_by_kind"],
        **terms,
        "bottleneck": bottleneck.replace("_s", ""),
        "model_flops_global": mf_global,
        "model_flops_dev": mf_dev,
        "useful_ratio": (mf_dev / tot["flops"]) if tot["flops"] else 0.0,
        "probe_while_loops": base["n_while"],
        "fsdp": bool(fsdp),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    rows = []
    for arch in archs:
        for shape in shapes:
            if not shape_applicable(arch, shape):
                continue
            r = analyze(arch, shape, use_cache=not args.force)
            rows.append(r)
            print(f"{arch:24s} {shape:12s} C={r['compute_s']*1e3:9.3f}ms "
                  f"M={r['memory_s']*1e3:9.3f}ms "
                  f"X={r['collective_s']*1e3:9.3f}ms "
                  f"dom={r['bottleneck']:10s} "
                  f"useful={r['useful_ratio']*100:5.1f}%")
    with open(os.path.join(RESULTS_DIR, "roofline", "table.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
