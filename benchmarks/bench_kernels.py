"""Kernel microbenchmark: Pallas vs XLA on the serving hot spots.

For each dispatch-table op (``attention``, ``decode_attention``, ``ssd``)
at serving-representative shapes, measures median device time per backend
(``xla`` = jnp reference, ``pallas`` = compiled kernel), records analytic
FLOPs / HBM bytes and the roofline bound
(``max(flops/peak_flops, bytes/hbm_bw)``, peaks from
``repro.launch.mesh.peak_rates`` by the chip's ``device_kind``), and runs
an interpret-mode parity check (``pallas_interpret`` vs ``xla`` max abs
error) so the artifact itself witnesses numerical agreement.

Compiled Pallas only lowers on a TPU; on a CPU host the ``pallas_s``
column is ``null`` (interpret mode is an emulation path — timing it would
be meaningless), the bound is the v5e one asked for by name, and no
``*_vs_bound`` ratio is written (a CPU time over a chip bound means
nothing); the parity check and the ``xla`` timings still run.

    python -m benchmarks.bench_kernels [--smoke] [--reps N] [--out PATH]

Writes ``BENCH_kernels.json`` at the repo root; ``--smoke`` runs the small
shape subset with fewer reps and writes ``BENCH_kernels.partial.json``
(gitignored) so partial runs never clobber the tracked artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from .common import timer  # noqa: F401  (bootstraps sys.path for src/)

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.launch.mesh import V5E, peak_rates

DTYPE = jnp.bfloat16
BYTES = 2                     # bf16

# (B, S, Hq, Hkv, hd, causal, window); smoke keeps the first of each op
ATTN_SHAPES = [
    (1, 512, 8, 4, 64, True, 0),
    (1, 1024, 8, 4, 64, True, 0),
    (2, 512, 8, 8, 64, True, 128),
]
# (B, L, Hq, Hkv, hd) — one-token decode over a KV cache (continuous-
# batching step shape: B in-flight requests share one dispatch)
DEC_SHAPES = [
    (8, 512, 8, 4, 64),
    (16, 1024, 8, 4, 64),
]
# (B, S, H, P, N) — Mamba2 SSD chunked scan, chunk=64
SSD_SHAPES = [
    (1, 512, 8, 64, 64),
    (2, 1024, 8, 64, 64),
]


def _attn_cost(B, S, Hq, Hkv, hd, causal, window):
    """QK^T + AV are each 2*B*S*S*Hq*hd FLOPs; causal masking halves the
    useful work.  Bytes: q/k/v read + o written once (flash kernels never
    materialize the S x S score matrix in HBM)."""
    flops = 4 * B * S * S * Hq * hd * (0.5 if causal else 1.0)
    bytes_ = BYTES * (B * S * Hq * hd * 2 + B * S * Hkv * hd * 2)
    return flops, bytes_


def _dec_cost(B, L, Hq, Hkv, hd):
    flops = 4 * B * L * Hq * hd
    bytes_ = BYTES * (B * L * Hkv * hd * 2 + B * Hq * hd * 2)
    return flops, bytes_


def _ssd_cost(B, S, H, P, N):
    """Dominant terms per token: state update (dt*B outer-product accumulate,
    2*H*P*N), output contraction C.state (2*H*P*N), plus the intra-chunk
    quadratic term amortized to ~2*H*P*chunk -> fold into a 6x multiplier."""
    flops = 6 * B * S * H * P * N
    bytes_ = BYTES * (B * S * (H * P * 2 + H + 2 * N))
    return flops, bytes_


def _rand(key, shape):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(DTYPE)


def _cases(smoke: bool):
    """Yield (op, label, make_args(), (flops, bytes)) rows."""
    k = jax.random.PRNGKey(0)
    attn = ATTN_SHAPES[:1] if smoke else ATTN_SHAPES
    dec = DEC_SHAPES[:1] if smoke else DEC_SHAPES
    ssd = SSD_SHAPES[:1] if smoke else SSD_SHAPES
    for B, S, Hq, Hkv, hd, causal, window in attn:
        ks = jax.random.split(k, 3)
        args = (_rand(ks[0], (B, S, Hq, hd)), _rand(ks[1], (B, S, Hkv, hd)),
                _rand(ks[2], (B, S, Hkv, hd)))
        kw = dict(causal=causal, window=window)
        yield ("attention", f"attn_B{B}_S{S}_H{Hq}/{Hkv}_d{hd}"
               + (f"_w{window}" if window else ""),
               args, kw, _attn_cost(B, S, Hq, Hkv, hd, causal, window))
    for B, L, Hq, Hkv, hd in dec:
        ks = jax.random.split(k, 3)
        args = (_rand(ks[0], (B, Hq, hd)), _rand(ks[1], (B, L, Hkv, hd)),
                _rand(ks[2], (B, L, Hkv, hd)),
                jnp.full((B,), L, jnp.int32))
        yield ("decode_attention", f"dec_B{B}_L{L}_H{Hq}/{Hkv}_d{hd}",
               args, {}, _dec_cost(B, L, Hq, Hkv, hd))
    for B, S, H, P, N in ssd:
        ks = jax.random.split(k, 5)
        args = (_rand(ks[0], (B, S, H, P)),
                jax.nn.softplus(_rand(ks[1], (B, S, H)).astype(jnp.float32)),
                -jnp.exp(jax.random.normal(ks[2], (H,))),
                _rand(ks[3], (B, S, N)), _rand(ks[4], (B, S, N)))
        yield ("ssd", f"ssd_B{B}_S{S}_H{H}_P{P}_N{N}",
               args, dict(chunk=64), _ssd_cost(B, S, H, P, N))


def _median_time(fn, args, kw, reps: int) -> float:
    call = jax.jit(lambda *a: fn(*a, **kw))
    jax.block_until_ready(call(*args))          # compile outside the clock
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(call(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _max_err(a, b) -> float:
    fa = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), a)
    fb = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), b)
    errs = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), fa, fb)
    return max(jax.tree_util.tree_leaves(errs))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="first shape per op, fewer reps, partial artifact")
    ap.add_argument("--reps", type=int, default=0,
                    help="timed repetitions per cell (default 20, smoke 5)")
    ap.add_argument("--out", default="",
                    help="JSON artifact path (default: BENCH_kernels.json "
                         "at the repo root, or BENCH_kernels.partial.json "
                         "with --smoke)")
    args = ap.parse_args()
    reps = args.reps or (5 if args.smoke else 20)

    dev = jax.devices()[0]
    platform = dev.platform
    compiled_ok = platform == "tpu"
    # a chip is bounded by its own peaks; a CPU host records the v5e bound
    # it is asked for by name, and no time of its own against it
    roofline_kind = dev.device_kind if compiled_ok else V5E
    peak = peak_rates(roofline_kind)
    if not compiled_ok:
        print(f"[bench_kernels] platform={platform}: compiled Pallas "
              f"cannot lower here; pallas_s will be null (interpret "
              f"parity + xla timings still run)", flush=True)

    t0 = time.time()
    rows = []
    for op_name, label, op_args, op_kw, (flops, bytes_) in _cases(args.smoke):
        fn = getattr(ops, op_name)
        bound_s = max(flops / peak.flops_bf16, bytes_ / peak.hbm_bw)
        row = {
            "op": op_name, "case": label, "dtype": "bfloat16",
            "flops": flops, "hbm_bytes": bytes_,
            "roofline_bound_s": bound_s,
            "roofline_bound": ("hbm" if bytes_ / peak.hbm_bw
                               >= flops / peak.flops_bf16 else "compute"),
        }
        backends = ("xla", "pallas") if compiled_ok else ("xla",)
        for backend in backends:
            t = _median_time(fn, op_args, dict(op_kw, backend=backend), reps)
            row[f"{backend}_s"] = t
            row[f"{backend}_vs_bound"] = t / bound_s if compiled_ok else None
        if compiled_ok:
            row["pallas_speedup"] = row["xla_s"] / row["pallas_s"]
        else:
            row["pallas_s"] = row["pallas_vs_bound"] = None
            row["pallas_speedup"] = None
        # interpret parity: the artifact itself witnesses agreement
        row["interpret_max_abs_err"] = _max_err(
            fn(*op_args, **dict(op_kw, backend="pallas_interpret")),
            fn(*op_args, **dict(op_kw, backend="xla")))
        rows.append(row)
        pal = (f"pallas={row['pallas_s']*1e3:.2f}ms "
               f"({row['pallas_speedup']:.2f}x, " if compiled_ok
               else "pallas=n/a (")
        print(f"  {label:>28}: xla={row['xla_s']*1e3:.2f}ms {pal}"
              f"bound={bound_s*1e6:.0f}us {row['roofline_bound']}-bound, "
              f"interp_err={row['interpret_max_abs_err']:.2e})", flush=True)

    repo_root = Path(__file__).resolve().parent.parent
    default_name = ("BENCH_kernels.partial.json" if args.smoke
                    else "BENCH_kernels.json")
    out_path = Path(args.out) if args.out else repo_root / default_name
    payload = {
        "schema": 1,
        "bench": "kernels",
        "smoke": bool(args.smoke),
        "python": sys.version.split()[0],
        "jax": jax.__version__,
        "device": platform,
        "device_kind": dev.device_kind,
        "roofline_device_kind": roofline_kind,
        "reps": reps,
        "peak_flops_bf16": peak.flops_bf16,
        "hbm_bw": peak.hbm_bw,
        "metric": "median wall seconds per dispatch (block_until_ready), "
                  "vs analytic roofline bound max(flops/peak, bytes/bw)",
        "rows": rows,
        "wall_s": round(time.time() - t0, 2),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
