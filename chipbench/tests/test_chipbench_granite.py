"""granite-4.0-h-small at a tiny size on the CPU: the plain float32
reference against the program's served path, the expert shares against
the uncut layer, the cell's check, and the expert metric readers."""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

import chipbench_tiny
from chipbench_tiny import BENCH, ROOT
import harness
import run
from arch import common
from arch import granitemoehybrid as granite

import jax
import jax.numpy as jnp

# widths, depth and vocabulary cut so that a run takes seconds; every
# mechanism kept: both mixers in the published order, a held share of
# the experts (3 of 4 here), top-2, the shared expert and the multipliers
CUT = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=32, shared_intermediate_size=64,
           mamba_n_heads=4, mamba_d_state=16, mamba_chunk_size=8,
           vocab_size=500, num_hidden_layers=4,
           layer_types=["mamba", "attention", "mamba", "mamba"],
           num_local_experts=3, num_experts_per_tok=2,
           attention_multiplier=1.0 / 32, initializer_range=0.2)
SEED = 2**40 + 9


def config(**cut):
    c = json.loads((BENCH / "configs" / "granite-4.0-h-small.json")
                   .read_text())
    c.update(CUT, **cut)
    c["published"] = dict(c["published"], num_local_experts=4)
    return c


def bundle(limit: float, **traffic) -> dict:
    tr = json.loads((BENCH / "traffic" / "granite-h-small-chat.json")
                    .read_text())
    tr.update(prompt_len=16, gen_len=8, max_batch=4, segment_s=1.0)
    tr["arrivals"]["rps"] = 40.0          # so that batches form
    tr.update(traffic)
    tr["check"] = dict(requests=8, rows=8, max_logit_gap=limit)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return dict(cell={"name": "tiny-granite", "chips": 1}, config=config(),
                traffic=tr, end_to_end=bench["end_to_end"],
                per_layer=bench["per_layer"])


def _program(c, kernels="xla"):
    from repro.models import init_params
    from repro.models.config import ModelConfig
    cfg = ModelConfig(**granite.program_config(c), kernels=kernels,
                      param_dtype="float32", compute_dtype="float32")
    specs = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return (cfg, common.make_weights(c, granite, specs, SEED),
            common.make_weights(c, granite, specs, SEED, program=True))


@pytest.mark.parametrize("kernels", ["xla", "pallas_interpret"])
def test_reference_matches_the_program_in_float32(kernels):
    """Prefill then decode through the cache, against the reference's
    full forward pass over the same held experts."""
    from repro.models import decode_step, init_cache, prefill
    c = config()
    cfg, pub, w = _program(c, kernels)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              c["vocab_size"])
    ref = np.asarray(granite.logits(c, pub, toks,
                                    jnp.tile(jnp.arange(24), (2, 1))))
    scale = np.abs(ref).max()
    with jax.default_matmul_precision("highest"):
        lg, cache = prefill(cfg, w, toks[:, :20], init_cache(cfg, 2, 24))
        steps = [lg[:, -1]]
        for t in range(20, 23):
            lg, cache = decode_step(cfg, w, cache, toks[:, t:t + 1],
                                    jnp.int32(t))
            steps.append(lg[:, -1])
    served = np.stack([np.asarray(s) for s in steps], 1)
    assert np.abs(served - ref[:, 19:23]).max() <= 1e-4 * scale


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips of a stage each hold a quarter of the experts: the
    routed parts their expert layers give, with the shared MLP (which every
    chip computes alike) counted once, add up to the uncut layer."""
    from repro.models.layers import moe_dropless, swiglu
    c = config(num_local_experts=8, num_experts_per_tok=3)
    c["published"] = dict(c["published"], num_local_experts=8)
    d, f, fs, E = 128, 32, 64, 8
    names = {"router": (d, E), "w_gate": (E, d, f), "w_up": (E, d, f),
             "w_down": (E, f, d), "shared_gate": (d, fs),
             "shared_up": (d, fs), "shared_down": (fs, d)}
    key = common.seed_key(SEED)
    lw = {n: granite.weight(c, n, jax.random.fold_in(key, i), s)
          for i, (n, s) in enumerate(names.items())}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, d))
    with jax.default_matmul_precision("highest"):
        uncut = granite._ffn(x, lw, c=c, mm=common.matmul(""))
        parts = 0.0
        for s in range(4):
            share = dict(lw, **{n: lw[n][2 * s:2 * s + 2]
                                for n in ("w_gate", "w_up", "w_down")})
            y, counts = moe_dropless(x, share, k=3, first=2 * s,
                                     kernel="pallas_interpret")
            parts = parts + y
            assert int(counts[2]) == 0
        shared = swiglu(x, lw["shared_gate"], lw["shared_up"],
                        lw["shared_down"])
    np.testing.assert_allclose(parts + shared, uncut, atol=1e-5, rtol=1e-4)


LIMIT = 0.02     # the tiny size's own limit, above its bfloat16 readings


def _run(fault=""):
    return run.run_cell(bundle(LIMIT), SEED, 0.3, False, time.perf_counter(),
                        kernels="xla", fault=fault)


def test_sound_run_is_correct_and_reports_the_counters():
    res, lines = _run()
    assert res["correct"], lines
    assert res["check"]["max_logit_gap"]["value"] <= LIMIT


@pytest.mark.parametrize("fault,reading", [
    ("stale_cache", "max_logit_gap"), ("drop", "missing_rows")])
def test_broken_timed_path_is_not_correct(fault, reading):
    res, lines = _run(fault=fault)
    assert not res["correct"]
    r = res["check"][reading]
    assert r["value"] > r["limit"], lines


def _attrs(rows, fetches, dropped=0):
    return {f"moe_{p}_{n}": v for p in ("prefill", "decode")
            for n, v in (("rows", rows), ("fetches", fetches),
                         ("dropped", dropped))}


def _timeline(attrs):
    from repro.spans import Span
    recs = []
    for i, a in enumerate(attrs):
        run_ = Span("executor.run_batch", {}, start=100 * i,
                    end=100 * i + 90)
        recs += [run_, Span("executor.sync", a, start=100 * i + 80,
                            end=100 * i + 89, parent=run_)]
    return recs


def test_expert_readers_read_the_sync_spans(monkeypatch):
    from repro import spans
    from reduce_trace import Event, Trace
    import metrics.expert_rows_per_fetch as per_fetch
    import metrics.moe_gmm_roofline as gmm
    c, gen = config(), 8
    attrs = [_attrs(40, 6), _attrs(20, 4)]
    monkeypatch.setattr(spans, "timeline", lambda: _timeline(attrs))
    batches = [SimpleNamespace(traced=True, bucket=4),
               SimpleNamespace(traced=True, bucket=2)]
    calls = c["num_hidden_layers"] * (1 + gen)
    ms = 1_000_000
    ops, host = [], []
    for i in range(2):
        host.append(Event("device_call", i * 100 * ms, i * 100 * ms + 90 * ms))
        ops += [Event(f"moe_gmm.{j}", i * 100 * ms + j * 50_000,
                      i * 100 * ms + j * 50_000 + 40_000)
                for j in range(calls)]
    pk = SimpleNamespace(flops_bf16=197e12, hbm_bw=819e9)
    ctx = dict(batches=batches, info=[], config=c,
               traffic=dict(prompt_len=16, gen_len=gen), arch=granite,
               trace=Trace([ops], host), peaks=pk)
    assert per_fetch.read(ctx) == pytest.approx(60 / 10)
    bound = 0.0
    for a in attrs:
        flops, parts, n = granite.moe_gmm_work(
            c, gen, 2 * a["moe_decode_rows"], 2 * a["moe_decode_fetches"])
        assert n == calls
        bound += max(flops / pk.flops_bf16, sum(parts.values()) / pk.hbm_bw)
    want = 100.0 * bound / (2 * calls * 40_000 * 1e-9)
    assert gmm.read(ctx) == pytest.approx(want)
    # a program that records no counters (the parent's) reports nothing
    monkeypatch.setattr(spans, "timeline",
                        lambda: _timeline([{}, {}]))
    ctx["info"] = []
    assert per_fetch.read(ctx) is None and gmm.read(ctx) is None
