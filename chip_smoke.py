"""Chip smoke test: the serving path end to end on one TPU chip.

Serves phi3-mini-3.8b and mamba2-370m at their published configurations
(random weights drawn from ``PRNGKey(0)``) as two tenants of one
Archipelago ``Experiment`` — LBS -> SGS -> worker -> JAX data plane — with
compiled Pallas kernels, in three phases:

  (a) ``backend="jax"``: one model run per invocation;
  (b) ``backend="jax-batched"``, windowed, ``max_batch=2``: the batch
      buckets of each model share one weight copy;
  (c) on the chip, Pallas against XLA on each model's prefill plus a few
      decode steps, float32 logits within ``LOGITS_RTOL``.

    python chip_smoke.py

It runs in one process and has no CPU mode: on any other platform it exits
nonzero and names the platform it found.  Any failure in any phase exits
nonzero.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import gc
import json
import os
import sys

# Both models at published widths; the prompt of mamba2 is not a multiple
# of its 64-token SSD chunk, so the padded kernel path is served too.
MODELS = {                      # dag id -> (arch, prompt_len, gen_len)
    "phi3": ("phi3-mini-3.8b", 128, 8),
    "mamba2": ("mamba2-370m", 96, 8),
}
# the mamba2 decode step is the jnp SSM recurrence by design
# (kernels.ops.KERNEL_TABLE["ssd_step"]); every other step is a kernel
KERNEL_EXPECTED = {("phi3", "prefill"): True, ("phi3", "decode"): True,
                   ("mamba2", "prefill"): True, ("mamba2", "decode"): False}
SLACK_S = 1.0                   # deadline = measured critical path + 1 s
RPS = 8.0                       # per tenant
# Traffic starts after every prewarmed sandbox is up (setup = compile +
# weight init, measured) and lasts a few seconds of simulated time.
TRAFFIC_START_S = 120.0
TRAFFIC_S = 4.0
# A request that meets a sandbox still being set up (a scale-out cold
# start costs the measured set-up, tens of seconds) completes after it:
# simulate long enough that every admitted request finishes.
DRAIN_S = 600.0
MAX_BATCH = 2
BATCH_WINDOW_S = 0.05           # simulated seconds a batch waits to fill
PARITY_BATCH = 2
PARITY_DECODE_STEPS = 4
# max|pallas - xla| / max|xla| over each step's logits, both in float32
# activations at "highest" matmul precision.  The kernels then differ from
# the references only in f32 summation order (~2e-6 for the SSD scan on the
# chip); depth amplifies that (about 50x for mamba2's 48 layers, measured
# in bf16), so 1e-3 leaves a margin of ten where a wrong mask or block
# gives errors of order one.
LOGITS_RTOL = 1e-3


def _apps():
    from repro.configs import get_config
    from repro.serving import ServedModel, ServingApp
    return [ServingApp(dag, {f"{dag}/generate": ServedModel(
                get_config(arch), prompt_len=prompt, gen_len=gen)},
                       slack=SLACK_S)
            for dag, (arch, prompt, gen) in MODELS.items()]


def _kernel_calls(inst) -> dict:
    """Whether the lowered prefill / decode programs of a served instance
    contain a compiled Pallas kernel."""
    import jax.numpy as jnp
    sm = inst.served
    toks = jnp.zeros((sm.batch, sm.prompt_len), jnp.int32)
    tok = jnp.zeros((sm.batch, 1), jnp.int32)
    pre = inst.prefill_fn.lower(inst.params, toks, inst.cache0).as_text()
    dec = inst.decode_fn.lower(inst.params, inst.cache0, tok,
                               jnp.int32(sm.prompt_len)).as_text()
    return {"prefill": "tpu_custom_call" in pre,
            "decode": "tpu_custom_call" in dec}


def _serve(phase: str, backend: str, backend_kwargs: dict, dev) -> None:
    from repro.core import ClusterConfig
    from repro.sim import Experiment, simulate
    from repro.sim.workload import ConstantRate, WindowedRate

    apps = _apps()
    end = TRAFFIC_START_S + TRAFFIC_S
    exp = Experiment(
        stack="archipelago", backend=backend,
        backend_kwargs=dict(backend_kwargs, kernels="pallas"),
        workload_factory="serving_apps",
        workload_kwargs=dict(
            apps=apps, duration=end, prewarm_per_fn=2,
            arrivals={a.dag_id: WindowedRate(ConstantRate(RPS),
                                             start=TRAFFIC_START_S, end=end)
                      for a in apps}),
        cluster=ClusterConfig(n_sgs=2, workers_per_sgs=2, cores_per_worker=2),
        warmup=TRAFFIC_START_S, drain=DRAIN_S)
    res = simulate(exp)
    be = res.sim.backend
    ex = be.executor
    if res.data_plane.get("kernels") != "pallas":
        raise RuntimeError(f"{phase}: data plane {res.data_plane}, "
                           f"not compiled Pallas")
    for dag in MODELS:
        fn = f"{dag}/generate"
        spec = be.fn_specs[fn]
        stats = res.per_class[dag]
        if backend == "jax":
            inst, exec_ms = ex.ensure_instance(fn), spec.exec_time * 1e3
        else:
            inst = ex.ensure_instance(fn, MAX_BATCH)
            shared = all(ex.ensure_instance(fn, b).params is inst.params
                         for b in ex.buckets())
            if not shared:
                raise RuntimeError(f"{phase} {dag}: buckets hold separate "
                                   f"weight copies")
            exec_ms = {b: ex.bucket_exec_s[(fn, b)] * 1e3
                       for b in ex.buckets()}
        calls = _kernel_calls(inst)
        print(f"[{phase}] {dag} ({MODELS[dag][0]}): "
              f"setup_incl_compile_s={spec.setup_time:.2f} "
              f"calibrated_exec_ms={exec_ms} "
              f"completed={stats.n_completed}/{stats.n_requests} "
              f"deadlines_met={stats.deadline_met_frac} "
              f"p50_s={stats.p50} p99_s={stats.p99} "
              f"cold_starts={stats.cold_starts} "
              f"tpu_custom_call={calls}", flush=True)
        if not 0 < stats.n_completed == stats.n_requests:
            raise RuntimeError(f"{phase} {dag}: completed {stats.n_completed}"
                               f" of {stats.n_requests} requests")
        for step, has in calls.items():
            if has != KERNEL_EXPECTED[(dag, step)]:
                raise RuntimeError(f"{phase} {dag} {step}: tpu_custom_call "
                                   f"{'present' if has else 'missing'}")
    print(f"[{phase}] executions={res.backend_counters} "
          f"accounting={res.accounting} wall_s={res.wall_s} "
          f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}",
          flush=True)
    lost = res.accounting.get("lost", 0)
    if lost:
        raise RuntimeError(f"{phase}: {lost} requests lost")


def _logits(cfg, params, toks, feed, precision=None):
    """Prefill then ``PARITY_DECODE_STEPS`` decode steps; returns each
    step's logits and the tokens fed.  ``feed`` None decodes greedily."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import decode_step, init_cache, prefill

    prompt = toks.shape[1]
    with jax.default_matmul_precision(precision):
        pf = jax.jit(lambda p, x, c: prefill(cfg, p, x, c))
        dc = jax.jit(lambda p, c, x, t: decode_step(cfg, p, c, x, t))
        lg, cache = pf(params, toks, init_cache(
            cfg, toks.shape[0], prompt + PARITY_DECODE_STEPS))
        steps, fed = [lg], []
        for i in range(PARITY_DECODE_STEPS):
            tok = (jnp.argmax(lg, axis=-1).astype(jnp.int32) if feed is None
                   else feed[i])
            fed.append(tok)
            lg, cache = dc(params, cache, tok, jnp.int32(prompt + i))
            steps.append(lg)
    return [np.asarray(s, np.float32) for s in steps], fed


def _parity(dev) -> None:
    """Pallas against XLA, on the chip, at the served widths: prefill then
    decode steps that every path takes on the reference's greedy tokens.

    What decides is float32 activations under "highest" matmul precision
    (weights stay bf16), with XLA as the plain reference.  The served bf16
    paths are printed beside it: in bf16 both sit far from the reference
    on random weights (one bf16 rounding of an SSD output amplifies to
    ~0.2 of mamba2's logits over 48 layers), so a bf16 pallas-vs-xla gap
    says nothing about the kernels."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params

    for dag, (arch, prompt, _) in MODELS.items():
        base = get_config(arch)
        params = jax.jit(lambda k: init_params(base, k))(
            jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (PARITY_BATCH, prompt), 0, base.vocab_size)
        f32 = base.with_(compute_dtype="float32")
        ref, feed = _logits(f32.with_(kernels="xla"), params, toks, None,
                            "highest")
        runs = {
            "pallas_f32": _logits(f32.with_(kernels="pallas"), params, toks,
                                  feed, "highest")[0],
            "xla_bf16": _logits(base.with_(kernels="xla"), params, toks,
                                feed)[0],
            "pallas_bf16": _logits(base.with_(kernels="pallas"), params,
                                   toks, feed)[0]}
        del params
        want = (PARITY_BATCH, 1, base.vocab_padded)
        err = {}
        for name, steps in runs.items():
            for i, (got, r) in enumerate(zip(steps, ref)):
                if got.shape != want or not np.isfinite(got).all():
                    raise RuntimeError(f"[c] {dag} {name} step {i}: logits "
                                       f"{got.shape}, finite "
                                       f"{np.isfinite(got).all()}; want "
                                       f"{want}, finite")
            err[name] = max(float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
                            for g, r in zip(steps, ref))
        print(f"[c] {dag} ({arch}): prefill + {PARITY_DECODE_STEPS} decode "
              f"steps, max|x - ref|/max|ref| against XLA float32 at highest "
              f"precision: pallas_f32={err['pallas_f32']:.4e} (tolerance "
              f"{LOGITS_RTOL}); served bf16: xla={err['xla_bf16']:.4e} "
              f"pallas={err['pallas_bf16']:.4e} "
              f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}",
              flush=True)
        if err["pallas_f32"] > LOGITS_RTOL:
            raise RuntimeError(f"[c] {dag}: pallas vs xla in float32 "
                               f"{err['pallas_f32']:.3e} > {LOGITS_RTOL}")


def _live_bytes() -> int:
    import jax
    return sum(a.nbytes for a in jax.live_arrays())


def main() -> int:
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}",
          flush=True)
    _serve("a", "jax", {}, dev)
    gc.collect()
    print(f"live bytes after (a): {_live_bytes()}", flush=True)
    _serve("b", "jax-batched", dict(batching="windowed", max_batch=MAX_BATCH,
                                    batch_window=BATCH_WINDOW_S), dev)
    gc.collect()
    print(f"live bytes after (b): {_live_bytes()}", flush=True)
    _parity(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
