"""Real JAX execution beneath the sandbox abstraction.

A *model instance* is the TPU-serving analogue of the paper's sandbox: a
compiled (prefill, decode) executable pair + resident weights + a KV-cache
slab.  Setting one up costs real time (XLA compile + weight init) — the
moral equivalent of the paper's container start + code download, and in the
same 0.1-10 s range (T3's SNE regime).

``JaxModelExecutor`` plugs into ``SemiGlobalScheduler`` through the
``execute`` hook: invocation -> measured wall seconds.
"""
from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.backends import pow2_bucket
from ..core.types import FunctionSpec, Invocation
from ..models import (decode_step, decode_step_ragged, init_cache,
                      init_params, prefill)
from ..models.config import ModelConfig
from ..models.transformer import MOE_COUNTS
from ..spans import span


def moe_counts(cache: Any) -> Dict[str, int]:
    """The expert layer's counters a request's final cache carries, summed
    over its prefill and over its decode steps (``moe_prefill_rows``, ...,
    ``moe_decode_dropped``); empty for a model without experts.  Reading
    them waits for the device and copies to the host."""
    if "moe" not in cache:
        return {}
    got = jax.device_get(cache["moe"])
    return {f"moe_{phase}_{name}": int(got[i, j])
            for i, phase in enumerate(("prefill", "decode"))
            for j, name in enumerate(MOE_COUNTS)}


def batch_seed(inv_ids: Iterable[int]) -> int:
    """Deterministic, order-INDEPENDENT seed for a batched execution.

    The member set alone determines the seed: coalescing order (which
    depends on flush timing) must not change what the batch computes.
    Seeding from ``invs[0].inv_id`` broke that — the same member set
    flushed in a different gather order executed different work."""
    data = b"".join(i.to_bytes(8, "little")
                    for i in sorted(int(i) for i in inv_ids))
    return zlib.crc32(data)


@dataclass
class ServedModel:
    """What a 'function' computes: prefill `prompt_len` tokens, then decode
    `gen_len` tokens, at batch size `batch`."""

    cfg: ModelConfig
    prompt_len: int = 64
    gen_len: int = 8
    batch: int = 1


@dataclass
class ModelInstance:
    """A warm sandbox: compiled executables + weights + cache."""

    served: ServedModel
    params: Any = None
    prefill_fn: Callable = None
    decode_fn: Callable = None
    cache0: Any = None
    setup_seconds: float = 0.0

    def setup(self, seed: int = 0) -> float:
        """Compile + initialize.  Returns real wall time (the sandbox setup
        overhead that Archipelago moves off the critical path).  Weights
        already set on the instance (shared with another instance of the
        same function) are kept; otherwise they are drawn from ``seed``."""
        t0 = time.perf_counter()
        sm = self.served
        cfg = sm.cfg
        if self.params is None:
            key = jax.random.PRNGKey(seed)
            self.params = jax.jit(lambda k: init_params(cfg, k))(key)
        max_len = sm.prompt_len + sm.gen_len
        self.cache0 = init_cache(cfg, sm.batch, max_len)

        def _prefill(params, tokens, cache, frontend=None):
            return prefill(cfg, params, tokens, cache, frontend)

        def _decode(params, cache, tok, t):
            return decode_step(cfg, params, cache, tok, t)

        self.prefill_fn = jax.jit(_prefill)
        self.decode_fn = jax.jit(_decode)
        # trigger compilation (part of setup, exactly like a container build)
        tokens = jnp.zeros((sm.batch, sm.prompt_len), jnp.int32)
        frontend = None
        if cfg.frontend:
            frontend = jnp.zeros((sm.batch, cfg.n_frontend_tokens,
                                  cfg.d_model), cfg.dtype())
            lg, c = self.prefill_fn(self.params, tokens, self.cache0, frontend)
        else:
            lg, c = self.prefill_fn(self.params, tokens, self.cache0)
        tok = jnp.zeros((sm.batch, 1), jnp.int32)
        lg2, _ = self.decode_fn(self.params, c, tok, jnp.int32(sm.prompt_len))
        jax.block_until_ready((lg, lg2))
        self.setup_seconds = time.perf_counter() - t0
        return self.setup_seconds

    def run(self, seed: int = 0) -> float:
        """One request: prefill + gen_len greedy decode steps.  Returns
        measured wall seconds."""
        sm = self.served
        cfg = sm.cfg
        t0 = time.perf_counter()
        with span("executor.prefill"):
            key = jax.random.PRNGKey(seed)
            tokens = jax.random.randint(key, (sm.batch, sm.prompt_len), 0,
                                        cfg.vocab_size)
            if cfg.frontend:
                frontend = jnp.zeros((sm.batch, cfg.n_frontend_tokens,
                                      cfg.d_model), cfg.dtype())
                logits, cache = self.prefill_fn(self.params, tokens,
                                                self.cache0, frontend)
            else:
                logits, cache = self.prefill_fn(self.params, tokens,
                                                self.cache0)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for i in range(sm.gen_len):
            with span("executor.decode_step"):
                logits, cache = self.decode_fn(self.params, cache, tok,
                                               jnp.int32(sm.prompt_len + i))
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with span("executor.sync") as sp:
            jax.block_until_ready(tok)
            if sp is not None:
                # recorded only while profiling: the counters become the
                # span's attributes after the final sync
                sp.attrs.update(moe_counts(cache))
        return time.perf_counter() - t0


class JaxModelExecutor:
    """Maps function names -> model instances; measures real setup/exec.

    Used two ways:
      * ``calibrate()`` produces FunctionSpecs whose exec_time / setup_time
        are *measured*, so the scheduler operates on real numbers.
      * as the SGS ``execute`` hook, it runs the actual model per invocation.
    """

    def __init__(self, served: Dict[str, ServedModel]):
        self.served = served
        self._instances: Dict[str, ModelInstance] = {}
        self.n_executions = 0

    def ensure_instance(self, fn_name: str) -> ModelInstance:
        inst = self._instances.get(fn_name)
        if inst is None:
            inst = ModelInstance(self.served[fn_name])
            inst.setup()
            self._instances[fn_name] = inst
        return inst

    def calibrate(self, mem_mb: float = 512.0,
                  runs: int = 3) -> Dict[str, FunctionSpec]:
        """Measure setup + exec time per function; build real FunctionSpecs."""
        specs = {}
        for name in self.served:
            inst = self.ensure_instance(name)
            times = [inst.run(seed=i) for i in range(runs)]
            specs[name] = FunctionSpec(
                name=name, exec_time=sorted(times)[len(times) // 2],
                mem_mb=mem_mb, setup_time=inst.setup_seconds)
        return specs

    def execute(self, inv: Invocation) -> float:
        """SGS execute hook: run the real model for this invocation."""
        inst = self.ensure_instance(inv.fn.name)
        self.n_executions += 1
        return inst.run(seed=inv.inv_id)


class BatchingJaxExecutor:
    """Batched data plane: pads concurrently in-flight invocations of the
    same ``ServedModel`` into one real batched execution.

    A *bucket* is a power-of-two batch size; each bucket gets its own
    compiled (prefill, decode) executable pair — all compiled up front in
    ``calibrate`` so sweeps pay XLA compiles exactly once.  At run time the
    coalescer (``repro.core.backends.BatchCoalescer``, which owns the
    time/size flush window) calls ``run_batch`` with the gathered
    invocations; the batch executes once at the smallest bucket that fits
    and every member shares the measured wall time.  Each invocation
    occupies one batch slot (one sequence): the bucket size *replaces* the
    ``ServedModel.batch`` dimension.

    Amortizing weight reads over the whole batch is why this sustains a
    multiple of the per-invocation executor's throughput once batches form
    — see ``benchmarks/bench_serving.py``'s batched-vs-unbatched
    comparison.  The buckets of one function share one copy of its
    weights (a published-width model would not fit device memory once per
    bucket).
    """

    def __init__(self, served: Dict[str, ServedModel], max_batch: int = 8):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.served = served
        self.max_batch = max_batch
        self._instances: Dict[Tuple[str, int], ModelInstance] = {}
        self._params: Dict[str, Any] = {}     # fn_name -> shared weights
        # calibration medians per (fn_name, bucket) — measured batched
        # execution seconds, recorded for reporting/analysis
        self.bucket_exec_s: Dict[Tuple[str, int], float] = {}
        self.n_executions = 0           # real batched runs

    def buckets(self) -> List[int]:
        """The power-of-two batch sizes compiled per model: 1, 2, 4, ...,
        up to the smallest power of two covering ``max_batch``."""
        out, b = [], 1
        top = pow2_bucket(self.max_batch)
        while b <= top:
            out.append(b)
            b *= 2
        return out

    def ensure_instance(self, fn_name: str, bucket: int) -> ModelInstance:
        key = (fn_name, bucket)
        inst = self._instances.get(key)
        if inst is None:
            inst = ModelInstance(replace(self.served[fn_name], batch=bucket),
                                 params=self._params.get(fn_name))
            inst.setup()
            self._params.setdefault(fn_name, inst.params)
            self._instances[key] = inst
        return inst

    def calibrate(self, mem_mb: float = 512.0,
                  runs: int = 3) -> Dict[str, FunctionSpec]:
        """Compile EVERY bucket executable per function (the whole compile
        bill lands here, off the serving path) and measure each bucket's
        batched execution time.  The returned ``FunctionSpec``s carry the
        batch-1 numbers — what a single invocation costs unbatched — so
        scheduling stays comparable with the per-invocation ``jax``
        backend; per-bucket medians live in ``bucket_exec_s``."""
        specs = {}
        for name in self.served:
            for b in self.buckets():
                inst = self.ensure_instance(name, b)
                times = [inst.run(seed=i) for i in range(runs)]
                self.bucket_exec_s[(name, b)] = sorted(times)[len(times) // 2]
            specs[name] = FunctionSpec(
                name=name, exec_time=self.bucket_exec_s[(name, 1)],
                mem_mb=mem_mb,
                setup_time=self._instances[(name, 1)].setup_seconds)
        return specs

    def run_batch(self, fn_name: str, invs: List[Invocation]) -> float:
        """Execute ``invs`` as ONE padded batch; returns measured wall
        seconds (the shared runtime of every member)."""
        bucket = pow2_bucket(len(invs))
        ids = [inv.inv_id for inv in invs]
        with span("executor.run_batch", bucket=bucket, n=len(invs),
                  members=ids):
            inst = self.ensure_instance(fn_name, bucket)
            self.n_executions += 1
            return inst.run(seed=batch_seed(ids))


@dataclass
class _ContinuousState:
    """Per-function continuous-serving state: resident weights + a slot slab.

    The *slab* is one persistent KV/SSM cache allocated at the padded
    capacity (``pow2_bucket(max_batch)`` sequences); every request owns one
    slot for its lifetime.  ``tok``/``pos`` hold each slot's last sampled
    token and absolute decode position.  Slots not marked active by the
    batcher are never gathered, so stale contents are harmless."""

    served: ServedModel
    cap: int
    params: Any = None
    slab: Any = None
    tok: Any = None                       # (cap, 1) int32
    pos: Any = None                       # (cap,)  int32
    join_fns: Dict[int, Callable] = field(default_factory=dict)
    step_fns: Dict[int, Callable] = field(default_factory=dict)
    setup_seconds: float = 0.0


class ContinuousJaxExecutor:
    """Step-granular data plane: real continuous batching over a slot slab.

    The real twin of ``repro.core.backends.ContinuousBatcher``'s hooks:

    * ``admit(fn, invs, slots)`` — ONE batched prefill of the joiners,
      scattered into their cache slots (plus the first sampled token).
    * ``step(fn, slots)`` — ONE fused ragged decode step for every active
      slot (``repro.models.decode_step_ragged``: per-row positions, so
      requests at different depths share the device step).
    * ``gen_steps(fn)`` — decode steps a request owes after its prefill.

    Batches are padded to power-of-two *buckets*; each bucket gets its own
    jitted (join, step) executable pair, all compiled in ``calibrate`` so
    the serving path never compiles.  Padding duplicates the first member's
    slot: duplicate gather rows compute identical values, so the duplicate
    scatter is deterministic.  Prompts are seeded from the order-independent
    ``batch_seed`` of the joining member set.

    Limitations: models with a modality frontend or an encoder stack
    (``cfg.frontend`` / encdec) keep the windowed data plane — their
    prefill needs per-request frontend frames, which the slot slab does not
    carry yet (see docs/SERVING.md).
    """

    def __init__(self, served: Dict[str, ServedModel], max_batch: int = 8):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        for name, sm in served.items():
            if sm.cfg.frontend or sm.cfg.arch_type == "encdec":
                raise NotImplementedError(
                    f"continuous batching does not support frontend/encdec "
                    f"models yet (function {name!r}, model {sm.cfg.name}); "
                    f"use batching='windowed'")
        self.served = served
        self.max_batch = max_batch
        self._state: Dict[str, _ContinuousState] = {}
        # calibration medians per (fn_name, bucket): batched prefill
        # seconds and per-decode-step seconds (roofline reporting)
        self.bucket_admit_s: Dict[Tuple[str, int], float] = {}
        self.bucket_step_s: Dict[Tuple[str, int], float] = {}
        self.n_executions = 0           # real device dispatches (admit+step)

    def buckets(self) -> List[int]:
        out, b = [], 1
        top = pow2_bucket(self.max_batch)
        while b <= top:
            out.append(b)
            b *= 2
        return out

    def gen_steps(self, fn_name: str) -> int:
        return self.served[fn_name].gen_len

    def _ensure(self, fn_name: str) -> _ContinuousState:
        st = self._state.get(fn_name)
        if st is None:
            st = self._setup(fn_name)
            self._state[fn_name] = st
        return st

    def _setup(self, fn_name: str) -> _ContinuousState:
        t0 = time.perf_counter()
        sm = self.served[fn_name]
        cfg = sm.cfg
        cap = pow2_bucket(self.max_batch)
        max_len = sm.prompt_len + sm.gen_len
        st = _ContinuousState(served=sm, cap=cap)
        st.params = jax.jit(lambda k: init_params(cfg, k))(
            jax.random.PRNGKey(0))
        st.slab = init_cache(cfg, cap, max_len)
        st.tok = jnp.zeros((cap, 1), jnp.int32)
        st.pos = jnp.zeros((cap,), jnp.int32)

        # the slab's per-layer entries are indexed by slot; the expert
        # counters of a MoE model's cache are not and stay as they are
        def scatter(slab, c, slot_ids):
            return dict(slab, layers=jax.tree.map(
                lambda s, cn: s.at[:, slot_ids].set(cn.astype(s.dtype)),
                slab["layers"], c["layers"]))

        def make_join(b: int) -> Callable:
            def _join(params, slab, tok, pos, tokens, slot_ids):
                cache = init_cache(cfg, b, max_len)
                lg, c = prefill(cfg, params, tokens, cache)
                first = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (b,1)
                slab = scatter(slab, c, slot_ids)
                tok = tok.at[slot_ids].set(first)
                pos = pos.at[slot_ids].set(
                    jnp.full((b,), sm.prompt_len, jnp.int32))
                return slab, tok, pos
            return jax.jit(_join)

        def make_step(b: int) -> Callable:
            def _step(params, slab, tok, pos, slot_ids):
                sub = dict(slab, layers=jax.tree.map(
                    lambda s: s[:, slot_ids], slab["layers"]))
                lg, c2 = decode_step_ragged(cfg, params, sub,
                                            tok[slot_ids], pos[slot_ids])
                ntok = jnp.argmax(lg, axis=-1).astype(jnp.int32)   # (b,1)
                slab = scatter(slab, c2, slot_ids)
                tok = tok.at[slot_ids].set(ntok)
                pos = pos.at[slot_ids].set(pos[slot_ids] + 1)
                return slab, tok, pos
            return jax.jit(_step)

        # compile every bucket up front (the whole compile bill is setup,
        # off the serving path — container build, in paper terms)
        for b in self.buckets():
            jf, sf = make_join(b), make_step(b)
            toks = jnp.zeros((b, sm.prompt_len), jnp.int32)
            ids = jnp.arange(b, dtype=jnp.int32)
            slab, tok, pos = jf(st.params, st.slab, st.tok, st.pos, toks, ids)
            slab, tok, pos = sf(st.params, slab, tok, pos, ids)
            jax.block_until_ready(tok)
            st.join_fns[b], st.step_fns[b] = jf, sf
        st.setup_seconds = time.perf_counter() - t0
        return st

    def _pad_slots(self, slots: List[int]) -> Tuple[int, jnp.ndarray]:
        """Pad the slot list to its bucket by repeating the first slot
        (duplicate rows compute identical values — deterministic)."""
        b = pow2_bucket(len(slots))
        pad = b - len(slots)
        return b, jnp.asarray(list(slots) + [slots[0]] * pad, jnp.int32)

    def admit(self, fn_name: str, invs: List[Invocation],
              slots: List[int]) -> float:
        return self._admit_seeded(fn_name,
                                  [inv.inv_id for inv in invs], slots)

    def _admit_seeded(self, fn_name: str, ids: List[int],
                      slots: List[int]) -> float:
        st = self._ensure(fn_name)
        sm = st.served
        t0 = time.perf_counter()
        b, slot_ids = self._pad_slots(slots)
        key = jax.random.PRNGKey(batch_seed(ids))
        toks = jax.random.randint(key, (len(slots), sm.prompt_len), 0,
                                  sm.cfg.vocab_size)
        if b > len(slots):
            toks = jnp.concatenate(
                [toks, jnp.broadcast_to(toks[:1],
                                        (b - len(slots),) + toks.shape[1:])])
        st.slab, st.tok, st.pos = st.join_fns[b](
            st.params, st.slab, st.tok, st.pos, toks, slot_ids)
        jax.block_until_ready(st.tok)
        self.n_executions += 1
        return time.perf_counter() - t0

    def step(self, fn_name: str, slots: List[int]) -> float:
        st = self._ensure(fn_name)
        t0 = time.perf_counter()
        b, slot_ids = self._pad_slots(slots)
        st.slab, st.tok, st.pos = st.step_fns[b](
            st.params, st.slab, st.tok, st.pos, slot_ids)
        jax.block_until_ready(st.tok)
        self.n_executions += 1
        return time.perf_counter() - t0

    def release_slots(self, fn_name: str, slots: List[int]) -> None:
        """Scrub the token/position rows of vacated cache slots.

        Called by the batcher when residents are dropped mid-flight (their
        worker crashed, core.fault): freed slots are never gathered again
        until re-admission overwrites them, so this is slab hygiene rather
        than correctness — it keeps dead requests' sampled tokens out of the
        state a debugger (or a later assertion) would inspect.  Cheap: two
        scatter updates, no cache-slab traffic."""
        st = self._state.get(fn_name)
        if st is None or not slots:
            return
        slot_ids = jnp.asarray(sorted(slots), jnp.int32)
        st.tok = st.tok.at[slot_ids].set(0)
        st.pos = st.pos.at[slot_ids].set(0)

    def calibrate(self, mem_mb: float = 512.0,
                  runs: int = 3) -> Dict[str, FunctionSpec]:
        """Compile every bucket executable per function and measure each
        bucket's batched prefill + per-step decode medians.  The returned
        ``FunctionSpec`` carries the batch-1 full-request time (prefill +
        ``gen_len`` steps) so scheduling stays comparable with the
        windowed/per-invocation backends; per-bucket medians live in
        ``bucket_admit_s`` / ``bucket_step_s``."""
        specs = {}
        for name in self.served:
            st = self._ensure(name)
            for b in self.buckets():
                slots = list(range(b))
                a = sorted(self._admit_seeded(name, slots, slots)
                           for _ in range(runs))
                s = sorted(self.step(name, slots) for _ in range(runs))
                self.bucket_admit_s[(name, b)] = a[runs // 2]
                self.bucket_step_s[(name, b)] = s[runs // 2]
            exec_s = (self.bucket_admit_s[(name, 1)]
                      + st.served.gen_len * self.bucket_step_s[(name, 1)])
            specs[name] = FunctionSpec(name=name, exec_time=exec_s,
                                       mem_mb=mem_mb,
                                       setup_time=st.setup_seconds)
        return specs
