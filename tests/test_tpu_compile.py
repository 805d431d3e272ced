"""Compile-only guard for the chip: the Pallas kernels and the served steps
compile for a described TPU v5e at published widths.

Nothing runs, so this says nothing about results or times (the
interpret-mode parity tests in ``test_kernels.py`` cover results); it
catches what interpret mode cannot — block shapes the tiling refuses,
primitives Mosaic does not lower, programs that do not fit the chip.  The
topology is described inside a fixture, never at import: only the worker
that is given this file loads the TPU compiler.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import (cache_len_on_lanes,
                                            decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import decode_step, init_cache, init_params, prefill

HBM_BYTES = 16e9              # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in TMPDIR
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# phi3-mini-3.8b: 32 heads of 96, no GQA grouping
PHI3_HEADS = (32, 32, 96)


@pytest.mark.parametrize("seq", [32, 512])
def test_flash_attention_compiles_at_phi3_widths(one_chip, seq):
    H, Hkv, hd = PHI3_HEADS
    q = _sds(one_chip, (1, seq, H, hd))
    kv = _sds(one_chip, (1, seq, Hkv, hd))
    assert _has_kernel(_compile(lambda q, k, v: flash_attention(q, k, v),
                                q, kv, kv))


@pytest.mark.parametrize("batch,cache_len", [(1, 136), (2, 136), (4, 1024)])
def test_decode_attention_compiles_at_phi3_decode_shapes(one_chip, batch,
                                                         cache_len):
    H, Hkv, hd = PHI3_HEADS
    q = _sds(one_chip, (batch, H, hd))
    kv = _sds(one_chip, (batch, cache_len, Hkv, hd))
    vlen = _sds(one_chip, (batch,), jnp.int32)
    assert _has_kernel(_compile(
        lambda q, k, v, n: decode_attention(q, k, v, n), q, kv, kv, vlen))


@pytest.mark.parametrize("batch,seq", [(1, 64), (2, 512)])
def test_ssd_scan_compiles_at_mamba2_widths(one_chip, batch, seq):
    cfg = get_config("mamba2-370m")
    H, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    args = (_sds(one_chip, (batch, seq, H, P)),
            _sds(one_chip, (batch, seq, H), jnp.float32),
            _sds(one_chip, (H,), jnp.float32),
            _sds(one_chip, (batch, seq, N)), _sds(one_chip, (batch, seq, N)))
    assert _has_kernel(_compile(
        lambda x, dt, A, b, c: ssd_scan(x, dt, A, b, c, chunk=cfg.ssm_chunk),
        *args))


# (arch, step, kernel expected): the mamba2 decode step is the jnp SSM
# recurrence by design (kernels.ops.KERNEL_TABLE["ssd_step"])
SERVED_STEPS = [("phi3-mini-3.8b", "prefill", True),
                ("phi3-mini-3.8b", "decode", True),
                ("mamba2-370m", "prefill", True),
                ("mamba2-370m", "decode", False)]


@pytest.mark.parametrize("arch,step,kernel", SERVED_STEPS)
def test_served_step_compiles_with_pallas_and_fits_one_chip(one_chip, arch,
                                                            step, kernel):
    """The published-width steps the chip smoke test serves (batch 2,
    prompt 128 + 8 generated), from ``jax.eval_shape`` shapes."""
    cfg = get_config(arch).with_(kernels="pallas")
    batch, prompt, gen = 2, 128, 8

    def place(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(lambda k: init_params(cfg, k),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, batch,
                                                    prompt + gen)))
    if step == "prefill":
        compiled = _compile(lambda p, x, c: prefill(cfg, p, x, c), params,
                            _sds(one_chip, (batch, prompt), jnp.int32), cache)
    else:
        compiled = _compile(lambda p, c, x, t: decode_step(cfg, p, c, x, t),
                            params, cache,
                            _sds(one_chip, (batch, 1), jnp.int32),
                            _sds(one_chip, (), jnp.int32))
    assert _has_kernel(compiled) == kernel
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


_INSTR = re.compile(r"\s*(ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\((.*?)\)(.*)$")


def _module(compiled):
    """The compiled module's instructions, ``name -> (dims, opcode,
    operands, arguments, called computation)``, and each computation's
    root (instruction names are unique across a module)."""
    ins, roots, comp = {}, {}, None
    for line in compiled.as_text().splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if m:
            root, name, shape, op, args, attrs = m.groups()
            dims = re.match(r"\w+\[([\d,]*)\]", shape)
            calls = re.search(r"calls=%([\w.\-]+)", attrs)
            ins[name] = (tuple(int(d) for d in dims.group(1).split(",") if d)
                         if dims else (), op,
                         re.findall(r"%([\w.\-]+)", args), args,
                         calls.group(1) if calls else None)
            if root:
                roots[comp] = name
    return ins, roots


def _kv_path(ins, roots, name, stacked):
    """The ops that carry a kernel operand from the stacked cache (the
    first value of ``stacked`` elements), kernel side first: each fusion
    by its root, bitcasts left out, following the moved data."""
    def unbitcast(n):
        while ins[n][1] == "bitcast":
            n = ins[n][2][0]
        return n

    path = []
    while math.prod(ins[name][0]) < stacked:
        _, op, operands, _, calls = ins[name]
        src = 0
        if op == "fusion":
            root = unbitcast(roots[calls])
            op = ins[root][1]
            param = unbitcast(ins[root][2][0])
            assert ins[param][1] == "parameter", (name, param)
            src = int(ins[param][3])
        if op != "bitcast":
            path.append(op)
        name = operands[src]
    return path


# (cache length, kv heads, head dim) of a stacked bf16 cache
# (layers, batch, L, Hkv, hd): phi3-chat's, shorter and longer caches at
# hd=96, GQA widths at hd 64/112/128, and tiny ones
CACHE_SHAPES = [(544, 32, 96), (136, 32, 96), (100, 32, 96), (96, 32, 96),
                (1024, 32, 96), (544, 8, 128), (1000, 4, 128), (544, 8, 64),
                (33, 2, 64), (2048, 16, 112), (160, 4, 112), (12, 2, 8)]


@pytest.mark.parametrize("L,Hkv,hd", CACHE_SHAPES)
def test_cache_len_on_lanes_matches_the_compiler(one_chip, L, Hkv, hd):
    """The decode kernel's orientation rule predicts the layout the chip's
    compiler gives a cache argument: the cache length minor or not."""
    compiled = _compile(lambda a: a + 1, _sds(one_chip, (4, 2, L, Hkv, hd)))
    layout = re.search(r"entry_computation_layout=\{\(\w+\[[\d,]*\]\{(\d+)",
                       compiled.as_text())
    assert (int(layout.group(1)) == 2) == cache_len_on_lanes(L, hd)


# (model, layers, bucket): phi3-chat's decode step at the buckets its
# traffic fills (cache 544, hd=96: the cache length on lanes), and a
# hd=128 GQA model's (head dim on lanes) in a 2-layer scan
KV_STEPS = [("phi3-mini-3.8b", None, 1), ("phi3-mini-3.8b", None, 2),
            ("minitron-8b", 2, 2)]


@pytest.mark.parametrize("arch,layers,batch", KV_STEPS)
def test_decode_attention_reads_the_cache_as_stored(one_chip, arch, layers,
                                                    batch):
    """The decode kernel takes K/V in the layout the chip keeps the cache
    in: no relayout copy of a layer's K or V (only the token's), and each
    reaches the kernel through one slice of the stacked cache and the
    token's update."""
    cfg = get_config(arch).with_(kernels="pallas")
    if layers:
        cfg = cfg.with_(n_layers=layers)
    cache_len = 544

    def place(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(lambda k: init_params(cfg, k),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, batch, cache_len)))
    compiled = _compile(lambda p, c, x, t: decode_step(cfg, p, c, x, t),
                        params, cache, _sds(one_chip, (batch, 1), jnp.int32),
                        _sds(one_chip, (), jnp.int32))
    ins, roots = _module(compiled)
    layer = (batch, cache_len, cfg.n_kv_heads, cfg.hd)
    relayouts = [n for n, (dims, op, *_) in ins.items()
                 if op in ("copy", "transpose")
                 and sorted(d for d in dims if d > 1)
                 == sorted(d for d in layer if d > 1)]
    assert not relayouts
    kernels = [n for n, (_, op, *_) in ins.items()
               if op == "custom-call" and n.startswith("decode_attention")]
    assert kernels
    for kern in kernels:
        for kv in ins[kern][2][2:4]:
            assert _kv_path(ins, roots, kv, cfg.n_layers * math.prod(layer)
                            ) == ["dynamic-update-slice", "dynamic-slice"]
