"""The kernel roofline's rule on a synthetic HLO module and trace: only
bytes that cross HBM count, and the ops that stage an operand between HBM
and VMEM are charged with the kernel."""
import re

import pytest

import chipbench_tiny
from chipbench_tiny import BENCH
import harness
import hlo
import yardstick
from harness import Batch
from reduce_trace import Event, Trace

MS = 1e6
MODULE = "jit__decode(7)"
KV = "bf16[2,1,24,4,32]{4,3,2,1,0:T(8,128)(2,1)}"          # the cache, HBM
LAYER = "bf16[1,1,24,4,32]{4,3,2,1,0:T(8,128)(2,1)S(1)}"    # one layer, VMEM
LAYER_T = "bf16[1,1,24,4,32]{4,2,3,1,0:T(8,128)(2,1)S(1)}"  # relayouted
ROWS = "bf16[1,24,4,32]{3,1,2,0:T(8,128)(2,1)S(1)}"
HEADS = "bf16[4,24,32]{2,1,0:T(8,128)(2,1)S(1)}"
TOKEN = "bf16[1,1,4,32]{3,1,2,0:T(2,128)(2,1)S(1)}"
Q = "bf16[4,1,32]{2,1,0:T(2,128)(2,1)S(1)}"


def _staged_kv(kv: str, i: int) -> str:
    """The parent's chain for one of K and V: the layer's slice read from
    HBM, relayouted in VMEM, the new token written in, a bitcast."""
    return f"""
  %constant_dynamic-slice_fusion.{i} = {LAYER} fusion(%{kv}, %layer), kind=kLoop, calls=%fused_slice.{i}
  %copy.{i} = {LAYER_T} copy(%constant_dynamic-slice_fusion.{i})
  %bitcast.{i}1 = {ROWS} bitcast(%copy.{i})
  %token.{i} = {TOKEN} reshape(%fusion.{i})
  %dynamic_update_slice.{i} = {ROWS} dynamic-update-slice(%bitcast.{i}1, %token.{i}, %zero, %pos, %zero, /*index=5*/%zero)
  %bitcast.{i}2 = {HEADS} bitcast(%dynamic_update_slice.{i})"""


def _slice_computation(i: int) -> str:
    return f"""
%fused_slice.{i} (param_0.{i}: bf16[2,1,24,4,32], param_1.{i}: s32[]) -> bf16[1,1,24,4,32] {{
  %param_0.{i} = {KV} parameter(0)
  %param_1.{i} = s32[]{{:T(128)}} parameter(1)
  %constant.{i} = s32[]{{:T(128)}} constant(0)
  ROOT %dynamic_slice.{i} = {LAYER} dynamic-slice(%param_0.{i}, %param_1.{i}, %constant.{i}, %constant.{i}, %constant.{i}, /*index=5*/%constant.{i}), dynamic_slice_sizes={{1,1,24,4,32}}
}}
"""


def _prefetched_kv(kv: str, i: int) -> str:
    """One of K and V copied whole from HBM into VMEM by an asynchronous
    copy, as XLA prefetches an operand."""
    hbm = HEADS.replace("S(1)", "")
    return f"""
  %{kv}_heads = {hbm} parameter({i + 1})
  %copy-start.{i} = ({HEADS}, {hbm}, u32[]{{:S(2)}}) copy-start(%{kv}_heads)
  %copy-done.{i} = {HEADS} copy-done(%copy-start.{i})"""


def module_text(kv: str) -> str:
    """A decode step's layer.  K and V: ``"staged"`` in VMEM behind the
    parent's chain, ``"prefetched"`` into VMEM by asynchronous copies,
    ``"hbm"`` read by the kernel from HBM, ``"computed"`` by a fusion
    straight into VMEM.  q comes from a compute fusion and the output goes
    to a reduce, in HBM with ``"hbm"``, else in VMEM."""
    q = Q.replace("S(1)", "") if kv == "hbm" else Q
    if kv == "staged":
        body = _staged_kv("cache_k", 4) + _staged_kv("cache_v", 5)
        k, v = "%bitcast.42", "%bitcast.52"
    elif kv == "prefetched":
        body = _prefetched_kv("cache_k", 4) + _prefetched_kv("cache_v", 5)
        k, v = "%copy-done.4", "%copy-done.5"
    else:
        shape = HEADS.replace("S(1)", "") if kv == "hbm" else HEADS
        made = ("parameter({})" if kv == "hbm" else
                "fusion(%w, %x), kind=kLoop, calls=%fused_matmul")
        body = f"""
  %slice_k = {shape} {made.format(5)}
  %slice_v = {shape} {made.format(6)}"""
        k, v = "%slice_k", "%slice_v"
    return f"""HloModule jit__decode, is_scheduled=true
{_slice_computation(4)}{_slice_computation(5)}
%fused_matmul (param_0.9: bf16[128,128], param_1.9: bf16[128]) -> bf16[128] {{
  %param_0.9 = bf16[128,128]{{1,0}} parameter(0)
  %param_1.9 = bf16[128]{{0:S(1)}} parameter(1)
  ROOT %dot.9 = bf16[128]{{0:S(1)}} dot(%param_1.9, %param_0.9), lhs_contracting_dims={{0}}, rhs_contracting_dims={{0}}
}}

ENTRY %main.1 (cache_k: bf16[2,1,24,4,32], cache_v: bf16[2,1,24,4,32], w: bf16[128,128], x: bf16[128], layer: s32[]) -> bf16[4,1,32] {{
  %cache_k = {KV} parameter(0)
  %cache_v = {KV} parameter(1)
  %w = bf16[128,128]{{1,0}} parameter(2)
  %x = bf16[128]{{0:S(1)}} parameter(3)
  %layer = s32[]{{:T(128)}} parameter(4)
  %zero = s32[]{{:T(128)}} constant(0)
  %pos = s32[]{{:T(128)}} constant(16)
  %fusion.4 = bf16[128]{{0:S(1)}} fusion(%w, %x), kind=kLoop, calls=%fused_matmul
  %fusion.5 = bf16[128]{{0:S(1)}} fusion(%w, %x), kind=kLoop, calls=%fused_matmul
  %fusion.6 = {q} fusion(%w, %x), kind=kLoop, calls=%fused_matmul
  %vlen = s32[4]{{0:T(128)S(1)}} broadcast(%pos), dimensions={{}}{body}
  %decode_attention.10 = {q} custom-call(%vlen, %fusion.6, {k}, {v}), custom_call_target="tpu_custom_call"
  ROOT %reduce.1 = bf16[4,32]{{1,0}} reduce(%decode_attention.10, %zero), dimensions={{1}}, to_apply=%fused_matmul
}}
"""


CHARGED = ["constant_dynamic-slice_fusion.4", "copy.4",
           "dynamic_update_slice.4", "constant_dynamic-slice_fusion.5",
           "copy.5", "dynamic_update_slice.5"]
OTHERS = ["fusion.4", "fusion.5", "fusion.6", "token.4", "token.5"]
KERNEL_MS, OP_MS = 0.01, 0.004
# a prefetch's issue and wait on the op line, and its transfer, in ms
ISSUE_MS, TRANSFER_MS = 1e-6, 0.012


def _prefetched_call(ops, moves, t0):
    """One call with K and V prefetched: both copies issued, then
    transferred while the fusions of q and the token run, then waited for,
    then the kernel.  The time they cover is TRANSFER_MS + 3 ISSUE_MS +
    KERNEL_MS; their issues and waits alone are 4 ISSUE_MS."""
    def ev(name, a, b):
        return Event(name, t0 + a * MS, t0 + b * MS, program=MODULE)
    d, t = ISSUE_MS, TRANSFER_MS
    ops += [ev("copy-start.4", 0, d), ev("copy-start.5", d, 2 * d)]
    moves += [ev("copy-start.4", 0, t), ev("copy-start.5", d, d + t)]
    ops += [ev(name, 0.002 * (i + 1), 0.002 * (i + 1) + 0.001)
            for i, name in enumerate(OTHERS)]
    ops += [ev("copy-done.4", d + t, 2 * d + t),
            ev("copy-done.5", 2 * d + t, 3 * d + t),
            ev("decode_attention.10", 0.02, 0.02 + KERNEL_MS)]
    return t0 + 0.04 * MS


def _ctx(kv, with_hlo=True):
    """The tiny phi3 bundle's decode calls, two traced batches, each call
    with the ops of its layer around it."""
    staged = kv == "staged"
    b = chipbench_tiny.bundle("phi3")
    c, t = b["config"], b["traffic"]
    arch = harness.arch_module(c)
    batches = [Batch(n=1, bucket=1, t0=0.0, t1=0.5, traced=True),
               Batch(n=1, bucket=1, t0=1.0, t1=1.5, traced=True)]
    ops, moves, spans, t0 = [], [], [], 0.0
    for _ in batches:
        start = t0
        calls = arch.kernel_work(c, t["prompt_len"], t["gen_len"],
                                 1)["decode_attention"][2]
        for _ in range(calls):
            if kv == "prefetched":
                t0 = _prefetched_call(ops, moves, t0)
                continue
            for name in OTHERS + (CHARGED if staged else []):
                ops.append(Event(name, t0, t0 + OP_MS * MS,
                                  program=MODULE))
                t0 += 0.005 * MS
            ops.append(Event("decode_attention.10", t0,
                             t0 + KERNEL_MS * MS, program=MODULE))
            t0 += 0.02 * MS
        spans.append(Event("device_call", start, t0))
    tr = Trace([ops], spans,
               {MODULE: module_text(kv)} if with_hlo else None, [moves])
    ctx = dict(batches=batches, config=c, traffic=t, arch=arch,
               peaks=yardstick.peaks("TPU v5 lite"), info=[], trace=tr)
    return ctx


def _read(ctx):
    return harness.load_module(
        BENCH / "metrics" / "decode_attention_roofline.py").read(ctx)


def _work(ctx):
    t = ctx["traffic"]
    return ctx["arch"].kernel_work(ctx["config"], t["prompt_len"],
                                   t["gen_len"], 1)["decode_attention"]


def test_module_text_is_parsed():
    mod = hlo.Module(module_text("staged"))
    da = mod.instr("decode_attention.10")
    assert da.opcode == "custom-call"
    assert da.operands == ["vlen", "fusion.6", "bitcast.42", "bitcast.52"]
    assert hlo.in_vmem(da.shape)
    assert mod.instr("dynamic_update_slice.4").operands[:2] == [
        "bitcast.41", "token.4"]
    assert hlo.tuple_shapes("(bf16[2]{0:S(1)}, /*index=1*/f32[3]{0})") == [
        "bf16[2]{0:S(1)}", "f32[3]{0}"]


def test_hbm_operands_read_as_under_the_old_rule():
    """Every operand in HBM: all bytes count, nothing is charged, and the
    reading is the old rule's (every byte over the kernel's time alone),
    which a trace without module text still reads by."""
    ops = hlo.staging(hlo.Module(module_text("hbm")), "decode_attention.10",
                      (None, "q", "k", "v"), ("o",))
    assert [(o.role, o.hbm, o.charged) for o in ops] == [
        ("q", True, ()), ("k", True, ()), ("v", True, ()), ("o", True, ())]
    ctx = _ctx("hbm")
    flops, parts, calls = _work(ctx)
    nbytes = sum(parts.values())
    want = 100.0 * 2 * yardstick.bound_seconds(flops, nbytes, ctx["peaks"]) / (
        2 * calls * KERNEL_MS * 1e-3)
    assert _read(ctx) == pytest.approx(want)
    assert _read(_ctx("hbm", with_hlo=False)) == pytest.approx(want)


def test_staged_kv_charges_the_ops_that_move_them():
    """K and V staged by slice fusion -> copy -> dynamic-update-slice ->
    bitcast: those ops' events join the kernel's time, the bytes of K and
    V count; the fusions that compute the token and q are not charged."""
    ops = hlo.staging(hlo.Module(module_text("staged")), "decode_attention.10",
                      (None, "q", "k", "v"), ("o",))
    got = {o.role: o for o in ops}
    assert got["k"].hbm and got["v"].hbm
    assert got["k"].charged == ("dynamic_update_slice.4", "copy.4",
                                "constant_dynamic-slice_fusion.4")
    assert got["v"].end == "constant_dynamic-slice_fusion.5"
    assert not got["q"].hbm and got["q"].end == "fusion.6"
    assert not got["o"].hbm
    ctx = _ctx("staged")
    flops, parts, calls = _work(ctx)
    secs = 2 * calls * (KERNEL_MS + len(CHARGED) * OP_MS) * 1e-3
    want = 100.0 * 2 * yardstick.bound_seconds(
        flops, parts["k"] + parts["v"], ctx["peaks"]) / secs
    assert _read(ctx) == pytest.approx(want)
    info = "\n".join(ctx["info"])
    for name in CHARGED:
        assert name in info
    assert not re.search(r"(\[| )fusion\.4 ", info) and "token.4" not in info
    assert "q at fusion.6" in info


def test_prefetched_kv_charged_by_their_transfers():
    """K and V copied into VMEM by asynchronous copies: the op line holds
    only each copy's issue and wait, a nanosecond each, and the transfer
    runs on a line of its own.  The transfers join the kernel's time, once
    where they overlap other ops; a trace without them leaves the kernel
    unmatched, where the issues alone would read far above its roofline."""
    ops = hlo.staging(hlo.Module(module_text("prefetched")),
                      "decode_attention.10", (None, "q", "k", "v"), ("o",))
    got = {o.role: o for o in ops}
    assert (got["k"].hbm, got["k"].charged, got["k"].transfers) == (
        True, ("copy-done.4", "copy-start.4"), ("copy-start.4",))
    assert got["v"].transfers == ("copy-start.5",)
    assert not got["q"].hbm and got["q"].transfers == ()
    ctx = _ctx("prefetched")
    flops, parts, calls = _work(ctx)
    secs = 2 * calls * (TRANSFER_MS + 3 * ISSUE_MS + KERNEL_MS) * 1e-3
    want = 100.0 * 2 * yardstick.bound_seconds(
        flops, parts["k"] + parts["v"], ctx["peaks"]) / secs
    assert _read(ctx) == pytest.approx(want)
    info = "\n".join(ctx["info"])
    assert "copy-start.4 transfer" in info and "copy-done.5" in info
    ctx = _ctx("prefetched")
    ctx["trace"].async_ops = [[]]
    assert _read(ctx) is None
    assert ("transfer of copy-start.4, copy-start.5 in 'jit__decode(7)' is "
            "not in the trace") in ctx["info"][-1]


def test_vmem_operands_from_compute_leave_the_bound():
    """q, k and v computed by fusions straight into VMEM, as prefill's
    are, and the output going on to compute: no byte crosses HBM, FLOPs
    set the bound, and no op is charged."""
    ops = hlo.staging(hlo.Module(module_text("computed")),
                      "decode_attention.10", (None, "q", "k", "v"), ("o",))
    assert [(o.role, o.hbm, o.charged, o.end) for o in ops] == [
        ("q", False, (), "fusion.6"), ("k", False, (), "slice_k"),
        ("v", False, (), "slice_v"), ("o", False, (), "no move to HBM")]
    ctx = _ctx("computed")
    flops, _, calls = _work(ctx)
    want = 100.0 * 2 * flops / ctx["peaks"].flops_bf16 / (
        2 * calls * KERNEL_MS * 1e-3)
    assert _read(ctx) == pytest.approx(want)
    assert "k at slice_k" in "\n".join(ctx["info"])


def test_batch_missing_a_charged_event_is_left_out():
    ctx = _ctx("staged")
    full = _read(ctx)
    ops = ctx["trace"].device_ops[0]
    span0 = ctx["trace"].host_spans[0]
    ops.remove(next(e for e in ops if e.name == "copy.5"
                    and e.end <= span0.end))
    # the first batch is left out; the second alone reads as both did
    ctx["info"] = []
    assert _read(ctx) == pytest.approx(full)
    assert "1 of 2 traced batches" in ctx["info"][0]
    span1 = ctx["trace"].host_spans[1]
    ops.remove(next(e for e in ops if e.name == "copy.4"
                    and e.start >= span1.start))
    assert _read(ctx) is None


def test_unmatched_module_is_not_reported():
    ctx = _ctx("staged")
    ctx["trace"].hlo = {"jit__prefill(3)": module_text("staged")}
    assert _read(ctx) is None
    assert "not reported" in ctx["info"][-1]
    # a module that lacks the kernel's instruction
    ctx = _ctx("staged")
    ctx["trace"].hlo[MODULE] = ctx["trace"].hlo[MODULE].replace(
        "decode_attention.10", "decode_attention.11")
    assert _read(ctx) is None
    assert "decode_attention.10 is not in module" in ctx["info"][-1]

    # a module whose text cannot be made (no HLO parser in the jaxlib at
    # hand): the roofline alone goes silent, with the reason
    class NoText(dict):
        def __getitem__(self, name):
            raise ImportError("cannot import name 'HloModule'")

    ctx = _ctx("staged")
    ctx["trace"].hlo = NoText(ctx["trace"].hlo)
    assert _read(ctx) is None
    assert "cannot be read: ImportError" in ctx["info"][-1]
