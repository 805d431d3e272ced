"""Unified model zoo: dense / MoE / SSM / hybrid / enc-dec / VLM / audio.

Design notes
------------
* Every architecture is a sequence of homogeneous *layer groups*
  (``cfg.groups()``).  Each group lowers to one ``lax.scan`` over stacked
  parameters, so HLO size is O(groups), not O(layers).
* KV caches for sliding-window groups are ring buffers of size
  ``min(window, max_len)`` — this is what makes ``long_500k`` decode feasible
  for SWA architectures.
* ``frontend`` embeddings (VLM patches / audio frames) are *inputs*: the
  modality encoders are stubs per the assignment carve-out.

Entry points:
  init_params(cfg, key)                        -> params
  forward(cfg, params, tokens, frontend=None)  -> (logits, aux)   # teacher forcing
  init_cache(cfg, batch, max_len)              -> cache
  prefill(cfg, params, tokens, cache, frontend=None) -> (logits, cache)
  decode_step(cfg, params, cache, token, t)    -> (logits, cache)
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kernel_ops
from .config import LayerGroup, ModelConfig
from .layers import (attention_block, causal_window_mask, gqa_attention,
                     gelu_mlp, mamba2_block, moe_block, moe_dropless,
                     rms_norm, swiglu, apply_rope)

Params = Dict[str, Any]
f32 = jnp.float32
# the expert layer's counters per call (``layers.moe_dropless``): rows on
# held experts, held experts with rows, held rows left uncomputed.  A MoE
# model's cache carries them summed over prefill (row 0) and over decode
# steps (row 1) under "moe".
MOE_COUNTS = ("rows", "fetches", "dropped")


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _norm_init(key, shape, dtype):
    return jnp.zeros(shape, dtype)


def _dense_init(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape, f32) * scale).astype(dtype)


def _attn_layer_shapes(cfg: ModelConfig, g: LayerGroup) -> Dict[str, tuple]:
    d, hd = cfg.d_model, cfg.hd
    hq, hkv, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    s: Dict[str, tuple] = {
        "ln1": (d,), "ln2": (d,),
        "wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
        "wo": (hq * hd, d),
    }
    if g.cross_attn:
        s.update({"ln_x": (d,), "xwq": (d, hq * hd), "xwk": (d, hkv * hd),
                  "xwv": (d, hkv * hd), "xwo": (hq * hd, d)})
    if g.moe:
        s.update(_moe_shapes(cfg))
    elif cfg.mlp == "swiglu":
        s.update({"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)})
    else:
        s.update({"w_up": (d, f), "w_down": (f, d)})
    return s


def _moe_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    """The router over every expert, the held experts, the shared one."""
    d, E, f = cfg.d_model, cfg.n_held, cfg.d_ff
    s = {"router": (d, cfg.n_experts), "w_gate": (E, d, f),
         "w_up": (E, d, f), "w_down": (E, f, d)}
    if cfg.d_shared:
        fs = cfg.d_shared
        s.update({"shared_gate": (d, fs), "shared_up": (d, fs),
                  "shared_down": (fs, d)})
    return s


def _mamba_layer_shapes(cfg: ModelConfig, g: LayerGroup) -> Dict[str, tuple]:
    d, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    H = cfg.n_ssm_heads
    dxbc = di + 2 * N
    s = {
        "ln": (d,),
        "in_proj": (d, 2 * di + 2 * N + H),
        "conv_w": (cfg.ssm_conv, dxbc), "conv_b": (dxbc,),
        "dt_bias": (H,), "A_log": (H,), "D": (H,),
        "norm_w": (di,), "out_proj": (di, d),
    }
    if g.moe:
        s.update(_moe_shapes(cfg), ln2=(d,))
    return s


def _init_layer(key, shapes: Dict[str, tuple], count: int, dtype) -> Params:
    out = {}
    keys = jax.random.split(key, len(shapes))
    for k, (name, shp) in zip(keys, sorted(shapes.items())):
        full = (count,) + shp if count > 1 else shp
        if name.startswith(("ln", "norm")):
            out[name] = jnp.zeros(full, dtype)
        elif name == "A_log":
            base = jnp.log(jnp.linspace(1.0, 16.0, shp[-1], dtype=f32))
            out[name] = jnp.broadcast_to(base, full).astype(f32)
        elif name in ("dt_bias", "conv_b", "D"):
            out[name] = jnp.zeros(full, f32) if name != "D" \
                else jnp.ones(full, f32)
        else:
            fan_in = shp[-2] if len(shp) >= 2 else shp[-1]
            out[name] = _dense_init(k, full, dtype,
                                    scale=1.0 / math.sqrt(fan_in))
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dtype = cfg.pdtype()
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": _dense_init(keys[0], (cfg.vocab_padded, cfg.d_model), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(
            keys[1], (cfg.d_model, cfg.vocab_padded), dtype)

    groups = cfg.groups()
    gkeys = jax.random.split(keys[2], len(groups))
    glist: List[Params] = []
    shared_done = False
    for gk, g in zip(gkeys, groups):
        if g.kind == "shared_attn":
            if not shared_done:
                shapes = _attn_layer_shapes(
                    cfg, LayerGroup("attn", 1, moe=False))
                params["shared_attn"] = _init_layer(gk, shapes, 1, dtype)
                shared_done = True
            glist.append({})        # placeholder; uses params["shared_attn"]
        elif g.kind == "mamba":
            glist.append(_init_layer(gk, _mamba_layer_shapes(cfg, g),
                                     g.count, dtype))
        else:
            glist.append(_init_layer(gk, _attn_layer_shapes(cfg, g),
                                     g.count, dtype))
    params["groups"] = glist

    if cfg.n_enc_layers:
        enc_shapes = _attn_layer_shapes(
            cfg, LayerGroup("attn", cfg.n_enc_layers))
        params["encoder"] = _init_layer(keys[3], enc_shapes,
                                        cfg.n_enc_layers, dtype)
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return params


# ---------------------------------------------------------------------------
# Layer-group execution (shared by forward / prefill / decode)
# ---------------------------------------------------------------------------


def _ffn(cfg: ModelConfig, g: LayerGroup, p: Params, x: jnp.ndarray,
         serving: bool = False):
    """(y, aux loss, counts): the layer's FFN.  A MoE layer serves
    (``serving``: prefill and decode) through the dropless expert layer,
    with its counters; training takes the capacity dispatch unless the
    config has no capacity.  Counts are None for a dense FFN."""
    zero = jnp.zeros((), f32)
    if not g.moe:
        if cfg.mlp == "swiglu":
            return swiglu(x, p["w_gate"], p["w_up"], p["w_down"]), zero, None
        return gelu_mlp(x, p["w_up"], p["w_down"]), zero, None
    with jax.named_scope("moe"):
        if _dropless(cfg, serving):
            y, counts = moe_dropless(x, p, k=cfg.experts_per_token,
                                     kernel=cfg.kernels,
                                     layer=p.get("layer"))
            aux = zero
        else:
            y, aux = moe_block(x, p, n_experts=cfg.n_experts,
                               k=cfg.experts_per_token,
                               capacity_factor=cfg.capacity_factor,
                               mlp=cfg.mlp)
            counts = jnp.zeros((len(MOE_COUNTS),), jnp.int32)
        if cfg.d_shared:
            y = y + swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return y, aux, counts


def _res(cfg: ModelConfig, y: jnp.ndarray) -> jnp.ndarray:
    """A sublayer's output as it joins the residual stream."""
    m = cfg.residual_multiplier
    return y if m == 1.0 else y * m


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _dropless(cfg: ModelConfig, serving: bool) -> bool:
    """Whether a MoE layer takes the dropless expert layer."""
    return serving or not cfg.capacity_factor


def _layer_xs(cfg: ModelConfig, g: LayerGroup, gp: Params, serving: bool):
    """What a stacked group's scan runs over, and how a step rebuilds its
    layer's parameters from that.  A dropless MoE group keeps its experts
    out of the scan: a step gets them stacked, with its layer's index, and
    the expert kernel reads only the experts that have rows (a scan's
    slice would copy every held expert of the layer, each step)."""
    if not (g.moe and _is_stacked(gp) and _dropless(cfg, serving)):
        return gp, lambda lp: lp
    experts = {n: gp[n] for n in EXPERT_WEIGHTS}
    rest = {n: v for n, v in gp.items() if n not in EXPERT_WEIGHTS}
    return ((rest, jnp.arange(g.count, dtype=jnp.int32)),
            lambda xs: dict(xs[0], layer=xs[1], **experts))


def _counts0(g: LayerGroup):
    return jnp.zeros((len(MOE_COUNTS),), jnp.int32) if g.moe else None


def _count(total, counts):
    """``total`` plus ``counts``; either is None where there are no
    experts."""
    if counts is None:
        return total
    return counts if total is None else total + counts


def _attn_group_fwd(cfg: ModelConfig, g: LayerGroup, gp: Params,
                    x: jnp.ndarray, positions: jnp.ndarray,
                    mask: Optional[jnp.ndarray],
                    enc_out: Optional[jnp.ndarray],
                    collect_kv: bool, serving: bool = False):
    """Run a stacked attention group via scan.  Returns (x, aux, kv,
    expert counts)."""
    xs, bind = _layer_xs(cfg, g, gp, serving)

    def body(carry, lp):
        h, aux, cnt = carry
        lp = bind(lp)
        a, k, v = attention_block(
            rms_norm(h, lp["ln1"], cfg.norm_eps), lp,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, hd=cfg.hd,
            positions=positions, mask=mask, rope_theta=cfg.rope_theta,
            kernel=cfg.kernels, causal=True, window=g.window,
            rope=cfg.rope, scale=cfg.score_scale)
        h = h + _res(cfg, a)
        if g.cross_attn:
            xa, _, _ = attention_block(
                rms_norm(h, lp["ln_x"], cfg.norm_eps),
                {"wq": lp["xwq"], "wk": lp["xwk"], "wv": lp["xwv"],
                 "wo": lp["xwo"]},
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, hd=cfg.hd,
                positions=positions, mask=None, rope_theta=cfg.rope_theta,
                kv_override=_enc_kv(cfg, lp, enc_out),
                kernel=cfg.kernels, causal=False, window=0)
            h = h + xa
        f, a_loss, c = _ffn(cfg, g, lp, rms_norm(h, lp["ln2"], cfg.norm_eps),
                            serving)
        h = h + _res(cfg, f)
        out = (k, v) if collect_kv else None
        return (h, aux + a_loss, _count(cnt, c)), out

    if cfg.remat:
        body = jax.checkpoint(body)     # layer-boundary remat (training mem)
    carry = (x, jnp.zeros((), f32), _counts0(g))
    if g.count == 1 and not _is_stacked(gp):
        (x, aux, cnt), kv = body(carry, gp)
        kv = jax.tree.map(lambda t: t[None], kv) if kv is not None else None
        return x, aux, kv, cnt
    (x, aux, cnt), kv = jax.lax.scan(body, carry, xs, unroll=cfg.scan_unroll)
    return x, aux, kv, cnt


def _is_stacked(gp: Params) -> bool:
    ln = gp.get("ln1", gp.get("ln"))
    return ln is not None and ln.ndim > 1


def _enc_kv(cfg: ModelConfig, lp: Params, enc_out: jnp.ndarray):
    B, Se, _ = enc_out.shape
    k = (enc_out @ lp["xwk"]).reshape(B, Se, cfg.n_kv_heads, cfg.hd)
    v = (enc_out @ lp["xwv"]).reshape(B, Se, cfg.n_kv_heads, cfg.hd)
    return k, v


def _mamba_group_fwd(cfg: ModelConfig, g: LayerGroup, gp: Params,
                     x: jnp.ndarray, cache: Optional[Dict],
                     collect_state: bool, serving: bool = False):
    """Run a group of Mamba-2 layers (each with its FFN where the group
    has experts).  Returns (x, new cache, expert counts)."""
    layer_xs, bind = _layer_xs(cfg, g, gp, serving)

    def body(carry, inp):
        h, cnt = carry
        if cache is not None:
            lp, lc = inp
        else:
            lp, lc = inp, None
        lp = bind(lp)
        y, new_c = mamba2_block(
            rms_norm(h, lp["ln"], cfg.norm_eps), lp,
            n_heads=cfg.n_ssm_heads, head_dim=cfg.ssm_head_dim,
            d_state=cfg.ssm_state, d_conv=cfg.ssm_conv, chunk=cfg.ssm_chunk,
            cache=lc, kernel=cfg.kernels, norm_eps=cfg.ssm_norm_eps)
        h = h + _res(cfg, y)
        if g.moe:
            f, _, c = _ffn(cfg, g, lp, rms_norm(h, lp["ln2"], cfg.norm_eps),
                           serving)
            h = h + _res(cfg, f)
            cnt = _count(cnt, c)
        return (h, cnt), (new_c if (collect_state or cache is not None)
                          else None)

    if cfg.remat:
        body = jax.checkpoint(body)
    carry = (x, _counts0(g))
    if not _is_stacked(gp):
        lc0 = jax.tree.map(lambda a: a[0], cache) if cache is not None \
            else None
        (x, cnt), nc0 = body(carry, (gp, lc0) if cache is not None else gp)
        if nc0 is not None:
            nc0 = jax.tree.map(lambda a: a[None], nc0)
        return x, nc0, cnt
    xs = (layer_xs, cache) if cache is not None else layer_xs
    (x, cnt), new_cache = jax.lax.scan(body, carry, xs,
                                       unroll=cfg.scan_unroll)
    return x, new_cache, cnt


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def _embed_scale(cfg: ModelConfig) -> float:
    return cfg.embedding_multiplier or math.sqrt(cfg.d_model)


def _embed(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
           frontend: Optional[jnp.ndarray]) -> jnp.ndarray:
    h = params["embed"][tokens].astype(cfg.dtype())
    h = h * _embed_scale(cfg)
    if frontend is not None and cfg.frontend and cfg.arch_type != "encdec":
        h = jnp.concatenate([frontend.astype(cfg.dtype()), h], axis=1)
    return h


def _unembed(cfg: ModelConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = h @ params["embed"].T.astype(h.dtype)
    else:
        logits = h @ params["lm_head"].astype(h.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _encode(cfg: ModelConfig, params: Params,
            frontend: jnp.ndarray) -> jnp.ndarray:
    """Whisper-style bidirectional encoder over (stub) frame embeddings."""
    h = frontend.astype(cfg.dtype())
    pos = jnp.arange(h.shape[1])[None, :]

    def body(carry, lp):
        hh = carry
        a, _, _ = attention_block(
            rms_norm(hh, lp["ln1"], cfg.norm_eps), lp,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, hd=cfg.hd,
            positions=pos, mask=None, rope_theta=cfg.rope_theta,
            kernel=cfg.kernels, causal=False, window=0)
        hh = hh + a
        f, _, _ = _ffn(cfg, LayerGroup("attn", 1), lp,
                       rms_norm(hh, lp["ln2"], cfg.norm_eps))
        return hh + f, None

    h, _ = jax.lax.scan(body, h, params["encoder"], unroll=cfg.scan_unroll)
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# forward (teacher-forcing; training and smoke tests)
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            frontend: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S_text); frontend: (B, F, d) when cfg.frontend is set.
    Returns (logits (B, S_total, V), aux_loss scalar)."""
    enc_out = None
    if cfg.arch_type == "encdec":
        assert frontend is not None, "encoder-decoder needs frontend frames"
        enc_out = _encode(cfg, params, frontend)
        frontend = None
    h = _embed(cfg, params, tokens, frontend)
    B, S, _ = h.shape
    positions = jnp.arange(S)[None, :]
    aux = jnp.zeros((), f32)
    shared_idx = 0
    for g, gp in zip(cfg.groups(), params["groups"]):
        if g.kind == "mamba":
            h, _, _ = _mamba_group_fwd(cfg, g, gp, h, None,
                                       collect_state=False)
        else:
            lp = params["shared_attn"] if g.kind == "shared_attn" else gp
            mask = causal_window_mask(positions[0], positions[0],
                                      g.window)[None, None, None]
            h, a, _, _ = _attn_group_fwd(cfg, g, lp, h, positions, mask,
                                         enc_out, collect_kv=False)
            aux = aux + a
            if g.kind == "shared_attn":
                shared_idx += 1
    return _unembed(cfg, params, h), aux


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Shapes only (jax.eval_shape-compatible via init_cache)."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


def _attn_cache_len(g: LayerGroup, max_len: int) -> int:
    return min(g.window, max_len) if g.window > 0 else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: Optional[int] = None) -> Dict[str, Any]:
    dt = cfg.dtype()
    groups = cfg.groups()
    entries: List[Dict[str, jnp.ndarray]] = []
    for g in groups:
        if g.kind == "mamba":
            entries.append({
                "conv": jnp.zeros((g.count, batch, cfg.ssm_conv - 1,
                                   cfg.d_inner + 2 * cfg.ssm_state), dt),
                "state": jnp.zeros((g.count, batch, cfg.n_ssm_heads,
                                    cfg.ssm_head_dim, cfg.ssm_state), f32),
            })
        else:
            W = _attn_cache_len(g, max_len)
            e = {"k": jnp.zeros((g.count, batch, W, cfg.n_kv_heads, cfg.hd), dt),
                 "v": jnp.zeros((g.count, batch, W, cfg.n_kv_heads, cfg.hd), dt)}
            if g.cross_attn:
                L = enc_len or cfg.n_enc_tokens
                e["xk"] = jnp.zeros((g.count, batch, L, cfg.n_kv_heads,
                                     cfg.hd), dt)
                e["xv"] = jnp.zeros((g.count, batch, L, cfg.n_kv_heads,
                                     cfg.hd), dt)
            entries.append(e)
    if any(g.moe for g in groups):
        return {"layers": entries,
                "moe": jnp.zeros((2, len(MOE_COUNTS)), jnp.int32)}
    return {"layers": entries}


# ---------------------------------------------------------------------------
# prefill: run the prompt, fill caches, return last-position logits
# ---------------------------------------------------------------------------


def prefill(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            cache: Dict[str, Any],
            frontend: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    enc_out = None
    if cfg.arch_type == "encdec":
        enc_out = _encode(cfg, params, frontend)
        frontend = None
    h = _embed(cfg, params, tokens, frontend)
    B, S, _ = h.shape
    positions = jnp.arange(S)[None, :]
    new_layers = []
    counts = None
    for g, gp, ce in zip(cfg.groups(), params["groups"], cache["layers"]):
        if g.kind == "mamba":
            stacked_zero = jax.tree.map(
                lambda t: jnp.zeros_like(t), ce)
            h, nc, cnt = _mamba_group_fwd(cfg, g, gp, h, stacked_zero,
                                          collect_state=True, serving=True)
            counts = _count(counts, cnt)
            new_layers.append(nc)
        else:
            lp = params["shared_attn"] if g.kind == "shared_attn" else gp
            mask = causal_window_mask(positions[0], positions[0],
                                      g.window)[None, None, None]
            h, _, kv, cnt = _attn_group_fwd(cfg, g, lp, h, positions, mask,
                                            enc_out, collect_kv=True,
                                            serving=True)
            counts = _count(counts, cnt)
            k, v = kv
            W = ce["k"].shape[2]
            e = {"k": _ring_fill(ce["k"], k, S, W),
                 "v": _ring_fill(ce["v"], v, S, W)}
            if g.cross_attn:
                def xkv(lp_layer):
                    return _enc_kv(cfg, lp_layer, enc_out)
                if _is_stacked(gp):
                    xk, xv = jax.vmap(
                        lambda l: _enc_kv(cfg, l, enc_out))(gp)
                else:
                    xk1, xv1 = _enc_kv(cfg, lp, enc_out)
                    xk, xv = xk1[None], xv1[None]
                e["xk"], e["xv"] = xk.astype(ce["xk"].dtype), \
                    xv.astype(ce["xv"].dtype)
            new_layers.append(e)
    logits = _unembed(cfg, params, h[:, -1:, :])
    return logits, _with_counts(cache, new_layers, counts, 0)


def _with_counts(cache: Dict[str, Any], layers: List, counts: jnp.ndarray,
                 row: int) -> Dict[str, Any]:
    """The new cache: ``layers``, and the expert counters with ``counts``
    added to ``row`` (0 prefill, 1 decode) where the model has them."""
    if "moe" not in cache:
        return {"layers": layers}
    return {"layers": layers, "moe": cache["moe"].at[row].add(counts)}


def _ring_fill(dst: jnp.ndarray, kv: jnp.ndarray, S: int, W: int
               ) -> jnp.ndarray:
    """Write prefill K/V (L,B,S,Hkv,hd) into a ring cache of width W."""
    if S >= W:
        tail = kv[:, :, S - W:, :, :]
        slots = (jnp.arange(S - W, S) % W)
        return dst.at[:, :, slots].set(tail.astype(dst.dtype))
    return dst.at[:, :, :S].set(kv.astype(dst.dtype))


# ---------------------------------------------------------------------------
# decode_step: one token, cache of max_len (THE `serve_step` the dry-run lowers)
# ---------------------------------------------------------------------------


def decode_step(cfg: ModelConfig, params: Params, cache: Dict[str, Any],
                token: jnp.ndarray, t: jnp.ndarray,
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """token: (B,1) int32; t: scalar int32 absolute position of this token.
    Returns (logits (B,1,V), updated cache)."""
    positions = jnp.full((1, 1), t, jnp.int32)
    return _decode_impl(cfg, params, cache, token, positions, t)


def decode_step_ragged(cfg: ModelConfig, params: Params,
                       cache: Dict[str, Any], token: jnp.ndarray,
                       t: jnp.ndarray,
                       ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """token: (B,1) int32; t: (B,) int32 — PER-ROW absolute positions.

    The continuous-batching decode step: every batch row advances its own
    sequence (per-row RoPE angle, per-row cache slot, per-row attention
    mask / ``valid_len``), so in-flight requests at different depths share
    one fused device step.  With a uniform ``t`` this computes exactly
    :func:`decode_step`."""
    positions = t[:, None].astype(jnp.int32)         # (B,1)
    return _decode_impl(cfg, params, cache, token, positions, t)


def _decode_impl(cfg: ModelConfig, params: Params, cache: Dict[str, Any],
                 token: jnp.ndarray, positions: jnp.ndarray, t: jnp.ndarray,
                 ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    h = params["embed"][token].astype(cfg.dtype()) * _embed_scale(cfg)
    new_layers = []
    counts = None
    for g, gp, ce in zip(cfg.groups(), params["groups"], cache["layers"]):
        if g.kind == "mamba":
            h, nc, cnt = _mamba_group_fwd(cfg, g, gp, h, ce,
                                          collect_state=False, serving=True)
        else:
            lp = params["shared_attn"] if g.kind == "shared_attn" else gp
            h, nc, cnt = _attn_group_decode(cfg, g, lp, ce, h, positions, t)
        counts = _count(counts, cnt)
        new_layers.append(nc)
    logits = _unembed(cfg, params, h)
    return logits, _with_counts(cache, new_layers, counts, 1)


def _attn_group_decode(cfg: ModelConfig, g: LayerGroup, gp: Params,
                       ce: Dict[str, jnp.ndarray], x: jnp.ndarray,
                       positions: jnp.ndarray, t: jnp.ndarray):
    """One-token attention-group step.  ``t`` is a scalar (uniform batch,
    the classic ``decode_step``) or (B,) (ragged continuous-batching rows);
    ``positions`` is the matching (1,1) / (B,1) RoPE position array."""
    W = ce["k"].shape[2]
    ragged = jnp.ndim(t) == 1
    slot = jnp.mod(t, W)                    # () or (B,)
    slots = jnp.arange(W)
    tb = t[:, None] if ragged else t        # (B,1) or scalar
    if g.window > 0:
        # absolute position stored in slot s: t - ((t - s) mod W)
        k_pos = tb - jnp.mod(tb - slots, W)
    else:
        k_pos = slots if not ragged else \
            jnp.broadcast_to(slots, (t.shape[0], W))
    valid = (k_pos >= 0) & (k_pos <= tb)    # (W,) or (B,W)
    mask = valid[None, None, None, None, :] if not ragged \
        else valid[:, None, None, None, :]  # (1,1,1,1,W) / (B,1,1,1,W)
    # full-attention caches (W == max_len) hold slots [0, t] as a prefix, so
    # decode routes to the flash-decoding kernel with valid_len = t+1;
    # sliding-window rings are not a prefix layout and stay on the masked
    # jnp reference (see docs/KERNELS.md)
    use_dec_kernel = cfg.kernels != "xla" and g.window <= 0
    layer_xs, bind = _layer_xs(cfg, g, gp, serving=True)

    def body(carry, inp):
        h, cnt = carry
        lp = gp if not _is_stacked(gp) else None
        if lp is None:
            lp, lc = inp
            lp = bind(lp)
        else:
            lc = inp
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        B = h.shape[0]
        q = (hn @ lp["wq"]).reshape(B, 1, cfg.n_heads, cfg.hd)
        k1 = (hn @ lp["wk"]).reshape(B, 1, cfg.n_kv_heads, cfg.hd)
        v1 = (hn @ lp["wv"]).reshape(B, 1, cfg.n_kv_heads, cfg.hd)
        if cfg.rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k1 = apply_rope(k1, positions, cfg.rope_theta)
        if ragged:
            rows = jnp.arange(B)
            nk = lc["k"].at[rows, slot].set(k1[:, 0].astype(lc["k"].dtype))
            nv = lc["v"].at[rows, slot].set(v1[:, 0].astype(lc["v"].dtype))
        else:
            nk = jax.lax.dynamic_update_slice_in_dim(
                lc["k"], k1.astype(lc["k"].dtype), slot, axis=1)
            nv = jax.lax.dynamic_update_slice_in_dim(
                lc["v"], v1.astype(lc["v"].dtype), slot, axis=1)
        if use_dec_kernel:
            vlen = jnp.broadcast_to(t + 1, (B,)).astype(jnp.int32)
            a1 = kernel_ops.decode_attention(q[:, 0], nk, nv, vlen,
                                             scale=cfg.score_scale,
                                             backend=cfg.kernels)
            a = a1[:, None]
        else:
            a = gqa_attention(q, nk, nv, mask, cfg.score_scale)
        h = h + _res(cfg, a.reshape(B, 1, cfg.n_heads * cfg.hd) @ lp["wo"])
        if g.cross_attn:
            hx = rms_norm(h, lp["ln_x"], cfg.norm_eps)
            qx = (hx @ lp["xwq"]).reshape(B, 1, cfg.n_heads, cfg.hd)
            if cfg.kernels != "xla":
                ax = kernel_ops.attention(qx, lc["xk"], lc["xv"],
                                          causal=False, window=0,
                                          backend=cfg.kernels)
            else:
                ax = gqa_attention(qx, lc["xk"], lc["xv"], None)
            h = h + ax.reshape(B, 1, cfg.n_heads * cfg.hd) @ lp["xwo"]
        f, _, c = _ffn(cfg, g, lp, rms_norm(h, lp["ln2"], cfg.norm_eps),
                       serving=True)
        h = h + _res(cfg, f)
        nc = dict(lc)
        nc["k"], nc["v"] = nk, nv
        return (h, _count(cnt, c)), nc

    carry = (x, _counts0(g))
    if not _is_stacked(gp):
        lc0 = jax.tree.map(lambda a: a[0], ce)
        (x, cnt), nc0 = body(carry, lc0)
        return x, jax.tree.map(lambda a: a[None], nc0), cnt
    (x, cnt), nc = jax.lax.scan(body, carry, (layer_xs, ce),
                                unroll=cfg.scan_unroll)
    return x, nc, cnt


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def lm_loss(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            frontend: Optional[jnp.ndarray] = None,
            aux_weight: float = 0.01) -> jnp.ndarray:
    """Next-token cross-entropy (+ MoE load-balance aux).

    Sharding-aware formulation: with vocab-sharded logits,
    ``take_along_axis`` would force GSPMD to all-gather the full (B,S,V)
    logit tensor.  Writing the picked-logit term as a one-hot contraction
    keeps the vocab axis local (partial dot + psum of a (B,S) scalar field)
    — identical math, ~V/shards less collective traffic (EXPERIMENTS.md
    §Perf, bonus iteration)."""
    logits, aux = forward(cfg, params, tokens, frontend)
    # predictions for text positions only (frontend tokens are prompts)
    n_text = tokens.shape[1]
    logits = logits[:, -n_text:, :].astype(f32)
    pred = logits[:, :-1]                        # (B, S-1, V)
    tgt = tokens[:, 1:]                          # (B, S-1)
    lse = jax.nn.logsumexp(pred, axis=-1)        # (B, S-1)
    onehot = jax.nn.one_hot(tgt, pred.shape[-1], dtype=f32)
    picked = jnp.einsum("bsv,bsv->bs", pred, onehot)
    nll = lse - picked
    return nll.mean() + aux_weight * aux
