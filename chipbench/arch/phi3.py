"""Phi-3-mini (arXiv:2404.14219): the benchmark's side of the architecture.

Three parts, none of which imports the program:

* ``program_config``: the program's model settings for a configuration file;
* ``weight``: the published value of each weight leaf, drawn from the
  seed; ``to_program`` maps it into the program's layout;
* ``logits``: a plain float32 forward pass written from the paper's layer
  equations (pre-norm decoder, RMSNorm with gain ``g``, rotary positions on
  the two halves of each head, causal softmax attention, SwiGLU, the
  embedding unscaled), run layer by layer.

One departure from the paper: Phi-3-mini's 2047-position sliding window is
not applied.  Every position the cells serve lies inside it, so full causal
attention is the same.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import common

f32 = jnp.float32


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
        name=c["name"], arch_type="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        mlp="swiglu", tie_embeddings=bool(c["tie_word_embeddings"]))


GAINS = ("ln1", "ln2", "final_norm")


def weight(c: Dict[str, Any], name: str, key, shape):
    """Published float32 value of one leaf: matrices N(0,
    ``initializer_range``) as the published config states; RMSNorm gains
    ``g`` drawn around 1 so that the norm path is exercised; rows of the
    padded vocabulary zero."""
    if name in GAINS:
        return 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    w = float(c["initializer_range"]) * jax.random.normal(key, shape, f32)
    if name == "embed":
        w = common.zero_rows(w, c["vocab_size"], axis=0)
    elif name == "lm_head":
        w = common.zero_rows(w, c["vocab_size"], axis=1)
    return w


def to_program(c: Dict[str, Any], name: str, w):
    """The published leaf in the program's layout, as a checkpoint loader
    would convert it: the program applies a gain stored as ``g - 1``, and
    multiplies the looked-up embedding by sqrt(hidden_size)."""
    if name in GAINS:
        return w - 1.0
    if name == "embed":
        return w / math.sqrt(c["hidden_size"])
    return w


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x: (B, T, H, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd)
    ang = pos[:, :, None].astype(f32) * inv              # (B, T, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(h, lw, *, n_heads, n_kv, eps, theta, quant):
    mm = common.matmul(quant)
    B, T, d = h.shape
    hd = d // n_heads
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _rms(h, lw["ln1"], eps)
    q = _rope(mm(x, lw["wq"]).reshape(B, T, n_heads, hd), pos, theta)
    k = _rope(mm(x, lw["wk"]).reshape(B, T, n_kv, hd), pos, theta)
    v = mm(x, lw["wv"]).reshape(B, T, n_kv, hd)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, n_heads * hd)
    h = h + mm(a, lw["wo"])
    x = _rms(h, lw["ln2"], eps)
    g = mm(x, lw["w_gate"])
    return h + mm(jax.nn.silu(g) * mm(x, lw["w_up"]), lw["w_down"])


def logits(c: Dict[str, Any], weights, tokens, at, quant: str = ""):
    """Float32 logits (B, K, V) of ``tokens`` (B, T) at positions ``at``
    (B, K).  ``quant`` names a lower precision for every matmul's inputs
    (the control); empty computes in float32 at "highest" precision."""
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    layer = jax.jit(lambda h, lw: _layer(h, lw, n_heads=nh, n_kv=nkv,
                                         eps=eps, theta=theta, quant=quant))
    with jax.default_matmul_precision("highest"):
        h = jax.jit(lambda e, t: e[t].astype(f32))(weights["embed"], tokens)
        g = weights["groups"][0]
        for i in range(c["num_hidden_layers"]):
            h = layer(h, common.layer_f32(g, i))
        head = jax.jit(lambda h, n, w, at: common.matmul(quant)(
            _rms(jnp.take_along_axis(h, at[..., None], 1), n.astype(f32),
                 eps), w.astype(f32)))
        return head(h, weights["final_norm"], weights["lm_head"], at)


def _dims(c):
    d, nh = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, nh, c["num_key_value_heads"],
            d // nh, c["intermediate_size"], c["vocab_size"])


def model_flops(c: Dict[str, Any], prompt: int, gen: int) -> float:
    """FLOPs one request needs: the prompt through every layer, causal
    attention over it, one row of logits; then ``gen`` decode steps, each
    through every layer, attending to the positions before it, with one
    row of logits."""
    L, d, nh, nkv, hd, ff, V = _dims(c)
    per_tok = 2 * (d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff)
    flops = prompt * L * per_tok + L * 2.0 * prompt * prompt * nh * hd
    flops += 2 * d * V
    for i in range(gen):
        flops += L * per_tok + L * 4.0 * (prompt + i + 1) * nh * hd + 2 * d * V
    return float(flops)


def kernel_work(c: Dict[str, Any], prompt: int, gen: int, bucket: int
                ) -> Dict[str, tuple]:
    """Per batch of ``bucket`` rows: kernel -> (FLOPs, bytes per operand,
    calls)."""
    from yardstick import (attention_cost, attention_parts,
                           decode_attention_cost, decode_attention_parts,
                           scaled, summed)
    L, d, nh, nkv, hd, ff, V = _dims(c)
    fa = attention_cost(bucket, prompt, nh, nkv, hd, causal=True)[0]
    dec = sum(decode_attention_cost(bucket, prompt + i + 1, nh, nkv, hd)[0]
              for i in range(gen))
    dec_parts = summed([decode_attention_parts(bucket, prompt + i + 1, nh,
                                               nkv, hd) for i in range(gen)])
    return {"flash_attention": (
                L * fa, scaled(attention_parts(bucket, prompt, nh, nkv, hd),
                               L), L),
            "decode_attention": (L * dec, scaled(dec_parts, L), L * gen)}
