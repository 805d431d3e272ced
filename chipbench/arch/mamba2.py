"""Mamba-2 (arXiv:2405.21060): the benchmark's side of the architecture.

Three parts, none of which imports the program:

* ``weight``: the published value of each weight leaf, drawn from the seed
  with ``mamba_ssm``'s initialisation; ``to_program`` maps it into the
  program's layout;
* ``program_config``: the program's model settings for a configuration file;
* ``logits``: a plain float32 forward pass written from the paper's
  equations and run layer by layer: the embedding unscaled; pre-norm
  residual blocks; in each, one input projection to (z, x, B, C, dt), a
  causal depthwise convolution and SiLU over (x, B, C),
  dt = softplus(dt + dt_bias), A = -exp(A_log), the SSD in its quadratic
  (dual) form y = (L o C B^T)(dt x) with L[t, s] = exp(sum_{s<r<=t} dt_r A)
  per head, the skip D x, the gated RMSNorm of y * silu(z) (eps
  ``gated_norm_epsilon``), and the output projection; a final RMSNorm and
  the tied embedding as the output head.  RMSNorm applies a gain ``g``.
  Everything is float32, the residual stream too (``residual_in_fp32``).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import common

f32 = jnp.float32
GAINS = ("ln", "norm_w", "final_norm")


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
        name=c["name"], arch_type="ssm", n_layers=c["n_layer"],
        d_model=c["d_model"], n_heads=0, n_kv_heads=0, d_ff=0,
        vocab_size=c["vocab_size"], ssm_state=c["d_state"],
        ssm_head_dim=c["headdim"], ssm_expand=c["expand"],
        ssm_chunk=c["chunk_size"], ssm_conv=c["d_conv"],
        norm_eps=float(c["norm_epsilon"]),
        tie_embeddings=bool(c["tie_embeddings"]))


def weight(c: Dict[str, Any], name: str, key, shape):
    """Published float32 value of one leaf, by ``mamba_ssm``'s
    initialisation: embedding N(0, ``initializer_range``); linear and
    convolution weights and biases uniform in +-1/sqrt(fan_in), the output
    projection then divided by sqrt(n_layer); dt drawn log-uniform in
    [dt_min, dt_max] and stored through the inverse softplus; A uniform in
    [1, 16]; D one.  RMSNorm gains ``g`` are drawn around 1 so that the
    norm path is exercised; rows of the padded vocabulary are zero."""
    u = lambda bound: jax.random.uniform(key, shape, f32, -bound, bound)
    if name in GAINS:
        w = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif name == "embed":
        w = common.zero_rows(
            c["initializer_range"] * jax.random.normal(key, shape, f32),
            c["vocab_size"], axis=0)
    elif name == "in_proj":
        w = u(1.0 / math.sqrt(shape[-2]))
    elif name == "out_proj":
        w = u(1.0 / math.sqrt(shape[-2])) / math.sqrt(c["n_layer"])
    elif name in ("conv_w", "conv_b"):
        w = u(1.0 / math.sqrt(c["d_conv"]))
    elif name == "dt_bias":
        lo, hi = math.log(c["dt_min"]), math.log(c["dt_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, f32, lo, hi)),
                         c["dt_init_floor"])
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif name == "D":
        w = jnp.ones(shape, f32)
    else:
        raise KeyError(f"mamba2 has no weight named {name!r}")
    return w


def to_program(c: Dict[str, Any], name: str, w):
    """The published leaf in the program's layout, as a checkpoint loader
    would convert it.  The program applies a gain stored as ``g - 1`` and
    multiplies the looked-up embedding by sqrt(d_model); with the head tied
    to the embedding, the final norm's gain carries that factor back."""
    root = math.sqrt(c["d_model"])
    if name == "embed":
        return w / root
    if name == "final_norm" and c["tie_embeddings"]:
        return w * root - 1.0
    if name in GAINS:
        return w - 1.0
    return w


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _block(h, lw, *, c, quant):
    mm = common.matmul(quant)
    B, T, _ = h.shape
    di = c["expand"] * c["d_model"]
    N, P, K = c["d_state"], c["headdim"], c["d_conv"]
    H = di // P
    x = _rms(h, lw["ln"], float(c["norm_epsilon"]))
    zxbcdt = mm(x, lw["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N],
                  zxbcdt[..., 2 * di + 2 * N:])
    # causal depthwise convolution: out[t] = sum_k w[k] * in[t - K + 1 + k]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(pad[:, k:k + T] * lw["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(xbc + lw["conv_b"])
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + lw["dt_bias"])                  # (B, T, H)
    A = -jnp.exp(lw["A_log"])                                 # (H,)
    cum = jnp.cumsum(dt * A, axis=1)                          # (B, T, H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]             # (B, t, s, H)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    L = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    CB = jnp.einsum("btn,bsn->bts", Cm, Bm)
    xh = xs.reshape(B, T, H, P)
    y = jnp.einsum("btsh,bts,bsh,bshp->bthp", L, CB, dt, xh)
    y = y + xh * lw["D"][:, None]
    y = y.reshape(B, T, di) * jax.nn.silu(z)
    y = _rms(y, lw["norm_w"], float(c["gated_norm_epsilon"]))
    return h + mm(y, lw["out_proj"])


def logits(c: Dict[str, Any], weights, tokens, at, quant: str = ""):
    """Float32 logits (B, K, V) of ``tokens`` (B, T) at positions ``at``
    (B, K).  ``quant`` names a lower precision for every weight matmul's
    inputs (the control); empty computes in float32 at "highest"."""
    block = jax.jit(lambda h, lw: _block(h, lw, c=c, quant=quant))
    eps = float(c["norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        h = jax.jit(lambda e, t: e[t].astype(f32))(weights["embed"], tokens)
        g = weights["groups"][0]
        for i in range(c["n_layer"]):
            h = block(h, common.layer_f32(g, i))
        head = jax.jit(lambda h, n, e, at: common.matmul(quant)(
            _rms(jnp.take_along_axis(h, at[..., None], 1),
                 n.astype(f32), eps), e.astype(f32).T))
        return head(h, weights["final_norm"], weights["embed"], at)


def model_flops(c: Dict[str, Any], prompt: int, gen: int) -> float:
    """FLOPs one request needs, counted per position in the recurrent form:
    the projections, the convolution, the state update and read-out; one
    row of logits after the prompt and after each of ``gen`` decode
    steps."""
    d, N, P, K = c["d_model"], c["d_state"], c["headdim"], c["d_conv"]
    di = c["expand"] * d
    H = di // P
    per_tok = 2 * (d * (2 * di + 2 * N + H) + di * d + K * (di + 2 * N)
                   + 2 * H * P * N)
    lm = 2 * d * c["vocab_size"]
    return float((prompt + gen) * c["n_layer"] * per_tok + (1 + gen) * lm)


def kernel_work(c: Dict[str, Any], prompt: int, gen: int, bucket: int
                ) -> Dict[str, tuple]:
    """Per batch of ``bucket`` rows: kernel -> (FLOPs, bytes, calls)."""
    from yardstick import ssd_cost
    di = c["expand"] * c["d_model"]
    H = di // c["headdim"]
    f, b = ssd_cost(bucket, prompt, H, c["headdim"], c["d_state"])
    L = c["n_layer"]
    return {"ssd_scan": (L * f, L * b, L)}
