"""IBM Granite 4.0-H (``granitemoehybrid``): the benchmark's side of the
architecture.

Three parts, none of which imports the program:

* ``program_config``: the program's model settings for a configuration
  file;
* ``weight``: the published value of each weight leaf, drawn from the seed
  with transformers' ``GraniteMoeHybridPreTrainedModel._init_weights``;
  ``to_program`` maps it into the program's layout;
* ``logits``: a plain float32 forward pass that follows
  ``transformers/models/granitemoehybrid/modeling_granitemoehybrid.py``
  (4.57) line by line, layer by layer: the embedding times
  ``embedding_multiplier``; in each decoder layer an RMSNorm, the layer's
  mixer, ``residual + out * residual_multiplier``, an RMSNorm, the routed
  experts plus the shared MLP, and the residual again; a final RMSNorm,
  the tied embedding as the head, the logits divided by
  ``logits_scaling``.  The Mamba mixer is ``torch_forward``'s prefill path:
  one input projection to (gate, x B C, dt), a causal depthwise
  convolution with bias and SiLU, dt = softplus(dt + dt_bias),
  A = -exp(A_log), the chunked SSD (chunks of ``mamba_chunk_size``, the
  in-chunk decay by ``segment_sum``, the chunk states and their
  recurrence), the skip D x, the gated RMSNorm of y * silu(gate) over all
  channels (``norm_before_gate=False``), and the output projection.
  Attention has no position encoding (``position_embedding_type:
  "nope"``) and scales its scores by ``attention_multiplier``.  The router
  takes the top ``num_experts_per_tok`` of its logits and a softmax over
  them; each expert is ``silu(x W_in[:f]) * (x W_in[f:])`` then
  ``W_out``; the shared MLP is the same with ``shared_intermediate_size``.

Departures, each the same in the program: only the experts this chip holds
(the first ``num_local_experts`` of the router's published count) add
their part, as the configuration's deployment states; the routed sum is
written as every held expert over every token weighted by its gate (zero
where the token did not pick it), which is the modeling code's sort,
split and ``index_add`` summed in another order.  Everything is float32 at
"highest", the residual stream too.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from . import common

f32 = jnp.float32
GAINS = ("ln", "ln1", "ln2", "norm_w", "final_norm")


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    for key, want in (("mamba_n_groups", 1), ("mamba_proj_bias", False),
                      ("mamba_conv_bias", True), ("attention_bias", False),
                      ("hidden_act", "silu")):
        if c[key] != want:
            raise ValueError(f"{c['name']}: the program serves {key}="
                             f"{want!r}, not {c[key]!r}")
    d, nh = c["hidden_size"], c["num_attention_heads"]
    return dict(
        name=c["name"], arch_type="hybrid",
        n_layers=c["num_hidden_layers"], d_model=d, n_heads=nh,
        n_kv_heads=c["num_key_value_heads"], head_dim=d // nh,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        n_experts=c["published"]["num_local_experts"],
        experts_held=c["num_local_experts"],
        experts_per_token=c["num_experts_per_tok"], capacity_factor=0.0,
        d_shared=c["shared_intermediate_size"],
        ssm_state=c["mamba_d_state"], ssm_heads=c["mamba_n_heads"],
        ssm_head_dim=c["mamba_d_head"], ssm_expand=c["mamba_expand"],
        ssm_chunk=c["mamba_chunk_size"], ssm_conv=c["mamba_d_conv"],
        ssm_norm_eps=float(c["rms_norm_eps"]),
        layer_types=tuple(c["layer_types"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        rope=c["position_embedding_type"] != "nope",
        norm_eps=float(c["rms_norm_eps"]), mlp="swiglu",
        tie_embeddings=bool(c["tie_word_embeddings"]))


def weight(c: Dict[str, Any], name: str, key, shape):
    """Published float32 value of one leaf, by ``_init_weights``: every
    linear, convolution and expert matrix N(0, ``initializer_range``),
    biases zero, ``dt_bias`` one, ``A_log`` the log of 1..heads, ``D``
    one.  RMSNorm gains (ones there) are drawn around 1 so that the norm
    path is exercised.  One departure: the embedding is drawn N(0,
    ``initializer_range / embedding_multiplier``), so that the stream the
    first layer sees starts at ``initializer_range``.  Drawn at
    ``initializer_range`` itself, 12 times the token's own embedding
    outweighs everything the layers add, and the tied head returns the
    input token at every position (its logit some 8 standard deviations
    above the rest at hidden size 4096): no served token could then show
    a fault."""
    if name in GAINS:
        return 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    if name in ("conv_b",):
        return jnp.zeros(shape, f32)
    if name in ("dt_bias", "D"):
        return jnp.ones(shape, f32)
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=f32)), shape)
    std = float(c["initializer_range"])
    if name == "embed":
        std /= float(c["embedding_multiplier"])
    w = std * jax.random.normal(key, shape, f32)
    if name == "embed":
        w = common.zero_rows(w, c["vocab_size"], axis=0)
    return w


def to_program(c: Dict[str, Any], name: str, w):
    """The published leaf in the program's layout, as a checkpoint loader
    would convert it: the program applies a gain stored as ``g - 1``.  The
    embedding is multiplied by ``embedding_multiplier`` on lookup in both,
    and the head is the embedding itself."""
    return w - 1.0 if name in GAINS else w


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _segment_sum(x):
    """``segment_sum``: out[..., i, j] = sum of x[..., j+1..i] for j <= i,
    -inf above the diagonal (cumulated, not differenced, for stability)."""
    Q = x.shape[-1]
    x = jnp.broadcast_to(x[..., None], x.shape + (Q,))
    x = jnp.where(jnp.tril(jnp.ones((Q, Q), bool), -1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((Q, Q), bool), 0), s, -jnp.inf)


def _ssd(x, dt, A, Bm, Cm, D, chunk):
    """``torch_forward``'s SSD without a cache: x (b, T, H, P), dt (b, T, H)
    after softplus, A (H,), B and C (b, T, N) (one group), D (H,) ->
    y (b, T, H, P)."""
    b, T, H, P = x.shape
    pad = (-T) % chunk
    padt = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
    D_residual = D[:, None] * padt(x)
    x = x * dt[..., None]
    A = A * dt                                                # (b, T, H)
    x, A, Bm, Cm = (padt(t) for t in (x, A, Bm, Cm))
    n = (T + pad) // chunk
    x = x.reshape(b, n, chunk, H, P)
    A = A.reshape(b, n, chunk, H).transpose(0, 3, 1, 2)       # (b, H, c, l)
    Bm = Bm.reshape(b, n, chunk, -1)
    Cm = Cm.reshape(b, n, chunk, -1)
    A_cumsum = jnp.cumsum(A, axis=-1)
    # 1. the output of each chunk on itself (diagonal blocks)
    L = jnp.exp(_segment_sum(A))                              # (b,H,c,l,s)
    G = jnp.einsum("bcln,bcsn->bcls", Cm, Bm)
    M = G[..., None] * L.transpose(0, 2, 3, 4, 1)             # (b,c,l,s,H)
    Y_diag = jnp.einsum("bclsh,bcshp->bclhp", M, x)
    # 2. each chunk's final state
    decay_states = jnp.exp(A_cumsum[..., -1:] - A_cumsum)     # (b, H, c, l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bm, decay_states, x)
    # 3. the recurrence over chunks, from a zero state
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segment_sum(
        jnp.pad(A_cumsum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)
    states = new_states[:, :-1]
    # 4. each chunk's state read out at its positions
    state_decay_out = jnp.exp(A_cumsum)
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cm, states, state_decay_out)
    y = (Y_diag + Y_off).reshape(b, -1, H, P) + D_residual
    return y[:, :T]


def _mamba(x, lw, *, c, mm):
    b, T, _ = x.shape
    di = int(c["mamba_expand"] * c["hidden_size"])
    N, P, K = c["mamba_d_state"], c["mamba_d_head"], c["mamba_d_conv"]
    H = c["mamba_n_heads"]
    proj = mm(x, lw["in_proj"])
    gate, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * N],
                     proj[..., 2 * di + 2 * N:])
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(pad[:, k:k + T] * lw["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(xbc + lw["conv_b"])
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    A = -jnp.exp(lw["A_log"])
    y = _ssd(xs.reshape(b, T, H, P), dt, A, Bm, Cm, lw["D"],
             c["mamba_chunk_size"]).reshape(b, T, di)
    y = _rms(y * jax.nn.silu(gate), lw["norm_w"], float(c["rms_norm_eps"]))
    return mm(y, lw["out_proj"])


def _attention(x, lw, *, c, mm):
    b, T, d = x.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nh
    q = mm(x, lw["wq"]).reshape(b, T, nh, hd)
    k = mm(x, lw["wk"]).reshape(b, T, nkv, hd)
    v = mm(x, lw["wv"]).reshape(b, T, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * float(c["attention_multiplier"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, T, nh * hd)
    return mm(a, lw["wo"])


def _ffn(x, lw, *, c, mm):
    """The routed experts held here plus the shared MLP."""
    logits = mm(x, lw["router"])                              # (b, T, E)
    top, idx = jax.lax.top_k(logits, c["num_experts_per_tok"])
    gates = jax.nn.softmax(top, axis=-1)
    out = 0.0
    for e in range(lw["w_gate"].shape[0]):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)  # (b, T)
        h = jax.nn.silu(mm(x, lw["w_gate"][e])) * mm(x, lw["w_up"][e])
        out = out + g[..., None] * mm(h, lw["w_down"][e])
    shared = jax.nn.silu(mm(x, lw["shared_gate"])) * mm(x, lw["shared_up"])
    return out + mm(shared, lw["shared_down"])


def _layer(h, lw, *, c, kind, quant):
    mm = common.matmul(quant)
    eps, r = float(c["rms_norm_eps"]), float(c["residual_multiplier"])
    if kind == "mamba":
        mix = _mamba(_rms(h, lw["ln"], eps), lw, c=c, mm=mm)
    else:
        mix = _attention(_rms(h, lw["ln1"], eps), lw, c=c, mm=mm)
    h = h + mix * r
    return h + _ffn(_rms(h, lw["ln2"], eps), lw, c=c, mm=mm) * r


def _runs(layer_types: List[str]) -> List[Tuple[str, int]]:
    """The program's groups of weights: runs of equal kind, in order."""
    out: List[Tuple[str, int]] = []
    for t in layer_types:
        if out and out[-1][0] == t:
            out[-1] = (t, out[-1][1] + 1)
        else:
            out.append((t, 1))
    return out


def logits(c: Dict[str, Any], weights, tokens, at, quant: str = ""):
    """Float32 logits (B, K, V) of ``tokens`` (B, T) at positions ``at``
    (B, K).  ``quant`` names a lower precision for every weight matmul's
    inputs (the control); empty computes in float32 at "highest"."""
    layer = {kind: jax.jit(lambda h, lw, kind=kind: _layer(
        h, lw, c=c, kind=kind, quant=quant)) for kind in ("mamba",
                                                          "attention")}
    eps = float(c["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        h = jax.jit(lambda e, t: e[t].astype(f32) * float(
            c["embedding_multiplier"]))(weights["embed"], tokens)
        for (kind, n), g in zip(_runs(c["layer_types"]), weights["groups"]):
            for i in range(n):
                lw = common.layer_f32(g, i) if n > 1 else jax.tree.map(
                    lambda a: a.astype(f32), g)
                h = layer[kind](h, lw)
        head = jax.jit(lambda h, nw, e, at: common.matmul(quant)(
            _rms(jnp.take_along_axis(h, at[..., None], 1), nw.astype(f32),
                 eps), e.astype(f32).T) / float(c["logits_scaling"]))
        return head(h, weights["final_norm"], weights["embed"], at)


def _dims(c):
    d, nh = c["hidden_size"], c["num_attention_heads"]
    kinds = c["layer_types"]
    return dict(d=d, nh=nh, nkv=c["num_key_value_heads"], hd=d // nh,
                n_mamba=kinds.count("mamba"),
                n_attn=kinds.count("attention"),
                di=int(c["mamba_expand"] * d), N=c["mamba_d_state"],
                P=c["mamba_d_head"], H=c["mamba_n_heads"],
                K=c["mamba_d_conv"], f=c["intermediate_size"],
                fs=c["shared_intermediate_size"],
                E=c["published"]["num_local_experts"],
                held=c["num_local_experts"], k=c["num_experts_per_tok"],
                V=c["vocab_size"], L=c["num_hidden_layers"])


def model_flops(c: Dict[str, Any], prompt: int, gen: int) -> float:
    """FLOPs one request needs, per position: each Mamba layer in its
    recurrent form (projections, convolution, state update and read-out),
    each attention layer's projections and its scores and values over the
    positions before it, and in every layer the router over all experts,
    the held experts a token picks on average under uniform routing
    (``num_experts_per_tok * held / published``: 2.5 of 18) and the shared
    MLP; one row of logits after the prompt and after each of ``gen``
    decode steps."""
    m = _dims(c)
    d, di, N, H, P = m["d"], m["di"], m["N"], m["H"], m["P"]
    mamba = 2 * (d * (2 * di + 2 * N + H) + di * d + m["K"] * (di + 2 * N)
                 + 2 * H * P * N)
    q = m["nh"] * m["hd"]
    attn = 2 * (2 * d * q + 2 * d * m["nkv"] * m["hd"])
    picks = m["k"] * m["held"] / m["E"]
    ffn = 2 * d * m["E"] + picks * 6 * d * m["f"] + 6 * d * m["fs"]
    per_tok = m["n_mamba"] * mamba + m["n_attn"] * attn + m["L"] * ffn
    flops = (prompt + gen) * per_tok
    flops += m["n_attn"] * 2.0 * prompt * prompt * q
    flops += sum(m["n_attn"] * 4.0 * (prompt + i + 1) * q
                 for i in range(gen))
    return float(flops + (1 + gen) * 2 * d * m["V"])


def kernel_work(c: Dict[str, Any], prompt: int, gen: int, bucket: int
                ) -> Dict[str, tuple]:
    """Per batch of ``bucket`` rows: kernel -> (FLOPs, bytes per operand,
    calls), for the kernels whose work the shapes fix.  The expert
    kernel's work depends on the routing: ``moe_gmm_work``."""
    from yardstick import (attention_cost, attention_parts,
                           decode_attention_cost, decode_attention_parts,
                           scaled, ssd_cost, ssd_parts, summed)
    m = _dims(c)
    nm, na = m["n_mamba"], m["n_attn"]
    nh, nkv, hd = m["nh"], m["nkv"], m["hd"]
    ssd = (bucket, prompt, m["H"], m["P"], m["N"])
    fa = attention_cost(bucket, prompt, nh, nkv, hd, causal=True)[0]
    dec = sum(decode_attention_cost(bucket, prompt + i + 1, nh, nkv, hd)[0]
              for i in range(gen))
    dec_parts = summed([decode_attention_parts(bucket, prompt + i + 1, nh,
                                               nkv, hd) for i in range(gen)])
    return {"ssd_scan": (nm * ssd_cost(*ssd)[0], scaled(ssd_parts(*ssd), nm),
                         nm),
            "flash_attention": (
                na * fa, scaled(attention_parts(bucket, prompt, nh, nkv, hd),
                                na), na),
            "decode_attention": (na * dec, scaled(dec_parts, na), na * gen)}


def moe_gmm_work(c: Dict[str, Any], gen: int, rows: int, fetches: int
                 ) -> tuple:
    """One batch's expert kernel work from its counters: (FLOPs, bytes per
    operand, calls).  ``rows`` rows computed and ``fetches`` held experts
    read, summed over the batch's prefill and decode steps; one call per
    layer and forward pass.  A row costs the SwiGLU FFN's three d x f
    matmuls (6 d f FLOPs) and is read and written once in bf16; a fetch
    reads the expert's gate, up and down weights once in bf16 (18.9 MB at
    d=4096, f=768)."""
    from yardstick import BF16
    m = _dims(c)
    d, f = m["d"], m["f"]
    expert = BF16 * d * f * fetches
    parts = {"x": BF16 * d * rows, "w_gate": expert, "w_up": expert,
             "w_down": expert, "o": BF16 * d * rows}
    return 6.0 * d * f * rows, parts, m["L"] * (1 + gen)
