"""Model configuration: one dataclass drives every architecture family.

A model is a stack of *layer groups*.  Each group is homogeneous (same kind,
same shapes) so it lowers to one ``lax.scan`` over stacked parameters — this
keeps HLO size and compile time independent of depth, which matters when
compiling 56-layer models for 512-device meshes on a CPU host.

Heterogeneous patterns (gemma3's 5 local : 1 global, zamba2's shared
attention every-k) are expressed as several groups.  Group order is the
execution order; for interleaved patterns we execute group-by-group, which
permutes layers relative to the original checkpoints.  FLOPs / memory /
collectives — everything the dry-run and roofline measure — are invariant
under this permutation (see DESIGN.md §4).  A config that gives its
per-layer pattern (``layer_types``, granite-4.0-h) is executed in the
published order instead: each run of equal kind is one group.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class LayerGroup:
    """A run of identical layers executed as one scan."""

    kind: str                   # "attn" | "mamba" | "shared_attn_marker"
    count: int
    window: int = 0             # 0 = full causal attention; >0 = sliding window
    cross_attn: bool = False    # decoder layers attending to encoder output
    moe: bool = False           # FFN is a mixture of experts


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str              # dense|moe|ssm|hybrid|encdec|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads

    # -- attention ---------------------------------------------------------
    rope_theta: float = 10_000.0
    sliding_window: int = 0     # uniform SWA width (mixtral-style); 0 = full
    local_window: int = 0       # local:global pattern (gemma3-style)
    local_ratio: int = 0        # local layers per global layer (5 for gemma3)

    # -- MoE -----------------------------------------------------------------
    n_experts: int = 0          # the router's width: experts published
    experts_per_token: int = 0
    # capacity for the training path's dispatch; 0 = dropless everywhere.
    # Serving (prefill / decode) is always dropless.
    capacity_factor: float = 1.25
    experts_held: int = 0       # experts 0..held-1 live here; 0 = all
    d_shared: int = 0           # shared SwiGLU expert's width; 0 = none

    # -- SSM (mamba2 / zamba2) ----------------------------------------------
    ssm_state: int = 0
    ssm_heads: int = 0          # 0 -> d_model // ssm_head_dim
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_norm_eps: float = 1e-6  # the gated RMSNorm's eps

    # -- per-layer mixer pattern ("mamba" | "attention"), published order ----
    layer_types: Tuple[str, ...] = ()

    # -- muP multipliers (granite): 0 / 1 keep the plain transformer ---------
    embedding_multiplier: float = 0.0   # 0 -> sqrt(d_model)
    residual_multiplier: float = 1.0    # each sublayer's output is scaled
    attention_multiplier: float = 0.0   # score scale; 0 -> 1/sqrt(hd)
    logits_scaling: float = 1.0         # logits are divided by it
    rope: bool = True                   # False: no position encoding

    # -- hybrid (zamba2): one shared attention block every k mamba layers ----
    shared_attn_every: int = 0

    # -- encoder-decoder (whisper) -------------------------------------------
    n_enc_layers: int = 0
    n_enc_tokens: int = 0       # encoder sequence length (1500 audio frames)

    # -- modality frontend stubs (vlm / audio): see DESIGN.md carve-out ------
    frontend: str = ""          # "" | "vision" | "audio"
    n_frontend_tokens: int = 0  # patch/frame embeddings prepended to the seq

    # -- kernel dispatch -----------------------------------------------------
    # which implementation services the hot spots (attention, decode
    # attention over KV caches, the SSD scan): "xla" = pure-jnp reference
    # (default; byte-compatible with the pre-dispatch model), "pallas" =
    # compiled Pallas TPU kernels, "pallas_interpret" = Pallas in interpret
    # mode (CPU validation).  See repro.kernels.ops.KERNEL_TABLE and
    # docs/KERNELS.md.
    kernels: str = "xla"

    # -- misc ------------------------------------------------------------------
    mlp: str = "swiglu"         # "swiglu" | "gelu"
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # training schedule tag (minicpm's WSD); consumed by repro.train
    lr_schedule: str = "cosine"

    # roofline probes: explicit layer-group override (see launch/roofline.py)
    override_groups: Optional[Tuple[LayerGroup, ...]] = None
    # roofline probes: fully unroll scans so cost_analysis sees straight-line
    # HLO (XLA counts while bodies ONCE regardless of trip count)
    scan_unroll: bool = False
    # activation rematerialization at layer boundaries (training memory)
    remat: bool = True

    # ------------------------------------------------------------------ derived
    @property
    def vocab_padded(self) -> int:
        """Embedding/unembedding allocation size: vocab rounded up to a
        multiple of 256 so the vocab axis shards evenly on any production
        mesh (logit columns beyond vocab_size are never valid targets)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_ssm_heads(self) -> int:
        if self.ssm_heads:
            return self.ssm_heads
        return (self.d_model * self.ssm_expand) // self.ssm_head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_held(self) -> int:
        """Experts whose weights this chip holds (the first ``n_held``)."""
        return self.experts_held or self.n_experts

    @property
    def score_scale(self) -> Optional[float]:
        """Attention's score scale; None for the kernels' 1/sqrt(hd)."""
        return self.attention_multiplier or None

    def dtype(self) -> jnp.dtype:
        return jnp.dtype(self.compute_dtype)

    def pdtype(self) -> jnp.dtype:
        return jnp.dtype(self.param_dtype)

    # -- layer-group derivation ------------------------------------------------
    def groups(self) -> Tuple[LayerGroup, ...]:
        """Decoder layer groups in execution order."""
        if self.override_groups is not None:
            return self.override_groups
        moe = self.n_experts > 0
        if self.layer_types:
            if len(self.layer_types) != self.n_layers:
                raise ValueError(f"{self.name}: {len(self.layer_types)} "
                                 f"layer types for {self.n_layers} layers")
            gs = []
            for t in self.layer_types:
                kind = {"mamba": "mamba", "attention": "attn"}[t]
                if gs and gs[-1].kind == kind:
                    gs[-1] = replace(gs[-1], count=gs[-1].count + 1)
                else:
                    gs.append(LayerGroup(kind, 1, moe=moe))
            return tuple(gs)
        if self.arch_type == "ssm":
            return (LayerGroup("mamba", self.n_layers),)
        if self.arch_type == "hybrid":
            # zamba2: mamba backbone, shared attention block every k layers
            k = self.shared_attn_every or 6
            gs = []
            remaining = self.n_layers
            while remaining > 0:
                c = min(k, remaining)
                gs.append(LayerGroup("mamba", c))
                remaining -= c
                if remaining >= 0 and c == k:
                    gs.append(LayerGroup("shared_attn", 1))
            return tuple(gs)
        if self.local_ratio > 0:
            # gemma3: r local layers per global layer (grouped, see module doc)
            n_global = max(1, self.n_layers // (self.local_ratio + 1))
            n_local = self.n_layers - n_global
            return (LayerGroup("attn", n_local, window=self.local_window,
                               moe=moe),
                    LayerGroup("attn", n_global, moe=moe))
        w = self.sliding_window
        cross = self.arch_type == "encdec"
        return (LayerGroup("attn", self.n_layers, window=w, moe=moe,
                           cross_attn=cross),)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    def pad_heads(self, multiple: int = 16) -> "ModelConfig":
        """Round head counts up to a multiple so they shard evenly on the
        ``model`` mesh axis (beyond-paper perf variant).

        Padding is *exact*: padded heads have zero wk/wv/wo weights, so their
        keys/values/outputs are identically zero and contribute nothing —
        semantics are preserved while the KV cache becomes head-shardable
        (avoiding GSPMD's head-dim sharding + RoPE-split full
        rematerialization).  Costs (multiple/heads)x extra attention FLOPs.
        """
        if not self.n_heads:
            return self
        up = lambda x: -(-x // multiple) * multiple
        return self.with_(n_heads=up(self.n_heads),
                          n_kv_heads=up(self.n_kv_heads))

    # -- sizes ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (for 6ND roofline math)."""
        d, v = self.d_model, self.vocab_size
        total = v * d                      # embedding
        if not self.tie_embeddings:
            total += v * d
        # held experts, router, shared expert (SwiGLU)
        moe = (self.n_held * 3 * d * self.d_ff + d * self.n_experts
               + 3 * d * self.d_shared)
        for g in self.groups():
            for _ in range(g.count):
                if g.kind in ("attn", "shared_attn"):
                    qkv = d * (self.n_heads * self.hd) \
                        + 2 * d * (self.n_kv_heads * self.hd) \
                        + (self.n_heads * self.hd) * d
                    total += qkv
                    if g.cross_attn:
                        total += qkv
                    ff_in = 2 * d * self.d_ff if self.mlp == "swiglu" \
                        else d * self.d_ff
                    ff = ff_in + self.d_ff * d
                    if g.moe:
                        total += moe
                    else:
                        total += ff
                    total += 2 * d        # norms
                elif g.kind == "mamba":
                    di, ns, nh = self.d_inner, self.ssm_state, self.n_ssm_heads
                    total += d * (2 * di + 2 * ns + nh)   # in_proj
                    total += self.ssm_conv * (di + 2 * ns)  # conv
                    total += di * d                      # out_proj
                    total += 3 * nh                      # A, dt_bias, D
                    total += d                           # norm
                    if g.moe:
                        total += moe + d                 # FFN and its norm
        # encoder stack
        if self.n_enc_layers:
            qkv = 4 * d * (self.n_heads * self.hd)
            ff = 2 * d * self.d_ff
            total += self.n_enc_layers * (qkv + ff + 2 * d)
        return int(total)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts), for MODEL_FLOPS."""
        if self.n_experts == 0:
            return self.param_count()
        d = self.d_model
        ff_in = 2 * d * self.d_ff if self.mlp == "swiglu" else d * self.d_ff
        ff = ff_in + self.d_ff * d
        # held experts a token picks on average, under uniform routing
        picked = self.experts_per_token * self.n_held / self.n_experts
        dead_experts = self.n_held - picked
        n_moe_layers = sum(g.count for g in self.groups() if g.moe)
        return int(self.param_count() - n_moe_layers * dead_experts * ff)
