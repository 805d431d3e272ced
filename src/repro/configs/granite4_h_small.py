"""IBM Granite 4.0-H Small (32B-A9B;
huggingface.co/ibm-granite/granite-4.0-h-small config.json) — a Mamba-2 /
attention hybrid: 40 layers in the published order, 36 Mamba-2 mixers and
4 GQA attention mixers without position encoding (layers 5, 15, 25, 35);
after every mixer a routed MoE (72 SwiGLU experts of width 768, top-10,
gates a softmax over the ten chosen router logits) plus a shared SwiGLU
expert of width 1536; muP multipliers on the embedding (12), every
sublayer's output (0.22), the attention scores (1/128) and the logits
(divided by 16)."""
from ..models.config import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", arch_type="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, vocab_size=100352,
    n_experts=72, experts_per_token=10, capacity_factor=0.0,
    d_shared=1536,
    ssm_state=128, ssm_heads=128, ssm_head_dim=64, ssm_expand=2,
    ssm_chunk=256, ssm_conv=4, ssm_norm_eps=1e-5,
    layer_types=_PERIOD * 4,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0, rope=False,
    norm_eps=1e-5, mlp="swiglu", tie_embeddings=True,
)

REDUCED = CONFIG.with_(
    n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
    d_ff=64, d_shared=128, vocab_size=1024,
    n_experts=4, experts_per_token=2,
    ssm_state=16, ssm_heads=8, ssm_head_dim=64, ssm_chunk=16,
    layer_types=("mamba", "attention", "mamba", "mamba"),
    attention_multiplier=1.0 / 64,
)
