"""granite-4.0-h-small [granitemoehybrid] — 40L d_model=4096: 36 Mamba-2
(128 heads × 64, d_state 128, conv 4 with bias, chunk 256) + 4 GQA NoPE
attention layers (32H, kv=8, head 128) at positions 5/15/25/35; MoE in
every layer: 72 experts of width 768, top-10, plus one shared expert of
width 1536; tied vocab 100,352.
[hf:ibm-granite/granite-4.0-h-small config.json]

Scalar multipliers: embeddings × 12, each residual branch × 0.22, attention
scores × 1/128 (in place of 1/sqrt(head)), logits / 16.  Routing is
GraniteMoE's: top-10 of the 72 router logits, then a softmax over those 10.
"""

from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="granitemoehybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_head=128,
    d_ff=768,
    moe_d_ff=768,
    shared_d_ff=1536,
    vocab_size=100352,
    n_experts=72,
    top_k=10,
    shared_expert=True,
    rope_variant="none",
    attention_multiplier=0.0078125,
    attn_every=10,
    ssm_state=128,
    ssm_heads=128,
    ssm_d_head=64,
    ssm_chunk=256,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_embeddings=True,
)

# one period (9 Mamba-2 + attention at offset 5) and the published share of
# held experts (8 of 72 -> 2 of 18) at tiny widths
SMOKE = ModelConfig(
    name="granite-4.0-h-small-smoke",
    family="granitemoehybrid",
    n_layers=10,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_head=16,
    d_ff=32,
    moe_d_ff=32,
    shared_d_ff=64,
    vocab_size=512,
    n_experts=18,
    experts_held=2,
    top_k=3,
    shared_expert=True,
    rope_variant="none",
    attention_multiplier=1.0 / 16,
    attn_every=10,
    ssm_state=16,
    ssm_heads=8,
    ssm_d_head=8,
    ssm_chunk=32,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_embeddings=True,
)
