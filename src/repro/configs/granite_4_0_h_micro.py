"""granite-4.0-h-micro — IBM Granite 4.0 hybrid: Mamba-2 + GQA attention.

40L in four periods of ten: attention at layers 5, 15, 25, 35, Mamba-2
everywhere else (`layer_types`), and a dense SwiGLU FFN of 8192
(`shared_intermediate_size`) after every mixer; no experts. d_model 2048.
Attention: 32 q-heads / 8 kv-heads of 64, no positional encoding
(`position_embedding_type: nope`), softmax scale `attention_multiplier`
0.015625. Mamba-2: 64 heads of 64 (expand 2), state 128, one B/C group,
conv 4 with bias, chunk 256, no projection bias. RMSNorm (eps 1e-5),
embedding x12, residual x0.22, logits /8, tied embeddings, vocab 100352.
[hf:ibm-granite/granite-4.0-h-micro config.json]
"""

from repro.configs.base import BlockDef, ModelConfig, register

MAMBA = BlockDef("mamba", "dense")
ATTN = BlockDef("attn", "dense")

CONFIG = register(
    ModelConfig(
        name="granite-4.0-h-micro",
        family="hybrid",
        num_layers=40,
        d_model=2048,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab_size=100352,
        pattern=(MAMBA, MAMBA, MAMBA, MAMBA, MAMBA, ATTN, MAMBA, MAMBA, MAMBA, MAMBA),
        norm_type="rmsnorm",
        norm_eps=1e-5,
        act="silu",
        glu=True,
        tie_embeddings=True,
        use_rope=False,
        attention_multiplier=0.015625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logit_scale=1.0 / 8.0,
        ssm_state=128,
        ssm_expand=2,
        ssm_head_dim=64,
        ssm_ngroups=1,
        ssm_chunk=256,
        ssm_conv_kernel=4,
        ssm_conv_bias=True,
        source="hf:ibm-granite/granite-4.0-h-micro",
    )
)
