"""granite-4.0-h-micro [granitehybrid] — 40 layers: 36 Mamba2 mixers and 4
NoPE grouped-query attention layers (indices 5, 15, 25, 35), each followed
by a SwiGLU MLP, with granite's embedding, attention, residual and logit
multipliers.  Values from the model's published config.json
(``model_type`` granitemoehybrid, no experts)."""
from repro.configs.base import ModelConfig

ATTENTION_AT = (5, 15, 25, 35)

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="granitehybrid",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
           "main/config.json",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,                       # shared_intermediate_size
    vocab_size=100352,
    mlp_activation="silu",
    use_rope=False,                  # position_embedding_type "nope"
    max_position_embeddings=131072,
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm_state=128,                   # mamba_d_state
    ssm_head_dim=64,                 # mamba_d_head (64 heads = 4096 / 64)
    ssm_expand=2,                    # d_inner 4096
    ssm_chunk=256,                   # mamba_chunk_size
    conv_kernel=4,                   # mamba_d_conv (with bias)
    layer_types=tuple("attention" if i in ATTENTION_AT else "mamba"
                      for i in range(40)),
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
)
