"""granite-4.0-h-micro [hybrid]: 40 layers in a period of 10 (5 Mamba-2,
1 GQA attention, 4 Mamba-2), a SwiGLU MLP after every mixer, NoPE
attention, and Granite's embedding, attention, residual and logit
scalars.  [hf: ibm-granite/granite-4.0-h-micro config.json]"""
import dataclasses
from repro.models.config import ModelConfig

PERIOD = ("ssm",) * 5 + ("dense",) + ("ssm",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192,
    vocab_size=100_352, use_rope=False, qkv_bias=False,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, ssm_conv=4,
    ssm_chunk=256, ssm_mlp=True, layer_pattern=PERIOD,
    norm_eps=1e-5, embed_multiplier=12.0, attn_scale=0.015625,
    residual_multiplier=0.22, logits_divisor=8.0,
    tie_embeddings=True, max_seq=131_072,
)

# two whole periods (so the layer scan runs, as at full depth) at small
# widths: 8 Mamba-2 heads of 16, state 16
SMOKE = dataclasses.replace(
    CONFIG, name="granite-4.0-h-micro-smoke", n_layers=20, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512, ssm_state=16,
    ssm_head_dim=16, ssm_chunk=16, attn_scale=1.0 / 16, max_seq=256)

# a served model: not one of the assigned dry-run shape cells
CELLS = ()
