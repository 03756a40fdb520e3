"""mellum2-12b-a2.5b [moe] — sliding (1024) and full attention layers in
one stack, YaRN on the full ones, 64 experts top-8 with normalized
top-k weights and no shared expert.

[https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json]
Layers follow ``layer_types``: (sliding, sliding, sliding, full) x 7. The
config's MTP head is left out: prefill scoring never uses it, and the
config gives it no width. It states no q/k norm, so none is applied.
"""
from repro.configs.base import FULL, SLIDING, ModelConfig, RopeYarn, register

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    source="https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=7168,               # the dense width; every layer's MLP is sparse
    vocab_size=98_304,
    sliding_window=1024,
    layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 7,
    rope_theta=500_000.0,
    rope_yarn=RopeYarn(factor=16.0, original_max_position=8192,
                       beta_fast=32.0, beta_slow=1.0,
                       attention_factor=1.2772588722239782),
    moe=True,
    n_experts=64,
    n_shared_experts=0,
    top_k=8,
    moe_d_ff=896,
    norm="rmsnorm",
    mlp_act="swiglu",
    tie_embeddings=False,
))
