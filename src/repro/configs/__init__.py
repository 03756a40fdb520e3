"""Architecture configs (assigned pool) + shape suite.

``ALL_ARCHS`` lists the registered architecture ids; importing this module
registers all of them. ``SHAPES`` is the assigned input-shape suite.
"""
from repro.configs.base import ModelConfig, get_config, list_configs, register

# import for registration side effects
from repro.configs import (  # noqa: F401
    qwen2_0_5b,
    llama_3_2_vision_90b,
    starcoder2_3b,
    recurrentgemma_2b,
    phi3_medium_14b,
    falcon_mamba_7b,
    deepseek_v2_lite_16b,
    qwen3_0_6b,
    whisper_large_v3,
    mixtral_8x22b,
    mellum2_12b_a2_5b,
)

ALL_ARCHS = (
    "qwen2-0.5b",
    "llama-3.2-vision-90b",
    "starcoder2-3b",
    "recurrentgemma-2b",
    "phi3-medium-14b",
    "falcon-mamba-7b",
    "deepseek-v2-lite-16b",
    "qwen3-0.6b",
    "whisper-large-v3",
    "mixtral-8x22b",
    "mellum2-12b-a2.5b",
)

# (name, seq_len, global_batch, kind)
SHAPES = {
    "train_4k":    dict(seq_len=4_096,   global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32_768,  global_batch=32,  kind="prefill"),
    "decode_32k":  dict(seq_len=32_768,  global_batch=128, kind="decode"),
    "long_500k":   dict(seq_len=524_288, global_batch=1,   kind="decode"),
}

__all__ = [
    "ModelConfig", "get_config", "list_configs", "register",
    "ALL_ARCHS", "SHAPES",
]
