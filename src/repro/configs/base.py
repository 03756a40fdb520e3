"""Model configuration system.

One ``ModelConfig`` dataclass covers every assigned architecture family:
dense (GQA), MoE (top-k routed + shared experts, MLA), SSM (Mamba-1),
hybrid (RG-LRU + local attention), encoder-decoder audio (Whisper) and
VLM (interleaved cross-attention), and stacks that mix sliding-window
and full attention layers (``layer_types``). Every config file in this
package instantiates one ``ModelConfig`` with the exact assigned
hyper-parameters and cites its source in ``source``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class RopeYarn:
    """YaRN scaling of rotary frequencies (arXiv:2309.00071), with the
    parameters of a Hugging Face ``rope_type: "yarn"`` entry."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0    # multiplies cos and sin


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # -- identity -----------------------------------------------------------
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""                 # citation (arXiv id / model card)

    # -- trunk --------------------------------------------------------------
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 32000
    head_dim: Optional[int] = None   # default: d_model // n_heads
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    mlp_act: str = "swiglu"          # swiglu | gelu | geglu
    tie_embeddings: bool = False

    # -- attention ----------------------------------------------------------
    rope_theta: float = 10_000.0
    use_rope: bool = True
    qkv_bias: bool = False           # qwen2-style QKV bias
    qk_norm: bool = False            # qwen3-style per-head RMSNorm on q,k
    sliding_window: Optional[int] = None   # SWA window; None = full attention
    # per-layer attention type (FULL / SLIDING, the published
    # ``layer_types``); empty: every layer attends alike (sliding_window)
    layer_types: Tuple[str, ...] = ()
    rope_yarn: Optional[RopeYarn] = None   # YaRN RoPE of the full layers
    attn_bias: bool = False          # bias on all attn projections (whisper)

    # -- MLA (deepseek-v2) ----------------------------------------------------
    use_mla: bool = False
    kv_lora_rank: int = 512
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_absorb: bool = False         # decode-time weight absorption (opt)

    # -- MoE ------------------------------------------------------------------
    moe: bool = False
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim
    first_dense_layers: int = 0      # deepseek: leading dense layers
    router_aux_weight: float = 0.01
    # experts this chip holds, 0 .. experts_held - 1 of n_experts (expert
    # parallelism); 0 = all of them
    experts_held: int = 0

    # -- SSM (mamba-1) ----------------------------------------------------------
    ssm: bool = False
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: Optional[int] = None    # default ceil(d_model / 16)

    # -- hybrid (RG-LRU, recurrentgemma) -----------------------------------
    block_pattern: Tuple[str, ...] = ()    # e.g. ("rec", "rec", "attn")
    lru_width: Optional[int] = None        # default d_model
    local_window: int = 2048

    # -- encoder-decoder (whisper) ------------------------------------------
    enc_dec: bool = False
    n_encoder_layers: int = 0
    encoder_seq: int = 1500          # stub frame-embedding count

    # -- VLM (llama-3.2-vision) ----------------------------------------------
    cross_attn_every: int = 0        # insert one cross-attn layer every N
    n_media_tokens: int = 0          # stub patch-embedding count

    # -- numerics -------------------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    attn_q_chunk: int = 512          # chunked-attention tile sizes (perf knobs)
    attn_kv_chunk: int = 1024
    attn_causal_skip: bool = False   # skip fully-masked kv blocks (perf)
    train_remat: bool = True         # activation checkpointing in train
    fsdp: bool = False               # ZeRO-3-style: shard param "embed" dims
                                     # over the data axis (all-gather at use)

    # -- EdgeRL execution-profile metadata -------------------------------------
    #   versions: quantization levels of this model available as EdgeRL
    #   versions (repro.quant registry names; paper analogue: VGG11/19).
    #   cut_points resolved at runtime from layer profiles (core/profiles.py).
    versions: Tuple[str, ...] = ("bf16", "w8", "w4")

    # ------------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.n_heads

    @property
    def resolved_dt_rank(self) -> int:
        if self.dt_rank is not None:
            return self.dt_rank
        return -(-self.d_model // 16)

    @property
    def resolved_lru_width(self) -> int:
        return self.lru_width if self.lru_width is not None else self.d_model

    @property
    def resolved_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def mixed_attention(self) -> bool:
        """Whether the trunk mixes full and sliding-window layers."""
        return len(set(self.layer_types[:self.n_layers])) > 1

    def attention_windows(self) -> Tuple[Optional[int], ...]:
        """Per decoder layer, the attention window (None = full)."""
        if not self.layer_types:
            return (self.sliding_window,) * self.n_layers
        return tuple(self.sliding_window if t == SLIDING else None
                     for t in self.layer_types[:self.n_layers])

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind for the decoder trunk.

        Kinds: "attn" (self-attention block), "rec" (RG-LRU block),
        "ssm" (mamba block), "xattn" (cross-attention block).
        """
        if self.ssm:
            return ("ssm",) * self.n_layers
        if self.block_pattern:
            p = self.block_pattern
            return tuple(p[i % len(p)] for i in range(self.n_layers))
        if self.cross_attn_every:
            kinds = []
            for i in range(self.n_layers):
                # every Nth slot is a gated cross-attention block
                if (i + 1) % self.cross_attn_every == 0:
                    kinds.append("xattn")
                else:
                    kinds.append("attn")
            return tuple(kinds)
        return ("attn",) * self.n_layers

    def active_params_per_token_factor(self) -> float:
        """Fraction of MoE expert params active per token (1.0 for dense)."""
        if not self.moe or self.n_experts == 0:
            return 1.0
        return (self.top_k + self.n_shared_experts) / (
            self.n_experts + self.n_shared_experts
        )

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: <=2 layers (per stack), d_model<=512, <=4 experts."""
        kw = dict(
            n_layers=max(2, min(2, self.n_layers)),
            d_model=min(self.d_model, 256),
            n_heads=min(self.n_heads, 4),
            n_kv_heads=min(self.n_kv_heads, 2),
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            head_dim=64 if self.resolved_head_dim >= 64 else self.resolved_head_dim,
            param_dtype="float32",
            compute_dtype="float32",
        )
        if self.moe:
            n_exp = min(self.n_experts, 4)
            kw.update(
                n_experts=n_exp,
                top_k=min(self.top_k, 2),
                moe_d_ff=min(self.moe_d_ff, 256),
                first_dense_layers=min(self.first_dense_layers, 1),
                # a partial hold keeps its share of the experts
                experts_held=(max(1, self.experts_held * n_exp
                                  // self.n_experts)
                              if self.experts_held else 0),
            )
        if self.use_mla:
            kw.update(kv_lora_rank=64, qk_rope_head_dim=16,
                      qk_nope_head_dim=32, v_head_dim=32)
        if self.ssm:
            kw.update(ssm_state=8, dt_rank=16)
        if self.block_pattern:
            # keep one full period plus remainder handling exercised
            kw.update(n_layers=max(2, len(self.block_pattern)),
                      lru_width=min(self.resolved_lru_width, 256),
                      local_window=64)
        if self.enc_dec:
            kw.update(n_encoder_layers=2, encoder_seq=16)
        if self.cross_attn_every:
            kw.update(n_layers=4, cross_attn_every=2, n_media_tokens=8)
        if self.sliding_window is not None:
            kw.update(sliding_window=min(self.sliding_window, 64))
        if self.mixed_attention:
            # one whole period of the layer pattern, and a window shorter
            # than the smoke tests' sequences
            period = self.layer_types.index(FULL) + 1
            kw.update(n_layers=period,
                      sliding_window=min(self.sliding_window, 8))
        return self.with_overrides(**kw)


_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    # import side-effect registration
    from repro.configs import ALL_ARCHS  # noqa: F401

    if name not in _REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs():
    from repro.configs import ALL_ARCHS  # noqa: F401

    return dict(_REGISTRY)
