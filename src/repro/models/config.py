"""Unified model configuration covering all assigned architecture families."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from repro.models.common import YarnScaling


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | rwkv | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_yarn: Optional[YarnScaling] = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # -- latent attention (MLA; on where kv_lora_rank > 0) ---------------------
    # head_dim is the per-head q.k width without rope (qk_nope_head_dim)
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # -- MoE ----------------------------------------------------------------
    moe_num_experts: int = 0     # the router's width
    moe_top_k: int = 0
    moe_num_shared: int = 0      # shared (always-on) experts
    moe_d_ff: int = 0            # per-(routed-)expert hidden dim
    moe_aux_coef: float = 0.01
    moe_norm_topk: bool = True   # renormalise the top-k gates to sum to 1
    # the experts held here, from expert 0 (0: all); choices of the other
    # experts are another chip's part
    moe_experts_held: int = 0
    first_dense_layers: int = 0  # leading dense layers (d_ff) of an MoE stack

    # -- SSM / RWKV / hybrid ----------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    attn_every: int = 0          # hybrid: shared attn block every k SSM layers
    rwkv_head_dim: int = 64
    rwkv_lora_rank: int = 64

    # -- modality frontends ---------------------------------------------------
    frontend: str = "none"       # none | vision | audio
    num_codebooks: int = 1       # audio: EnCodec codebooks

    # -- numerics / execution ---------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    attention_impl: str = "auto"      # auto | dense | chunked | pallas
    attention_chunk: int = 1024
    # scan_carry = baseline (double-buffers the cache); readonly_fused is the
    # measured-best default (§Perf D1/D2)
    decode_cache_mode: str = "readonly_fused"
    rwkv_chunk: int = 64   # measured optimum on train_4k (§Perf R2): 4.3x memory term vs 32
    ssm_chunk: int = 64
    logits_softcap: float = 0.0

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(1, self.num_heads))

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.moe_num_experts

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def attention_free(self) -> bool:
        return self.family == "rwkv"

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (SSM / hybrid / linear-attention)."""
        return self.family in ("rwkv", "hybrid")

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- parameter counting (roofline MODEL_FLOPS) ------------------------------
    def param_count(self) -> int:
        from repro.models.model import param_specs
        import numpy as np
        specs = param_specs(self)
        import jax
        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: hasattr(x, "logical_axes"))
        return int(sum(np.prod(l.shape) for l in leaves))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: shared experts and, of the routed
        experts held, the top-k share a token visits on average)."""
        total = self.param_count()
        if self.family != "moe" or not self.moe_num_experts:
            return total
        per_expert = 3 * self.d_model * self.moe_d_ff  # gate/up/down
        held = self.experts_held
        inactive = held - self.moe_top_k * held / self.moe_num_experts
        moe_layers = self.num_layers - self.first_dense_layers
        return int(total - moe_layers * inactive * per_expert)
