"""ModelConfig — one declarative config covering every assigned family.

A model is a stack of *segments*; each segment is a repeated **pattern
unit** of blocks (so hybrids like RecurrentGemma's (rglru, rglru, local)
and DeepSeek's (3 dense then 58 MoE layers) scan cleanly over homogeneous
stacks).  Block mixers: attn | local_attn | mla | ssm | rglru.
FFN kinds: dense | moe | none.

The serving-facing runtime switches (``attn_impl``, ``fused_decode``,
``spec_verify``) select the BitStopper score path and its paged decode /
speculative-verify kernels; scheduler-level policy (pool sizing, prefix
sharing, oversubscription/preemption) lives in ``ServeConfig``
(``repro.serving.engine``), not here — see ``docs/architecture.md`` and
``docs/serving.md`` for the split.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.besf import BitStopperConfig
from repro.models.layers import Yarn


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    mixer: str                    # attn | local_attn | mla | ssm | rglru
    ffn: str = "dense"            # dense | moe | none


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | audio | vlm
    d_model: int
    vocab: int
    segments: tuple[tuple[tuple[BlockSpec, ...], int], ...]
    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rope_yarn: Yarn | None = None  # YaRN on full-attention layers
                                   # (window layers keep plain RoPE)
    qk_norm: bool = False         # RMSNorm over head_dim on q and k
    window: int | None = None
    act: str = "swiglu"
    norm: str = "rms"             # rms | ln
    tie_embeddings: bool = True
    # MLA (deepseek)
    q_rank: int = 0
    kv_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE
    n_routed: int = 0
    n_shared: int = 0
    top_k: int = 0
    d_expert: int = 0
    d_shared: int = 0
    moe_capacity_factor: float = 1.25
    moe_resident: bool = False     # decode: fully-sharded resident experts
    moe_dropless: bool = False     # every token reaches every expert it
                                   # picked (serving; capacity = tokens)
    n_experts_held: int = 0        # experts this device holds, the range
    expert_offset: int = 0         # [offset, offset + held); 0 = all
    attn_chunk: int = 512          # chunked-attention tile size (xla path)
    # SSM
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    # RG-LRU
    lru_width: int = 0
    # extras
    mtp: bool = False             # multi-token prediction head (deepseek)
    frontend: str | None = None   # None | audio | vision
    # runtime
    attn_impl: str = "xla"
    bitstopper: BitStopperConfig = BitStopperConfig()
    fused_decode: bool = False    # paged serving: Pallas paged-decode kernel
    spec_verify: bool = False     # speculative serving: route multi-query
                                  # forwards through the paged BESF verify
                                  # (draft-block scoring), not block prefill
    dtype: str = "float32"        # activation dtype
    param_dtype: str = "float32"
    remat: str = "none"           # none | full | dots
    scan_layers: bool = True
    sub_quadratic: bool = False   # True iff long_500k decode is runnable

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def n_layers(self) -> int:
        return sum(len(unit) * reps for unit, reps in self.segments)

    @property
    def activation_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def parameter_dtype(self):
        return jnp.bfloat16 if self.param_dtype == "bfloat16" else jnp.float32

    # ------ derived per-module configs ------

    def attn_config(self, local: bool = False):
        from repro.models.attention import AttnConfig
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            qkv_bias=self.qkv_bias, rope_theta=self.rope_theta,
            yarn=None if local else self.rope_yarn, qk_norm=self.qk_norm,
            window=self.window if local else None,
            impl=self.attn_impl, bitstopper=self.bitstopper,
            chunk_q=self.attn_chunk, chunk_k=self.attn_chunk,
            fused_decode=self.fused_decode, spec_verify=self.spec_verify,
        )

    def mla_config(self):
        from repro.models.mla import MLAConfig
        return MLAConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            q_rank=self.q_rank, kv_rank=self.kv_rank,
            qk_nope_dim=self.qk_nope_dim, qk_rope_dim=self.qk_rope_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            impl=self.attn_impl, bitstopper=self.bitstopper,
        )

    def moe_config(self):
        from repro.models.moe import MoEConfig
        return MoEConfig(
            d_model=self.d_model, n_routed=self.n_routed, top_k=self.top_k,
            d_expert=self.d_expert, n_shared=self.n_shared,
            d_shared=self.d_shared, act=self.act,
            capacity_factor=self.moe_capacity_factor,
            resident=self.moe_resident, dropless=self.moe_dropless,
            n_held=self.n_experts_held,
            offset=self.expert_offset,
        )

    def ssm_config(self):
        from repro.models.ssm import SSMConfig
        return SSMConfig(
            d_model=self.d_model, d_state=self.ssm_state,
            d_conv=self.ssm_conv, expand=self.ssm_expand,
            head_dim=self.ssm_head_dim,
        )

    def rglru_config(self):
        from repro.models.rglru import RGLRUConfig
        return RGLRUConfig(
            d_model=self.d_model, width=self.lru_width or self.d_model,
            n_heads=self.n_heads,
        )


def uniform_segments(n_layers: int, mixer: str = "attn",
                     ffn: str = "dense") -> tuple:
    return (((BlockSpec(mixer, ffn),), n_layers),)
