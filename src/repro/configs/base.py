"""Architecture configuration system.

Every assigned architecture is a frozen ``ArchConfig`` registered under its
public id (e.g. ``qwen2-72b``).  A config fully determines the model built by
``repro.models.model.build_model``.  ``reduced()`` derives a tiny same-family
config used by the per-arch smoke tests (full configs are only ever lowered
via ShapeDtypeStructs in the dry-run).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

_REGISTRY: Dict[str, "ArchConfig"] = {}


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # --- attention ---------------------------------------------------------
    attn_type: str = "gqa"           # gqa | mla
    qkv_bias: bool = False
    qk_norm: bool = False
    sliding_window: int = 0          # 0 -> full attention
    local_global_ratio: int = 0      # gemma3: N local layers per global layer
    rope_theta: float = 10_000.0
    attn_scale: float = 0.0          # 0 -> head_dim ** -0.5

    # --- MLA (DeepSeek-V2) --------------------------------------------------
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128

    # --- MoE ----------------------------------------------------------------
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim
    first_dense_layers: int = 0      # leading dense layers (DeepSeek: 1)
    dense_d_ff: int = 0              # d_ff of those leading dense layers

    # --- SSM (Mamba2) -------------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_ngroups: int = 1             # B/C groups, each shared by H/G heads
    ssm_dt_min: float = 0.0          # dt clamped from below (time_step_min)

    # --- zamba2 shared blocks -------------------------------------------------
    hybrid_layer_ids: Tuple[int, ...] = ()  # layers a shared block feeds
    num_mem_blocks: int = 1          # shared blocks, used in turn
    adapter_rank: int = 0            # per-use LoRA on the shared MLP's gate/up
    concat_embed: bool = False       # shared block reads [stream ‖ embedding]

    # --- xLSTM ---------------------------------------------------------------
    slstm_every: int = 0             # 1-in-k layers are sLSTM, rest mLSTM

    # --- modality frontend stubs ---------------------------------------------
    frontend: str = ""               # "" | audio_frames | vision_patches

    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # ------------------------------------------------------------------ api
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def resolved_attn_scale(self) -> float:
        return self.attn_scale or self.resolved_head_dim ** -0.5

    @property
    def attn_in_dim(self) -> int:
        """Width of the attention's input: zamba2's shared block reads the
        stream concatenated with the token embedding."""
        return 2 * self.d_model if self.concat_embed else self.d_model

    @property
    def ssm_conv_channels(self) -> int:
        return self.ssm_d_inner + 2 * self.ssm_ngroups * self.ssm_state

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_n_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def is_recurrent(self) -> bool:
        """Archs with O(1)/bounded decode state (run long_500k)."""
        return self.family in ("ssm", "hybrid")

    def supports_shape(self, shape_name: str) -> bool:
        if shape_name == "long_500k":
            return self.is_recurrent
        return True

    # Parameter count (embedding included once; used for MODEL_FLOPS=6ND).
    def param_count(self) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        if self.family in ("dense", "audio", "vlm") or (
            self.family == "moe" and False
        ):
            per_layer = self._attn_params() + 3 * d * self.d_ff + 2 * d
        elif self.family == "moe":
            moe_layers = self.n_layers - self.first_dense_layers
            dense_ff = self.dense_d_ff or self.d_ff
            total = self.first_dense_layers * (
                self._attn_params() + 3 * d * dense_ff + 2 * d
            )
            experts = (self.n_routed_experts + self.n_shared_experts)
            router = d * self.n_routed_experts
            total += moe_layers * (
                self._attn_params()
                + experts * 3 * d * self.moe_d_ff
                + router
                + 2 * d
            )
            return emb + total + d
        elif self.family == "ssm":
            # xLSTM: mLSTM block params approx (qkv + out + gates + up/down)
            di = 2 * d
            per_layer = 4 * d * di + 3 * di + 2 * d
        elif self.family == "hybrid":
            di, nh, cc = self.ssm_d_inner, self.ssm_n_heads, self.ssm_conv_channels
            mamba = (d * (di + cc + nh)           # in_proj: z, x/B/C, dt
                     + (self.ssm_conv + 1) * cc    # depthwise conv and bias
                     + 3 * nh + di                 # dt_bias, A_log, D; gated norm
                     + di * d + d)                 # out_proj; layer norm
            din = self.attn_in_dim
            shared = (self._attn_params(din) + 3 * d * self.d_ff + din + d)
            use = d * d + (self.adapter_rank * (d + 2 * self.d_ff)
                           if self.adapter_rank else 0)
            return (emb + self.n_layers * mamba + self.num_mem_blocks * shared
                    + len(self.hybrid_layer_ids) * use + d)
        total = emb + self.n_layers * per_layer + d
        return total

    def _attn_params(self, d_in: int = 0) -> int:
        d, hd = d_in or self.d_model, self.resolved_head_dim
        if self.attn_type == "mla":
            r = self.kv_lora_rank
            qd = self.qk_rope_head_dim + self.qk_nope_head_dim
            q = (
                d * self.q_lora_rank + self.q_lora_rank * self.n_heads * qd
                if self.q_lora_rank
                else d * self.n_heads * qd
            )
            kv = d * (r + self.qk_rope_head_dim) + r * self.n_heads * (
                self.qk_nope_head_dim + self.v_head_dim
            )
            o = self.n_heads * self.v_head_dim * d
            return q + kv + o
        q = d * self.n_heads * hd
        kv = 2 * d * self.n_kv_heads * hd
        o = self.n_heads * hd * self.d_model
        b = (self.n_heads + 2 * self.n_kv_heads) * hd if self.qkv_bias else 0
        return q + kv + o + b

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if self.family != "moe":
            return self.param_count()
        d = self.d_model
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        moe_layers = self.n_layers - self.first_dense_layers
        dense_ff = self.dense_d_ff or self.d_ff
        total = self.first_dense_layers * (
            self._attn_params() + 3 * d * dense_ff + 2 * d
        )
        active = self.moe_top_k + self.n_shared_experts
        total += moe_layers * (
            self._attn_params()
            + active * 3 * d * self.moe_d_ff
            + d * self.n_routed_experts
            + 2 * d
        )
        return emb + total + d

    # ------------------------------------------------------------- reduced
    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw = dict(
            name=self.name + "-smoke",
            n_layers=min(self.n_layers, 7 if self.hybrid_layer_ids else 2),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2)
            if self.n_kv_heads < self.n_heads
            else 4,
            d_ff=256 if self.d_ff else 0,
            vocab_size=256,
            head_dim=32 if self.head_dim else 0,
        )
        if self.attn_type == "mla":
            kw.update(
                kv_lora_rank=32,
                q_lora_rank=32 if self.q_lora_rank else 0,
                qk_rope_head_dim=16,
                qk_nope_head_dim=32,
                v_head_dim=32,
            )
        if self.family == "moe":
            kw.update(
                n_routed_experts=8,
                moe_top_k=2,
                n_shared_experts=min(self.n_shared_experts, 1),
                moe_d_ff=64,
                dense_d_ff=256 if self.dense_d_ff else 0,
            )
        if self.family in ("ssm", "hybrid"):
            kw.update(ssm_state=16, ssm_head_dim=16,
                      ssm_ngroups=min(self.ssm_ngroups, 2))
        if self.hybrid_layer_ids:
            # uneven stretches between uses, two of them adjacent
            kw.update(hybrid_layer_ids=(1, 4, 5),
                      adapter_rank=min(self.adapter_rank, 8))
        if self.sliding_window:
            kw.update(sliding_window=64)
        return replace(self, **kw)


def register(cfg: ArchConfig) -> ArchConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    from repro.configs import (  # noqa: F401
        deepseek_v2_236b,
        deepseek_v2_lite_16b,
        gemma3_27b,
        musicgen_medium,
        pixtral_12b,
        qwen2_72b,
        qwen3_14b,
        stablelm_3b,
        xlstm_350m,
        zamba2_1p2b,
        zamba2_7b,
    )
