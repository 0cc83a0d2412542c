"""Model/run configuration system.

One frozen ``ModelConfig`` per architecture (exact published dims in
``repro.configs.<arch>``), plus the assigned input-shape set and
``input_specs()`` builders used by smoke tests, the dry-run and the
launchers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    tie_embeddings: bool = False
    norm_eps: float = 1e-6

    # attention features
    qkv_bias: bool = False
    sliding_window: Optional[int] = None        # SWA width (danube)
    local_global: bool = False                  # gemma2 alternation
    local_window: int = 4096
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    mlp_act: str = "silu"                       # silu | gelu
    mlp_gated: bool = True
    post_norms: bool = False                    # gemma2 post-block norms

    # MoE
    n_experts: int = 0
    moe_top_k: int = 0
    moe_capacity_factor: float = 1.25
    moe_dense_residual: bool = False            # arctic: dense MLP + MoE
    moe_dispatch: str = "scatter"               # scatter | index (§Perf)

    # MLA (minicpm3)
    mla: bool = False
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    rope_head_dim: int = 32
    nope_head_dim: int = 64

    # SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256

    # hybrid (zamba2): one shared attention block applied every k layers
    shared_attn_every: int = 0

    # enc-dec (whisper)
    encoder_layers: int = 0
    n_audio_frames: int = 1500

    # VLM (qwen2-vl)
    mrope: bool = False
    n_vision_tokens: int = 0

    # numerics / execution
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"   # full | save_collectives (§Perf A6/B4)
    seq_parallel: bool = False   # residual sharded on (model, seq) — §Perf
    optimizer_dtype: str = "float32"            # adam m/v dtype

    # ---------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_decode(self) -> bool:
        return True    # all assigned archs have a decoder

    @property
    def supports_long_context(self) -> bool:
        """long_500k eligibility: sub-quadratic state (SSM/hybrid) or
        windowed/local attention.  Pure full-attention archs skip it."""
        return (self.family in ("ssm", "hybrid")
                or self.sliding_window is not None
                or self.local_global)

    def param_count(self) -> float:
        """Analytic parameter count (for 6·N·D MODEL_FLOPS)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        hd = self.head_dim_
        emb = v * d * (1 if self.tie_embeddings else 2)
        per_layer = 0.0
        if self.family in ("ssm", "hybrid"):
            d_in = self.ssm_expand * d
            nheads = d_in // self.ssm_head_dim
            per_layer = (d * (2 * d_in + 2 * self.ssm_state + nheads)
                         + self.ssm_conv * (d_in + 2 * self.ssm_state)
                         + d_in * d + 2 * nheads)
        else:
            if self.mla:
                attn = (d * self.q_lora_rank
                        + self.q_lora_rank * self.n_heads
                        * (self.nope_head_dim + self.rope_head_dim)
                        + d * (self.kv_lora_rank + self.rope_head_dim)
                        + self.kv_lora_rank * self.n_heads
                        * (self.nope_head_dim + self.nope_head_dim)
                        + self.n_heads * self.nope_head_dim * d)
            else:
                attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) \
                    + self.n_heads * hd * d
            if self.n_experts:
                mlp = self.n_experts * 3 * d * ff
                if self.moe_dense_residual:
                    mlp += 3 * d * ff
            else:
                mlp = 3 * d * ff
            per_layer = attn + mlp + 2 * d
        n = emb + self.n_layers * per_layer
        if self.family == "hybrid" and self.shared_attn_every:
            n += (d * hd * (self.n_heads + 2 * self.n_kv_heads)
                  + self.n_heads * hd * d + 3 * d * ff)
        if self.family == "encdec":
            # encoder layers + cross attention in decoder
            enc = self.encoder_layers * (4 * d * d + 3 * d * ff + 2 * d)
            cross = self.n_layers * 4 * d * d
            n += enc + cross
        return float(n)

    def active_param_count(self) -> float:
        """Active params (MoE: top-k experts only) for 6·N_active·D."""
        if not self.n_experts:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        total = self.param_count()
        moe_all = self.n_layers * self.n_experts * 3 * d * ff
        moe_active = self.n_layers * self.moe_top_k * 3 * d * ff
        return float(total - moe_all + moe_active)


# ---------------------------------------------------------------------------
# Assigned input shapes.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """(runs?, reason-if-skipped) — see DESIGN.md §Arch-applicability."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("pure full-attention architecture: a 512k-token "
                       "decode KV cache with no windowing/state is skipped "
                       "per assignment")
    return True, ""


def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model-input ShapeDtypeStructs for an assigned shape (dry-run entry)."""
    shape = SHAPES[shape_name]
    if shape.kind == "decode":
        # decode lowers serve_step: one new token against a seq_len cache
        return token_inputs(cfg, ShapeSpec(shape.name, 1, shape.global_batch,
                                           "decode"), for_train=False)
    return token_inputs(cfg, shape, for_train=shape.kind == "train")


def token_inputs(cfg: ModelConfig, shape: ShapeSpec,
                 for_train: bool) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.family == "encdec":
        # frontend stub: precomputed audio-frame embeddings
        specs["encoder_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_audio_frames, cfg.d_model), cfg.activation_dtype)
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
    elif cfg.family == "vlm":
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_vision_tokens, cfg.d_model), cfg.activation_dtype)
        specs["positions"] = jax.ShapeDtypeStruct((3, b, s), i32)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
    if for_train:
        specs["targets"] = jax.ShapeDtypeStruct((b, s), i32)
    return specs
