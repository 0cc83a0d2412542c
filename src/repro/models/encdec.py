"""Encoder-decoder model (Whisper backbone; conv frontend is a stub —
``input_specs()`` supplies precomputed audio-frame embeddings)."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models.layers import (attention_specs, embed, lm_head, mlp_specs,
                                 rms_norm)


def encoder_layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": tfm._norm_spec(cfg),
        "attn": attention_specs(cfg),
        "ln2": tfm._norm_spec(cfg),
        "mlp": mlp_specs(cfg),
    }


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    specs = tfm.model_specs(cfg)
    specs["encoder"] = tfm._stack(encoder_layer_specs(cfg),
                                  cfg.encoder_layers, "enc_layers")
    specs["enc_final_norm"] = tfm._norm_spec(cfg)
    return specs


def encode(params, encoder_embeds, cfg: ModelConfig):
    """Bidirectional encoder over the (stubbed) audio-frame embeddings."""
    bsz, frames, _ = encoder_embeds.shape
    x = encoder_embeds.astype(cfg.activation_dtype)
    positions = jnp.broadcast_to(jnp.arange(frames, dtype=jnp.int32)[None],
                                 (bsz, frames))

    def body(x, lp):
        x, _, _ = tfm._attn_layer(x, lp, cfg, positions, causal=False)
        return x, ()

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def _cross_kv(enc, p):
    """One decoder layer's cross-attention (k, v) of the encoder output."""
    return (jnp.einsum("bsd,dhk->bshk", enc, p["wk"]),
            jnp.einsum("bsd,dhk->bshk", enc, p["wv"]))


def forward(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            attn_fn=None):
    """Encoder + causal decoder with cross attention -> logits."""
    enc = encode(params, batch["encoder_embeds"], cfg)
    tokens = batch["tokens"]
    bsz, seq = tokens.shape
    x = embed(tokens, params["embed"], cfg)
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None],
                                 (bsz, seq))

    def body(x, lp):
        x, _, _ = tfm._attn_layer(x, lp, cfg, positions, attn_fn=attn_fn,
                                  cross=_cross_kv(enc, lp["cross"]))
        return x, ()

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return lm_head(x, params["embed"], cfg), jnp.zeros((), jnp.float32)


def prefill_cross_cache(params, encoder_embeds, cfg: ModelConfig):
    """Precompute per-layer cross K/V from the encoder output (decode path)."""
    enc = encode(params, encoder_embeds, cfg)

    def body(_, lp):
        return None, _cross_kv(enc, lp["cross"])

    _, (cks, cvs) = jax.lax.scan(body, None, params["layers"])
    return cks, cvs
