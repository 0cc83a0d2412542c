"""Top-level model API: specs / init / forward / loss / decode per config."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer
from repro.models.layers import init_from_specs, sds_from_specs


def model_specs(cfg: ModelConfig):
    if cfg.family == "encdec":
        return encdec.model_specs(cfg)
    return transformer.model_specs(cfg)


def init_params(cfg: ModelConfig, key: jax.Array):
    return init_from_specs(model_specs(cfg), key)


def params_sds(cfg: ModelConfig):
    return sds_from_specs(model_specs(cfg))


def forward(params, batch, cfg: ModelConfig, attn_fn=None):
    if cfg.family == "encdec":
        return encdec.forward(params, batch, cfg, attn_fn=attn_fn)
    return transformer.forward(params, batch, cfg, attn_fn=attn_fn)


def decode_step(params, cache, tokens, cfg: ModelConfig, n=None,
                attn_fn=None):
    """``attn_fn`` is taken for the serve step's signature and unused:
    attention against a cache has no fused kernel."""
    return transformer.decode_step(params, cache, tokens, cfg, n=n)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return transformer.init_cache(cfg, batch, max_seq)


def init_cache_specs(cfg: ModelConfig, batch: int, max_seq: int):
    return transformer.init_cache_specs(cfg, batch, max_seq)


def cross_entropy(logits, targets, z_loss: float = 1e-4):
    """Token-mean CE with optional z-loss; logits f32 [B,S,V]."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - gold)
    if z_loss:
        ce = ce + z_loss * jnp.mean(lse ** 2)
    return ce


def loss_fn(params, batch, cfg: ModelConfig, attn_fn=None):
    logits, aux = forward(params, batch, cfg, attn_fn=attn_fn)
    loss = cross_entropy(logits, batch["targets"])
    if cfg.n_experts:
        loss = loss + 1e-2 * aux
    return loss, {"ce": loss, "aux": aux}
