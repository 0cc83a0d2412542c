"""Decoder-only model assembly (dense / MoE / MLA / VLM / SSM / hybrid).

Scan-over-layers with stacked parameters keeps the HLO compact (one layer
body compiled once regardless of depth) — essential for the 40-cell × 512-
device dry-run.  Per-layer behaviour variation (gemma2's local/global
alternation, zamba2's shared-attention applications) is carried by scanned
flag arrays rather than unrolled branches.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.parallel.act_sharding import BATCH, MODEL, constrain
from repro.models.layers import (CACHE_TILE, PSpec, attention,
                                 attention_specs, embed, embed_specs,
                                 kv_cache_row, lm_head, mla_attention,
                                 mla_specs, mlp, mlp_specs, rms_norm)

BIG_WINDOW = 1 << 30
MROPE_SECTIONS = (16, 24, 24)     # qwen2-vl frequency split (head_dim 128)


def _stack(specs, n: int, axis_name: str = "layers"):
    return jax.tree.map(
        lambda sp: PSpec((n,) + sp.shape, (axis_name,) + sp.axes, sp.dtype,
                         sp.init),
        specs, is_leaf=lambda x: isinstance(x, PSpec))


def _norm_spec(cfg: ModelConfig) -> PSpec:
    return PSpec((cfg.d_model,), ("embed",), "float32", init="zeros")


# ---------------------------------------------------------------------------
# Param specs.
# ---------------------------------------------------------------------------
def decoder_layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.family == "ssm" or (cfg.family == "hybrid"):
        out = {"ln1": _norm_spec(cfg), "ssm": ssm_mod.ssm_specs(cfg)}
        if cfg.family == "hybrid":
            return out
        return out
    out: Dict[str, Any] = {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg)}
    if cfg.mla:
        out["attn"] = mla_specs(cfg)
    else:
        out["attn"] = attention_specs(cfg)
    if cfg.family == "encdec":
        out["ln_cross"] = _norm_spec(cfg)
        out["cross"] = attention_specs(cfg)
    if cfg.n_experts:
        out["moe"] = moe_mod.moe_specs(cfg)
        if cfg.moe_dense_residual:
            out["mlp"] = mlp_specs(cfg)
    else:
        out["mlp"] = mlp_specs(cfg)
    if cfg.post_norms:
        out["ln1_post"] = _norm_spec(cfg)
        out["ln2_post"] = _norm_spec(cfg)
    return out


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "embed": embed_specs(cfg),
        "layers": _stack(decoder_layer_specs(cfg), cfg.n_layers),
        "final_norm": _norm_spec(cfg),
    }
    if cfg.family == "hybrid" and cfg.shared_attn_every:
        specs["shared_attn"] = {
            "ln": _norm_spec(cfg),
            "attn": attention_specs(cfg),
            "ln2": _norm_spec(cfg),
            "mlp": mlp_specs(cfg),
        }
    return specs


# ---------------------------------------------------------------------------
# Per-layer flags.
# ---------------------------------------------------------------------------
def layer_flags(cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    ln = cfg.n_layers
    if cfg.local_global:
        # even layers local sliding window, odd layers global (gemma2)
        window = np.where(np.arange(ln) % 2 == 0, cfg.local_window,
                          BIG_WINDOW)
    elif cfg.sliding_window:
        window = np.full(ln, cfg.sliding_window)
    else:
        window = np.full(ln, BIG_WINDOW)
    flags = {"window": jnp.asarray(window, jnp.int32)}
    if cfg.family == "hybrid" and cfg.shared_attn_every:
        apply = np.arange(ln) % cfg.shared_attn_every == 0
        slot = np.cumsum(apply) - 1
        flags["shared_apply"] = jnp.asarray(apply)
        flags["shared_slot"] = jnp.asarray(np.maximum(slot, 0), jnp.int32)
    return flags


def n_shared_apps(cfg: ModelConfig) -> int:
    if cfg.family != "hybrid" or not cfg.shared_attn_every:
        return 0
    return int(np.sum(np.arange(cfg.n_layers) % cfg.shared_attn_every == 0))


# ---------------------------------------------------------------------------
# KV / state caches.
# ---------------------------------------------------------------------------
def cache_capacity(max_seq: int) -> int:
    """Positions a cache holds for ``max_seq``: whole tiles of CACHE_TILE,
    so that the TPU can lay the sequence axis minor (``update_cache``)."""
    return -(-max_seq // CACHE_TILE) * CACHE_TILE


def init_cache_specs(cfg: ModelConfig, batch: int, max_seq: int):
    """ShapeDtypeStruct tree for the decode cache.

    Every cache that grows by a position holds ``cache_capacity(max_seq)``
    positions; the attention caches are [L, B, KV/pack, S, pack*D]
    (``layers.kv_cache_row``).
    """
    dt = jnp.dtype(cfg.dtype)
    max_seq = cache_capacity(max_seq)
    ln = cfg.n_layers
    sds = jax.ShapeDtypeStruct
    cache: Dict[str, Any] = {"pos": sds((), jnp.int32)}
    if cfg.family == "ssm":
        d_in, h, n = ssm_mod.ssm_dims(cfg)
        cache["state"] = sds((ln, batch, h, cfg.ssm_head_dim, n), jnp.float32)
        cache["conv"] = sds((ln, batch, cfg.ssm_conv - 1, d_in + 2 * n), dt)
        return cache
    if cfg.family == "hybrid":
        d_in, h, n = ssm_mod.ssm_dims(cfg)
        cache["state"] = sds((ln, batch, h, cfg.ssm_head_dim, n), jnp.float32)
        cache["conv"] = sds((ln, batch, cfg.ssm_conv - 1, d_in + 2 * n), dt)
        kv = (n_shared_apps(cfg), batch) + kv_cache_row(cfg, max_seq)
        cache["shared_k"] = sds(kv, dt)
        cache["shared_v"] = sds(kv, dt)
        return cache
    if cfg.mla:
        cache["latent"] = sds((ln, batch, max_seq, cfg.kv_lora_rank), dt)
        cache["k_rope"] = sds((ln, batch, max_seq, cfg.rope_head_dim), dt)
        return cache
    cache["k"] = sds((ln, batch) + kv_cache_row(cfg, max_seq), dt)
    cache["v"] = sds((ln, batch) + kv_cache_row(cfg, max_seq), dt)
    if cfg.family == "encdec":
        hd = cfg.head_dim_
        cache["cross_k"] = sds((ln, batch, cfg.n_audio_frames,
                                cfg.n_kv_heads, hd), dt)
        cache["cross_v"] = sds((ln, batch, cfg.n_audio_frames,
                                cfg.n_kv_heads, hd), dt)
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        init_cache_specs(cfg, batch, max_seq))


# ---------------------------------------------------------------------------
# Forward (train / prefill).
# ---------------------------------------------------------------------------
def _dense_layer(x, lp, cfg, positions, window, mrope_sections, attn_fn):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla:
        a, _ = mla_attention(h, lp["attn"], cfg, positions)
    else:
        a, _ = attention(h, lp["attn"], cfg, positions, window=window,
                         mrope_sections=mrope_sections, attn_fn=attn_fn)
    # name the post-collective activations so the save_collectives remat
    # policy keeps them: the backward then never re-runs the TP all-reduces
    # / MoE all-to-alls of the forward (§Perf A6/B4)
    a = checkpoint_name(a, "attn_out")
    if cfg.post_norms:
        a = rms_norm(a, lp["ln1_post"], cfg.norm_eps)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        b, s, d = h.shape
        m, aux = moe_mod.moe_mlp(h.reshape(b * s, d), lp["moe"], cfg)
        m = m.reshape(b, s, d)
        if cfg.moe_dense_residual:
            m = m + mlp(h, lp["mlp"], cfg)
    else:
        m, aux = mlp(h, lp["mlp"], cfg), None
    m = checkpoint_name(m, "mlp_out")
    if cfg.post_norms:
        m = rms_norm(m, lp["ln2_post"], cfg.norm_eps)
    return x + m, aux


def forward(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            attn_fn=None):
    """Full-sequence forward -> logits [B,S,V] (train & prefill path)."""
    tokens = batch["tokens"]
    bsz, seq = tokens.shape
    x = embed(tokens, params["embed"], cfg)
    if cfg.family == "vlm":
        ve = batch["vision_embeds"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, ve, (0, 0, 0))
        positions = batch["positions"]
        mrope_sections = MROPE_SECTIONS if cfg.mrope else None
    else:
        positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None],
                                     (bsz, seq))
        mrope_sections = None
    flags = layer_flags(cfg)
    shared = params.get("shared_attn")
    aux_sum = jnp.zeros((), jnp.float32)

    def body(x, scanned):
        lp = scanned["params"]
        # sequence parallelism: the residual lives seq-sharded on the model
        # axis between layers; TP matmuls gather/reduce-scatter around it
        x = constrain(x, [BATCH, MODEL if cfg.seq_parallel else None, None])
        aux_local = jnp.zeros((), jnp.float32)
        if cfg.family in ("ssm", "hybrid"):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            y, _ = ssm_mod.ssm_forward(h, lp["ssm"], cfg)
            x = x + y
            if cfg.family == "hybrid":
                def with_attn(x):
                    h2 = rms_norm(x, shared["ln"], cfg.norm_eps)
                    a, _ = attention(h2, shared["attn"], cfg, positions,
                                     window=scanned["window"])
                    x = x + a
                    h2 = rms_norm(x, shared["ln2"], cfg.norm_eps)
                    return x + mlp(h2, shared["mlp"], cfg)
                x = jax.lax.cond(scanned["shared_apply"], with_attn,
                                 lambda x: x, x)
        else:
            x, aux = _dense_layer(x, lp, cfg, positions, scanned["window"],
                                  mrope_sections, attn_fn)
            if aux is not None:
                aux_local = (aux["load_balance"]
                             + 1e-3 * aux["router_z"]).astype(jnp.float32)
        return x, aux_local

    if cfg.remat:
        policy = None
        if cfg.remat_policy == "save_collectives":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out", "moe_dispatch")
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)

    scanned = {"params": params["layers"], "window": flags["window"]}
    if "shared_apply" in flags:
        scanned["shared_apply"] = flags["shared_apply"]
    x, aux_per_layer = jax.lax.scan(body, x, scanned)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params["embed"], cfg)
    return logits, jnp.sum(aux_per_layer)


# ---------------------------------------------------------------------------
# Decode (one new token against a cache).
# ---------------------------------------------------------------------------
def decode_step(params, cache, tokens, cfg: ModelConfig,
                positions_override=None, attn_fn=None):
    """tokens [B, 1] -> (logits [B,1,V], new cache).

    ``attn_fn`` reaches the attention layer with the same contract as the
    forward path: a fused kernel that takes over when attention runs
    without a KV cache.  The cached decode path keeps the reference
    attention (today's flash hook is full-sequence only), so threading the
    hook here is signature parity with ``forward`` — callers configure one
    kernel once for both paths.
    """
    bsz = tokens.shape[0]
    pos = cache["pos"]
    x = embed(tokens, params["embed"], cfg)
    positions = (positions_override if positions_override is not None
                 else jnp.full((bsz, 1), pos, jnp.int32))
    flags = layer_flags(cfg)
    shared = params.get("shared_attn")

    # every cache rides in the scan's carry and is written in place at the
    # scanned layer's index: the caches that grow by a position (k/v, the
    # MLA latent, the shared k/v) take a one-position write, the SSM state
    # and conv window a whole layer's
    if cfg.family in ("ssm", "hybrid"):
        scanned = {"params": params["layers"],
                   "layer": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
        kv = {}
        if cfg.family == "hybrid":
            scanned.update(shared_apply=flags["shared_apply"],
                           shared_slot=flags["shared_slot"],
                           window=flags["window"])
            kv = {"k": cache["shared_k"], "v": cache["shared_v"]}

        def body(carry, sc):
            x, state, conv, kv = carry
            i = sc["layer"]
            # the scan runs under ``ssm_state``, which names the conv
            # window's read and write-back here; the mixer is named ``ssm``
            # (its state update ``ssm_state`` inside it), the hybrid's
            # shared block ``attn``
            cv = jax.lax.dynamic_index_in_dim(conv, i, keepdims=False)
            with jax.named_scope("ssm"):
                h = rms_norm(x, sc["params"]["ln1"], cfg.norm_eps)
                y, (state, cv) = ssm_mod.ssm_forward(
                    h, sc["params"]["ssm"], cfg, state=state, layer=i,
                    conv_state=cv)
                x = x + y
            conv = jax.lax.dynamic_update_index_in_dim(conv, cv, i, 0)
            if cfg.family == "hybrid":
                def with_attn(args):
                    x, kv = args
                    h2 = rms_norm(x, shared["ln"], cfg.norm_eps)
                    a, kv = attention(h2, shared["attn"], cfg, positions,
                                      kv_cache=kv, cache_pos=pos,
                                      cache_layer=sc["shared_slot"],
                                      window=sc["window"], attn_fn=attn_fn)
                    x = x + a
                    h2 = rms_norm(x, shared["ln2"], cfg.norm_eps)
                    return x + mlp(h2, shared["mlp"], cfg), kv

                with jax.named_scope("attn"):
                    x, kv = jax.lax.cond(sc["shared_apply"], with_attn,
                                         lambda a: a, (x, kv))
            return (x, state, conv, kv), None

        with jax.named_scope("ssm_state"):
            (x, state, conv, kv), _ = jax.lax.scan(
                body, (x, cache["state"], cache["conv"], kv), scanned)
        new_cache = dict(cache, pos=pos + 1, state=state, conv=conv)
        if cfg.family == "hybrid":
            new_cache.update(shared_k=kv["k"], shared_v=kv["v"])
    else:
        scanned = {"params": params["layers"], "window": flags["window"],
                   "layer": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
        names = ("latent", "k_rope") if cfg.mla else ("k", "v")
        kv = {n: cache[n] for n in names}
        if cfg.family == "encdec":
            scanned.update(cross_k=cache["cross_k"], cross_v=cache["cross_v"])

        def body(carry, sc):
            x, kv = carry
            lp = sc["params"]
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            if cfg.mla:
                a, kv = mla_attention(h, lp["attn"], cfg, positions,
                                      kv_cache=kv, cache_pos=pos,
                                      cache_layer=sc["layer"])
            else:
                a, kv = attention(h, lp["attn"], cfg, positions,
                                  kv_cache=kv, cache_pos=pos,
                                  cache_layer=sc["layer"],
                                  window=sc["window"], attn_fn=attn_fn)
            if cfg.post_norms:
                a = rms_norm(a, lp["ln1_post"], cfg.norm_eps)
            x = x + a
            if cfg.family == "encdec":
                h = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
                a, _ = attention(h, lp["cross"], cfg, positions,
                                 kv_override=(sc["cross_k"], sc["cross_v"]))
                x = x + a
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.n_experts:
                b2, s2, d2 = h.shape
                m, _ = moe_mod.moe_mlp(h.reshape(b2 * s2, d2), lp["moe"], cfg)
                m = m.reshape(b2, s2, d2)
                if cfg.moe_dense_residual:
                    m = m + mlp(h, lp["mlp"], cfg)
            else:
                m = mlp(h, lp["mlp"], cfg)
            if cfg.post_norms:
                m = rms_norm(m, lp["ln2_post"], cfg.norm_eps)
            return (x + m, kv), None

        (x, kv), _ = jax.lax.scan(body, (x, kv), scanned)
        new_cache = dict(cache, pos=pos + 1, **kv)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params["embed"], cfg)
    return logits, new_cache
