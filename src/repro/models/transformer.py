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
    if cfg.family in ("ssm", "hybrid"):
        return {"ln1": _norm_spec(cfg), "ssm": ssm_mod.ssm_specs(cfg)}
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
    if cfg.family in ("ssm", "hybrid"):
        d_in, h, n = ssm_mod.ssm_dims(cfg)
        cache["state"] = sds((ln, batch, h, cfg.ssm_head_dim, n), jnp.float32)
        cache["conv"] = sds((ln, batch, cfg.ssm_conv - 1, d_in + 2 * n), dt)
        if cfg.family == "hybrid":
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
# Decoder blocks: one function per kind, scanned by ``forward``,
# ``decode_step`` and ``encdec.forward`` (the attention block by
# ``encdec.encode`` too).  Given a cache slot, a block writes it and
# returns it.
# ---------------------------------------------------------------------------
def _attn_layer(x, lp, cfg, positions, *, window=None, mrope_sections=None,
                attn_fn=None, kv=None, cache_pos=None, layer=None,
                cross=None, causal=True, n=None):
    """Self attention (MLA where ``cfg.mla``), then cross attention over
    ``cross=(k, v)`` where given, then the dense or MoE MLP, each behind
    its norm and residual.

    ``kv``: the stacked caches of every layer, written at ``cache_pos`` in
    layer ``layer``; ``attn_fn`` takes over attention without them.
    ``n``: the valid positions of each row, the first ones; the MoE routes
    only those, so pads take no expert's capacity.
    Returns (x, MoE aux or None, kv).
    """
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla:
        a, kv = mla_attention(h, lp["attn"], cfg, positions, kv_cache=kv,
                              cache_pos=cache_pos, cache_layer=layer)
    else:
        a, kv = attention(h, lp["attn"], cfg, positions, kv_cache=kv,
                          cache_pos=cache_pos, cache_layer=layer,
                          window=window, mrope_sections=mrope_sections,
                          attn_fn=attn_fn, causal=causal)
    # name the post-collective activations so the save_collectives remat
    # policy keeps them: the backward then never re-runs the TP all-reduces
    # / MoE all-to-alls of the forward (§Perf A6/B4)
    a = checkpoint_name(a, "attn_out")
    if cfg.post_norms:
        a = rms_norm(a, lp["ln1_post"], cfg.norm_eps)
    x = x + a
    if cross is not None:
        h = rms_norm(x, lp["ln_cross"], cfg.norm_eps)
        a, _ = attention(h, lp["cross"], cfg, positions, kv_override=cross)
        x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        b, s, d = h.shape
        m, aux = moe_mod.moe_mlp(
            h.reshape(b * s, d), lp["moe"], cfg,
            valid=None if n is None else jnp.tile(jnp.arange(s) < n, b))
        m = m.reshape(b, s, d)
        if cfg.moe_dense_residual:
            m = m + mlp(h, lp["mlp"], cfg)
    else:
        m, aux = mlp(h, lp["mlp"], cfg), None
    m = checkpoint_name(m, "mlp_out")
    if cfg.post_norms:
        m = rms_norm(m, lp["ln2_post"], cfg.norm_eps)
    return x + m, aux, kv


def _ssm_layer(x, lp, cfg, *, state=None, layer=None, conv=None, n=None):
    """The Mamba-2 mixer behind its norm and residual, named ``ssm``.

    ``state``: the stacked state [L,B,H,P,N], rewritten at layer ``layer``;
    ``conv``: this layer's conv window; ``n``: the valid positions, the
    first ones, which alone advance them.  Returns (x, (state, conv)).
    """
    with jax.named_scope("ssm"):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        y, carry = ssm_mod.ssm_forward(h, lp["ssm"], cfg, state=state,
                                       layer=layer, conv_state=conv,
                                       n_valid=n)
        return x + y, carry


def _shared_block(x, sp, cfg, positions, apply, window, *, kv=None,
                  cache_pos=None, slot=None):
    """The hybrid's shared attention and MLP block where ``apply`` holds,
    named ``attn``.  ``kv``: the shared k/v caches, written at ``cache_pos``
    in slot ``slot``.  Returns (x, kv)."""
    def with_attn(args):
        x, kv = args
        h = rms_norm(x, sp["ln"], cfg.norm_eps)
        a, kv = attention(h, sp["attn"], cfg, positions, kv_cache=kv,
                          cache_pos=cache_pos, cache_layer=slot,
                          window=window)
        x = x + a
        h = rms_norm(x, sp["ln2"], cfg.norm_eps)
        return x + mlp(h, sp["mlp"], cfg), kv

    with jax.named_scope("attn"):
        return jax.lax.cond(apply, with_attn, lambda a: a, (x, kv))


# ---------------------------------------------------------------------------
# Forward (train / prefill).
# ---------------------------------------------------------------------------
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

    def body(x, scanned):
        lp = scanned["params"]
        # sequence parallelism: the residual lives seq-sharded on the model
        # axis between layers; TP matmuls gather/reduce-scatter around it
        x = constrain(x, [BATCH, MODEL if cfg.seq_parallel else None, None])
        aux = None
        if cfg.family in ("ssm", "hybrid"):
            x, _ = _ssm_layer(x, lp, cfg)
            if cfg.family == "hybrid":
                x, _ = _shared_block(x, shared, cfg, positions,
                                     scanned["shared_apply"],
                                     scanned["window"])
        else:
            x, aux, _ = _attn_layer(x, lp, cfg, positions,
                                    window=scanned["window"],
                                    mrope_sections=mrope_sections,
                                    attn_fn=attn_fn)
        if aux is None:
            return x, jnp.zeros((), jnp.float32)
        return x, (aux["load_balance"]
                   + 1e-3 * aux["router_z"]).astype(jnp.float32)

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
# Decode (new tokens against a cache).
# ---------------------------------------------------------------------------
def decode_step(params, cache, tokens, cfg: ModelConfig, n=None):
    """tokens [B, T] at positions ``pos .. pos+T-1`` -> (logits [B,1,V] at
    the last valid position, new cache).

    ``n``: how many of the T tokens are valid, given where T > 1; the cache
    advances by ``n``.  Pad positions write keys past the valid ones, which
    only pad queries see and later launches overwrite, and give the SSM a
    step of zero, so they neither decay nor feed its state.  At T = 1 this
    is the one-token decode step.
    """
    bsz, t = tokens.shape
    pos = cache["pos"]
    x = embed(tokens, params["embed"], cfg)
    positions = pos + jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                                       (bsz, t))
    flags = layer_flags(cfg)
    shared = params.get("shared_attn")

    # every cache rides in the scan's carry and is written in place at the
    # scanned layer's index: the caches that grow by a position (k/v, the
    # MLA latent, the shared k/v) take a T-position write, the SSM state
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
            # window's read and write-back here
            cv = jax.lax.dynamic_index_in_dim(conv, i, keepdims=False)
            x, (state, cv) = _ssm_layer(x, sc["params"], cfg, state=state,
                                        layer=i, conv=cv, n=n)
            conv = jax.lax.dynamic_update_index_in_dim(conv, cv, i, 0)
            if cfg.family == "hybrid":
                x, kv = _shared_block(x, shared, cfg, positions,
                                      sc["shared_apply"], sc["window"],
                                      kv=kv, cache_pos=pos,
                                      slot=sc["shared_slot"])
            return (x, state, conv, kv), None

        with jax.named_scope("ssm_state"):
            (x, state, conv, kv), _ = jax.lax.scan(
                body, (x, cache["state"], cache["conv"], kv), scanned)
        new_cache = dict(cache, state=state, conv=conv)
        if cfg.family == "hybrid":
            new_cache.update(shared_k=kv["k"], shared_v=kv["v"])
    else:
        scanned = {"params": params["layers"], "window": flags["window"],
                   "layer": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
        names = ("latent", "k_rope") if cfg.mla else ("k", "v")
        kv = {name: cache[name] for name in names}
        if cfg.family == "encdec":
            scanned.update(cross_k=cache["cross_k"], cross_v=cache["cross_v"])

        def body(carry, sc):
            x, kv = carry
            cross = ((sc["cross_k"], sc["cross_v"]) if cfg.family == "encdec"
                     else None)
            x, _, kv = _attn_layer(x, sc["params"], cfg, positions,
                                   window=sc["window"], kv=kv, cache_pos=pos,
                                   layer=sc["layer"], cross=cross, n=n)
            return (x, kv), None

        (x, kv), _ = jax.lax.scan(body, (x, kv), scanned)
        new_cache = dict(cache, **kv)
    new_cache["pos"] = pos + (t if n is None else n)

    # only the last valid position goes through the head
    if n is not None:
        x = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params["embed"], cfg)
    return logits, new_cache
