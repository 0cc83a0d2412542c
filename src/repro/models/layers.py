"""Shared layer library: norms, RoPE/M-RoPE, GQA/SWA/softcap attention, MLA,
gated MLPs, embeddings.  Spec-first parameter construction so the same code
path builds real arrays (smoke tests), ShapeDtypeStructs (dry-run) and
sharding specs (launcher).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig
from repro.parallel.act_sharding import BATCH, MODEL, constrain

# The TPU's lane width.  An attention cache packs KV heads into rows of this
# many lanes (``kv_pack``) and holds a whole number of tiles of this many
# positions (``transformer.cache_capacity``).
CACHE_TILE = 128


# ---------------------------------------------------------------------------
# Spec-first parameters.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]     # logical axis name per dim
    dtype: str = "bfloat16"
    init: str = "normal"                # normal | zeros | ones

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def init_from_specs(specs, key, scale: float = 0.02):
    """Materialize a PSpec tree into arrays."""
    leaves, treedef = jax.tree.flatten(specs,
                                       is_leaf=lambda x: isinstance(x, PSpec))
    keys = jax.random.split(key, len(leaves))
    vals = []
    for k, sp in zip(keys, leaves):
        dt = jnp.dtype(sp.dtype)
        if sp.init == "zeros":
            vals.append(jnp.zeros(sp.shape, dt))
        elif sp.init == "ones":
            vals.append(jnp.ones(sp.shape, dt))
        else:
            fan_in = sp.shape[-2] if len(sp.shape) >= 2 else sp.shape[-1]
            std = scale if fan_in <= 0 else min(scale, 1.0 / math.sqrt(fan_in))
            vals.append((jax.random.normal(k, sp.shape, jnp.float32)
                         * std).astype(dt))
    return jax.tree.unflatten(treedef, vals)


def sds_from_specs(specs):
    """ShapeDtypeStruct tree (dry-run: no allocation)."""
    return jax.tree.map(
        lambda sp: jax.ShapeDtypeStruct(sp.shape, jnp.dtype(sp.dtype)),
        specs, is_leaf=lambda x: isinstance(x, PSpec))


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------
def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE.
# ---------------------------------------------------------------------------
def _rope_angles(positions, head_dim: int, theta: float):
    """positions [..., S] -> (sin, cos) [..., S, head_dim/2] in f32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, positions, theta: float = 10000.0,
               mrope_sections: Optional[Tuple[int, ...]] = None):
    """x [B, S, H, D]; positions [B, S] or [3, B, S] (M-RoPE)."""
    d = x.shape[-1]
    half = d // 2
    if mrope_sections is not None:
        # Qwen2-VL M-RoPE: frequency bands split across (t, h, w) position ids
        sin_parts, cos_parts = [], []
        for i, sec in enumerate(mrope_sections):
            s, c = _rope_angles(positions[i], d, theta)
            sin_parts.append(s)
            cos_parts.append(c)
        idx = []
        off = 0
        for i, sec in enumerate(mrope_sections):
            idx.append((i, off, off + sec))
            off += sec
        sin = jnp.concatenate([sin_parts[i][..., a:b] for i, a, b in idx], -1)
        cos = jnp.concatenate([cos_parts[i][..., a:b] for i, a, b in idx], -1)
    else:
        sin, cos = _rope_angles(positions, d, theta)
    sin = sin[:, :, None, :]      # [B, S, 1, half]
    cos = cos[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA + sliding window + logit softcap).
# ---------------------------------------------------------------------------
def attention_specs(cfg: ModelConfig) -> Dict[str, PSpec]:
    d, hd = cfg.d_model, cfg.head_dim_
    h, kv = cfg.n_heads, cfg.n_kv_heads
    dt = cfg.dtype
    out = {
        "wq": PSpec((d, h, hd), ("embed", "q_heads", "head_dim"), dt),
        "wk": PSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wv": PSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wo": PSpec((h, hd, d), ("q_heads", "head_dim", "embed"), dt),
    }
    if cfg.qkv_bias:
        out["bq"] = PSpec((h, hd), ("q_heads", "head_dim"), dt, init="zeros")
        out["bk"] = PSpec((kv, hd), ("kv_heads", "head_dim"), dt, init="zeros")
        out["bv"] = PSpec((kv, hd), ("kv_heads", "head_dim"), dt, init="zeros")
    return out


def _softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def _attn_weights(scores, cfg: ModelConfig, q_pos, k_pos, window,
                  causal=True):
    """scores q.k [B,KV,G,Sq,Sk] (f32) -> probs [B,KV,G,Sq,Sk] (f32)."""
    # scores [B,KV,G,Sq,Sk]: model axis on kv-heads, else q-groups, else Sq
    scores = constrain(scores, [BATCH, MODEL, MODEL, MODEL, None])
    scores = scores / math.sqrt(cfg.head_dim_)
    scores = _softcap(scores, cfg.attn_logit_softcap)
    mask = k_pos[:, None, :] <= q_pos[:, :, None] if causal else \
        (k_pos[:, None, :] < jnp.iinfo(jnp.int32).max)        # [B,Sq,Sk]
    if window is not None:
        mask &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = constrain(probs, [BATCH, MODEL, MODEL, MODEL, None])
    return probs     # [B,KV,G,Sq,Sk]


def kv_pack(cfg: ModelConfig) -> int:
    """KV heads that share one row of the decode cache: as many whole heads
    as fit in CACHE_TILE lanes, a divisor of the KV heads."""
    return math.gcd(cfg.n_kv_heads, max(1, CACHE_TILE // cfg.head_dim_))


def kv_cache_row(cfg: ModelConfig, capacity: int) -> Tuple[int, int, int]:
    """One layer's attention cache after its batch axis, as ``attention``
    stores it: [KV/pack, capacity, pack*D]."""
    pack = kv_pack(cfg)
    return (cfg.n_kv_heads // pack, capacity, pack * cfg.head_dim_)


def _pack_heads(x, pack: int):
    """[B,S,KV,D] -> [B,KV/pack,S,pack*D]: ``pack`` heads side by side."""
    b, s, kv, d = x.shape
    return x.reshape(b, s, kv // pack, pack * d).swapaxes(1, 2)


def _packed_scores(q, k, pack: int):
    """q [B,Sq,H,D] against a packed k [B,P,Sk,pack*D] -> [B,KV,G,Sq,Sk].

    Each query head is laid in the lanes of its own KV head and is zero in
    the others of its pack (block-diagonal), so one dot over whole rows
    scores every head of a pack: ``pack`` times the FLOPs, and the cache
    is read as it is stored.
    """
    b, sq, h, d = q.shape
    p = k.shape[1]
    g = h // (p * pack)
    eye = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
    qb = (q.reshape(b, sq, p, pack, g, 1, d) * eye).reshape(
        b, sq, p, pack * g, pack * d)
    scores = jnp.einsum("bqpmj,bpsj->bpmqs", qb, k).astype(jnp.float32)
    return scores.reshape(b, p * pack, g, sq, k.shape[2])


def _packed_values(probs, v, pack: int):
    """probs [B,KV,G,Sq,Sk] over a packed v [B,P,Sk,pack*D] -> [B,Sq,H,D]:
    each head keeps the lanes of its own KV head."""
    b, kv, g, sq, sk = probs.shape
    p = v.shape[1]
    d = v.shape[-1] // pack
    out = jnp.einsum("bpmqs,bpsj->bqpmj",
                     probs.reshape(b, p, pack * g, sq, sk), v)
    out = jnp.einsum("bqpigjd,ij->bqpigd",
                     out.reshape(b, sq, p, pack, g, pack, d),
                     jnp.eye(pack, dtype=out.dtype))
    return out.reshape(b, sq, kv * g, d)


def update_cache(cache, new, layer, pos, seq_axis):
    """Write ``new`` into the stacked cache [L, ...] of every layer at
    position ``pos`` of ``seq_axis`` (an axis of one layer's slice) in layer
    ``layer``, and read back that layer's slice.  Returns (cache, slice).

    Only the new positions are written: inside the decode step's layer
    scan, where the stacked cache is a carry, the update is in place.  The
    cache keeps the layout the TPU gives it by default, which the attention
    dots read without a copy: its last axis minor when that fills whole
    128-lane tiles, else its sequence axis.  Unpinned, the loop would take
    the layout of the small update and relayout the whole cache on entry,
    on exit and for each layer's read (v5e compile).
    """
    at = [layer] + [0] * new.ndim
    at[1 + seq_axis] = pos
    cache = jax.lax.dynamic_update_slice(cache, new[None].astype(cache.dtype),
                                         at)
    order = list(range(cache.ndim))
    if cache.shape[-1] % CACHE_TILE:
        order.append(order.pop(1 + seq_axis))
    cache = with_layout_constraint(cache, Layout(tuple(order)))
    return cache, jax.lax.dynamic_index_in_dim(cache, layer, 0, False)


def _cache_positions(b, smax, cache_pos, s):
    """Key positions [B,Smax] of a cache filled up to ``cache_pos + s``;
    the unfilled tail reads as int32 max, past every causal mask."""
    k_pos = jnp.broadcast_to(jnp.arange(smax, dtype=jnp.int32)[None],
                             (b, smax))
    return jnp.where(k_pos <= (cache_pos + s - 1), k_pos,
                     jnp.iinfo(jnp.int32).max)


def attention(x, p, cfg: ModelConfig, positions, *, kv_cache=None,
              cache_pos=None, cache_layer=None, window=None,
              mrope_sections=None, kv_override=None, attn_fn=None,
              causal=True):
    """Returns (out [B,S,d], new_kv_cache).

    ``kv_cache``: the stacked cache of every layer, dict(k=[L,B,P,Smax,W],
    v=...) with ``kv_pack(cfg)`` KV heads side by side in each row of W
    lanes (P = KV / pack, W = pack * D).  This layer's keys and values are
    written at ``cache_pos`` in layer ``cache_layer`` (``update_cache``),
    attention reads that layer's slice as stored, and the whole cache is
    returned.
    ``kv_override``: precomputed (k, v) for cross-attention.
    ``attn_fn``: optional fused kernel (flash attention) for the
    no-cache full-sequence path.
    """
    with jax.named_scope("attn"):
        b, s, d_model = x.shape
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if "bq" in p:
            q = q + p["bq"]
        if kv_override is not None:
            k, v = kv_override
            k_pos = jnp.broadcast_to(
                jnp.arange(k.shape[1], dtype=jnp.int32)[None], (b, k.shape[1]))
            q = apply_rope(q, positions, cfg.rope_theta, mrope_sections)
            new_cache = kv_cache
            # cross attention: no causal mask
            kvh = k.shape[2]
            group = cfg.n_heads // kvh
            qg = q.reshape(b, s, kvh, group, q.shape[-1])
            scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
            scores = scores / math.sqrt(q.shape[-1])
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
            out = out.reshape(b, s, cfg.n_heads, -1)
            return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache

        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if "bk" in p:
            k = k + p["bk"]
            v = v + p["bv"]
        q = apply_rope(q, positions, cfg.rope_theta, mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, mrope_sections)

        q_pos = positions if positions.ndim == 2 else positions[0]
        if kv_cache is not None:
            pack = kv_pack(cfg)
            with jax.named_scope("kv_cache"):
                ck, k_full = update_cache(kv_cache["k"], _pack_heads(k, pack),
                                          cache_layer, cache_pos, 2)
                cv, v_full = update_cache(kv_cache["v"], _pack_heads(v, pack),
                                          cache_layer, cache_pos, 2)
                k_pos = _cache_positions(b, k_full.shape[2], cache_pos, s)
            probs = _attn_weights(_packed_scores(q, k_full, pack), cfg, q_pos,
                                  k_pos, window, causal=causal)
            out = _packed_values(probs.astype(x.dtype), v_full, pack)
            return (jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
                    {"k": ck, "v": cv})

        if attn_fn is not None:
            out = attn_fn(q, k, v, cfg)
            return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), None
        kvh = k.shape[2]
        qg = q.reshape(b, s, kvh, cfg.n_heads // kvh, q.shape[-1])
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
        k_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        probs = _attn_weights(scores, cfg, q_pos, k_pos, window,
                              causal=causal)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(x.dtype), v)
        out = out.reshape(b, s, cfg.n_heads, -1)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), None


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3 / DeepSeek style).
# ---------------------------------------------------------------------------
def mla_specs(cfg: ModelConfig) -> Dict[str, PSpec]:
    d = cfg.d_model
    h = cfg.n_heads
    dt = cfg.dtype
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd = cfg.nope_head_dim, cfg.rope_head_dim
    return {
        "wq_a": PSpec((d, qr), ("embed", "lora"), dt),
        "q_norm": PSpec((qr,), ("lora",), "float32", init="zeros"),
        "wq_b": PSpec((qr, h, nd + rd), ("lora", "q_heads", "head_dim"), dt),
        "wkv_a": PSpec((d, kvr + rd), ("embed", "lora"), dt),
        "kv_norm": PSpec((kvr,), ("lora",), "float32", init="zeros"),
        "wk_b": PSpec((kvr, h, nd), ("lora", "q_heads", "head_dim"), dt),
        "wv_b": PSpec((kvr, h, nd), ("lora", "q_heads", "head_dim"), dt),
        "wo": PSpec((h, nd, d), ("q_heads", "head_dim", "embed"), dt),
    }


def mla_attention(x, p, cfg: ModelConfig, positions, *, kv_cache=None,
                  cache_pos=None, cache_layer=None):
    """MLA: the cache stores the compressed latent + rope key only.

    ``kv_cache``: the stacked dict(latent=[L,B,Smax,R], k_rope=[L,B,Smax,rd]),
    written and read as ``attention``'s.
    """
    b, s, _ = x.shape
    h = cfg.n_heads
    nd, rd, kvr = cfg.nope_head_dim, cfg.rope_head_dim, cfg.kv_lora_rank

    q_lat = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"])
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ p["wkv_a"]                                   # [B,S,kvr+rd]
    latent = rms_norm(kv[..., :kvr], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, kvr:], positions, cfg.rope_theta)
    k_rope = k_rope[..., 0, :]                            # [B,S,rd]

    if kv_cache is not None:
        with jax.named_scope("kv_cache"):
            lat_c, latent_full = update_cache(kv_cache["latent"], latent,
                                              cache_layer, cache_pos, 1)
            kr_c, k_rope_full = update_cache(kv_cache["k_rope"], k_rope,
                                             cache_layer, cache_pos, 1)
            new_cache = {"latent": lat_c, "k_rope": kr_c}
            k_pos = _cache_positions(b, latent_full.shape[1], cache_pos, s)
    else:
        new_cache = None
        latent_full, k_rope_full = latent, k_rope
        k_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    k_nope = jnp.einsum("bsr,rhk->bshk", latent_full, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", latent_full, p["wv_b"])

    scale = 1.0 / math.sqrt(nd + rd)
    sc = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
          + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope_full)
          ).astype(jnp.float32) * scale
    # [B,H,Sq,Sk]: model axis on heads if divisible, else query seq
    sc = constrain(sc, [BATCH, MODEL, MODEL, None])
    causal = k_pos[:, None, :] <= positions[:, :, None]
    sc = jnp.where(causal[:, None, :, :], sc, -1e30)
    probs = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
    probs = constrain(probs, [BATCH, MODEL, MODEL, None])
    out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# MLP.
# ---------------------------------------------------------------------------
def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None,
              gated: Optional[bool] = None) -> Dict[str, PSpec]:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.dtype
    gated = cfg.mlp_gated if gated is None else gated
    out = {
        "w_in": PSpec((d, ff), ("embed", "ff"), dt),
        "w_out": PSpec((ff, d), ("ff", "embed"), dt),
    }
    if gated:
        out["w_gate"] = PSpec((d, ff), ("embed", "ff"), dt)
    return out


def mlp(x, p, cfg: ModelConfig):
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    with jax.named_scope("mlp"):
        if "w_gate" in p:
            h = act(x @ p["w_gate"]) * (x @ p["w_in"])
        else:
            h = act(x @ p["w_in"])
        h = constrain(h, [BATCH] + [None] * (h.ndim - 2) + [MODEL])
        return h @ p["w_out"]


# ---------------------------------------------------------------------------
# Embeddings / LM head.
# ---------------------------------------------------------------------------
def embed_specs(cfg: ModelConfig) -> Dict[str, PSpec]:
    out = {"tok": PSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                        cfg.dtype)}
    if not cfg.tie_embeddings:
        out["head"] = PSpec((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                            cfg.dtype)
    return out


def embed(tokens, p, cfg: ModelConfig):
    with jax.named_scope("embed"):
        x = jnp.take(p["tok"], tokens, axis=0)
        if cfg.family == "encdec" or cfg.mlp_act == "gelu":
            x = x * math.sqrt(cfg.d_model)   # gemma/whisper-style scaling
        return x.astype(cfg.activation_dtype)


def lm_head(x, p, cfg: ModelConfig):
    with jax.named_scope("lm_head"):
        w = p["tok"].T if cfg.tie_embeddings else p["head"]
        logits = x @ w.astype(x.dtype)
        logits = constrain(logits, [BATCH, None, MODEL])
        return _softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
