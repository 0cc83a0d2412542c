"""Pure-jnp oracles for every Pallas kernel (allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int | None = None,
                        softcap: float | None = None):
    """q,k,v [B,S,H,D] (same kv heads) -> [B,S,H,D]; plain softmax."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """GQA decode: q [B,H,D]; caches [B,S,KV,D]; lengths [B] valid lens."""
    b, s, kvh, d = k_cache.shape
    h = q.shape[1]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    valid = jnp.arange(s)[None, :] < lengths[:, None]          # [B,S]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v_cache)
    return out.reshape(b, h, d)


def ssd_chunk_ref(x, dt, a, b_mat, c_mat):
    """Intra-chunk SSD (one chunk): x [B,L,H,P], dt [B,L,H], a [H],
    b_mat/c_mat [B,L,N] -> (y [B,L,H,P], state [B,H,P,N]).

    y[t]     = sum_{s<=t} C[t]·B[s] exp(sum_{r in (s,t]} dt[r]a) dt[s] x[s]
    state    = sum_s exp(sum_{r in (s,L)} dt[r]a) dt[s] B[s] x[s]
    """
    bsz, l, h, p = x.shape
    da = dt * a[None, None, :]                          # [B,L,H]
    da_cs = jnp.cumsum(da, axis=1)
    diff = da_cs[:, :, None, :] - da_cs[:, None, :, :]  # [B,T,S,H]
    idx = jnp.arange(l)
    mask = idx[:, None] >= idx[None, :]
    decay = jnp.where(mask[None, :, :, None], jnp.exp(diff), 0.0)
    y = jnp.einsum("btn,bsn,btsh,bsh,bshp->bthp",
                   c_mat, b_mat, decay, dt, x)
    decay_end = jnp.exp(da_cs[:, -1:, :] - da_cs)       # [B,L,H]
    state = jnp.einsum("bsn,bsh,bsh,bshp->bhpn",
                       b_mat, decay_end, dt, x)
    return y, state


def ssm_decode_ref(state, da, dtx, b_mat, c_mat):
    """One Mamba2 decode step of one layer: state [B,H,P,N], da [B,H],
    dtx [B,H,P] (dt * x), b_mat/c_mat [B,N] -> (new state, y [B,H,P]).

    new = state * da + dtx (x) B        y = new . C
    """
    new = state * da[:, :, None, None] + jnp.einsum("bhp,bn->bhpn", dtx,
                                                    b_mat)
    return new, jnp.einsum("bhpn,bn->bhp", new, c_mat)
