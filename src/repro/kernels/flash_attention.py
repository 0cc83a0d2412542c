"""Flash attention — Pallas TPU kernel.

Blockwise online-softmax attention: the [Sq, Sk] score matrix never
materializes in HBM (the 224 GiB/device buffer of the naive path).  Tiling
is TPU-native: heads are laid out ahead of (seq, D), so every block's last
two dims are (rows, D); a query block of 512 rows stays resident in VMEM
while the innermost grid axis streams K/V blocks of 512 through VMEM,
MXU-aligned [BQ, D] x [D, BK] partial products, with running (max, sum)
rescaling in f32 VMEM scratch.  No block spans the whole sequence, so VMEM
use does not grow with context length.

Supports causal masking, sliding windows (gemma2/danube) and logit softcap
(gemma2).  Same-kv-head layout: GQA callers broadcast kv heads in the ops
wrapper (cheap: D is small) or pass grouped heads.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _k_blocks(q_idx, *, causal: bool, block_q: int, block_k: int, n_k: int):
    """K blocks a query block reads: all of them, or up to the diagonal."""
    if not causal:
        return n_k
    return jnp.minimum(((q_idx + 1) * block_q + block_k - 1) // block_k, n_k)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_k: int,
                  kv_len: Optional[int] = None):
    """One (batch, head, q-block, k-block) step of the online softmax."""
    bq = q_ref.shape[2]
    q_idx, k_idx = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal early exit: only K blocks that intersect the mask
    upper = _k_blocks(q_idx, causal=causal, block_q=bq, block_k=block_k,
                      n_k=n_k)

    @pl.when(k_idx < upper)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # [BQ, D]
        k = k_ref[0, 0]                                         # [BK, D]
        v = v_ref[0, 0]
        q_pos = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        scores = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [BQ, BK]
        if softcap is not None:
            scores = jnp.tanh(scores / softcap) * softcap
        k_pos = (k_idx * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
        mask = jnp.ones((bq, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        if kv_len is not None:          # padded tail: positions >= kv_len
            mask &= k_pos < kv_len
        scores = jnp.where(mask, scores, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)         # [BQ,1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(k_idx == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q,k,v [B,S,H,D] (kv heads already expanded to H) -> [B,S,H,D]."""
    b, s, h, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    # Non-divisible tails: pad S up to a common block multiple and mask the
    # padded kv positions in-kernel.  The divisible path takes no pad branch
    # and builds the exact same jaxpr as before (bitwise-preserving).
    tile = math.lcm(block_q, block_k)
    s_pad = s if s % tile == 0 else -(-s // tile) * tile
    kv_len = None
    if s_pad != s:
        widths = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        kv_len = s
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))     # [B,H,S,D]
    n_k = s_pad // block_k
    kernel = functools.partial(
        _flash_kernel, sm_scale=1.0 / math.sqrt(d), causal=causal,
        window=window, softcap=softcap, block_k=block_k, kv_len=kv_len)

    def kv_map(b_, h_, i, j):
        # past the diagonal, repeat the last block read: no new copy is issued
        last = _k_blocks(i, causal=causal, block_q=block_q, block_k=block_k,
                         n_k=n_k) - 1
        return b_, h_, jnp.minimum(j, last), 0

    out = pl.pallas_call(
        kernel,
        grid=(b, h, s_pad // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    out = out.transpose(0, 2, 1, 3)
    if s_pad != s:
        out = out[:, :s]
    return out
