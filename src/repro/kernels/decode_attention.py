"""GQA decode attention — Pallas TPU kernel.

One program per (batch, kv-head) sweeps the cache along the innermost grid
axis: the query group [G, D] stays resident in VMEM, the KV cache streams
through VMEM in [BK, D] blocks (heads laid out ahead of (seq, D)), invalid
(beyond ``length``) positions are masked, and the running softmax lives in
f32 VMEM scratch.  The valid lengths are prefetched into SMEM.  This is the
HBM-bandwidth-bound hot loop of serving (decode_32k / long_500k shapes):
arithmetic intensity ~G MACs per cache byte, so the tiling goal is purely
streaming efficiency.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, block_k: int):
    d = q_ref.shape[-1]
    b_idx, k_idx = pl.program_id(0), pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b_idx]
    q = q_ref[0, 0].astype(jnp.float32) / math.sqrt(d)          # [G, D]
    k = k_ref[0, 0]                                             # [BK, D]
    v = v_ref[0, 0]
    scores = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # [G, BK]
    k_pos = (k_idx * block_k
             + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
    scores = jnp.where(k_pos < length, scores, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     block_k: int = DEFAULT_BLOCK_K,
                     interpret: bool = False):
    """q [B,H,D]; caches [B,S,KV,D]; lengths [B] -> [B,H,D]."""
    b, h, d = q.shape
    _, s, kvh, _ = k_cache.shape
    g = h // kvh
    block_k = min(block_k, s)
    # Non-divisible tails: zero-pad the cache to a block multiple.  Padded
    # positions sit at k_pos >= s >= length, so the existing validity mask
    # already excludes them; the divisible path is untouched (bitwise).
    if s % block_k != 0:
        s_pad = -(-s // block_k) * block_k
        widths = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
        s = s_pad
    qg = q.reshape(b, kvh, g, d)
    kc = k_cache.transpose(0, 2, 1, 3)                          # [B,KV,S,D]
    vc = v_cache.transpose(0, 2, 1, 3)
    q_spec = pl.BlockSpec((1, 1, g, d), lambda b_, kv, j, lens: (b_, kv, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, kv, j, lens: (b_, kv, j, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, s // block_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((g, d), jnp.float32),
                            pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kc, vc)
    return out.reshape(b, h, d)
