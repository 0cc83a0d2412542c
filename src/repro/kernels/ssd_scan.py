"""Mamba2 SSD intra-chunk kernel — Pallas TPU.

One program per (batch·chunk, head): computes the quadratic intra-chunk
output and the chunk's contribution to the inter-chunk state in VMEM.
Heads are laid out ahead of (L, P), so every block's last two dims are
whole array dims.  The [L, L] decay matrix (L = 256 chunk) is built once
per head in f32 VREG/VMEM — ~256 KiB, well under VMEM — and both
contractions are MXU-shaped ([L, L] x [L, P] and [L, N]^T x [L, P]).  The
cumulative decay and the linear inter-chunk recurrence stay in XLA (tiny,
bandwidth-trivial).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The decay weights and chunk states are float32 by design.  A TPU's default
# matmul precision rounds float32 operands to bfloat16 (one MXU pass), which
# puts the output off the exact result by more than the bfloat16 tolerance
# at mamba2-2.7b widths, so every float32 contraction here asks for full
# precision.
_F32 = jax.lax.Precision.HIGHEST


def _ssd_chunk_kernel(x_ref, dt_ref, cs_row_ref, cs_col_ref, wend_ref,
                      b_ref, c_ref, y_ref, st_ref):
    """Blocks: x [1,1,L,P]; dt, cs_row [1,1,1,L]; cs_col, wend [1,1,L,1];
    b/c [1,L,N]; outputs y [1,1,L,P], st [1,1,P,N].

    ``cs`` is the within-chunk cumulative ``dt * a`` (row and column
    copies, so no in-kernel transpose) and ``wend[s] = exp(cs[-1] - cs[s])
    * dt[s]`` the decay-to-end weight; both come from the wrapper.
    """
    l = x_ref.shape[2]
    x = x_ref[0, 0].astype(jnp.float32)                 # [L, P]
    dt = dt_ref[0, 0].astype(jnp.float32)               # [1, L]
    bm = b_ref[0].astype(jnp.float32)                   # [L, N]
    cm = c_ref[0].astype(jnp.float32)                   # [L, N]

    # decay[t, s] = exp(cs[t] - cs[s]) for s <= t
    diff = cs_col_ref[0, 0] - cs_row_ref[0, 0]          # [L, L]
    ti = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    decay = jnp.where(ti >= si, jnp.exp(diff), 0.0)

    # scores[t, s] = (C[t]·B[s]) * decay[t, s] * dt[s]
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             precision=_F32,
                             preferred_element_type=jnp.float32)  # [L, L]
    w = cb * decay * dt
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            precision=_F32,
                            preferred_element_type=jnp.float32)   # [L, P]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # chunk state: sum_s exp(cs[-1]-cs[s]) dt[s] B[s] x[s] -> [P, N]
    st = jax.lax.dot_general(x, bm * wend_ref[0, 0],
                             (((0,), (0,)), ((), ())),
                             precision=_F32,
                             preferred_element_type=jnp.float32)  # [P, N]
    st_ref[0, 0] = st


def ssd_chunk(x, dt, a, b_mat, c_mat, *, interpret: bool = False):
    """Intra-chunk SSD over independent chunks.

    x [B,L,H,P], dt [B,L,H], a [H], b_mat/c_mat [B,L,N]
    -> (y [B,L,H,P] f32, states [B,H,P,N] f32)
    """
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]
    # heads ahead of (L, P); the scan over dt * a runs here in XLA
    dt_h = dt.astype(jnp.float32).transpose(0, 2, 1)    # [B,H,L]
    cs = jnp.cumsum(dt_h * a.astype(jnp.float32)[None, :, None], axis=-1)
    wend = jnp.exp(cs[..., -1:] - cs) * dt_h
    row = lambda v: v[:, :, None, :]                    # [B,H,1,L]
    col = lambda v: v[:, :, :, None]                    # [B,H,L,1]
    per_head = lambda *blk: pl.BlockSpec((1, 1) + blk,
                                         lambda b_, h_: (b_, h_, 0, 0))
    shared = pl.BlockSpec((1, l, n), lambda b_, h_: (b_, 0, 0))
    y, st = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(bsz, h),
        in_specs=[per_head(l, p), per_head(1, l), per_head(1, l),
                  per_head(l, 1), per_head(l, 1), shared, shared],
        out_specs=[per_head(l, p), per_head(p, n)],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), row(dt_h), row(cs), col(cs), col(wend),
      b_mat, c_mat)
    return y.transpose(0, 2, 1, 3), st


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int, h0=None, *,
                interpret: bool = False):
    """Drop-in for ``repro.models.ssm.ssd_chunked_ref`` using the kernel for
    the intra-chunk part; inter-chunk recurrence in XLA."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    # Non-divisible tails: zero-pad the sequence to a chunk multiple.  Every
    # padded row carries dt = 0, so it contributes exp(0) = 1 decay and a
    # zero dt-weighted update — the inter-chunk state and all real rows are
    # exact, and the padded y rows are sliced off.  The divisible path takes
    # no pad branch (bitwise-preserving).
    s_out = s
    if s % chunk != 0:
        s = -(-s // chunk) * chunk
        pz = s - s_out
        x = jnp.pad(x, [(0, 0), (0, pz), (0, 0), (0, 0)])
        dt = jnp.pad(dt, [(0, 0), (0, pz), (0, 0)])
        b_mat = jnp.pad(b_mat, [(0, 0), (0, pz), (0, 0)])
        c_mat = jnp.pad(c_mat, [(0, 0), (0, pz), (0, 0)])
    nc = s // chunk
    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = b_mat.reshape(bsz, nc, chunk, n)
    cc = c_mat.reshape(bsz, nc, chunk, n)

    # fold chunks into the batch dim for one big kernel launch
    xf = xc.reshape(bsz * nc, chunk, h, p)
    df = dtc.reshape(bsz * nc, chunk, h)
    bf = bc.reshape(bsz * nc, chunk, n)
    cf = cc.reshape(bsz * nc, chunk, n)
    y_diag, states = ssd_chunk(xf, df, a, bf, cf, interpret=interpret)
    y_diag = y_diag.reshape(bsz, nc, chunk, h, p)
    states = states.reshape(bsz, nc, h, p, n)

    da = dtc.astype(jnp.float32) * a[None, None, None, :]
    da_cs = jnp.cumsum(da, axis=2)
    chunk_decay = jnp.exp(da_cs[:, :, -1, :])

    def scan_fn(carry, inp):
        st, dec = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry

    init = (jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None
            else h0.astype(jnp.float32))
    last, prev = jax.lax.scan(
        scan_fn, init, (jnp.moveaxis(states, 1, 0),
                        jnp.moveaxis(chunk_decay, 1, 0)))
    prev = jnp.moveaxis(prev, 0, 1)                     # [B,NC,H,P,N]
    state_decay = jnp.exp(da_cs)                        # [B,NC,L,H]
    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp",
                       cc.astype(jnp.float32), prev, state_decay,
                       precision=_F32)
    y = (y_diag + y_off).reshape(bsz, s, h, p)
    if s != s_out:
        y = y[:, :s_out]
    return y, last
