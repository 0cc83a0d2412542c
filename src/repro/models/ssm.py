"""Mamba2 — state-space duality (SSD) layer, arXiv:2405.21060.

Training/prefill uses the chunked SSD algorithm (quadratic within a chunk,
linear state recurrence across chunks); decode is the O(1) stateful
recurrence, whose state update is the Pallas kernel ``repro.kernels.
ssm_decode`` (in place on the stacked state).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import PSpec, rms_norm
from repro.parallel.act_sharding import BATCH, MODEL, constrain


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    d_in = cfg.ssm_expand * cfg.d_model
    n_heads = d_in // cfg.ssm_head_dim
    return d_in, n_heads, cfg.ssm_state


def ssm_specs(cfg: ModelConfig) -> Dict[str, PSpec]:
    d = cfg.d_model
    d_in, h, n = ssm_dims(cfg)
    conv_ch = d_in + 2 * n                     # x, B, C go through the conv
    dt = cfg.dtype
    return {
        "in_proj": PSpec((d, 2 * d_in + 2 * n + h), ("embed", "ssm_inner"), dt),
        "conv_w": PSpec((cfg.ssm_conv, conv_ch), ("conv", "ssm_inner"), dt),
        "conv_b": PSpec((conv_ch,), ("ssm_inner",), dt, init="zeros"),
        "a_log": PSpec((h,), ("ssm_heads",), "float32", init="zeros"),
        "d_skip": PSpec((h,), ("ssm_heads",), "float32", init="ones"),
        "dt_bias": PSpec((h,), ("ssm_heads",), "float32", init="zeros"),
        "norm_w": PSpec((d_in,), ("ssm_inner",), "float32", init="zeros"),
        "out_proj": PSpec((d_in, d), ("ssm_inner", "embed"), dt),
    }


def _causal_conv(x, w, b, state: Optional[jnp.ndarray] = None,
                 n_valid=None):
    """Depthwise causal conv1d.  x [B,S,C], w [K,C].  With ``state``
    ([B,K-1,C]) performs the streaming update and returns (y, new_state):
    the K-1 inputs that end at the ``n_valid``-th of x (the last where
    None)."""
    k = w.shape[0]
    if state is not None:
        with jax.named_scope("ssm_state"):
            window = jnp.concatenate([state, x], axis=1)   # [B, K-1+S, C]
            if n_valid is None:
                new_state = window[:, -(k - 1):]
            else:
                new_state = jax.lax.dynamic_slice_in_dim(window, n_valid,
                                                         k - 1, axis=1)
    else:
        window = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        new_state = None
    y = sum(window[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return y + b, new_state


def _segsum(a):
    """Stable segment-sum: a [..., L] -> [..., L, L] lower-tri cumulative."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    idx = jnp.arange(l)
    mask = idx[:, None] >= idx[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked_ref(x, dt, a, b_mat, c_mat, chunk: int,
                    h0: Optional[jnp.ndarray] = None):
    """Reference chunked SSD.

    x  [B,S,H,P]  inputs (already dt-scaled NOT applied; we apply here)
    dt [B,S,H]    softplus'd step sizes
    a  [H]        negative decay rates
    b_mat, c_mat [B,S,N]
    h0 [B,H,P,N]  the state before the first position (zero where None)
    Returns (y [B,S,H,P], last_state [B,H,P,N]); the states are read out
    in float32.
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    nc = s // chunk
    xc = x.reshape(bsz, nc, chunk, h, p)
    xc = constrain(xc, [BATCH, None, None, MODEL, None])
    dtc = dt.reshape(bsz, nc, chunk, h)
    dtc = constrain(dtc, [BATCH, None, None, MODEL])
    bc = b_mat.reshape(bsz, nc, chunk, n)
    cc = c_mat.reshape(bsz, nc, chunk, n)

    da = dtc * a[None, None, None, :]                     # [B,NC,L,H]
    da_cs = jnp.cumsum(da, axis=2)                        # [B,NC,L,H]

    # intra-chunk (quadratic in chunk length); heads on the model axis
    lmat = jnp.exp(_segsum(jnp.moveaxis(da, 2, 3)))       # [B,NC,H,L,L]
    lmat = constrain(lmat, [BATCH, None, MODEL, None, None])
    y_diag = jnp.einsum("bcln,bcsn,bchls,bcsh,bcshp->bclhp",
                        cc, bc, lmat, dtc, xc)
    y_diag = constrain(y_diag, [BATCH, None, None, MODEL, None])

    # chunk -> state contribution
    decay_to_end = jnp.exp(da_cs[:, :, -1:, :] - da_cs)   # [B,NC,L,H]
    states = jnp.einsum("bcln,bclh,bclh,bclhp->bchpn",
                        bc, decay_to_end, dtc, xc)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(da_cs[:, :, -1, :])             # [B,NC,H]

    def scan_fn(carry, inp):
        st, dec = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry

    init = (jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None
            else h0.astype(jnp.float32))
    last, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0).astype(jnp.float32),
         jnp.moveaxis(chunk_decay, 1, 0).astype(jnp.float32)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)         # [B,NC,H,P,N]

    # inter-chunk output
    state_decay = jnp.exp(da_cs)                          # [B,NC,L,H]
    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp",
                       cc.astype(jnp.float32), prev_states, state_decay)
    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y, last


@jax.named_scope("ssm")
def ssm_forward(x, p, cfg: ModelConfig, *, state=None, layer=None,
                conv_state=None, n_valid=None):
    """Full Mamba2 block.  ``state`` given -> decode mode: ``state`` is the
    stacked per-layer state [L,B,H,P,N], ``layer`` this block's index in it
    and ``conv_state`` this block's conv window; returns (y, (state with the
    layer rewritten, conv_state)).  At S = 1 the state takes the O(1)
    recurrence; at S > 1 (a prompt's launch) the chunked SSD starts from
    it.  ``n_valid``: the valid positions, the first ones; the others get a
    step of zero, so they neither decay nor feed the state.

    Named ``ssm``; in decode mode the ops that read or rewrite the state
    (the recurrence with its read-out, the conv window's shift) are named
    ``ssm_state`` inside it."""
    bsz, s, _ = x.shape
    d_in, h, n = ssm_dims(cfg)
    hd = cfg.ssm_head_dim

    z_x_bc_dt = x @ p["in_proj"]
    z = z_x_bc_dt[..., :d_in]
    xbc = z_x_bc_dt[..., d_in:2 * d_in + 2 * n]
    dt_raw = z_x_bc_dt[..., 2 * d_in + 2 * n:]

    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state,
                                 n_valid)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(bsz, s, h, hd)
    b_mat = xbc[..., d_in:d_in + n]
    c_mat = xbc[..., d_in + n:]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    a = -jnp.exp(p["a_log"])

    if state is not None and s == 1:
        # imported here: Pallas takes seconds to import, which only the SSM
        # decode path should pay
        from repro.kernels import ops as kernel_ops

        # O(1) decode recurrence (S == 1 expected): one read and one write
        # of the layer's state, in the stacked state's own buffer
        xs1 = xs[:, 0].astype(jnp.float32)                 # [B,H,P]
        dt1 = dt[:, 0]                                     # [B,H]
        with jax.named_scope("ssm_state"):
            da = jnp.exp(dt1 * a[None, :])                 # [B,H]
            new_state, y = kernel_ops.ssm_decode_update(
                state, layer, da, dt1[:, :, None] * xs1,
                b_mat[:, 0].astype(jnp.float32),
                c_mat[:, 0].astype(jnp.float32))
        y = y + p["d_skip"][None, :, None] * xs1
        y = y.reshape(bsz, 1, d_in).astype(x.dtype)
        carry = (new_state, new_conv)
    else:
        h0 = None
        if state is not None:
            with jax.named_scope("ssm_state"):
                h0 = jax.lax.dynamic_index_in_dim(state, layer,
                                                  keepdims=False)
        if n_valid is not None:
            dt = jnp.where(jnp.arange(s)[None, :, None] < n_valid, dt, 0.0)
        y4, last = ssd_chunked_ref(xs, dt, a, b_mat, c_mat,
                                   min(cfg.ssm_chunk, s), h0=h0)
        y4 = y4 + p["d_skip"][None, None, :, None] * xs.astype(y4.dtype)
        y = y4.reshape(bsz, s, d_in).astype(x.dtype)
        if state is not None:
            with jax.named_scope("ssm_state"):
                last = jax.lax.dynamic_update_index_in_dim(state, last,
                                                           layer, 0)
        carry = (last, new_conv)

    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    return y @ p["out_proj"], carry


def init_ssm_state(cfg: ModelConfig, batch: int):
    d_in, h, n = ssm_dims(cfg)
    conv_ch = d_in + 2 * n
    return (jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
            jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch),
                      jnp.dtype(cfg.dtype)))
