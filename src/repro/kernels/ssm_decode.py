"""Mamba2 decode state update — Pallas TPU.

One decode step of the SSD recurrence for one layer of the stacked state:

    h' = h * exp(dt * a) + (dt * x) (x) B        y = h' . C

The stacked state ``[L, B, H, P, N]`` float32 is the kernel's input and,
through ``input_output_aliases``, its output: the layer index is prefetched
into SMEM and picks the layer's blocks in the ``index_map``, so each block
of that layer is read once and written back over itself, and every other
layer is left as it is.  Under a jit that donates the state this is the
whole of the step's traffic on it: one read and one write.

The kernel reads the state as the row-major array it is on the TPU when N
fills whole 128-lane tiles (mamba2's 128).  For a smaller N the TPU's
default layout puts another axis minor (zamba2's N = 64: the heads), and a
Pallas operand would cost a relayout of the whole stacked state on the
step's entry and another on its exit; compiled for such a state,
``ssm_decode_update`` leaves the layer's update to XLA, in the carry (v5e
compile: no copy of the state).

Blocks are ``(bb, bh, P, N)`` of one layer, chosen from the shapes: ``bh``
is 8 heads where the heads come in eights (else all of them), ``bb`` the
most requests whose block, as tiled in VMEM, stays within
``BLOCK_BYTES``.  The decay arrives broadcast along P and B, C as rows
``[B, 1, N]``, so every operand's block ends in whole array dims or in
eights of heads and whole P.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 2 * 2 ** 20          # one state block: 2 MiB in, 2 MiB out
LANES = 128


def _ssm_decode_kernel(layer_ref, h_ref, da_ref, dtx_ref, b_ref, c_ref,
                       h_out_ref, y_ref):
    """Blocks: h, h_out [bb,bh,P,N]; da, dtx, y [bb,bh,P]; b, c [bb,1,N]."""
    del layer_ref                       # consumed by the index maps
    new = (h_ref[...] * da_ref[...][..., None]
           + dtx_ref[...][..., None] * b_ref[...][:, :, None, :])
    h_out_ref[...] = new
    y_ref[...] = jnp.sum(new * c_ref[...][:, :, None, :], axis=-1)


def vmem_bytes(p: int, n: int) -> int:
    """Bytes of one head's [p, n] float32 state in VMEM, whose (8, 128)
    tiles pad P to eights and N to 128 lanes."""
    return -(-p // 8) * 8 * -(-n // LANES) * LANES * 4


def blocks(b: int, h: int, p: int, n: int):
    """(bb, bh) for a state of ``b`` requests and ``h`` heads of [p, n]."""
    bh = 8 if h % 8 == 0 else h
    row = bh * vmem_bytes(p, n)
    bb = max((d for d in range(1, b + 1)
              if b % d == 0 and d * row <= BLOCK_BYTES), default=1)
    return bb, bh


def ssm_decode_update(state, layer, da, dtx, b_mat, c_mat, *,
                      interpret: bool = False):
    """state [L,B,H,P,N] f32; layer () int; da [B,H]; dtx [B,H,P];
    b_mat, c_mat [B,N] (all f32) -> (state with layer ``layer`` advanced
    by one step, written in place, y [B,H,P])."""
    _, b, h, p, n = state.shape
    if n % LANES and not interpret:
        return _update_in_xla(state, layer, da, dtx, b_mat, c_mat)
    bb, bh = blocks(b, h, p, n)
    da = jnp.broadcast_to(da[:, :, None], (b, h, p))
    state_spec = pl.BlockSpec((None, bb, bh, p, n),
                              lambda i, j, lyr: (lyr[0], i, j, 0, 0))
    head_spec = pl.BlockSpec((bb, bh, p), lambda i, j, lyr: (i, j, 0))
    row_spec = pl.BlockSpec((bb, 1, n), lambda i, j, lyr: (i, 0, 0))
    return pl.pallas_call(
        _ssm_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // bb, h // bh),
            in_specs=[state_spec, head_spec, head_spec, row_spec, row_spec],
            out_specs=[state_spec, head_spec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, h, p), jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state, da, dtx,
      b_mat[:, None], c_mat[:, None])


def _update_in_xla(state, layer, da, dtx, b_mat, c_mat):
    """The same step as XLA ops on the layer's slice of the stacked state,
    for a state the TPU does not lay out row-major."""
    h = jax.lax.dynamic_index_in_dim(state, layer, keepdims=False)
    h = h * da[:, :, None, None] + dtx[..., None] * b_mat[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h, c_mat)
    return jax.lax.dynamic_update_index_in_dim(state, h, layer, 0), y
