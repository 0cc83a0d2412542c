"""Pipeline parallelism: GPipe-style microbatch pipeline over a "stage"
mesh axis using explicit ``ppermute`` hops (shard_map).

The model is split into S stages with stacked per-stage parameters; M
microbatches flow through the classic (M + S - 1)-tick schedule, each tick
computing one stage body and shifting activations one hop along the ICI
ring.  Output equals the sequential composition of the stages — asserted in
``tests/test_distributed.py``.

This complements the DP/FSDP/TP/EP axes of ``parallel.sharding``: at
1000+-node scale, PP over pods bounds the TP domain to one pod while the
pipeline hops cross DCN with only [microbatch, d_model]-sized tensors.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, stage_params, microbatches,
                     mesh: Mesh, axis: str = "stage"):
    """Run ``microbatches`` through S pipeline stages.

    stage_fn:      (params_one_stage, x) -> y  (same shape as x)
    stage_params:  pytree stacked on a leading [S, ...] axis
    microbatches:  [M, mb, ...] array
    Returns [M, mb, ...] outputs equal to applying all stages in order.
    """
    n_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    ticks = m + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(params_local, xs_local):
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs_local.shape[1:]
        outputs = jnp.zeros((m,) + mb_shape, xs_local.dtype)

        def tick(t, carry):
            held, outputs = carry
            # compute this stage's body on what it holds (valid when the
            # wavefront has reached it: stage <= t < stage + M)
            valid = (t >= stage) & (t < stage + m)
            y = stage_fn(params_local, held)
            y = jnp.where(valid, y, held)
            # last stage records its finished microbatch
            mb_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            record = valid & (stage == n_stages - 1)
            outputs = jax.lax.dynamic_update_slice(
                outputs,
                jnp.where(record, y, jax.lax.dynamic_slice(
                    outputs, (mb_idx,) + (0,) * len(mb_shape),
                    (1,) + mb_shape)[0])[None],
                (mb_idx,) + (0,) * len(mb_shape))
            # shift activations one hop down the ring
            shifted = jax.lax.ppermute(y, axis, perm)
            # stage 0 injects the next microbatch
            nxt = jnp.clip(t + 1, 0, m - 1)
            inject = jax.lax.dynamic_slice(
                xs_local, (nxt,) + (0,) * len(mb_shape),
                (1,) + mb_shape)[0]
            held = jnp.where(stage == 0, inject, shifted)
            return held, outputs

        held0 = xs_local[0]
        # the carry becomes stage-varying after the first ppermute
        held0 = jax.lax.pcast(held0, (axis,), to="varying")
        outputs = jax.lax.pcast(outputs, (axis,), to="varying")
        _, outputs = jax.lax.fori_loop(0, ticks, tick, (held0, outputs))
        return outputs[None]      # [1, M, ...] per stage

    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(P(axis), P()),   # params sharded by stage
                       out_specs=P(axis))
    outs = fn(stage_params, microbatches)         # [S, M, ...]
    return outs[-1]
