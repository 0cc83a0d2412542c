"""Mixture-of-Experts layer — sort-based (MegaBlocks-style) dispatch.

Dense one-hot dispatch ([T, E, C] einsums) is memory-infeasible at
128-expert/1M-token scale, so tokens are sorted by expert id, packed into an
[E, C, d] buffer (capacity-dropped), run through batched expert matmuls and
combined back through the inverse permutation.  Under GSPMD with experts
sharded on the "model" axis this lowers to the expected all-to-all pattern.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import PSpec
from repro.parallel.act_sharding import BATCH, MODEL, constrain


def moe_specs(cfg: ModelConfig) -> Dict[str, PSpec]:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.dtype
    return {
        "router": PSpec((d, e), ("embed", "experts"), "float32"),
        "w_in": PSpec((e, d, ff), ("experts", "embed", "ff"), dt),
        "w_gate": PSpec((e, d, ff), ("experts", "embed", "ff"), dt),
        "w_out": PSpec((e, ff, d), ("experts", "ff", "embed"), dt),
    }


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(math.ceil(cfg.moe_capacity_factor * n_tokens * cfg.moe_top_k
                      / cfg.n_experts))
    return max((c + 7) // 8 * 8, 8)


def moe_mlp(x, p, cfg: ModelConfig, valid=None):
    """x [T, d] -> [T, d] plus aux losses dict.  ``valid`` [T]: the tokens
    routed (all where None); the others take no slot and give zeros."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    c = capacity(cfg, t)
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu

    logits = (x.astype(jnp.float32) @ p["router"])            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                      # [T, k]
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1)                                 # [T*k]
    if valid is not None:
        # a pad's choices sort after every expert's and are dropped
        flat_e = jnp.where(jnp.repeat(valid, k), flat_e, e)
    order = jnp.argsort(flat_e)                               # sort by expert
    sorted_e = flat_e[order]
    tok_idx = order // k

    counts = jnp.bincount(flat_e, length=e)                   # [E]
    starts = jnp.cumsum(counts) - counts
    pos_in_grp = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_grp < c
    if valid is not None:
        keep &= sorted_e < e
    dest = jnp.where(keep, sorted_e * c + pos_in_grp, e * c)  # drop row

    if cfg.moe_dispatch == "index":
        # §Perf "moe-index": scatter only 4-byte token indices into the
        # slot map, then GATHER the d-wide rows — GSPMD lowers the sharded
        # gather as the dispatch all-to-all instead of materializing a
        # replicated [E*C, d] scatter operand.
        slot_tok = jnp.full((e * c + 1,), t, jnp.int32).at[dest].set(tok_idx)
        x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
        xe = x_pad[slot_tok[:-1]].reshape(e, c, d)
    else:
        buf = jnp.zeros((e * c + 1, d), x.dtype).at[dest].set(x[tok_idx])
        xe = buf[:-1].reshape(e, c, d)
    # expert-parallel: the [E, C, d] buffer lives expert-sharded; getting
    # tokens into it is the all-to-all under GSPMD
    xe = constrain(xe, [MODEL, None, None])
    # checkpointable under the save_collectives policy: the backward then
    # reuses the dispatched buffer instead of re-running the all-to-all
    from jax.ad_checkpoint import checkpoint_name
    xe = checkpoint_name(xe, "moe_dispatch")

    h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    h = constrain(h, [MODEL, None, None])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"]).reshape(e * c, d)

    if cfg.moe_dispatch == "index":
        # combine mirrors dispatch: scatter expert rows back to token-major
        # order (model-sharded updates -> data-sharded buffer == all-to-all),
        # instead of gathering from the expert-sharded buffer.
        slot_orig = jnp.full((e * c + 1,), t * k, jnp.int32).at[dest].set(
            order.astype(jnp.int32))
        ycomb = jnp.zeros((t * k, d), x.dtype).at[slot_orig[:-1]].set(
            ye, mode="drop")
        y_flat = ycomb.reshape(t, k, d)
    else:
        y_sorted = ye[jnp.where(keep, dest, 0)] * keep[:, None].astype(x.dtype)
        inv = jnp.argsort(order)
        y_flat = y_sorted[inv].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", y_flat, gate.astype(x.dtype))

    # aux: load-balancing loss (Switch-style) + router z-loss
    me = probs.mean(axis=0)                                   # [E]
    ce = (counts / max(t * k, 1)).astype(jnp.float32)
    aux = {
        "load_balance": e * jnp.sum(me * ce),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "drop_fraction": 1.0 - keep.mean(),
    }
    return y, aux
