"""Logical-axis sharding rules (MaxText-style), divisibility-aware.

Parameters carry logical axis names (``PSpec.axes``); these rules map them
to mesh axes.  A rule is skipped when the dimension is not divisible by the
mesh-axis extent or the mesh axis is already consumed by an earlier dim —
so odd configs (whisper's 51865 vocab, qwen2's 14 heads on a 16-way model
axis) degrade to replication instead of failing, and GSPMD handles the rest.

Mesh axes: ``pod`` (DCN), ``data`` (DP/FSDP), ``model`` (TP/EP).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import PSpec

# logical axis -> preferred mesh axes, in priority order.  "fsdp" expands to
# the data axis (and pod axis in multi-pod meshes) for parameter sharding.
LOGICAL_RULES: Dict[str, Tuple[str, ...]] = {
    "vocab": ("model",),
    "ff": ("model",),
    "q_heads": ("model",),
    "kv_heads": ("model",),
    "experts": ("model",),
    "ssm_inner": ("model",),
    "embed": ("fsdp",),
    "head_dim": (),
    "lora": (),
    "layers": (),
    "enc_layers": (),
    "conv": (),
    "ssm_heads": (),
}

# activation / batch rules
BATCH_AXES = ("pod", "data")


def abstract_mesh(axis_sizes: Sequence[int],
                  axis_names: Sequence[str]) -> "jax.sharding.AbstractMesh":
    """Device-free mesh for rule evaluation.

    The sharding rules only consume ``mesh.shape`` / ``mesh.axis_names``.
    """
    sizes = tuple(int(s) for s in axis_sizes)
    names = tuple(axis_names)
    if len(sizes) != len(names):
        raise ValueError(f"{len(sizes)} axis sizes vs {len(names)} names")
    return jax.sharding.AbstractMesh(sizes, names)


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[n] for n in names]))


def spec_to_pspec(spec: PSpec, mesh: Mesh, *, fsdp: bool = True) -> P:
    """PartitionSpec for one parameter spec under the rules."""
    out = []
    used: set = set()
    for dim, axis in zip(spec.shape, spec.axes):
        assigned: Optional[Tuple[str, ...]] = None
        for rule_axis in LOGICAL_RULES.get(axis, ()):
            mesh_axes: Tuple[str, ...]
            if rule_axis == "fsdp":
                if not fsdp:
                    continue
                mesh_axes = fsdp_axes(mesh)
            else:
                mesh_axes = (rule_axis,) if rule_axis in mesh.axis_names else ()
            if not mesh_axes or any(m in used for m in mesh_axes):
                continue
            if dim % _axis_size(mesh, mesh_axes) != 0:
                continue
            assigned = mesh_axes
            break
        if assigned:
            used.update(assigned)
            out.append(assigned if len(assigned) > 1 else assigned[0])
        else:
            out.append(None)
    return P(*out)


def param_shardings(specs, mesh: Mesh, *, fsdp: bool = True):
    """NamedSharding tree matching a PSpec tree."""
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, spec_to_pspec(sp, mesh, fsdp=fsdp)),
        specs, is_leaf=lambda x: isinstance(x, PSpec))


def batch_pspec(mesh: Mesh, batch_size: int, ndim: int,
                batch_dim: int = 0) -> P:
    """Shard the batch dim over (pod, data), falling back when indivisible."""
    axes = [a for a in BATCH_AXES if a in mesh.axis_names]
    while axes and batch_size % _axis_size(mesh, axes) != 0:
        axes.pop(0)
    spec = [None] * ndim
    if axes:
        spec[batch_dim] = tuple(axes) if len(axes) > 1 else axes[0]
    return P(*spec)


def input_shardings(input_sds: Dict[str, jax.ShapeDtypeStruct], mesh: Mesh,
                    batch_dim_overrides: Optional[Dict[str, int]] = None):
    """Attach batch sharding to model-input ShapeDtypeStructs."""
    out = {}
    overrides = batch_dim_overrides or {}
    for name, sds in input_sds.items():
        bdim = overrides.get(name, 1 if name == "positions" else 0)
        b = sds.shape[bdim] if sds.shape else 1
        ns = NamedSharding(mesh, batch_pspec(mesh, b, len(sds.shape), bdim))
        out[name] = jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=ns)
    return out


# the decode cache's attention entries (``models.transformer.init_cache_specs``)
KV_CACHE_KEYS = ("k", "v", "shared_k", "shared_v")


def cache_shardings(cache_sds, mesh: Mesh):
    """Decode-cache shardings.

    Rule: shard batch over (pod, data); for the attention caches
    [L, B, KV/pack, S, pack*D] (``models.layers.attention``) prefer the
    kv-head rows on "model" when divisible, else shard the sequence dim on
    "model" (sequence-parallel attention over the cache).  Other 5-D state
    [L, B, H, P, N] prefers dim 3, then dim 2.
    """
    model_n = mesh.shape.get("model", 1)

    def one(path, sds):
        shape = sds.shape
        spec = [None] * len(shape)
        if len(shape) == 0:
            return jax.ShapeDtypeStruct(shape, sds.dtype,
                                        sharding=NamedSharding(mesh, P()))
        if len(shape) >= 2:
            bp = batch_pspec(mesh, shape[1], len(shape), 1)
            spec = list(bp)
        if len(shape) == 5 and model_n > 1:
            name = path[-1].key if path else None
            order = (2, 3) if name in KV_CACHE_KEYS else (3, 2)
            for dim in order:
                if shape[dim] % model_n == 0:
                    spec[dim] = "model"
                    break
        if len(shape) == 4 and shape[2] % model_n == 0 and model_n > 1:
            # [L, B, S, latent] (MLA) or [L, B, H, ...]: shard dim 2
            spec[2] = "model"
        ns = NamedSharding(mesh, P(*spec))
        return jax.ShapeDtypeStruct(shape, sds.dtype, sharding=ns)

    return jax.tree_util.tree_map_with_path(one, cache_sds)
