"""Seeded weights and data, made by the benchmark and never by the program.

A configuration's reference module declares its weights as a layout,
``{path: (shape, dtype, std[, mean])}``; ``make`` fills every leaf from the
seed in one jitted call on the default device, in the dtype the model is
served in.
The program and the reference each get these values from here: the
reference regenerates them after the window from the same seed, so it takes
nothing the program made.

Each leaf is ``mean + normal * std`` with a key folded from the seed and
the leaf's index in sorted path order, so the same seed gives the same
values on any device and in any process.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Layout = Dict[str, tuple]      # path -> (shape, dtype, std[, mean])


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    key = jax.random.PRNGKey(stream)
    seed = int(seed)
    for part in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF):
        key = jax.random.fold_in(key, np.uint32(part))
    return key


def make(layout: Layout, seed: int) -> Dict[str, jax.Array]:
    """Every leaf of ``layout`` from ``seed``, in one jitted call."""
    paths = sorted(layout)

    def build(key):
        out = {}
        for i, path in enumerate(paths):
            shape, dtype, std, *mean = layout[path]
            k = jax.random.fold_in(key, i)
            out[path] = (jax.random.normal(k, shape, jnp.float32) * std
                         + (mean[0] if mean else 0.0)).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed, stream=1))


def nest(flat: Dict[str, jax.Array]) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``: the program's parameter tree."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def flatten(tree, prefix: str = "") -> Dict[str, object]:
    """The inverse of ``nest`` for a tree of dicts."""
    out: Dict[str, object] = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def tokens(seed: int, stream: int, shape: Tuple[int, ...],
           vocab: int) -> np.ndarray:
    """Token ids in ``[0, vocab)`` on the host, from (seed, stream)."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])
    return rng.integers(0, vocab, size=shape, dtype=np.int32)
