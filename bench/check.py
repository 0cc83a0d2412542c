"""What decides ``correct``: the readings of the program against the plain
reference, and the reference's own optimizer.

Training (three steps from the seed):

- ``loss_gap``: the widest gap between the program's loss and the
  reference's over the first three steps;
- ``grad_gap``: the first gradient as the optimizer got it, read from its
  first moment after step 1, per leaf (per layer for stacked leaves): the
  gap between the program's norm and the reference's, over the reference's
  norm or the median leaf's, whichever is larger, at the worst leaf;
- ``delta_gap``: the same for the parameters' change after step 3.

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of both norms: Adam moves them by round-off alone.

Decode: ``logit_gap``, the widest gap by which a served token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from weights import flatten, nest

GRAD_FLOOR = 1e-3          # of the median leaf's reference gradient norm


# ---------------------------------------------------------------------------
# Norms per leaf, and per layer for the stacked ``layers/`` leaves.
# ---------------------------------------------------------------------------
def _norms(flat: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The norm of each leaf; a stacked ``layers/`` leaf is read layer by
    layer, so that one broken layer shows."""
    out = {}
    for path, x in flat.items():
        x = x.astype(jnp.float32)
        if path.startswith("layers/"):
            out[path] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[path] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


leaf_norms_jit = jax.jit(lambda tree: _norms(flatten(tree)))
diff_norms_jit = jax.jit(
    lambda a, b: _norms({k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
                         for k in a}))


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64)
            for k, v in leaf_norms_jit(tree).items()}


def change_norms(after, before) -> Dict[str, np.ndarray]:
    """Per-leaf norms of ``after - before`` (two trees of one structure)."""
    a, b = flatten(after), flatten(before)
    return {k: np.asarray(v, np.float64)
            for k, v in diff_norms_jit(a, b).items()}


def worst_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              ref_grad: Dict[str, np.ndarray]) -> Tuple[float, str]:
    """The largest ``|prog - ref| / max(ref, median ref)`` over the leaves
    (and layers) that the reference's gradient moves."""
    gall = np.concatenate([ref_grad[k] for k in sorted(ref_grad)])
    gfloor = GRAD_FLOOR * float(np.median(gall))
    keep = {k: ref_grad[k] >= gfloor for k in ref}
    rall = np.concatenate([ref[k][keep[k]] for k in sorted(ref)])
    med = float(np.median(rall))
    worst, where = 0.0, ""
    for k in sorted(ref):
        p, r = np.asarray(prog[k], np.float64), ref[k]
        if p.shape != r.shape:
            return math.inf, f"{k}: shape {p.shape} vs {r.shape}"
        rel = np.abs(p - r) / np.maximum(r, med)
        rel = np.where(keep[k], rel, 0.0)
        rel = np.where(np.isfinite(p), rel, np.inf)
        i = int(np.argmax(rel))
        if rel[i] > worst or not np.isfinite(rel[i]):
            worst, where = float(rel[i]), f"{k}[{i}]"
    return worst, where


# ---------------------------------------------------------------------------
# The control's precision: float8, per tensor scaled.
# ---------------------------------------------------------------------------
def fp8(x):
    """Round ``x`` as a per-tensor scaled float8 (e4m3) tensor would hold it."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(jax.lax.stop_gradient(x))),
                                1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def ce_sums(x, head, targets, quant=None, chunk: int = 512):
    """(sum of cross-entropy, sum of logsumexp squared) of the hidden
    states ``x`` [S, d] under the tied head [V, d], in float32 at the
    highest precision, holding ``chunk`` rows of logits at a time."""
    s = x.shape[0]
    n = max(1, s // chunk)
    xs, ts = x.reshape(n, s // n, -1), targets.reshape(n, s // n)

    @jax.checkpoint
    def one(carry, xt):
        xc, tc = xt
        a, b = (quant(xc), quant(head)) if quant is not None else (xc, head)
        lg = jnp.einsum("sd,vd->sv", a, b,
                        precision=jax.lax.Precision.HIGHEST)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
        return (carry[0] + jnp.sum(lse - gold),
                carry[1] + jnp.sum(lse * lse)), None

    zero = jnp.zeros((), jnp.float32)
    return jax.lax.scan(one, (zero, zero), (xs, ts))[0]


# ---------------------------------------------------------------------------
# Training: three steps of the reference with plain AdamW.
# ---------------------------------------------------------------------------
def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_frac`` of ``lr``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    decay = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) \
        * 0.5 * (1 + math.cos(math.pi * frac))
    return opt["lr"] * warm * decay


def _adamw(p, g, m, v, scale, step, lr, opt):
    b1, b2 = opt["b1"], opt["b2"]

    def one(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        p = p - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                      + opt["weight_decay"] * p)
        return p, m, v

    out = jax.tree.map(one, p, g, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def train_reference(ref, c, make_weights: Callable[[], dict],
                    batches: List[dict], opt: dict, quant=None,
                    rows: Optional[int] = None) -> dict:
    """Readings of three reference steps from ``make_weights()``.

    ``batches``: the program's first three batches, host arrays
    ``tokens``/``targets`` [B, S].  ``rows``: use only the first ``rows``
    rows of each, the mean taken over them (a planted fault).
    """
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    params = jax.jit(f32)(nest(make_weights()))
    z_loss = opt["z_loss"]

    def row_loss(w, tok, tgt, n_tokens):
        ce, z = ref.loss_sums(w, tok, tgt, c, quant)
        return (ce + z_loss * z) / n_tokens, ce / n_tokens

    vg = jax.jit(jax.value_and_grad(row_loss, has_aux=True))
    acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    sqsum = jax.jit(lambda t: sum(jnp.sum(x * x) for x in jax.tree.leaves(t)))
    adamw = jax.jit(lambda p, g, m, v, scale, step, lr:
                    _adamw(p, g, m, v, scale, step, lr, opt),
                    donate_argnums=(0, 2, 3))

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for step, b in enumerate(batches[:3], start=1):
        tok, tgt = np.asarray(b["tokens"]), np.asarray(b["targets"])
        n = rows if rows is not None else tok.shape[0]
        n_tokens = float(n * tok.shape[1])
        grads, loss = None, 0.0
        for r in range(n):
            (l, _), g = vg(params, jnp.asarray(tok[r]), jnp.asarray(tgt[r]),
                           n_tokens)
            loss += float(l)
            grads = g if grads is None else acc(grads, g)
        gnorm = math.sqrt(float(sqsum(grads)))
        scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
        if step == 1:
            grad_norms = leaf_norms(grads)
        params, m, v = adamw(params, grads, m, v, scale, float(step),
                             lr_at(step, opt))
        del grads
        losses.append(loss)
    del m, v
    delta = change_norms(params, nest(make_weights()))
    return {"losses": losses, "grad": grad_norms, "delta": delta}


def train_readings(prog: dict, ref: dict):
    """The three numbers compared, and the leaf each gap was widest at."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    if not all(map(math.isfinite, prog["losses"])):
        loss_gap = math.inf
    grad_gap, grad_at = worst_gap(prog["grad"], ref["grad"], ref["grad"])
    delta_gap, delta_at = worst_gap(prog["delta"], ref["delta"], ref["grad"])
    return ({"loss_gap": loss_gap, "grad_gap": grad_gap,
             "delta_gap": delta_gap},
            {"grad_at": grad_at, "delta_at": delta_at})


# ---------------------------------------------------------------------------
# Decode: the served tokens' gap below the reference's best logit.
# ---------------------------------------------------------------------------
def served_gap(ref_logits: np.ndarray, seq: np.ndarray, prompt: int) -> float:
    """``ref_logits`` [S, V] of ``seq`` [S]; positions ``prompt-1 .. S-2``
    produced the served tokens ``seq[prompt:]``."""
    lg = ref_logits[prompt - 1:-1]
    served = seq[prompt:]
    got = np.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return float(np.max(lg.max(axis=-1) - got))


def chosen_gap(ref_logits: np.ndarray, low_logits: np.ndarray,
               prompt: int) -> float:
    """The control's reading: the reference's gap of the token that the
    lower precision puts first, at the same positions."""
    lg = ref_logits[prompt - 1:-1]
    pick = np.argmax(low_logits[prompt - 1:-1], axis=-1)
    got = np.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return float(np.max(lg.max(axis=-1) - got))
