"""Plain float32 reference for Mamba-2 (arXiv:2405.21060), and its required
work.

The reference is the published block written out in ``jax.numpy``, with the
state-space layer as its sequential recurrence, one position at a time:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

not the chunked (state-space duality) form the program computes.  Around
it: RMSNorm, the input projection to (z, xBC, dt), a depthwise causal
convolution of width ``d_conv`` over xBC with SiLU, dt = softplus(dt + bias),
the gated RMSNorm of y * silu(z), the output projection, and a head tied to
the embedding.  It imports nothing of the program, and every matmul runs at
``Precision.HIGHEST``.  A norm's stored weight is an offset (scale 1 + w), as
the parameter tree this benchmark fills stores it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from check import ce_sums

HIGHEST = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64         # positions per rematerialised block of the scan


def dims(c):
    s = {**c["ssm_defaults"], **c["ssm_cfg"]}
    d = c["d_model"]
    d_in = s["expand"] * d
    return dict(d=d, layers=c["n_layer"], d_in=d_in, n=s["d_state"],
                p=s["headdim"], h=d_in // s["headdim"], k=s["d_conv"],
                vocab=c["vocab_size"])


def layout(c):
    """The parameter tree, as paths, with the std (and mean) of each leaf."""
    g = dims(c)
    d, L, d_in, n, h, k = g["d"], g["layers"], g["d_in"], g["n"], g["h"], \
        g["k"]
    conv = d_in + 2 * n
    bf, f32 = "bfloat16", "float32"
    return {
        "embed/tok": ((g["vocab"], d), bf, 1 / math.sqrt(d)),
        "final_norm": ((d,), f32, 0.1),
        "layers/ln1": ((L, d), f32, 0.1),
        "layers/ssm/in_proj": ((L, d, 2 * d_in + 2 * n + h), bf,
                               1 / math.sqrt(d)),
        "layers/ssm/conv_w": ((L, k, conv), bf, 1 / math.sqrt(k)),
        "layers/ssm/conv_b": ((L, conv), bf, 0.1),
        "layers/ssm/a_log": ((L, h), f32, 0.5, 1.0),
        "layers/ssm/d_skip": ((L, h), f32, 0.1, 1.0),
        "layers/ssm/dt_bias": ((L, h), f32, 0.5, -4.6),
        "layers/ssm/norm_w": ((L, d_in), f32, 0.1),
        "layers/ssm/out_proj": ((L, d_in, d), bf, 1 / math.sqrt(d_in)),
    }


# ---------------------------------------------------------------------------
# Required work, from shapes only.
# ---------------------------------------------------------------------------
def matmul_params(c) -> int:
    """Weights every token multiplies by: in/out projections and the head."""
    g = dims(c)
    per_layer = g["d"] * (2 * g["d_in"] + 2 * g["n"] + g["h"]) \
        + g["d_in"] * g["d"]
    return g["layers"] * per_layer + g["vocab"] * g["d"]


def layer_params(c) -> int:
    g = dims(c)
    conv = g["d_in"] + 2 * g["n"]
    return (g["d"] * (2 * g["d_in"] + 2 * g["n"] + g["h"])
            + g["d_in"] * g["d"] + (g["k"] + 1) * conv + 3 * g["h"]
            + g["d_in"] + g["d"])


def state_bytes(c) -> int:
    """The float32 state of one layer of one sequence."""
    g = dims(c)
    return g["h"] * g["p"] * g["n"] * 4


def _recurrence_flops(c) -> int:
    """Per position and layer: decay the state (HPN), add dt x B^T (2 HPN),
    read y = h C (2 HPN)."""
    g = dims(c)
    return 5 * g["h"] * g["p"] * g["n"]


def train_flops_per_token(c, seq_len: int) -> float:
    """Forward and backward: 6 N_matmul, plus three times the forward
    recurrence; no chunking, no recompute."""
    return 6.0 * matmul_params(c) + 3.0 * dims(c)["layers"] \
        * _recurrence_flops(c)


def _per_token_flops(c) -> float:
    return 2.0 * matmul_params(c) + dims(c)["layers"] * _recurrence_flops(c)


def prefill_work(c, batch: int, prompt: int):
    """(FLOPs, bytes): weights read once, each sequence's state written."""
    flops = batch * prompt * _per_token_flops(c)
    nbytes = 2.0 * matmul_params(c) + batch * dims(c)["layers"] \
        * state_bytes(c)
    return flops, nbytes


def decode_work(c, batch: int, context: int):
    """(FLOPs, bytes) of one decode step: weights read once, each
    sequence's state read and written; ``context`` does not matter."""
    flops = batch * _per_token_flops(c)
    nbytes = 2.0 * matmul_params(c) + 2.0 * batch * dims(c)["layers"] \
        * state_bytes(c)
    return flops, nbytes


# ---------------------------------------------------------------------------
# The reference.
# ---------------------------------------------------------------------------
def _mm(eq, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _recurrence(x, dt, a, b, cm, remat):
    """x [S,H,P], dt [S,H], a [H], b and cm [S,N] -> y [S,H,P], position
    by position."""
    s, h, p = x.shape
    n = b.shape[-1]

    def one(state, inp):
        xt, dtt, bt, ct = inp
        state = state * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, ct, precision=HIGHEST)

    state0 = jnp.zeros((h, p, n), jnp.float32)
    if not remat or s % SCAN_BLOCK:
        return jax.lax.scan(one, state0, (x, dt, b, cm))[1]

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(one, state, inp)

    blocks = lambda t: t.reshape((s // SCAN_BLOCK, SCAN_BLOCK) + t.shape[1:])
    _, y = jax.lax.scan(block, state0, tuple(map(blocks, (x, dt, b, cm))))
    return y.reshape(s, h, p)


def _layer(x, lw, c, quant, remat):
    g = dims(c)
    d_in, n, h, k = g["d_in"], g["n"], g["h"], g["k"]
    eps = c["norm_epsilon"]
    w = lw["ssm"]
    s = x.shape[0]
    u = _norm(x, lw["ln1"], eps)
    proj = _mm("sd,de->se", u, w["in_proj"], quant)
    z, xbc, dt = proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * n], \
        proj[:, 2 * d_in + 2 * n:]
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], 0)
    xbc = sum(pad[i:i + s] * w["conv_w"][i] for i in range(k)) + w["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :d_in].reshape(s, h, g["p"])
    b, cm = xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = _recurrence(xs, dt, -jnp.exp(w["a_log"]), b, cm, remat)
    y = y + w["d_skip"][None, :, None] * xs
    y = _norm(y.reshape(s, d_in) * jax.nn.silu(z), w["norm_w"], eps)
    return x + _mm("se,ed->sd", y, w["out_proj"], quant)


def hidden(w, tokens, c, quant=None, remat=False):
    """Final normed hidden states [S, d] of one sequence ``tokens`` [S]."""
    x = w["embed"]["tok"][tokens]
    body = lambda x, lw: (_layer(x, lw, c, quant, remat), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["layers"])
    return _norm(x, w["final_norm"], c["norm_epsilon"])


def logits(w, tokens, c, quant=None):
    """Logits [S, V] of one sequence, float32."""
    return _mm("sd,vd->sv", hidden(w, tokens, c, quant), w["embed"]["tok"],
               quant)


def loss_sums(w, tokens, targets, c, quant=None):
    """(sum of cross-entropy, sum of logsumexp squared) over one sequence."""
    x = hidden(w, tokens, c, quant, remat=True)
    return ce_sums(x, w["embed"]["tok"], targets, quant)
