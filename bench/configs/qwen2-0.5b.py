"""Plain float32 reference for Qwen2 (arXiv:2407.10671), and its required work.

The reference is the published decoder written out in ``jax.numpy``: RMSNorm,
rotary embeddings (rotate-half, base ``rope_theta``), grouped-query causal
attention with q/k/v biases, a SiLU-gated MLP and a head tied to the
embedding.  It imports nothing of the program.  Every matmul runs at
``Precision.HIGHEST`` so that the TPU computes it in float32.

One departure from the published form, in parametrisation only: a norm's
stored weight ``w`` is an offset, and the norm scales by ``1 + w``.  That is
how the parameter tree this benchmark fills stores it; the function is the
same RMSNorm.

``quant``, when given, rounds both operands of every matmul (a per-tensor
scaled float8 cast for the control) before the float32 product.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from check import ce_sums

HIGHEST = jax.lax.Precision.HIGHEST


def dims(c):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return dict(d=d, layers=c["num_hidden_layers"], heads=h,
                kv=c["num_key_value_heads"], hd=d // h,
                ff=c["intermediate_size"], vocab=c["vocab_size"])


def layout(c):
    """The parameter tree, as paths, with the std each leaf is drawn at."""
    g = dims(c)
    d, L, h, kv, hd, ff, v = (g["d"], g["layers"], g["heads"], g["kv"],
                              g["hd"], g["ff"], g["vocab"])
    bf, f32 = "bfloat16", "float32"
    out = {
        "embed/tok": ((v, d), bf, 1 / math.sqrt(d)),
        "final_norm": ((d,), f32, 0.1),
        "layers/ln1": ((L, d), f32, 0.1),
        "layers/ln2": ((L, d), f32, 0.1),
        "layers/attn/wq": ((L, d, h, hd), bf, 1 / math.sqrt(d)),
        "layers/attn/wk": ((L, d, kv, hd), bf, 1 / math.sqrt(d)),
        "layers/attn/wv": ((L, d, kv, hd), bf, 1 / math.sqrt(d)),
        "layers/attn/wo": ((L, h, hd, d), bf, 1 / math.sqrt(h * hd)),
        "layers/mlp/w_in": ((L, d, ff), bf, 1 / math.sqrt(d)),
        "layers/mlp/w_gate": ((L, d, ff), bf, 1 / math.sqrt(d)),
        "layers/mlp/w_out": ((L, ff, d), bf, 1 / math.sqrt(ff)),
    }
    if c.get("qkv_bias"):
        out.update({
            "layers/attn/bq": ((L, h, hd), bf, 0.1),
            "layers/attn/bk": ((L, kv, hd), bf, 0.1),
            "layers/attn/bv": ((L, kv, hd), bf, 0.1),
        })
    return out


# ---------------------------------------------------------------------------
# Required work, from shapes only.
# ---------------------------------------------------------------------------
def matmul_params(c) -> int:
    """Weights every token multiplies by: projections, MLP and the head."""
    g = dims(c)
    d, hd = g["d"], g["hd"]
    attn = d * hd * (g["heads"] + 2 * g["kv"]) + g["heads"] * hd * d
    mlp = 3 * d * g["ff"]
    return g["layers"] * (attn + mlp) + g["vocab"] * d


def kv_bytes_per_token(c) -> int:
    g = dims(c)
    return g["layers"] * 2 * g["kv"] * g["hd"] * 2          # bf16 k and v


def train_flops_per_token(c, seq_len: int) -> float:
    """Forward and backward: 6 N_matmul, plus causal attention's scores and
    weighted sum over on average half the sequence, 3 x 4 x L x (S/2) x d."""
    g = dims(c)
    attn = 6 * g["layers"] * seq_len * g["heads"] * g["hd"]
    return 6.0 * matmul_params(c) + attn


def prefill_work(c, batch: int, prompt: int):
    """(FLOPs, bytes) of one forward over ``prompt`` tokens per sequence,
    filling the cache: weights read once, the prompt's keys and values
    written."""
    g = dims(c)
    flops = batch * (2.0 * matmul_params(c) * prompt
                     + 2 * g["layers"] * prompt * prompt
                     * g["heads"] * g["hd"])
    nbytes = 2.0 * matmul_params(c) + batch * prompt * kv_bytes_per_token(c)
    return flops, nbytes


def decode_work(c, batch: int, context: int):
    """(FLOPs, bytes) of one decode step whose new token sees ``context``
    positions: weights read once, the filled cache read, one position
    written."""
    g = dims(c)
    flops = batch * (2.0 * matmul_params(c)
                     + 4 * g["layers"] * context * g["heads"] * g["hd"])
    nbytes = 2.0 * matmul_params(c) + batch * (context + 1) \
        * kv_bytes_per_token(c)
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


def _rope(x, theta):
    """x [S, H, D], positions 0..S-1, rotate-half convention."""
    s, _, dd = x.shape
    half = dd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, c, quant):
    g = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    s = x.shape[0]
    a = lw["attn"]
    h = _norm(x, lw["ln1"], eps)
    q = _mm("sd,dhk->shk", h, a["wq"], quant)
    k = _mm("sd,dhk->shk", h, a["wk"], quant)
    v = _mm("sd,dhk->shk", h, a["wv"], quant)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    grp = g["heads"] // g["kv"]
    q = q.reshape(s, g["kv"], grp, g["hd"])
    scores = _mm("skgd,tkd->kgst", q, k, quant) / math.sqrt(g["hd"])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("kgst,tkd->skgd", probs, v, quant).reshape(s, g["heads"], g["hd"])
    x = x + _mm("shk,hkd->sd", o, a["wo"], quant)
    m = lw["mlp"]
    h = _norm(x, lw["ln2"], eps)
    up = jax.nn.silu(_mm("sd,df->sf", h, m["w_gate"], quant)) \
        * _mm("sd,df->sf", h, m["w_in"], quant)
    return x + _mm("sf,fd->sd", up, m["w_out"], quant)


def hidden(w, tokens, c, quant=None, remat=False):
    """Final normed hidden states [S, d] of one sequence ``tokens`` [S]."""
    x = w["embed"]["tok"][tokens]
    body = lambda x, lw: (_layer(x, lw, c, quant), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["layers"])
    return _norm(x, w["final_norm"], c["rms_norm_eps"])


def logits(w, tokens, c, quant=None):
    """Logits [S, V] of one sequence, float32."""
    return _mm("sd,vd->sv", hidden(w, tokens, c, quant), w["embed"]["tok"],
               quant)


def loss_sums(w, tokens, targets, c, quant=None):
    """(sum of cross-entropy, sum of logsumexp squared) over one sequence."""
    x = hidden(w, tokens, c, quant, remat=True)
    return ce_sums(x, w["embed"]["tok"], targets, quant)
