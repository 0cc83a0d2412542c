"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to auto: compiled on TPU, interpret-mode (Python
execution of the kernel body) everywhere else — which is how the kernels
are validated in this CPU container.  ``make_attn_fn`` adapts flash
attention to the model layer's ``attn_fn`` hook (GQA broadcast included).

Block configuration is resolved *outside* the jitted inner functions, so
each distinct config compiles once and the default path builds the exact
same jaxpr as an explicit-default call:

    block_config=None     — kernel defaults (bitwise-identical to before)
    block_config="auto"   — the autotuner's persisted winner for this
                            kernel (``repro.kernels.autotune``); falls back
                            to the defaults bitwise when no entry exists
    block_config=(...)    — explicit block sizes, e.g. ``(256, 512)`` for
                            flash ``(block_q, block_k)``
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ssm_decode as _ssm_dec


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _resolve_blocks(kernel: str, block_config, defaults: Tuple[int, ...],
                    operating_point=None) -> Tuple[int, ...]:
    """Map a ``block_config`` argument to concrete block sizes."""
    if block_config is None:
        return defaults
    if isinstance(block_config, str):
        if block_config != "auto":
            raise ValueError(f"unknown block_config {block_config!r}: "
                             "expected None, 'auto', or a tuple of ints")
        from repro.kernels import autotune     # lazy: avoid import cycle
        cfg = autotune.best_config(kernel, operating_point=operating_point)
        return tuple(cfg) if cfg else defaults
    if isinstance(block_config, int):
        return (block_config,)
    cfg = tuple(int(c) for c in block_config)
    if len(cfg) != len(defaults):
        raise ValueError(f"{kernel} block_config needs {len(defaults)} "
                         f"entries, got {cfg!r}")
    return cfg


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "interpret", "block_q",
                                             "block_k"))
def _flash_jit(q, k, v, *, causal: bool, window: Optional[int],
               softcap: Optional[float], interpret: bool,
               block_q: int, block_k: int):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    block_config=None, operating_point=None):
    block_q, block_k = _resolve_blocks(
        "flash_attention", block_config,
        (_fa.DEFAULT_BLOCK_Q, _fa.DEFAULT_BLOCK_K), operating_point)
    return _flash_jit(q, k, v, causal=causal, window=window, softcap=softcap,
                      interpret=_auto_interpret(interpret),
                      block_q=block_q, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("interpret", "block_k"))
def _decode_jit(q, k_cache, v_cache, lengths, *, interpret: bool,
                block_k: int):
    return _dec.decode_attention(q, k_cache, v_cache, lengths,
                                 block_k=block_k, interpret=interpret)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     interpret: Optional[bool] = None,
                     block_config=None, operating_point=None):
    (block_k,) = _resolve_blocks("decode_attention", block_config,
                                 (_dec.DEFAULT_BLOCK_K,), operating_point)
    return _decode_jit(q, k_cache, v_cache, lengths,
                       interpret=_auto_interpret(interpret), block_k=block_k)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, dt, a, b_mat, c_mat, h0, *, chunk: int, interpret: bool):
    return _ssd.ssd_chunked(x, dt, a, b_mat, c_mat, chunk, h0,
                            interpret=interpret)


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int = 256, h0=None, *,
                interpret: Optional[bool] = None,
                block_config=None, operating_point=None):
    if block_config is not None:
        (chunk,) = _resolve_blocks("ssd_chunked", block_config, (chunk,),
                                   operating_point)
    return _ssd_jit(x, dt, a, b_mat, c_mat, h0, chunk=chunk,
                    interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode_jit(state, layer, da, dtx, b_mat, c_mat, *, interpret: bool):
    return _ssm_dec.ssm_decode_update(state, layer, da, dtx, b_mat, c_mat,
                                      interpret=interpret)


def ssm_decode_update(state, layer, da, dtx, b_mat, c_mat, *,
                      interpret: Optional[bool] = None):
    """One decode step of layer ``layer`` of the stacked SSM state, written
    over the state's own buffer -> (state, y); see ``ssm_decode``."""
    return _ssm_decode_jit(state, layer, da, dtx, b_mat, c_mat,
                           interpret=_auto_interpret(interpret))


def make_attn_fn(interpret: Optional[bool] = None, block_config=None):
    """Flash attention as the model's ``attn_fn`` hook: the model layer
    calls attn_fn(q, k, v, cfg) on the full-sequence path."""
    def attn_fn(q, k, v, cfg):
        h, kvh = q.shape[2], k.shape[2]
        if kvh != h:
            k = jnp.repeat(k, h // kvh, axis=2)
            v = jnp.repeat(v, h // kvh, axis=2)
        window = cfg.sliding_window
        return flash_attention(q, k, v, causal=True, window=window,
                               softcap=cfg.attn_logit_softcap,
                               interpret=interpret, block_config=block_config)
    return attn_fn
