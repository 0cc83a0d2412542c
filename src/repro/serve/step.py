"""Serving steps: batched prefill + decode against a KV/state cache."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.spans import span


def make_prefill_step(cfg: ModelConfig, attn_fn=None):
    """prefill(params, batch) -> (last-token logits, aux).

    Lowered for the ``prefill_*`` shapes: the full-sequence forward is the
    dominant cost; cache materialization is the decode path's first update.
    """
    def prefill(params, batch):
        logits, aux = model_mod.forward(params, batch, cfg, attn_fn=attn_fn)
        return logits[:, -1:], aux
    return prefill


def make_serve_step(cfg: ModelConfig, attn_fn=None):
    """serve_step(params, cache, tokens[B,1]) -> (next token ids, cache)."""
    def serve_step(params, cache, tokens):
        logits, cache = model_mod.decode_step(params, cache, tokens, cfg,
                                              attn_fn=attn_fn)
        with jax.named_scope("lm_head"):
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache
    return serve_step


# ModelConfig is a frozen dataclass and attn_fn a stable callable, so the
# pair keys compiled serve steps across generate calls — one jit per
# (config, kernel), not one per invocation.
_SERVE_STEP_CACHE: Dict[Tuple[ModelConfig, Any], Any] = {}


def jitted_serve_step(cfg: ModelConfig, attn_fn=None):
    """The jitted decode step for ``cfg``, compiled once and reused.

    The cache is donated: the step writes the new position into the
    buffers it is given and returns them, so the cache passed in is
    consumed and only the returned one may be used again.
    """
    key = (cfg, attn_fn)
    step = _SERVE_STEP_CACHE.get(key)
    if step is None:
        step = _SERVE_STEP_CACHE[key] = jax.jit(make_serve_step(cfg, attn_fn),
                                                donate_argnums=(1,))
    return step


def greedy_generate(params, cfg: ModelConfig, prompt: jnp.ndarray,
                    max_new: int, max_seq: int, attn_fn=None):
    """Greedy decode loop, as the examples and serving run it.

    Each launch runs under a host span (``repro.spans``): the prompt's
    teacher-forced launches under ``serve.prompt_step``, the generating
    ones under ``serve.decode_step``, all inside ``serve.generate``.
    """
    b, p = prompt.shape
    with span("serve.generate", batch=b, prompt_len=p, max_new=max_new,
              max_seq=max_seq):
        with span("serve.init_cache"):
            cache = model_mod.init_cache(cfg, b, max_seq)
        step = jitted_serve_step(cfg, attn_fn)
        # teacher-force the prompt through the decode path
        tok = prompt[:, :1]
        out = [tok]
        for i in range(p - 1):
            with span("serve.prompt_step"):
                _, cache = step(params, cache, prompt[:, i:i + 1])
                out.append(prompt[:, i + 1:i + 2])
        tok = prompt[:, -1:]
        for _ in range(max_new):
            with span("serve.decode_step"):
                tok, cache = step(params, cache, tok)
            out.append(tok)
        with span("serve.concat"):
            return jnp.concatenate(out, axis=1)
