"""Serving steps: batched prefill + decode against a KV/state cache."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.models.layers import CACHE_TILE
from repro.spans import span

# Prompt positions a prefill launch carries: one tile of the cache, so that
# every launch, the last one padded, fits the cache (``cache_capacity``)
# and is one program whatever the prompt's length.
PREFILL_WIDTH = CACHE_TILE


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
    """serve_step(params, cache, tokens) -> (next token ids [B,1], cache).

    ``tokens`` fill the cache's next positions: a decode launch's ids
    [B,1], or a prompt's launch as the pair (ids [B,PREFILL_WIDTH], n) of
    which the first ``n`` are valid.  The token returned is the greedy one
    after the last valid position."""
    def serve_step(params, cache, tokens):
        tokens, n = tokens if isinstance(tokens, tuple) else (tokens, None)
        logits, cache = model_mod.decode_step(params, cache, tokens, cfg,
                                              n=n, attn_fn=attn_fn)
        with jax.named_scope("lm_head"):
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache
    return serve_step


# ModelConfig is a frozen dataclass and attn_fn a stable callable, so the
# pair keys compiled serve steps across generate calls — one jit per
# (config, kernel), not one per invocation.
_SERVE_STEP_CACHE: Dict[Tuple[ModelConfig, Any], Any] = {}


def jitted_serve_step(cfg: ModelConfig, attn_fn=None):
    """The jitted serve step for ``cfg``, compiled once per token width
    and reused.

    The cache is donated: the step writes the new positions into the
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

    The prompt but its last token fills the cache in launches of
    ``PREFILL_WIDTH`` positions, the last one padded, cut on the host from
    one fetch of the prompt: the same two programs, [B, PREFILL_WIDTH] and
    [B, 1], serve every prompt length, and the output is joined on the
    host, so the call waits for its last token.  Each launch runs under a
    host span (``repro.spans``): the prompt's under ``serve.prompt_step``,
    with the valid ``tokens`` and the launch's ``width``, the generating
    ones under ``serve.decode_step``, all inside ``serve.generate``.
    """
    b, p = prompt.shape
    with span("serve.generate", batch=b, prompt_len=p, max_new=max_new,
              max_seq=max_seq):
        with span("serve.init_cache"):
            cache = model_mod.init_cache(cfg, b, max_seq)
        step = jitted_serve_step(cfg, attn_fn)
        host = np.asarray(prompt)
        for start in range(0, p - 1, PREFILL_WIDTH):
            n = min(PREFILL_WIDTH, p - 1 - start)
            chunk = np.zeros((b, PREFILL_WIDTH), host.dtype)
            chunk[:, :n] = host[:, start:start + n]
            with span("serve.prompt_step", tokens=n, width=PREFILL_WIDTH):
                _, cache = step(params, cache, (chunk, np.int32(n)))
        tok, new = prompt[:, -1:], []
        for _ in range(max_new):
            with span("serve.decode_step"):
                tok, cache = step(params, cache, tok)
            new.append(tok)
        # joined on the host: a device op per position would cost the host
        # more than a launch costs the device, and a join on the device
        # would be a program per prompt length
        with span("serve.concat"):
            return jnp.asarray(np.concatenate([host] + jax.device_get(new),
                                              axis=1))
