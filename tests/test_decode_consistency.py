"""Teacher-forcing equivalence: decoding token-by-token through the cache
must reproduce the full-sequence forward logits — the strongest correctness
check on every cache implementation (KV, MLA latent, SSM state, hybrid)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgs
from repro.models import model as M

pytestmark = pytest.mark.slow   # heavy model/distributed tier

B, S = 2, 8

# f32 smoke variants for tight comparison; whisper needs its cross cache
# and has a test of its own
ARCHS = [a for a in cfgs.ARCHS if cfgs.get_config(a).family != "encdec"]


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch):
    cfg = dataclasses.replace(cfgs.get_smoke_config(arch), dtype="float32")
    if cfg.family == "vlm":
        # decode path uses pure text positions; compare on text-only batch
        cfg = dataclasses.replace(cfg, n_vision_tokens=0)
    if cfg.n_experts:
        # token-choice routing is batch-dependent through the capacity
        # limit; equivalence holds when nothing is dropped
        cfg = dataclasses.replace(cfg, moe_capacity_factor=16.0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)

    batch = {"tokens": tokens}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.zeros((B, 0, cfg.d_model), jnp.float32)
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
    full_logits, _ = jax.jit(lambda p, b: M.forward(p, b, cfg))(params, batch)

    cache = M.init_cache(cfg, B, S + 1)
    step = jax.jit(lambda p, c, t: M.decode_step(p, c, t, cfg))
    dec_logits = []
    for t in range(S):
        lg, cache = step(params, cache, tokens[:, t:t + 1])
        dec_logits.append(lg[:, 0])
    dec = jnp.stack(dec_logits, axis=1)

    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


def test_whisper_decode_matches_forward():
    cfg = dataclasses.replace(cfgs.get_smoke_config("whisper-small"),
                              dtype="float32")
    from repro.models import encdec
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    enc_emb = jnp.asarray(
        rng.standard_normal((B, cfg.n_audio_frames, cfg.d_model)) * 0.02,
        jnp.float32)
    batch = {"tokens": tokens, "encoder_embeds": enc_emb}
    full_logits, _ = jax.jit(lambda p, b: M.forward(p, b, cfg))(params, batch)

    cache = M.init_cache(cfg, B, S + 1)
    ck, cv = encdec.prefill_cross_cache(params, enc_emb, cfg)
    cache = dict(cache, cross_k=ck, cross_v=cv)
    step = jax.jit(lambda p, c, t: M.decode_step(p, c, t, cfg))
    outs = []
    for t in range(S):
        lg, cache = step(params, cache, tokens[:, t:t + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)
