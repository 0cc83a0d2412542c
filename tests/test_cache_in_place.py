"""The decode cache is updated in place: the jitted serve step donates it
and aliases every leaf, at the decode launch's width and the prompt's, a
launch writes the new position and nothing else (an SSM rewrites every
layer's state and conv window), and donation leaves greedy decoding's
tokens as they were."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgs
from repro.models import model as M
from repro.models import transformer
from repro.models.layers import CACHE_TILE, kv_pack
from repro.serve.step import (PREFILL_WIDTH, greedy_generate,
                              jitted_serve_step, make_serve_step)

B, P, NEW, MAX_SEQ = 2, 5, 4, 12
# dense, MLA, hybrid, SSM
ARCHS = ["qwen2-0.5b", "minicpm3-4b", "zamba2-2.7b", "mamba2-2.7b"]
# the sequence axis of each cache that grows by a position
SEQ_AXIS = {"k": 3, "v": 3, "shared_k": 3, "shared_v": 3,
            "latent": 2, "k_rope": 2}
# the caches every launch rewrites whole, a layer at a time
REWRITTEN = ("state", "conv")


def _setup(arch, dtype=None):
    cfg = cfgs.get_smoke_config(arch)
    if dtype:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch", ARCHS)
def test_jitted_serve_step_aliases_every_cache_leaf(arch):
    cfg, params = _setup(arch)
    cache = M.init_cache(cfg, B, MAX_SEQ)
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(cache))
    # a decode launch, and a prompt's with its count of valid tokens
    for tokens in (jnp.zeros((B, 1), jnp.int32),
                   (jnp.zeros((B, PREFILL_WIDTH), jnp.int32), jnp.int32(3))):
        mem = jitted_serve_step(cfg).lower(params, cache, tokens).compile() \
            .memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes - cache["pos"].nbytes, \
            mem
        # the plain step, which launch/serve.py counts, donates nothing
        mem = jax.jit(make_serve_step(cfg)).lower(params, cache, tokens) \
            .compile().memory_analysis()
        assert mem.alias_size_in_bytes == 0, mem


@pytest.mark.parametrize("arch", ARCHS)
def test_a_launch_writes_its_position_and_nothing_else(arch):
    cfg, params = _setup(arch, dtype="float32")
    cache = M.init_cache(cfg, B, MAX_SEQ)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), len(cache)))
    cache = {name: (jnp.int32(3) if name == "pos" else
                    jax.random.normal(next(keys), x.shape, x.dtype))
             for name, x in cache.items()}
    before = jax.tree.map(np.asarray, cache)
    _, after = jax.jit(make_serve_step(cfg))(params, cache,
                                             jnp.ones((B, 1), jnp.int32))
    assert int(after["pos"]) == 4
    grown = [name for name in after if name in SEQ_AXIS]
    rewritten = [name for name in after if name in REWRITTEN]
    assert grown or rewritten
    for name in rewritten:
        # every layer advanced its own state and shifted its own window
        new = np.asarray(after[name])
        assert np.all(np.any(new != before[name], axis=tuple(
            range(1, new.ndim)))), name
    for name in grown:
        old = np.moveaxis(before[name], SEQ_AXIS[name], 0)
        new = np.moveaxis(np.asarray(after[name]), SEQ_AXIS[name], 0)
        np.testing.assert_array_equal(np.delete(new, 3, 0),
                                      np.delete(old, 3, 0), err_msg=name)
        # every layer (or shared-attention slot) wrote its own keys
        assert np.all(np.any(new[3] != old[3], axis=tuple(
            range(1, new[3].ndim)))), name


def _greedy_matches_undonated_launches(arch):
    cfg, params = _setup(arch)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, cfg.vocab,
                                jnp.int32)
    out = greedy_generate(params, cfg, prompt, max_new=NEW, max_seq=MAX_SEQ)

    # the launches greedy_generate makes, undonated: the prompt but its last
    # token in one padded launch, then the decode launches
    step = jax.jit(make_serve_step(cfg))
    cache = M.init_cache(cfg, B, MAX_SEQ)
    chunk = jnp.zeros((B, PREFILL_WIDTH), jnp.int32).at[:, :P - 1].set(
        prompt[:, :P - 1])
    _, cache = step(params, cache, (chunk, jnp.int32(P - 1)))
    toks = [prompt]
    tok = prompt[:, -1:]
    for _ in range(NEW):
        tok, cache = step(params, cache, tok)
        toks.append(tok)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.concatenate(toks, axis=1)))

    # the donated step consumes the cache it is given
    cache = M.init_cache(cfg, B, MAX_SEQ)
    _, kept = jitted_serve_step(cfg)(params, cache, tok)
    leaf = "k" if "k" in cache else "state"
    assert cache[leaf].is_deleted() and not kept[leaf].is_deleted()


def test_greedy_generate_is_unchanged_by_donation():
    _greedy_matches_undonated_launches("qwen2-0.5b")


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b"])
def test_greedy_generate_is_unchanged_by_donating_the_state(arch):
    """The SSM state, rewritten in place by the decode kernel, gives the
    tokens an undonated per-launch loop gives."""
    _greedy_matches_undonated_launches(arch)


def test_attention_caches_hold_whole_lane_rows_and_tiles():
    specs = transformer.init_cache_specs(cfgs.get_config("qwen2-0.5b"), 128,
                                         1149)
    # two kv heads of 64 side by side in 128 lanes, 1149 positions in 1152
    assert specs["k"].shape == (24, 128, 1, 1152, 128)
    assert specs["v"].shape == specs["k"].shape
    # heads of 128 lanes each keep a row of their own
    cfg = cfgs.get_config("gemma2-27b")
    assert kv_pack(cfg) == 1
    assert transformer.init_cache_specs(cfg, 2, 4096)["k"].shape == \
        (46, 2, 16, 4096, 128)
    assert [transformer.cache_capacity(n) for n in (1, 128, 129)] == \
        [CACHE_TILE, CACHE_TILE, 2 * CACHE_TILE]
