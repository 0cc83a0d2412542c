"""A prompt fed through the serve step in launches of ``PREFILL_WIDTH``
positions, the last one padded, leaves the cache that feeding it one token
at a time leaves: the same next logits, the same filled positions (keys and
values, or the SSM state and conv window) and the same position, in
float32.  Pad positions change nothing, and every prompt length runs the
same two compiled programs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgs
from repro.models import model as M
from repro.serve import step as step_mod
from repro.serve.step import PREFILL_WIDTH as C
from repro.serve.step import greedy_generate, jitted_serve_step

B = 2
LENGTHS = (1, C - 1, C, C + 1, 2 * C + 3)
MAX_SEQ = max(LENGTHS) + 2
# dense, SSM, hybrid, MLA, MoE
ARCHS = ["qwen2-0.5b", "mamba2-2.7b", "zamba2-2.7b", "minicpm3-4b",
         "llama4-scout-17b-a16e"]
# the sequence axis of each cache that grows by a position
SEQ_AXIS = {"k": 3, "v": 3, "shared_k": 3, "shared_v": 3,
            "latent": 2, "k_rope": 2}
# the largest gap allowed, as a share of the largest magnitude compared
TOL = 1e-5


def _config(arch):
    """The float32 smoke config.  An MoE's capacity factor becomes E/k, so
    that an expert holds every token of a launch: a prompt's launch routes
    its B·C tokens together and, at the configured factor, can drop tokens
    over an expert's capacity that one-token launches keep."""
    cfg = dataclasses.replace(cfgs.get_smoke_config(arch), dtype="float32")
    if cfg.n_experts:
        cfg = dataclasses.replace(
            cfg, moe_capacity_factor=cfg.n_experts / cfg.moe_top_k)
    return cfg


def _step(cfg):
    """The serve step's model call, undonated, with its logits."""
    return jax.jit(lambda p, c, t, n=None: M.decode_step(p, c, t, cfg, n=n))


def _chunks(tokens, width=C, fill=None):
    """[B,L] -> (chunk [B,width], valid count) launches, the last padded
    with ``fill`` (zeros where None)."""
    host = np.asarray(tokens)
    for start in range(0, host.shape[1], width):
        n = min(width, host.shape[1] - start)
        chunk = np.zeros((host.shape[0], width), host.dtype) if fill is None \
            else np.array(fill[:, :width])
        chunk[:, :n] = host[:, start:start + n]
        yield chunk, np.int32(n)


@pytest.fixture(scope="module", params=ARCHS)
def by_token(request):
    """Per arch: the prompt, and after each length in LENGTHS the cache that
    one-token launches leave and the logits of the next launch."""
    cfg = _config(request.param)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, max(LENGTHS) + 1),
                                0, cfg.vocab, jnp.int32)
    step = _step(cfg)
    cache, after = M.init_cache(cfg, B, MAX_SEQ), {}
    for i in range(max(LENGTHS)):
        _, cache = step(params, cache, tokens[:, i:i + 1])
        if i + 1 in LENGTHS:
            logits, _ = step(params, cache, tokens[:, i + 1:i + 2])
            after[i + 1] = (jax.tree.map(np.asarray, cache),
                            np.asarray(logits))
    return cfg, params, tokens, step, after


def _close(got, want, what):
    gap = float(np.max(np.abs(got - want)))
    assert gap <= TOL * float(np.max(np.abs(want))), (what, gap)


@pytest.mark.parametrize("length", LENGTHS)
def test_chunked_prefill_matches_token_by_token(by_token, length):
    cfg, params, tokens, step, after = by_token
    cache = M.init_cache(cfg, B, MAX_SEQ)
    for chunk, n in _chunks(tokens[:, :length]):
        _, cache = step(params, cache, chunk, n)
    logits, _ = step(params, cache, tokens[:, length:length + 1])
    want_cache, want_logits = after[length]
    assert int(cache["pos"]) == int(want_cache["pos"]) == length
    _close(np.asarray(logits), want_logits, "logits")
    for name, want in want_cache.items():
        got = np.asarray(cache[name])
        if name in SEQ_AXIS:
            # the filled positions; pads past them are never read
            got, want = (np.take(a, np.arange(length), axis=SEQ_AXIS[name])
                         for a in (got, want))
        _close(got, want, name)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b"])
def test_pads_leave_the_ssm_state_bit_for_bit(arch):
    """Trailing pads neither decay nor feed the state, nor enter the conv
    window: with pads of any token, or none at all, both come out the
    same, bit for bit."""
    cfg = _config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    step = _step(cfg)
    n = C // 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, n), 0, cfg.vocab,
                                jnp.int32)
    noise = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (B, C), 0,
                                          cfg.vocab, jnp.int32))

    def ssm_cache(launches):
        cache = M.init_cache(cfg, B, MAX_SEQ)
        for chunk, valid in launches:
            _, cache = step(params, cache, chunk, valid)
        return {k: np.asarray(cache[k]) for k in ("pos", "state", "conv")}

    zeros = ssm_cache(_chunks(tokens))
    assert int(zeros["pos"]) == n
    for other in (ssm_cache(_chunks(tokens, fill=noise)),
                  ssm_cache(_chunks(tokens, width=n))):
        for name, want in zeros.items():
            np.testing.assert_array_equal(other[name], want, err_msg=name)


def test_greedy_generate_prompt_lengths_share_one_compiled_step(
        monkeypatch):
    """Once a 2-token prompt has run, a longer prompt at the same batch and
    cache compiles no serve step: the prompt's launches and the decode
    launches are the two programs the short call compiled."""
    monkeypatch.setattr(step_mod, "_SERVE_STEP_CACHE", {})
    cfg = cfgs.get_smoke_config("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, 2 * C + 3), 0,
                                cfg.vocab, jnp.int32)
    greedy_generate(params, cfg, prompt[:, :2], max_new=2, max_seq=MAX_SEQ)
    step = jitted_serve_step(cfg)
    compiled = step._cache_size()
    assert compiled == 2
    out = greedy_generate(params, cfg, prompt, max_new=2, max_seq=MAX_SEQ)
    assert step._cache_size() == compiled
    np.testing.assert_array_equal(np.asarray(out)[:, :prompt.shape[1]],
                                  np.asarray(prompt))


def test_moe_pads_take_no_expert_capacity():
    """A prompt launch's pads route to no expert: where they would fill an
    expert before the next row's tokens reach it, those tokens still get
    their slots, and the pads come out zero."""
    from repro.models import moe
    from repro.models.layers import init_from_specs
    cfg = dataclasses.replace(
        cfgs.get_smoke_config("arctic-480b"), d_model=16, d_ff=32,
        n_experts=4, moe_top_k=1, moe_capacity_factor=1.0, dtype="float32")
    params = init_from_specs(moe.moe_specs(cfg), jax.random.PRNGKey(0))
    # two rows of 16 positions, 4 valid: 32 copies of one token, all routed
    # to one expert of 8 slots, where row 0's 12 pads sort before row 1
    rows, width, n = 2, 16, 4
    assert moe.capacity(cfg, rows * width) == 2 * n
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(1), (1, 16)),
                 (rows * width, 1))
    valid = jnp.tile(jnp.arange(width) < n, rows)
    y, _ = moe.moe_mlp(x, params, cfg, valid=valid)
    y = np.asarray(y).reshape(rows, width, -1)
    assert np.all(np.any(y[:, 0] != 0, axis=-1))
    np.testing.assert_array_equal(y[:, :n], np.broadcast_to(y[:1, :1],
                                                            y[:, :n].shape))
    np.testing.assert_array_equal(y[:, n:], 0)
