"""The program's own trace: host spans around each launch of
``greedy_generate``, named scopes in the compiled serve step, and the
compile counter, checked on the CPU at the tiny qwen2 size."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs as cfgs
from repro.launch import compile_cache
from repro.models import model as M
from repro.serve.step import PREFILL_WIDTH, greedy_generate, make_serve_step
from repro.spans import PREFIX

P, NEW, MAX_SEQ = 5, 3, 12


@pytest.fixture(scope="module")
def tiny():
    cfg = cfgs.get_smoke_config("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, P), 0, cfg.vocab,
                                jnp.int32)
    return cfg, params, prompt


def _profiled_call(tiny, tdir):
    cfg, params, prompt = tiny
    jax.profiler.start_trace(str(tdir))
    try:
        out = np.asarray(greedy_generate(params, cfg, prompt, max_new=NEW,
                                         max_seq=MAX_SEQ))
    finally:
        jax.profiler.stop_trace()
    (path,) = tdir.rglob("*.xplane.pb")
    spans = [ev for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PREFIX)]
    return out, spans


def test_profiled_call_returns_the_same_tokens_and_its_spans(tiny, tmp_path):
    cfg, params, prompt = tiny
    plain = np.asarray(greedy_generate(params, cfg, prompt, max_new=NEW,
                                       max_seq=MAX_SEQ))
    out, spans = _profiled_call(tiny, tmp_path)
    np.testing.assert_array_equal(out, plain)
    names = [ev.name.removeprefix(PREFIX) for ev in spans]
    assert names.count("serve.generate") == 1
    assert names.count("serve.init_cache") == 1
    # the prompt but its last token, in one launch of PREFILL_WIDTH
    assert names.count("serve.prompt_step") == 1
    assert names.count("serve.decode_step") == NEW
    assert names.count("serve.concat") == 1
    (gen,) = [ev for ev in spans if ev.name == PREFIX + "serve.generate"]
    assert dict(gen.stats) == {"batch": 2, "prompt_len": P, "max_new": NEW,
                               "max_seq": MAX_SEQ}
    # the launches sit inside the call, in order: prompt, then decode
    steps = sorted((ev for ev in spans if ev.name.endswith("_step")),
                   key=lambda ev: ev.start_ns)
    assert [ev.name.endswith("prompt_step") for ev in steps] == \
        [True] + [False] * NEW
    assert dict(steps[0].stats) == {"tokens": P - 1, "width": PREFILL_WIDTH}
    assert gen.start_ns <= steps[0].start_ns
    assert steps[-1].end_ns <= gen.end_ns


def test_compiled_serve_step_names_its_scopes(tiny):
    cfg, params, prompt = tiny
    cache = M.init_cache(cfg, prompt.shape[0], MAX_SEQ)
    hlo = jax.jit(make_serve_step(cfg)).lower(
        params, cache, prompt[:, :1]).compile().as_text()
    op_names = " ".join(
        part.split('"')[1] for part in hlo.split("op_name=")[1:])
    stack = set(op_names.replace(" ", "/").split("/"))
    for scope in ("embed", "attn", "kv_cache", "mlp", "lm_head"):
        assert scope in stack, scope


def test_compile_stats_counts_up_to_a_time(monkeypatch, tmp_path):
    # an env var keeps the persistent cache off the checkout in tests
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.use_compile_cache()
    x = np.asarray(jnp.arange(4.0))
    t = time.perf_counter()
    before = compile_cache.compile_stats(until=t)
    np.asarray(jax.jit(lambda x: x * 3 + 1)(x))
    total = compile_cache.compile_stats()
    assert compile_cache.compile_stats(until=t) == before
    assert total["programs"] == before["programs"] + 1
    assert total["seconds"] > before["seconds"]
    assert compile_cache.compile_line().startswith("[compile] ")
