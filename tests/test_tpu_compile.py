"""Compiles for a described (not attached) TPU v5e chip: what the chip's
compiler refuses — block shapes off the (8, 128) tiling, more fast memory
than a kernel may use, a step that does not fit the device — fails here
without a chip.  Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture only: describing it
loads the TPU library, which one process at a time may hold, so it must
never happen while a module is imported or collected.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as cfgs
from repro.kernels import ops
from repro.models import model as model_mod
from repro.serve import step as step_mod
from repro.serve.step import jitted_serve_step
from repro.train import optimizer as opt_mod
from repro.train.step import init_state, make_train_step

HBM_BYTES = 15.75 * 2 ** 30          # what the compiler lets one v5e use


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_qwen2_widths(one_chip):
    x = _sds(one_chip, (1, 4096, 14, 64))
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
             x, x, x)


@pytest.mark.parametrize("cache", [32768, 131072])
def test_decode_attention_compiles_over_long_cache(one_chip, cache):
    kv = _sds(one_chip, (2, cache, 2, 64))
    _compile(lambda q, k, v, n: ops.decode_attention(q, k, v, n,
                                                     interpret=False),
             _sds(one_chip, (2, 14, 64)), kv, kv,
             _sds(one_chip, (2,), jnp.int32))


def test_ssd_compiles_at_mamba2_widths(one_chip):
    s, h, p, n = 4096, 80, 64, 128
    f32 = jnp.float32
    _compile(lambda x, dt, a, b, c: ops.ssd_chunked(x, dt, a, b, c, chunk=256,
                                                    interpret=False),
             _sds(one_chip, (1, s, h, p)), _sds(one_chip, (1, s, h), f32),
             _sds(one_chip, (h,), f32), _sds(one_chip, (1, s, n)),
             _sds(one_chip, (1, s, n)))


def test_ssm_decode_compiles_at_mamba2_widths(one_chip):
    layers, b, h, p, n = 8, 128, 80, 64, 128
    f32 = jnp.float32
    _compile(lambda st, i, da, dtx, bm, cm: ops.ssm_decode_update(
        st, i, da, dtx, bm, cm, interpret=False),
        _sds(one_chip, (layers, b, h, p, n), f32),
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (b, h), f32),
        _sds(one_chip, (b, h, p), f32), _sds(one_chip, (b, n), f32),
        _sds(one_chip, (b, n), f32))


def test_qwen2_train_step_fits_one_chip(one_chip):
    """The full-width qwen2-0.5b step that ``launch/train.run`` jits, at
    4 x 1024: compiled, and its arguments, outputs and temporaries fit."""
    cfg = cfgs.get_config("qwen2-0.5b")
    opt_cfg = opt_mod.OptConfig(total_steps=4, warmup_steps=2,
                                mv_dtype=cfg.optimizer_dtype,
                                master_fp32=cfg.optimizer_dtype == "float32")
    state = jax.eval_shape(lambda k: init_state(cfg, opt_cfg, k),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), state)
    tokens = _sds(one_chip, (4, 1024), jnp.int32)
    step = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0,))
    mem = step.lower(state, {"tokens": tokens, "targets": tokens}) \
        .compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, mem


def _decode_step(one_chip, batch, max_seq, cfg=None, width=1):
    """The full-width serve step ``serve/step.greedy_generate`` jits (cache
    donated) for ``cfg`` (qwen2-0.5b unless given), compiled at ``batch`` x
    ``max_seq`` for launches of ``width`` tokens (a prompt's, with its count
    of valid tokens, where more than 1)."""
    cfg = cfg or cfgs.get_config("qwen2-0.5b")
    on_chip = lambda a: _sds(one_chip, a.shape, a.dtype)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: model_mod.init_params(cfg, k), jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model_mod.init_cache(cfg, batch, max_seq)))
    tokens = _sds(one_chip, (batch, width), jnp.int32)
    if width > 1:
        tokens = (tokens, _sds(one_chip, (), jnp.int32))
    compiled = jitted_serve_step(cfg).lower(params, cache, tokens).compile()
    return compiled, cache


def test_qwen2_decode_step_compiles(one_chip):
    compiled, _ = _decode_step(one_chip, 2, 32)
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes < HBM_BYTES, mem
    # the cache is written in place: the output is the input's buffers but
    # for the next tokens and the position
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2 ** 20, mem


def test_qwen2_decode_step_reads_the_cache_in_place(one_chip):
    """At the benchmark cell's shape (128 requests, 1149 positions) no
    layer copies or re-lays the cache: the one-position writes are in
    place and attention reads each layer's slice as it is stored."""
    compiled, cache = _decode_step(one_chip, 128, 1149)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2 ** 20, mem
    assert mem.temp_size_in_bytes < 2 ** 24, mem
    capacity = cache["k"].shape[3]
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"= \S+\[[^\]]*\b%d\b[^\]]*\]\S* copy(-start)?\("
                           % capacity, line)]
    assert not copies, copies


def test_mamba2_decode_step_updates_the_state_in_place(one_chip,
                                                       monkeypatch):
    """At the benchmark cell's shape (mamba2-2.7b cut to 8 layers, 128
    requests, 1149 positions) the decode kernel reads and writes each
    layer's state once, in the donated cache's own buffer: no copy of the
    stacked state, and no staging buffer for a layer of it."""
    # off a TPU the program takes the kernel's interpreter; this compiles
    # for one, so the compiled kernel is steered in here
    monkeypatch.setattr(ops, "_auto_interpret", lambda interpret: False)
    monkeypatch.setattr(step_mod, "_SERVE_STEP_CACHE", {})
    cfg = dataclasses.replace(cfgs.get_config("mamba2-2.7b"), n_layers=8)
    compiled, cache = _decode_step(one_chip, 128, 1149, cfg)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2 ** 20, mem
    assert mem.temp_size_in_bytes < 2 ** 24, mem
    assert cache["state"].shape == (8, 128, 80, 64, 128)
    shape = r"f32\[8,128,80,64,128\]"
    lines = compiled.as_text().splitlines()
    copies = [line for line in lines
              if re.search(r"= %s\S* copy(-start)?\(" % shape, line)]
    assert not copies, copies
    # the state's one read and write is the kernel's call, named ssm_state
    calls = [re.search(r'op_name="([^"]*)"', line).group(1) for line in lines
             if re.search(shape, line) and "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert [p for p in calls[0].split("/")
            if p in ("ssm", "ssm_state")][-1] == "ssm_state", calls


def test_zamba2_decode_step_updates_the_state_in_place(one_chip,
                                                       monkeypatch):
    """zamba2-2.7b's state (N = 64) is not laid out row-major on the TPU,
    so its layers are updated by XLA in the carry: still no copy of the
    stacked state, and no relayout of it on the step's entry or exit."""
    monkeypatch.setattr(ops, "_auto_interpret", lambda interpret: False)
    monkeypatch.setattr(step_mod, "_SERVE_STEP_CACHE", {})
    compiled, cache = _decode_step(one_chip, 16, 1149,
                                   cfgs.get_config("zamba2-2.7b"))
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2 ** 20, mem
    assert mem.temp_size_in_bytes < 2 ** 24, mem
    shape = r"f32\[%s\]" % ",".join(map(str, cache["state"].shape))
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"= %s\S* copy(-start)?\(" % shape, line)]
    assert not copies, copies


def _nbytes(sds) -> int:
    return math.prod(sds.shape) * sds.dtype.itemsize


def _no_copy(compiled, shape):
    """No copy of an array of ``shape`` (the stacked cache) in ``compiled``."""
    pat = r"= \w+\[%s\]\S* copy(-start)?\(" % ",".join(map(str, shape))
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(pat, line)]
    assert not copies, copies


def test_qwen2_prefill_step_writes_the_cache_in_place(one_chip):
    """The prompt's launch at the benchmark cell's shape (128 requests of
    PREFILL_WIDTH positions, a 1149-position cache) writes its positions
    into the donated cache: every leaf aliased, no copy of the cache, and
    no more temporaries than one of its stacked keys or values."""
    compiled, cache = _decode_step(one_chip, 128, 1149,
                                   width=step_mod.PREFILL_WIDTH)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2 ** 20, mem
    assert mem.temp_size_in_bytes < _nbytes(cache["k"]), mem
    _no_copy(compiled, cache["k"].shape)


def test_mamba2_prefill_step_updates_the_state_in_place(one_chip,
                                                        monkeypatch):
    """The prompt's launch of mamba2-2.7b cut to 8 layers (128 requests of
    PREFILL_WIDTH positions) runs the chunked SSD from each layer's state and
    writes the final state back into the donated cache: no copy of the
    stacked state, and fewer temporaries than its size."""
    monkeypatch.setattr(ops, "_auto_interpret", lambda interpret: False)
    monkeypatch.setattr(step_mod, "_SERVE_STEP_CACHE", {})
    cfg = dataclasses.replace(cfgs.get_config("mamba2-2.7b"), n_layers=8)
    compiled, cache = _decode_step(one_chip, 128, 1149, cfg,
                                   width=step_mod.PREFILL_WIDTH)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 2 ** 20, mem
    assert mem.temp_size_in_bytes < _nbytes(cache["state"]), mem
    _no_copy(compiled, cache["state"].shape)
