"""The chip smoke script and the entry-point plumbing around it, on the CPU:
the device gate, every phase at a tiny size (kernels in interpret mode),
the compile-cache placement and the training CLI's exit code."""
import contextlib
import importlib.util
import json
import math
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import train as train_mod

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gate_refuses_a_host_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "cpu" in r.stderr
    assert '"ok"' not in r.stdout


def test_phases_rehearse_at_tiny_size(smoke):
    losses = smoke.train_phase(smoke=True, steps=2, seq_len=32,
                               global_batch=2)
    assert len(losses) == 2 and all(map(math.isfinite, losses))
    new = smoke.decode_phase(smoke=True, prompt_len=4, max_new=4)
    assert new.shape == (2, 4)
    smoke.kernel_phase(interpret=True, seq=128, cache=512, heads=4,
                       kv_heads=2, head_dim=64, ssd_heads=2, ssd_p=16,
                       ssd_n=16, chunk=32)
    smoke.fused_predictor_phase()


def test_phase_failure_raises(smoke):
    with pytest.raises(smoke.PhaseFailed, match="outside tolerance"):
        smoke._compare("k", [1.0, 2.0], [1.0, 2.5], "float32")


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("losses,rc", [([5.0, 4.0], 0), ([5.0, 6.0], 0),
                                       ([5.0, float("nan")], 1)])
def test_train_cli_fails_on_non_finite_loss(monkeypatch, capsys, losses, rc):
    monkeypatch.setattr(train_mod, "use_compile_cache", lambda: None)
    monkeypatch.setattr(train_mod, "run",
                        lambda *a, **k: (None, list(losses), None))
    assert train_mod.main(["--steps", "2"]) == rc
    assert "[train] loss" in capsys.readouterr().out


def test_smoke_last_line_is_the_result_object(smoke, monkeypatch, capsys):
    """With every phase stubbed, main prints the contract's last line."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 1}

    monkeypatch.setattr(smoke, "device_gate", lambda: (Dev(), 1))
    for phase in ("train_phase", "decode_phase", "kernel_phase",
                  "fused_predictor_phase"):
        monkeypatch.setattr(smoke, phase, lambda **_: None)
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "x")
    monkeypatch.setattr(jax, "default_device",
                        lambda d: contextlib.nullcontext())
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda cb: None)
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
