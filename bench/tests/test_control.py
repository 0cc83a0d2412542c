"""The control and the planted faults, at tiny sizes on the CPU.

The control (the reference in float8 in the program's place) and each fault
a cell can have must come out as not correct.  On the chip the same is
read at the cells' own sizes by ``control.py``; here a test run can hold
it.
"""
import jax
import pytest

import control
import run
from conftest import TINY_LIMITS, fake_gate


def _exceeds(readings, limits):
    return any(readings[k] > v for k, v in limits.items())


@pytest.mark.parametrize("config", ["tiny", "tinym"])
@pytest.mark.parametrize("kind", ["train", "decode"])
def test_control_fails_and_program_passes(tiny_root, config, kind):
    # a window of 0 s makes exactly one call (or step): the requests
    # compared do not depend on how fast this machine is
    r = control.readings(f"{config}.{kind}", 1002, 0.0, tiny_root,
                         fake_gate)
    limits = TINY_LIMITS[f"{config}.{kind}"]
    assert not _exceeds(r["program"], limits), r
    assert _exceeds(r["control"], limits), r
    if kind == "train":
        assert _exceeds(r["half_batch"], limits), r


def _run_correct(root, workload, capsys):
    import json
    rc = run.main(["--workload", workload, "--seed", "77", "--seconds",
                   "0.3"], root=root, gate=fake_gate)
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1])["correct"]


def test_fault_train_state_unchanged(tiny_root, capsys, monkeypatch):
    from repro.train import step as step_mod
    real = step_mod.make_train_step

    def broken(*a, **k):
        fn = real(*a, **k)
        return lambda state, batch: (state, fn(state, batch)[1])

    monkeypatch.setattr(step_mod, "make_train_step", broken)
    assert not _run_correct(tiny_root, "tiny.train", capsys)


def test_fault_train_half_batch(tiny_root, capsys, monkeypatch):
    from repro.train import step as step_mod
    real = step_mod.make_train_step

    def broken(*a, **k):
        fn = real(*a, **k)
        return lambda state, batch: fn(state, jax.tree.map(
            lambda x: x[: x.shape[0] // 2], batch))

    monkeypatch.setattr(step_mod, "make_train_step", broken)
    assert not _run_correct(tiny_root, "tiny.train", capsys)


def test_fault_decode_token_altered(tiny_root, capsys, monkeypatch):
    from repro.serve import step as serve_mod
    real = serve_mod.jitted_serve_step

    def broken(cfg, attn_fn=None):
        fn = real(cfg, attn_fn)

        def step(params, cache, tok):
            nxt, cache = fn(params, cache, tok)
            return (nxt + 1) % cfg.vocab, cache
        return step

    monkeypatch.setattr(serve_mod, "jitted_serve_step", broken)
    assert not _run_correct(tiny_root, "tiny.decode", capsys)


def test_fault_decode_state_unchanged(tiny_root, capsys, monkeypatch):
    from repro.serve import step as serve_mod
    real = serve_mod.jitted_serve_step

    def broken(cfg, attn_fn=None):
        fn = real(cfg, attn_fn)
        return lambda params, cache, tok: (fn(params, cache, tok)[0], cache)

    monkeypatch.setattr(serve_mod, "jitted_serve_step", broken)
    assert not _run_correct(tiny_root, "tiny.decode", capsys)
