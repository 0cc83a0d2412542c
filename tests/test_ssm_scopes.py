"""The decode step names the Mamba-2 mixer ``ssm`` and every op that reads,
updates, shifts or writes back the recurrent or conv state ``ssm_state``,
which is what the benchmark's ``ssm_state_share.decode`` reads out of a
device trace; and the names change nothing that runs."""
import contextlib
import os
import pathlib
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.extend import core as jex_core

from repro import configs as cfgs
from repro.models import model as model_mod
from repro.models import ssm as ssm_mod
from repro.serve import step as step_mod

# the names of the program's scopes
SCOPES = ("embed", "attn", "kv_cache", "mlp", "lm_head", "ssm", "ssm_state")
BATCH, MAX_SEQ = 2, 8


def _innermost(stack: str) -> str:
    return next((p for p in reversed(stack.split("/")) if p in SCOPES), "")


def _ops(cfg):
    """(kind, result dims, name stack) of each op of the compiled serve step
    that carries a name stack."""
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    cache = model_mod.init_cache(cfg, BATCH, MAX_SEQ)
    tokens = jnp.zeros((BATCH, 1), jnp.int32)
    text = jax.jit(step_mod.make_serve_step(cfg)).lower(
        params, cache, tokens).compile().as_text()
    out = []
    for line in text.splitlines():
        op = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                      r"([\w\-]+)\(", line)
        stack = re.search(r'op_name="([^"]*)"', line)
        if op and stack:
            dims = tuple(int(d) for d in op.group(1).split(",") if d)
            out.append((op.group(2), dims, stack.group(1)))
    return out


def _subjaxprs(params):
    for value in params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def _kernel_stacks(cfg):
    """The name stack of each Pallas call of the serve step, with the
    stacks of the loops and calls around it: on the chip each call is one
    custom call, and its op carries this stack."""
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    cache = model_mod.init_cache(cfg, BATCH, MAX_SEQ)
    closed = jax.make_jaxpr(step_mod.make_serve_step(cfg))(
        params, cache, jnp.zeros((BATCH, 1), jnp.int32))
    stacks = []

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            own = str(eqn.source_info.name_stack)
            stack = "/".join(p for p in (outer, own) if p)
            if eqn.primitive.name == "pallas_call":
                stacks.append(stack)
                continue
            for sub in _subjaxprs(eqn.params):
                walk(sub, stack)

    walk(closed.jaxpr, "")
    return stacks


@pytest.fixture(scope="module")
def mamba():
    cfg = cfgs.get_smoke_config("mamba2-2.7b")
    d_in, h, n = ssm_mod.ssm_dims(cfg)
    state = (BATCH, h, cfg.ssm_head_dim, n)
    conv = (BATCH, cfg.ssm_conv - 1, d_in + 2 * n)
    return cfg, state, conv, _ops(cfg), _kernel_stacks(cfg)


def _scopes_of(ops, kind, dims):
    return {_innermost(s) for k, d, s in ops if k == kind and d == dims}


def test_mamba2_step_names_the_state_ops(mamba):
    cfg, state, conv, ops, kernels = mamba
    layers = cfg.n_layers
    # the state's one read and write, in place in the stacked cache: the
    # kernel's call inside the mixer (a custom call on the chip)
    assert len(kernels) == 1, kernels
    assert _innermost(kernels[0]) == "ssm_state" and "/ssm/" in kernels[0], \
        kernels
    # the write-back of each layer's conv window into the stacked cache
    assert _scopes_of(ops, "dynamic-update-slice", (layers,) + conv) == \
        {"ssm_state"}
    # the conv window: the new input joined to the state, then shifted
    window = conv[:1] + (conv[1] + 1,) + conv[2:]
    assert _scopes_of(ops, "concatenate", window) == {"ssm_state"}
    assert _scopes_of(ops, "slice", conv) == {"ssm_state"}
    # the recurrence, inside the mixer
    rec = [s for k, d, s in ops if k in ("multiply", "add") and d == state]
    assert rec and all(_innermost(s) == "ssm_state" and "/ssm/" in s
                       for s in rec), rec
    # no op with the state's shape, per layer or stacked, goes unnamed
    shaped = {state, (1,) + state, (layers,) + state, conv, (1,) + conv,
              (layers,) + conv}
    stray = [(k, d, s) for k, d, s in ops if d in shaped
             and k != "parameter" and _innermost(s) != "ssm_state"]
    assert not stray, stray


def test_mamba2_step_names_the_mixer(mamba):
    cfg, _, _, ops, _ = mamba
    d_in, h, n = ssm_mod.ssm_dims(cfg)
    # the input projection to z, x, B, C and dt
    assert _scopes_of(ops, "dot", (BATCH, 2 * d_in + 2 * n + h)) == {"ssm"}
    assert "lm_head" in {_innermost(s) for _, _, s in ops}


def test_qwen2_step_carries_no_ssm_scope():
    ops = _ops(cfgs.get_smoke_config("qwen2-0.5b"))
    names = {p for _, _, s in ops for p in s.split("/")}
    assert "attn" in names
    assert not names & {"ssm", "ssm_state"}


def test_only_the_ssm_decode_step_imports_pallas():
    """Pallas takes seconds to import, paid in set-up: lowering qwen2's
    serve step leaves it unimported, and the SSM decode branch alone,
    which calls the state-update kernel, brings it in."""
    code = textwrap.dedent("""
        import sys
        import jax
        import jax.numpy as jnp
        from repro import configs as cfgs
        from repro.models import model as model_mod
        from repro.serve.step import make_serve_step

        def lowered_with_pallas(arch):
            cfg = cfgs.get_smoke_config(arch)
            params = jax.eval_shape(
                lambda k: model_mod.init_params(cfg, k), jax.random.PRNGKey(0))
            cache = jax.eval_shape(lambda: model_mod.init_cache(cfg, 2, 8))
            jax.jit(make_serve_step(cfg)).lower(
                params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32))
            return "jax.experimental.pallas" in sys.modules

        print(lowered_with_pallas("qwen2-0.5b"),
              lowered_with_pallas("mamba2-2.7b"))
    """)
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    path = os.pathsep.join(filter(None, [str(src),
                                         os.environ.get("PYTHONPATH")]))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["False", "True"], r.stdout


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "qwen2-0.5b"])
def test_scopes_change_no_arithmetic(arch, monkeypatch):
    """The serve step lowers to the same modules with every scope taken
    out, at the decode launch's width and the prompt's, and
    ``greedy_generate`` gives the same tokens bit for bit."""
    cfg = cfgs.get_smoke_config(arch)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(1))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (BATCH, 3), 0,
                                cfg.vocab, jnp.int32)
    cache = model_mod.init_cache(cfg, BATCH, MAX_SEQ)
    chunk = jnp.zeros((BATCH, step_mod.PREFILL_WIDTH), jnp.int32)

    def lowered():
        step = jax.jit(step_mod.make_serve_step(cfg))
        return [step.lower(params, cache, tokens).as_text()
                for tokens in (prompt[:, :1], (chunk, jnp.int32(2)))]

    def generate():
        return np.asarray(step_mod.greedy_generate(params, cfg, prompt, 4,
                                                   MAX_SEQ))

    named, tokens = lowered(), generate()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(ssm_mod, "ssm_forward",
                        ssm_mod.ssm_forward.__wrapped__)
    monkeypatch.setattr(step_mod, "_SERVE_STEP_CACHE", {})
    plain = lowered()
    assert "ssm_state" not in jax.jit(step_mod.make_serve_step(cfg)).lower(
        params, cache, prompt[:, :1]).as_text(debug_info=True)
    assert plain == named
    np.testing.assert_array_equal(generate(), tokens)
