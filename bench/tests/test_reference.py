"""The plain reference against the program's own model, on the CPU at a
tiny size, with both in float32: what is left is summation order."""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import check
import drive
import weights

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def _ref(name="qwen2-0.5b"):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_").replace(".", "_"),
        BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _f32_program_and_weights(c, ref, seed=4):
    pcfg = dataclasses.replace(drive.program_config(c), dtype="float32",
                               remat=False)
    lay = {k: (s, "float32", *rest)
           for k, (s, _, *rest) in ref.layout(c).items()}
    flat = weights.make(lay, seed)
    return pcfg, weights.nest(flat)


def test_qwen2_reference_matches_program_forward_float32():
    from repro.models import model as model_mod
    ref, c = _ref(), json.loads((DATA / "tiny-qwen2.json").read_text())
    pcfg, w = _f32_program_and_weights(c, ref)
    toks = weights.tokens(1, 0, (2, 24), c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog, _ = model_mod.forward(w, {"tokens": jnp.asarray(toks)}, pcfg)
    for r in range(2):
        want = ref.logits(w, jnp.asarray(toks[r]), c)
        # float32 on both sides: differences are summation order, ~1e-6
        # of logits of order 1
        np.testing.assert_allclose(np.asarray(prog[r]), np.asarray(want),
                                   rtol=0, atol=2e-5)


def test_qwen2_reference_loss_matches_program_loss_float32():
    from repro.models import model as model_mod
    ref, c = _ref(), json.loads((DATA / "tiny-qwen2.json").read_text())
    pcfg, w = _f32_program_and_weights(c, ref)
    toks = weights.tokens(2, 0, (2, 33), c["vocab_size"])
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "targets": jnp.asarray(toks[:, 1:])}
    with jax.default_matmul_precision("highest"):
        prog, _ = model_mod.loss_fn(w, batch, pcfg)
    ce = z = 0.0
    for r in range(2):
        a, b = ref.loss_sums(w, batch["tokens"][r], batch["targets"][r], c)
        ce, z = ce + float(a), z + float(b)
    n = toks.shape[0] * (toks.shape[1] - 1)
    # the program's objective: mean cross-entropy + 1e-4 mean lse^2;
    # float32 on both sides, summation order only
    assert abs(float(prog) - (ce / n + 1e-4 * z / n)) < 1e-5


def test_mamba2_sequential_reference_matches_program_chunked_float32():
    """The program's chunked SSD (chunk 8 over 32 positions) and the
    reference's position-by-position recurrence, over a whole forward."""
    from repro.models import model as model_mod
    ref = _ref("mamba2-2.7b")
    c = json.loads((DATA / "tiny-mamba2.json").read_text())
    pcfg, w = _f32_program_and_weights(c, ref)
    toks = weights.tokens(3, 0, (2, 32), c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog, _ = model_mod.forward(w, {"tokens": jnp.asarray(toks)}, pcfg)
    for r in range(2):
        want = ref.logits(w, jnp.asarray(toks[r]), c)
        # float32 on both sides; the chunked form sums the same terms in
        # another order (exp of cumulative sums), ~1e-5 of logits ~1
        np.testing.assert_allclose(np.asarray(prog[r]), np.asarray(want),
                                   rtol=0, atol=1e-4)
    # the training reference's blocked, rematerialised scan is the same
    # recurrence: equal to the plain scan to float32 rounding
    k = iter(jax.random.split(jax.random.PRNGKey(0), 5))
    s, h, p, n = 2 * ref.SCAN_BLOCK, 3, 4, 5
    x = jax.random.normal(next(k), (s, h, p))
    dt = jax.nn.softplus(jax.random.normal(next(k), (s, h)))
    a = -jnp.exp(jax.random.normal(next(k), (h,)))
    b, cm = (jax.random.normal(next(k), (s, n)) for _ in range(2))
    np.testing.assert_allclose(
        np.asarray(ref._recurrence(x, dt, a, b, cm, remat=True)),
        np.asarray(ref._recurrence(x, dt, a, b, cm, remat=False)),
        rtol=1e-6, atol=1e-6)


def test_fp8_rounding_is_coarser_than_bfloat16():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    e8 = float(jnp.max(jnp.abs(check.fp8(x) - x) / jnp.abs(x).max()))
    e16 = float(jnp.max(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32)
                                - x) / jnp.abs(x).max()))
    assert e8 > 4 * e16
