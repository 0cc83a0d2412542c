"""Multi-device tests (8 host CPU devices via subprocess — jax locks the
device count at first init, so each scenario runs in its own process)."""
import subprocess
import sys
import textwrap
import os

import pytest

pytestmark = pytest.mark.slow   # heavy model/distributed tier

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_compressed_psum_close_to_exact():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, functools
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.parallel.compression import compressed_psum

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 512))

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data", None),
                       out_specs=P("data", None))
    def mean_compressed(xl):
        return compressed_psum(xl / 8.0, "data")

    got = mean_compressed(x)
    want = jnp.broadcast_to(x.mean(0, keepdims=True), x.shape)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    assert err / scale < 0.02, (err, scale)
    print("ok", err)
    """)


def test_error_feedback_converges():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, functools
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.parallel.compression import make_error_feedback

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    step = make_error_feedback()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256)) * 0.01

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data", None), P("data", None)),
                       out_specs=(P("data", None), P("data", None)))
    def run(xl, res):
        out, new_res = step(xl, res, "data")
        return out, new_res

    res = jnp.zeros_like(x)
    acc_c = jnp.zeros((1, 256))
    acc_t = jnp.zeros((1, 256))
    for i in range(30):
        out, res = run(x, res)
        acc_c = acc_c + out[:1]
        acc_t = acc_t + jnp.sum(x, 0, keepdims=True)
    # error feedback: accumulated compressed sums track the true sums
    rel = float(jnp.linalg.norm(acc_c - acc_t) / jnp.linalg.norm(acc_t))
    assert rel < 0.01, rel
    print("ok", rel)
    """)


def test_pjit_train_step_on_mesh_and_elastic_reshard():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs as cfgs
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.models.layers import sds_from_specs
    from repro.parallel import sharding as sh
    from repro.train import optimizer as opt_mod
    from repro.train.elastic import reshard
    from repro.train.step import init_state, make_train_step

    cfg = cfgs.get_smoke_config("qwen2-0.5b")
    mesh = make_mesh((2, 4), ("data", "model"))
    opt_cfg = opt_mod.OptConfig()
    specs = M.model_specs(cfg)
    with mesh:
        state = init_state(cfg, opt_cfg, jax.random.PRNGKey(0))
        state = jax.device_put(
            state.params, sh.param_shardings(specs, mesh)), state.opt
        from repro.train.step import TrainState
        state = TrainState(params=state[0], opt=state[1])
        step = jax.jit(make_train_step(cfg, opt_cfg))
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "targets": jnp.zeros((8, 16), jnp.int32)}
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))

    # elastic: shrink to a 4-device mesh, step again
    mesh2 = make_mesh((1, 4), ("data", "model"))
    with mesh2:
        p2 = reshard(jax.device_get(state.params), specs, mesh2)
        from repro.train.optimizer import init_opt_state
        state2 = TrainState(params=p2, opt=init_opt_state(p2, opt_cfg))
        step2 = jax.jit(make_train_step(cfg, opt_cfg))
        state2, m2 = step2(state2, batch)
        assert np.isfinite(float(m2["loss"]))
    print("ok")
    """)


def test_hlo_collective_accounting_on_real_compile():
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.hlo import collective_bytes
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))

    def step(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), ()
        h, _ = jax.lax.scan(body, x, ws)
        return h.sum()

    xs = jax.ShapeDtypeStruct((256, 512), jnp.float32,
                              sharding=NamedSharding(mesh, P("data", None)))
    ws = jax.ShapeDtypeStruct((6, 512, 512), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, None,
                                                             "model")))
    compiled = jax.jit(step).lower(xs, ws).compile()
    st = collective_bytes(compiled.as_text())
    # the scanned loop body must be multiplied by its trip count (6)
    assert any(abs(v - 6.0) < 0.5 for v in st.while_trips.values()), \\
        st.while_trips
    assert st.wire_bytes_per_chip > 0
    print("ok", st.by_kind)
    """)


def test_pipeline_parallel_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import pipeline_forward
    mesh = make_mesh((4,), ("stage",))
    S, M, mb, d = 4, 6, 2, 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.3
    def stage_fn(w, x):
        return jnp.tanh(x @ w)
    xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
    out = pipeline_forward(stage_fn, ws, xs, mesh)
    ref = xs
    for s in range(S):
        ref = jax.vmap(lambda x: stage_fn(ws[s], x))(ref)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, err
    print("ok", err)
    """)


def test_dryrun_variants_build_on_small_mesh():
    _run("""
    import jax
    from repro.launch.dryrun import build_cell
    from repro.launch.mesh import make_mesh
    from repro.core.opcount import count_fn
    mesh = make_mesh((2, 4), ("data", "model"))
    for variant in ("baseline", "zero1", "moe-index", "serve-repl"):
        for arch, shape in (("qwen2-0.5b", "train_4k"),
                            ("arctic-480b", "decode_32k")):
            fn, args, mf = build_cell(arch, shape, mesh, variant=variant)
            c = count_fn(fn, *args)
            assert c.flops > 0
    print("ok")
    """)


def test_opcount_shard_map_collectives():
    _run("""
    import jax, jax.numpy as jnp, functools
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.opcount import count_fn

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data", None),
                       out_specs=P("data", None))
    def fn(x):
        return jax.lax.psum(x, "data")

    c = count_fn(fn, jax.ShapeDtypeStruct((8, 1024), jnp.float32))
    want = 2 * (1024 * 4) * 7 / 8     # 2(n-1)/n x local bytes
    got = c.units.get("ici.all_reduce", 0.0)
    assert abs(got - want) / want < 0.01, (got, want)
    print("ok", got)
    """)
