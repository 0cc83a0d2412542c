"""Bring-up check: the main path on one TPU chip, through the user entry points.

    python chip_smoke.py

Phases, in order, each printing its own lines:

1. device gate — exits non-zero unless JAX's first device is a TPU;
2. training at full width: qwen2-0.5b, 4 steps at 4 x 1024 through
   ``repro.launch.train.run`` with the ``sim-v5e-air`` energy system;
3. greedy decode at full width through ``repro.serve.step.greedy_generate``;
4. the three Pallas kernels compiled for the chip (``interpret=False``),
   each compared with its pure-jnp oracle;
5. the jit-fused energy predictor against the plain numpy path, bitwise.

Any failing phase raises, so the script exits non-zero.  The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.  Random
weights and data come from ``--seed``.  One process holds the chip: the
script starts no subprocess.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
ENERGY_SYSTEM = "sim-v5e-air"
# tests/test_kernels.py tolerances, by input dtype
TOL = {"bfloat16": dict(rtol=5e-2, atol=5e-2),
       "float32": dict(rtol=2e-5, atol=2e-5)}


class PhaseFailed(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def device_gate():
    """The first JAX device, which must be a TPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"[gate] no TPU: JAX found {len(devs)} {dev.platform} "
              f"device(s) ({dev.device_kind})", file=sys.stderr)
        sys.exit(2)
    print(f"[gate] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}; using {dev}")
    return dev, len(devs)


def train_phase(arch: str = ARCH, *, smoke: bool = False, steps: int = 4,
                seq_len: int = 1024, global_batch: int = 4,
                seed: int = 0):
    """``run`` prints one line per step with its time: step 0 includes the
    compile; its last line is the monitor's ``live MAPE``."""
    from repro.launch.train import run
    t0 = time.perf_counter()
    _, losses, _ = run(arch, smoke=smoke, steps=steps, seq_len=seq_len,
                       global_batch=global_batch,
                       energy_system=ENERGY_SYSTEM, seed=seed)
    print(f"[train] run() wall time {time.perf_counter() - t0!r} s "
          f"(energy table, step profile and compile included)")
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    for i, loss in enumerate(losses):
        print(f"[train] loss[{i}] = {loss!r}")
    check(all(map(math.isfinite, losses)), f"non-finite loss in {losses}")
    return losses


def decode_phase(arch: str = ARCH, *, smoke: bool = False, batch: int = 2,
                 prompt_len: int = 16, max_new: int = 16, seed: int = 0):
    import jax
    import numpy as np
    from repro import configs as cfgs
    from repro.models import model as model_mod
    from repro.serve.step import greedy_generate
    cfg = cfgs.get_smoke_config(arch) if smoke else cfgs.get_config(arch)
    kp, kt = jax.random.split(jax.random.PRNGKey(seed))
    params = model_mod.init_params(cfg, kp)
    prompt = jax.random.randint(kt, (batch, prompt_len), 0, cfg.vocab,
                                dtype=jax.numpy.int32)
    t0 = time.perf_counter()
    out = greedy_generate(params, cfg, prompt, max_new=max_new,
                          max_seq=prompt_len + max_new)
    out = np.asarray(jax.block_until_ready(out))
    wall = time.perf_counter() - t0
    new = out[:, prompt_len:]
    check(out.shape == (batch, prompt_len + max_new), f"shape {out.shape}")
    check(np.array_equal(out[:, :prompt_len], np.asarray(prompt)),
          "prompt not echoed")
    in_range = int(((new >= 0) & (new < cfg.vocab)).sum())
    print(f"[decode] {cfg.name}: {batch} prompts x {prompt_len} tokens, "
          f"{new.size} new tokens, {in_range} in [0, {cfg.vocab}); "
          f"wall {wall!r} s (compile included)")
    print(f"[decode] new tokens: {new.tolist()}")
    check(in_range == new.size == batch * max_new, "token out of range")
    return new


def _compare(name: str, got, want, dtype: str) -> None:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} vs "
                                   f"{want.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite output")
    err = np.abs(got - want)
    tol = TOL[dtype]
    bound = tol["atol"] + tol["rtol"] * np.abs(want)
    worst = float(np.max(err - bound))
    print(f"[kernel] {name}: max abs err {float(err.max())!r}, "
          f"rtol={tol['rtol']} atol={tol['atol']}: "
          f"{'within' if worst <= 0 else 'OUT OF'} tolerance")
    check(worst <= 0, f"{name} outside tolerance")


def kernel_phase(*, interpret: bool = False, seq: int = 4096,
                 cache: int = 32768, heads: int = 14, kv_heads: int = 2,
                 head_dim: int = 64, ssd_heads: int = 80, ssd_p: int = 64,
                 ssd_n: int = 128, chunk: int = 256, seed: int = 0):
    """Flash at qwen2-0.5b widths, decode over a long cache, SSD at
    mamba2-2.7b widths, each against its oracle.  The oracles see the same
    values upcast to float32 and run at the highest matmul precision, so
    the error measured is the kernel's."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.models.ssm import ssd_chunked_ref
    bf16 = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 12))

    def oracle(fn, *args, **kw):
        args = [x.astype(jnp.float32) if x.dtype == bf16 else x
                for x in args]
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: fn(*a, **kw))(*args)

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        print(f"[kernel] {name}: first call (compile included) "
              f"{time.perf_counter() - t0!r} s, interpret={interpret}")
        return out

    shape = (1, seq, heads, head_dim)
    q, k, v = (jax.random.normal(next(ks), shape, bf16) for _ in range(3))
    got = timed("flash_attention",
                lambda *a: ops.flash_attention(*a, causal=True,
                                               interpret=interpret), q, k, v)
    _compare(f"flash_attention [1,{seq},{heads},{head_dim}] bf16", got,
             oracle(ref.flash_attention_ref, q, k, v, causal=True),
             "bfloat16")

    b = 2
    qd = jax.random.normal(next(ks), (b, heads, head_dim), bf16)
    kc = jax.random.normal(next(ks), (b, cache, kv_heads, head_dim), bf16)
    vc = jax.random.normal(next(ks), (b, cache, kv_heads, head_dim), bf16)
    lengths = jnp.array([cache, cache // 3 + 1], jnp.int32)
    got = timed("decode_attention",
                lambda *a: ops.decode_attention(*a, interpret=interpret),
                qd, kc, vc, lengths)
    _compare(f"decode_attention KV={kv_heads} G={heads // kv_heads} "
             f"D={head_dim} cache={cache} bf16", got,
             oracle(ref.decode_attention_ref, qd, kc, vc, lengths),
             "bfloat16")

    x = jax.random.normal(next(ks), (1, seq, ssd_heads, ssd_p), bf16)
    dt = jax.nn.softplus(jax.random.normal(next(ks), (1, seq, ssd_heads)))
    a = -jnp.exp(jax.random.normal(next(ks), (ssd_heads,)) * 0.3)
    bm = jax.random.normal(next(ks), (1, seq, ssd_n), bf16)
    cm = jax.random.normal(next(ks), (1, seq, ssd_n), bf16)
    y, st = timed("ssd_chunked",
                  lambda *a_: ops.ssd_chunked(*a_, chunk=chunk,
                                              interpret=interpret),
                  x, dt, a, bm, cm)
    y_ref, st_ref = oracle(ssd_chunked_ref, x, dt, a, bm, cm, chunk=chunk)
    name = f"ssd_chunked H={ssd_heads} P={ssd_p} N={ssd_n} chunk={chunk}"
    _compare(f"{name} y", y, y_ref, "bfloat16")
    _compare(f"{name} state", st, st_ref, "bfloat16")


def fused_predictor_phase(n_jobs: int = 64, seed: int = 11):
    """Fused vs plain predictor on one table: every total bitwise equal."""
    import numpy as np
    from repro.api import EnergyModel
    from repro.core import isa
    from repro.core.counting import OpCounts
    from repro.core.predict import TablePredictor
    table = EnergyModel.from_store(ENERGY_SYSTEM).table
    plain, fused = TablePredictor(table), TablePredictor(table, fused=True)
    check(fused.enable_fused(), "enable_fused() returned False")
    rng = np.random.default_rng(seed)
    names = [c.name for c in isa.OP_CLASSES]
    programs, durations, counters = [], [], []
    for _ in range(n_jobs):
        c = OpCounts()
        for cls in rng.choice(names, size=int(rng.integers(8, 28)),
                              replace=False):
            c.add(str(cls), float(rng.uniform(1e3, 1e9)))
        c.boundary_read_bytes = float(rng.uniform(1e6, 1e10))
        c.boundary_write_bytes = float(rng.uniform(1e6, 1e10))
        c.fused_bytes = float(rng.uniform(1e6, 1e10))
        programs.append(c)
        durations.append(float(rng.uniform(0.5, 30.0)))
        counters.append({"hbm_read_bytes": float(rng.uniform(1e6, 1e10)),
                         "hbm_write_bytes": float(rng.uniform(1e6, 1e10))})
    for mode in ("pred", "direct"):
        for ctrs in (None, counters):
            a = plain.predict_batch(programs, durations, ctrs, mode=mode)
            b = fused.predict_batch(programs, durations, ctrs, mode=mode)
            same = all(pa.total_j == pb.total_j
                       and pa.dynamic_j == pb.dynamic_j
                       and pa.coverage == pb.coverage
                       and np.array_equal(pa.class_energy_vec,
                                          pb.class_energy_vec)
                       for pa, pb in zip(a, b))
            print(f"[fused] {n_jobs} jobs, mode={mode}, counters="
                  f"{'given' if ctrs else 'static'}: totals "
                  f"{'bitwise equal' if same else 'DIFFER'} "
                  f"(sum {sum(p.total_j for p in b)!r} J)")
            check(same, f"fused totals differ from plain ({mode})")
    print(f"[fused] kernel ran on {fused.fused_device}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev, count = device_gate()
    import jax
    from repro.launch.compile_cache import use_compile_cache
    events = {"hits": 0, "misses": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"[cache] persistent compilation cache at {use_compile_cache()}")

    t0 = time.perf_counter()
    with jax.default_device(dev):
        train_phase(seed=args.seed)
        print(f"[mem] peak_bytes_in_use after training "
              f"{dev.memory_stats()['peak_bytes_in_use']}")
        decode_phase(seed=args.seed)
        kernel_phase(seed=args.seed)
        fused_predictor_phase()
    print(f"[cache] persistent cache hits {events['hits']}, "
          f"misses {events['misses']}")
    print(f"[smoke] all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
