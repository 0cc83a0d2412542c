"""The general drivers: one per kind of mix, each reading its parameters from
the mix's data file.

- ``train``: set-up builds the program's jitted train step and its state
  from the seed and drives it through its first three steps, reading the
  loss, the first gradient and the change of the parameters; the window
  hands that same step and state on, one step after another on distinct
  rows, each registered with the program's live energy monitor, and ends
  with the monitor's bill.  This is the loop of ``launch.train.run``
  written out, since ``run`` builds its own step and state on every call
  and cannot be handed those whose first steps were checked: a change to
  ``run``'s loop does not show in a train cell until ``run`` can take them.
- ``decode``: a closed loop of ``greedy_generate`` calls on prompts drawn
  from the seed, after a short call on the same cache as warm-up; a traced
  run traces one more call after the window.

Each returns a ``Run``: what was attempted and failed, the end-to-end
values, the facts the per-layer readers take, the numbers compared for
``correct``, and the device's memory peak.  The reference runs once the
window has closed and the program's state is freed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import time
from pathlib import Path
from typing import Callable, Dict, Optional

import numpy as np

import check
import devtrace as trace_mod
import weights


@dataclasses.dataclass
class Cell:
    name: str
    config: dict                 # the configuration file
    ref: object                  # its reference module
    mix: dict                    # the traffic mix file
    seed: int
    seconds: float
    traced: bool
    out_dir: Path                # scratch inside the checkout


@dataclasses.dataclass
class Run:
    attempted: int = 0
    failed: int = 0
    values: Dict[str, float] = dataclasses.field(default_factory=dict)
    facts: Dict[str, object] = dataclasses.field(default_factory=dict)
    readings: Dict[str, float] = dataclasses.field(default_factory=dict)
    notes: Dict[str, str] = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    trace: Optional[dict] = None
    extra: Dict[str, object] = dataclasses.field(default_factory=dict)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)


def program_config(c: dict):
    """The program's configuration for this file: the repo's arch, with the
    file's cuts applied as overrides."""
    from repro import configs
    base = configs.get_config(c["program"]["arch"])
    over = c["program"].get("overrides") or {}
    return dataclasses.replace(base, **over) if over else base


def _program_params(cell: Cell, pcfg):
    """The weights, made from the seed, in the program's tree; the tree has
    to match the program's own parameter shapes leaf for leaf."""
    import jax
    from repro.models import model as model_mod
    flat = weights.make(cell.ref.layout(cell.config), cell.seed)
    want = {k: (tuple(v.shape), str(v.dtype))
            for k, v in weights.flatten(model_mod.params_sds(pcfg)).items()}
    got = {k: (tuple(v.shape), str(v.dtype)) for k, v in flat.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"reference layout and program tree differ: {diff}")
    return weights.nest(flat)


def _memory_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


@contextlib.contextmanager
def _traced(cell: Cell, run: Run):
    """Profile the block when the run is traced, and reduce the trace."""
    import jax
    if not cell.traced:
        yield
        return
    tdir = cell.out_dir / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0         # host spans only, no Python calls
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with span("window"):
            yield
    finally:
        jax.profiler.stop_trace()
    run.trace = trace_mod.reduce_dir(tdir)


_LOWERED: list = []
_HOOKED = False


def _lowerings() -> list:
    """A list to which every program that JAX lowers from now on adds its
    event: a window that finds one there compiled or loaded a program."""
    global _HOOKED
    import jax
    if not _HOOKED:
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_: event.endswith(
                "jaxpr_to_mlir_module_duration") and _LOWERED.append(event))
        _HOOKED = True
    return _LOWERED


def _free(*trees) -> None:
    import jax
    for t in trees:
        for x in jax.tree.leaves(t):
            if hasattr(x, "delete"):
                x.delete()
    gc.collect()


# ---------------------------------------------------------------------------
# Training.
# ---------------------------------------------------------------------------
def train(cell: Cell) -> Run:
    import jax
    import jax.numpy as jnp
    from repro.api import EnergyModel
    from repro.core.opcount import count_fn
    from repro.train import optimizer as opt_mod
    from repro.train.step import TrainState, make_train_step

    c, mix, run = cell.config, cell.mix, Run()
    pcfg = program_config(c)
    b, s, vocab = mix["batch"], mix["seq_len"], c["vocab_size"]
    opt = dict(mix["optimizer"])
    opt_cfg = opt_mod.OptConfig(**opt, mv_dtype=pcfg.optimizer_dtype,
                                master_fp32=pcfg.optimizer_dtype == "float32")

    def batch(i: int) -> Dict[str, np.ndarray]:
        tok = weights.tokens(cell.seed, 10 + i, (b, s + 1), vocab)
        return {"tokens": tok[:, :-1], "targets": tok[:, 1:]}

    with span("setup/weights"):
        params = _program_params(cell, pcfg)
        state = TrainState(params, jax.jit(
            lambda p: opt_mod.init_opt_state(p, opt_cfg))(params))
    step_fn = jax.jit(make_train_step(pcfg, opt_cfg), donate_argnums=(0,))

    def one(state, i: int, live=None):
        with span("feed"):
            bt = {k: jnp.asarray(v) for k, v in batch(i).items()}
        t0 = time.perf_counter()
        with span("step"):
            state, met = step_fn(state, bt)
        with span("loss_sync"):
            loss = float(met["loss"])
        dt = time.perf_counter() - t0
        if live is not None:
            with span("monitor_step"):
                live.step(i, duration_s=dt, work_units=b * s)
        return state, loss, met

    # set-up: the first three steps, read for the comparison
    losses, walls = [], []
    for i in range(3):
        t0 = time.perf_counter()
        with span(f"setup/step{i}"):
            state, loss, met = one(state, i)
        walls.append(time.perf_counter() - t0)
        losses.append(loss)
        if i == 0:
            gnorm = float(met["grad_norm"])
            scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
            # mu after one step is (1 - b1) * scale * g
            grad = {k: v / ((1 - opt["b1"]) * scale) for k, v in
                    check.leaf_norms(state.opt["mu"]).items()}
    init = weights.nest(weights.make(cell.ref.layout(c), cell.seed))
    delta = check.change_norms(state.opt.get("master", state.params), init)
    _free(init)
    prog = {"losses": losses, "grad": grad, "delta": delta}

    with span("setup/energy"):
        counts = count_fn(make_train_step(pcfg, opt_cfg), state,
                          {k: jnp.asarray(v) for k, v in batch(0).items()})
        energy = EnergyModel.from_store(mix["energy_system"])
    steps = max(1, round(cell.seconds / min(walls[1:])))

    def window(first: int, n: int, metered: bool):
        nonlocal state
        mon = (energy.monitor(live=True, step_counts=counts,
                              telemetry_chunk=mix["telemetry_chunk"])
               if metered else None)
        bad = 0
        t0 = time.perf_counter()
        for i in range(first, first + n):
            state, loss, _ = one(state, i, mon.live if mon else None)
            bad += not math.isfinite(loss)
        if mon is not None:
            with span("bill"):
                mon.live.finish()
        return time.perf_counter() - t0, bad

    lowered = _lowerings()
    run.facts["setup_end"] = time.perf_counter()
    lowered.clear()
    wall, bad = window(3, steps, metered=True)
    run.notes["programs lowered in the window"] = str(len(lowered))
    run.attempted, run.failed = steps, bad
    run.values["train_tokens_per_s"] = steps * b * s / wall
    run.facts.update(kind="train", steps=steps, window_s=wall,
                     tokens_per_s=run.values["train_tokens_per_s"],
                     flops_per_token=cell.ref.train_flops_per_token(c, s))
    if cell.traced:
        nxt = 3 + steps
        off, _ = window(nxt, steps, metered=False)
        run.facts["unmetered_window_s"] = off
        with _traced(cell, run):
            window(nxt + steps, max(1, steps // 3), metered=True)

    run.memory_peak_bytes = _memory_peak()
    _free(state)
    del state
    ref = check.train_reference(
        cell.ref, c, lambda: weights.make(cell.ref.layout(c), cell.seed),
        [batch(i) for i in range(3)], {**opt, **mix["objective"]})
    run.extra.update(ref=ref, batches=[batch(i) for i in range(3)])
    readings, where = check.train_readings(prog, ref)
    run.readings.update(readings)
    run.notes.update(where)
    return run


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------
def decode(cell: Cell) -> Run:
    import jax
    import jax.numpy as jnp
    from repro.serve.step import greedy_generate

    c, mix, run = cell.config, cell.mix, Run()
    pcfg = program_config(c)
    b, p, new, cap = mix["batch"], mix["prompt"], mix["new_tokens"], \
        mix["cache"]
    vocab = c["vocab_size"]

    def prompts(i: int) -> np.ndarray:
        return weights.tokens(cell.seed, 1000 + i, (b, p), vocab)

    def call(i: int) -> np.ndarray:
        with span("prompt_draw"):
            pr = jnp.asarray(prompts(i))
        with span("call"):
            out = greedy_generate(params, pcfg, pr, max_new=new, max_seq=cap)
        with span("result_fetch"):
            return np.asarray(out)

    with span("setup/weights"):
        params = _program_params(cell, pcfg)
    with span("setup/warmup"):
        # a 2-token prompt with 2 new tokens runs the window's compiled step
        # on the same cache; what depends on the lengths is the slice of a
        # prompt token and the concatenation of a call's output
        pr = jnp.asarray(prompts(-1))
        np.asarray(greedy_generate(params, pcfg, pr[:, :2], max_new=2,
                                   max_seq=cap))
        np.asarray(pr[:, 1:2])
        np.asarray(jnp.concatenate([pr[:, :1]] * (p + new), axis=1))

    outs, lowered = [], _lowerings()
    run.facts["setup_end"] = time.perf_counter()
    t0 = time.perf_counter()
    lowered.clear()
    ends = []
    while not outs or time.perf_counter() - t0 < cell.seconds:
        outs.append(call(len(outs)))
        ends.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    took = np.diff([0.0] + ends)
    run.notes["call seconds"] = (f"first {took[0]:.3f}, the others "
                                 f"{took[1:].min(initial=0):.3f} to "
                                 f"{took[1:].max(initial=0):.3f}")
    run.notes["programs lowered in the window"] = str(len(lowered))
    n_calls = len(outs)
    if cell.traced:
        # one more call, traced: a trace of the whole window would hold
        # hundreds of thousands of device ops, too many to reduce in time
        with _traced(cell, run):
            outs.append(call(n_calls))
    run.memory_peak_bytes = _memory_peak()
    _free(params)
    del params

    run.attempted = len(outs) * b
    for i, out in enumerate(outs):
        served = out[:, p:]
        ok = ((out.shape == (b, p + new))
              & np.all((served >= 0) & (served < vocab), axis=1)
              & np.all(out[:, :p] == prompts(i), axis=1))
        run.failed += int(np.sum(~ok))
    run.values["output_tokens_per_s"] = n_calls * b * new / wall
    pre = cell.ref.prefill_work(c, b, p)
    steps = [pre] + [cell.ref.decode_work(c, b, p + t) for t in range(new)]
    run.facts.update(kind="decode", calls=n_calls, window_s=wall,
                     call_work=steps)

    # the comparison: a sample of finished requests, drawn from the seed
    rng = np.random.default_rng([cell.seed & 0xFFFFFFFFFFFFFFFF, 99])
    picks = rng.choice(n_calls * b, size=min(mix["check_requests"],
                                             n_calls * b), replace=False)
    w = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t))(
        weights.nest(weights.make(cell.ref.layout(c), cell.seed)))
    ref_logits = jax.jit(lambda w, t: cell.ref.logits(w, t, c))
    gap, seqs = 0.0, [outs[k // b][k % b] for k in sorted(picks)]
    for seq in seqs:
        lg = np.asarray(ref_logits(w, jnp.asarray(seq)))
        gap = max(gap, check.served_gap(lg, seq, p))
    run.extra["sequences"] = seqs
    run.readings["logit_gap"] = gap
    run.facts["checked_tokens"] = len(picks) * new
    return run


DRIVERS: Dict[str, Callable[[Cell], Run]] = {"train": train,
                                              "decode": decode}
