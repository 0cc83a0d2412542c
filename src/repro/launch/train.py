"""End-to-end training driver with fault tolerance + energy monitoring.

Runs a (reduced or full) config for N steps on the available mesh:
checkpoint/restart (atomic, keep-k), simulated failure injection, straggler
monitoring, elastic re-mesh on device loss, and the Wattchmen fleet monitor
attributing per-step energy (the paper as a production feature).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 20 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfgs
from repro.api import EnergyModel
from repro.configs.base import ShapeSpec
from repro.core.opcount import count_fn
from repro.data.pipeline import DataConfig, model_batch
from repro.launch.compile_cache import compile_line, use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as model_mod
from repro.parallel import sharding as sh
from repro.train import checkpoint as ckpt_mod
from repro.train import optimizer as opt_mod
from repro.train.elastic import StragglerMonitor
from repro.train.step import TrainState, init_state, make_train_step


def run(arch: str, *, smoke: bool = True, steps: int = 20,
        seq_len: int = 64, global_batch: int = 4,
        ckpt_dir: Optional[str] = None, ckpt_every: int = 10,
        fail_at: Optional[int] = None, microbatches: int = 1,
        energy_system: Optional[str] = "sim-v5e-air",
        energy_donor: Optional[str] = None,
        energy_profile_fraction: Optional[float] = None,
        telemetry_chunk: Optional[int] = 4096,
        freq_mhz: Optional[float] = None, governor: bool = False,
        sla_tokens_per_s: Optional[float] = None,
        telemetry_shards: Optional[int] = None,
        chaos_profile: Optional[str] = None, chaos_seed: int = 0,
        seed: int = 0, verbose: bool = True):
    cfg = cfgs.get_smoke_config(arch) if smoke else cfgs.get_config(arch)
    shape = ShapeSpec("run", seq_len, global_batch, "train")
    opt_cfg = opt_mod.OptConfig(total_steps=max(steps, 2), warmup_steps=2,
                                mv_dtype=cfg.optimizer_dtype,
                                master_fp32=cfg.optimizer_dtype == "float32")
    dcfg = DataConfig(seed=seed, vocab=cfg.vocab, seq_len=seq_len,
                      global_batch=global_batch)

    train_step = jax.jit(make_train_step(cfg, opt_cfg,
                                         microbatches=microbatches),
                         donate_argnums=(0,))

    start_step = 0
    state = init_state(cfg, opt_cfg, jax.random.PRNGKey(seed))
    if ckpt_dir and ckpt_mod.latest_step(ckpt_dir) is not None:
        state, start_step = ckpt_mod.restore(ckpt_dir, state)
        if verbose:
            print(f"[train] restored checkpoint at step {start_step}")

    # Wattchmen integration: profile the step once, monitor every step —
    # live=True adds the telemetry stream (measured J/step + drift repair).
    # A first-seen energy_system trains through the resumable calibration
    # pipeline; with a donor it is bootstrapped from a fraction of the
    # microbenchmark suite instead of a full profile (Fig. 14).
    monitor, plane = None, None
    if energy_system:
        example = model_batch(cfg, shape, dcfg, 0)
        counts = count_fn(make_train_step(cfg, opt_cfg,
                                          microbatches=microbatches),
                          state, example)
        if energy_donor is not None:
            model = EnergyModel.train(
                energy_system, resume=True, store=True,
                profile_fraction=energy_profile_fraction or 0.5,
                donor=energy_donor)
        else:
            model = EnergyModel.from_store(energy_system)
        # DVFS: --freq-mhz pins the whole run at one operating point;
        # --governor picks the run's frequency from the sweet-spot
        # governor's exploration order (training is one long session, so
        # the loop closes across runs: per-step measured J/work feeds the
        # governor and its verdict is reported at the end).
        point, gov = freq_mhz, None
        if governor:
            from repro.dvfs import GovernorConfig, SweetSpotGovernor
            fam = [(f, c) for f, c, _ in model.table.family()
                   if f is not None]
            if len(fam) < 2:
                model.calibrate_points(duration_s=3.0, repeats=2)
                fam = [(f, c) for f, c, _ in model.table.family()
                       if f is not None]
            gov = SweetSpotGovernor(
                fam, GovernorConfig(sla_work_per_s=sla_tokens_per_s))
            work = float(seq_len * global_batch)
            gov.seed_exploration(
                lambda p: model.predict(counts, 1.0, operating_point=p)
                .total_j / max(work, 1e-12))
            point = gov.propose()
        chaos = None
        if chaos_profile and chaos_profile != "none":
            from repro.telemetry.faults import ChaosPlan
            chaos = ChaosPlan.profile(chaos_profile, seed=chaos_seed)
            if verbose:
                print(f"[chaos] profile {chaos_profile!r} seed={chaos_seed}:"
                      f" telemetry runs behind the fault-injection layer")
        monitor = model.monitor(live=True, step_counts=counts,
                                telemetry_chunk=telemetry_chunk,
                                operating_point=point, governor=gov,
                                chaos=chaos)
        # --telemetry-shards: the run's session rides a sharded telemetry
        # plane (plane-wide drains, merge-based snapshot) instead of
        # finishing stand-alone
        plane = (model.plane(telemetry_shards, chaos=chaos)
                 if telemetry_shards else None)
        if plane is not None:
            monitor.bind(plane)

    straggler = StragglerMonitor()
    losses = []
    for step in range(start_step, steps):
        if fail_at is not None and step == fail_at:
            raise RuntimeError(f"simulated node failure at step {step}")
        batch = {k: jnp.asarray(v)
                 for k, v in model_batch(cfg, shape, dcfg, step).items()}
        t0 = time.time()
        state, metrics = train_step(state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        losses.append(loss)
        straggler.record(step, dt)
        if monitor is not None:
            monitor.live.step(step, duration_s=dt,
                              work_units=seq_len * global_batch)
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt_mod.save(ckpt_dir, step + 1, state)
        if verbose:
            print(f"[train] step {step} loss={loss:.4f} ({dt * 1e3:.3f} ms)")
    if monitor is not None and monitor.live.steps_registered:
        if plane is not None:
            monitor.live.start()
            plane.finish_all()       # plane-wide drain over all shards
            summary = monitor.live.summary
            if verbose:
                fleet = plane.snapshot()["fleet"]
                print(f"[plane] {len(plane.shards)} shards, "
                      f"{fleet['n_sessions']} sessions, "
                      f"{fleet['measured_j']:.4e} J merged exactly")
        else:
            summary = monitor.live.finish()
        if verbose:
            rec = monitor.records[-1]
            print(f"[train] E/token={rec.joules_per_unit_work:.2e}J "
                  f"live MAPE {summary.mape_pct:.1f}% over {summary.steps} "
                  f"steps" + (", DRIFT flagged" if summary.drift.drifting
                              else ""))
        dev_pt = monitor.live.operating_point
        if verbose and dev_pt is not None:
            what = "governed" if gov is not None else "pinned"
            print(f"[dvfs] {what} at f={dev_pt[0]:g} MHz"
                  + (f" ({len(gov.decisions)} decisions, "
                     f"{gov.decisions[-1].reason})" if gov is not None
                     else ""))
    return state, losses, monitor


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--energy-system", default="sim-v5e-air")
    ap.add_argument("--energy-donor", default=None,
                    help="bootstrap the energy table by affine transfer "
                         "from this system's table (Fig. 14)")
    ap.add_argument("--energy-profile-fraction", type=float, default=None,
                    help="fraction of the microbenchmark suite to measure "
                         "when bootstrapping from --energy-donor")
    ap.add_argument("--telemetry-chunk", type=int, default=4096,
                    help="streaming ingestion chunk size (0 = per-sample)")
    ap.add_argument("--freq-mhz", type=float, default=None,
                    help="pin the device at this core frequency")
    ap.add_argument("--governor", action="store_true",
                    help="let the sweet-spot governor pick the run's "
                         "frequency and feed it per-step measurements")
    ap.add_argument("--sla-tokens-per-s", type=float, default=None,
                    help="throughput floor the governor must hold")
    ap.add_argument("--telemetry-shards", type=int, default=None,
                    help="shard the telemetry plane across N workers "
                         "(0/None = single-process service)")
    ap.add_argument("--chaos-profile", default=None,
                    choices=["none", "light", "heavy"],
                    help="run telemetry behind the deterministic "
                         "fault-injection layer (soak/chaos testing)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos plan (same seed = same faults)")
    args = ap.parse_args(argv)
    use_compile_cache()
    _, losses, _ = run(args.arch, smoke=args.smoke, steps=args.steps,
                       seq_len=args.seq_len, global_batch=args.global_batch,
                       ckpt_dir=args.ckpt_dir, fail_at=args.fail_at,
                       microbatches=args.microbatches,
                       energy_system=args.energy_system,
                       energy_donor=args.energy_donor,
                       energy_profile_fraction=args.energy_profile_fraction,
                       telemetry_chunk=args.telemetry_chunk or None,
                       freq_mhz=args.freq_mhz, governor=args.governor,
                       sla_tokens_per_s=args.sla_tokens_per_s,
                       telemetry_shards=args.telemetry_shards or None,
                       chaos_profile=args.chaos_profile,
                       chaos_seed=args.chaos_seed)
    print(compile_line())
    finite = bool(np.isfinite(losses).all())
    ok = finite and losses[-1] < losses[0]
    print(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({'improved' if ok else 'check'})")
    return 0 if finite else 1


if __name__ == "__main__":
    sys.exit(main())
