"""Serving driver: energy-aware continuous batching with a per-request
energy ledger (measured and predicted joules per request/tenant, from the
Wattchmen table + simulated telemetry).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --tenants 2 --requests 6 --budget-j-per-token 2e-4

A multi-request workload (staggered arrivals, mixed prompt/output lengths
across tenants) is run through ``serve.EnergyServer``: admission packs the
decode batch to the J/token budget, drift can shed load, and every aligned
step's joules land on individual requests with bitwise conservation.  The
per-step op counts the scheduler prices and the device executes are traced
from the *real* model prefill/decode steps (``core.opcount.count_fn``), so
the energy accounting reflects the actual architecture at each batch size.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfgs
from repro.api import EnergyModel
from repro.core.opcount import count_fn
from repro.launch.compile_cache import compile_line, use_compile_cache
from repro.models import model as model_mod
from repro.serve.scheduler import EnergyPolicy, Request
from repro.serve.step import make_prefill_step, make_serve_step


def model_counts_fn(cfg, params, *, max_seq: int, attn_fn=None):
    """counts_fn(kind, batch, tokens) traced from the real model steps.

    Decode counts come from the cached ``decode_step`` at the phase's
    batch size; prefill counts from the full-sequence forward at the
    phase's padded prompt length.  ``EnergyServer`` memoizes per
    (kind, batch, tokens), so each shape is traced once.
    """
    def counts(kind: str, batch: int, tokens: int):
        if kind == "prefill":
            fn = make_prefill_step(cfg, attn_fn)
            sample = {"tokens": jnp.zeros((batch, tokens), jnp.int32)}
            return count_fn(fn, params, sample)
        cache = model_mod.init_cache(cfg, batch, max_seq)
        if cfg.family == "encdec":
            from repro.models import encdec
            enc = jnp.zeros((batch, cfg.n_audio_frames, cfg.d_model),
                            cfg.activation_dtype)
            ck, cv = encdec.prefill_cross_cache(params, enc, cfg)
            cache = dict(cache, cross_k=ck, cross_v=cv)
        return count_fn(make_serve_step(cfg, attn_fn), params, cache,
                        jnp.zeros((batch, 1), jnp.int32))
    return counts


def make_workload(*, tenants: int, requests: int, prompt_len: int,
                  max_new: int, seed: int = 0):
    """Staggered multi-tenant request mix for the serving demo.

    Prompt and output lengths are drawn from {½×, 1×, 2×} the nominal
    values and arrivals from a geometric inter-arrival process, so the
    batch genuinely churns: joins, evictions, and occupancy changes.
    """
    rng = np.random.default_rng(seed)
    reqs = []
    step = 0
    for i in range(requests):
        reqs.append(Request(
            id=f"r{i}", tenant=f"tenant-{i % max(tenants, 1)}",
            prompt_len=int(prompt_len * rng.choice([0.5, 1.0, 2.0])) or 1,
            max_new=int(max_new * rng.choice([0.5, 1.0, 2.0])) or 1,
            arrival_step=step))
        step += int(rng.geometric(0.4)) - 1
    return reqs


def _governor_state_path(energy_system: str):
    """Where the sweet-spot governor persists across serve restarts."""
    from repro.core.store import default_store
    return default_store().run_dir(energy_system) / "governor_state.json"


def run(arch: str, *, smoke: bool = True, tenants: int = 2,
        requests: int = 6, prompt_len: int = 16, max_new: int = 16,
        max_batch: int = 4, budget_j_per_token: Optional[float] = None,
        energy_system: str = "sim-v5e-air", seed: int = 0,
        telemetry_chunk: Optional[int] = 4096,
        min_phase_seconds: float = 4.0, verbose: bool = True,
        freq_mhz: Optional[float] = None, governor: bool = False,
        sla_tokens_per_s: Optional[float] = None,
        telemetry_shards: Optional[int] = None,
        chaos_profile: Optional[str] = None, chaos_seed: int = 0):
    cfg = cfgs.get_smoke_config(arch) if smoke else cfgs.get_config(arch)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(seed))
    max_seq = 2 * prompt_len + 2 * max_new + 1   # covers the 2× draws

    model = EnergyModel.from_store(energy_system)
    gov = None
    if governor:
        from repro.dvfs import GovernorConfig, SweetSpotGovernor
        fam = [(f, c) for f, c, _ in model.table.family() if f is not None]
        if len(fam) < 2:
            # no calibrated family yet: sweep a small grid first
            model.calibrate_points(duration_s=3.0, repeats=2)
            fam = [(f, c) for f, c, _ in model.table.family()
                   if f is not None]
        gov = SweetSpotGovernor(
            fam, GovernorConfig(sla_work_per_s=sla_tokens_per_s))
        # resume where the previous serve run left off: a converged
        # governor re-enters exploit at the same operating point instead
        # of re-exploring the whole grid on every restart
        state_path = _governor_state_path(energy_system)
        if state_path.exists():
            try:
                gov.load_state(json.loads(state_path.read_text()))
                if verbose:
                    print(f"[dvfs] restored governor state "
                          f"({state_path})")
            except (ValueError, KeyError) as exc:
                print(f"[dvfs] ignoring stale governor state: {exc}")
    # sharded telemetry plane: billing, governor pane and the per-phase
    # sessions ride it exactly like the one-process service (the plane is
    # a drop-in TelemetryService with a merge-based snapshot)
    chaos = None
    if chaos_profile and chaos_profile != "none":
        from repro.telemetry.faults import ChaosPlan
        chaos = ChaosPlan.profile(chaos_profile, seed=chaos_seed)
        if verbose:
            print(f"[chaos] profile {chaos_profile!r} seed={chaos_seed}: "
                  f"telemetry runs behind the fault-injection layer")
    plane = (model.plane(telemetry_shards, chaos=chaos)
             if telemetry_shards else None)
    server = model.serve(
        model_counts_fn(cfg, params, max_seq=max_seq),
        policy=EnergyPolicy(max_batch=max_batch,
                            budget_j_per_token=budget_j_per_token),
        min_phase_seconds=min_phase_seconds,
        telemetry_chunk=telemetry_chunk, name=f"serve/{arch}",
        operating_point=freq_mhz, governor=gov, service=plane,
        chaos=chaos)
    workload = make_workload(tenants=tenants, requests=requests,
                             prompt_len=prompt_len, max_new=max_new,
                             seed=seed)
    report = server.run(workload)
    if gov is not None:
        state_path = _governor_state_path(energy_system)
        state_path.parent.mkdir(parents=True, exist_ok=True)
        state_path.write_text(json.dumps(gov.state_dict(), indent=1))
        if verbose:
            print(f"[dvfs] governor state saved ({state_path})")

    if verbose:
        print(f"[serve] {arch}: {len(workload)} requests / {tenants} "
              f"tenants, max_batch={max_batch}"
              + (f", budget {budget_j_per_token:.3e} J/token"
                 if budget_j_per_token else ""))
        print(report.table())
        for t, bill in report.billing.bills.items():
            print(f"[bill] {t}: {bill.measured_j:.4e} J over "
                  f"{bill.requests} requests, {bill.j_per_token:.3e} J/token"
                  f" (residual {bill.residual_j:+.3e} J)")
        deferred = [e for e in report.events if e.event == "defer"]
        shed = [e for e in report.events if e.event == "shed"]
        print(f"[serve] {len(report.ledger)} aligned steps in "
              f"{len(report.phases)} phases; live MAPE "
              f"{report.mape_pct:.1f}%; {len(deferred)} deferrals, "
              f"{len(shed)} sheds, overhead {report.overhead_j:.3e} J")
        if gov is not None and gov.current is not None:
            print(f"[dvfs] governor holding f={gov.current[0]:g} MHz "
                  f"(cap {gov.current[1]} W) after "
                  f"{len(gov.decisions)} decisions")
        elif freq_mhz is not None:
            print(f"[dvfs] pinned at f={freq_mhz:g} MHz")
        if plane is not None:
            fleet = plane.snapshot()["fleet"]
            print(f"[plane] {len(plane.shards)} shards, "
                  f"{fleet['n_sessions']} sessions, "
                  f"{fleet['measured_j']:.4e} J merged exactly")
    return report, server


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--budget-j-per-token", type=float, default=None)
    ap.add_argument("--telemetry-chunk", type=int, default=4096,
                    help="streaming ingestion chunk size (0 = per-sample)")
    ap.add_argument("--freq-mhz", type=float, default=None,
                    help="pin the device at this core frequency")
    ap.add_argument("--governor", action="store_true",
                    help="close the loop: sweet-spot DVFS per phase")
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
    report, _ = run(args.arch, smoke=args.smoke, tenants=args.tenants,
                    requests=args.requests, prompt_len=args.prompt_len,
                    max_new=args.max_new, max_batch=args.max_batch,
                    budget_j_per_token=args.budget_j_per_token,
                    telemetry_chunk=args.telemetry_chunk or None,
                    freq_mhz=args.freq_mhz, governor=args.governor,
                    sla_tokens_per_s=args.sla_tokens_per_s,
                    telemetry_shards=args.telemetry_shards or None,
                    chaos_profile=args.chaos_profile,
                    chaos_seed=args.chaos_seed)
    print(compile_line())
    assert len(report.requests) == args.requests
    return 0


if __name__ == "__main__":
    sys.exit(main())
