"""Readings of the program, of the control and of a planted fault, for
setting a cell's limits; the benchmark's own runs never run this.

    python3 bench/control.py --workload qwen2-0.5b.decode-16k \
        --seeds 11,12,13 --seconds 1

For each seed it drives the cell as a run does (``drive.py``, a short
window) and prints one JSON line with:

- ``program``: the numbers the run compares;
- ``control``: the same numbers with the reference put in the program's
  place, computed in float8 (per-tensor scaled e4m3), the precision below
  the configuration's bfloat16.  For decode, at every served position the
  reference's gap of the token that float8 puts first;
- ``half_batch`` (training): the reference on the first half of each
  batch's rows, the mean taken over them.

It exits 2 off a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

import run as run_mod


def readings(workload: str, seed: int, seconds: float, root: Path,
             gate) -> dict:
    _, cell, parts, _ = run_mod.load_cell(workload, root)
    gate(cell["chips"])
    import jax
    import jax.numpy as jnp
    import check
    import drive
    import weights
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    c = drive.Cell(**parts, seed=seed, seconds=seconds, traced=False)
    run = drive.DRIVERS[c.mix["kind"]](c)
    out = {"seed": seed, "program": dict(run.readings)}
    make = lambda: weights.make(c.ref.layout(c.config), seed)
    if c.mix["kind"] == "train":
        opt = {**c.mix["optimizer"], **c.mix["objective"]}
        ref, batches = run.extra["ref"], run.extra["batches"]
        low = check.train_reference(c.ref, c.config, make, batches, opt,
                                    quant=check.fp8)
        out["control"] = check.train_readings(low, ref)[0]
        half = check.train_reference(c.ref, c.config, make, batches, opt,
                                     rows=c.mix["batch"] // 2)
        out["half_batch"] = check.train_readings(half, ref)[0]
    else:
        w = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32),
                                           t))(weights.nest(make()))
        f32 = jax.jit(lambda w, t: c.ref.logits(w, t, c.config))
        low = jax.jit(lambda w, t: c.ref.logits(w, t, c.config, check.fp8))
        gap = 0.0
        for seq in run.extra["sequences"]:
            t = jnp.asarray(seq)
            gap = max(gap, check.chosen_gap(np.asarray(f32(w, t)),
                                            np.asarray(low(w, t)),
                                            c.mix["prompt"]))
        out["control"] = {"logit_gap": gap}
    return out


def main(argv=None, root: Path = run_mod.ROOT, gate=run_mod.tpu_gate) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            line = readings(args.workload, seed, args.seconds, Path(root),
                            gate)
        except run_mod.NoChip as e:
            print(f"[control] no chip: {e}", file=sys.stderr)
            return 2
        print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
