"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload qwen2-0.5b.train-4k --seed 7 \
        --seconds 30 --trace 0

Everything that belongs to one cell is found by name from ``BENCHMARK.json``:
the configuration ``bench/configs/<config>.json`` with its reference
``bench/configs/<config>.py``, the traffic mix ``bench/mixes/<traffic>.json``
(whose ``kind`` picks the driver in ``drive.py``), the limits of the
comparison ``bench/limits/<workload>.json`` and one reader
``bench/metrics/<metric>.py`` per per-layer metric.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, the device's busy time and the
breakdown.  It exits 2 and prints no result when JAX's first device is not
a TPU or there are fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    pass


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prepare_env(root: Path) -> None:
    """Caches at fixed paths inside the checkout, set before JAX loads."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["REPRO_TABLE_STORE"] = str(root / ".bench_out" / "tables")
    for p in (str(root / "src"), str(root / "bench")):
        if p not in sys.path:
            sys.path.insert(0, p)


def tpu_gate(chips: int) -> dict:
    """The device the run measures; raises ``NoChip`` off a TPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" or len(devs) < chips:
        raise NoChip(f"JAX found {len(devs)} {dev.platform} device(s) "
                     f"({dev.device_kind}); the cell needs {chips} TPU "
                     f"chip(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def per_layer_for(spec: dict, cell: dict, e2e: list) -> list:
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def end_to_end_for(spec: dict, cell: dict) -> list:
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def load_cell(workload: str, root: Path):
    """(BENCHMARK.json, the cell's entry, the parts of a ``drive.Cell`` that
    its files give, its limits), everything found by name."""
    prepare_env(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / "bench"
    cell = next((w for w in spec["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    parts = dict(
        name=cell["name"],
        config=json.loads((root / conf["file"]).read_text()),
        ref=load_module(Path(root / conf["file"]).with_suffix(".py"),
                        "bench_ref_" + conf["name"].replace("-", "_")
                        .replace(".", "_")),
        mix=json.loads((bench / "mixes" / f"{cell['traffic']}.json")
                       .read_text()),
        out_dir=root / ".bench_out")
    limits = json.loads((bench / "limits" / f"{cell['name']}.json")
                        .read_text())
    return spec, cell, parts, limits


def measure(args, root: Path, gate) -> dict:
    """One run of one cell: the result object to print, whose last key,
    ``checks``, holds each number compared with its limit."""
    spec, cell, c, limits = load_cell(args.workload, root)
    device = gate(cell["chips"])
    peaks = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if device["kind"] not in peaks:
        raise KeyError(f"no peaks for device kind {device['kind']!r} in "
                       f"bench/peaks.json")
    import drive
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    c = drive.Cell(**c, seed=args.seed, seconds=float(args.seconds),
                   traced=bool(args.trace))
    run = drive.DRIVERS[c.mix["kind"]](c)
    bench = root / "bench"
    run.facts["setup_s"] = run.facts["setup_end"] - PROCESS_START
    run.values["setup_s"] = run.facts["setup_s"]
    run.facts["peak"] = peaks[device["kind"]]
    run.facts["trace"] = run.trace

    e2e = end_to_end_for(spec, cell)
    if args.trace:
        metrics = {}
        for m in per_layer_for(spec, cell, e2e):
            reader = load_module(bench / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run.facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": run.values[m["name"]],
                               "unit": m["unit"]} for m in e2e}
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    if args.trace and run.trace:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in run.readings.items()}
    correct = (run.failed == 0 and set(limits) <= set(checks) and all(
        math.isfinite(ch["value"]) and ch["value"] <= ch["limit"]
        for ch in checks.values()))
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace and run.trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    for k, v in run.notes.items():
        print(f"[bench] {k}: {v}", file=sys.stderr)
    return result


def main(argv=None, root: Path = ROOT, gate=tpu_gate) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = measure(args, Path(root), gate)
    except NoChip as e:
        print(f"[bench] no chip: {e}", file=sys.stderr)
        return 2
    for name, ch in result["checks"].items():
        print(f"[bench] check {name} = {ch['value']!r} "
              f"(limit {ch['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
