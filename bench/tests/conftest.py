"""Tests of the benchmark, on the CPU at tiny sizes:

    python -m pytest bench/tests

``tiny_root`` lays out a checkout of its own: the program's ``src``, a copy
of ``bench/`` and a ``BENCHMARK.json`` whose cells are a throwaway
configuration and mixes, added as data files only and found by name.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (str(REPO / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY_LIMITS = {
    # at 64 wide the program's bfloat16 and the float32 reference agree to
    # about 1e-2 in loss and a few percent in a leaf's norm
    "tiny.train": {"loss_gap": 0.05, "grad_gap": 0.25, "delta_gap": 0.25},
    # bfloat16 logits of about 1 round by 4e-3 and near-ties decide
    # greedily: one call on seeds 1000-1011 reads 0 to 0.024, float8 0 to
    # 0.46 (0.23 and 0.25 on seed 1002, which the control test uses)
    "tiny.decode": {"logit_gap": 0.05},
    "tinym.train": {"loss_gap": 0.05, "grad_gap": 0.25, "delta_gap": 0.25},
    "tinym.decode": {"logit_gap": 0.05},
}
TINY = {"tiny": "qwen2-0.5b", "tinym": "mamba2-2.7b"}


def fake_gate(chips):
    return {"platform": "cpu", "kind": "cpu", "count": chips}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    (root / "src").symlink_to(REPO / "src")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, real in TINY.items():
        src = "tiny-qwen2" if name == "tiny" else "tiny-mamba2"
        shutil.copy(DATA / f"{src}.json", root / f"bench/configs/{name}.json")
        shutil.copy(BENCH / f"configs/{real}.py",
                    root / f"bench/configs/{name}.py")
    for kind in ("train", "decode"):
        shutil.copy(DATA / f"tiny-{kind}.json",
                    root / f"bench/mixes/tiny-{kind}.json")
    # the faked gate names the CPU, which gets peaks of its own here only
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench/peaks.json").write_text(json.dumps(peaks))
    for cell, limits in TINY_LIMITS.items():
        (root / f"bench/limits/{cell}.json").write_text(json.dumps(limits))
    # the metrics of both kinds of cell, whichever cells BENCHMARK.json
    # holds today
    spec = dict(json.loads((REPO / "BENCHMARK.json").read_text()),
                **json.loads((DATA / "metrics.json").read_text()))
    spec["configs"] = [{"name": name, "source": "test",
                        "file": f"bench/configs/{name}.json", "reduced": [],
                        "why": "test"} for name in TINY]
    spec["workloads"] = [
        {"name": f"{name}.{k}", "config": name, "traffic": f"tiny-{k}",
         "chips": 1, "why": "test"} for name in TINY
        for k in ("train", "decode")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kinds = {"train" if ".train" in w else "decode"
                     for w in m["workloads"]}
            m["workloads"] = [f"{n}.{k}" for n in TINY for k in kinds]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
