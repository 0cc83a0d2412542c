"""A whole run of each tiny cell on the CPU, the chip's gate faked."""
import json

import pytest

import run
from conftest import fake_gate


def _run(root, workload, capsys, trace=0, seed=2**33 + 5):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", str(trace)],
                  root=root, gate=fake_gate)
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("config", ["tiny", "tinym"])
@pytest.mark.parametrize("kind,e2e", [("train", "train_tokens_per_s"),
                                      ("decode", "output_tokens_per_s")])
def test_cell_runs_and_is_correct(tiny_root, capsys, config, kind, e2e):
    res, err = _run(tiny_root, f"{config}.{kind}", capsys)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {e2e, "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    # set-up warmed every program the window runs
    assert "[bench] programs lowered in the window: 0\n" in err
    assert err.strip().splitlines()[-1].startswith("[bench] check ")


@pytest.mark.parametrize("kind", ["train", "decode"])
def test_traced_run_reports_per_layer_metrics(tiny_root, capsys, kind):
    res, _ = _run(tiny_root, f"tiny.{kind}", capsys, trace=1)
    assert res["correct"], res["checks"]
    # the CPU's trace has no device plane, so the idle shares stay out
    assert f"mfu.{kind}" in res["metrics"]
    assert not any(k.startswith("idle_share") for k in res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


def test_no_tpu_exits_nonzero_and_names_device(tiny_root, capsys):
    rc = run.main(["--workload", "tiny.decode", "--seed", "1", "--seconds",
                   "1"], root=tiny_root)
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "cpu" in err
