"""The trace reduction, on a small recorded trace checked by hand, and on a
profile that JAX writes here on the CPU."""
import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"


def test_reduce_small_trace_by_hand():
    t = json.loads((DATA / "small_trace.json").read_text())
    r = devtrace.reduce(t["devices"], t["spans"], tuple(t["window"]))
    # device 0: [100,300) u [250,400) u [500,800) (holding [600,700)),
    #   busy 600 ns; gaps [0,100) [400,500) [800,1000)
    # device 1: [900,1100) clipped to [900,1000), busy 100 ns; gap [0,900)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(350e-9)          # (600 + 100) / 2
    assert r["idle_share"] == pytest.approx(0.65)
    ops = dict(r["device_ops"])
    # self time: the second fusion's 50 ns inside the first count once;
    # the while loop holds the copy, so it keeps 300 - 100
    assert ops["fusion"] == pytest.approx((150 + 150) / 2 * 1e-9)
    assert ops["%while.2 (s32[]"] == pytest.approx(200 / 2 * 1e-9)
    assert ops["%copy.1 bf16[16,64]"] == pytest.approx(100 / 2 * 1e-9)
    assert ops["dot"] == pytest.approx(100 / 2 * 1e-9)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    # middles: 50 -> feed (its span 0..120 is shorter than call's 0..1000),
    # 450 -> call, 900 -> loss_sync (800..900), 450 (device 1) -> call
    assert gaps["feed"] == pytest.approx(100 / 2 * 1e-9)
    assert gaps["call"] == pytest.approx((100 + 900) / 2 * 1e-9)
    assert gaps["loss_sync"] == pytest.approx(200 / 2 * 1e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert [k for k, _ in r["idle_gaps"]][0] == "call"


def test_reduce_empty_window_has_no_busy_time():
    r = devtrace.reduce([[]], [], (0, 10))
    assert r["busy_s"] == 0 and r["idle_share"] == 1.0
    assert r["idle_gaps"] == [[devtrace.NO_SPAN, pytest.approx(1e-8)]]


def test_reads_host_spans_from_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation(devtrace.SPAN_PREFIX + "call"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    r = devtrace.reduce_dir(tmp_path)
    assert r["window_s"] > 0
    # the CPU backend writes no device plane: nothing is busy or idle
    assert r["busy_s"] == 0 and r["idle_gaps"] == []
    files = list(tmp_path.rglob("*.xplane.pb"))
    devices, spans = devtrace.from_xplane(files[0])
    assert devices == []
    assert {s[0] for s in spans} == {devtrace.WINDOW_SPAN,
                                     devtrace.SPAN_PREFIX + "call"}
