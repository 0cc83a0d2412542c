"""Required work from shapes, and the peaks table, against hand arithmetic."""
import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


def _config(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_").replace(".", "_"),
        BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PEAK = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_qwen2_matmul_params_and_kv_bytes():
    ref, c = _config("qwen2-0.5b")
    # per layer: q,k,v 896 x (14 + 2 + 2) x 64, o 14 x 64 x 896,
    # MLP 3 x 896 x 4864; head 151936 x 896
    per_layer = 896 * 18 * 64 + 14 * 64 * 896 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    assert ref.matmul_params(c) == 24 * per_layer + 151936 * 896
    assert ref.matmul_params(c) == 493_961_216          # "494M"
    assert ref.kv_bytes_per_token(c) == 24 * 2 * 2 * 64 * 2 == 12_288


def test_qwen2_train_flops_per_token():
    ref, c = _config("qwen2-0.5b")
    # 6 N + 6 L S d_attn at S = 4096: 2.964e9 + 0.528e9
    f = ref.train_flops_per_token(c, 4096)
    assert f == 6 * 493_961_216 + 6 * 24 * 4096 * 896
    assert f == pytest.approx(3.49e9, rel=2e-3)


def test_qwen2_decode_work():
    ref, c = _config("qwen2-0.5b")
    f, b = ref.decode_work(c, 16, 600)
    assert f == 16 * (2 * 493_961_216 + 4 * 24 * 600 * 896)
    assert b == 2 * 493_961_216 + 16 * 601 * 12_288
    f, b = ref.prefill_work(c, 16, 512)
    assert f == 16 * (2 * 493_961_216 * 512 + 2 * 24 * 512 * 512 * 896)
    assert b == 2 * 493_961_216 + 16 * 512 * 12_288


def test_mamba2_layer_params_state_bytes_and_flops():
    ref, c = _config("mamba2-2.7b")
    # in_proj 2560 x (2 x 5120 + 2 x 128 + 80), out_proj 5120 x 2560
    matmul_layer = 2560 * 10_576 + 5120 * 2560
    # conv weights and bias over x, B and C: 5 x 5376; A_log, D, dt_bias
    # per head; the gated norm over 5120; the layer norm over 2560
    assert ref.layer_params(c) == matmul_layer + 5 * 5376 + 3 * 80 \
        + 5120 + 2560 == 40_216_560                        # "40.2M"
    assert ref.matmul_params(c) == 8 * matmul_layer + 50280 * 2560
    assert ref.state_bytes(c) == 80 * 64 * 128 * 4 == 2_621_440   # 2.62 MB
    # 6 N + three times the forward recurrence's 5 H P N per layer
    assert ref.train_flops_per_token(c, 4096) == \
        6 * 450_170_880 + 3 * 8 * 5 * 80 * 64 * 128
    f, b = ref.decode_work(c, 128, 700)
    assert f == 128 * (2 * 450_170_880 + 8 * 5 * 80 * 64 * 128)
    assert b == 2 * 450_170_880 + 2 * 128 * 8 * 2_621_440


def test_peaks_table_has_v5e_and_no_default():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["devices"]["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks


def test_unknown_device_kind_raises(tiny_root, capsys):
    import run
    rc = None
    with pytest.raises(KeyError, match="no peaks"):
        rc = run.main(["--workload", "tiny.decode", "--seed", "5",
                       "--seconds", "0.2"], root=tiny_root,
                      gate=lambda n: {"platform": "tpu",
                                      "kind": "TPU v99", "count": n})
    assert rc is None


@pytest.mark.parametrize("slack", [1.0, 1.0001, 1.5, 10.0])
def test_mfu_never_passes_100_at_or_above_the_bound(slack):
    """A window no shorter than the least time the required work takes
    reads at most 100%."""
    ref, c = _config("qwen2-0.5b")
    fpt = ref.train_flops_per_token(c, 4096)
    fastest = PEAK["flops_per_s"] / fpt                 # tokens/s at peak
    train = _metric("mfu.train").read(
        {"kind": "train", "peak": PEAK, "flops_per_token": fpt,
         "tokens_per_s": fastest / slack})
    assert train <= 100.0 + 1e-9
    work = [ref.prefill_work(c, 16, 512)] + [
        ref.decode_work(c, 16, 512 + t) for t in range(256)]
    least = sum(max(f / PEAK["flops_per_s"], b / PEAK["hbm_bytes_per_s"])
                for f, b in work)
    decode = _metric("mfu.decode").read(
        {"kind": "decode", "peak": PEAK, "call_work": work, "calls": 3,
         "window_s": 3 * least * slack})
    assert decode <= 100.0 + 1e-9
    assert decode == pytest.approx(100.0 / slack)


def test_readers_return_nothing_without_their_source():
    for name in ("mfu.train", "mfu.decode", "idle_share.train",
                 "idle_share.decode", "accounting_share.train"):
        assert _metric(name).read({"kind": "other", "peak": PEAK}) is None
