"""The reader of ``ssm_state_share.decode`` on a trace built by hand."""
import copy
import json
from pathlib import Path

import pytest

import run
import scopes

DATA = Path(__file__).resolve().parent / "data"
READER = run.load_module(
    Path(scopes.__file__).resolve().parent / "metrics"
    / "ssm_state_share.decode.py", "test_metric_ssm_state_share_decode")
BUSY = 40 + 2 * 200 + 10


@pytest.fixture
def t():
    # a decode call of 2 requests through a 2-layer state-space model,
    # state [2,2,2,2,4] and conv state [2,2,3,6] stacked over the layers:
    # init_cache's broadcast of the state (40 ns), 2 launches of 200 ns and
    # the output's concatenation (10).  A launch is the layer scan's while
    # loop (160 ns, 30 of them its own) holding the input projection 30
    # (ssm), the scan's slice of the state 10 and its stacking writes of
    # the state 20 and conv state 10 (the scan's ssm_state scope alone),
    # the conv shift 10 and the recurrence 20 (ssm_state inside ssm), an
    # mlp op 15 and the output projection 15 (ssm); then XLA's unnamed
    # copies of the stacked state 20 and conv state 5, an unnamed norm 5 of
    # three axes, and the argmax 10 (lm_head).
    return json.loads((DATA / "ssm_trace.json").read_text())


def test_counts_the_ops_named_ssm_state(t):
    assert scopes.busy_s(t) == pytest.approx(BUSY * 1e-9)
    per_launch = 30 + 10 + 20 + 10 + 10 + 20 + 20 + 5
    assert READER.share(t) == pytest.approx(
        100 * (40 + 2 * per_launch) / BUSY)


def test_does_not_count_other_scopes_or_other_shapes(t):
    # under ssm, mlp or lm_head, or unnamed with a shape no counted op has:
    # 30 + 15 + 15 + 10 + 5 a launch and the concatenation
    assert READER.share(t) == pytest.approx(
        100 * (BUSY - 2 * 75 - 10) / BUSY)
    # an unnamed op counts by the shape of a counted op only: with the
    # conv stack's writes named ssm, its copy no longer counts either
    moved = copy.deepcopy(t)
    conv_dus = next(n for n in moved["scopes"]
                    if n.startswith("%dynamic-update-slice_fusion.2 "))
    moved["scopes"][conv_dus] = moved["scopes"][conv_dus].replace(
        "/body/", "/body/closed_call/ssm/")
    assert READER.share(moved) == pytest.approx(
        READER.share(t) - 100 * 2 * (10 + 5) / BUSY)


def test_reads_nothing_without_device_or_generate_span(t, monkeypatch):
    assert READER.share(dict(t, devices=[], modules=[])) is None
    anon = dict(t, spans=[s for s in t["spans"] if s[0] != scopes.GENERATE])
    assert READER.share(anon) is None
    # a model without the scope: nothing is state
    plain = json.loads((DATA / "scoped_trace.json").read_text())
    assert READER.share(plain) == 0.0
    monkeypatch.setattr(scopes, "load_dir", lambda _dir: t)
    facts = {"kind": "decode", "trace": {"busy_s": 1.0}}
    assert READER.read(facts) == pytest.approx(READER.share(t))
    for f in ({"kind": "decode", "trace": {"busy_s": 0}},
              {"kind": "decode"}, {"kind": "train", "trace": {"busy_s": 1}}):
        assert READER.read(f) is None
    monkeypatch.setattr(scopes, "load_dir", lambda _dir: None)
    assert READER.read(facts) is None
