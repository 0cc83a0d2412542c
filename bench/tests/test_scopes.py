"""The reading of the program's names out of a trace (``scopes.py``) and
the three readers built on it, on a trace built by hand, on protobuf bytes
built by hand, and on a profile that JAX writes here on the CPU."""
import copy
import json
from pathlib import Path

import pytest

import run
import scopes

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(scopes.__file__).resolve().parent / "metrics"


@pytest.fixture
def t():
    # a decode call of 2 requests, 3 prompt tokens and 1 new token into 8
    # cache positions: init_cache, 2 prompt launches, an eager slice, 1
    # decode launch, the concatenation.  Each launch (200 ns) is a while
    # loop of 180 ns holding mlp 60, the kv_cache update 20 and mask 10, an
    # unscoped cache-shaped copy 40 and an attention op 30 of the cache's
    # rank but not its shape, then the argmax 20 (lm_head).
    return json.loads((DATA / "scoped_trace.json").read_text())


def test_result_shapes_and_scopes_from_names():
    assert scopes.result_dims(
        "%copy.5 = bf16[1,2,8,2,4]{4,3,2,1,0:T(2,128)} copy(...)") == \
        (1, 2, 8, 2, 4)
    assert scopes.result_dims("%while.4 = (s32[], bf16[2,8]) while()") is None
    assert scopes.scope_of(
        "jit(serve_step)/while/body/closed_call/attn/kv_cache/le:") == \
        "kv_cache"
    assert scopes.scope_of("jit(serve_step)/lm_head/argmax:") == "lm_head"
    assert scopes.scope_of("jit(serve_step)/while/body/add:") == \
        scopes.NO_SCOPE


def test_cache_ops_by_scope_and_by_shape(t):
    busy = 50 + 3 * 200 + 5 + 10
    assert scopes.busy_s(t) == pytest.approx(busy * 1e-9)
    got = scopes.by_scope(t)
    # the update and the mask by their scope; the unscoped copy and
    # init_cache's broadcast by their shape [.., 2, 8, a, b]; the attention
    # op f32[2,2,8,4] has the cache's rank but not its shape; the while
    # loop's tuple result is not a cache, its 20 ns are its own
    want = {"kv_cache": 3 * 30, scopes.BY_SHAPE: 3 * 40 + 50,
            "mlp": 3 * 60, "attn": 3 * 30, "lm_head": 3 * 20,
            scopes.NO_SCOPE: 3 * 20 + 5 + 10}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(busy * 1e-9)
    assert scopes.kv_cache_share(t) == pytest.approx(
        100 * (90 + 170) / busy)


def test_phases_are_matched_to_launches_in_order(t):
    # the spans are listed out of order; the k-th execution of the serve
    # step, by start, belongs to the k-th launch span
    assert scopes.phases(t) == {"serve.prompt_step": (200, 700),
                                "serve.decode_step": (800, 1000)}
    # the prompt's busy time holds the eager slice between its launches
    assert scopes.prompt_share(t) == pytest.approx(
        100 * (200 + 5 + 200) / (50 + 3 * 200 + 5 + 10))


def test_nothing_is_read_when_counts_differ_or_no_device(t):
    short = copy.deepcopy(t)
    short["spans"] = [s for s in short["spans"]
                      if s[0] != scopes.DECODE_STEP]
    assert scopes.phases(short) is None
    assert scopes.prompt_share(short) is None
    bare = dict(t, devices=[], modules=[])
    assert scopes.kv_cache_share(bare) is None
    assert scopes.prompt_share(bare) is None
    # without the program's generate span the cache's shape is unknown
    anon = dict(t, spans=[s for s in t["spans"] if s[0] != scopes.GENERATE])
    assert scopes.kv_cache_share(anon) is None
    assert "kv_cache_share" in scopes.report(t)


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _len(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _plane(name, events):
    body = _len(2, name.encode())
    for mid, (ev, stats) in enumerate(events, 1):
        md = _int(1, mid) + _len(2, ev.encode()) + b"".join(
            _len(5, st) for st in stats)
        body += _len(4, _int(1, mid) + _len(2, md))
    for sid, sname in ((7, "tf_op"), (8, "jit(f)/mlp/dot:"),
                       (9, "flops")):
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                            + _len(2, sname.encode())))
    body += _len(3, b"\x12\x07XLA Ops")           # a line, skipped
    return _len(1, body)


def test_name_stacks_from_the_protobuf():
    dev = _plane("/device:TPU:0", [
        ("%a = bf16[2] fusion()", [_int(1, 9) + _int(4, 12),
                                   _int(1, 7) + _len(5, b"jit(f)/attn/x:")]),
        ("%b = bf16[2] fusion()", [_int(1, 7) + _int(7, 8)]),    # by ref
        ("%c = bf16[2] copy()", [_int(1, 9) + _int(4, 3)])])
    host = _plane("/host:CPU", [("%d = x", [_int(1, 7) + _len(5, b"y")])])
    assert scopes._tf_ops(dev + host) == {"%a = bf16[2] fusion()":
                                          "jit(f)/attn/x:",
                                          "%b = bf16[2] fusion()":
                                          "jit(f)/mlp/dot:"}


def test_cpu_profile_holds_the_program_spans(tmp_path):
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro import configs as cfgs
    from repro.models import model as M
    from repro.serve.step import greedy_generate

    cfg = dataclasses.replace(cfgs.get_smoke_config("qwen2-0.5b"),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.array([[5, 9, 2]], jnp.int32)
    greedy_generate(params, cfg, prompt, max_new=2, max_seq=8)
    jax.profiler.start_trace(str(tmp_path))
    greedy_generate(params, cfg, prompt, max_new=2, max_seq=8
                    ).block_until_ready()
    jax.profiler.stop_trace()
    loaded = scopes.load_dir(tmp_path)
    assert scopes.load_dir(tmp_path) is loaded          # kept, not re-read
    (gen,) = [s for s in loaded["spans"] if s[0] == scopes.GENERATE]
    assert gen[3] == {"batch": 1, "prompt_len": 3, "max_new": 2,
                      "max_seq": 8}
    # the CPU backend writes no device plane: nothing is read
    assert loaded["devices"] == [] and loaded["scopes"] == {}
    assert scopes.kv_cache_share(loaded) is None
    assert scopes.prompt_share(loaded) is None
    assert scopes.load_dir(tmp_path / "none") is None


def _reader(name):
    return run.load_module(METRICS / f"{name}.py",
                           "test_metric_" + name.replace(".", "_"))


def test_trace_readers(t, monkeypatch):
    monkeypatch.setattr(scopes, "load_dir", lambda _dir: t)
    facts = {"kind": "decode", "trace": {"busy_s": 1.0}}
    assert _reader("kv_cache_share.decode").read(facts) == \
        pytest.approx(scopes.kv_cache_share(t))
    assert _reader("prompt_share.decode").read(facts) == \
        pytest.approx(scopes.prompt_share(t))
    # no device plane (the CPU), or no trace: nothing is read
    for f in ({"kind": "decode", "trace": {"busy_s": 0}},
              {"kind": "decode"}, {"kind": "train", "trace": {"busy_s": 1}}):
        assert _reader("kv_cache_share.decode").read(f) is None
        assert _reader("prompt_share.decode").read(f) is None


def test_compile_seconds_of_setup(monkeypatch):
    from repro.launch import compile_cache
    asked = []

    def stats(until=None):
        asked.append(until)
        return {"programs": 4, "hits": 3, "misses": 1, "seconds": 2.5,
                "load_seconds": 0.5}

    monkeypatch.setattr(compile_cache, "compile_stats", stats)
    reader = _reader("compile_s.setup")
    facts = {"setup_end": 12.0, "trace": {"busy_s": 1.0}}
    assert reader.read(facts) == 2.5 and asked == [12.0]
    assert reader.read(dict(facts, trace={"busy_s": 0})) is None
    assert reader.read({"trace": {"busy_s": 1.0}}) is None
