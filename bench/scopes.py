"""Device time of a traced decode call by the program's own names.

    python3 bench/scopes.py <trace_dir>

prints device time by scope, by phase, and device idle time by the
innermost host span, the program's ``repro:`` spans included.  The
per-layer readers ``kv_cache_share.decode`` and ``prompt_share.decode``
call ``load_dir`` on ``<checkout>/.bench_out/trace``, the directory that
``drive._traced`` writes.

What it takes from the newest ``.xplane.pb`` there:

- the device ops (the ``XLA Ops`` line of each device plane) with their
  name stack: the ``tf_op`` stat of each op's event metadata, the
  ``op_name`` of its HLO instruction, such as
  ``jit(serve_step)/while/body/closed_call/attn/kv_cache/le:``.
  ``jax.profiler.ProfileData`` does not expose event metadata, so
  ``_tf_ops`` reads it from the protobuf's wire format;
- each device's module executions (the ``XLA Modules`` line);
- the host spans whose names start with ``repro:`` or ``bench:``, with
  their arguments.

Everything below ``load`` works on plain lists, so that a test can check
it on a trace built by hand (``tests/data/scoped_trace.json``).
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import devtrace

TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"
PROGRAM_PREFIX = "repro:"
GENERATE = PROGRAM_PREFIX + "serve.generate"
PROMPT_STEP = PROGRAM_PREFIX + "serve.prompt_step"
DECODE_STEP = PROGRAM_PREFIX + "serve.decode_step"
STEP_MODULE = "jit_serve_step("          # a module event's name: jit_<fn>(id)
SCOPES = ("embed", "attn", "kv_cache", "mlp", "lm_head")
CACHE_SCOPE = "kv_cache"
BY_SHAPE = "kv_cache (by shape)"
NO_SCOPE = "(no scope)"

Event = Tuple[str, int, int]
Span = Tuple[str, int, int, dict]


# ---------------------------------------------------------------------------
# Reading the trace.
# ---------------------------------------------------------------------------
def _fields(buf: memoryview):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a view, not decoded."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return out

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, wire, varint()
        elif wire == 2:
            size = varint()
            yield field, wire, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")


def _map_values(buf: memoryview):
    """The values of one entry of a protobuf map (field 2 of the entry)."""
    return [v for f, _, v in _fields(buf) if f == 2]


def _tf_ops(raw: bytes) -> Dict[str, str]:
    """Op name -> name stack (``tf_op``) over every device plane of an
    ``XSpace`` (tsl/profiler/protobuf/xplane.proto): planes are field 1,
    a plane's name field 2, its event metadata field 4 and stat metadata
    field 5 (maps keyed by id); an event metadata's name is field 2 and its
    stats field 5; a stat's metadata id is field 1, a string value field 5
    and a reference to a stat metadata's name field 7."""
    out: Dict[str, str] = {}
    for f, _, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, _, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.extend(_map_values(v))
            elif pf == 5:
                for md in _map_values(v):
                    d = {mf: mv for mf, _, mv in _fields(md)}
                    stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        for md in events:
            ev_name, stack = "", None
            for ef, _, ev in _fields(md):
                if ef == 2:
                    ev_name = bytes(ev).decode()
                elif ef == 5:
                    st = {sf: sv for sf, _, sv in _fields(ev)}
                    if st.get(1) in tf_op:
                        stack = (bytes(st[5]).decode() if 5 in st else
                                 stat_names.get(st.get(7), ""))
            if stack:
                out[ev_name] = stack
    return out


def load(path: Path) -> dict:
    """The parts of one ``.xplane.pb`` that the readers take, as plain
    lists: ``devices`` (ops per device), ``modules`` (module executions
    per device), ``spans`` (host spans with their arguments) and
    ``scopes`` (op name -> name stack)."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    devices: List[List[Event]] = []
    modules: List[List[Event]] = []
    spans: List[Span] = []
    prefixes = (PROGRAM_PREFIX, devtrace.SPAN_PREFIX)
    names: Dict[str, str] = {}          # one string per op name, not per event
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: [(names.setdefault(ev.name, ev.name),
                                  int(ev.start_ns), int(ev.duration_ns))
                                 for ev in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            if lines.get("XLA Ops"):
                devices.append(lines["XLA Ops"])
                modules.append(lines.get("XLA Modules", []))
        elif plane.name.startswith("/host:"):
            spans.extend((ev.name, int(ev.start_ns), int(ev.duration_ns),
                          dict(ev.stats))
                         for line in plane.lines for ev in line.events
                         if ev.name.startswith(prefixes))
    return {"devices": devices, "modules": modules, "spans": spans,
            "scopes": _tf_ops(raw) if devices else {}}


_LOADED: Dict[Tuple[str, float], dict] = {}


def load_dir(trace_dir: Path = TRACE_DIR) -> Optional[dict]:
    """``load`` of the newest ``.xplane.pb`` under ``trace_dir``, kept
    by path and modification time; None where there is none."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    key = (str(files[-1]), files[-1].stat().st_mtime)
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(files[-1])
    return _LOADED[key]


# ---------------------------------------------------------------------------
# The reductions, on plain lists.
# ---------------------------------------------------------------------------
def window(t: dict) -> Optional[Tuple[int, int]]:
    """The traced call: the harness's window span where there is one,
    else from the first ``serve.generate`` span to the last device op."""
    spans = t["spans"]
    wins = [(s, s + d) for n, s, d, _ in spans if n == devtrace.WINDOW_SPAN]
    if wins:
        return wins[-1]
    gens = [s for n, s, _, _ in spans if n == GENERATE]
    ends = [s + d for ops in t["devices"] for _, s, d in ops]
    if not gens or not ends:
        return None
    return min(gens), max(ends)


def _clipped(ops: Sequence[Event], w0: int, w1: int):
    out = []
    for name, s, d in ops:
        iv = devtrace._clip(s, s + d, w0, w1)
        if iv:
            out.append((name,) + iv)
    return out


def _busy(ops: Sequence[Event], w0: int, w1: int) -> int:
    return sum(e - s for s, e in devtrace._union(
        [iv[1:] for iv in _clipped(ops, w0, w1)]))


def result_dims(name: str) -> Optional[Tuple[int, ...]]:
    """The shape of an op's result from its HLO text:
    ``%copy.3 = bf16[1,8,24,2,64]{...} copy(...)`` -> (1, 8, 24, 2, 64);
    None for a tuple result, such as a ``while`` loop's, whose own time is
    the loop's control and not its elements'."""
    m = re.match(r"[a-z0-9]+\[([0-9,]*)\]", name.partition(" = ")[2])
    return tuple(int(x) for x in m.group(1).split(",") if x) if m else None


def scope_of(stack: str) -> str:
    """The innermost of ``SCOPES`` in a name stack, or ``NO_SCOPE``."""
    parts = stack.rstrip(":").split("/")
    return next((p for p in reversed(parts) if p in SCOPES), NO_SCOPE)


def cache_shapes(t: dict) -> set:
    """(batch, max_seq) of each ``serve.generate`` call in the trace."""
    return {(a["batch"], a["max_seq"]) for n, _, _, a in t["spans"]
            if n == GENERATE and "batch" in a and "max_seq" in a}


def op_scope(name: str, t: dict, shapes: set) -> str:
    """The scope an op is counted under: ``kv_cache`` where its name stack
    holds it, else ``BY_SHAPE`` where its result ends in
    [batch, max_seq, a, b] after any leading axes, else its own."""
    scope = scope_of(t["scopes"].get(name, ""))
    dims = result_dims(name)
    if scope != CACHE_SCOPE and dims and len(dims) >= 4 \
            and (dims[-4], dims[-3]) in shapes:
        return BY_SHAPE
    return scope


def by_scope(t: dict) -> Dict[str, float]:
    """Device self seconds of the call by scope (mean over devices)."""
    w, shapes = window(t), cache_shapes(t)
    out: Dict[str, float] = defaultdict(float)
    if w is None:
        return out
    scope: Dict[str, str] = {}
    for ops in t["devices"]:
        clipped = _clipped(ops, *w)
        # _self_times labels each op by its name; index them to keep names
        for i, ns in devtrace._self_times(
                [(str(k),) + iv[1:] for k, iv in enumerate(clipped)]):
            name = clipped[int(i)][0]
            if name not in scope:
                scope[name] = op_scope(name, t, shapes)
            out[scope[name]] += ns / 1e9 / len(t["devices"])
    return dict(out)


def busy_s(t: dict) -> float:
    w = window(t)
    if w is None or not t["devices"]:
        return 0.0
    return sum(_busy(ops, *w) for ops in t["devices"]) / len(
        t["devices"]) / 1e9


def kv_cache_share(t: dict) -> Optional[float]:
    """Percent of the call's device busy time spent in ops that move the
    KV cache; None without a device plane or a ``serve.generate`` span."""
    busy = busy_s(t)
    if not busy or not cache_shapes(t):
        return None
    scopes = by_scope(t)
    return 100.0 * (scopes.get(CACHE_SCOPE, 0.0)
                    + scopes.get(BY_SHAPE, 0.0)) / busy


def phases(t: dict) -> Optional[Dict[str, Tuple[int, int]]]:
    """(first start, last end) of the serve-step executions launched
    under each phase's spans, per phase: the k-th execution in start order
    is matched to the k-th launch span.  None where the counts differ."""
    w = window(t)
    if w is None or len(t["devices"]) != 1:
        return None
    launches = sorted((s, n) for n, s, _, _ in t["spans"]
                      if n in (PROMPT_STEP, DECODE_STEP) and w[0] <= s <= w[1])
    execs = sorted((s, s + d) for n, s, d in t["modules"][0]
                   if n.startswith(STEP_MODULE) and w[0] <= s <= w[1])
    if not launches or len(launches) != len(execs):
        return None
    out: Dict[str, Tuple[int, int]] = {}
    for (_, phase), (s, e) in zip(launches, execs):
        key = phase.removeprefix(PROGRAM_PREFIX)
        lo, hi = out.get(key, (s, e))
        out[key] = (min(lo, s), max(hi, e))
    return out


def prompt_share(t: dict) -> Optional[float]:
    """Percent of the call's device busy time that lies between the start
    of the first and the end of the last serve-step execution launched
    under a ``serve.prompt_step`` span."""
    ph, busy = phases(t), busy_s(t)
    key = PROMPT_STEP.removeprefix(PROGRAM_PREFIX)
    if not ph or key not in ph or not busy:
        return None
    return 100.0 * _busy(t["devices"][0], *ph[key]) / 1e9 / busy


def report(t: dict) -> str:
    w = window(t)
    if w is None:
        return "no device plane or no traced call in this trace"
    busy = busy_s(t)
    lines = [f"window {(w[1] - w[0]) / 1e9:.6f} s, device busy {busy:.6f} s"]
    lines.append("device self time by scope:")
    for k, v in sorted(by_scope(t).items(), key=lambda kv: -kv[1]):
        lines.append(f"  {k:24s} {v:12.6f} s {100 * v / busy:7.2f}%")
    lines.append("device busy time by phase:")
    ph = phases(t) or {}
    rest = busy
    for k, (s, e) in ph.items():
        v = _busy(t["devices"][0], s, e) / 1e9
        rest -= v
        lines.append(f"  {k:24s} {v:12.6f} s {100 * v / busy:7.2f}%")
    lines.append(f"  {'outside the launches':24s} {rest:12.6f} s "
                 f"{100 * rest / busy:7.2f}%")
    r = devtrace.reduce(t["devices"], [sp[:3] for sp in t["spans"]
                                       if sp[0] != devtrace.WINDOW_SPAN], w)
    lines.append("device idle time by the innermost host span:")
    for k, v in r["idle_gaps"]:
        lines.append(f"  {k:24s} {v:12.6f} s")
    lines.append(f"kv_cache_share {kv_cache_share(t)}  "
                 f"prompt_share {prompt_share(t)}")
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1])
    loaded = load_dir(Path(sys.argv[1]))
    if loaded is None:
        sys.exit(f"no .xplane.pb under {sys.argv[1]}")
    print(report(loaded))
