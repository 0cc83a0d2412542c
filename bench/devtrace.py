"""The one reduction from a profiler trace to busy time, idle share and the
breakdown.

``reduce`` works on plain event lists so that a test can check it by hand:
device operations and host spans as ``(name, start_ns, duration_ns)`` and
the traced window as ``(start_ns, end_ns)``.  ``from_xplane`` reads those
lists out of the ``.xplane.pb`` that ``jax.profiler`` writes: the device
operations are the events of each device plane's ``XLA Ops`` line, and the
host spans are the ``jax.profiler.TraceAnnotation`` events whose names
start with ``SPAN_PREFIX``, which the harness puts around its own calls.

- busy: the union of the device operations' intervals inside the window,
  averaged over the devices;
- idle share: 1 - busy / window;
- device_ops: the operations with most device self time (less the
  operations nested in them), summed by op name and result type;
- idle_gaps: device idle time inside the window, summed by the innermost
  host span that covers the middle of each gap.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
NO_SPAN = "outside any span"


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w0: int, w1: int):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _label(name: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``%fusion.3
    bf16[8,128]``: the op and its result type, without the HLO body."""
    lhs, _, rhs = name.partition(" = ")
    kind = rhs.split("{")[0].split(" ")[0]
    return f"{lhs} {kind}".strip()[:120]


def _self_times(events: List[Tuple[str, int, int]]):
    """(label, self ns) of each event: its time less that of the events
    nested inside it (a while loop holds its body's operations)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e - s for _, s, e in evs]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    return [(_label(n), t) for (n, _, _), t in zip(evs, own)]


def _covering(spans: Sequence[Event], t: float) -> str:
    """The innermost (shortest) host span containing time ``t``."""
    best, best_dur = NO_SPAN, None
    for name, s, d in spans:
        if s <= t <= s + d and (best_dur is None or d < best_dur):
            best, best_dur = name, d
    return best


def reduce(devices: Sequence[Sequence[Event]], spans: Sequence[Event],
           window: Tuple[int, int], top: int = 10) -> Dict[str, object]:
    """Busy seconds (mean over devices), window seconds, idle share and
    the breakdown of one traced window."""
    w0, w1 = window
    busy_ns, by_op = 0, defaultdict(int)
    gaps = defaultdict(int)
    for events in devices:
        clipped = []
        for name, s, d in events:
            iv = _clip(s, s + d, w0, w1)
            if iv:
                clipped.append((name,) + iv)
        for label, t in _self_times(clipped):
            by_op[label] += t
        union = _union([iv[1:] for iv in clipped])
        busy_ns += sum(e - s for s, e in union)
        edges = [w0] + [t for iv in union for t in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                label = _covering(spans, (g0 + g1) / 2)
                gaps[label.removeprefix(SPAN_PREFIX)] += g1 - g0
    n = max(len(devices), 1)
    window_s = (w1 - w0) / 1e9
    busy_s = busy_ns / n / 1e9
    rank = lambda d: sorted(([k, v / n / 1e9] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": rank(by_op), "idle_gaps": rank(gaps)}


def from_xplane(path: Path) -> Tuple[List[List[Event]], List[Event]]:
    """(device op events per device, host spans) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: List[List[Event]] = []
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for line in plane.lines if line.name == "XLA Ops"
                   for ev in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            spans.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                         for line in plane.lines for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX))
    return devices, spans


def reduce_dir(trace_dir: Path, top: int = 10) -> Dict[str, object]:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``, over the host
    span named ``WINDOW_SPAN``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, spans = from_xplane(files[-1])
    windows = [(s, s + d) for name, s, d in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {files[-1]}")
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    return reduce(devices, inner, windows[-1], top)
