"""Share of the traced decode call's device busy time spent in ops that
move the KV cache: device self time of the ops whose name stack holds the
program's ``kv_cache`` scope, or whose result ends in [batch, max_seq, a, b]
after any leading axes (the scan's slices and stacks of the per-layer
cache and XLA's copies of it, which carry no scope of their own), over the
call's busy time.  ``batch`` and ``max_seq`` are the arguments of the
program's ``serve.generate`` span (``scopes.py``)."""
from pathlib import Path

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_out" / "trace"


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "decode" or not trace or not trace["busy_s"]:
        return None
    import scopes
    t = scopes.load_dir(TRACE_DIR)
    return None if t is None else scopes.kv_cache_share(t)
