"""Share of the traced decode call's device busy time spent on the prompt:
busy time from the start of the first to the end of the last execution of
the serve step launched under the program's ``serve.prompt_step`` spans,
over the call's busy time.  The k-th execution in start order is matched to
the k-th launch span (``serve.prompt_step`` or ``serve.decode_step``);
nothing is read where their counts differ (``scopes.py``)."""
from pathlib import Path

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_out" / "trace"


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "decode" or not trace or not trace["busy_s"]:
        return None
    import scopes
    t = scopes.load_dir(TRACE_DIR)
    return None if t is None else scopes.prompt_share(t)
