"""Share of the traced decode window in which no operation ran on the
device: 1 - busy / window, from the profiler trace (``devtrace.py``)."""


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "decode" or not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["idle_share"]
