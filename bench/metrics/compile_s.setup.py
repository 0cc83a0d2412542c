"""Seconds of set-up spent tracing, lowering, and compiling or loading
programs before the window opened, as the program's compile counter
(``repro.launch.compile_cache.compile_stats``) recorded them.  Read where
the traced run's trace has a device plane; a program without the counter
reads nothing."""


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace["busy_s"] or "setup_end" not in facts:
        return None
    try:
        from repro.launch.compile_cache import compile_stats
    except ImportError:
        return None
    return compile_stats(until=facts["setup_end"])["seconds"]
