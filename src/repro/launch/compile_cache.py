"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call ``use_compile_cache()`` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here.  Otherwise the cache goes to ``<checkout>/.jax_cache``, a path
derived from the package's own location: it never depends on a temporary
name, a pid or the time, so a second run of the same checkout finds what the
first one compiled.

It also counts what compiling costs: ``use_compile_cache()`` registers
``jax.monitoring`` listeners once, and ``compile_stats()`` sums the events
they recorded up to a given time.
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import List, Optional, Tuple

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# compiling, or loading from the persistent cache: the load's own time is
# also recorded as RETRIEVE, and is part of this one
COMPILE = "/jax/core/compile/backend_compile_duration"
RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"

# (time.perf_counter() at the event, event, seconds; 0 for a count)
_EVENTS: List[Tuple[float, str, float]] = []
_LISTENING = False


def _on_duration(event: str, secs: float, **_) -> None:
    if event in (TRACE, LOWER, COMPILE, RETRIEVE):
        _EVENTS.append((time.perf_counter(), event, secs))


def _on_event(event: str, **_) -> None:
    if event in (HIT, MISS):
        _EVENTS.append((time.perf_counter(), event, 0.0))


def compile_stats(until: Optional[float] = None) -> dict:
    """What compiling cost up to ``until`` (a ``time.perf_counter()``
    reading; None for everything so far), counted from the listeners that
    ``use_compile_cache()`` registered: programs lowered, persistent-cache
    hits and misses, seconds spent tracing, lowering and compiling or
    loading, and of those the seconds spent loading."""
    evs = [(e, s) for t, e, s in list(_EVENTS) if until is None or t <= until]
    return {"programs": sum(e == LOWER for e, _ in evs),
            "hits": sum(e == HIT for e, _ in evs),
            "misses": sum(e == MISS for e, _ in evs),
            "seconds": sum(s for e, s in evs if e in (TRACE, LOWER, COMPILE)),
            "load_seconds": sum(s for e, s in evs if e == RETRIEVE)}


def compile_line(until: Optional[float] = None) -> str:
    """``compile_stats`` as the one line an entry point prints."""
    st = compile_stats(until)
    return (f"[compile] {st['programs']} programs lowered, "
            f"{st['hits']} cache hits, {st['misses']} misses, "
            f"{st['seconds']:.3f} s tracing, lowering and compiling "
            f"({st['load_seconds']:.3f} s of it loading)")


def use_compile_cache() -> str:
    """Turn the persistent cache on and start counting compile events;
    returns the directory the cache writes to."""
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _LISTENING = True
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
