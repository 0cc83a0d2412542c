"""Host spans of the program, written into the profile that
``jax.profiler`` records, so they share a clock with the device's ops.

Every span is named ``repro:<name>``; keyword arguments are stored on the
span as its stats.  Outside a profile a span costs one enter and exit.
"""
from __future__ import annotations

import jax

PREFIX = "repro:"


def span(name: str, **args):
    """``with span("serve.decode_step"): ...`` marks one phase on the host."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
