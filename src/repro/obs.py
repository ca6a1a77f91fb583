"""Named spans of the search's stages, on the JAX profiler's clock.

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation``: under a
running profiler it lands in the trace's host timeline beside the device
ops, and its keyword arguments become the event's stats.  ``mark`` opens
and closes an empty span, for a count that is final only once the work it
counts is done.  Without a profiler both cost one check and record nothing.

Neither reads a clock or keeps state.  Where ``jax`` has not been imported
no profiler can be running, so both do nothing and import nothing: the
numpy-only paths stay free of JAX.  Spans belong in host code only, never
inside a jitted or Pallas function, and their counts are integers the
caller already holds.
"""
from __future__ import annotations

import contextlib
import sys


def span(name: str, **counts):
    """Context manager that records ``name`` (with ``counts``) while a
    profiler trace is running."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **counts)


def mark(name: str, **counts) -> None:
    """Record an empty ``name`` span carrying ``counts``."""
    with span(name, **counts):
        pass
