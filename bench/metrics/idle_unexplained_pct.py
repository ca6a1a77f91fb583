"""idle_unexplained_pct — the device.

Share of the device's idle time in the traced window during which the
program was in none of its stages: 100 * idle time under no ``repro.*``
stage span / idle time, summed over the chips used.  ``repro.polish`` and
``repro.hillclimb`` enclose whole calls and are not stages.  A reading near
0 says every idle gap has a named cause; a program without stage spans
reads 100.
"""
from bench import program_trace, trace


def overlap_ns(gaps, spans) -> int:
    """Length of the overlap of two sorted lists of disjoint intervals."""
    total, j = 0, 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += min(b, spans[k][1]) - max(a, spans[k][0])
            k += 1
    return total


def read(ctx):
    t = ctx["trace"]
    prog = program_trace.of(ctx)
    lo, hi = t.window
    stages = trace.merge(trace.clip(prog.stages() if prog else [], lo, hi))
    idle = explained = 0
    for ev in t.ops.values():
        idle_gaps = trace.gaps(ev, lo, hi)
        idle += sum(b - a for a, b in idle_gaps)
        explained += overlap_ns(idle_gaps, stages)
    if not idle:
        return None
    return 100.0 * (idle - explained) / idle
