"""circulant_use_pct — circulant pricer (``core.engines.jax_circulant``).

Share of the candidate rows the pricer priced whose values the hillclimb's
accept loop consumed: 100 * examined / priced rows, summed over the
``repro.hillclimb.tally`` marks (``bench.program_trace``).  The rest is
waste: padding of the last chunk of a batch, and the rows after an
acceptance, which are priced again against the new base.
"""
from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None:
        return None
    priced = prog.stat("repro.hillclimb.tally", "priced_rows")
    if not priced:
        return None
    return 100.0 * prog.stat("repro.hillclimb.tally", "examined") / priced
