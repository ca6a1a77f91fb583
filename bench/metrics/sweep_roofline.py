"""sweep_roofline — kernels (``kernels.bfs_sweep._pallas_sweep``).

Share of its memory roofline that the BFS sweep kernel reaches: the least
time the chip could take, the bytes every implementation must move (each
swept graph's neighbour table once, one int32 row per real source; counted
at the dispatch boundary by ``bench.workcount.sweep_bytes``) over the HBM
bandwidth of ``bench/peaks.json``, divided by the kernel's device time in
the trace.  The kernel is the Pallas custom call that the compiler names
after the program's jitted ``sweep`` function, inside the sharded delta
program ``jit_per_shard`` (``?`` where the op lies outside every program
interval of the trace).
"""
import re

from bench import trace

NAME = re.compile(r"^(jit_per_shard|\?)/sweep\.\d+$")


def read(ctx):
    t = ctx["trace"]
    bw = ctx["peaks"].get("hbm_bytes_per_s")
    nbytes = ctx["counters"].get("sweep_bytes", 0)
    lo, hi = t.window
    secs = sum(sum(trace.op_seconds(ev, lo, hi, NAME.match).values())
               for ev in t.ops.values())
    if not bw or not nbytes or secs <= 0:
        return None
    return 100.0 * (nbytes / bw) / secs
