"""patch_roofline — kernels (``kernels.bfs_sweep._pallas_patch``).

Share of its memory roofline that the min-plus patch kernel reaches: the
bytes no implementation can avoid (each patched chain's (s, n) int32 base
rows read once per dispatch, one row per endpoint of each patched
proposal's added edges; the state written back is not counted; counted at
the dispatch boundary by ``bench.workcount.patch_bytes``) over the HBM
bandwidth of ``bench/peaks.json``, divided by the kernel's device time in
the trace.  The kernel is the Pallas custom call that the compiler names
after the ``per_shard`` body of the sharded delta program ``jit_per_shard``
(``?`` where the op lies outside every program interval of the trace).
"""
import re

from bench import trace

NAME = re.compile(r"^(jit_per_shard|\?)/per_shard\.\d+$")


def read(ctx):
    t = ctx["trace"]
    bw = ctx["peaks"].get("hbm_bytes_per_s")
    nbytes = ctx["counters"].get("patch_bytes", 0)
    lo, hi = t.window
    secs = sum(sum(trace.op_seconds(ev, lo, hi, NAME.match).values())
               for ev in t.ops.values())
    if not bw or not nbytes or secs <= 0:
        return None
    return 100.0 * (nbytes / bw) / secs
