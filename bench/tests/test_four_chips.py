"""The four-chip polish cell's readers, on four virtual CPU devices.

A tiny traced run of the 16-replica cell (``tiny.cell(replicas=16,
chips=4)``, four chains a device) reads ``place_ms`` and
``state_move_mb`` from the program's spans and tally; a traced run of the
hillclimb traffic (``polish=False``) reads nothing for either.
"""
import json
import os
import subprocess
import sys
import textwrap

from bench.tests import tiny

SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    from bench import run
    from bench.tests import tiny
    # the jnp twins of the kernels: the same programs' placement, faster
    # here than the kernels in interpret mode
    traffic = dict(tiny.POLISH, job={"strategy": "large",
                                     "proposal_batch": 2})
    c = tiny.cell(traffic, replicas=16, chips=4, per_layer=tiny.PER_LAYER)
    res = tiny.run(c, trace=True)
    # the readers on the traced run's context, as the harness calls them
    from bench import program_trace, trace
    ctx = {"trace": trace.load_xplane(program_trace.newest()),
           "counters": {}}
    polish = {m: run.load_reader(m)(ctx)
              for m in ("place_ms", "state_move_mb")}
    h = tiny.cell(tiny.HILLCLIMB, n=1024, k=8, fold=8, budget=100,
                  replicas=16, chips=4, per_layer=[])
    tiny.run(h, trace=True)
    ctx = {"trace": trace.load_xplane(program_trace.newest()),
           "counters": {}}
    hill = {m: run.load_reader(m)(ctx)
            for m in ("place_ms", "state_move_mb")}
    print(json.dumps({"correct": res["correct"],
                      "devices": res["device"]["count"],
                      "polish": polish, "hillclimb": hill}))
""")


def test_four_chip_cell_reads_placement_and_moves():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = str(tiny.__file__).rsplit("/bench/", 1)[0]
    out = subprocess.run([sys.executable, "-c", SCRIPT, root], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] and got["devices"] == 4
    assert got["polish"]["place_ms"] > 0
    # the tiny job's one exchange (after iteration 50 of 66) moves at most
    # one (s, n) int32 block: s = 64 rows of n = 256 vertices
    assert 0 <= got["polish"]["state_move_mb"] <= 64 * 256 * 4 / 66 / 1e6
    assert got["hillclimb"] == {"place_ms": None, "state_move_mb": None}
