"""A whole run with the timed path broken underneath must read not correct.

Each test skips only the harness's look for a chip, runs a tiny cell end to
end through ``bench.run.run_cell``, and plants one fault in the program's
path: a step that returns its state unchanged, half of a batch left out,
the exchange between chips left out, an answer altered where it is
produced.  The sound run and the control (the reference in float32, in the
program's place) are checked alongside.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import check, reference
from bench.tests import tiny


@pytest.fixture
def restore():
    """Undo every attribute a test patches on the program's modules."""
    saved = []

    def patch(mod, name, value):
        saved.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    yield patch
    for mod, name, value in reversed(saved):
        setattr(mod, name, value)


def test_sound_polish_run_is_correct():
    res = tiny.run(tiny.cell())
    assert res["correct"], res["checks"]
    assert res["checks"]["checked_jobs"]["value"] >= 1


def test_sound_hillclimb_run_is_correct():
    res = tiny.run(tiny.cell(tiny.HILLCLIMB, n=1024, k=8, fold=8, budget=100))
    assert res["correct"], res["checks"]


def test_state_returned_unchanged_is_caught(restore):
    import jax.numpy as jnp

    from repro.core.engines import pallas_sweep

    real = pallas_sweep.sharded_delta_state

    def unchanged(base, nbrs, srcs, patches, sentinel, **kw):
        totals, maxima, _ = real(base, nbrs, srcs, patches, sentinel, **kw)
        per = nbrs.shape[0] // base.shape[0]
        return totals, maxima, jnp.repeat(jnp.asarray(base), per, axis=0)

    restore(pallas_sweep, "sharded_delta_state", unchanged)
    res = tiny.run(tiny.cell())
    assert not res["correct"]


def test_half_of_the_batch_left_out_is_caught(restore):
    from repro.core.engines import pallas_sweep

    real = pallas_sweep.sharded_delta_state

    def half(base, nbrs, srcs, patches, sentinel, **kw):
        totals, maxima, state = real(base, nbrs, srcs, patches, sentinel, **kw)
        # the first half is left out and reads the mean of the rest (the
        # warm start's pricing is a batch of one and stays whole)
        h = len(totals) // 2
        totals, maxima = totals.copy(), maxima.copy()
        if h:
            totals[:h] = totals[h:].mean().astype(totals.dtype)
            maxima[:h] = maxima[h:].max()
        return totals, maxima, state

    restore(pallas_sweep, "sharded_delta_state", half)
    res = tiny.run(tiny.cell())
    assert not res["correct"]


def test_half_of_a_circulant_chunk_left_out_is_caught(restore):
    from repro.core import engines
    from repro.core.engines import jax_circulant

    real = jax_circulant._jax_sweep

    def half(n, m):
        sweep = real(n, m)

        def broken(chunk):
            total, diam, conn = (np.asarray(x).copy() for x in sweep(chunk))
            h = len(total) // 2
            total[h:2 * h], diam[h:2 * h], conn[h:2 * h] = (
                total[:h], diam[:h], conn[:h])
            return total, diam, conn

        return broken

    # price through the jitted pricer at this small n, as the cell does at
    # n >= 4096
    restore(engines, "resolve_circulant", lambda engine, n: "jax")
    restore(jax_circulant, "_jax_sweep", half)
    res = tiny.run(tiny.cell(tiny.HILLCLIMB, n=1024, k=8, fold=8, budget=100))
    assert not res["correct"]


@pytest.mark.parametrize("traffic,size", [
    (tiny.POLISH, {}), (tiny.HILLCLIMB, {"n": 1024, "k": 8, "fold": 8, "budget": 100})])
def test_answer_altered_where_produced_is_caught(restore, traffic, size):
    from repro.core import search

    real = search.large_search

    def altered(*a, **kw):
        res = real(*a, **kw)
        res.mpl = float(np.nextafter(res.mpl, np.inf))
        return res

    restore(search, "large_search", altered)
    res = tiny.run(tiny.cell(traffic, **size))
    assert not res["correct"]
    assert res["checks"]["mpl_gap"]["value"] > 0


@pytest.mark.parametrize("traffic,size", [
    (tiny.POLISH, {}), (tiny.HILLCLIMB, {"n": 1024, "k": 8, "fold": 8, "budget": 100})])
def test_control_reads_not_correct(traffic, size):
    """The reference in float32, put in the program's place."""
    from bench import traffic as gen

    c = tiny.cell(traffic, **size)
    fields = gen.job_fields(c["config"], c["traffic"])
    for seed in (3, 2**31 + 5):
        want = check.expected(fields, seed)
        got = check.expected(fields, seed, "float32")
        nums = check.summarize(0, [check.compare(fields, got, want)])
        assert not check.passed({**nums,
                                 "checked_jobs": {"value": 1, "limit": 1}})
        assert nums["mpl_gap"]["value"] > 0


def test_exchange_between_chips_left_out_is_caught():
    """Four virtual CPU devices, 16 replicas: the results of the shards on
    devices 1-3 are replaced by device 0's, as if the gather had not run."""
    script = textwrap.dedent("""
        import sys
        sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
        import numpy as np
        from repro.core.engines import pallas_sweep
        from bench.tests import tiny
        real = pallas_sweep.sharded_delta_state
        def no_exchange(base, nbrs, srcs, patches, sentinel, **kw):
            totals, maxima, state = real(base, nbrs, srcs, patches,
                                         sentinel, **kw)
            if sys.argv[2] == "fault" and len(totals) >= 4:
                q = len(totals) // 4
                totals = np.tile(totals[:q], 4)
                maxima = np.tile(maxima[:q], 4)
            return totals, maxima, state
        pallas_sweep.sharded_delta_state = no_exchange
        c = tiny.cell(replicas=16, chips=4)
        print("CORRECT", tiny.run(c)["correct"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = str(tiny.__file__).rsplit("/bench/", 1)[0]
    for mode, want in (("sound", "True"), ("fault", "False")):
        out = subprocess.run([sys.executable, "-c", script, root, mode],
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert f"CORRECT {want}" in out.stdout, out.stdout[-2000:]


def test_replay_matches_the_program_on_several_seeds():
    from bench import traffic as gen
    from repro import api

    c = tiny.cell(n=512, k=6, fold=4)
    fields = gen.job_fields(c["config"], c["traffic"])
    fields["engine"] = None  # the jnp twins: the same trajectory, faster here
    for seed in (2, 99, 2**31 + 1):
        got = check.observed(api.search(gen.spec(fields, seed)))
        nums = check.compare(fields, got, check.expected(fields, seed))
        assert not nums["mismatched"] and nums["mpl_gap"] == 0


def test_graph_checks_find_structure_faults():
    n, k, fold = 64, 4, 4
    edges = set(reference.circulant_edges(n, [1, 9]))
    bfs = reference.DenseBFS(n, n // fold)
    assert reference.graph_checks(n, k, fold, edges, bfs)["faults"] == []
    broken = set(edges)
    broken.remove((0, 9))
    broken.add((0, 10))  # vertex 9 loses a neighbour, 10 gains one
    faults = reference.graph_checks(n, k, fold, broken, bfs)["faults"]
    assert any("degree" in f for f in faults)
    assert any("rotation" in f for f in faults)
