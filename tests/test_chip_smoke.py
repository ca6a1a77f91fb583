"""``chip_smoke.py`` at a tiny size on the CPU (Pallas interpret mode), its
refusal to report without a TPU, and the compile-cache placement it and the
benchmark driver share."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = dict(n=64, k=4, fold=4, proposal_batch=2, polish_iters=8)


def test_polish_and_circulant_phases_tiny():
    out = chip_smoke.phase_polish(interpret=True, replicas=2, **TINY)
    assert out["device_dispatches"] > 0
    assert out["evals_delta"] + out["evals_full"] > 0
    assert out["certified_total_hops"] > 0
    circ = chip_smoke.phase_circulant(n=64, k=4, seed=1)
    assert circ["offsets"][0] == 1 and circ["mpl"] > 1


def test_polish_phase_catches_a_wrong_interpret_mode():
    with pytest.raises(AssertionError, match="interpret"):
        chip_smoke.phase_polish(interpret=False, replicas=2, **TINY)


def test_four_chip_phase_tiny(devices8):
    out = devices8(f"""
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        out = chip_smoke.phase_four_chips(replicas=4, **{TINY!r})
        print(out["device_dispatches"] > 0, out["allreduce_shape"])
    """, n_devices=4)
    assert out.strip() == "True [4, 1024, 1024]"


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err  # found the package, refused the platform


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env={**env, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiles land there."""
    cache = tmp_path / "cache"
    code = (f"import sys; sys.path.insert(0, {str(ROOT / 'src')!r})\n"
            "from repro import compile_cache\n"
            "print(compile_cache.enable())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    import jax

    from repro import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
