"""chip_smoke.py rehearsed on the CPU at a tiny size: every phase gives
its reference answers (the phases raise on any mismatch), `main()` refuses
a device that is not a TPU, and the compile cache lands where
`repro.compile_cache` says."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def loaded():
    rng = np.random.default_rng(0)
    ht, ref = chip_smoke.phase_hashtable(
        rng, nslots=256, load_keys=512, batch=64,
        find_batches=(("uniform", 1), ("zipfian", 1)))
    return rng, ht, ref


def test_hashtable_phase(loaded):
    _, ht, ref = loaded
    assert len(ref) == 512
    assert ht.win.data.shape == (chip_smoke.P, 256 * 3)


def test_pipeline_phase(loaded):
    rng, ht, ref = loaded
    final = chip_smoke.phase_pipeline(rng, ht, ref, batch=32, nbatches=2)
    assert len(ref) > 512
    assert final.win.data.shape == ht.win.data.shape


def test_queue_phase():
    chip_smoke.phase_queue(np.random.default_rng(1), capacity=1024, n=32)


def test_txn_phase():
    chip_smoke.phase_txn(batches=1, nops=2)


def test_kernel_phase():
    chip_smoke.phase_kernels(np.random.default_rng(2), words=2048,
                             slots=256, m=64)


def test_main_refuses_a_cpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) == 1
    assert capsys.readouterr().out == ""


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""
_COMPILE = """
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


def _cache_dirs(env_dir, code=_CACHE_PROBE):
    """(helper's answer, JAX's cache dir) in a fresh process."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_compile_cache_follows_env(tmp_path):
    got = _cache_dirs(str(tmp_path), _CACHE_PROBE + _COMPILE)
    assert got == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_checkout():
    want = os.path.join(ROOT, ".jax_cache")
    assert _cache_dirs(None) == [want, want]
