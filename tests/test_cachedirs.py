"""Where the compile and result caches live (``repro.cachedirs``)."""
import os
import subprocess
import sys

import jax

from repro import cachedirs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_caches_live_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("REPRO_SIM_CACHE", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert cachedirs.CHECKOUT == REPO
        assert cachedirs.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert cachedirs.sim_cache_dir() == os.path.join(REPO, ".sim_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    monkeypatch.setenv("REPRO_SIM_CACHE", "/elsewhere/sim")
    assert cachedirs.sim_cache_dir() == "/elsewhere/sim"


def test_compile_cache_env_places_the_entries(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, importing the runner leaves
    that directory in place and compiled entries land there."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.sim import runner\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert os.listdir(tmp_path)
