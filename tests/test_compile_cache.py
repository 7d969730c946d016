"""Where the persistent compile cache lives (`utils/compile_cache.py`), and
that `chip_smoke.py`'s parent stays off JAX (one process per chip)."""
import os
import subprocess
import sys

import jax
import pytest

from idunno_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Put back what the test changes: other tests in this worker must see
    the cache configuration they saw before."""
    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", keep[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep[1])


def test_env_var_places_the_cache(cache_config, monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the code sets no directory: the
    value JAX itself read from the variable stays."""
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    jax.config.update("jax_compilation_cache_dir", placed)  # as at import
    assert compile_cache.enable_persistent_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed


def test_default_is_the_fixed_in_checkout_path(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_persistent_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.cache_counters()["dir"] == want


def test_jax_reads_the_variable_itself(tmp_path):
    """The claim the first case leans on, in a fresh interpreter pinned to
    the CPU (it loads no libtpu)."""
    placed = str(tmp_path / "cc")
    out = subprocess.run(
        [sys.executable, "-c",
         "from idunno_tpu.utils.compile_cache import "
         "enable_persistent_cache as e; print(e())"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": placed})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == placed


def test_importing_chip_smoke_leaves_jax_unloaded():
    """The parent of a chip-holding child must not touch JAX. The child of
    THIS test never loads JAX or libtpu either."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import chip_smoke; "
         "bad = [m for m in ('jax', 'jaxlib', 'flax', 'libtpu') "
         "if m in sys.modules]; print(bad); sys.exit(bool(bad))"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, (out.stdout, out.stderr[-2000:])
