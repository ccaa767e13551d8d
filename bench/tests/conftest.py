import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

KEPT = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture(autouse=True)
def jax_config_kept(monkeypatch, tmp_path):
    """A run points JAX's compile cache at the checkout unless
    JAX_COMPILATION_CACHE_DIR is set: set it, and give back every cache
    setting the run touches, so the test process is left as it was."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    kept = {k: getattr(jax.config, k) for k in KEPT}
    yield
    for k, v in kept.items():
        jax.config.update(k, v)
