"""The ``mosaic`` fixture of the described-chip compile tests
(``test_tpu_compile.py``, ``test_tpu_compile_mesh.py``)."""
import jax
import pytest

from repro import kernels as K


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lower through Mosaic, with jit caches and JAX's persistent
    compilation cache out of the way (no interpret-mode trace is reused,
    and nothing unreadable is written)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(K, "INTERPRET", False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
