"""§4.5 library interface + launcher smoke coverage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import persistent_cache
from repro.core.library import pick


class TestLibrary:
    def test_vendor_fallback_for_odd_shapes(self):
        choice = pick(100, 100, 100)
        assert choice.name == "vendor:xla_dot"
        a = jnp.ones((100, 100))
        b = jnp.ones((100, 100))
        np.testing.assert_allclose(choice(a, b), a @ b)

    def test_tuned_kernel_for_aligned_shapes(self):
        choice = pick(128, 128, 128)
        assert choice.name.startswith("library:")
        rng = np.random.RandomState(0)
        a = jnp.asarray(rng.randn(128, 128), jnp.float32)
        b = jnp.asarray(rng.randn(128, 128), jnp.float32)
        np.testing.assert_allclose(choice(a, b), a @ b, rtol=1e-4, atol=1e-4)

    def test_decode_shape_routes_to_skinny(self):
        assert pick(8, 128, 128).name == "library:skinny_m"


class TestPersistentCache:
    @pytest.fixture
    def cache_dir_restored(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_dir_is_left_to_jax(self, monkeypatch, cache_dir_restored):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert persistent_cache.enable_persistent_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None

    def test_fixed_dir_in_the_checkout(self, monkeypatch, cache_dir_restored):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = persistent_cache.enable_persistent_cache()
        assert got == str(persistent_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert persistent_cache.CACHE_DIR.parent.joinpath(
            "chip_smoke.py").exists()
