import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess tests (~1 min each)")


@pytest.fixture
def fresh_compiles():
    """Compile without JAX's persistent cache: its keys leave metadata
    out, so an entry another test wrote before the scopes existed would
    come back without them, and a hit there fires no backend compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
