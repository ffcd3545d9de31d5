import jax
import pytest

# Tests run on the real device set (1 CPU device) — the dry-run alone forces
# 512 host devices, in its own process. Keep x64 off (TPU parity).
jax.config.update("jax_platform_name", "cpu")
# No persistent compilation cache, even where an entry point under test
# points one at a directory: compiles for a described TPU could be written
# but never read back here.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
