"""Suite-wide test isolation."""
import gc

import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _fresh_jax_caches():
    """Drop the programs a test module compiled once it ends, so that no
    module sees executables another module left alive (tests that walk
    the process's live programs, as ``bench/program_trace`` does, would
    otherwise depend on which modules ran before them in a worker)."""
    yield
    jax.clear_caches()
    gc.collect()
