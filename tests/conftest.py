import os
import sys

# NB: do NOT set XLA_FLAGS / host device count here — smoke tests and
# benches must see 1 device.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Each module leaves no compiled program behind.  A worker process
    runs many modules, and a profiler trace taken later in it lists every
    executable still alive (``bench/tests`` read such traces)."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()
