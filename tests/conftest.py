"""Shared test config.  NOTE: no global XLA device-count flags here —
smoke tests and benches must see the real single CPU device; only the
dry-run subprocess tests use forced host platform device counts.

The persistent compilation cache stays off, in this process and in the
subprocesses tests start (JAX reads the variable at import)."""

import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.default_rng(0)
