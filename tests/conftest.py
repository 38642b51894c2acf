"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 CPU device
(only launch/dryrun.py forces 512 placeholder devices, in its own process).

The suite runs on the CPU, even on a machine with a chip: a chip belongs
to one process, and the suite never takes it.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


def assert_trees_close(a, b, *, atol=1e-5, rtol=1e-5):
    import numpy as np
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x, dtype=np.float64), np.asarray(y, dtype=np.float64),
            atol=atol, rtol=rtol)
