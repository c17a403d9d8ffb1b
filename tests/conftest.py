import jax
import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — tests must see the real single CPU
# device (the 512-device override belongs to launch/dryrun.py only).


@pytest.fixture(scope="session")
def mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
