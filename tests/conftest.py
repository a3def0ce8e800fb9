import os

# Tests run single-device (the dry-run sets its own 512-device flag in a
# subprocess); keep CPU math deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent compilation cache in tests (nor in the children they
# start), even where an entry point points it at the checkout
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax
import pytest


@pytest.fixture
def run_dir(tmp_path):
    return str(tmp_path / "run")


@pytest.fixture
def mesh1():
    """Trivial 1-device mesh with the production axis names."""
    from repro.launch.mesh import make_mesh
    return make_mesh((1,), ("data",))


@pytest.fixture
def key():
    return jax.random.key(0)
