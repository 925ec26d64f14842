import os

# Tests run on the single real CPU device; only the dry-run uses 512
# placeholder devices (set inside launch/dryrun.py, NOT here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture
def fake_mesh():
    """Factory for an AbstractMesh — sharding-spec construction tests need
    mesh *geometry* only, and a real Mesh can't be built from one CPU device
    (the emulated-fleet suite in tests/multihost/ covers real meshes)."""
    def make(data=4, model=4):
        return jax.sharding.AbstractMesh((data, model), ("data", "model"))
    return make
