import os

# Tests that need a multi-device mesh spawn their own env; the default test
# process keeps a SMALL forced device count (8) so meshed unit tests can run.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402  (initialize after the flag)

import pytest


@pytest.fixture(autouse=True)
def _clear_mesh_context():
    """Keep the module-level mesh context from leaking across tests."""
    yield
    from repro.models.common import clear_mesh_context
    clear_mesh_context()
