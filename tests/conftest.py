"""Shared fixtures.

No XLA_FLAGS device-count override lives here — smoke tests and benches
run on the single real CPU device; CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the shard_map
tests exercise 8 virtual devices (see ``mesh8``).
"""

import jax
import numpy as np
import pytest

from repro import compat


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def mesh8():
    """A (2, 4) mesh when 8 host devices are available, else skip."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return compat.make_mesh((2, 4), ("data", "model"))
