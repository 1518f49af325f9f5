"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 CPU device;
only launch/dryrun.py forces 512 host devices (in its own process)."""
import jax
import pytest

from repro.data.scenes import structured_scene
from repro.data.trajectory import orbit_trajectory


@pytest.fixture(scope='session')
def small_scene():
    return structured_scene(jax.random.PRNGKey(0), 1200)


@pytest.fixture(scope='session')
def cams64():
    return orbit_trajectory(6, width=64, height_px=64)
