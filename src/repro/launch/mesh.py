"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's forced-host-
device trick to work and for tests to see a single CPU device.

Production target: TPU v5e pods.  Single pod = 16 x 16 = 256 chips
(data, model); multi-pod adds a leading 'pod' axis (2 x 16 x 16 = 512).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ('pod', 'data', 'model') if multi_pod else ('data', 'model')
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f'need {n} devices for the production mesh, have {len(devices)} — '
            f'launch with XLA_FLAGS=--xla_force_host_platform_device_count=512 '
            f'for the dry-run (see launch/dryrun.py)')
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes)


def make_test_mesh(shape=(2, 2), axes=('data', 'model')):
    """Small mesh for unit tests (requires forced host devices)."""
    import numpy as np
    n = 1
    for s in shape:
        n *= s
    return jax.sharding.Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)


def make_serve_mesh(num_devices: int | None = None):
    """1-D ``devices`` mesh for the sharded serving fleet (one scene-block
    worker per device).  Requires genuinely distinct devices — jax meshes
    reject duplicates — so CPU CI launches with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    import numpy as np
    from repro.runtime.sharding import DEVICES_AXIS
    avail = jax.devices()
    n = len(avail) if num_devices is None else num_devices
    if len(avail) < n:
        raise RuntimeError(
            f'need {n} devices for the serving mesh, have {len(avail)} — '
            f'launch with XLA_FLAGS=--xla_force_host_platform_device_count='
            f'{n} on CPU')
    return jax.sharding.Mesh(np.asarray(avail[:n]), (DEVICES_AXIS,))


def serve_devices(num_workers: int, available=None) -> list:
    """Device handle per fleet worker, cycling over the available devices.

    Unlike a mesh, workers may OVERSUBSCRIBE: tier-1 CI runs the N-worker
    fleet on a single CPU device (workers are independent host loops over
    per-device steppers, not collective participants), while the
    multi-device CI job and real deployments get one worker per distinct
    device.  ``available`` narrows the devices cycled over (default: all
    of them) — e.g. one device, to oversubscribe every worker onto it."""
    avail = list(available) if available is not None else jax.devices()
    return [avail[i % len(avail)] for i in range(num_workers)]
