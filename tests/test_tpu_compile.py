"""Compile rehearsals: every Pallas kernel lowered natively (``interpret=False``,
``body='dense'``) for a described TPU v5e at the paper configuration's
widths — 1920x1080 is T = 120 x 68 = 8,160 tiles, capacity K = 1,024,
P = 256 pixels a tile; the slot-batched kernel at S = 4 slots; the cache
probe at G = 510 groups of 4,096 queries (4x4 tiles of 256 pixels).

Nothing runs: the TPU compiler refuses here what the chip would refuse
(block shapes off the (8, 128) tiling, ops Mosaic cannot lower, VMEM
overruns), at no chip time.  The topology is described inside a fixture
so that only the worker that runs these tests loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import radiance_cache as rc
from repro.kernels import rasterize as rk
from repro.kernels import rc_lookup as lk

T, K, K_RECORD, TILES_X = 8160, 1024, 5, 120
SLOTS = 4
GROUPS, QUERIES = 510, 4096


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


def _shapes(sharding, *lead):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    feats = (s((*lead, K, 2)), s((*lead, K, 3)), s((*lead, K, 3)),
             s((*lead, K)), s((*lead, K), jnp.int32))
    state = (s((*lead, rk.P, 3)), s((*lead, rk.P)),
             s((*lead, rk.P, K_RECORD), jnp.int32),
             s((*lead, rk.P), jnp.int32), s((*lead, rk.P), jnp.int32),
             s((*lead, rk.P), jnp.int32))
    return s, feats, state


def _rasterize(sharding):
    s, feats, state = _shapes(sharding, T)
    return (lambda *a: rk.rasterize_pallas(
        *a[:11], tiles_x=TILES_X, k_record=K_RECORD, chunk=64,
        stop_at_k=True, interpret=False, ncap=a[11], body='dense'),
        (*feats, *state, s((T,), jnp.int32)))


def _compact(sharding):
    s, feats, state = _shapes(sharding, T)
    lanes = (s((T, rk.P)), s((T, rk.P)), s((T, rk.P), jnp.int32),
             s((T, rk.P), jnp.int32))
    return (lambda *a: rk.rasterize_compact_pallas(
        *a, k_record=K_RECORD, chunk=64, interpret=False, body='dense'),
        (*feats, *lanes, *state))


def _slots(sharding):
    s, feats, state = _shapes(sharding, SLOTS, T)
    return (lambda *a: rk.rasterize_slots_pallas(
        *a[:11], tiles_x=TILES_X, k_record=K_RECORD, chunk=64,
        stop_at_k=True, interpret=False, ncap=a[11], body='dense'),
        (*feats, *state, s((SLOTS, T), jnp.int32)))


def _rc_lookup(sharding):
    cfg = rc.CacheConfig(k=K_RECORD)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (lambda tags, values, ids: lk.rc_lookup_pallas(
        tags, values, ids, cfg, query_chunk=512, interpret=False),
        (s((GROUPS, cfg.n_sets, cfg.n_ways, K_RECORD), jnp.int32),
         s((GROUPS, cfg.n_sets, cfg.n_ways, 3), jnp.float32),
         s((GROUPS, QUERIES, K_RECORD), jnp.int32)))


@pytest.mark.parametrize('build', [_rasterize, _compact, _slots, _rc_lookup],
                         ids=['rasterize', 'compact', 'slots', 'rc_lookup'])
def test_kernel_compiles_for_v5e_at_paper_widths(build, one_chip,
                                                 no_persistent_cache):
    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'tpu_custom_call' in compiled.as_text()
