"""Functional radiance cache: exactness, LRU, and hypothesis properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import radiance_cache as rc

CFG = rc.CacheConfig(n_sets=16, n_ways=2, k=3)


def _ids(*rows):
    return jnp.asarray(rows, jnp.int32)


def _rgb(n, base=0.1):
    return jnp.asarray([[base + i, base + i, base + i] for i in range(n)],
                       jnp.float32)


def test_insert_then_lookup_hits():
    cache = rc.init_cache(1, CFG)
    ids = _ids([1, 2, 3], [4, 5, 6])
    rgb = _rgb(2)
    cache = rc.insert(cache, 0, ids, rgb, jnp.asarray([True, True]), CFG)
    hit, val, _, _, _ = rc.lookup(cache, 0, ids, CFG)
    assert bool(hit.all())
    np.testing.assert_allclose(np.asarray(val), np.asarray(rgb))


def test_miss_on_unknown_tag():
    cache = rc.init_cache(1, CFG)
    cache = rc.insert(cache, 0, _ids([1, 2, 3]), _rgb(1),
                      jnp.asarray([True]), CFG)
    hit, _, _, _, _ = rc.lookup(cache, 0, _ids([1, 2, 4]), CFG)
    assert not bool(hit.any())


def test_padding_id_is_not_invalid_tag():
    """-1 is legal record padding; must be storable and matchable."""
    cache = rc.init_cache(1, CFG)
    ids = _ids([7, -1, -1])
    cache = rc.insert(cache, 0, ids, _rgb(1), jnp.asarray([True]), CFG)
    hit, _, _, _, _ = rc.lookup(cache, 0, ids, CFG)
    assert bool(hit.all())


def test_lru_eviction_prefers_oldest():
    cfg = rc.CacheConfig(n_sets=1, n_ways=2, k=2)   # one set, two ways
    cache = rc.init_cache(1, cfg)
    a, b, c = _ids([1, 1]), _ids([2, 2]), _ids([3, 3])
    one = jnp.asarray([True])
    cache = rc.insert(cache, 0, a, _rgb(1, 0.1), one, cfg)
    cache = rc.insert(cache, 0, b, _rgb(1, 0.2), one, cfg)
    # touch a -> b becomes LRU
    _, _, _, _, cache = rc.lookup(cache, 0, a, cfg)
    cache = rc.insert(cache, 0, c, _rgb(1, 0.3), one, cfg)
    hit_a, _, _, _, _ = rc.lookup(cache, 0, a, cfg)
    hit_b, _, _, _, _ = rc.lookup(cache, 0, b, cfg)
    hit_c, _, _, _, _ = rc.lookup(cache, 0, c, cfg)
    assert bool(hit_a.all()) and bool(hit_c.all()) and not bool(hit_b.any())


def test_insert_conflict_lowest_pixel_wins():
    cfg = rc.CacheConfig(n_sets=1, n_ways=1, k=2, insert_rounds=1)
    cache = rc.init_cache(1, cfg)
    ids = _ids([5, 5], [6, 6])     # same set (only one), same victim way
    cache = rc.insert(cache, 0, ids, _rgb(2), jnp.asarray([True, True]), cfg)
    hit, val, _, _, _ = rc.lookup(cache, 0, ids, cfg)
    assert bool(hit[0]) and not bool(hit[1])


def test_duplicate_tags_single_entry():
    cache = rc.init_cache(1, CFG)
    ids = _ids([9, 9, 9], [9, 9, 9])
    cache = rc.insert(cache, 0, ids, _rgb(2), jnp.asarray([True, True]), CFG)
    tags = np.asarray(cache.tags[0])
    n_present = (np.all(tags == np.asarray([9, 9, 9]), axis=-1)).sum()
    assert n_present == 1


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 500), st.integers(0, 500),
                          st.integers(0, 500)), min_size=1, max_size=16,
                unique=True))
def test_property_inserted_retrievable(tag_rows):
    """Any batch of unique tags inserted into an empty, large-enough cache
    is fully retrievable with its own values."""
    cfg = rc.CacheConfig(n_sets=64, n_ways=4, k=3, insert_rounds=8)
    cache = rc.init_cache(1, cfg)
    ids = jnp.asarray(tag_rows, jnp.int32)
    n = ids.shape[0]
    rgb = jnp.arange(n * 3, dtype=jnp.float32).reshape(n, 3)
    cache = rc.insert(cache, 0, ids, rgb, jnp.ones((n,), bool), cfg)
    hit, val, _, _, _ = rc.lookup(cache, 0, ids, cfg)
    # every tag either hits with ITS value, or lost a (rare) way conflict —
    # with 64 sets x 4 ways >= 256 slots and <=16 inserts, conflicts need
    # >4 of 16 tags in one set: possible but then values must still match
    hits = np.asarray(hit)
    vals = np.asarray(val)
    assert hits.mean() >= 0.75
    np.testing.assert_allclose(vals[hits], np.asarray(rgb)[hits])


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 2), st.integers(1, 8))
def test_property_set_index_in_range(seed, k):
    cfg = rc.CacheConfig(n_sets=32, n_ways=2, k=k)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (20, k), -1, 10000)
    idx = np.asarray(rc.set_index(ids.astype(jnp.int32), cfg))
    assert ((idx >= 0) & (idx < 32)).all()


def test_bitconcat_index_mode():
    cfg = rc.CacheConfig(n_sets=64, n_ways=2, k=3, index_mode='bitconcat')
    ids = jnp.asarray([[8, 16, 24], [8, 16, 25]], jnp.int32)
    idx = np.asarray(rc.set_index(ids, cfg))
    assert ((idx >= 0) & (idx < 64)).all()


# ---------------------------------------------------------------------------
# insert_compacted: the full-batch rounds on the pending records alone
# ---------------------------------------------------------------------------

_CCFG = rc.CacheConfig(n_sets=4, n_ways=2, k=3)   # few slots: many conflicts
_N = 8192                                          # records, every (C, V)
_full_insert = jax.jit(lambda cc, ii, rr, dd: jax.vmap(
    lambda *a: rc.insert_all_groups_multi(*a, _CCFG))(cc, ii, rr, dd))
_compacted_insert = jax.jit(
    lambda *a: rc.insert_compacted(*a, _CCFG))


def _warm_caches(c, g, seed):
    """Caches that hold entries, touched ages and a running clock."""
    caches = jax.vmap(lambda _: rc.init_cache(g, _CCFG))(jnp.arange(c))
    key = jax.random.PRNGKey(seed)
    for i in range(2):
        ids = jax.random.randint(jax.random.fold_in(key, i),
                                 (c, 1, g, 64, 3), 0, 8)
        rgb = jax.random.uniform(jax.random.fold_in(key, 10 + i),
                                 (c, 1, g, 64, 3))
        caches = _full_insert(caches, ids, rgb, jnp.ones(ids.shape[:4], bool))
    _, _, _, _, caches = jax.vmap(
        lambda cc, ii: rc.lookup_all_groups_multi(cc, ii, _CCFG))(
            caches, ids[:, :, :, ::2])
    return caches


@pytest.mark.parametrize('edge', ['lo', 'hi'])
@pytest.mark.parametrize('branch', range(7))
@pytest.mark.parametrize('c,v', [(1, 1), (1, 2), (2, 1)])
def test_compacted_insert_matches_full_batch(c, v, branch, edge):
    """``insert_compacted`` evolves every cache exactly as the full-batch
    ``insert_all_groups_multi``: tags, values, LRU ages and clock bit for
    bit, whichever capacity branch the pending count lands in (each
    branch's lowest and highest count, and overflow into the full batch),
    over 4 sets x 2 ways so all four rounds resolve slot conflicts, with
    duplicate records across viewers, non-finite colours that must not
    insert and, at V == 2, a dead viewer."""
    g = 2
    b = _N // (c * v * g)
    caps = rc.insert_capacities(_N)
    assert len(caps) == 6
    bounds = [0, *caps, _N]
    count = bounds[branch] + 1 if edge == 'lo' else bounds[branch + 1]
    seed = 100 * c + 10 * v + branch
    key = jax.random.PRNGKey(seed)
    shape = (c, v, g, b)
    ids = jax.random.randint(jax.random.fold_in(key, 0), shape + (3,), 0, 12)
    if v == 2:   # viewer 1 repeats half of viewer 0's records
        ids = ids.at[:, 1, :, ::2].set(ids[:, 0, :, ::2])
    rgb = jax.random.uniform(jax.random.fold_in(key, 1), shape + (3,))
    order = np.asarray(jax.random.permutation(jax.random.fold_in(key, 2),
                                              _N))
    flat = np.zeros(_N, bool)
    flat[order[:count]] = True
    live = np.ones(shape, bool)
    if v == 2 and count < _N // 2:   # viewer 1 dead: its records never insert
        live[:, 1] = False
        flat = np.zeros(_N, bool)
        flat[np.flatnonzero(live.reshape(-1))[:count]] = True
    do = flat.reshape(shape)
    # non-finite colours on records that are not pending: asked to insert,
    # yet gated out by the finite check
    bad = np.flatnonzero(~flat & live.reshape(-1))[:7]
    rgb = rgb.reshape(_N, 3).at[bad, 1].set(
        jnp.asarray([np.nan, np.inf, -np.inf, np.nan, np.inf, np.nan,
                     -np.inf][:len(bad)], jnp.float32)).reshape(shape + (3,))
    do = jnp.asarray(do.reshape(-1).copy()).at[bad].set(True).reshape(shape)
    caches = _warm_caches(c, g, seed)

    want = _full_insert(caches, ids, rgb, do)
    got, share, ran = _compacted_insert(caches, ids, rgb, do)
    assert int(ran) == branch
    assert float(share) == count / _N
    for field in rc.CacheState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)
    # the batch did insert: a parity over an unchanged cache proves nothing
    assert not np.array_equal(np.asarray(want.tags), np.asarray(caches.tags))
