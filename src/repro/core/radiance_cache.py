"""RC — Radiance Caching (paper Sec. 3.2) as a functional set-associative cache.

Cache key  : the ids of the first ``k`` *significant* Gaussians a pixel's ray
             intersects (the alpha-record emitted by the rasterizer).
Cache value: the pixel RGB.
Geometry   : ``n_sets`` sets x ``n_ways`` ways, one independent cache per
             tile *group* (the paper shares one LuminCache across a 4x4 block
             of 16x16 tiles = 64x64 pixels, double-buffered per group).

Indexing follows LuminCache (Fig. 16): ``log2(n_sets)/k`` low bits of each id
are concatenated to form the set index.  For the tag we store the exact ids
(int32) instead of the paper's 16-bit slices — strictly stronger matching
with zero aliasing; the hardware cost model still charges the 10-byte tag.

Replacement: LRU via an age counter (a faithful stand-in for the paper's
pseudo-LRU tree bits; both approximate LRU).  In-batch insert conflicts
(two pixels mapping to the same victim slot in the same frame) are resolved
deterministically: the lowest pixel index wins, mirroring the sequential
insert order of the hardware.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class CacheConfig(NamedTuple):
    n_sets: int = 1024
    n_ways: int = 4
    k: int = 5              # alpha-record length (ids per tag)
    index_bits_shift: int = 3   # paper uses bits [3:18]; index starts at bit 3
    index_mode: str = 'hash'    # 'hash' (mixed, default) | 'bitconcat' (paper HW)
    insert_rounds: int = 4      # batch-insert rounds (hardware inserts serially;
                                # each round lands at most one entry per slot)


class CacheState(NamedTuple):
    """Functional cache state; leading dim = tile group."""

    tags: jax.Array    # [G, S, W, k] int32 (-2 = invalid slot)
    values: jax.Array  # [G, S, W, 3] float32
    age: jax.Array     # [G, S, W] int32 (higher = more recently used)
    clock: jax.Array   # [G] int32 monotonic insert counter


INVALID_TAG = -2  # -1 is a legal record padding value, so invalid slots use -2


def init_cache(num_groups: int, cfg: CacheConfig) -> CacheState:
    g, s, w, k = num_groups, cfg.n_sets, cfg.n_ways, cfg.k
    return CacheState(
        tags=jnp.full((g, s, w, k), INVALID_TAG, jnp.int32),
        values=jnp.zeros((g, s, w, 3), jnp.float32),
        age=jnp.zeros((g, s, w), jnp.int32),
        clock=jnp.zeros((g,), jnp.int32),
    )


def occupancy(cache: CacheState) -> jax.Array:
    """Fraction of valid (non-invalid-tag) slots across all groups — a cheap
    telemetry signal for how warmed-up a viewer's cache is."""
    valid = jnp.any(cache.tags != INVALID_TAG, axis=-1)   # [G, S, W]
    return jnp.mean(valid.astype(jnp.float32))


def set_index(ids: jax.Array, cfg: CacheConfig) -> jax.Array:
    """Set index from the k record ids ([..., k] -> [...]).

    'bitconcat' concatenates ``log2(n_sets)/k`` low bits of each id — exactly
    LuminCache's indexing (Fig. 16).  It relies on ids being numerous enough
    to fill those bits; for the small procedural scenes used on CPU we default
    to 'hash', a multiplicative mix of the same ids (same hardware cost class:
    a few adders), which distributes small-id populations uniformly.
    """
    if cfg.index_mode == 'bitconcat':
        bits_total = cfg.n_sets.bit_length() - 1   # log2(n_sets)
        per_id = max(1, bits_total // cfg.k)
        mask = (1 << per_id) - 1
        shifted = (ids >> cfg.index_bits_shift) & mask      # [..., k]
        weights = (1 << (per_id * jnp.arange(cfg.k, dtype=jnp.int32)))
        idx = jnp.sum(shifted.astype(jnp.int32) * weights, axis=-1)
        return jnp.abs(idx) % cfg.n_sets
    # 'hash': odd-constant multiplicative mixing, xor-folded.  Must stay in
    # exact lockstep with repro.kernels.rc_lookup._mix_index.
    consts = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)
    h = (ids[..., 0] + 3).astype(jnp.uint32) * jnp.uint32(consts[0])
    for i in range(1, ids.shape[-1]):
        m = ((ids[..., i] + 3).astype(jnp.uint32)
             * jnp.uint32(consts[i % len(consts)]))
        h = (h ^ m) * jnp.uint32(0x9E3779B1)
    h = h ^ (h >> 15)
    return (h % jnp.uint32(cfg.n_sets)).astype(jnp.int32)


def _match(tags_at_set: jax.Array, ids: jax.Array) -> jax.Array:
    """tags_at_set [B, W, k] vs ids [B, k] -> [B, W] exact-match mask."""
    return jnp.all(tags_at_set == ids[:, None, :], axis=-1)


def lookup(cache: CacheState, group: int | jax.Array, ids: jax.Array,
           cfg: CacheConfig, live: jax.Array | None = None):
    """Query one group's cache with B records. Returns (hit [B], value [B,3],
    set_idx [B], way [B], cache-with-updated-LRU-age).

    ``live`` ([B] bool, optional) suppresses the LRU touch for dead records
    (idle serving lanes probing a *shared* cache must not age-bump entries);
    hit/value outputs are unaffected — callers mask them.  The clock still
    advances by the full batch so the age sequence is independent of which
    lanes happen to be live.
    """
    tags, values, age, clock = (cache.tags[group], cache.values[group],
                                cache.age[group], cache.clock[group])
    sidx = set_index(ids, cfg)                    # [B]
    cand = tags[sidx]                              # [B, W, k]
    m = _match(cand, ids)                          # [B, W]
    hit = jnp.any(m, axis=-1)
    way = jnp.argmax(m, axis=-1)
    val = values[sidx, way]
    # LRU touch for hits (deterministic: later pixels touch later).
    b = ids.shape[0]
    touched = hit if live is None else hit & live
    touch_age = clock + 1 + jnp.arange(b, dtype=jnp.int32)
    age = age.at[sidx, way].max(jnp.where(touched, touch_age, -1))
    new_clock = clock + b
    new_cache = CacheState(cache.tags,
                           cache.values,
                           cache.age.at[group].set(age),
                           cache.clock.at[group].set(new_clock))
    return hit, val, sidx, way, new_cache


def _insert_round(tags, values, age, sidx, ids, rgb, pending, pix, new_age):
    """One insert round: re-probe, then at most one new entry lands per
    (set, way) slot.  Returns the new tags, values, ages and pending mask.

    The re-probe drops pending records whose tag is already present
    (landed in an earlier round, or a duplicate of one that did).
    Winners-only scatter: losing lanes get out-of-range indices and are
    dropped (``mode='drop'``), so no stale value can clobber a winner.
    Victim way = first invalid way, else least-recently-used (min age).
    Conflicts on the same slot: lowest ``pix`` (the record's pixel in its
    group) wins, mirroring the hardware's sequential insert order; a
    winner's age becomes its ``new_age``.
    """
    s, w = age.shape
    slot_tags = tags[sidx]                                   # [B, W, k]
    pending = pending & ~jnp.any(_match(slot_tags, ids), axis=-1)
    invalid = jnp.all(slot_tags == INVALID_TAG, axis=-1)     # [B, W]
    slot_age = jnp.where(invalid, jnp.iinfo(jnp.int32).min, age[sidx])
    victim = jnp.argmin(slot_age, axis=-1)                   # [B]

    slot = sidx * w + victim                                 # [B]
    none = jnp.iinfo(jnp.int32).max
    winner = jnp.full((s * w,), none, jnp.int32).at[slot].min(
        jnp.where(pending, pix, none))
    wins = pending & (winner[slot] == pix)

    sidx_eff = jnp.where(wins, sidx, s)                      # out of range -> drop
    tags = tags.at[sidx_eff, victim].set(ids, mode='drop')
    values = values.at[sidx_eff, victim].set(rgb, mode='drop')
    age = age.at[sidx_eff, victim].set(new_age, mode='drop')
    return tags, values, age, pending


def touch_all_groups(cache: CacheState, ids: jax.Array, hit: jax.Array,
                     way: jax.Array, cfg: CacheConfig,
                     live: jax.Array | None = None) -> CacheState:
    """Apply the LRU side effect of a lookup (age bump for hits) without
    re-probing — used by the kernel fast path, whose Pallas lookup returns
    (hit, way) but leaves cache state untouched.  Matches ``lookup``'s age
    and clock evolution exactly so both paths stay bit-identical.
    ``live`` ([G, B] bool, optional) masks dead records out of the touch
    (see ``lookup``)."""
    def one(tags, values, age, clock, gids, ghit, gway, glive):
        b = gids.shape[0]
        sidx = set_index(gids, cfg)
        touch_age = clock + 1 + jnp.arange(b, dtype=jnp.int32)
        age = age.at[sidx, gway].max(jnp.where(ghit & glive, touch_age, -1))
        return age, clock + b

    if live is None:
        live = jnp.ones(hit.shape, bool)
    age, clock = jax.vmap(one)(cache.tags, cache.values, cache.age,
                               cache.clock, ids, hit, way, live)
    return CacheState(cache.tags, cache.values, age, clock)


def insert(cache: CacheState, group: int | jax.Array, ids: jax.Array,
           rgb: jax.Array, do_insert: jax.Array, cfg: CacheConfig) -> CacheState:
    """Insert B (ids -> rgb) entries into one group's cache where ``do_insert``.

    Hardware inserts pixels serially; a vectorized batch can land at most one
    entry per slot per scatter, so we run ``cfg.insert_rounds`` rounds.  Each
    round first re-probes the cache so duplicates of already-landed tags
    become hits and drop out of the insert set.
    """
    tags, values, age, clock = (cache.tags[group], cache.values[group],
                                cache.age[group], cache.clock[group])
    sidx = set_index(ids, cfg)                               # [B]
    b = ids.shape[0]
    pix = jnp.arange(b, dtype=jnp.int32)
    pending = do_insert
    for _ in range(max(1, cfg.insert_rounds)):
        tags, values, age, pending = _insert_round(
            tags, values, age, sidx, ids, rgb, pending, pix, clock + 1 + pix)
        clock = clock + b

    return CacheState(cache.tags.at[group].set(tags),
                      cache.values.at[group].set(values),
                      cache.age.at[group].set(age),
                      cache.clock.at[group].set(clock))


def lookup_all_groups(cache: CacheState, ids: jax.Array, cfg: CacheConfig,
                      live: jax.Array | None = None):
    """vmapped lookup over all groups. ids: [G, B, k]; live: [G, B] bool
    (optional, masks dead records out of the LRU touch)."""
    def one(tags, values, age, clock, gids, glive):
        sub = CacheState(tags[None], values[None], age[None], clock[None])
        hit, val, sidx, way, new = lookup(sub, 0, gids, cfg, live=glive)
        return hit, val, sidx, way, (new.tags[0], new.values[0], new.age[0], new.clock[0])
    if live is None:
        live = jnp.ones(ids.shape[:-1], bool)
    hit, val, sidx, way, (t, v, a, c) = jax.vmap(one)(
        cache.tags, cache.values, cache.age, cache.clock, ids, live)
    return hit, val, sidx, way, CacheState(t, v, a, c)


def insert_all_groups(cache: CacheState, ids: jax.Array, rgb: jax.Array,
                      do_insert: jax.Array, cfg: CacheConfig) -> CacheState:
    """vmapped insert over all groups. ids: [G, B, k], rgb: [G, B, 3].

    Non-finite values are never inserted: a NaN/Inf escaping the rasterizer
    (device corruption, fault injection) must not be published to a cache
    other viewers of the scene read back.  The gate is bit-neutral on
    finite data — the mask is unchanged — so golden traces are untouched.
    """
    do_insert = do_insert & jnp.isfinite(rgb).all(axis=-1)
    def one(tags, values, age, clock, gids, grgb, gdo):
        sub = CacheState(tags[None], values[None], age[None], clock[None])
        new = insert(sub, 0, gids, grgb, gdo, cfg)
        return new.tags[0], new.values[0], new.age[0], new.clock[0]
    t, v, a, c = jax.vmap(one)(cache.tags, cache.values, cache.age, cache.clock,
                               ids, rgb, do_insert)
    return CacheState(t, v, a, c)


# ---------------------------------------------------------------------------
# Multi-viewer (scene-shared) forms
# ---------------------------------------------------------------------------
# One cache serves every viewer of a scene.  The batched forms flatten the
# viewer axis *slot-major* into the record batch, so the whole fleet's probes
# and inserts evolve the cache exactly as if one sequential stream had issued
# them in (slot, pixel) order: cross-viewer insert conflicts resolve by that
# order (lowest slot, then lowest pixel, wins — the multi-viewer extension of
# the hardware's sequential insert), duplicate records across viewers dedupe
# through the insert rounds' re-probe, and the result depends only on the
# slot -> records mapping, never on host-side iteration order.  With V == 1
# the flatten is the identity, so the shared path is bit-identical to the
# per-viewer functions — the parity anchor for single-viewer serving.

def slot_major(x: jax.Array) -> jax.Array:
    """[V, G, B, ...] per-viewer grouped records -> [G, V*B, ...] one
    slot-major batch per group (viewer 0's pixels first)."""
    v, g, b = x.shape[:3]
    return jnp.moveaxis(x, 0, 1).reshape(g, v * b, *x.shape[3:])


def slot_split(x: jax.Array, v: int) -> jax.Array:
    """Inverse of ``slot_major``: [G, V*B, ...] -> [V, G, B, ...]."""
    g, vb = x.shape[:2]
    return jnp.moveaxis(x.reshape(g, v, vb // v, *x.shape[2:]), 0, 1)


def lookup_all_groups_multi(cache: CacheState, ids: jax.Array,
                            cfg: CacheConfig,
                            live: jax.Array | None = None):
    """Shared-cache lookup for V viewers: ids [V, G, B, k], live [V] bool.

    Returns (hit [V, G, B], val [V, G, B, 3], sidx, way, new cache).  LRU
    touches land in (slot, pixel) order; dead viewers (``live`` False) probe
    without touching."""
    v = ids.shape[0]
    live_f = None
    if live is not None:
        live_f = slot_major(jnp.broadcast_to(live[:, None, None],
                                             ids.shape[:3]))
    hit, val, sidx, way, cache = lookup_all_groups(cache, slot_major(ids),
                                                   cfg, live=live_f)
    return (slot_split(hit, v), slot_split(val, v), slot_split(sidx, v),
            slot_split(way, v), cache)


def insert_all_groups_multi(cache: CacheState, ids: jax.Array,
                            rgb: jax.Array, do_insert: jax.Array,
                            cfg: CacheConfig) -> CacheState:
    """Shared-cache insert for V viewers: ids [V, G, B, k], rgb [V, G, B, 3],
    do_insert [V, G, B].  Conflicts resolve deterministically by
    (slot, pixel) order; duplicate tags across viewers land once."""
    return insert_all_groups(cache, slot_major(ids), slot_major(rgb),
                             slot_major(do_insert), cfg)


# ---------------------------------------------------------------------------
# Compacted insert over every scene's cache at once
# ---------------------------------------------------------------------------
# A warm cache hits on most records, and only misses insert; yet the rounds
# of ``insert`` gather, re-probe and scatter every record of the batch (the
# losers ride along with dropped indices), so a round costs what the whole
# batch costs.  ``insert_compacted`` gathers the pending records into a
# short list first and runs the same rounds on it alone, with a
# ``lax.switch`` over a few static list capacities: the smallest that holds
# the pending count runs, and a count above them all takes the full-batch
# ``insert_all_groups_multi``.  The cache evolves exactly as under
# ``insert_all_groups_multi`` mapped over the caches, bit for bit.

def insert_capacities(n: int) -> tuple[int, ...]:
    """The record capacities of ``insert_compacted``'s compacted branches
    for a batch of ``n`` records: 3/4 of it halved five times (3/128 to
    3/4), each rounded up to a multiple of 128; those not below ``n`` are
    left out.  The list pads at most 2x past the pending count it holds;
    past 3/4 the full-batch branch runs, where a compacted list would save
    little.  Branch ``len(insert_capacities(n))`` is the full batch."""
    caps = {-(-3 * n // (512 << i)) * 128 for i in range(6)}
    return tuple(sorted(cap for cap in caps if cap < n))


def _rounds_compacted(caches: CacheState, ids: jax.Array, rgb: jax.Array,
                      pending: jax.Array, cap: int,
                      cfg: CacheConfig) -> CacheState:
    """``insert``'s rounds over the first ``cap`` pending records of every
    cache and group (``pending`` holds at most ``cap``), on views of the
    caches with one axis of sets: set ``(c*G + g)*S + s``."""
    c, v, g, b = pending.shape
    n, vb = pending.size, v * b
    s, w, k = cfg.n_sets, cfg.n_ways, cfg.k
    # the pending records' indices in order, padded with n: a sort of one
    # key per record (no scatter over the batch)
    key = jnp.where(pending.reshape(n), jnp.arange(n, dtype=jnp.int32), n)
    idx = jax.lax.sort(key)[:cap]
    pend = idx < n
    idx = jnp.minimum(idx, n - 1)
    cg = idx // (v * g * b) * g + idx // b % g    # the record's (cache, group)
    pix = idx // (g * b) % v * b + idx % b        # its slot-major pixel there
    rid = ids.reshape(n, k)[idx]
    rrgb = rgb.reshape(n, 3)[idx]
    gset = cg * s + set_index(rid, cfg)
    clock = caches.clock.reshape(c * g)[cg]

    tags = caches.tags.reshape(c * g * s, w, k)
    values = caches.values.reshape(c * g * s, w, 3)
    age = caches.age.reshape(c * g * s, w)
    rounds = max(1, cfg.insert_rounds)
    for r in range(rounds):
        tags, values, age, pend = _insert_round(
            tags, values, age, gset, rid, rrgb, pend, pix,
            clock + r * vb + 1 + pix)
    return CacheState(tags.reshape(caches.tags.shape),
                      values.reshape(caches.values.shape),
                      age.reshape(caches.age.shape),
                      caches.clock + rounds * vb)


def insert_compacted(caches: CacheState, ids: jax.Array, rgb: jax.Array,
                     do_insert: jax.Array, cfg: CacheConfig):
    """``insert_all_groups_multi`` mapped over C caches, on the pending
    records alone: caches leaves [C, G, ...], ids [C, V, G, B, k], rgb
    [C, V, G, B, 3], do_insert [C, V, G, B].

    Returns ``(new caches, pending share, branch)``: the share of records
    that were pending (``do_insert`` with finite rgb) and the index of the
    branch that ran, ``len(insert_capacities(C*V*G*B))`` for the full
    batch.  Call it outside any ``vmap``: under one the switch becomes a
    select that runs every branch."""
    pending = do_insert & jnp.isfinite(rgb).all(axis=-1)
    caps = insert_capacities(pending.size)
    count = jnp.sum(pending, dtype=jnp.int32)
    branch = jnp.sum(count > jnp.asarray(caps, jnp.int32), dtype=jnp.int32)

    def full(cc, ii, rr, pp):
        return jax.vmap(lambda *a: insert_all_groups_multi(*a, cfg))(
            cc, ii, rr, pp)

    branches = [lambda cc, ii, rr, pp, cap=cap: _rounds_compacted(
        cc, ii, rr, pp, cap, cfg) for cap in caps] + [full]
    new = jax.lax.switch(branch, branches, caches, ids, rgb, pending)
    return new, count / pending.size, branch
