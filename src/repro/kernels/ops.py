"""Jitted wrappers over the Pallas kernels — the fast path of LuminSys.

Modes (mirroring the LuminCore execution phases):
  * ``rasterize_full``     — baseline / S^2-only rasterization;
  * ``rasterize_prefix``   — RC phase A: integrate until each pixel's
                             alpha-record fills (or terminates);
  * ``rasterize_resume``   — RC phase B: cache-miss pixels continue from
                             their saved state;
  * ``rc_lookup``          — LuminCache probe (one-hot-matmul kernel);
  * ``rasterize_with_rc``  — the full cached-rasterization pipeline
                             (A -> lookup -> B -> insert), bit-identical in
                             output to the functional path in
                             ``repro.core.pipeline`` but with the compute
                             savings realized at chunk granularity.

``interpret=None`` resolves from the platform in one place
(``default_interpret``): the Pallas interpreter off-TPU, native Mosaic
kernels on TPU.  All four ``pallas_call``s compile for a TPU v5e at the
paper configuration's widths (``tests/test_tpu_compile.py``), and the
slot-batched serving path (phase A, lookup, compacted phase B) has run on
one v5e chip through ``chip_smoke.py``.

In a device trace each kernel's operation carries the ``name=`` of its
``pallas_call``: ``_kernel_slots`` (phase A, slot-batched),
``_kernel_compact`` (compacted phase B), ``rc_lookup`` (the probe) and
``_kernel_tiles`` (the per-tile kernel of the unbatched wrappers).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import radiance_cache as rc
from repro.core.groups import regroup, ungroup
from repro.core.rasterize import RasterAux, chunk_caps, pad_tile_features
from repro.core.tiling import TileFeatures
from repro.kernels import rasterize as rk
from repro.kernels import rc_lookup as lk
from repro.obs.trace import shade_stage


def default_interpret() -> bool:
    """The one place ``interpret`` is resolved: native kernels on TPU, the
    Pallas interpreter everywhere else."""
    return jax.default_backend() != 'tpu'


def default_body(interpret: bool) -> str:
    """Chunk-backend flavor: the scan 'dense' body is built for TPU vector
    units; interpret mode (CPU) pays its log(C) scan passes for real, so it
    gets the sequential FIFO body (which also skips render-pose-invisible
    Gaussians with a real branch, and which Mosaic cannot lower)."""
    return 'seq' if interpret else 'dense'


# Canonical implementations live beside the reference rasterizer (which
# shares the chunk accounting); re-exported here for the kernel wrappers.
pad_features = pad_tile_features


def trim_features(feats: TileFeatures, tiles_x: int,
                  t_img: int | None = None) -> TileFeatures:
    """Drop per-tile list entries that provably cannot be *significant*
    anywhere in their tile, and compact survivors to the front.

    Under S^2 a tile's shared list was built at the speculative sort pose
    with an inflated footprint; by the render pose — especially late in a
    sharing window, and for slots whose cohort sorted ticks ago — a sizable
    fraction of entries can no longer reach alpha > 1/255 inside the tile.
    They still cost chunk iterations (and, in the slot-batched kernel,
    couple every slot's trip count to the stalest list).  An entry is kept
    iff the level-set ellipse ``alpha == ALPHA_SIGNIFICANT`` (axis-aligned
    bbox of the conic quadratic at ``q = 2 ln(opacity/alpha_sig)``, inflated
    by a safety margin so float rounding can never flip a kept/dropped
    decision) overlaps its tile.  Only insignificant evaluations are
    dropped, so images, alpha-records, transmittance and every cache
    decision are bit-identical; the *examined* counter (``n_iterated``)
    honestly shrinks — this is the fast path measuring less work, not the
    oracle changing its answer.

    ``t_img``: tiles per image when the leading axis flattens slot x tile
    (the slot-batched path); defaults to "all tiles are one image".
    """
    t, k = feats.ids.shape
    timg = t if t_img is None else t_img
    a = feats.conic[..., 0]
    b = feats.conic[..., 1]
    c = feats.conic[..., 2]
    op = feats.opacity
    from repro.core.gaussians import ALPHA_SIGNIFICANT
    # alpha > sig  <=>  a dx^2 + 2b dx dy + c dy^2 < 2 ln(op / sig)
    q = 2.0 * jnp.log(jnp.maximum(op, 1e-12) / ALPHA_SIGNIFICANT)
    can_sig = q > 0.0
    det = jnp.maximum(a * c - b * b, 1e-12)
    q_safe = jnp.maximum(q, 0.0) * 1.02          # float-rounding headroom
    rx = jnp.sqrt(q_safe * c / det) + 0.5        # bbox half-extents + margin
    ry = jnp.sqrt(q_safe * a / det) + 0.5

    tix = jnp.arange(t, dtype=jnp.int32) % timg
    x0 = ((tix % tiles_x) * rk.TILE).astype(jnp.float32)[:, None]
    y0 = ((tix // tiles_x) * rk.TILE).astype(jnp.float32)[:, None]
    mx, my = feats.mean2d[..., 0], feats.mean2d[..., 1]
    overlap = ((mx + rx >= x0) & (mx - rx <= x0 + rk.TILE)
               & (my + ry >= y0) & (my - ry <= y0 + rk.TILE))
    keep = overlap & can_sig & (feats.ids >= 0)

    # stable partition: survivors first, depth order preserved
    perm = jnp.argsort(~keep, axis=1, stable=True)
    kept = jnp.take_along_axis(keep, perm, axis=1)

    def g(x):
        p = perm[..., None] if x.ndim == 3 else perm
        return jnp.take_along_axis(x, p, axis=1)

    return TileFeatures(
        mean2d=g(feats.mean2d), conic=g(feats.conic), color=g(feats.color),
        opacity=jnp.where(kept, g(feats.opacity), 0.0),
        ids=jnp.where(kept, g(feats.ids), -1))


def _baseline_state(t: int, k_record: int, live=None):
    p = rk.P
    if live is None:
        live_tp = jnp.ones((t, p), jnp.int32)
    else:
        live_tp = jnp.broadcast_to(jnp.asarray(live, bool),
                                   (t, p)).astype(jnp.int32)
    return (jnp.zeros((t, p, 3), jnp.float32),
            jnp.ones((t, p), jnp.float32),
            jnp.full((t, p, k_record), -1, jnp.int32),
            jnp.zeros((t, p), jnp.int32),
            jnp.zeros((t, p), jnp.int32),            # start_iter
            live_tp)                                 # live


def _to_aux(st: rk.RasterState) -> RasterAux:
    return RasterAux(alpha_record=st.record, n_significant=st.n_sig,
                     n_iterated=st.n_iter, iter_at_k=st.iter_at_k,
                     transmittance=st.trans)


def rasterize_full(feats: TileFeatures, tiles_x: int, *, k_record: int = 5,
                   chunk: int = 64, bg: float = 0.0, live=None,
                   interpret: bool | None = None):
    """Baseline rasterization. Returns (tile_colors [T,P,3], RasterAux, chunks [T,1]).

    ``live`` (anything broadcastable to [T, P] bool) masks dead pixels/lanes:
    they contribute nothing, count zero iterations, and whole-dead tiles skip
    their chunk loop entirely.
    """
    interpret = default_interpret() if interpret is None else interpret
    feats = pad_features(feats, chunk)
    t = feats.ids.shape[0]
    st = rk.rasterize_pallas(
        feats.mean2d, feats.conic, feats.color, feats.opacity, feats.ids,
        *_baseline_state(t, k_record, live), tiles_x=tiles_x,
        k_record=k_record, chunk=chunk, stop_at_k=False, interpret=interpret,
        ncap=chunk_caps(feats.ids, chunk), body=default_body(interpret))
    colors = st.acc + st.trans[..., None] * bg
    return colors, _to_aux(st), st.chunks


def rasterize_prefix(feats: TileFeatures, tiles_x: int, *, k_record: int = 5,
                     chunk: int = 64, live=None,
                     interpret: bool | None = None) -> rk.RasterState:
    """RC phase A. K must already be padded (call pad_features first)."""
    interpret = default_interpret() if interpret is None else interpret
    t = feats.ids.shape[0]
    return rk.rasterize_pallas(
        feats.mean2d, feats.conic, feats.color, feats.opacity, feats.ids,
        *_baseline_state(t, k_record, live), tiles_x=tiles_x,
        k_record=k_record, chunk=chunk, stop_at_k=True, interpret=interpret,
        ncap=chunk_caps(feats.ids, chunk), body=default_body(interpret))


def resume_live_mask(state_a: rk.RasterState, miss: jax.Array,
                     k_record: int) -> jax.Array:
    """Which pixels phase B must actually integrate: cache misses whose
    record filled in phase A (others already completed) and whose
    transmittance has not bottomed out."""
    from repro.core.gaussians import TRANSMITTANCE_EPS
    return (miss & (state_a.rec_cnt >= k_record)
            & (state_a.trans > TRANSMITTANCE_EPS))


def _combine_resume(state_a: rk.RasterState, st: rk.RasterState, bg: float):
    colors = st.acc + st.trans[..., None] * bg
    aux = RasterAux(alpha_record=st.record,
                    n_significant=state_a.n_sig + st.n_sig,
                    n_iterated=state_a.n_iter + st.n_iter,
                    iter_at_k=jnp.minimum(state_a.iter_at_k, st.iter_at_k),
                    transmittance=st.trans)
    return colors, aux, st.chunks


def rasterize_resume(feats: TileFeatures, tiles_x: int, state_a: rk.RasterState,
                     miss: jax.Array, *, k_record: int = 5, chunk: int = 64,
                     bg: float = 0.0, interpret: bool | None = None):
    """RC phase B: continue integration for miss pixels whose record filled.

    ``miss``: [T, P] bool.  Returns (tile_colors, RasterAux, chunks).
    Pixels that completed in phase A (record never filled) keep their phase-A
    color; hit pixels' colors are owned by the caller (cache values).
    """
    interpret = default_interpret() if interpret is None else interpret
    live = resume_live_mask(state_a, miss, k_record)
    st = rk.rasterize_pallas(
        feats.mean2d, feats.conic, feats.color, feats.opacity, feats.ids,
        state_a.acc, state_a.trans, state_a.record, state_a.rec_cnt,
        state_a.iter_at_k, live,
        tiles_x=tiles_x, k_record=k_record, chunk=chunk, stop_at_k=False,
        interpret=interpret, ncap=chunk_caps(feats.ids, chunk),
        body=default_body(interpret))
    return _combine_resume(state_a, st, bg)


def rasterize_resume_compacted(feats: TileFeatures, tiles_x: int,
                               state_a: rk.RasterState, miss: jax.Array,
                               *, k_record: int = 5, chunk: int = 64,
                               bg: float = 0.0,
                               interpret: bool | None = None,
                               t_img: int | None = None):
    """RC phase B with **miss compaction** — LuminCore's PE remap in software.

    ``rasterize_resume`` pays per *tile*: one scattered miss pixel forces its
    whole tile back through the chunk loop, so at a 95% hit rate phase B
    still costs nearly a full pass (the warp-divergence pathology, measured
    as negative ``chunk_savings_%`` before this stage existed).  Here the
    miss pixels of the whole frame are gathered — with their saved phase-A
    alpha-record state — into dense compacted tiles (stable sort keeps them
    source-tile-major for locality), only those tiles walk the chunk loop
    (all-hit compacted tiles exit at zero chunks), and the results scatter
    back to their home pixels.  Phase-B chunk count then scales with the
    miss *count*, not the tile count.

    Bit-compatible with ``rasterize_resume`` (same per-pixel op sequence;
    the accumulate is a reduce instead of an MXU dot, so colors agree to
    float32 ulp, integer state exactly).
    """
    interpret = default_interpret() if interpret is None else interpret
    t, p = state_a.trans.shape
    live = resume_live_mask(state_a, miss, k_record)

    # pack miss lanes first, source-tile-major (a stable partition: cheaper
    # than an argsort and order-preserving within each half)
    flat = live.reshape(-1)                                    # [T*P]
    n_live = jnp.sum(flat.astype(jnp.int32))
    rank_live = jnp.cumsum(flat.astype(jnp.int32)) - 1
    rank_dead = jnp.cumsum((~flat).astype(jnp.int32)) - 1 + n_live
    dest = jnp.where(flat, rank_live, rank_dead)               # [T*P]
    idx = jnp.arange(t * p, dtype=jnp.int32)
    perm = jnp.zeros((t * p,), jnp.int32).at[dest].set(idx)
    inv = dest

    # ``t_img`` = tiles per image: when the leading axis is a flattened
    # slot x tile product (cross-slot compaction in the batched serving
    # path), pixel coordinates repeat every t_img tiles
    timg = t if t_img is None else t_img
    tix = jnp.arange(t * p, dtype=jnp.int32) // p
    pix = jnp.arange(t * p, dtype=jnp.int32) % p
    tim = tix % timg
    px = ((tim % tiles_x) * rk.TILE + pix % rk.TILE + 0.5).astype(jnp.float32)
    py = ((tim // tiles_x) * rk.TILE + pix // rk.TILE + 0.5).astype(jnp.float32)
    ncap_t = chunk_caps(feats.ids, chunk)                      # [T]

    def gather(x):
        return x.reshape(t * p, *x.shape[2:])[perm].reshape(t, p, *x.shape[2:])

    st = rk.rasterize_compact_pallas(
        feats.mean2d, feats.conic, feats.color, feats.opacity, feats.ids,
        gather(px.reshape(t, p)), gather(py.reshape(t, p)),
        gather(tix.reshape(t, p)), gather(ncap_t[tix].reshape(t, p)),
        gather(state_a.acc), gather(state_a.trans), gather(state_a.record),
        gather(state_a.rec_cnt), gather(state_a.iter_at_k),
        gather(live),
        k_record=k_record, chunk=chunk, interpret=interpret,
        body=default_body(interpret))

    def scatter(x):
        return x.reshape(t * p, *x.shape[2:])[inv].reshape(t, p, *x.shape[2:])

    st = rk.RasterState(
        acc=scatter(st.acc), trans=scatter(st.trans), record=scatter(st.record),
        rec_cnt=scatter(st.rec_cnt), n_sig=scatter(st.n_sig),
        n_iter=scatter(st.n_iter), iter_at_k=scatter(st.iter_at_k),
        chunks=st.chunks)   # chunk counts belong to compacted tiles; sum is
                            # the phase-B cost either way
    return _combine_resume(state_a, st, bg)


def rc_lookup(cache: rc.CacheState, ids: jax.Array, cfg: rc.CacheConfig,
              *, query_chunk: int = 512, interpret: bool | None = None):
    """LuminCache probe for all groups. ids [G, B, k].

    On TPU this is the one-hot-matmul Pallas kernel (a gather re-expressed
    for the MXU, where vector gathers are weak).  In interpret mode the MXU
    trick is a pure pessimization — a [B, n_sets] one-hot GEMM a scalar core
    must actually execute — so the probe runs the bit-identical gather
    formulation (the kernel's oracle) instead.  Same outputs either way.
    """
    interpret = default_interpret() if interpret is None else interpret
    if interpret:
        from repro.kernels import ref
        return ref.rc_lookup_ref(cache.tags, cache.values, ids, cfg)
    # the largest lane-aligned divisor of B up to ``query_chunk`` (a query
    # block must be a multiple of 128 lanes or all of B)
    b = ids.shape[1]
    qc = next((q for q in range(min(query_chunk, b), 0, -1)
               if b % q == 0 and q % rk.LANES == 0), b)
    return lk.rc_lookup_pallas(cache.tags, cache.values, ids, cfg,
                               query_chunk=qc, interpret=interpret)


def rc_probe(cache: rc.CacheState, ids_g: jax.Array, cfg: rc.CacheConfig,
             *, interpret: bool | None = None):
    """Cache lookup + LRU touch for one viewer, implementation chosen by
    platform.  Returns (hit_g, val_g, way_g, cache-with-touch-applied).

    In interpret mode the gather-formulation probe applies the touch inline
    (one pass); the Pallas kernel leaves cache state untouched, so on TPU
    the touch runs as a separate step after it — identical evolution."""
    interp = default_interpret() if interpret is None else interpret
    if interp:
        hit_g, val_g, _, way_g, cache = rc.lookup_all_groups(cache, ids_g,
                                                             cfg)
        return hit_g, val_g, way_g, cache
    hit_g, val_g, _, way_g = rc_lookup(cache, ids_g, cfg, interpret=interp)
    cache = rc.touch_all_groups(cache, ids_g, hit_g, way_g, cfg)
    return hit_g, val_g, way_g, cache


def rc_probe_multi(cache: rc.CacheState, ids: jax.Array, cfg: rc.CacheConfig,
                   live: jax.Array | None = None,
                   *, interpret: bool | None = None):
    """Shared-cache probe for V viewers of one scene: ids [V, G, B, k],
    live [V] bool.  Returns (hit [V,G,B], val [V,G,B,3], way [V,G,B],
    cache-with-touch-applied).

    The viewer axis flattens slot-major into each group's record batch, so
    LRU evolution is the deterministic (slot, pixel) serial order and V == 1
    is bit-identical to ``rc_probe``.  Dead viewers probe without touching.
    On TPU the flattened batch goes through the one-hot-matmul Pallas lookup
    and the (masked) touch runs as a separate step — identical evolution.
    """
    interp = default_interpret() if interpret is None else interpret
    if interp:
        hit, val, _, way, cache = rc.lookup_all_groups_multi(cache, ids, cfg,
                                                             live=live)
        return hit, val, way, cache
    v = ids.shape[0]
    ids_f = rc.slot_major(ids)
    live_f = None
    if live is not None:
        live_f = rc.slot_major(jnp.broadcast_to(live[:, None, None],
                                                ids.shape[:3]))
    hit_f, val_f, _, way_f = rc_lookup(cache, ids_f, cfg, interpret=interp)
    cache = rc.touch_all_groups(cache, ids_f, hit_f, way_f, cfg, live=live_f)
    return (rc.slot_split(hit_f, v), rc.slot_split(val_f, v),
            rc.slot_split(way_f, v), cache)


class RCStats(NamedTuple):
    """Kernel-path statistics. True compute savings are chunk-granular:
    compare (chunks_prefix + chunks_resume) against ``chunks_bound`` (what a
    count-capped full pass over the same tiles would cost) — the benchmarks
    do exactly that."""

    hit_rate: jax.Array
    chunks_prefix: jax.Array   # chunk iterations, phase A (sum over tiles)
    chunks_resume: jax.Array   # chunk iterations, phase B
    chunks_bound: jax.Array    # count-capped full-pass chunk total (scalar)
    hit: jax.Array             # [T, P] bool per-pixel cache-hit mask
    # the compacted insert (``rc.insert_compacted``; slot-batched path
    # only): share of records pending insert, and the branch that ran
    insert_pending: jax.Array | None = None
    insert_branch: jax.Array | None = None


def rasterize_with_rc(feats: TileFeatures, tiles_x: int, tiles_y: int,
                      cache: rc.CacheState, cfg: rc.CacheConfig,
                      group_tiles: int, *, k_record: int = 5, chunk: int = 64,
                      bg: float = 0.0, live=None, compact: bool = True,
                      interpret: bool | None = None):
    """Cached rasterization, hardware-phase ordering (A -> lookup -> B -> insert).

    ``live`` (broadcastable to [T, P] bool) masks dead pixels/idle lanes out
    of both phases; ``compact=True`` routes phase B through the
    miss-compacted resume (``rasterize_resume_compacted``) so its chunk cost
    scales with the miss count instead of the tile count.

    Returns (final tile colors [T,P,3], new cache, RasterAux, RCStats).
    """
    feats = pad_features(feats, chunk)
    st_a = rasterize_prefix(feats, tiles_x, k_record=k_record, chunk=chunk,
                            live=live, interpret=interpret)
    ids_g = regroup(st_a.record, tiles_x, tiles_y, group_tiles)
    hit_g, val_g, way_g, cache = rc_probe(cache, ids_g, cfg,
                                          interpret=interpret)
    hit = ungroup(hit_g[..., None], tiles_x, tiles_y, group_tiles)[..., 0]
    cached = ungroup(val_g, tiles_x, tiles_y, group_tiles)

    miss = ~hit
    if live is not None:
        miss = miss & jnp.broadcast_to(jnp.asarray(live, bool), miss.shape)
    resume = rasterize_resume_compacted if compact else rasterize_resume
    colors, aux, chunks_b = resume(
        feats, tiles_x, st_a, miss, k_record=k_record, chunk=chunk, bg=bg,
        interpret=interpret)
    final = jnp.where(hit[..., None], cached, colors)

    # cache update: completed (miss) pixels insert their fresh values
    raw_g = regroup(colors, tiles_x, tiles_y, group_tiles)
    cache = rc.insert_all_groups(cache, ids_g, raw_g, ~hit_g, cfg)

    stats = RCStats(
        hit_rate=jnp.mean(hit.astype(jnp.float32)),
        chunks_prefix=jnp.sum(st_a.chunks),
        chunks_resume=jnp.sum(chunks_b),
        chunks_bound=jnp.sum(chunk_caps(feats.ids, chunk)),
        hit=hit,
    )
    return final, cache, aux, stats


# ---------------------------------------------------------------------------
# Slot-batched wrappers — the multi-viewer serving fast path
# ---------------------------------------------------------------------------
# A vmapped pallas_call batches by growing the grid: S x T programs that
# interpret mode executes serially, so batched serving gained no vector
# width.  These wrappers instead ride the slot axis inside each program's
# block (rk.rasterize_slots_pallas) and compact cache misses ACROSS slots,
# so one tick's shade is T fat programs plus one fleet-wide compacted
# resume.  Outputs are bit-identical per lane to the per-slot functions.

def pad_features_slots(feats_b: TileFeatures, chunk: int) -> TileFeatures:
    """``pad_features`` for [S, T, K, ...] feature stacks."""
    s, t = feats_b.ids.shape[:2]
    flat = TileFeatures(*[x.reshape((s * t,) + x.shape[2:]) for x in feats_b])
    flat = pad_features(flat, chunk)
    return TileFeatures(*[x.reshape((s, t) + x.shape[1:]) for x in flat])


def _slots_state(s: int, t: int, k_record: int, live) -> tuple:
    p = rk.P
    live_stp = jnp.broadcast_to(
        jnp.asarray(live, bool).reshape((-1,) + (1,) * 2), (s, t, p))
    return (jnp.zeros((s, t, p, 3), jnp.float32),
            jnp.ones((s, t, p), jnp.float32),
            jnp.full((s, t, p, k_record), -1, jnp.int32),
            jnp.zeros((s, t, p), jnp.int32),
            jnp.zeros((s, t, p), jnp.int32),
            live_stp.astype(jnp.int32))


def rasterize_prefix_slots(feats_b: TileFeatures, tiles_x: int, *,
                           k_record: int = 5, chunk: int = 64, live=None,
                           interpret: bool | None = None) -> rk.RasterState:
    """RC phase A for all serving slots in one slot-batched kernel call.
    ``feats_b`` leaves are [S, T, K, ...] and must be pre-padded
    (``pad_features_slots``); ``live`` is [S] bool (idle slots).  Returned
    state leaves are [S, T, P, ...]; ``chunks`` is the per-tile trip count
    [T, 1] (slot-coupled)."""
    interpret = default_interpret() if interpret is None else interpret
    s, t = feats_b.ids.shape[:2]
    if live is None:
        live = jnp.ones((s,), bool)
    ncap = chunk_caps(
        feats_b.ids.reshape(s * t, -1), chunk).reshape(s, t)
    return rk.rasterize_slots_pallas(
        feats_b.mean2d, feats_b.conic, feats_b.color, feats_b.opacity,
        feats_b.ids, *_slots_state(s, t, k_record, live),
        tiles_x=tiles_x, k_record=k_record, chunk=chunk, stop_at_k=True,
        interpret=interpret, ncap=ncap, body=default_body(interpret))


def rasterize_full_slots(feats_b: TileFeatures, tiles_x: int, *,
                         k_record: int = 5, chunk: int = 64,
                         bg: float = 0.0, live=None,
                         interpret: bool | None = None):
    """Slot-batched baseline rasterization (no RC).  Returns
    (colors [S,T,P,3], RasterAux with [S,T,P,...] leaves, chunks [T,1])."""
    interpret = default_interpret() if interpret is None else interpret
    feats_b = pad_features_slots(feats_b, chunk)
    s, t = feats_b.ids.shape[:2]
    if live is None:
        live = jnp.ones((s,), bool)
    ncap = chunk_caps(
        feats_b.ids.reshape(s * t, -1), chunk).reshape(s, t)
    st = rk.rasterize_slots_pallas(
        feats_b.mean2d, feats_b.conic, feats_b.color, feats_b.opacity,
        feats_b.ids, *_slots_state(s, t, k_record, live),
        tiles_x=tiles_x, k_record=k_record, chunk=chunk, stop_at_k=False,
        interpret=interpret, ncap=ncap, body=default_body(interpret))
    colors = st.acc + st.trans[..., None] * bg
    return colors, _to_aux(st), st.chunks


def rasterize_resume_compacted_slots(feats_b: TileFeatures, tiles_x: int,
                                     st_a: rk.RasterState, miss: jax.Array,
                                     *, t_img: int, k_record: int = 5,
                                     chunk: int = 64, bg: float = 0.0,
                                     interpret: bool | None = None):
    """Cross-slot miss-compacted phase B: the whole fleet's miss pixels
    pack into one run of compacted tiles (fewer live programs than
    per-slot compaction by up to S x).  ``feats_b``/``st_a``/``miss`` carry
    [S, T, ...] leaves; ``t_img`` = tiles per image (= T)."""
    s, t = feats_b.ids.shape[:2]

    def flat(x):
        return x.reshape((s * t,) + x.shape[2:])

    feats_f = TileFeatures(*[flat(x) for x in feats_b])
    st_f = rk.RasterState(acc=flat(st_a.acc), trans=flat(st_a.trans),
                          record=flat(st_a.record), rec_cnt=flat(st_a.rec_cnt),
                          n_sig=flat(st_a.n_sig), n_iter=flat(st_a.n_iter),
                          iter_at_k=flat(st_a.iter_at_k), chunks=st_a.chunks)
    colors, aux, chunks_b = rasterize_resume_compacted(
        feats_f, tiles_x, st_f, flat(miss), k_record=k_record, chunk=chunk,
        bg=bg, interpret=interpret, t_img=t_img)

    def unflat(x):
        return x.reshape((s, t) + x.shape[1:])

    aux = RasterAux(*[unflat(x) for x in aux])
    return unflat(colors), aux, chunks_b


def rasterize_with_rc_slots(feats_b: TileFeatures, tiles_x: int,
                            tiles_y: int, caches: rc.CacheState,
                            cfg: rc.CacheConfig, group_tiles: int, *,
                            viewers_per_scene: int = 1,
                            k_record: int = 5, chunk: int = 64,
                            bg: float = 0.0, live=None,
                            compact: bool = True,
                            interpret: bool | None = None):
    """Slot-batched cached rasterization: phase A in one slot-batched
    kernel, scene-major shared-cache probe, cross-slot miss-compacted
    resume, and an insert of the pending records alone over every scene's
    cache at once (``rc.insert_compacted``), each inside its shade stage's
    named scope (``repro.obs.trace.shade_stage``: raster, rc_probe,
    rc_insert).
    ``caches`` leaves carry a leading [C] axis with ``C = S //
    viewers_per_scene`` (slot ``i`` probes scene ``i // V``'s cache; slots
    of one scene share it, conflicts resolving in deterministic (slot,
    pixel) order — see ``rc_probe_multi``); ``live`` is [S] bool and masks
    idle slots out of LRU touches and inserts as well as the chunk loops.  With ``viewers_per_scene == 1`` every slot owns a private cache
    and per-lane results are bit-identical to mapping ``rasterize_with_rc``
    over slots; only the *chunk accounting* differs (phase-A trips are
    slot-coupled, so ``chunks_prefix``/``chunks_bound`` are fleet totals and
    ``hit_rate`` is per-slot [S]).
    """
    with shade_stage('raster'):
        feats_b = pad_features_slots(feats_b, chunk)
        s, t = feats_b.ids.shape[:2]
        v = viewers_per_scene
        c = s // v
        if live is None:
            live = jnp.ones((s,), bool)
        live = jnp.asarray(live, bool).reshape(s)

        st_a = rasterize_prefix_slots(feats_b, tiles_x, k_record=k_record,
                                      chunk=chunk, live=live,
                                      interpret=interpret)

    with shade_stage('rc_probe'):
        ids_g = jax.vmap(
            lambda r: regroup(r, tiles_x, tiles_y, group_tiles))(st_a.record)
        ids_cv = ids_g.reshape(c, v, *ids_g.shape[1:])   # [C, V, G, B, k]
        live_cv = live.reshape(c, v)
        hit_cv, val_cv, way_cv, caches = jax.vmap(
            lambda cc, ii, lv: rc_probe_multi(cc, ii, cfg, live=lv,
                                              interpret=interpret)
        )(caches, ids_cv, live_cv)
        hit_g = hit_cv.reshape(s, *hit_cv.shape[2:])     # [S, G, B]
        val_g = val_cv.reshape(s, *val_cv.shape[2:])
        hit = jax.vmap(
            lambda h: ungroup(h[..., None], tiles_x, tiles_y,
                              group_tiles)[..., 0])(hit_g)
        cached = jax.vmap(
            lambda vv: ungroup(vv, tiles_x, tiles_y, group_tiles))(val_g)

    with shade_stage('raster'):
        miss = ~hit & live[:, None, None]
        if compact:
            colors, aux, chunks_b = rasterize_resume_compacted_slots(
                feats_b, tiles_x, st_a, miss, t_img=t, k_record=k_record,
                chunk=chunk, bg=bg, interpret=interpret)
        else:
            colors, aux, chunks_b = jax.vmap(
                lambda f, st, m: rasterize_resume(
                    TileFeatures(*f), tiles_x,
                    rk.RasterState(*st, chunks=jnp.zeros((t, 1), jnp.int32)),
                    m, k_record=k_record, chunk=chunk, bg=bg,
                    interpret=interpret)
            )(tuple(feats_b),
              (st_a.acc, st_a.trans, st_a.record, st_a.rec_cnt, st_a.n_sig,
               st_a.n_iter, st_a.iter_at_k), miss)
        final = jnp.where(hit[..., None], cached, colors)

    with shade_stage('rc_insert'):
        raw_g = jax.vmap(
            lambda cl: regroup(cl, tiles_x, tiles_y, group_tiles))(colors)
        raw_cv = raw_g.reshape(c, v, *raw_g.shape[1:])
        # the rounds run on the pending records alone (most records hit)
        caches, insert_pending, insert_branch = rc.insert_compacted(
            caches, ids_cv, raw_cv, ~hit_cv & live_cv[:, :, None, None], cfg)

    with shade_stage('raster'):
        ncap = chunk_caps(feats_b.ids.reshape(s * t, -1), chunk)
        stats = RCStats(
            hit_rate=jnp.mean(hit.astype(jnp.float32), axis=(1, 2)),   # [S]
            # one slot-coupled trip covers all S slots' lanes of its tile,
            # so scale by S to keep the RCStats contract (chunks_prefix +
            # chunks_resume comparable to chunks_bound, both in
            # per-slot-tile chunk units)
            chunks_prefix=jnp.sum(st_a.chunks) * s,   # fleet
            chunks_resume=jnp.sum(chunks_b),          # fleet (cross-slot)
            chunks_bound=jnp.sum(ncap),               # fleet
            hit=hit,                                  # [S, T, P]
            insert_pending=insert_pending,
            insert_branch=insert_branch,
        )
    return final, caches, aux, stats
