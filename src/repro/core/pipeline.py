"""LuminSys — the full frame pipeline (paper Sec. 3.3).

Combines the three stages with both optimizations:

  pose history --> predict pose --> [Projection + Sorting] at predicted pose
       (speculative, once per sharing window, expanded viewport)
  every frame  --> sorting-shared prep (refresh geometry + SH colors)
               --> Rasterization with alpha-record extraction
               --> Radiance-Cache lookup: hits take the cached RGB and
                   terminate early; misses complete integration and insert.

Everything is expressed as pure functions over fixed shapes.  State is split
along the sharing axis of a serving fleet:

  * ``SceneShared``  — what every viewer of one *scene* shares: ONE radiance
    cache, plus a pose-cell-keyed pool of ``SortShared`` entries (refcounted
    by the viewers consuming them);
  * ``ViewerPrivate`` — what stays per-viewer: previous pose, frame counter,
    current pose-cell id, pool index;
  * ``ViewerState``  — the single-viewer composition (one scene, one viewer,
    a pool of one): exactly the pre-split state model, carried by
    ``render_step``/``LuminSys``.

The frame is split into two phases over that state:

  * ``sort_phase``  — pose prediction + speculative Projection/Sorting,
    writing a ``SortShared`` pool entry (runs once per sharing window);
  * ``shade_phase`` — sorting-shared prep + rasterization + radiance cache,
    consuming the viewer's pool entry and returning the updated
    ``SceneShared`` functionally (runs every frame, sort-free).

``render_step`` composes the two with a ``lax.cond`` on
``frame_idx % window`` — the single-viewer contract is unchanged and it still
jits/vmaps as one step.  The multi-viewer serving path
(``repro.serve.stepper``) instead schedules the phases itself: a pose-cell
sort scheduler elects one sorter per due (scene, cell) group each tick and
advances all slots through ``batched_shade_phase``, whose cache stages run
scene-major so viewers of one scene probe and fill one shared cache in
deterministic (slot, pixel) order.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import radiance_cache as rc
from repro.core.camera import Camera
from repro.core.gaussians import GaussianScene
from repro.core.projection import project
from repro.core.rasterize import RasterAux, assemble_image, rasterize_tiles
from repro.core.s2 import (SortShared, empty_sort_shared,
                           predict_window_pose, shared_features,
                           speculative_sort)
from repro.core.sorting import sort_scene
from repro.core.tiling import TILE, gather_tile_features, tile_grid
from repro.obs.trace import shade_stage


@dataclasses.dataclass(frozen=True)
class LuminaConfig:
    """Algorithm configuration (paper defaults: window=6, margin=4, k=5).

    ``backend`` selects the shade implementation: ``'reference'`` is the
    pure-JAX rasterizer + functional cache (the oracle), ``'pallas'`` routes
    shading through the chunked Pallas kernels (``repro.kernels.ops``) —
    phase A/lookup/resume/insert with the ``live`` mask reaching the kernel
    so idle serving lanes skip chunk iterations, and (with ``rc_compact``)
    the miss-compacted phase-B resume.  The switch threads everywhere the
    config does: ``LuminSys``, both serve steppers, and the serve CLI's
    ``--backend`` flag.
    """

    window: int = 6            # sharing window N (frames per sort)
    margin: int = 4            # expanded-viewport margin, pixels per side
    capacity: int = 256        # per-tile Gaussian budget
    k_record: int = 5          # alpha-record length
    group_tiles: int = 4       # cache shared across group_tiles^2 tiles (4x4 in paper)
    cache: rc.CacheConfig = rc.CacheConfig()
    sort_method: str = 'dense'
    max_tiles_per_gaussian: int = 16
    bg: float = 0.0
    use_s2: bool = True
    use_rc: bool = True
    backend: str = 'reference'  # 'reference' | 'pallas'
    shade_chunk: int = 64       # pallas backend: Gaussians per chunk iteration
    rc_compact: bool = True     # pallas backend: miss-compacted phase B

    def __post_init__(self):
        if self.backend not in ('reference', 'pallas'):
            raise ValueError(f'unknown shade backend: {self.backend!r}')
        object.__setattr__(self, 'cache',
                           self.cache._replace(k=self.k_record))


def platform_backend() -> str:
    """The shade backend for this platform: the Pallas kernels where they
    compile natively (TPU), the pure-JAX reference elsewhere."""
    return 'pallas' if jax.default_backend() == 'tpu' else 'reference'


class FrameStats(NamedTuple):
    hit_rate: jax.Array          # fraction of pixels served from the cache
    sig_frac: jax.Array          # significant / iterated Gaussians
    mean_iterated: jax.Array     # average Gaussians iterated per pixel
    saved_frac: jax.Array        # fraction of integration skipped thanks to RC
    sorted_this_frame: jax.Array # 1.0 if Projection+Sorting ran
    # the shade run's compacted insert (slot-batched kernel path only,
    # else None): share of records pending insert, and the branch of
    # ``rc.insert_compacted`` that ran
    insert_pending: jax.Array | None = None
    insert_branch: jax.Array | None = None


# Pixel <-> cache-group reshaping lives in repro.core.groups (shared with the
# kernel fast path); re-exported here for convenience.
from repro.core.groups import group_dims, num_groups, regroup, ungroup  # noqa: E402


# ---------------------------------------------------------------------------
# Stages
# ---------------------------------------------------------------------------

def render_frame_baseline(scene: GaussianScene, cam: Camera, cfg: LuminaConfig,
                          live=None, early_exit: bool = True):
    """Full 3DGS pipeline (Projection -> Sorting -> Rasterization), no reuse.

    ``early_exit=False`` selects the dense-scan rasterizer formulation —
    required by gradient consumers (the fine-tuning loss): the chunked
    early-exit ``while_loop`` is not reverse-mode differentiable.
    """
    proj = project(scene, cam)
    lists = sort_scene(proj, cam.width, cam.height, cfg.capacity,
                       method=cfg.sort_method,
                       max_tiles_per_gaussian=cfg.max_tiles_per_gaussian)
    feats = gather_tile_features(proj, lists)
    colors, aux = rasterize_tiles(feats, lists.tiles_x, k_record=cfg.k_record,
                                  bg=cfg.bg, live=live,
                                  early_exit=early_exit)
    image = assemble_image(colors, lists.tiles_x, lists.tiles_y,
                           cam.width, cam.height)
    return image, colors, aux, lists


def rc_apply(cache: rc.CacheState, tile_colors: jax.Array, aux: RasterAux,
             tiles_x: int, tiles_y: int, cfg: LuminaConfig):
    """Radiance-cache lookup + update for one frame's tile colors.

    Returns (final tile colors, new cache, hit mask [T,P], saved-iteration
    fraction scalar).
    """
    ids_g = regroup(aux.alpha_record, tiles_x, tiles_y, cfg.group_tiles)
    raw_g = regroup(tile_colors, tiles_x, tiles_y, cfg.group_tiles)
    hit, val, _, _, cache = rc.lookup_all_groups(cache, ids_g, cfg.cache)
    final_g = jnp.where(hit[..., None], val, raw_g)
    cache = rc.insert_all_groups(cache, ids_g, raw_g, ~hit, cfg.cache)

    hit_t = ungroup(hit[..., None], tiles_x, tiles_y, cfg.group_tiles)[..., 0]
    final = ungroup(final_g, tiles_x, tiles_y, cfg.group_tiles)
    # A hit pixel stops after identifying its k significant Gaussians; pixels
    # whose record never filled (iter_at_k >= n_iterated) save nothing.
    saved = jnp.where(hit_t, jnp.maximum(aux.n_iterated - aux.iter_at_k, 0), 0)
    saved_frac = jnp.sum(saved) / jnp.maximum(jnp.sum(aux.n_iterated), 1)
    return final, cache, hit_t, saved_frac


def _stats(aux: RasterAux, hit, saved_frac, sorted_flag) -> FrameStats:
    tot_iter = jnp.maximum(jnp.sum(aux.n_iterated), 1)
    return FrameStats(
        hit_rate=jnp.mean(hit.astype(jnp.float32)),
        sig_frac=jnp.sum(aux.n_significant) / tot_iter,
        mean_iterated=jnp.mean(aux.n_iterated.astype(jnp.float32)),
        saved_frac=saved_frac,
        sorted_this_frame=jnp.asarray(sorted_flag, jnp.float32),
    )


# ---------------------------------------------------------------------------
# Functional core: SceneShared + ViewerPrivate (+ the single-viewer
# composition ViewerState) and the two-phase render step
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ViewerPrivate:
    """What one viewer carries that no one else can share.

    prev_cam  : camera of the previous rendered frame (pose prediction input)
    frame_idx : int32 scalar frame counter (drives the sort cadence)
    cell_id   : int32 pose-cell key of the sort entry this viewer consumes
                (``repro.core.posecell``; -1 before the first sort)
    pool_idx  : int32 index into its scene's ``SceneShared.pool``
    """

    prev_cam: Camera
    frame_idx: jax.Array
    cell_id: jax.Array
    pool_idx: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SceneShared:
    """Per-*scene* state shared by every viewer of that scene.

    cache     : ONE radiance cache for the scene — all viewers probe and
                insert into it in deterministic (slot, pixel) order
                (``radiance_cache.lookup_all_groups_multi`` / ``_multi``)
    pool      : pose-cell-keyed pool of ``SortShared`` entries, leaves with
                a leading [P] axis; viewers in the same pose cell consume
                one entry, so the pool holds O(distinct cells) live buffers
                instead of one per viewer
    pool_cell : [P] int32 pose-cell key held by each entry (-1 = free)
    pool_refs : [P] int32 count of live viewers referencing each entry
    pool_tick : [P] int32 tick of each entry's last speculative sort
                (scheduler freshness; -window before any sort)

    The pool bookkeeping (``pool_cell``/``pool_refs``/``pool_tick``) is
    owned by the host-side scheduler, which keeps these device copies in
    sync so the functional state stays self-describing — no jitted
    computation reads them.

    A fleet of scenes is this pytree with a leading scene axis [C]; see
    ``init_fleet``.
    """

    cache: rc.CacheState
    pool: SortShared
    pool_cell: jax.Array
    pool_refs: jax.Array
    pool_tick: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ViewerState:
    """The single-viewer composition: one scene, one viewer, a pool of one —
    its own cache and its own sort, exactly the pre-split state model.  This
    is what ``render_step``/``LuminSys`` carry; multi-viewer serving holds
    ``SceneShared``/``ViewerPrivate`` separately (``repro.serve.stepper``).

    Being a pytree, a batch of viewers is just a ``ViewerState`` whose leaves
    carry a leading slot axis — ``render_step`` vmaps over it unchanged.
    """

    scene_shared: SceneShared
    viewer: ViewerPrivate

    # Convenience views mirroring the pre-split field names.
    @property
    def cache(self) -> rc.CacheState:
        return self.scene_shared.cache

    @property
    def shared(self) -> SortShared:
        """The sort entry this viewer consumes (entry 0 of its own pool)."""
        return jax.tree.map(lambda x: x[0], self.scene_shared.pool)

    @property
    def prev_cam(self) -> Camera:
        return self.viewer.prev_cam

    @property
    def frame_idx(self) -> jax.Array:
        return self.viewer.frame_idx


def copy_pytree(tree):
    """Fresh buffers for every array leaf — required before handing a pytree
    to a donating jitted call while the original is referenced elsewhere."""
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)


def pytree_nbytes(tree) -> int:
    """Total device bytes across a pytree's array leaves (telemetry)."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def init_scene_shared(scene: GaussianScene, cfg: LuminaConfig, cam0: Camera,
                      pool_size: int = 1) -> SceneShared:
    """Cold-start shared state for one scene at ``cam0``'s resolution."""
    cache = rc.init_cache(num_groups(cam0.width, cam0.height, cfg.group_tiles),
                          cfg.cache)
    entry = empty_sort_shared(
        scene, cam0, margin=cfg.margin, capacity=cfg.capacity,
        method=cfg.sort_method,
        max_tiles_per_gaussian=cfg.max_tiles_per_gaussian)
    pool = jax.tree.map(lambda x: jnp.stack([x] * pool_size), entry)
    return SceneShared(
        cache=cache, pool=pool,
        pool_cell=jnp.full((pool_size,), -1, jnp.int32),
        pool_refs=jnp.zeros((pool_size,), jnp.int32),
        pool_tick=jnp.full((pool_size,), -cfg.window, jnp.int32))


def init_viewer_private(cam0: Camera) -> ViewerPrivate:
    """Cold-start private state for one viewer."""
    # prev_cam gets its own buffers: the state is donated into jitted steps,
    # and the first step is typically called with cam0 itself — donating
    # aliased leaves is an XLA error (`f(donate(a), a)`).
    return ViewerPrivate(prev_cam=copy_pytree(cam0), frame_idx=jnp.int32(0),
                         cell_id=jnp.int32(-1), pool_idx=jnp.int32(0))


def init_viewer_state(scene: GaussianScene, cfg: LuminaConfig,
                      cam0: Camera) -> ViewerState:
    """Cold-start state for one viewer rendering at ``cam0``'s resolution."""
    return ViewerState(scene_shared=init_scene_shared(scene, cfg, cam0),
                       viewer=init_viewer_private(cam0))


def init_fleet(scene: GaussianScene, cfg: LuminaConfig, cam0: Camera,
               slots: int, viewers_per_scene: int = 1,
               pool_size: int | None = None):
    """Cold-start serving state: ``slots`` viewers over
    ``slots // viewers_per_scene`` scenes.

    Returns ``(SceneShared with [C]-leading leaves, ViewerPrivate with
    [S]-leading leaves)``; slot ``i`` belongs to scene ``i //
    viewers_per_scene`` (a static block layout, so the scene-major cache
    reshapes in ``batched_shade_phase`` are pure views).  ``pool_size``
    defaults to ``viewers_per_scene`` — the worst case of every viewer in
    its own pose cell — so pool allocation can never fail; co-located
    viewers leave all but one entry free (live count is what telemetry and
    the benchmarks watch).
    """
    v = viewers_per_scene
    if slots % v:
        raise ValueError(f'slots ({slots}) must be a multiple of '
                         f'viewers_per_scene ({v})')
    c = slots // v
    p = v if pool_size is None else pool_size
    shared1 = init_scene_shared(scene, cfg, cam0, pool_size=p)
    priv1 = init_viewer_private(cam0)
    shared = jax.tree.map(lambda x: jnp.stack([x] * c), shared1)
    priv = jax.tree.map(lambda x: jnp.stack([x] * slots), priv1)
    return shared, priv


def sort_entry(scene: GaussianScene, private: ViewerPrivate, cam: Camera,
               cfg: LuminaConfig) -> SortShared:
    """Pose prediction + speculative Projection/Sorting for one viewer:
    the raw ``SortShared`` entry a sharing window consumes.

    Pure and unconditional — the *caller* decides when it runs and where the
    entry lands (``sort_phase`` writes the single-viewer pool; the pose-cell
    scheduler in ``repro.serve.stepper`` scatters entries into each scene's
    pool, one per distinct cell).
    """
    pred = predict_window_pose(private.prev_cam, cam, private.frame_idx,
                               cfg.window)
    return speculative_sort(
        scene, pred, margin=cfg.margin, capacity=cfg.capacity,
        method=cfg.sort_method,
        max_tiles_per_gaussian=cfg.max_tiles_per_gaussian)


def sort_phase(scene: GaussianScene, shared: SceneShared,
               private: ViewerPrivate, cam: Camera,
               cfg: LuminaConfig) -> SceneShared:
    """Phase 1 of a frame: run ``sort_entry`` and write it into the viewer's
    pool entry, stamping ``pool_tick`` with the viewer's frame counter.
    Returns the updated ``SceneShared`` (cache untouched).  Pose-cell
    bookkeeping (``pool_cell``/``pool_refs``) is the serving scheduler's
    job — the single-viewer cadence never needs it.
    """
    entry = sort_entry(scene, private, cam, cfg)
    pool = jax.tree.map(
        lambda full, upd: full.at[private.pool_idx].set(upd),
        shared.pool, entry)
    return dataclasses.replace(
        shared, pool=pool,
        pool_tick=shared.pool_tick.at[private.pool_idx].set(
            private.frame_idx.astype(jnp.int32)))


def shade_phase(scene: GaussianScene, shared: SceneShared,
                private: ViewerPrivate, cam: Camera,
                cfg: LuminaConfig, *, sorted_flag=0.0, active=None):
    """Phase 2 of a frame: sorting-shared prep + rasterization + radiance
    cache, consuming the viewer's pool entry
    (``shared.pool[private.pool_idx]``).  Sort-free by construction — its
    cost is the per-frame cost S^2 amortizes the sort against.

    ``sorted_flag`` is threaded into ``FrameStats.sorted_this_frame`` (the
    phase itself never sorts, so whoever scheduled the sort reports it).
    ``active`` (scalar bool per call/lane) reaches the rasterizer's ``live``
    input: evicted/idle lanes in the batched serving path contribute nothing
    and count zero iterations instead of burning chunk iterations.

    ``cfg.backend`` picks the shade implementation: ``'reference'`` shades
    through the pure-JAX rasterizer and applies the radiance cache after the
    fact (RC savings *modeled*); ``'pallas'`` shades through the chunked
    kernel pipeline — prefix / lookup / miss-compacted resume / insert —
    where hits genuinely stop integration at the alpha-record and the
    ``live`` mask skips chunk iterations (RC savings *measured*).  The two
    agree on every integer cache decision; images agree to float32 ulp
    (the kernel evaluates alpha densely per chunk, so contraction order
    differs).  ``FrameStats.saved_frac`` keeps per-backend semantics: the
    modeled per-pixel integration saving on ``reference``, the realized
    chunk-level saving vs a count-capped full pass on ``pallas``.

    Returns ``(new_shared, new_private, image, FrameStats)`` — the shared
    state comes back functionally updated (cache evolution), the pool is
    never touched by a shade.
    """
    tiles_x, tiles_y = tile_grid(cam.width, cam.height)
    sort = jax.tree.map(lambda x: x[private.pool_idx], shared.pool)
    feats, lists = _prep_features(scene, sort, cam, cfg)

    if cfg.backend == 'pallas':
        from repro.kernels import ops
        # significance-exact list trim: entries that cannot reach
        # alpha > 1/255 inside their tile at the *render* pose (stale S^2
        # margin entries) are dropped and survivors compacted — images,
        # records and cache decisions are bit-unchanged, only examined-work
        # counters shrink (see ops.trim_features)
        feats = ops.trim_features(feats, tiles_x)
        if cfg.use_rc:
            colors, cache, aux, kst = ops.rasterize_with_rc(
                feats, tiles_x, tiles_y, shared.cache, cfg.cache,
                cfg.group_tiles, k_record=cfg.k_record,
                chunk=cfg.shade_chunk, bg=cfg.bg, live=active,
                compact=cfg.rc_compact)
            hit = kst.hit
            saved_frac = 1.0 - ((kst.chunks_prefix + kst.chunks_resume)
                                .astype(jnp.float32)
                                / jnp.maximum(kst.chunks_bound, 1))
        else:
            colors, aux, _ = ops.rasterize_full(
                feats, tiles_x, k_record=cfg.k_record, chunk=cfg.shade_chunk,
                bg=cfg.bg, live=active)
            cache = shared.cache
            hit = jnp.zeros(aux.n_iterated.shape, bool)
            saved_frac = jnp.float32(0.0)
    else:
        colors, aux = rasterize_tiles(feats, lists.tiles_x,
                                      k_record=cfg.k_record, bg=cfg.bg,
                                      live=active)
        if cfg.use_rc:
            colors, cache, hit, saved_frac = rc_apply(shared.cache, colors,
                                                      aux, tiles_x, tiles_y,
                                                      cfg)
        else:
            cache = shared.cache
            hit = jnp.zeros(aux.n_iterated.shape, bool)
            saved_frac = jnp.float32(0.0)

    image = assemble_image(colors, tiles_x, tiles_y, cam.width, cam.height)
    stats = _stats(aux, hit, saved_frac,
                   jnp.asarray(sorted_flag, jnp.float32))
    new_shared = dataclasses.replace(shared, cache=cache)
    new_private = dataclasses.replace(private, prev_cam=cam,
                                      frame_idx=private.frame_idx + 1)
    return new_shared, new_private, image, stats


def render_step(scene: GaussianScene, state: ViewerState, cam: Camera,
                cfg: LuminaConfig):
    """One frame of the Lumina pipeline as a pure function: the composition
    ``sort_phase`` (under a ``lax.cond`` on ``frame_idx % window``) followed
    by ``shade_phase``, over the single-viewer state composition.

    Returns ``(new_state, image, FrameStats)``.  The cond keeps the whole
    step one jittable function; note that under vmap the cond lowers to a
    select and every lane pays the sort — batched serving uses the pose-cell
    scheduler in ``repro.serve.stepper`` instead.
    """
    shared, private = state.scene_shared, state.viewer
    if cfg.use_s2:
        do_sort = (private.frame_idx % cfg.window) == 0
        shared = jax.lax.cond(
            do_sort,
            lambda sh: sort_phase(scene, sh, private, cam, cfg),
            lambda sh: sh,
            shared)
        sorted_flag = do_sort.astype(jnp.float32)
    else:
        sorted_flag = jnp.float32(1.0)
    shared, private, image, stats = shade_phase(
        scene, shared, private, cam, cfg, sorted_flag=sorted_flag)
    return ViewerState(scene_shared=shared, viewer=private), image, stats


def batched_render_step(scene: GaussianScene, states: ViewerState,
                        cams: Camera, cfg: LuminaConfig):
    """vmap of ``render_step`` over a slot axis: states/cams carry a leading
    [S] axis (build cams with ``repro.core.camera.stack_cameras``); the scene
    is shared.  Returns batched ``(states, images, FrameStats)``.

    Each lane keeps its own sort cadence (exact parity with independent
    ``LuminSys`` runs), so the per-lane ``lax.cond`` lowers to a select under
    vmap and the speculative sort executes for every lane on every tick —
    this is the parity oracle, not the serving fast path.  The serving path
    (``repro.serve.stepper.BatchedStepper``) staggers sort phases across
    slots and runs the sort only for the due pose cells each tick.
    """
    return jax.vmap(lambda st, cm: render_step(scene, st, cm, cfg))(
        states, cams)


def scene_of_slot(slots: int, viewers_per_scene: int) -> jax.Array:
    """Static slot -> scene map: slot ``i`` serves scene ``i // V`` (block
    layout, so per-scene reshapes of slot-major arrays are pure views)."""
    return jnp.arange(slots, dtype=jnp.int32) // viewers_per_scene


def gather_sort_entries(shared: SceneShared, priv: ViewerPrivate,
                        viewers_per_scene: int = 1) -> SortShared:
    """Per-slot ``SortShared`` views out of the scene pools:
    entry ``pool[scene_of(slot), priv.pool_idx[slot]]`` for every slot."""
    s = priv.frame_idx.shape[0]
    c_of = scene_of_slot(s, viewers_per_scene)
    return jax.tree.map(lambda x: x[c_of, priv.pool_idx], shared.pool)


def batched_shade_phase(scene: GaussianScene, shared: SceneShared,
                        priv: ViewerPrivate, cams: Camera,
                        sorted_flags: jax.Array, active: jax.Array,
                        cfg: LuminaConfig, viewers_per_scene: int = 1):
    """The per-tick shade for all serving slots over scene-shared state.
    ``shared`` carries [C]-leading leaves (C = S // viewers_per_scene),
    ``priv``/``cams`` [S]-leading; ``sorted_flags`` [S] float32 and
    ``active`` [S] bool are per-slot scalars from the scheduler.  Returns
    ``(new_shared, new_priv, images, FrameStats)``.

    Rasterization is per-slot (vmapped); the radiance-cache stages run
    **scene-major**: each scene's cache serves all its viewers' probes and
    inserts as one slot-major batch (``rc.lookup_all_groups_multi`` /
    ``insert_all_groups_multi``), so cross-viewer conflicts resolve in
    deterministic (slot, pixel) order and idle lanes (``active`` False)
    neither touch LRU state nor insert.  With ``viewers_per_scene == 1``
    the scene-major reshape is the identity and every slot owns a private
    cache — bit-identical to pre-split serving.

    On the pallas backend the kernel stages run **slot-batched** (phase A
    puts every slot's lanes of a tile in one program, phase B compacts
    misses across the whole fleet) against the same shared caches
    (``ops.rasterize_with_rc_slots``); only chunk *accounting* is
    fleet-coupled, so ``FrameStats.saved_frac`` on that path is the
    fleet-level measured saving (same value reported to every slot)."""
    if cfg.backend == 'pallas':
        return _batched_shade_pallas(scene, shared, priv, cams, sorted_flags,
                                     active, cfg, viewers_per_scene)
    s = sorted_flags.shape[0]
    v = viewers_per_scene
    c = s // v
    tiles_x, tiles_y = tile_grid(cams.width, cams.height)
    sorts = gather_sort_entries(shared, priv, v)

    def raster_one(sort, cam, act):
        feats, lists = _prep_features(scene, sort, cam, cfg)
        return rasterize_tiles(feats, lists.tiles_x, k_record=cfg.k_record,
                               bg=cfg.bg, live=act)

    colors, aux = jax.vmap(raster_one)(sorts, cams, active)

    if cfg.use_rc:
        ids_g = jax.vmap(
            lambda r: regroup(r, tiles_x, tiles_y, cfg.group_tiles)
        )(aux.alpha_record)                                  # [S, G, B, k]
        raw_g = jax.vmap(
            lambda x: regroup(x, tiles_x, tiles_y, cfg.group_tiles))(colors)
        ids_cv = ids_g.reshape(c, v, *ids_g.shape[1:])       # [C, V, G, B, k]
        raw_cv = raw_g.reshape(c, v, *raw_g.shape[1:])
        act_cv = active.reshape(c, v)
        hit_cv, val_cv, _, _, caches = jax.vmap(
            lambda cc, ii, lv: rc.lookup_all_groups_multi(cc, ii, cfg.cache,
                                                          live=lv)
        )(shared.cache, ids_cv, act_cv)
        final_cv = jnp.where(hit_cv[..., None], val_cv, raw_cv)
        caches = jax.vmap(
            lambda cc, ii, rr, dd: rc.insert_all_groups_multi(cc, ii, rr, dd,
                                                              cfg.cache)
        )(caches, ids_cv, raw_cv, ~hit_cv & act_cv[:, :, None, None])
        hit = jax.vmap(
            lambda h: ungroup(h[..., None], tiles_x, tiles_y,
                              cfg.group_tiles)[..., 0]
        )(hit_cv.reshape(s, *hit_cv.shape[2:]))
        colors = jax.vmap(
            lambda x: ungroup(x, tiles_x, tiles_y, cfg.group_tiles)
        )(final_cv.reshape(s, *final_cv.shape[2:]))
        # A hit pixel stops after identifying its k significant Gaussians
        # (same modeled-saving formula as rc_apply, per slot).
        saved = jnp.where(hit, jnp.maximum(aux.n_iterated - aux.iter_at_k,
                                           0), 0)
        saved_frac = (jnp.sum(saved, axis=(1, 2))
                      / jnp.maximum(jnp.sum(aux.n_iterated, axis=(1, 2)), 1))
    else:
        caches = shared.cache
        hit = jnp.zeros(aux.n_iterated.shape, bool)
        saved_frac = jnp.zeros((s,), jnp.float32)

    images = jax.vmap(
        lambda cg: assemble_image(cg, tiles_x, tiles_y, cams.width,
                                  cams.height))(colors)
    stats = jax.vmap(_stats)(aux, hit, saved_frac, sorted_flags)
    new_shared = dataclasses.replace(shared, cache=caches)
    new_priv = dataclasses.replace(priv, prev_cam=cams,
                                   frame_idx=priv.frame_idx + 1)
    return new_shared, new_priv, images, stats


def _prep_features(scene: GaussianScene, sort: SortShared, cam: Camera,
                   cfg: LuminaConfig):
    """Per-frame shade prep: S^2 sorting-shared feature refresh of the given
    sort entry, or a fresh Projection+Sorting in baseline mode.  One
    definition for the per-slot and slot-batched paths — their bit-identity
    depends on it."""
    if cfg.use_s2:
        return shared_features(scene, cam, sort)
    proj = project(scene, cam)
    lists = sort_scene(proj, cam.width, cam.height, cfg.capacity,
                       method=cfg.sort_method,
                       max_tiles_per_gaussian=cfg.max_tiles_per_gaussian)
    return gather_tile_features(proj, lists), lists


def batched_prep_features(scene: GaussianScene, shared: SceneShared,
                          priv: ViewerPrivate, cams: Camera,
                          cfg: LuminaConfig, viewers_per_scene: int = 1):
    """Per-slot shade prep (``_prep_features``) over a slot axis:
    [S, T, K, ...] feature stacks."""
    sorts = gather_sort_entries(shared, priv, viewers_per_scene)
    return jax.vmap(
        lambda so, cm: _prep_features(scene, so, cm, cfg)[0])(sorts, cams)


def trim_features_slots(feats_b, tiles_x: int):
    """``ops.trim_features`` over [S, T, K, ...] feature stacks (same
    per-row math as the unbatched trim, so slot-batched and per-slot shades
    stay bit-identical)."""
    from repro.core.tiling import TileFeatures
    from repro.kernels import ops
    s, t = feats_b.ids.shape[:2]
    flat = TileFeatures(*[x.reshape((s * t,) + x.shape[2:]) for x in feats_b])
    flat = ops.trim_features(flat, tiles_x, t_img=t)
    return TileFeatures(*[x.reshape((s, t) + x.shape[1:]) for x in flat])


def _batched_shade_pallas(scene: GaussianScene, shared: SceneShared,
                          priv: ViewerPrivate, cams: Camera,
                          sorted_flags: jax.Array, active: jax.Array,
                          cfg: LuminaConfig, viewers_per_scene: int = 1):
    """Slot-batched pallas shade over scene-shared caches (see
    ``batched_shade_phase``)."""
    from repro.kernels import ops
    tiles_x, tiles_y = tile_grid(cams.width, cams.height)
    s = sorted_flags.shape[0]
    with shade_stage('prep'):
        feats_b = batched_prep_features(scene, shared, priv, cams, cfg,
                                        viewers_per_scene)
        feats_b = trim_features_slots(feats_b, tiles_x)

    if cfg.use_rc:
        # the kernel wrapper marks its raster, probe and insert stages
        colors, caches, aux, kst = ops.rasterize_with_rc_slots(
            feats_b, tiles_x, tiles_y, shared.cache, cfg.cache,
            cfg.group_tiles, viewers_per_scene=viewers_per_scene,
            k_record=cfg.k_record, chunk=cfg.shade_chunk,
            bg=cfg.bg, live=active, compact=cfg.rc_compact)
        hit = kst.hit                                    # [S, T, P]
        # fleet-coupled chunk accounting -> fleet-level measured saving
        saved = 1.0 - ((kst.chunks_prefix + kst.chunks_resume)
                       .astype(jnp.float32)
                       / jnp.maximum(kst.chunks_bound, 1))
        saved_b = jnp.broadcast_to(saved, (s,))
        insert = {'insert_pending': jnp.broadcast_to(kst.insert_pending, (s,)),
                  'insert_branch': jnp.broadcast_to(kst.insert_branch, (s,))}
    else:
        insert = {}
        with shade_stage('raster'):
            colors, aux, _ = ops.rasterize_full_slots(
                feats_b, tiles_x, k_record=cfg.k_record,
                chunk=cfg.shade_chunk, bg=cfg.bg, live=active)
        caches = shared.cache
        hit = jnp.zeros(aux.n_iterated.shape, bool)
        saved_b = jnp.zeros((s,), jnp.float32)

    with shade_stage('raster'):
        images = jax.vmap(
            lambda c: assemble_image(c, tiles_x, tiles_y, cams.width,
                                     cams.height))(colors)
    stats = jax.vmap(_stats)(aux, hit, saved_b, sorted_flags)._replace(
        **insert)
    new_shared = dataclasses.replace(shared, cache=caches)
    new_priv = dataclasses.replace(priv, prev_cam=cams,
                                   frame_idx=priv.frame_idx + 1)
    return new_shared, new_priv, images, stats


def batched_sort_phase(scene: GaussianScene, privates: ViewerPrivate,
                       cams: Camera, cfg: LuminaConfig) -> SortShared:
    """vmap of ``sort_entry`` over a (small) cohort axis: privates/cams carry
    a leading [C] axis of just the slots elected to sort.  Where the entries
    land (which scene pool, which pose cell) is the scheduler's decision —
    this just produces them."""
    return jax.vmap(lambda pv, cm: sort_entry(scene, pv, cm, cfg))(
        privates, cams)


# ---------------------------------------------------------------------------
# The runner — thin single-viewer wrapper over the functional core
# ---------------------------------------------------------------------------

class LuminSys:
    """Stateful frame-sequencer: carries one ``ViewerState`` through the
    jitted ``render_step``.

    Usage::

        sys = LuminSys(scene, cfg, example_cam)
        for cam in trajectory:
            image, stats = sys.step(cam)
    """

    def __init__(self, scene: GaussianScene, cfg: LuminaConfig, cam0: Camera):
        self.scene = scene
        self.cfg = cfg
        self.tiles_x, self.tiles_y = tile_grid(cam0.width, cam0.height)
        self.state = init_viewer_state(scene, cfg, cam0)
        # The previous ViewerState is dead the instant the step returns —
        # donate it so XLA updates the cache/shared buffers in place instead
        # of copying the full O(N) state every frame.
        self._step = jax.jit(functools.partial(render_step, cfg=cfg),
                             donate_argnums=(1,))

    @property
    def cache(self) -> rc.CacheState:
        """The *current* cache state.  The step donates its input state, so a
        reference held across a later ``step`` call points at deleted buffers
        — re-read the property (or copy) instead of caching it."""
        return self.state.cache

    @property
    def frame_idx(self) -> int:
        return int(self.state.frame_idx)

    def step(self, cam: Camera):
        self.state, image, stats = self._step(self.scene, self.state, cam)
        return image, stats
