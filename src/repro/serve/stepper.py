"""Slot steppers: how a set of viewer slots advances one frame.

Two interchangeable engines behind one interface:

* ``BatchedStepper``    — the serving fast path over **scene-centric**
  state: slots are partitioned into scenes (``viewers_per_scene`` slots per
  scene, a static block layout), each scene holding ONE shared radiance
  cache and a pose-cell-keyed pool of speculative-sort entries
  (``SceneShared``), while per-slot state shrinks to a ``ViewerPrivate``.
  A **pose-cell sort scheduler** generalizes the PR-2 cohort scheduler:
  slot ``i`` comes due when ``global_tick % window == i % window`` (plus
  sort-on-admit outside the tick), due slots are grouped by (scene,
  pose cell), and each group elects one **leader** (lowest slot) to run the
  speculative sort — co-located viewers share one ``SortShared`` buffer, so
  the pool holds O(distinct cells) live entries instead of one per slot.
  A due slot whose cell already has a *fresh* entry (sorted within the
  window, by a still-active owner still in that cell) adopts it without
  sorting at all.  Each tick then advances all live slots through one
  ``batched_shade_phase``, whose cache stages run scene-major: every
  viewer of a scene probes and fills the scene's single cache, conflicts
  resolving in deterministic (slot, pixel) order.
* ``SequentialStepper`` — each active slot advances through its own
  single-viewer jitted ``render_step`` (the reference/baseline the benchmark
  compares against; per-viewer sort cadence, exact ``LuminSys`` semantics,
  fully private state).

With ``viewers_per_scene == 1`` (the default) every slot is its own scene:
private cache, singleton pose-cell groups, the exact PR-2 cohort cadence —
single-viewer behavior is bit-identical to the pre-split engine, preserved
by the parity oracles in ``tests/test_serve.py``.

Cadence caveats: the scheduler shifts *when* each slot sorts relative to an
independent per-viewer run (every frame still renders from a sort no older
than ``window`` frames in private mode; a shared entry adopted from another
viewer's leader can be up to ``2*window - 1`` ticks old across an ownership
handoff).  For a single viewer in slot 0 admitted at tick 0 the cadences
coincide and the engines agree on every integer cache decision.

Both engines **donate** their state buffers into the jitted calls (the
previous tick's state is dead the instant the step returns), so XLA updates
the O(S*N) state in place instead of round-tripping a copy every tick.

**Idle-lane compaction**: when whole scenes are idle, the batched engine
gathers the active scene blocks into a dense prefix (padded to a
power-of-two bucket so at most log2(C) shade widths ever compile), shades
only that sub-batch, and scatters results back — idle scenes are not shaded
at all and their state is left untouched.  Idle slots *within* an active
scene ride the shade with ``active=False``: they contribute nothing, touch
no LRU state and insert nothing into the shared cache.  With one slot per
scene this reduces exactly to the PR-3 per-slot compaction.

**Stage attribution**: the shade program marks its stages with
``jax.named_scope`` (``repro.obs.trace.shade_stage``: prep, raster,
rc_probe, rc_insert, and lanes for the lane-compaction gathers and
scatters here), so a ``jax.profiler`` trace attributes every device
operation of the one fused program to its stage.

Interface::

    stepper.admit(slot)                  # reset a slot to cold-start state
    out = stepper.step({slot: cam, ..})  # advance the given slots one frame
    # out: {slot: (image, FrameStats, TickTiming)}
    stepper.sort_log                     # per-step {'scheduled','admit',
                                         #           'joined'} counts
    stepper.last_timing                  # tick-level TickTiming of the last
                                         # non-empty step
    stepper.state_metrics()              # occupancy + state-memory bytes

Async host-loop seam (``repro.serve.events``)::

    plan = stepper.plan_step(cams)       # pure host planning (worker-safe)
    infl = stepper.step_dispatch(cams, plan)  # host mutations + async
                                              # device dispatch
    out = stepper.step_finish(infl)      # block on the device, assemble
    # step(cams, plan) == step_finish(step_dispatch(cams, plan))
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import posecell
from repro.core import radiance_cache as rc
from repro.core.buckets import pow2_bucket
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.core.camera import Camera, stack_cameras
from repro.core.gaussians import GaussianScene
from repro.core.pipeline import (LuminaConfig, SceneShared, ViewerPrivate,
                                 ViewerState, batched_shade_phase,
                                 batched_sort_phase, copy_pytree, init_fleet,
                                 init_scene_shared, init_viewer_private,
                                 init_viewer_state, pytree_nbytes,
                                 render_step)
from repro.core.tiling import tile_grid


class TickTiming(NamedTuple):
    """Per-phase latency attribution for the tick a frame rode in."""

    latency_s: float     # wall-clock of the whole tick (sort + shade)
    sort_ms: float       # wall-clock of the tick's sort-phase calls
    shade_ms: float      # wall-clock of the tick's shade-phase call
    sorted_slots: int    # speculative sorts executed this tick (incl. admits)


class _SortGroup(NamedTuple):
    """One due (scene, cell) group resolved by the pose-cell scheduler."""

    scene: int
    cell: int
    leader: int          # lowest due slot; runs the sort if one is needed
    members: tuple       # all due slots adopting the entry
    riders: tuple        # non-due co-located slots consolidated onto it
    entry: int           # pool index the group lands in
    sorts: bool          # False = adopted a fresh entry, no sort executed


class _StepPlan(NamedTuple):
    """Precomputed host scheduling for one ``step(cams)`` call (see
    ``BatchedStepper.plan_step``): the pure planning output the async host
    loop computes off-thread while the device executes the previous tick."""

    active: frozenset    # slots rendering this step (stalled slots removed)
    admits: tuple        # slots sorting on admit (outside the cohort)
    due: tuple           # all slots consuming a sort refresh this step
    groups: tuple        # _SortGroup plan from the pose-cell scheduler
    stream: object = None  # StreamPlan when scene residency is streamed


class _InFlight(NamedTuple):
    """A dispatched-but-unfinished batched step: everything ``step_finish``
    needs to block, attribute timing and assemble per-slot outputs."""

    cams: dict           # the step's {slot: cam} request
    images: object       # dispatched (not yet synced) device arrays
    stats: object
    pos: dict            # slot -> lane in images/stats
    t0: float            # perf_counter at step start
    t1: float            # perf_counter at shade dispatch
    sort_s: float        # host+device seconds of the sort phase
    n_sched: int
    n_admit: int
    tick: int = 0        # global_tick the step ran at (trace span args)


class BatchedStepper:
    """All live slots advance in one scene-major ``batched_shade_phase``
    call per tick (gathered to a dense scene prefix when some scenes are
    idle); speculative sorts run once per due (scene, pose-cell) group."""

    def __init__(self, scene: GaussianScene, cfg: LuminaConfig,
                 cam0: Camera, slots: int,
                 viewers_per_scene: int = 1, pool_size: int | None = None,
                 cell_size: float = posecell.CELL_SIZE,
                 cell_ang_bins: int = posecell.ANG_BINS,
                 streaming=None):
        if slots % viewers_per_scene:
            raise ValueError(f'slots ({slots}) must be a multiple of '
                             f'viewers_per_scene ({viewers_per_scene})')
        # Streaming residency (repro.serve.streaming.ResidencyManager): the
        # effective scene is the manager's masked arena view — same shape
        # every tick, so a residency change swaps ``self.scene`` without
        # recompiling anything (the scene is an argument to every jitted
        # call, never a closure capture).
        self._streaming = streaming
        if streaming is not None:
            if streaming.grace_ticks is None:
                # eviction grace must outlive any stale sorted tile list:
                # one full sort window plus dispatch slack
                streaming.grace_ticks = (max(1, cfg.window)
                                         if cfg.use_s2 else 1) + 2
            scene = streaming.scene()
        self.scene = scene
        self.cfg = cfg
        self.slots = slots
        self.viewers_per_scene = viewers_per_scene
        self.num_scenes = slots // viewers_per_scene
        self.pool_size = (viewers_per_scene if pool_size is None
                          else pool_size)
        # Dropless allocation: in shared mode the pool no longer reserves
        # the every-viewer-its-own-cell worst case (``pool_size`` entries
        # per scene) up front.  Capacity starts at one entry and
        # grows/shrinks with the live pose-cell count in power-of-two
        # buckets (``_resize_pool``), the same capacity-bucket routing a
        # dropless-MoE router applies to token -> expert dispatch.  An
        # explicit ``pool_size`` pins the static worst-case layout (the
        # baseline the benchmark compares against); private mode (one
        # viewer per scene) is already a pool-of-one.
        self.dynamic_pool = pool_size is None and viewers_per_scene > 1
        self.pool_cap = 1 if self.dynamic_pool else self.pool_size
        self.cell_size = cell_size
        self.cell_ang_bins = cell_ang_bins
        self.window = max(1, cfg.window) if cfg.use_s2 else 1
        # Fixed sort-call width: at most ceil(S/window) groups are due per
        # scheduled tick, so the gather/sort/scatter call jits once for the
        # worst-case cohort (admit bursts are chunked to the same width).
        self.cohort = -(-slots // self.window)
        self.global_tick = 0
        self.tiles_x, self.tiles_y = tile_grid(cam0.width, cam0.height)

        self.shared: SceneShared
        self.priv: ViewerPrivate
        self.shared, self.priv = init_fleet(
            scene, cfg, cam0, slots, viewers_per_scene=viewers_per_scene,
            pool_size=self.pool_cap)
        self._fresh_shared = init_scene_shared(scene, cfg, cam0,
                                               pool_size=self.pool_cap)
        self._fresh_priv = init_viewer_private(cam0)

        # slot -> scene (static block layout) and host-side scheduler
        # mirrors of the device pool bookkeeping
        self._scene_of = np.arange(slots) // viewers_per_scene
        self._pool_cell = np.full((self.num_scenes, self.pool_cap), -1,
                                  np.int64)
        self._pool_tick = np.full((self.num_scenes, self.pool_cap),
                                  -self.window, np.int64)
        self._pool_owner = np.full((self.num_scenes, self.pool_cap), -1,
                                   np.int64)
        self._slot_pool = np.zeros((slots,), np.int64)
        self._refs = np.zeros((self.num_scenes, self.pool_cap), np.int64)
        # occupied slots (admit .. release) and stashed co-resident viewer
        # contexts (slot oversubscription): both hold pool references, so
        # a paced-idle or stashed viewer's sort entry is never reclaimed
        self._resident: set[int] = set()
        self._stash: dict[str, dict] = {}

        # observability: the SessionManager shares its tracer/registry with
        # the stepper; standalone steppers default to no-op/private ones
        self.tracer = obs_trace.NULL
        self.metrics = obs_metrics.Registry()

        self._slot_cams: list[Camera] = [cam0] * slots
        # frames each slot rendered since it last consumed a sort refresh
        # (drives the paced-slot staleness catch-up in _due_scheduled)
        self._frames_since_due = np.zeros((slots,), np.int64)
        self._pending_sort: set[int] = set()   # admitted, not yet sorted
        self.sort_log: list[dict] = []         # per-step sort accounting
        self.last_timing: TickTiming | None = None

        self._shade = jax.jit(
            functools.partial(batched_shade_phase, cfg=cfg,
                              viewers_per_scene=viewers_per_scene),
            donate_argnums=(1, 2))
        # scene-block shade jits per within-scene lane width (lane
        # compaction; the full-width instance is the legacy _shade_sub)
        self._lane_jits: dict[int, object] = {}
        self._shade_sub = self._get_lane_jit(viewers_per_scene)
        self._sort_pool = jax.jit(self._sort_pool_fn, donate_argnums=(1,))
        self._resize = jax.jit(self._resize_pool_fn, donate_argnums=(0, 2))
        self._admit_scene = jax.jit(self._admit_scene_fn,
                                    donate_argnums=(0, 1))
        self._admit_priv = jax.jit(self._admit_priv_fn, donate_argnums=(0,))
        self._occupancy = jax.jit(rc.occupancy)
        # static byte accounting for state_metrics()
        self._pool_entry_bytes = (pytree_nbytes(self.shared.pool)
                                  // (self.num_scenes * self.pool_cap))
        self._cache_bytes = pytree_nbytes(self.shared.cache)

    # -- jitted bodies ------------------------------------------------------

    def _sort_pool_fn(self, scene, shared, priv, cams, slot_idx, scene_tgt,
                      pool_tgt, cells, tick):
        """Run the elected leaders' sorts and scatter the entries into the
        scene pools.

        ``slot_idx`` [W] int32 leader slots (padded with duplicates of a
        real slot); ``scene_tgt``/``pool_tgt`` [W] int32 scatter targets —
        ``num_scenes`` (out of bounds, dropped) for padding lanes.  Shared
        state is donated: all leaves except the updated pool alias straight
        through; privates are read-only (pose prediction inputs).
        """
        sub_priv = jax.tree.map(lambda x: x[slot_idx], priv)
        sub_cams = jax.tree.map(lambda x: x[slot_idx], cams)
        entries = batched_sort_phase(scene, sub_priv, sub_cams, self.cfg)
        pool = jax.tree.map(
            lambda full, upd: full.at[scene_tgt, pool_tgt].set(upd,
                                                               mode='drop'),
            shared.pool, entries)
        return dataclasses.replace(
            shared, pool=pool,
            pool_cell=shared.pool_cell.at[scene_tgt, pool_tgt].set(
                cells, mode='drop'),
            pool_tick=shared.pool_tick.at[scene_tgt, pool_tgt].set(
                tick, mode='drop'))

    def _shade_sub_fn(self, scene, shared, priv, cams, sorted_mask,
                      scene_idx, scene_tgt, slot_idx, slot_tgt, act_sub,
                      lanes=None):
        """Active-scene-prefix shade: gather the ``scene_idx`` scene blocks
        (and their ``slot_idx`` slots), shade only them, scatter the
        advanced state back.  ``scene_tgt``/``slot_tgt`` use
        ``num_scenes``/``slots`` (= dropped) for padding lanes; ``act_sub``
        [B*L] bool is False for padding and for idle slots inside active
        scenes.  ``lanes`` is the within-scene lane width L of the gathered
        sub-batch: the full ``viewers_per_scene`` on the legacy scene-block
        path, or a smaller power-of-two bucket when lane compaction gathers
        only each scene's live lanes.  Untouched scenes' state — and, under
        lane compaction, the idle lanes of shaded scenes — pass through
        unchanged."""
        lanes = self.viewers_per_scene if lanes is None else lanes
        with obs_trace.shade_stage('lanes'):
            sub_shared = jax.tree.map(lambda x: x[scene_idx], shared)
            sub_priv = jax.tree.map(lambda x: x[slot_idx], priv)
            sub_cams = jax.tree.map(lambda x: x[slot_idx], cams)
            sub_sorted = sorted_mask[slot_idx]
        new_sh, new_pr, images, stats = batched_shade_phase(
            scene, sub_shared, sub_priv, sub_cams, sub_sorted, act_sub,
            self.cfg, lanes)
        with obs_trace.shade_stage('lanes'):
            shared2 = jax.tree.map(
                lambda full, upd: full.at[scene_tgt].set(upd, mode='drop'),
                shared, new_sh)
            priv2 = jax.tree.map(
                lambda full, upd: full.at[slot_tgt].set(upd, mode='drop'),
                priv, new_pr)
        return shared2, priv2, images, stats

    def _get_lane_jit(self, lanes: int):
        """Jitted scene-block shade at within-scene lane width ``lanes``
        (one compile per power-of-two width, so at most log2(V) variants
        ever build — the same bound the scene-bucket compaction holds)."""
        fn = self._lane_jits.get(lanes)
        if fn is None:
            fn = jax.jit(functools.partial(self._shade_sub_fn, lanes=lanes),
                         donate_argnums=(1, 2))
            self._lane_jits[lanes] = fn
        return fn

    def _resize_pool_fn(self, cache, pool, priv, perm, remap, cell, tick,
                        refs):
        """Device half of a pool-capacity resize: gather the kept entries
        into the new layout (``perm`` [C, new_cap] old entry index per
        scene) and remap every viewer's ``pool_idx`` (``remap`` [C,
        old_cap] new index per old entry).  Entry payloads move bit-intact
        and every referencing lane follows its entry, so per-viewer output
        is unchanged by construction.  The pool is passed (and returned)
        separately from the rest of ``SceneShared``: its leaves change
        shape across the call, so only the shape-stable cache/priv buffers
        are donated."""
        c_idx = jnp.arange(self.num_scenes, dtype=jnp.int32)[:, None]
        new_pool = jax.tree.map(lambda x: x[c_idx, perm], pool)
        scene_of = jnp.asarray(self._scene_of, jnp.int32)
        new_idx = remap[scene_of, priv.pool_idx]
        shared = SceneShared(cache=cache, pool=new_pool, pool_cell=cell,
                             pool_tick=tick, pool_refs=refs)
        priv = dataclasses.replace(priv, pool_idx=new_idx)
        return shared, priv

    @staticmethod
    def _admit_scene_fn(shared, priv, fresh_shared, fresh_priv, scene_i,
                        slot):
        """Private-mode admit: cold-start the slot's whole scene (cache +
        pool) and its private state — exactly the pre-split semantics."""
        shared = jax.tree.map(
            lambda full, one: full.at[scene_i].set(one), shared, fresh_shared)
        priv = jax.tree.map(
            lambda full, one: full.at[slot].set(one), priv, fresh_priv)
        return shared, priv

    @staticmethod
    def _admit_priv_fn(priv, fresh_priv, slot):
        """Shared-mode admit: only the viewer's private state resets; the
        scene's cache (and any live pool entries) persist — that is the
        cross-viewer reuse this engine exists for."""
        return jax.tree.map(lambda full, one: full.at[slot].set(one),
                            priv, fresh_priv)

    # -- dropless pool capacity ---------------------------------------------

    def _resize_pool(self, new_cap: int,
                     keep: Optional[list] = None) -> None:
        """Resize the per-scene pool to ``new_cap`` entries.

        ``keep`` (shrink only) lists the entry indices each scene must
        preserve; they compact to a dense prefix in index order.  Growth
        passes ``keep=None`` and pads: old entries keep their indices, new
        entries start free (cell -1, aged tick, zero refs — their gathered
        payload is whatever entry 0 held, which nothing ever reads before a
        sort overwrites it).  Host mirrors, ``_slot_pool``, stashed lane
        contexts and the device state all move through the same mapping.
        """
        old = self.pool_cap
        c = self.num_scenes
        perm = np.zeros((c, new_cap), np.int64)
        remap = np.zeros((c, old), np.int64)
        cell = np.full((c, new_cap), -1, np.int64)
        tick = np.full((c, new_cap), -self.window, np.int64)
        owner = np.full((c, new_cap), -1, np.int64)
        refs = np.zeros((c, new_cap), np.int64)
        for ci in range(c):
            kept = (sorted(keep[ci]) if keep is not None
                    else list(range(min(old, new_cap))))
            for j, p in enumerate(kept):
                perm[ci, j] = p
                remap[ci, p] = j
                cell[ci, j] = self._pool_cell[ci, p]
                tick[ci, j] = self._pool_tick[ci, p]
                owner[ci, j] = self._pool_owner[ci, p]
                refs[ci, j] = self._refs[ci, p]
        self.shared, self.priv = self._resize(
            self.shared.cache, self.shared.pool, self.priv,
            jnp.asarray(perm, jnp.int32),
            jnp.asarray(remap, jnp.int32), jnp.asarray(cell, jnp.int32),
            jnp.asarray(tick, jnp.int32), jnp.asarray(refs, jnp.int32))
        self._pool_cell, self._pool_tick = cell, tick
        self._pool_owner, self._refs = owner, refs
        self._slot_pool = remap[self._scene_of, self._slot_pool]
        for ctx in self._stash.values():
            ctx['slot_pool'] = int(
                remap[int(self._scene_of[ctx['slot']]), ctx['slot_pool']])
        self.pool_cap = new_cap
        self.metrics.counter('pool.resizes',
                             'sort-pool capacity resizes').inc()
        self.metrics.gauge('pool.capacity',
                           'allocated sort-pool entries per scene'
                           ).set(new_cap)

    def _grow_pool_for(self, groups) -> None:
        """Grow capacity to cover the plan's highest entry index (the
        planner allocates virtual indices past ``pool_cap`` when no free
        entry exists — the dropless contract: route every live pose cell,
        never drop one)."""
        need = 1 + max((g.entry for g in groups), default=-1)
        if need > self.pool_cap:
            self._resize_pool(pow2_bucket(need))

    def _keep_entries(self) -> list:
        """Entries a shrink must preserve, per scene: referenced by any
        resident lane (active, paced-idle or stashed), plus entries still
        adoptable (sorted within the window by a still-resident owner) —
        dropping those would turn a would-be adoption into a re-sort and
        change per-viewer output vs the static pool."""
        keep = [set() for _ in range(self.num_scenes)]
        for ci in range(self.num_scenes):
            for p in range(self.pool_cap):
                if self._refs[ci, p] > 0:
                    keep[ci].add(p)
                elif (int(self._pool_owner[ci, p]) in self._resident
                      and self.global_tick - self._pool_tick[ci, p]
                      < self.window):
                    keep[ci].add(p)
        return keep

    def _maybe_shrink_pool(self) -> None:
        keep = self._keep_entries()
        used = max((len(k) for k in keep), default=0)
        target = pow2_bucket(used)
        if target < self.pool_cap:
            self._resize_pool(target, keep=keep)

    # -- slot residency / oversubscription ----------------------------------

    def release(self, slot: int) -> None:
        """The manager vacated ``slot``: drop it from the resident set so
        its pool entry no longer counts as referenced and the bucketed
        pool may reclaim the capacity."""
        self._resident.discard(slot)
        self._pending_sort.discard(slot)

    def stash_lane(self, slot: int, key: str) -> None:
        """Park the slot's current viewer context under ``key`` so a
        co-resident viewer can interleave into the same physical lane
        (slot oversubscription).  The parked context keeps its pool
        reference — a stashed viewer's sort entry is never reclaimed."""
        self._stash[key] = {
            'slot': int(slot),
            'priv': jax.tree.map(lambda x: np.asarray(x[slot]), self.priv),
            'cam': jax.tree.map(np.asarray, self._slot_cams[slot]),
            'frames_since_due': int(self._frames_since_due[slot]),
            'pending_sort': slot in self._pending_sort,
            'slot_pool': int(self._slot_pool[slot]),
        }
        self._pending_sort.discard(slot)

    def unstash_lane(self, slot: int, key: str) -> None:
        """Swap a parked viewer context back into its physical lane (the
        jitted admit scatter — lane shapes always match, no recompile)."""
        ctx = self._stash.pop(key)
        if ctx['slot'] != slot:
            raise ValueError(f'stash {key!r} belongs to slot '
                             f'{ctx["slot"]}, not {slot}')
        priv_lane = jax.tree.map(jnp.asarray, ctx['priv'])
        self.priv = self._admit_priv(self.priv, priv_lane, jnp.int32(slot))
        self._slot_cams[slot] = jax.tree.map(jnp.asarray, ctx['cam'])
        self._frames_since_due[slot] = ctx['frames_since_due']
        self._slot_pool[slot] = ctx['slot_pool']
        if ctx['pending_sort']:
            self._pending_sort.add(slot)
        else:
            self._pending_sort.discard(slot)

    def drop_stash(self, key: str) -> None:
        """A stashed viewer was evicted: its parked context (and pool
        reference) goes away."""
        self._stash.pop(key, None)

    # -- scheduling ---------------------------------------------------------

    def reset(self) -> None:
        """Cold-start every scene and viewer WITHOUT recompiling: fresh
        fleet state, pool bookkeeping and tick counter on the already-jitted
        callables.  Benchmarks use this between repetitions — in shared mode
        ``admit`` deliberately keeps scene caches warm, so only a reset
        separates repetitions honestly."""
        if self._streaming is not None:
            self._streaming.reset()
            self.scene = self._streaming.scene()
        self.pool_cap = 1 if self.dynamic_pool else self.pool_size
        self.shared, self.priv = init_fleet(
            self.scene, self.cfg, self._fresh_priv.prev_cam, self.slots,
            viewers_per_scene=self.viewers_per_scene,
            pool_size=self.pool_cap)
        c = self.num_scenes
        self._pool_cell = np.full((c, self.pool_cap), -1, np.int64)
        self._pool_tick = np.full((c, self.pool_cap), -self.window, np.int64)
        self._pool_owner = np.full((c, self.pool_cap), -1, np.int64)
        self._slot_pool = np.zeros((self.slots,), np.int64)
        self._refs = np.zeros((c, self.pool_cap), np.int64)
        self._frames_since_due[:] = 0
        self._pending_sort.clear()
        self._resident.clear()
        self._stash.clear()
        self.global_tick = 0
        self.sort_log = []
        self.last_timing = None

    def admit(self, slot: int) -> None:
        # fresh templates are read (not donated) by the admit scatters, so
        # they stay valid across admits without copies
        if self.viewers_per_scene == 1:
            scene_i = int(self._scene_of[slot])
            self.shared, self.priv = self._admit_scene(
                self.shared, self.priv, self._fresh_shared,
                self._fresh_priv, jnp.int32(scene_i), jnp.int32(slot))
            self._pool_cell[scene_i] = -1
            self._pool_tick[scene_i] = -self.window
            self._pool_owner[scene_i] = -1
        else:
            self.priv = self._admit_priv(self.priv, self._fresh_priv,
                                         jnp.int32(slot))
        self._slot_pool[slot] = 0
        self._frames_since_due[slot] = 0
        self._resident.add(slot)
        # The slot's camera is only known at the next step(): run its
        # sort-on-admit there, outside the scheduled per-tick cohort.
        self._pending_sort.add(slot)

    def quarantine(self, slot: int) -> None:
        """Blast-radius containment for a poisoned slot: its private state
        (the corrupt ``prev_cam`` rides there) resets to the cold-start
        template, any pool entry it *owns* is marked stale (owner cleared,
        tick aged out of the window) so no co-located viewer adopts it as
        fresh, and the slot re-sorts on its next frame.  In private mode
        this is a full scene cold-start; in shared mode the scene's cache
        persists — the ``jnp.isfinite`` insert gate already kept the
        poisoned values out of it."""
        scene_i = int(self._scene_of[slot])
        if self.viewers_per_scene > 1:
            owned = np.flatnonzero(self._pool_owner[scene_i] == slot)
            self._pool_owner[scene_i, owned] = -1
            self._pool_tick[scene_i, owned] = -self.window
        # co-residents stashed on this physical lane may reference an
        # invalidated entry: force them through a fresh sort on return
        for ctx in self._stash.values():
            if ctx['slot'] == slot:
                ctx['pending_sort'] = True
        self.admit(slot)
        # the stacked camera batch reads _slot_cams every dispatch — a NaN
        # lane must not linger past containment
        self._slot_cams[slot] = self._fresh_priv.prev_cam

    def _due_scheduled(self, active: set, exclude: set,
                       fsd=None) -> list[int]:
        """Slots due for a scheduled sort refresh this tick: the cohort
        residue leg (``global_tick % window == slot % window``) plus a
        staleness catch-up for frame-paced viewers.

        The residue leg assumes a slot renders every tick; a paced slot
        (``ViewerSession.pace`` > 1) renders only every ``pace`` ticks, and
        when its render ticks never align with its residue (e.g. ``pace %
        window == 0`` off-phase) it would ride its admission sort forever
        while faster co-resident viewers keep ``global_tick`` advancing.
        The catch-up leg marks a slot due when the frame it is about to
        render would otherwise be its ``window``-th since the last refresh
        (``frames_since_due`` counts the rendered-unrefreshed frames, so
        the trigger is ``>= window - 1``) — restoring the documented "no
        frame renders from a sort older than ``window`` *frames*" bound on
        the slot's own frame clock, at exactly the legacy refresh spacing.
        For always-active (pace-1) slots the residue leg fires no later
        than the catch-up could (a refresh every ``window`` ticks ==
        ``window`` frames), so the legacy cohort cadence — and its
        bit-parity oracles — are untouched.
        """
        fsd = self._frames_since_due if fsd is None else fsd
        r = self.global_tick % self.window
        return [i for i in range(self.slots)
                if i in active and i not in exclude
                and (i % self.window == r
                     or fsd[i] >= self.window - 1)]

    def _plan_groups(self, due: list[int], active: set,
                     cells: dict[int, int], slot_pool=None,
                     protect=()) -> list[_SortGroup]:
        """Group the due slots by (scene, pose cell), elect leaders, pick
        pool entries, and decide which groups actually sort.

        Deterministic given (slot -> cell, pool bookkeeping): groups are
        processed in (scene, leader) order, entry allocation prefers the
        entry already holding the cell, then the lowest-index free entry
        (refs counted over active non-due slots plus earlier groups).  A
        group *adopts* without sorting iff its cell's entry is fresh
        (sorted within the window) and owned by a still-active slot outside
        the group that is still in that cell — so a lone viewer (or any
        private-mode slot) always sorts on its own cadence, bit-identical
        to the cohort scheduler.

        Non-due active slots of the same scene whose *current* cell matches
        a group's ride along onto its entry ("riders"): they were going to
        render this cell from an older buffer of their own; consolidating
        them onto the freshly sorted (strictly fresher, same-cell, so
        margin-equivalent) entry keeps co-located fleets at one live buffer
        per cell instead of one per cadence phase.  Riders do not count as
        sorted — their cadence is untouched.

        With the bucketed pool, entries referenced by paced-idle residents
        and by stashed (oversubscribed) viewer contexts are seeded into the
        refcounts too, so a viewer idling this tick never has its entry
        stolen.  When every in-capacity entry is referenced, the dynamic
        pool allocates *virtual* entry indices past ``pool_cap`` — the
        dropless contract: ``_grow_pool_for`` resizes before the sorts
        scatter, so no pose cell is ever dropped.  ``slot_pool``/``protect``
        let ``plan_step`` substitute post-lane-swap entry assignments.
        """
        sp = self._slot_pool if slot_pool is None else slot_pool
        groups: dict[tuple[int, int], list[int]] = {}
        for i in due:
            groups.setdefault((int(self._scene_of[i]), cells[i]),
                              []).append(i)
        rider_pool: dict[tuple[int, int], list[int]] = {}
        for i in sorted(active):
            key = (int(self._scene_of[i]), cells[i])
            if i not in due and key in groups:
                rider_pool.setdefault(key, []).append(i)

        refs = np.zeros((self.num_scenes, self.pool_cap), np.int64)
        for i in active:
            if i not in due and (int(self._scene_of[i]), cells[i]) \
                    not in groups:
                refs[self._scene_of[i], sp[i]] += 1
        for i in self._resident:
            if i not in active and i not in self._pending_sort:
                refs[self._scene_of[i], sp[i]] += 1
        for ctx in self._stash.values():
            if not ctx['pending_sort']:
                refs[self._scene_of[ctx['slot']], ctx['slot_pool']] += 1
        for scene_i, p in protect:
            refs[scene_i, p] += 1
        claimed: set[tuple[int, int]] = set()
        next_new: dict[int, int] = {}
        planned = []
        for (scene_i, cell), members in sorted(groups.items(),
                                               key=lambda kv: min(kv[1])):
            leader = min(members)
            riders = tuple(rider_pool.get((scene_i, cell), ()))
            # an entry still tagged with this cell is only reusable if no
            # earlier group claimed it this tick (a stale held entry with
            # zero refs is fair game for another group's free-entry search;
            # reusing it anyway would scatter two sorts into one slot)
            held = [int(p)
                    for p in np.flatnonzero(self._pool_cell[scene_i] == cell)
                    if (scene_i, int(p)) not in claimed]
            entry = held[0] if held else -1
            if entry >= 0:
                owner = int(self._pool_owner[scene_i, entry])
                fresh = (self.global_tick - self._pool_tick[scene_i, entry]
                         < self.window)
                owner_ok = (owner in active and owner not in members
                            and cells.get(owner) == cell)
                if fresh and owner_ok:
                    planned.append(_SortGroup(scene_i, cell, leader,
                                              tuple(members), riders,
                                              entry, False))
                    claimed.add((scene_i, entry))
                    refs[scene_i, entry] += len(members) + len(riders)
                    continue
            if entry < 0:
                free = [p for p in range(self.pool_cap)
                        if refs[scene_i, p] == 0
                        and (scene_i, p) not in claimed]
                if free:
                    entry = free[0]
                elif self.dynamic_pool:
                    # every in-capacity entry is referenced: allocate a
                    # virtual index past pool_cap; _grow_pool_for resizes
                    # before the sorts scatter (dropless)
                    entry = next_new.get(scene_i, self.pool_cap)
                    next_new[scene_i] = entry + 1
                else:
                    # static pool: a free entry always exists (each slot
                    # references at most one entry and the pool holds one
                    # per slot); fall back to overwriting the leader's
                    # current entry defensively
                    entry = int(self._slot_pool[leader])
            planned.append(_SortGroup(scene_i, cell, leader, tuple(members),
                                      riders, entry, True))
            claimed.add((scene_i, entry))
            if entry < self.pool_cap:
                refs[scene_i, entry] += len(members) + len(riders)
        return planned

    def _run_sorts(self, cam_b: Camera, groups: list[_SortGroup]) -> None:
        """Execute the sorting groups' leader sorts, ``cohort`` at a time."""
        tick = jnp.int32(self.global_tick)
        for i in range(0, len(groups), self.cohort):
            batch = groups[i:i + self.cohort]
            pad = self.cohort - len(batch)
            slot_idx = jnp.asarray([g.leader for g in batch]
                                   + [batch[0].leader] * pad, jnp.int32)
            scene_tgt = jnp.asarray([g.scene for g in batch]
                                    + [self.num_scenes] * pad, jnp.int32)
            pool_tgt = jnp.asarray([g.entry for g in batch] + [0] * pad,
                                   jnp.int32)
            cell_keys = jnp.asarray([g.cell for g in batch] + [0] * pad,
                                    jnp.int32)
            self.shared = self._sort_pool(self.scene, self.shared, self.priv,
                                          cam_b, slot_idx, scene_tgt,
                                          pool_tgt, cell_keys, tick)
        for g in groups:
            self._pool_cell[g.scene, g.entry] = g.cell
            self._pool_tick[g.scene, g.entry] = self.global_tick
            self._pool_owner[g.scene, g.entry] = g.leader

    def _apply_assignments(self, groups: list[_SortGroup],
                           active: set) -> None:
        """Point every group member at its entry (host mirrors + device
        ``ViewerPrivate``) and refresh the pool refcounts."""
        slots, pools, cellv = [], [], []
        for g in groups:
            for m in g.members + g.riders:
                self._slot_pool[m] = g.entry
                slots.append(m)
                pools.append(g.entry)
                cellv.append(g.cell)
        if slots:
            idx = jnp.asarray(slots, jnp.int32)
            self.priv = dataclasses.replace(
                self.priv,
                pool_idx=self.priv.pool_idx.at[idx].set(
                    jnp.asarray(pools, jnp.int32)),
                cell_id=self.priv.cell_id.at[idx].set(
                    jnp.asarray(cellv, jnp.int32)))
        refs = np.zeros((self.num_scenes, self.pool_cap), np.int64)
        for i in active:
            refs[self._scene_of[i], self._slot_pool[i]] += 1
        # paced-idle residents and stashed co-resident contexts hold their
        # entries across idle ticks (not a steal candidate, not shrinkable)
        for i in self._resident:
            if i not in active and i not in self._pending_sort:
                refs[self._scene_of[i], self._slot_pool[i]] += 1
        for ctx in self._stash.values():
            if not ctx['pending_sort']:
                refs[self._scene_of[ctx['slot']], ctx['slot_pool']] += 1
        self._refs = refs
        self.shared = dataclasses.replace(
            self.shared, pool_refs=jnp.asarray(refs, jnp.int32))

    def _slot_cell_key(self, slot: int, cam: Camera) -> int:
        """Pose-cell key for a slot rendering ``cam``.  In private mode
        (one viewer per scene) cells are moot — the slot id keys its own
        singleton group, sparing the quantization work."""
        if self.viewers_per_scene == 1:
            return slot
        return posecell.pose_cell_key(cam, cell_size=self.cell_size,
                                      ang_bins=self.cell_ang_bins)

    def plan_step(self, cams: dict[int, Camera], pending_admits=(),
                  lane_swaps=None) -> _StepPlan:
        """Pure host planning for a coming ``step(cams)`` call: pose-cell
        quantization, the sort-on-admit set, the due cohort and the sort
        groups.  Reads only the host-side scheduler mirrors (never device
        arrays) and mutates nothing — the async host loop runs this on a
        worker thread while the device executes the previous tick.  The
        caller must sequence it after the previous ``step_dispatch`` has
        returned (that dispatch's host bookkeeping is this plan's input).

        ``pending_admits`` names slots whose ``admit()`` is planned but not
        yet applied — the manager plans ahead of admission, so those slots'
        sort-on-admit must be scheduled here even though ``_pending_sort``
        does not contain them yet.

        ``lane_swaps`` maps slot -> stash key for oversubscribed lanes the
        manager will swap before dispatch: the plan substitutes the
        incoming context's pending/cadence/entry bookkeeping for the
        slot's, and protects the outgoing occupant's entry (it is stashed,
        not released) from the free-entry search.
        """
        stream = None
        if self._streaming is not None and cams:
            # residency first: slots stalled on a missing chunk drop out of
            # this tick entirely (no render, no sort, cursor retried), so
            # the scheduling below sees only the slots that will run.
            # Pending admits are named so their cold-start loads are exempt
            # from the per-tick load budget.
            admit_guess = ((set(self._pending_sort) | set(pending_admits))
                           & set(cams))
            stream = self._streaming.plan(self.global_tick, cams,
                                          admit_guess)
            if stream.stalled:
                cams = {s: c for s, c in cams.items()
                        if s not in stream.stalled}
        active = set(cams)
        if not cams or not self.cfg.use_s2:
            return _StepPlan(frozenset(active), (), (), (), stream)
        swaps = dict(lane_swaps or {})
        cells = {i: self._slot_cell_key(i, cams[i]) for i in active}
        pending = set(self._pending_sort)
        slot_pool = self._slot_pool
        fsd = self._frames_since_due
        protect = []
        if swaps:
            slot_pool = slot_pool.copy()
            fsd = fsd.copy()
            for slot, key in swaps.items():
                ctx = self._stash[key]
                if slot not in self._pending_sort:
                    protect.append((int(self._scene_of[slot]),
                                    int(self._slot_pool[slot])))
                pending.discard(slot)
                if ctx['pending_sort']:
                    pending.add(slot)
                slot_pool[slot] = ctx['slot_pool']
                fsd[slot] = ctx['frames_since_due']
        # Sort-on-admit outside the tick's scheduled cohort: newly
        # admitted slots must not render a stale or zero-filled entry.
        admits = sorted((pending | set(pending_admits)) & active)
        sched = self._due_scheduled(active, exclude=set(admits), fsd=fsd)
        due = sorted(set(admits) | set(sched))
        groups = self._plan_groups(due, active, cells, slot_pool=slot_pool,
                                   protect=protect)
        return _StepPlan(active=frozenset(active), admits=tuple(admits),
                         due=tuple(due), groups=tuple(groups),
                         stream=stream)

    def _apply_stream(self, stream) -> None:
        """Execute a residency plan (evictions, loads, LOD render masks)
        and swap the streamed scene view in for this tick's shade.  The
        manager publishes through this stepper's registry/tracer so the
        ``stream.*`` series land where the session rolls tick metrics up;
        they are re-pointed every call because the session installs its
        tracer after construction."""
        mgr = self._streaming
        mgr.metrics = self.metrics
        mgr.tracer = self.tracer
        mgr.apply(stream)
        if mgr.dirty:
            # scene is an argument to every jitted callable (same shapes:
            # the arena is fixed-size), so the swap never recompiles
            self.scene = mgr.scene()

    def step_dispatch(self, cams: dict[int, Camera],
                      plan: Optional[_StepPlan] = None):
        """Host scheduling + async device dispatch for one step.  Returns an
        ``_InFlight`` handle; all host-side mutations (sort bookkeeping,
        ``global_tick``, ``sort_log``) are complete when this returns — only
        the device shade is still executing.  ``step_finish`` blocks on it.
        """
        if not cams:
            return None
        with self.tracer.span('step_dispatch', tick=self.global_tick,
                              slots=len(cams)):
            return self._dispatch(cams, plan)

    def _dispatch(self, cams: dict[int, Camera],
                  plan: Optional[_StepPlan]):
        if plan is None:
            plan = self.plan_step(cams)
        if plan.stream is not None:
            self._apply_stream(plan.stream)
            if plan.stream.stalled:
                # a stalled slot renders nothing this tick: its cursor is
                # never advanced (no output), so the same frame retries
                # next tick against the freshly loaded chunks
                cams = {s: c for s, c in cams.items()
                        if s not in plan.stream.stalled}
            if not cams:
                # every requested slot stalled — the loads above still ran,
                # so the retried tick can make progress
                self.global_tick += 1
                self.sort_log.append({'scheduled': 0, 'admit': 0,
                                      'joined': 0})
                return None
        for slot, cam in cams.items():
            self._slot_cams[slot] = cam
        cam_b = stack_cameras(self._slot_cams)
        active = set(cams)

        t0 = time.perf_counter()
        n_admit = n_sched = n_joined = 0
        if self.cfg.use_s2:
            groups = list(plan.groups)
            sorting = [g for g in groups if g.sorts]
            if self.dynamic_pool:
                # grow BEFORE the sorts scatter: the planner's virtual
                # entry indices must be in capacity or the mode='drop'
                # scatter would silently discard the sort
                self._grow_pool_for(groups)
            if sorting:
                self._run_sorts(cam_b, sorting)
            self._apply_assignments(groups, active)
            self._pending_sort -= active
            if self.dynamic_pool:
                # shrink AFTER assignments refreshed the refcounts, so
                # capacity tracks the live pose-cell count this tick
                self._maybe_shrink_pool()
            admit_set = set(plan.admits)
            n_admit = sum(1 for g in sorting if g.leader in admit_set)
            n_sched = len(sorting) - n_admit
            n_joined = (sum(len(g.members) for g in groups if not g.sorts)
                        + sum(len(g.riders) for g in groups))
            # executions vs adoptions, attributed per (scene, pose cell):
            # the redundancy ledger the pose-cell scheduler is judged by
            for g in groups:
                adopted = len(g.members) - (1 if g.sorts else 0)
                if g.sorts:
                    self.metrics.counter(
                        'sort.executed', 'speculative sorts run',
                        scene=g.scene, cell=g.cell).inc()
                if adopted:
                    self.metrics.counter(
                        'sort.adopted', 'due slots adopting a leader sort',
                        scene=g.scene, cell=g.cell).inc(adopted)
                if g.riders:
                    self.metrics.counter(
                        'sort.riders',
                        'non-due slots consolidated onto a fresh entry',
                        scene=g.scene, cell=g.cell).inc(len(g.riders))
            # Two deliberately different telemetry views of "sorted":
            # per-session ``sorted_this_frame`` flags every DUE slot — it
            # reached its cadence point and renders from a sort refreshed
            # for its cell this window (executed by it or adopted from the
            # group leader), so per-viewer sorts_per_frame stays ~1/window.
            # Tick-level ``sorted_slots``/sort_log count only EXECUTED
            # sorts — the fleet's cost.  Their ratio IS the sharing win.
            # (Riders are not due and not flagged: cadence untouched.)
            sorted_set = set(plan.due)
            for i in active:
                self._frames_since_due[i] = (0 if i in sorted_set
                                             else self._frames_since_due[i]
                                             + 1)
            if sorting:
                with self.tracer.span('sort_wait', tick=self.global_tick):
                    jax.block_until_ready(self.shared.pool.lists.indices)
        else:
            # Baseline mode runs Projection+Sorting for every active lane
            # every frame (inside shade_phase, so its cost lands in
            # shade_ms): count those sorts so tick_rollup/sort_log never
            # report an amortization this mode doesn't have.
            self._pending_sort -= active
            sorted_set = active
            n_sched = len(sorted_set)
            self.metrics.counter(
                'sort.executed',
                'per-lane sorts (no-S2 baseline)').inc(n_sched)
        sort_s = time.perf_counter() - t0
        if n_sched + n_admit:
            # the sort window on the device lane (the leader sorts block
            # inside dispatch, so begin/end are explicit)
            self.tracer.complete('sort', t0, t0 + sort_s,
                                 tick=self.global_tick,
                                 executed=n_sched + n_admit)

        sorted_mask = jnp.asarray(
            [1.0 if i in sorted_set else 0.0 for i in range(self.slots)],
            jnp.float32)

        v = self.viewers_per_scene
        active_scenes = sorted({int(self._scene_of[i]) for i in active})
        per_scene = {c: [i for i in range(c * v, (c + 1) * v) if i in active]
                     for c in active_scenes}
        # within-scene lane width: the pow2 bucket of the busiest active
        # scene's live lane count (lane compaction); v itself when every
        # lane bucket rounds up to full width
        lanes = (pow2_bucket(max(len(s) for s in per_scene.values()), cap=v)
                 if v > 1 else 1)
        t1 = time.perf_counter()
        if lanes == v and len(active_scenes) == self.num_scenes:
            # every scene live at full lane width: full shade, no
            # gather/scatter (idle slots inside a scene still pass
            # active=False)
            active_mask = jnp.asarray([i in active
                                       for i in range(self.slots)], bool)
            self.shared, self.priv, images, stats = self._shade(
                self.scene, self.shared, self.priv, cam_b, sorted_mask,
                active_mask)
            pos = {slot: slot for slot in active}
        elif lanes == v:
            # idle-scene compaction: shade only the active scene blocks,
            # padded to a power-of-two bucket so shade widths compile at
            # most log2(C) times; idle scenes are untouched
            bucket = pow2_bucket(len(active_scenes), cap=self.num_scenes)
            pad = bucket - len(active_scenes)
            scenes_g = active_scenes + [active_scenes[0]] * pad
            slots_g = [c * v + j for c in scenes_g for j in range(v)]
            scene_idx = jnp.asarray(scenes_g, jnp.int32)
            scene_tgt = jnp.asarray(active_scenes + [self.num_scenes] * pad,
                                    jnp.int32)
            slot_idx = jnp.asarray(slots_g, jnp.int32)
            slot_tgt = jnp.asarray(
                [c * v + j for c in active_scenes for j in range(v)]
                + [self.slots] * (pad * v), jnp.int32)
            act_sub = jnp.asarray(
                [i < len(active_scenes) * v and slots_g[i] in active
                 for i in range(bucket * v)])
            self.shared, self.priv, images, stats = self._shade_sub(
                self.scene, self.shared, self.priv, cam_b, sorted_mask,
                scene_idx, scene_tgt, slot_idx, slot_tgt, act_sub)
            pos = {slot: j for j, slot in enumerate(slots_g[:len(
                active_scenes) * v]) if slot in active}
        else:
            # within-scene lane compaction: gather each active scene's
            # LIVE lanes (padded to the common ``lanes`` bucket with inert
            # duplicates), shade the dense sub-batch, scatter only the
            # live lanes back.  Idle lanes of active scenes are untouched
            # — in particular never shaded and never charged a lane of
            # shade width.  Bit-identical per-viewer output: inactive
            # lanes contribute nothing to the shared cache/LRU, and the
            # skipped idle-lane private update only bumps ``frame_idx``
            # (read solely as ``frame_idx == 0``) and rewrites
            # ``prev_cam`` with the value it already holds.
            bucket = pow2_bucket(len(active_scenes), cap=self.num_scenes)
            pad = bucket - len(active_scenes)
            scenes_g = active_scenes + [active_scenes[0]] * pad
            slots_g: list[int] = []
            slot_tgt_l: list[int] = []
            for c in active_scenes:
                live = per_scene[c]
                fill = lanes - len(live)
                slots_g += live + [live[0]] * fill
                slot_tgt_l += live + [self.slots] * fill
            for _ in range(pad):
                slots_g += [slots_g[0]] * lanes
                slot_tgt_l += [self.slots] * lanes
            scene_idx = jnp.asarray(scenes_g, jnp.int32)
            scene_tgt = jnp.asarray(active_scenes + [self.num_scenes] * pad,
                                    jnp.int32)
            slot_idx = jnp.asarray(slots_g, jnp.int32)
            slot_tgt = jnp.asarray(slot_tgt_l, jnp.int32)
            act_sub = jnp.asarray([t < self.slots for t in slot_tgt_l])
            shade = self._get_lane_jit(lanes)
            self.shared, self.priv, images, stats = shade(
                self.scene, self.shared, self.priv, cam_b, sorted_mask,
                scene_idx, scene_tgt, slot_idx, slot_tgt, act_sub)
            pos = {s: j for j, (s, t) in enumerate(zip(slots_g, slot_tgt_l))
                   if t < self.slots}

        self.global_tick += 1
        self.sort_log.append({'scheduled': n_sched, 'admit': n_admit,
                              'joined': n_joined})
        return _InFlight(cams=cams, images=images, stats=stats, pos=pos,
                         t0=t0, t1=t1, sort_s=sort_s, n_sched=n_sched,
                         n_admit=n_admit, tick=self.global_tick - 1)

    def step_finish(self, infl) -> dict:
        """Block on a dispatched step's device work and assemble the per-slot
        outputs + tick timing."""
        if infl is None:
            return {}
        jax.block_until_ready(infl.images)
        t2 = time.perf_counter()
        # the async device window: dispatch -> outputs ready.  This is the
        # span the threaded driver's worker plan(t+1) should sit under.
        self.tracer.complete('shade', infl.t1, t2, tick=infl.tick,
                             slots=len(infl.cams))

        timing = TickTiming(latency_s=t2 - infl.t0,
                            sort_ms=infl.sort_s * 1e3,
                            shade_ms=(t2 - infl.t1) * 1e3,
                            sorted_slots=infl.n_sched + infl.n_admit)
        self.last_timing = timing
        # every rider of the batch waited for the whole tick
        return {slot: (infl.images[infl.pos[slot]],
                       jax.tree.map(lambda x: x[infl.pos[slot]], infl.stats),
                       timing)
                for slot in infl.cams}

    def step(self, cams: dict[int, Camera],
             plan: Optional[_StepPlan] = None) -> dict:
        return self.step_finish(self.step_dispatch(cams, plan))

    # -- telemetry ----------------------------------------------------------

    def state_metrics(self) -> dict:
        """Occupancy and state-memory footprint of the shared state.

        Three tiers, finest to coarsest: ``*_bytes`` charge only entries
        with live referencing viewers (the number of distinct (scene,
        pose-cell) sorts actually held); ``*_alloc_bytes`` report what the
        device currently allocates — under the dropless bucketed pool that
        is ``pool_cap`` entries per scene, tracking live work instead of
        the worst case; ``*_reserved_bytes`` report the static worst case
        (``pool_size`` entries per scene, the every-viewer-its-own-cell
        layout) the dynamic pool replaces — alloc == reserved when a
        pinned ``pool_size`` disables bucketing."""
        live = int((self._refs > 0).sum())
        pool_bytes = live * self._pool_entry_bytes
        pool_alloc = (self.num_scenes * self.pool_cap
                      * self._pool_entry_bytes)
        pool_reserved = (self.num_scenes * self.pool_size
                         * self._pool_entry_bytes)
        m = {
            # dispatched async, NOT synced here: the serving tick must not
            # block on a telemetry reduction (tick_rollup converts to float
            # after the timed loop)
            'occupancy': self._occupancy(self.shared.cache),
            'sort_pool_live': live,
            'sort_pool_total': self.num_scenes * self.pool_cap,
            'sort_pool_bytes': pool_bytes,
            'sort_pool_alloc_bytes': pool_alloc,
            'sort_pool_reserved_bytes': pool_reserved,
            'cache_bytes': self._cache_bytes,
            'state_bytes': pool_bytes + self._cache_bytes,
            'state_alloc_bytes': pool_alloc + self._cache_bytes,
            'state_reserved_bytes': pool_reserved + self._cache_bytes,
        }
        if self._streaming is not None:
            mgr = self._streaming
            cnt = mgr.counters()
            m.update({
                'stream_resident_bytes': mgr.resident_bytes,
                'stream_arena_bytes': mgr.arena_bytes,
                'stream_full_bytes': mgr.chunked.scene_bytes,
                'stream_stalls': cnt['stalls'],
                'stream_loads': cnt['loads'],
                'stream_prefetch_hits': cnt['prefetch_hits'],
                'stream_evictions': cnt['evictions'],
            })
        self.metrics.gauge(
            'state.alloc_bytes',
            'device bytes backing live serving state').set(
                float(m['state_alloc_bytes']))
        self.metrics.gauge(
            'state.reserved_bytes',
            'worst-case static-pool serving state bytes').set(
                float(m['state_reserved_bytes']))
        return m

    # -- checkpoint/restore --------------------------------------------------

    def state_dict(self) -> tuple:
        """``(arrays, meta)`` snapshot of everything a bit-identical resume
        needs: the device pytrees (``SceneShared``/``ViewerPrivate`` plus the
        stacked per-slot cameras — a restored dispatch re-stacks the same
        batch) and the host-side scheduler mirrors as plain JSON-able meta.
        The arrays pytree is what ``repro.checkpoint`` serializes; callers
        must snapshot at a tick boundary (nothing in flight — the shade
        donates these buffers)."""
        arrays = {'shared': self.shared, 'priv': self.priv,
                  'slot_cams': stack_cameras(self._slot_cams)}
        if self._stash:
            arrays['stash'] = {k: {'priv': ctx['priv'], 'cam': ctx['cam']}
                               for k, ctx in self._stash.items()}
        stream_meta = None
        if self._streaming is not None:
            stream_arrays, stream_meta = self._streaming.state_dict()
            arrays['stream'] = stream_arrays
        meta = {
            'global_tick': int(self.global_tick),
            'pool_cap': int(self.pool_cap),
            'pool_cell': self._pool_cell.tolist(),
            'pool_tick': self._pool_tick.tolist(),
            'pool_owner': self._pool_owner.tolist(),
            'slot_pool': self._slot_pool.tolist(),
            'refs': self._refs.tolist(),
            'frames_since_due': self._frames_since_due.tolist(),
            'pending_sort': sorted(int(i) for i in self._pending_sort),
            'resident': sorted(int(i) for i in self._resident),
            'stash': {k: {'slot': int(ctx['slot']),
                          'frames_since_due': int(ctx['frames_since_due']),
                          'pending_sort': bool(ctx['pending_sort']),
                          'slot_pool': int(ctx['slot_pool'])}
                      for k, ctx in self._stash.items()},
        }
        if stream_meta is not None:
            meta['stream'] = stream_meta
        return arrays, meta

    def load_state(self, arrays, meta: dict) -> None:
        """Restore a ``state_dict`` snapshot onto the already-compiled
        callables.  Shapes must match the snapshot (the checkpoint loader
        verifies them against a ``state_template`` built for the saved
        geometry); a snapshot taken at a different ``pool_cap`` than the
        live stepper holds simply retraces the affected jits on the next
        step — capacity is part of the crash-consistent state.
        ``jnp.asarray`` materializes fresh device buffers, so the next
        step's donation never aliases the caller's numpy copies."""
        self.shared = jax.tree.map(jnp.asarray, arrays['shared'])
        self.priv = jax.tree.map(jnp.asarray, arrays['priv'])
        cam_b = arrays['slot_cams']
        self._slot_cams = [
            jax.tree.map(lambda x, i=i: jnp.asarray(x)[i], cam_b)
            for i in range(self.slots)]
        self.global_tick = int(meta['global_tick'])
        self.pool_cap = int(meta.get('pool_cap', self.pool_size))
        self._pool_cell = np.asarray(meta['pool_cell'], np.int64)
        self._pool_tick = np.asarray(meta['pool_tick'], np.int64)
        self._pool_owner = np.asarray(meta['pool_owner'], np.int64)
        self._slot_pool = np.asarray(meta['slot_pool'], np.int64)
        self._refs = np.asarray(meta['refs'], np.int64)
        self._frames_since_due = np.asarray(meta['frames_since_due'],
                                            np.int64)
        self._pending_sort = set(int(i) for i in meta['pending_sort'])
        # legacy snapshots (pre-oversubscription) default every slot
        # resident — conservative: entries stay protected until the
        # manager's occupancy catches up
        self._resident = set(int(i) for i in
                             meta.get('resident', range(self.slots)))
        stash_arrays = arrays.get('stash', {})
        self._stash = {}
        for k, sm in meta.get('stash', {}).items():
            sa = stash_arrays[k]
            self._stash[k] = {
                'slot': int(sm['slot']),
                'priv': jax.tree.map(np.asarray, sa['priv']),
                'cam': jax.tree.map(np.asarray, sa['cam']),
                'frames_since_due': int(sm['frames_since_due']),
                'pending_sort': bool(sm['pending_sort']),
                'slot_pool': int(sm['slot_pool']),
            }
        if self._streaming is not None and 'stream' in meta:
            self._streaming.load_state(arrays['stream'], meta['stream'])
            self.scene = self._streaming.scene()

    def state_template(self, meta: dict):
        """Arrays pytree matching a snapshot's geometry WITHOUT mutating
        the live state: the checkpoint loader needs a shape template
        before deserializing, and a crashed run may have saved at a
        different pool capacity (or with stashed lanes) than a freshly
        constructed stepper holds.  ``meta`` is the snapshot's manifest
        extra (``state_dict()[1]``); only shapes matter — leaf values are
        never read."""
        shared = self.shared
        cap = int(meta.get('pool_cap', self.pool_cap))
        if cap != self.pool_cap:
            c = self.num_scenes
            shared = dataclasses.replace(
                shared,
                pool=jax.tree.map(
                    lambda x: np.zeros((c, cap) + x.shape[2:], x.dtype),
                    shared.pool),
                pool_cell=np.zeros((c, cap), np.int32),
                pool_tick=np.zeros((c, cap), np.int32),
                pool_refs=np.zeros((c, cap), np.int32))
        arrays = {'shared': shared, 'priv': self.priv,
                  'slot_cams': stack_cameras(self._slot_cams)}
        stash_meta = meta.get('stash', {})
        if stash_meta:
            lane = jax.tree.map(lambda x: np.asarray(x[0]), self.priv)
            cam = jax.tree.map(np.asarray, self._slot_cams[0])
            arrays['stash'] = {k: {'priv': lane, 'cam': cam}
                               for k in stash_meta}
        if self._streaming is not None and 'stream' in meta:
            arrays['stream'] = self._streaming.state_template()
        return arrays

    # -- viewer extraction / injection (fleet migration) ---------------------

    def extract_viewer(self, slot: int, with_scene: bool = False) -> dict:
        """Snapshot one viewer's lane for re-admission on another stepper.

        The payload always carries the ``ViewerPrivate`` lane and the slot's
        last camera (pose-prediction continuity across the move).  With
        ``with_scene`` (private mode only) it additionally carries the slot's
        whole ``SceneShared`` block plus the host pool mirrors for it — a
        *scene-carry* move that keeps the radiance cache warm.  Scene-carry
        payloads are only valid for an **aligned** restore (same slot index
        on a stepper at the same ``global_tick``): ``pool_owner`` stores slot
        ids and ``pool_tick`` stores absolute ticks, and neither is
        re-encoded here.  Cross-slot moves must restore cold
        (``shared=None``) and eat the documented sort-on-admit staleness."""
        scene_i = int(self._scene_of[slot])
        payload = {
            'priv': jax.tree.map(lambda x: np.asarray(x[slot]), self.priv),
            'cam': jax.tree.map(np.asarray, self._slot_cams[slot]),
            'frames_since_due': int(self._frames_since_due[slot]),
            'pending_sort': slot in self._pending_sort,
            'shared': None,
            'pool_rows': None,
        }
        if with_scene:
            if self.viewers_per_scene != 1:
                raise ValueError('scene-carry extraction needs a private '
                                 'scene block (viewers_per_scene == 1)')
            payload['shared'] = jax.tree.map(
                lambda x: np.asarray(x[scene_i]), self.shared)
            payload['pool_rows'] = {
                'pool_cell': self._pool_cell[scene_i].copy(),
                'pool_tick': self._pool_tick[scene_i].copy(),
                'pool_owner': self._pool_owner[scene_i].copy(),
                'slot_pool': int(self._slot_pool[slot]),
                'refs': self._refs[scene_i].copy(),
            }
        return payload

    def restore_viewer(self, slot: int, payload: dict) -> None:
        """Re-admit an ``extract_viewer`` payload into ``slot``.

        Scene-carry payloads reuse the jitted private-mode admit scatter
        (lane shapes match the cold templates, so no recompilation) and
        restore the pool mirrors — bit-identical continuation when the
        alignment contract above holds.  Cold payloads go through the normal
        ``admit`` (fresh scene, sort-on-admit queued) and then overwrite
        just the private lane, so the migrated viewer resumes its pose
        trajectory against a cold cache: at most one sort-window of sharing
        staleness, never a wrong image."""
        scene_i = int(self._scene_of[slot])
        priv_lane = jax.tree.map(jnp.asarray, payload['priv'])
        if payload.get('shared') is not None:
            if self.viewers_per_scene != 1:
                raise ValueError('scene-carry restore needs a private '
                                 'scene block (viewers_per_scene == 1)')
            shared_lane = jax.tree.map(jnp.asarray, payload['shared'])
            self.shared, self.priv = self._admit_scene(
                self.shared, self.priv, shared_lane, priv_lane,
                jnp.int32(scene_i), jnp.int32(slot))
            rows = payload['pool_rows']
            self._pool_cell[scene_i] = np.asarray(rows['pool_cell'],
                                                  np.int64)
            self._pool_tick[scene_i] = np.asarray(rows['pool_tick'],
                                                  np.int64)
            self._pool_owner[scene_i] = np.asarray(rows['pool_owner'],
                                                   np.int64)
            self._slot_pool[slot] = int(rows['slot_pool'])
            self._refs[scene_i] = np.asarray(rows['refs'], np.int64)
            self._frames_since_due[slot] = int(payload['frames_since_due'])
            if payload['pending_sort']:
                self._pending_sort.add(slot)
            else:
                self._pending_sort.discard(slot)
        else:
            self.admit(slot)
            self.priv = self._admit_priv(self.priv, priv_lane,
                                         jnp.int32(slot))
        self._slot_cams[slot] = jax.tree.map(jnp.asarray, payload['cam'])


class SequentialStepper:
    """Reference engine: one single-viewer jitted step per active slot,
    per-viewer sort cadence (``frame_idx % window``), fully private state
    (each slot carries its own scene: cache + pool-of-one)."""

    viewers_per_scene = 1

    def __init__(self, scene: GaussianScene, cfg: LuminaConfig,
                 cam0: Camera, slots: int):
        self.scene = scene
        self.cfg = cfg
        self.slots = slots
        self._fresh = init_viewer_state(scene, cfg, cam0)
        # Per-slot copies: the step donates its state, so slots must never
        # share buffers with each other or with the cold-start template.
        self._states: list[ViewerState] = [copy_pytree(self._fresh)
                                           for _ in range(slots)]
        self._step = jax.jit(functools.partial(render_step, cfg=cfg),
                             donate_argnums=(1,))
        self.tracer = obs_trace.NULL
        self.metrics = obs_metrics.Registry()
        self.sort_log: list[dict] = []
        self.last_timing: TickTiming | None = None
        self._last_active = 0
        self._pool_entry_bytes = pytree_nbytes(self._fresh.scene_shared.pool)
        self._cache_bytes = pytree_nbytes(self._fresh.scene_shared.cache)

    def admit(self, slot: int) -> None:
        self._states[slot] = copy_pytree(self._fresh)

    def release(self, slot: int) -> None:
        """No dynamic capacity to reclaim on the static engine."""

    def quarantine(self, slot: int) -> None:
        """Containment on the private engine is a full cold-start: every
        piece of the slot's state (cache included) is its own."""
        self.admit(slot)

    def reset(self) -> None:
        """Cold-start every slot (see ``BatchedStepper.reset``)."""
        self._states = [copy_pytree(self._fresh) for _ in range(self.slots)]
        self.sort_log = []
        self.last_timing = None
        self._last_active = 0

    def state_dict(self) -> tuple:
        """``(arrays, meta)`` snapshot (see ``BatchedStepper.state_dict``):
        per-slot ``ViewerState`` pytrees, no host mirrors to carry."""
        return {f'slot{i}': st for i, st in enumerate(self._states)}, {}

    def load_state(self, arrays, meta: dict) -> None:
        del meta
        self._states = [jax.tree.map(jnp.asarray, arrays[f'slot{i}'])
                        for i in range(self.slots)]

    def step_dispatch(self, cams: dict[int, Camera], plan=None):
        """Nothing dispatches ahead on the sequential engine: each slot's
        step blocks for its per-slot latency attribution, so the whole tick
        executes inside ``step_finish``.  The threaded host loop still
        overlaps its planning with that execution (the jitted per-slot
        steps release the GIL) — the uniform protocol at the baseline's
        pipelining depth."""
        del plan
        return cams

    def step_finish(self, cams) -> dict:
        return self.step(cams) if cams else {}

    def step(self, cams: dict[int, Camera], plan=None) -> dict:
        del plan   # host sort planning is a batched-engine concept
        out = {}
        sorts = 0
        t_start = time.perf_counter()
        for slot, cam in cams.items():
            t0 = time.perf_counter()
            self._states[slot], image, stats = self._step(
                self.scene, self._states[slot], cam)
            jax.block_until_ready(image)
            t_done = time.perf_counter()
            dt = t_done - t0
            self.tracer.complete('render_step', t0, t_done, slot=slot)
            sorted_flag = int(float(stats.sorted_this_frame))
            sorts += sorted_flag
            # The monolithic reference step fuses the phases; its whole
            # latency is attributed to shade (sort_ms stays 0) — the split
            # attribution is what the batched engine exists to provide.
            out[slot] = (image, stats,
                         TickTiming(latency_s=dt, sort_ms=0.0,
                                    shade_ms=dt * 1e3,
                                    sorted_slots=sorted_flag))
        self.sort_log.append({'scheduled': sorts, 'admit': 0, 'joined': 0})
        if sorts:
            self.metrics.counter('sort.executed',
                                 'per-viewer cadence sorts').inc(sorts)
        self.last_timing = TickTiming(
            latency_s=time.perf_counter() - t_start, sort_ms=0.0,
            shade_ms=(time.perf_counter() - t_start) * 1e3,
            sorted_slots=sorts)
        self._last_active = len(cams)
        return out

    def state_metrics(self) -> dict:
        """Private-state footprint: every occupied slot holds a full sort
        buffer and a full cache — the O(S) memory the scene-shared engine
        exists to collapse; the engine allocates all ``slots`` copies up
        front (``*_alloc_bytes``).  (No occupancy scan: S separate device
        reductions per tick would tax the baseline's own timing.)"""
        live = self._last_active
        pool_bytes = live * self._pool_entry_bytes
        per_slot = self._pool_entry_bytes + self._cache_bytes
        return {
            'sort_pool_live': live,
            'sort_pool_total': self.slots,
            'sort_pool_bytes': pool_bytes,
            'sort_pool_alloc_bytes': self._pool_entry_bytes * self.slots,
            'sort_pool_reserved_bytes': self._pool_entry_bytes * self.slots,
            'cache_bytes': self._cache_bytes * live,
            'state_bytes': pool_bytes + self._cache_bytes * live,
            'state_alloc_bytes': per_slot * self.slots,
            'state_reserved_bytes': per_slot * self.slots,
        }
