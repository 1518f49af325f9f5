"""Viewer sessions and the slot-based session manager.

The manager mirrors the continuous-batching LM server
(``repro.launch.serve``): a fixed number of slots, a queue of pending
viewers with arrival times, admit-on-free-slot, evict-on-completion.  A
viewer session is a camera trajectory (one camera per frame) plus its
telemetry; slots hold whichever sessions are currently live, and the
stepper advances every live slot one frame per tick.

Scene-centric serving: sessions carry a ``scene_id`` and the manager groups
slots by scene — when the stepper serves ``viewers_per_scene > 1`` slots per
scene block, a session is only admitted into a free slot of *its* scene's
block, so co-scene viewers land on the block whose ``SceneShared`` (radiance
cache + sort pool) they are meant to share.  With one viewer per scene (the
default) scene identity does not constrain placement and admission is plain
FIFO over all free slots, exactly the pre-split behavior.

**Host pipeline**: a tick decomposes into three explicit operations —

  * ``plan_tick``    — pure planning (evictions, admissions, due cameras,
    the stepper's pose-cell sort plan); numpy/python only, safe off-thread;
  * ``apply_plan``   — atomic commit of the plan under the manager lock
    (no observer ever sees a half-admitted tick);
  * ``observe_tick`` — telemetry + cursor advance once device outputs land.

``run_tick`` is their inline composition (identical to the pre-pipeline
synchronous engine); ``run(driver=...)`` hands the sequencing to a driver
from ``repro.serve.events`` — ``'sync'`` (virtual clock, deterministic
replay) or ``'threaded'`` (host planning double-buffered against the
device step).

**Frame pacing**: a session with ``pace = p`` consumes one frame every
``p`` ticks (open-loop clients slower than the tick clock, see
``repro.serve.traffic``); its slot stays occupied on off ticks but renders
nothing.  ``pace = 1`` (the default) is the legacy every-tick behavior.

**Slot oversubscription** (``oversubscribe=True``, shared-scene steppers
only): paced sessions whose render ticks provably never collide — admission
requires ``(tick - admitted_tick_r) % gcd(pace_r, pace_new) != 0`` against
every current resident, which pins the newcomer to a disjoint residue class
forever — interleave in ONE physical slot.  The lane's occupant renders;
co-residents are parked in the stepper's stash (``stash_lane``) and swapped
in on their due ticks (``TickPlan.switches``).  A half-rate pace-2 pair
thus serves two viewers from one slot's worth of device state.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
import warnings
from collections import deque
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.camera import Camera
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import faults as serve_faults
from repro.serve.events import HostTiming, TickPlan, _step_split, get_driver
from repro.serve.telemetry import SessionTelemetry


@dataclasses.dataclass
class ViewerSession:
    """One viewer's camera stream: frames are consumed front-to-back.

    ``scene_id`` names the scene this viewer watches; viewers sharing it are
    eligible to share that scene's radiance cache and speculative sorts.
    ``pace`` is the session's frame interval in ticks (>= 1): a pace-``p``
    viewer renders on ticks ``admitted_tick + k * p`` only.
    """

    sid: int
    cams: list          # list[Camera], one per frame
    arrival_tick: int = 0
    cursor: int = 0
    scene_id: int = 0
    pace: int = 1
    telemetry: Optional[SessionTelemetry] = None

    def __post_init__(self):
        if self.pace < 1:
            raise ValueError(f'session pace must be >= 1, got {self.pace}')
        if self.telemetry is None:
            self.telemetry = SessionTelemetry(sid=self.sid,
                                              arrival_tick=self.arrival_tick)

    @property
    def done(self) -> bool:
        return self.cursor >= len(self.cams)

    def current_cam(self) -> Camera:
        return self.cams[self.cursor]


class SessionManager:
    """Admit/evict viewers over a fixed set of render slots.

    ``stepper`` is any object with the ``admit(slot)`` / ``step({slot: cam})``
    interface of ``repro.serve.stepper``; the manager owns which sessions sit
    in which slots and feeds their per-frame stats into telemetry.  When the
    stepper exposes ``viewers_per_scene > 1``, slots are grouped into scene
    blocks and sessions are placed by ``scene_id`` (see module docstring).

    All session-placement mutations (``apply_plan``/``observe_tick`` and the
    legacy ``admit_ready``/``evict_finished``) hold ``self._lock``;
    ``snapshot()`` reads under the same lock, so concurrent observers (the
    threaded driver's telemetry consumers, tests) always see a consistent
    admission state.
    """

    #: dispatch retry policy for injected/transient device failures
    max_retries = 3
    backoff_s = 0.002
    #: default bound on the threaded driver's completion-queue wait (s)
    default_watchdog_s = 30.0

    def __init__(self, stepper, slots: int, tracer=None,
                 metrics: Optional[obs_metrics.Registry] = None,
                 injector=None, watchdog_s: Optional[float] = None,
                 max_pending: Optional[int] = None,
                 oversubscribe: bool = False):
        self.stepper = stepper
        self.slots = slots
        # Observability (repro.obs): a span tracer (NULL no-op by default)
        # and a typed metrics registry, shared with the stepper so sort
        # scheduling events land in the same trace.
        self.tracer = tracer if tracer is not None else obs_trace.NULL
        self.metrics = metrics if metrics is not None else \
            obs_metrics.Registry()
        stepper.tracer = self.tracer
        stepper.metrics = self.metrics
        # Fault layer (repro.serve.faults): a NULL injector by default —
        # the same seam pattern as the NULL tracer, so the unfaulted hot
        # path is untouched and every conformance test exercises the fault
        # layer disabled.  ``watchdog_s`` bounds the threaded driver's
        # completion wait (``default_watchdog_s`` when unset) and, when set
        # explicitly (or when faults are injected), arms a per-tick finish
        # watchdog timer around ``step_finish``.
        self.injector = injector if injector is not None else \
            serve_faults.NULL
        self.watchdog_s = watchdog_s
        self.max_pending = max_pending
        self.shed: list[ViewerSession] = []
        # crash-consistent checkpointing (wired via enable_checkpoints)
        self._ckpt = None
        self._ckpt_every = 0
        self._ckpt_extra: Optional[dict] = None
        self.viewers_per_scene = getattr(stepper, 'viewers_per_scene', 1)
        self.num_scenes = max(1, slots // self.viewers_per_scene)
        # Slot oversubscription needs the stepper's lane stash AND a shared
        # scene block (a private-mode scene is one pool-of-one per slot —
        # interleaving two viewers through it would thrash the cache the
        # block exists to keep warm).
        self.oversubscribe = bool(
            oversubscribe and hasattr(stepper, 'stash_lane')
            and self.viewers_per_scene > 1)
        if oversubscribe and not self.oversubscribe:
            raise ValueError('oversubscribe requires a shared-scene stepper '
                             '(viewers_per_scene > 1) with a lane stash')
        # stashed co-resident sessions per slot (the lane's occupant stays
        # in slot_session; everyone else parks here + in the stepper stash)
        self._coresidents: dict[int, list[ViewerSession]] = {}
        self.slot_session: list[Optional[ViewerSession]] = [None] * slots
        self.pending: deque[ViewerSession] = deque()
        self.finished: list[ViewerSession] = []
        self.tick = 0
        self._lock = threading.Lock()
        # host planning spent on zero-frame ticks (arrival gaps, paced
        # idle ticks) carries into the next logged entry, so host_ms /
        # host_overlap stay honest for open-loop workloads
        self._carry_host_ms = 0.0
        self._carry_overlap_ms = 0.0
        # Per-tick phase attribution: {'tick', 'frames', 'sorted_slots',
        # 'sort_ms', 'shade_ms', 'latency_ms', 'host_ms', 'overlap_ms'} per
        # rendered tick (empty ticks are skipped), plus the stepper's state
        # metrics (cache occupancy, live sort-pool entries, state bytes)
        # when it exposes ``state_metrics()``.
        self.tick_log: list[dict] = []

    # -- lifecycle ---------------------------------------------------------

    def submit(self, session: ViewerSession) -> bool:
        """Queue a session for admission.  Lock-safe against a concurrent
        threaded run: a session submitted mid-run is simply picked up by
        the next tick's plan.

        With ``max_pending`` set, a full backlog load-sheds: the session is
        rejected up front (recorded in ``self.shed`` + the ``serve.shed``
        counter) instead of queueing unboundedly — admission collapse under
        a flash crowd is an explicit, observable decision.  Returns whether
        the session was accepted."""
        with self._lock:
            if self.max_pending is not None \
                    and len(self.pending) >= self.max_pending:
                self.shed.append(session)
                accepted = False
            else:
                self.pending.append(session)
                accepted = True
        if not accepted:
            self.metrics.counter(
                'serve.shed',
                'sessions rejected by the admission backlog bound').inc()
            self.tracer.instant('shed', sid=session.sid,
                                arrival_tick=session.arrival_tick)
            return False
        self.tracer.instant('arrival', sid=session.sid,
                            arrival_tick=session.arrival_tick)
        return True

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slot_session) if s is None]

    def active_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slot_session) if s is not None]

    def resident_count(self) -> int:
        """Sessions currently holding serving state: lane occupants plus
        stashed co-residents.  The fleet's load figure — an oversubscribed
        worker is carrying more viewers than its occupied slot count."""
        return (sum(1 for s in self.slot_session if s is not None)
                + sum(len(v) for v in self._coresidents.values()))

    def _scene_block(self, scene_id: int) -> range:
        """Slot range of a session's scene block (scene ids beyond the
        stepper's scene count wrap — the block is a cache domain, not a
        registry of world scenes)."""
        c = scene_id % self.num_scenes
        v = self.viewers_per_scene
        return range(c * v, (c + 1) * v)

    def _admit_into(self, slot: int, sess: ViewerSession) -> None:
        sess.telemetry.admitted_tick = self.tick
        self.slot_session[slot] = sess
        self.stepper.admit(slot)

    def admit_ready(self) -> list[int]:
        """Admit arrived pending sessions into free slots (FIFO; with scene
        blocks, FIFO per admissible session — a session whose block is full
        waits without blocking later sessions bound for other scenes)."""
        with self._lock:
            return self._admit_ready_locked()

    def _admit_ready_locked(self) -> list[int]:
        admitted = []
        if self.viewers_per_scene == 1:
            for slot in self.free_slots():
                if not self.pending or self.pending[0].arrival_tick > self.tick:
                    break
                self._admit_into(slot, self.pending.popleft())
                admitted.append(slot)
            return admitted
        waiting = deque()
        while self.pending:
            sess = self.pending.popleft()
            if sess.arrival_tick > self.tick:
                waiting.append(sess)
                continue
            free = [i for i in self._scene_block(sess.scene_id)
                    if self.slot_session[i] is None]
            if free:
                self._admit_into(free[0], sess)
                admitted.append(free[0])
            else:
                waiting.append(sess)
        self.pending = waiting
        return admitted

    def vacate(self, slot: int) -> ViewerSession:
        """Remove the session occupying ``slot`` WITHOUT marking it finished
        — the fleet's migration seam (the viewer continues on another
        device).  The slot's device state is left as-is; the next admit
        into it cold-starts it."""
        with self._lock:
            sess = self.slot_session[slot]
            if sess is None:
                raise RuntimeError(f'vacate: slot {slot} is empty')
            if self._coresidents.get(slot):
                raise RuntimeError(f'vacate: slot {slot} has stashed '
                                   'co-residents (drain them first)')
            self.slot_session[slot] = None
            self._release_slot(slot)
            return sess

    def place(self, slot: int, sess: ViewerSession,
              payload: Optional[dict] = None,
              admitted_tick: Optional[int] = None) -> None:
        """Direct placement into a free slot, bypassing the FIFO queue —
        the fleet's migration / device-loss recovery seam.  With
        ``payload`` the stepper restores an extracted viewer lane
        (warm scene-carry or cold, per the payload — see
        ``BatchedStepper.extract_viewer``); without one, a plain cold
        admit.  ``admitted_tick`` preserves the original admission tick so
        a paced session keeps its frame cadence across the move (defaults
        to the current tick, matching a fresh admit)."""
        with self._lock:
            occupant = self.slot_session[slot]
            if occupant is not None:
                raise RuntimeError(f'place: slot {slot} occupied by sid '
                                   f'{occupant.sid}')
            sess.telemetry.admitted_tick = (
                self.tick if admitted_tick is None else int(admitted_tick))
            self.slot_session[slot] = sess
            if payload is None:
                self.stepper.admit(slot)
            else:
                self.stepper.restore_viewer(slot, payload)

    def evict_finished(self) -> list[int]:
        with self._lock:
            return self._evict_finished_locked()

    def _release_slot(self, slot: int) -> None:
        """Tell the stepper the slot no longer hosts a viewer, so a dynamic
        pool can stop protecting (and eventually reclaim) its sort entry."""
        release = getattr(self.stepper, 'release', None)
        if release is not None:
            release(slot)

    def _evict_finished_locked(self) -> list[int]:
        evicted = []
        for slot, sess in enumerate(self.slot_session):
            if sess is not None and sess.done:
                co = self._coresidents.get(slot)
                if co:
                    # promote a stashed co-resident instead of freeing the
                    # slot (cursors only advance while active, so stashed
                    # sessions are never done themselves)
                    succ = min(co, key=lambda c: c.telemetry.admitted_tick)
                    co.remove(succ)
                    sess.telemetry.finished_tick = self.tick
                    self.finished.append(sess)
                    self.slot_session[slot] = succ
                    self.stepper.unstash_lane(slot, str(succ.sid))
                    evicted.append(slot)
                    continue
                sess.telemetry.finished_tick = self.tick
                self.finished.append(sess)
                self.slot_session[slot] = None
                self._release_slot(slot)
                evicted.append(slot)
        return evicted

    # -- the host pipeline: plan / apply / observe -------------------------

    def _frame_due(self, sess: ViewerSession, tick: int) -> bool:
        """Does this (already-admitted) session consume a frame on
        ``tick``?  Paced sessions render every ``pace`` ticks counted from
        admission; sessions admitted this very tick don't come through
        here — ``plan_tick`` assigns their first frame directly."""
        return (tick - sess.telemetry.admitted_tick) % sess.pace == 0

    def plan_tick(self, tick: Optional[int] = None,
                  advanced=()) -> TickPlan:
        """Compute the next tick's host decisions without mutating anything.

        ``advanced`` names the slots of an in-flight, not-yet-observed tick:
        their sessions are treated as one frame further along (the threaded
        driver's double-buffer adjustment — eviction/camera choices for tick
        ``t+1`` are a pure function of tick ``t``'s inputs, never its device
        outputs).  With no tick in flight (the sync path) it is empty and
        this reads the literal manager state.

        The returned plan also carries the stepper's pose-cell sort plan
        (``plan_step``) when the stepper has a host planning phase, computed
        against the post-admission active set — the piece of per-tick host
        work the async pipeline exists to overlap.
        """
        tick = self.tick if tick is None else tick
        if self.injector.enabled \
                and self.injector.take('plan_exc', tick) is not None:
            # injected BEFORE any planning work: plan_tick is pure, so the
            # recovery replan (inline, degraded) sees identical inputs
            raise serve_faults.InjectedPlanError(
                f'injected plan_tick fault at tick {tick}')
        with self.tracer.span('plan_tick', tick=tick):
            return self._plan_tick(tick, advanced)

    def _plan_tick(self, tick: int, advanced=()) -> TickPlan:
        adv = frozenset(advanced)

        def cursor_of(slot: int, sess: ViewerSession) -> int:
            # the in-flight frame (if any) belongs to the slot's current
            # lane occupant; stashed co-residents never render in flight,
            # so their cursors read literally
            return sess.cursor + (1 if slot in adv else 0)

        cor_slots = {slot for slot, lst in self._coresidents.items() if lst}
        evict = tuple(
            slot for slot, sess in enumerate(self.slot_session)
            if sess is not None and slot not in cor_slots
            and cursor_of(slot, sess) >= len(sess.cams))
        free = sorted(set(self.free_slots()) | set(evict))
        placements = self._plan_admissions(free, tick)
        admit = tuple((slot, sess.sid) for slot, sess in placements)
        admitted_slots = {slot for slot, _ in admit}

        # Oversubscribed lanes: at most one resident (occupant or stashed
        # co-resident) is due per tick — the admission-time residue check
        # guarantees it.  A due co-resident swaps in; a finished occupant
        # retires into the swap (its lane needs no stashing).
        cams: dict[int, Camera] = {}
        switches = []
        for slot in sorted(cor_slots):
            sess = self.slot_session[slot]
            occupant_done = cursor_of(slot, sess) >= len(sess.cams)
            due_co = [c for c in self._coresidents[slot] if not c.done
                      and (tick - c.telemetry.admitted_tick) % c.pace == 0]
            if due_co:
                inc = due_co[0]
                switches.append((slot, inc.sid))
                cams[slot] = inc.cams[inc.cursor]
            elif occupant_done:
                inc = min(self._coresidents[slot],
                          key=lambda c: c.telemetry.admitted_tick)
                switches.append((slot, inc.sid))
            elif self._frame_due(sess, tick):
                cams[slot] = sess.cams[cursor_of(slot, sess)]

        for slot, sess in enumerate(self.slot_session):
            if sess is None or slot in evict or slot in admitted_slots \
                    or slot in cor_slots:
                continue
            if self._frame_due(sess, tick):
                cams[slot] = sess.cams[cursor_of(slot, sess)]
        for slot, sess in placements:
            cams[slot] = sess.cams[0]

        sort_plan = None
        plan_step = getattr(self.stepper, 'plan_step', None)
        if plan_step is not None:
            if switches:
                sort_plan = plan_step(
                    cams, pending_admits=admitted_slots,
                    lane_swaps={slot: str(sid) for slot, sid in switches})
            else:
                sort_plan = plan_step(cams, pending_admits=admitted_slots)
        return TickPlan(tick=tick, evict=evict, admit=admit, cams=cams,
                        sort_plan=sort_plan, switches=tuple(switches))

    def _plan_admissions(self, free: list, tick: int) -> list:
        """Pure mirror of ``admit_ready`` over a hypothetical free-slot list:
        returns ``(slot, session)`` placements in pending-queue order
        without popping anything.  The pending snapshot is taken under the
        lock (this runs on the planner worker; ``submit`` may race), and in
        FIFO mode only the first ``len(free)`` entries are materialized —
        a deep open-loop backlog must not cost O(queue) host work per tick.
        """
        with self._lock:
            if self.viewers_per_scene == 1:
                pending = list(itertools.islice(self.pending, len(free)))
            else:
                pending = list(self.pending)
        placements = []
        if self.viewers_per_scene == 1:
            k = 0
            for slot in free:
                if k >= len(pending) or pending[k].arrival_tick > tick:
                    break
                placements.append((slot, pending[k]))
                k += 1
            return placements
        remaining = set(free)
        co_placed: set[int] = set()
        for sess in pending:
            if sess.arrival_tick > tick:
                continue
            block = [i for i in self._scene_block(sess.scene_id)
                     if i in remaining]
            if block:
                placements.append((block[0], sess))
                remaining.discard(block[0])
                continue
            if not self.oversubscribe or sess.pace < 2:
                continue
            # Block full: co-place onto an occupied slot whose residents'
            # render ticks are residue-disjoint from the newcomer's.  The
            # newcomer renders on ticks ≡ tick (mod pace); resident r on
            # ticks ≡ admitted_r (mod pace_r) — they never collide iff
            # tick ≢ admitted_r (mod gcd(pace_r, pace)), and that residue
            # relation is permanent, so one admission-time check covers
            # the whole co-residency.  One co-placement per slot per tick
            # (two same-tick admits would share a residue by definition).
            for slot in self._scene_block(sess.scene_id):
                occ = self.slot_session[slot]
                if occ is None or slot in co_placed or slot in remaining:
                    continue
                residents = [occ] + self._coresidents.get(slot, [])
                if any(r.pace < 2 for r in residents):
                    continue
                if all((tick - r.telemetry.admitted_tick)
                       % math.gcd(r.pace, sess.pace) != 0
                       for r in residents):
                    placements.append((slot, sess))
                    co_placed.add(slot)
                    break
        return placements

    def apply_plan(self, plan: TickPlan) -> None:
        """Atomically commit a plan's evictions and admissions.  Holding the
        lock across the whole commit is the no-partial-admission guarantee:
        a session is either fully pending or fully admitted (placed, stepper
        slot reset, ``admitted_tick`` stamped) in any concurrent view."""
        with self.tracer.span('apply_plan', tick=plan.tick,
                              admits=len(plan.admit),
                              evicts=len(plan.evict)), self._lock:
            if plan.tick != self.tick:
                raise RuntimeError(f'stale plan: tick {plan.tick} applied at '
                                   f'manager tick {self.tick}')
            retired = 0
            for slot in plan.evict:
                sess = self.slot_session[slot]
                if sess is None or not sess.done:
                    raise RuntimeError(f'plan evicts slot {slot} whose '
                                       f'session is not finished')
                sess.telemetry.finished_tick = plan.tick
                self.finished.append(sess)
                self.slot_session[slot] = None
                self._release_slot(slot)
                self.tracer.instant('evict', slot=slot, sid=sess.sid,
                                    tick=plan.tick)
            for slot, sid in getattr(plan, 'switches', ()):
                sess = self.slot_session[slot]
                co = self._coresidents.get(slot, [])
                inc = next((c for c in co if c.sid == sid), None)
                if inc is None:
                    raise RuntimeError(f'planned switch-in {sid} is not a '
                                       f'co-resident of slot {slot}')
                co.remove(inc)
                if sess.done:
                    # the outgoing occupant retires through the swap — its
                    # lane state needs no stashing
                    sess.telemetry.finished_tick = plan.tick
                    self.finished.append(sess)
                    retired += 1
                    self.tracer.instant('evict', slot=slot, sid=sess.sid,
                                        tick=plan.tick)
                else:
                    self.stepper.stash_lane(slot, str(sess.sid))
                    co.append(sess)
                self.slot_session[slot] = inc
                self.stepper.unstash_lane(slot, str(inc.sid))
                self.tracer.instant('switch', slot=slot, sid=inc.sid,
                                    tick=plan.tick)
            self.metrics.counter(
                'serve.evicted', 'sessions leaving their slot').inc(
                    len(plan.evict) + retired)
            for slot, sid in plan.admit:
                occupant = self.slot_session[slot]
                sess = next((s for s in self.pending if s.sid == sid), None)
                if sess is None:
                    raise RuntimeError(f'planned session {sid} not pending')
                if occupant is not None:
                    if not self.oversubscribe:
                        raise RuntimeError(f'plan admits into occupied slot '
                                           f'{slot}')
                    # co-placement: park the lane's occupant, cold-start the
                    # newcomer into the lane (the scene cache persists — the
                    # sharing the block exists for)
                    self.stepper.stash_lane(slot, str(occupant.sid))
                    self._coresidents.setdefault(slot, []).append(occupant)
                    self.metrics.counter(
                        'serve.oversubscribed',
                        'sessions co-placed onto an occupied slot').inc()
                self.pending.remove(sess)
                self._admit_into(slot, sess)
                self.tracer.instant('admit', slot=slot, sid=sid,
                                    tick=plan.tick)
            self.metrics.counter(
                'serve.admitted', 'sessions placed into a slot').inc(
                    len(plan.admit))
            self.metrics.gauge(
                'serve.queue_depth', 'pending sessions after admission').set(
                    len(self.pending))

    def observe_tick(self, plan: TickPlan, outputs: dict,
                     host: Optional[HostTiming] = None) -> int:
        """Record a completed tick: per-frame telemetry, cursor advance, the
        tick log entry (mirrored into the metrics registry's ``tick.*``
        series), and the clock advance to ``plan.tick + 1``."""
        with self.tracer.span('observe_tick', tick=plan.tick,
                              frames=len(outputs)), self._lock:
            # the frames' counters, read from the device
            with self.tracer.span('fetch', tick=plan.tick):
                fetched = {slot: (float(stats.hit_rate),
                                  float(stats.saved_frac),
                                  float(stats.sorted_this_frame))
                           for slot, (_image, stats, _t) in outputs.items()}
            for slot, (_image, _stats, timing) in outputs.items():
                sess = self.slot_session[slot]
                hit_rate, saved_frac, sorted_flag = fetched[slot]
                sess.telemetry.observe_frame(
                    latency_s=timing.latency_s,
                    hit_rate=hit_rate,
                    saved_frac=saved_frac,
                    sorted_flag=sorted_flag,
                    sort_ms=timing.sort_ms,
                    shade_ms=timing.shade_ms)
                sess.cursor += 1
                self.metrics.histogram(
                    'cache.hit_rate', 'per-frame RC hit rate',
                    scene=sess.scene_id).observe(hit_rate)
                self.metrics.histogram(
                    'rc.saved_frac', 'integration skipped via RC',
                    scene=sess.scene_id).observe(saved_frac)
            # paced-idle accounting: resident sessions that rendered nothing
            # this tick (pace gaps; a done session awaiting eviction also
            # counts — its slot is held either way).  Stashed co-residents
            # are idle residents too: oversubscription converts their idle
            # slot-ticks into another viewer's frames, and this counter is
            # the denominator that shows it.
            idle = (sum(1 for s in self.slot_session if s is not None)
                    + sum(len(v) for v in self._coresidents.values())
                    - len(outputs))
            if idle > 0:
                self.metrics.counter(
                    'serve.paced_idle',
                    'occupied slot-ticks that rendered no frame').inc(idle)
                self.tracer.instant('pace', tick=plan.tick, idle_slots=idle)
            self.metrics.counter('serve.frames',
                                 'frames rendered').inc(len(outputs))
            if outputs:
                tick_timing = self.stepper.last_timing
                entry = {
                    'tick': plan.tick,
                    'frames': len(outputs),
                    'sorted_slots': tick_timing.sorted_slots,
                    'sort_ms': tick_timing.sort_ms,
                    'shade_ms': tick_timing.shade_ms,
                    'latency_ms': tick_timing.latency_s * 1e3,
                    'host_ms': self._carry_host_ms
                               + (host.host_ms if host else 0.0),
                    'overlap_ms': self._carry_overlap_ms
                                  + (host.overlap_ms if host else 0.0),
                }
                self._carry_host_ms = self._carry_overlap_ms = 0.0
                # one shade run a tick: any frame's stats carry its insert
                # counter (device scalars, not synced here, as occupancy)
                _image, stats, _t = next(iter(outputs.values()))
                if stats.insert_branch is not None:
                    entry['insert_pending'] = stats.insert_pending
                    entry['insert_branch'] = stats.insert_branch
                metrics = getattr(self.stepper, 'state_metrics', None)
                if metrics is not None:
                    entry.update(metrics())
                self.tick_log.append(entry)
                obs_metrics.publish_tick(self.metrics, entry)
                self.metrics.histogram(
                    'serve.tick_latency_ms',
                    'wall latency of rendered ticks').observe(
                        entry['latency_ms'])
            elif host is not None:
                self._carry_host_ms += host.host_ms
                self._carry_overlap_ms += host.overlap_ms
            self.tick = plan.tick + 1
            return len(outputs)

    def snapshot(self) -> dict:
        """A consistent view of session placement for concurrent observers:
        pending sids, ``(slot, sid, admitted_tick)`` for occupied slots,
        finished sids, and the tick — all read under the manager lock."""
        with self._lock:
            return {
                'tick': self.tick,
                'pending': tuple(s.sid for s in self.pending),
                'slotted': tuple(
                    (slot, s.sid, s.telemetry.admitted_tick)
                    for slot, s in enumerate(self.slot_session)
                    if s is not None),
                'finished': tuple(s.sid for s in self.finished),
            }

    # -- fault handling (shared by both drivers) ---------------------------
    #
    # Each helper reduces exactly to the pre-hardening path under the NULL
    # injector: one attribute test, no wrapping, no extra work — so the
    # unfaulted golden traces stay bit-identical with the fault layer
    # present but disabled.

    def count_fault(self, kind: str, tick: int) -> None:
        """One observed fault event (injected or real-but-contained)."""
        self.metrics.counter('serve.faults',
                             'fault events observed by the host loop',
                             kind=kind).inc()
        self.tracer.instant('fault', kind=kind, tick=tick)

    def count_degraded(self, tick: int) -> None:
        """One tick the host loop fell back from its pipelined fast path
        (inline replan, shed dispatch, worker restart)."""
        self.metrics.counter(
            'serve.degraded_ticks',
            'ticks served in degraded (inline/shed) mode').inc()
        self.tracer.instant('degraded', tick=tick)

    def plan_tick_hardened(self, tick: Optional[int] = None,
                           advanced=()) -> TickPlan:
        """``plan_tick`` surviving an injected planner exception: the fault
        fires before any planning work and planning is pure, so the inline
        retry sees identical inputs (the sync-driver arm of the recovery
        the threaded driver gets from its worker-error fallback)."""
        try:
            return self.plan_tick(tick, advanced)
        except serve_faults.InjectedPlanError:
            t = self.tick if tick is None else tick
            self.count_fault('plan_exc', t)
            self.count_degraded(t)
            return self.plan_tick(tick, advanced)

    def poison_outputs(self, outputs: dict, tick: int) -> dict:
        """Apply a pending ``nan_poison`` event: one slot's finished shade
        output is replaced with NaNs — the corrupted-device-result scenario
        (a NaN camera demonstrably does NOT reproduce it: non-finite pose
        comparisons all fail, nothing rasterizes, and the image comes back
        finite background).  Injection happens here, *detection* is
        ``contain_outputs``'s independent finite scan — the containment
        path never peeks at the injector's choice.  The scene cache is
        threatened separately: ``insert_all_groups`` carries the
        ``jnp.isfinite`` gate that keeps non-finite rgb out of
        ``SceneShared`` no matter how the corruption arose.  With no output
        this tick the event stays armed.  Returns the (possibly
        substituted) outputs dict."""
        inj = self.injector
        if not inj.enabled or not outputs \
                or not inj.peek('nan_poison', tick):
            return outputs
        ev = inj.take('nan_poison', tick)
        slot = inj.poison_slot(ev, sorted(outputs))
        self.count_fault('nan_poison', tick)
        self.tracer.instant('poison', slot=slot, tick=tick)
        img, stats, timing = outputs[slot]
        outputs = dict(outputs)
        outputs[slot] = (jnp.full_like(img, jnp.nan), stats, timing)
        return outputs

    def dispatch_hardened(self, dispatch, cams: dict, plan: TickPlan):
        """Dispatch with retry-with-backoff.  Injected dispatch faults fire
        *before* the real dispatch mutates any host state or donates any
        buffer, so re-attempting is trivially safe.  A transient event
        costs ``count`` backed-off retries and then succeeds; a persistent
        event exhausts the retry budget and **sheds the tick** — returns
        ``(None, False)``, no cursor advances, and every due frame is
        replanned next tick (by which time the one-shot event is consumed).
        """
        inj = self.injector
        if not inj.enabled:
            return dispatch(cams, plan=plan.sort_plan), True
        retries = self.metrics.counter('serve.retries',
                                       'dispatch retry attempts')
        ev = inj.take('dispatch_persistent', plan.tick)
        if ev is not None:
            self.count_fault('dispatch_persistent', plan.tick)
            with self.tracer.span('dispatch_retry', tick=plan.tick,
                                  outcome='shed'):
                for attempt in range(self.max_retries):
                    retries.inc()
                    time.sleep(self.backoff_s * (2 ** attempt))
            self.count_degraded(plan.tick)
            self.tracer.instant('tick_shed', tick=plan.tick,
                                frames=len(cams))
            return None, False
        ev = inj.take('dispatch_transient', plan.tick)
        if ev is not None:
            self.count_fault('dispatch_transient', plan.tick)
            with self.tracer.span('dispatch_retry', tick=plan.tick,
                                  outcome='recovered', failures=ev.count):
                for attempt in range(min(ev.count, self.max_retries)):
                    retries.inc()
                    time.sleep(self.backoff_s * (2 ** attempt))
        return dispatch(cams, plan=plan.sort_plan), True

    def finish_hardened(self, finish, inflight, tick: int) -> dict:
        """``step_finish`` under a stall watchdog.  An injected ``stall``
        delays completion inside the watchdog window; a deadline expiry
        (armed when ``watchdog_s`` is set explicitly or faults are being
        injected — never on the plain hot path) emits a ``RuntimeWarning``
        + ``serve.watchdog`` counter but keeps waiting: surfacing a hung
        device is the watchdog's job, abandoning in-flight donated buffers
        would corrupt state."""
        inj = self.injector
        deadline = self.watchdog_s
        if deadline is None and inj.enabled:
            deadline = self.default_watchdog_s
        timer = None
        if deadline is not None:
            def expired():
                self.metrics.counter(
                    'serve.watchdog',
                    'finish/plan watchdog deadline expiries').inc()
                self.tracer.instant('watchdog', what='step_finish',
                                    tick=tick)
                warnings.warn(
                    f'serve watchdog: step_finish exceeded {deadline}s at '
                    f'tick {tick} (device stalled?)', RuntimeWarning,
                    stacklevel=2)
            timer = threading.Timer(deadline, expired)
            timer.daemon = True
            timer.start()
        try:
            ev = inj.take('stall', tick) if inj.enabled else None
            if ev is not None:
                self.count_fault('stall', tick)
                with self.tracer.span('device_stall', tick=tick,
                                      delay_s=ev.delay_s):
                    time.sleep(ev.delay_s)
            return finish(inflight)
        finally:
            if timer is not None:
                timer.cancel()

    def contain_outputs(self, outputs: dict, tick: int) -> tuple:
        """Per-viewer blast-radius containment: any output whose image is
        non-finite is dropped (never reaches telemetry or the viewer — its
        cursor does not advance, the frame retries after recovery) and its
        slot is quarantined (``stepper.quarantine``: private state reset,
        owned pool entry invalidated; the ``jnp.isfinite`` insert gate
        already kept its values out of the scene cache).  Returns
        ``(clean_outputs, poisoned_slots)``.  Only scans when faults are
        being injected — the host must not sync-and-scan every healthy
        frame."""
        if not self.injector.enabled or not outputs:
            return outputs, ()
        poisoned = tuple(
            slot for slot, (img, _stats, _timing) in outputs.items()
            if not bool(np.isfinite(np.asarray(img)).all()))
        if not poisoned:
            return outputs, ()
        quarantine = getattr(self.stepper, 'quarantine', self.stepper.admit)
        for slot in poisoned:
            self.tracer.instant('quarantine', slot=slot, tick=tick)
            quarantine(slot)
        self.metrics.counter(
            'serve.quarantined',
            'poisoned frames dropped and their slots reset').inc(
                len(poisoned))
        clean = {s: o for s, o in outputs.items() if s not in poisoned}
        return clean, poisoned

    def step_hardened(self, plan: TickPlan) -> tuple:
        """The full hardened device leg of one tick (dispatch with retry ->
        finish under watchdog -> poison -> containment), shared by the
        sync driver's ``run_tick`` and usable standalone.  Returns
        ``(outputs, poisoned_slots)``."""
        dispatch, finish = _step_split(self.stepper)
        inflight, ok = self.dispatch_hardened(dispatch, plan.cams, plan)
        if not ok:
            return {}, ()
        outputs = self.finish_hardened(finish, inflight, plan.tick)
        outputs = self.poison_outputs(outputs, plan.tick)
        return self.contain_outputs(outputs, plan.tick)

    # -- crash-consistent checkpoint/restore -------------------------------

    def enable_checkpoints(self, manager, every: int,
                           extra: Optional[dict] = None) -> None:
        """Snapshot serving state through a ``repro.checkpoint``
        ``CheckpointManager`` every ``every`` ticks (``maybe_checkpoint`` is
        called by both drivers at each tick boundary).  ``extra`` is
        JSON-able context stored alongside (e.g. the traffic trace), so a
        snapshot is self-describing for the multi-device migration path."""
        self._ckpt = manager
        self._ckpt_every = int(every)
        self._ckpt_extra = extra

    def maybe_checkpoint(self) -> bool:
        if self._ckpt is None or self._ckpt_every <= 0:
            return False
        if self.tick == 0 or self.tick % self._ckpt_every:
            return False
        self.checkpoint_now()
        return True

    def checkpoint_now(self, blocking: bool = False) -> None:
        """Snapshot at the current tick boundary.  Must run with no tick in
        flight: the stepper's buffers are donated into the next dispatch,
        and ``CheckpointManager.save`` device_gets them synchronously before
        returning — after that the background serialization races nothing.
        (The threaded driver's concurrent ``plan_tick`` only *reads* host
        state, so planning t+1 may overlap the snapshot safely.)"""
        with self.tracer.span('checkpoint', tick=self.tick):
            arrays, stepper_meta = self.stepper.state_dict()
            with self._lock:
                meta = {
                    'tick': self.tick,
                    'stepper': stepper_meta,
                    'slots': [
                        None if s is None else {
                            'sid': s.sid, 'cursor': s.cursor,
                            'admitted_tick': s.telemetry.admitted_tick}
                        for s in self.slot_session],
                    'coresidents': {
                        str(slot): [{'sid': c.sid, 'cursor': c.cursor,
                                     'admitted_tick':
                                         c.telemetry.admitted_tick}
                                    for c in lst]
                        for slot, lst in self._coresidents.items() if lst},
                    'pending': [s.sid for s in self.pending],
                    'finished': [s.sid for s in self.finished],
                    'shed': [s.sid for s in self.shed],
                }
            if self._ckpt_extra:
                meta['extra'] = self._ckpt_extra
            self._ckpt.save(arrays, step=self.tick, extra=meta,
                            blocking=blocking)

    def restore_serving(self, ckpt, sessions,
                        max_step: Optional[int] = None) -> Optional[int]:
        """Restore the newest complete checkpoint into this manager.

        ``sessions`` must be the same session list (sids + trajectories)
        the checkpointed run was built from — the snapshot stores cursors
        and placement, not camera data.  Stepper state, host scheduler
        mirrors, per-slot placement, pending order and the manager tick all
        restore; a subsequent run continues bit-identically to the
        uninterrupted one (the kill-and-restore oracle in
        ``tests/test_chaos.py``).  Returns the restored tick, or None when
        no usable checkpoint exists (caller falls back to a fresh run).

        The shape template is built per checkpoint step: a snapshot's pool
        capacity (and stash population) is part of its geometry, so the
        manifest's ``extra`` is peeked first and handed to the stepper's
        ``state_template`` — a freshly constructed stepper's own
        ``state_dict`` only matches snapshots taken at its initial
        capacity.

        ``max_step`` caps the restore at a given checkpoint step — the
        fleet restores every worker to its newest *common* step so a kill
        landing mid-save on one device cannot leave the workers on
        different ticks."""
        out = self._restore_arrays(ckpt, max_step=max_step)
        if out is None:
            return None
        arrays, step, meta = out
        self.stepper.load_state(arrays, meta['stepper'])
        by_sid = {s.sid: s for s in sessions}
        with self._lock:
            self.tick = int(meta['tick'])
            self.slot_session = []
            for m in meta['slots']:
                if m is None:
                    self.slot_session.append(None)
                    continue
                sess = by_sid.pop(m['sid'])
                sess.cursor = int(m['cursor'])
                sess.telemetry.admitted_tick = int(m['admitted_tick'])
                self.slot_session.append(sess)
            self._coresidents = {}
            for slot_s, lst in meta.get('coresidents', {}).items():
                co = []
                for m in lst:
                    sess = by_sid.pop(m['sid'])
                    sess.cursor = int(m['cursor'])
                    sess.telemetry.admitted_tick = int(m['admitted_tick'])
                    co.append(sess)
                self._coresidents[int(slot_s)] = co
            self.finished = []
            for sid in meta['finished']:
                sess = by_sid.pop(sid)
                sess.cursor = len(sess.cams)
                self.finished.append(sess)
            self.shed = [by_sid.pop(sid) for sid in meta.get('shed', ())]
            self.pending = deque(by_sid.pop(sid)
                                 for sid in meta['pending'])
        self.tracer.instant('restore', tick=self.tick, step=step)
        self.metrics.counter('serve.restores',
                             'runs resumed from a checkpoint').inc()
        return int(step)

    def _restore_arrays(self, ckpt, max_step=None) -> Optional[tuple]:
        """Newest loadable checkpoint as ``(arrays, step, meta)``, building
        the shape template per step from the manifest's stepper geometry.
        ``max_step`` skips snapshots newer than the given step (fleet
        common-step restore).  Falls back to the plain ``restore_latest``
        protocol for steppers without ``state_template`` (or checkpoint
        stores without manifest peeking), and one step back on any
        unreadable snapshot — the same fallback ladder
        ``CheckpointManager.restore_latest`` walks."""
        state_template = getattr(self.stepper, 'state_template', None)
        manifest_extra = getattr(ckpt, 'manifest_extra', None)
        if state_template is None or manifest_extra is None:
            if max_step is not None:
                raise ValueError('max_step needs the manifest-template '
                                 'restore path')
            template, _ = self.stepper.state_dict()
            return ckpt.restore_latest(template)
        from repro.checkpoint.manager import load_checkpoint
        ckpt.wait()
        steps = [s for s in ckpt.all_steps()
                 if max_step is None or s <= max_step]
        for step in reversed(steps):
            try:
                extra = manifest_extra(step)
                if extra is None:
                    raise ValueError('manifest unreadable')
                template = state_template(extra.get('stepper', {}))
                arrays, meta = load_checkpoint(ckpt.dir, template, step=step)
                return arrays, step, meta
            except Exception as e:   # corrupt / partial: fall back one step
                ckpt.metrics.counter(
                    'ckpt.restore_fallback',
                    'checkpoints skipped as unreadable at restore').inc()
                warnings.warn(f'checkpoint step {step} unreadable ({e}); '
                              'falling back to previous',
                              RuntimeWarning, stacklevel=2)
        return None

    # -- the serving loop --------------------------------------------------

    def run_tick(self) -> int:
        """One scheduler tick: evict, admit, render every due slot one frame
        (plan -> apply -> step -> observe, inline).

        Returns the number of frames rendered this tick.

        The device leg runs through the hardened helpers (poison/retry/
        watchdog/containment) — each a no-op reducing to the pre-hardening
        ``stepper.step`` composition under the NULL injector.
        """
        with self.tracer.span('tick', tick=self.tick):
            t0 = time.perf_counter()
            plan = self.plan_tick_hardened()
            host = HostTiming(host_ms=(time.perf_counter() - t0) * 1e3)
            self.apply_plan(plan)
            outputs, _poisoned = self.step_hardened(plan)
            return self.observe_tick(plan, outputs, host=host)

    def drained(self) -> bool:
        return not self.pending and not self.active_slots()

    def run(self, max_ticks: int = 100_000,
            driver: str = 'sync') -> list[ViewerSession]:
        """Drive ticks until every submitted session has completed.

        ``driver='sync'`` is the virtual-clock host loop (deterministic,
        bit-identical replay); ``driver='threaded'`` double-buffers host
        planning against the device step (``repro.serve.events``).
        """
        return get_driver(driver, self).run(max_ticks)
