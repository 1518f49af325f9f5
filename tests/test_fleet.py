"""Elastic multi-device serving fleet (``repro.serve.fleet``).

The contracts under test:

* the placement planners (``plan_route`` / ``plan_rebalance`` /
  ``plan_shrink``) are pure and deterministic, conserve viewers, are no-ops
  when already balanced, and never place anything on a dead device;
* ``ThreadedFleetDriver`` is **bit-identical** to the virtual N-device
  ``SyncFleetDriver`` oracle — same per-frame images, same routing, same
  final clock — on both shade backends;
* a slot-aligned live migration carries the viewer's whole scene lane and
  continues bit-identically to never having moved (the lockstep
  ``global_tick`` clock is what makes this hold across idle ticks);
  unaligned moves restore cold (frames conserved, at most one sort-window
  of sharing staleness — the fresh-admission bound);
* ``device_loss`` with checkpointing rolls the whole fleet back to its
  last crash-consistent snapshot: survivors and slot-aligned victims
  replay bit-identically vs the unfaulted golden run, spilled victims
  re-queue at their snapshot cursor, **zero viewers are dropped** and
  replayed frames are not double-counted;
* without checkpoints the recovery is cold: victims re-queue at their
  current cursor and no delivered frame is ever re-rendered;
* under degraded capacity the bounded fleet queue sheds *new* arrivals
  (recorded + counted) while every accepted viewer still drains.

The straggler cold-start contract (single host never self-flags,
first-observation EWMA seeding, metrics mirror) rides along — the fleet's
threaded driver is its second consumer.
"""
import dataclasses
import hashlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.manager import CheckpointManager
from repro.core.pipeline import LuminaConfig
from repro.data.trajectory import orbit_trajectory
from repro.obs import metrics as obs_metrics
from repro.runtime.straggler import StragglerDetector
from repro.serve import faults, fleet
from repro.serve.session import SessionManager, ViewerSession
from repro.serve.stepper import BatchedStepper

CFG = LuminaConfig(capacity=192, window=3)


def _digest(arr) -> str:
    return hashlib.sha256(np.asarray(arr).tobytes()).hexdigest()


def _sessions(frames=(3, 3, 3), arrivals=None, paces=None):
    arrivals = arrivals if arrivals is not None else (0,) * len(frames)
    out = []
    for sid, (n, arr) in enumerate(zip(frames, arrivals)):
        cams = orbit_trajectory(n, width=64, height_px=64,
                                start_deg=60.0 * sid)
        out.append(ViewerSession(sid=sid, cams=cams, arrival_tick=arr,
                                 pace=paces[sid] if paces else 1))
    return out


class FleetRecorder:
    """Stepper wrapper digesting every rendered frame, keyed by
    ``(sid, frame_idx)`` — the key survives migration, rollback and
    re-admission, so continuations compare against a golden run per
    *viewer frame* rather than per slot.  Repeated digests under one key
    are at-least-once replay (rollback recovery re-renders them).

    Setattr passes through to the wrapped stepper: the fleet's lockstep
    clause assigns ``stepper.global_tick`` and the manager assigns
    ``tracer``/``metrics`` — shadowing those on the wrapper would silently
    break the real stepper's cadence clock."""

    _OWN = ('_s', 'mgr', 'frames')

    def __init__(self, stepper):
        object.__setattr__(self, '_s', stepper)
        object.__setattr__(self, 'mgr', None)
        object.__setattr__(self, 'frames', {})

    def __getattr__(self, name):
        return getattr(self._s, name)

    def __setattr__(self, name, value):
        if name in self._OWN:
            object.__setattr__(self, name, value)
        else:
            setattr(self._s, name, value)

    def _record(self, out):
        for slot, (img, _st, _t) in out.items():
            sess = self.mgr.slot_session[slot]
            if sess is not None:
                self.frames.setdefault((sess.sid, sess.cursor),
                                       []).append(_digest(img))
        return out

    def step(self, cams, plan=None):
        return self._record(self._s.step(cams, plan=plan))

    def step_dispatch(self, cams, plan=None):
        return self._s.step_dispatch(cams, plan)

    def step_finish(self, infl):
        return self._record(self._s.step_finish(infl))


def _make_fleet(steppers, *, ckpt_root=None, ckpt_every=0, injector=None,
                max_pending=None):
    """Fleet over module-shared compiled steppers (reset between runs —
    recompiling one stepper per device per test would dominate the
    suite), each wrapped in a digest recorder."""
    dev = None
    workers = []
    for d, stp in enumerate(steppers):
        stp.reset()
        rec = FleetRecorder(stp)
        mgr = SessionManager(rec, slots=stp.slots,
                             metrics=obs_metrics.Registry())
        rec.mgr = mgr
        ckpt = None
        if ckpt_root is not None and ckpt_every > 0:
            ckpt = CheckpointManager(ckpt_root / f'device{d}',
                                     metrics=mgr.metrics)
            mgr.enable_checkpoints(ckpt, ckpt_every)
        workers.append(fleet.FleetWorker(d, dev, mgr, ckpt))
    return fleet.FleetManager(workers, injector=injector,
                              max_pending=max_pending)


def _frames_of(fm):
    merged = {}
    for w in fm.workers:
        for key, digs in w.mgr.stepper.frames.items():
            merged.setdefault(key, []).extend(digs)
    return merged


def _drain(fm, driver='sync', max_ticks=300, **kw):
    return fleet.get_fleet_driver(driver, fm, **kw).run(max_ticks)


@pytest.fixture(scope='module')
def fleet_steppers(small_scene):
    cam0 = orbit_trajectory(1, width=64, height_px=64)[0]
    return [BatchedStepper(small_scene, CFG, cam0, slots=2)
            for _ in range(2)]


# ---------------------------------------------------------------------------
# Pure placement planners
# ---------------------------------------------------------------------------

def test_plan_route_least_loaded_and_sticky_scene():
    pending = ((10, 0), (11, 1), (12, 0))
    routes = fleet.plan_route(pending, {0: 2, 1: 0}, {0, 1})
    assert routes == ((10, 1), (11, 1), (12, 0))
    # a homed scene keeps attracting its viewers even when loaded...
    routes = fleet.plan_route(pending, {0: 2, 1: 0}, {0, 1},
                              scene_home={0: 0})
    assert routes == ((10, 0), (11, 1), (12, 0))
    # ...unless its home is dead
    routes = fleet.plan_route(pending, {1: 0}, {1}, scene_home={0: 0})
    assert routes == ((10, 1), (11, 1), (12, 1))
    with pytest.raises(ValueError):
        fleet.plan_route(pending, {}, set())


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 5), st.lists(st.integers(0, 6), max_size=12),
       st.integers(0, 4))
def test_plan_route_properties(n_alive, scene_ids, load_seed):
    alive = set(range(n_alive))
    pending = tuple((100 + i, sc) for i, sc in enumerate(scene_ids))
    loads = {d: (d * load_seed) % 3 for d in alive}
    routes = fleet.plan_route(pending, loads, alive)
    # deterministic, conserves sids in order, alive targets only
    assert routes == fleet.plan_route(pending, loads, alive)
    assert [sid for sid, _ in routes] == [sid for sid, _ in pending]
    assert all(d in alive for _, d in routes)
    # least-loaded greedy never widens the spread past max(initial, 1)
    final = dict(loads)
    for _, d in routes:
        final[d] += 1
    spread0 = max(loads.values()) - min(loads.values())
    assert max(final.values()) - min(final.values()) <= max(spread0, 1)


def test_plan_rebalance_noop_when_balanced():
    assignments = {0: (1, 2), 1: (3,), 2: (4, 5)}
    assert fleet.plan_rebalance(assignments, {0, 1, 2}) == ()


def test_plan_rebalance_evacuates_dead_then_levels():
    # device 9 is dead: its queued sids must move first, onto alive devices
    assignments = {0: (1, 2, 3, 4), 1: (), 9: (8,)}
    moves = fleet.plan_rebalance(assignments, {0, 1})
    assert moves[0] == (8, 9, 1)
    assert all(dst in {0, 1} for _, _, dst in moves)
    movable = {0: [1, 2, 3, 4], 1: [8]}
    for sid, src, dst in moves[1:]:
        movable[src].remove(sid)
        movable[dst].append(sid)
    assert abs(len(movable[0]) - len(movable[1])) <= 1


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 4), min_size=1, max_size=5),
       st.integers(0, 3), st.integers(1, 2))
def test_plan_rebalance_properties(sizes, dead_n, slack):
    alive = set(range(len(sizes)))
    dead = len(sizes)
    assignments, sid = {}, 0
    for d, n in enumerate(sizes):
        assignments[d] = tuple(range(sid, sid + n))
        sid += n
    if dead_n:
        assignments[dead] = tuple(range(sid, sid + dead_n))
    moves = fleet.plan_rebalance(assignments, alive, slack=slack)
    assert moves == fleet.plan_rebalance(assignments, alive, slack=slack)
    movable = {d: list(assignments[d]) for d in alive}
    for s, src, dst in moves:
        assert dst in alive
        if src in movable:
            movable[src].remove(s)
        movable[dst].append(s)
    # every dead-device sid evacuated onto an alive device
    placed = {s for d in alive for s in movable[d]}
    assert set(assignments.get(dead, ())) <= placed
    # termination invariant: no device still holding movable load sits more
    # than `slack` above the global minimum
    loads = {d: len(movable[d]) for d in alive}
    cands = [d for d in alive if movable[d]]
    if cands:
        assert max(loads[d] for d in cands) - min(loads.values()) <= slack


def test_plan_shrink_prefers_aligned_slots():
    aligned, spilled = fleet.plan_shrink(
        ((7, 0), (8, 1), (9, 1)), {1: (1,), 2: (0, 1)}, {1, 2})
    assert aligned == ((7, 2, 0), (8, 1, 1), (9, 2, 1))
    assert spilled == ()
    aligned, spilled = fleet.plan_shrink(((7, 0), (8, 0)), {1: (0,)}, {1})
    assert aligned == ((7, 1, 0),)
    assert spilled == (8,)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 3), max_size=6), st.integers(1, 3),
       st.integers(0, 3))
def test_plan_shrink_properties(victim_slots, n_alive, mask):
    victims = tuple((200 + i, s) for i, s in enumerate(victim_slots))
    alive = set(range(n_alive))
    free = {d: tuple(s for s in range(4) if (s + d + mask) % 2)
            for d in alive}
    aligned, spilled = fleet.plan_shrink(victims, free, alive)
    assert (aligned, spilled) == fleet.plan_shrink(victims, free, alive)
    # partition of the victims, aligned strictly onto originally-free
    # same-index slots, each (device, slot) used at most once
    assert sorted([s for s, _, _ in aligned] + list(spilled)) \
        == sorted(s for s, _ in victims)
    by_sid = dict(victims)
    seats = [(d, slot) for _, d, slot in aligned]
    assert len(seats) == len(set(seats))
    for s, d, slot in aligned:
        assert d in alive and slot == by_sid[s] and slot in free[d]


def test_get_fleet_driver_rejects_unknown_name():
    with pytest.raises(ValueError, match='unknown fleet driver'):
        fleet.get_fleet_driver('warp', None)


# ---------------------------------------------------------------------------
# Straggler cold-start hardening (the threaded fleet driver's detector)
# ---------------------------------------------------------------------------

def test_straggler_first_observation_seeds_ewma():
    det = StragglerDetector(2)
    det.observe(0, 5.0)
    assert det.stats[0].ewma == 5.0, 'cold start must seed, not zero-mix'


def test_straggler_single_host_never_self_flags():
    det = StragglerDetector(1, patience=1, threshold=1.1)
    for t in (1.0, 9.0, 9.0, 9.0, 9.0):
        det.observe_step({0: t})
    assert not det.flagged, 'a one-host fleet has no one to be slower than'


def test_straggler_metrics_mirror():
    reg = obs_metrics.Registry()
    det = StragglerDetector(4, patience=2, metrics=reg)
    for _ in range(4):
        det.observe_step({0: 1.0, 1: 1.0, 2: 1.0, 3: 4.0})
    assert 3 in det.flagged
    assert reg['straggler.flagged{host=3}'].value == 1
    assert reg['straggler.flagged_total'].value == 1


# ---------------------------------------------------------------------------
# Driver conformance: threaded fleet vs the sync N-device oracle
# ---------------------------------------------------------------------------

def _conformance_run(steppers, driver):
    fm = _make_fleet(steppers)
    for s in _sessions(frames=(3, 3, 3, 2), arrivals=(0, 0, 1, 4),
                       paces=(1, 1, 1, 2)):
        fm.submit(s)
    finished = _drain(fm, driver)
    return fm, _frames_of(fm), finished


def test_threaded_fleet_conforms_to_sync_oracle(fleet_steppers):
    fm_s, frames_s, fin_s = _conformance_run(fleet_steppers, 'sync')
    fm_t, frames_t, fin_t = _conformance_run(fleet_steppers, 'threaded')
    assert frames_s, 'recorder saw no frames'
    assert frames_s == frames_t, 'threaded fleet diverged bitwise'
    assert [s.sid for s in fin_s] == [s.sid for s in fin_t] == [0, 1, 2, 3]
    assert fm_s.tick == fm_t.tick
    assert fm_s.home == fm_t.home, 'routing diverged'
    assert [s.telemetry.frames for s in fin_s] \
        == [s.telemetry.frames for s in fin_t]


def test_threaded_fleet_conforms_to_sync_oracle_pallas(small_scene):
    cfg = dataclasses.replace(CFG, backend='pallas')
    cam0 = orbit_trajectory(1, width=64, height_px=64)[0]
    steppers = [BatchedStepper(small_scene, cfg, cam0, slots=2)
                for _ in range(2)]
    fm_s, frames_s, _ = _conformance_run(steppers, 'sync')
    fm_t, frames_t, _ = _conformance_run(steppers, 'threaded')
    assert frames_s and frames_s == frames_t, \
        'threaded fleet diverged bitwise on the pallas backend'
    assert fm_s.tick == fm_t.tick


# ---------------------------------------------------------------------------
# Live migration
# ---------------------------------------------------------------------------

MIG_FRAMES = (6, 3, 6)   # sid1 drains early, so device 1 runs idle ticks
                         # before the migration lands on it — exercising
                         # the lockstep global_tick clock


@pytest.fixture(scope='module')
def golden_migration(fleet_steppers):
    fm = _make_fleet(fleet_steppers)
    for s in _sessions(frames=MIG_FRAMES):
        fm.submit(s)
    fleet.SyncFleetDriver(fm).run(200)
    frames = _frames_of(fm)
    assert all(len(v) == 1 for v in frames.values())
    return {k: v[0] for k, v in frames.items()}


def test_aligned_migration_is_bit_identical(fleet_steppers,
                                            golden_migration):
    fm = _make_fleet(fleet_steppers)
    for s in _sessions(frames=MIG_FRAMES):
        fm.submit(s)
    for _ in range(4):          # sid1 (device 1) finishes at tick 3
        fm.run_tick()
    assert fm.workers[1].mgr.drained()
    # sid2 sits at device 0 slot 1; slot 1 is free on device 1 -> aligned
    assert fm.migrate(2, 1) == 1
    assert fm.metrics['fleet.migrations{kind=aligned}'].value == 1
    while not fm.drained():
        fm.run_tick()
        assert fm.tick < 200
    frames = {k: v[0] for k, v in _frames_of(fm).items()}
    assert frames == golden_migration, \
        'aligned migration diverged from the never-moved golden run'


def test_cold_migration_conserves_frames(fleet_steppers, golden_migration):
    fm = _make_fleet(fleet_steppers)
    for s in _sessions(frames=MIG_FRAMES):
        fm.submit(s)
    for _ in range(2):
        fm.run_tick()
    # sid0 sits at device 0 slot 0; slot 0 on device 1 is occupied by
    # sid1 -> the move restores cold into the free slot 1
    assert fm.migrate(0, 1) == 1
    assert fm.metrics['fleet.migrations{kind=cold}'].value == 1
    finished = _drain(fm)
    assert [s.sid for s in finished] == [0, 1, 2]
    frames = _frames_of(fm)
    # every frame rendered exactly once (the cursor moved with the viewer)
    for (sid, n) in enumerate(MIG_FRAMES):
        assert {f for (s, f) in frames if s == sid} == set(range(n))
    assert all(len(v) == 1 for v in frames.values())
    assert all(s.telemetry.frames == n
               for s, n in zip(finished, MIG_FRAMES))
    # untouched viewers are unaffected (private scene blocks)
    for key, digs in frames.items():
        if key[0] != 0:
            assert digs[0] == golden_migration[key]


def test_migration_requeues_when_destination_is_full(fleet_steppers):
    fm = _make_fleet(fleet_steppers)
    for s in _sessions(frames=(4, 4, 4, 4)):
        fm.submit(s)
    fm.run_tick()
    assert fm.migrate(0, 1) is None      # both device-1 slots occupied
    assert fm.metrics['fleet.migrations{kind=requeued}'].value == 1
    assert [s.sid for s in fm.pending] == [0]
    assert 0 not in fm.home
    finished = _drain(fm)
    assert [s.sid for s in finished] == [0, 1, 2, 3]
    assert all(s.telemetry.frames == 4 for s in finished)
    frames = _frames_of(fm)
    assert all(len(v) == 1 for v in frames.values()), \
        're-queued viewer re-rendered delivered frames'


def test_migration_rejects_bad_targets(fleet_steppers):
    fm = _make_fleet(fleet_steppers)
    for s in _sessions(frames=(3, 3)):
        fm.submit(s)
    fm.run_tick()
    with pytest.raises(ValueError, match='not alive'):
        fm.migrate(0, 7)
    with pytest.raises(ValueError, match='already on device'):
        fm.migrate(0, fm.home[0])


# ---------------------------------------------------------------------------
# Device loss
# ---------------------------------------------------------------------------

LOSS_FRAMES = (8, 8, 8)
# routing puts sids 0+2 on device 0 (slots 0, 1) and sid 1 on device 1
# (slot 0).  Losing device 0 leaves only slot 1 free on the survivor:
# sid2 restores aligned, sid0 spills to the queue.


@pytest.fixture(scope='module')
def golden_loss(fleet_steppers):
    fm = _make_fleet(fleet_steppers)
    for s in _sessions(frames=LOSS_FRAMES):
        fm.submit(s)
    fleet.SyncFleetDriver(fm).run(200)
    frames = _frames_of(fm)
    assert all(len(v) == 1 for v in frames.values())
    return {k: v[0] for k, v in frames.items()}


def _loss_injector(tick, device=0):
    return faults.FaultInjector(faults.FaultTrace(seed=0, events=(
        faults.FaultEvent(tick=tick, kind='device_loss', slot=device),)))


def test_device_loss_checkpoint_rollback_matches_golden(
        fleet_steppers, golden_loss, tmp_path):
    """The chaos oracle: lose a checkpointed device mid-run; the whole
    fleet rolls back to the last crash-consistent snapshot and every
    surviving or slot-aligned lane replays bit-identically to the
    unfaulted golden run; the spilled lane re-queues at its snapshot
    cursor.  Zero dropped viewers, no double-counted frames."""
    fm = _make_fleet(fleet_steppers, ckpt_root=tmp_path, ckpt_every=2,
                     injector=_loss_injector(tick=5, device=0))
    for s in _sessions(frames=LOSS_FRAMES):
        fm.submit(s)
    finished = _drain(fm)
    # zero dropped viewers; telemetry counts each frame exactly once
    assert [s.sid for s in finished] == [0, 1, 2]
    assert all(s.telemetry.frames == 8 for s in finished)
    m = fm.metrics
    assert m['fleet.device_lost{device=0}'].value == 1
    assert m['fleet.migrations{kind=loss_aligned}'].value == 1
    assert m['fleet.migrations{kind=loss_spilled}'].value == 1
    assert m['fleet.alive_devices'].value == 1
    frames = _frames_of(fm)
    # survivor (sid1, restored own snapshot) and aligned victim (sid2,
    # restored from the dead device's snapshot): every rendering — the
    # pre-loss original AND the rolled-back replay — equals golden
    for sid in (1, 2):
        assert any(len(frames[(sid, f)]) > 1 for f in range(8)), \
            f'sid {sid}: rollback never replayed a frame'
        for f in range(8):
            assert all(d == golden_loss[(sid, f)]
                       for d in frames[(sid, f)]), \
                f'sid {sid} frame {f} diverged from golden'
    # spilled victim: full coverage from its snapshot cursor; its cold
    # re-admission re-sorts, so its continuation carries at most one
    # sort-window of sharing staleness (the fresh-admission bound) and is
    # not required to match golden bitwise
    assert {f for (s, f) in frames if s == 0} == set(range(8))
    for f in range(4):          # pre-divergence frames still match
        assert frames[(0, f)][0] == golden_loss[(0, f)]


def test_device_loss_cold_recovery_requeues_at_cursor(
        fleet_steppers, golden_loss):
    """No checkpoints: host cursors are crash-consistent in-process, so
    victims re-admit cold at their current frame — delivered frames are
    never re-rendered."""
    fm = _make_fleet(fleet_steppers, injector=_loss_injector(tick=3))
    for s in _sessions(frames=LOSS_FRAMES):
        fm.submit(s)
    finished = _drain(fm)
    assert [s.sid for s in finished] == [0, 1, 2]
    assert all(s.telemetry.frames == 8 for s in finished)
    assert fm.metrics['fleet.requeued'].value == 2
    assert fm.metrics['fleet.alive_devices'].value == 1
    frames = _frames_of(fm)
    assert all(len(v) == 1 for v in frames.values()), \
        'cold recovery re-rendered a delivered frame'
    for sid, n in enumerate(LOSS_FRAMES):
        assert {f for (s, f) in frames if s == sid} == set(range(n))
    # frames rendered before the loss are the golden frames
    for sid in range(3):
        for f in range(3):
            assert frames[(sid, f)][0] == golden_loss[(sid, f)]


def test_restore_at_launch_resumes_fleet(fleet_steppers, tmp_path):
    """Kill the whole fleet between ticks and relaunch with ``--restore``
    semantics: ``restore_at_launch`` adopts the newest checkpoint step
    COMMON to every device worker, every restored lane replays
    bit-identically to the unfaulted golden run, and every viewer still
    delivers every frame."""
    frames = (6, 6, 6)
    fm_g = _make_fleet(fleet_steppers)
    for s in _sessions(frames=frames):
        fm_g.submit(s)
    assert [s.sid for s in _drain(fm_g)] == [0, 1, 2]
    golden = {k: v[0] for k, v in _frames_of(fm_g).items()}

    # victim: checkpoint every 2 ticks, die between ticks (SIGKILL)
    fm_v = _make_fleet(fleet_steppers, ckpt_root=tmp_path, ckpt_every=2)
    for s in _sessions(frames=frames):
        fm_v.submit(s)
    while fm_v.tick < 5:
        fm_v.run_tick()
    for w in fm_v.workers:
        w.mgr._ckpt.wait()

    # survivor: fresh fleet, restore at launch instead of submitting
    fm_s = _make_fleet(fleet_steppers, ckpt_root=tmp_path, ckpt_every=2)
    restored = fm_s.restore_at_launch(_sessions(frames=frames))
    assert restored is not None and restored >= 2, restored
    assert fm_s.metrics['fleet.restores'].value == 1
    finished = _drain(fm_s)
    assert sorted(s.sid for s in finished) == [0, 1, 2]
    # fresh session objects only render the continuation — delivery is
    # complete (cursor at the end), not re-counted from frame 0
    assert all(s.cursor == 6 for s in finished)
    assert all(0 < s.telemetry.frames <= 6 for s in finished)
    cont = _frames_of(fm_s)
    for sid in range(3):
        covered = {f for (s, f) in cont if s == sid}
        assert max(covered) == 5, f'sid {sid} never reached its last frame'
        for f in covered:
            assert all(d == golden[(sid, f)] for d in cont[(sid, f)]), \
                f'sid {sid} frame {f} diverged from golden after restore'


def test_restore_at_launch_without_common_step_returns_none(
        fleet_steppers, tmp_path):
    """One worker with no usable snapshot (or no overlap in steps) means
    no crash-consistent fleet state: restore_at_launch refuses rather
    than resuming workers at different ticks."""
    fm_v = _make_fleet(fleet_steppers, ckpt_root=tmp_path, ckpt_every=2)
    for s in _sessions(frames=(6, 6, 6)):
        fm_v.submit(s)
    while fm_v.tick < 5:
        fm_v.run_tick()
    for w in fm_v.workers:
        w.mgr._ckpt.wait()
    # wipe one device's snapshots: no common step remains
    import shutil
    shutil.rmtree(tmp_path / 'device1')
    fm_s = _make_fleet(fleet_steppers, ckpt_root=tmp_path, ckpt_every=2)
    assert fm_s.restore_at_launch(_sessions(frames=(6, 6, 6))) is None


def test_loss_of_last_device_is_refused(fleet_steppers):
    fm = _make_fleet(fleet_steppers[:1], injector=_loss_injector(tick=1))
    for s in _sessions(frames=(3,)):
        fm.submit(s)
    with pytest.warns(RuntimeWarning, match='last alive device'):
        finished = _drain(fm)
    assert [s.sid for s in finished] == [0]
    assert fm.metrics['fleet.device_loss_ignored'].value == 1


def test_degraded_fleet_sheds_new_load_not_accepted_viewers(fleet_steppers):
    """Bounded admission under degraded capacity: excess arrivals shed
    (recorded + counted), every accepted viewer drains to completion."""
    fm = _make_fleet(fleet_steppers, max_pending=3,
                     injector=_loss_injector(tick=2))
    accepted = [fm.submit(s) for s in _sessions(
        frames=(4,) * 6, arrivals=(0, 0, 6, 6, 6, 6))]
    assert accepted == [True, True, True, False, False, False]
    assert [s.sid for s in fm.shed] == [3, 4, 5]
    assert fm.metrics['fleet.shed'].value == 3
    finished = _drain(fm)
    assert [s.sid for s in finished] == [0, 1, 2], \
        'an accepted viewer was dropped under degraded capacity'
    assert all(s.telemetry.frames == 4 for s in finished)
    assert len(fm.alive) == 1
    agg = fm.aggregate()
    assert agg['devices'] == 2 and agg['alive_devices'] == 1
    assert agg['shed'] == 3


# ---------------------------------------------------------------------------
# Placement: every worker's state lives on its own device
# ---------------------------------------------------------------------------

_PLACEMENT_CHILD = '''
import json
import jax
from repro.core.pipeline import LuminaConfig
from repro.data.scenes import structured_scene
from repro.data.trajectory import orbit_trajectory
from repro.serve.fleet import serve_fleet
from repro.serve.session import ViewerSession

scene = structured_scene(jax.random.PRNGKey(0), 300)
cams = [orbit_trajectory(3, width=32, height_px=32, start_deg=90.0 * i)
        for i in range(4)]
sessions = [ViewerSession(sid=i, cams=c, scene_id=i)
            for i, c in enumerate(cams)]
fm, finished = serve_fleet(scene, LuminaConfig(capacity=64, window=2),
                           cams[0][0], sessions, num_devices=4,
                           slots_per_device=1)
print(json.dumps({
    'placement': [[str(w.device), sorted(map(str, w.state_devices()))]
                  for w in fm.workers],
    'frames': sorted(s.telemetry.frames for s in finished)}))
'''


def test_fleet_worker_state_stays_on_its_device():
    """Ticks, admissions and restores run under their worker's device: with
    four distinct (virtual CPU) devices, each worker's stepper state sits
    on its own device after serving — nothing drifts to the first one."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS=(os.environ.get('XLA_FLAGS', '')
                          + ' --xla_force_host_platform_device_count=4'))
    out = subprocess.run([sys.executable, '-c', _PLACEMENT_CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    devices = [dev for dev, _ in res['placement']]
    assert len(set(devices)) == 4, devices
    for dev, held in res['placement']:
        assert held == [dev], (dev, held)
    assert res['frames'] == [3, 3, 3, 3]
