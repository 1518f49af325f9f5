"""Multi-viewer serving: two-phase core parity, cohort scheduling, session
lifecycle, donation hygiene, CLI."""
import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.camera import stack_cameras
from repro.core.pipeline import (LuminaConfig, LuminSys, ViewerState,
                                 batched_render_step, init_viewer_state,
                                 render_step, shade_phase, sort_phase)
from repro.data.trajectory import orbit_trajectory
from repro.serve.session import SessionManager, ViewerSession
from repro.serve.stepper import BatchedStepper, SequentialStepper
from repro.serve.telemetry import SessionTelemetry, aggregate, tick_rollup


CFG = LuminaConfig(capacity=256, window=3)


def _trajectories(n, frames):
    return [orbit_trajectory(frames, width=64, height_px=64,
                             start_deg=120.0 * i) for i in range(n)]


def assert_images_ulp_close(got, want, *, ulps=128, err_msg=''):
    """Image comparison with an explicitly ulp-scaled float32 tolerance.

    Why not exact equality: the batched (vmapped) and sequential paths
    compile to *different* XLA programs, and on CPU the batched lowering
    reorders/contracts FMAs in the projection einsums and the rasterizer's
    weighted color sums.  Every integer decision (cache tags, hit masks,
    sort orders) is asserted bitwise elsewhere; the images legitimately
    differ by a few ulps of the accumulated magnitude, so the bound is
    ``ulps`` x float32-eps x magnitude (floored at 1.0, the compositing
    scale) instead of an ad-hoc atol.
    """
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = np.maximum(np.maximum(np.abs(got), np.abs(want)), 1.0)
    tol = np.float32(ulps) * np.finfo(np.float32).eps * scale
    err = np.abs(got - want)
    worst = float((err / (np.finfo(np.float32).eps * scale)).max()) \
        if err.size else 0.0
    assert (err <= tol).all(), (
        f'{err_msg}: images differ by {worst:.0f} ulps (> {ulps} allowed)')


def test_render_step_matches_luminsys(small_scene, cams64):
    """The jitted functional step IS LuminSys: identical image stream."""
    sys_ = LuminSys(small_scene, CFG, cams64[0])
    state = init_viewer_state(small_scene, CFG, cams64[0])
    step = jax.jit(functools.partial(render_step, cfg=CFG))
    for cam in cams64:
        img_w, st_w = sys_.step(cam)
        state, img_f, st_f = step(small_scene, state, cam)
        np.testing.assert_array_equal(np.asarray(img_w), np.asarray(img_f))
        assert float(st_w.hit_rate) == float(st_f.hit_rate)
    assert int(state.frame_idx) == len(cams64)


def test_two_phase_composition_matches_render_step(small_scene, cams64):
    """Manually scheduling sort_phase + shade_phase at the per-viewer cadence
    reproduces the monolithic render_step stream: the split is a pure
    refactor, the schedule is the only new degree of freedom."""
    state_m = init_viewer_state(small_scene, CFG, cams64[0])
    state_p = init_viewer_state(small_scene, CFG, cams64[0])
    step = jax.jit(functools.partial(render_step, cfg=CFG))
    sortp = jax.jit(functools.partial(sort_phase, cfg=CFG))
    shadep = jax.jit(functools.partial(shade_phase, cfg=CFG))
    for f, cam in enumerate(cams64):
        state_m, img_m, st_m = step(small_scene, state_m, cam)
        shared, priv = state_p.scene_shared, state_p.viewer
        if f % CFG.window == 0:
            shared = sortp(small_scene, shared, priv, cam)
        shared, priv, img_p, st_p = shadep(small_scene, shared, priv, cam)
        state_p = ViewerState(scene_shared=shared, viewer=priv)
        np.testing.assert_allclose(np.asarray(img_m), np.asarray(img_p),
                                   atol=1e-6, err_msg=f'frame {f}')
        assert float(st_m.hit_rate) == pytest.approx(float(st_p.hit_rate),
                                                     abs=1e-6)
    np.testing.assert_array_equal(np.asarray(state_m.cache.tags),
                                  np.asarray(state_p.cache.tags))


def test_batched_vmap_parity_with_sequential(small_scene):
    """N viewers stepped via one vmapped call match N independent LuminSys
    runs: every integer cache decision (tags, LRU age, clock, hit counts)
    is bitwise identical; images agree to float32 ulp (XLA's batched
    lowering reorders FMA contractions in the projection einsums, so exact
    bit equality across the two compiled programs is not attainable on CPU).
    """
    n, frames = 3, 5
    trajs = _trajectories(n, frames)
    refs = [LuminSys(small_scene, CFG, t[0]) for t in trajs]
    states = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[init_viewer_state(small_scene, CFG, t[0]) for t in trajs])
    step_b = jax.jit(
        lambda st, cm: batched_render_step(small_scene, st, cm, CFG))

    for f in range(frames):
        cams = stack_cameras([t[f] for t in trajs])
        states, images, stats = step_b(states, cams)
        for v in range(n):
            img_ref, st_ref = refs[v].step(trajs[v][f])
            assert_images_ulp_close(images[v], img_ref,
                                    err_msg=f'viewer {v} frame {f}')
            assert float(stats.hit_rate[v]) == pytest.approx(
                float(st_ref.hit_rate), abs=1e-6)
            assert float(stats.sorted_this_frame[v]) == float(
                st_ref.sorted_this_frame)

    for v in range(n):
        cache_b = jax.tree.map(lambda x: x[v], states.cache)
        cache_s = refs[v].state.cache
        np.testing.assert_array_equal(np.asarray(cache_b.tags),
                                      np.asarray(cache_s.tags))
        np.testing.assert_array_equal(np.asarray(cache_b.age),
                                      np.asarray(cache_s.age))
        np.testing.assert_array_equal(np.asarray(cache_b.clock),
                                      np.asarray(cache_s.clock))
        np.testing.assert_allclose(np.asarray(cache_b.values),
                                   np.asarray(cache_s.values), atol=1e-5)


def test_cohort_single_viewer_matches_sequential(small_scene):
    """Satellite (a): for one viewer in slot 0 admitted at tick 0, the cohort
    cadence coincides with the per-viewer cadence — the cohort-scheduled
    batched engine and the sequential reference agree on every sort
    decision, every integer cache decision and the images."""
    traj = orbit_trajectory(2 * CFG.window + 1, width=64, height_px=64)
    bat = BatchedStepper(small_scene, CFG, traj[0], slots=1)
    seq = SequentialStepper(small_scene, CFG, traj[0], slots=1)
    bat.admit(0)
    seq.admit(0)
    for f, cam in enumerate(traj):
        img_b, st_b, _ = bat.step({0: cam})[0]
        img_s, st_s, _ = seq.step({0: cam})[0]
        assert float(st_b.sorted_this_frame) == float(st_s.sorted_this_frame)
        assert_images_ulp_close(img_b, img_s, err_msg=f'frame {f}')
        assert float(st_b.hit_rate) == pytest.approx(float(st_s.hit_rate),
                                                     abs=1e-6)
    cache_b = jax.tree.map(lambda x: x[0], bat.shared.cache)
    cache_s = seq._states[0].cache
    for field in ('tags', 'age', 'clock'):
        np.testing.assert_array_equal(np.asarray(getattr(cache_b, field)),
                                      np.asarray(getattr(cache_s, field)))


def test_cohort_multi_viewer_matches_replayed_cadence(small_scene):
    """Multi-slot cohort gather/scatter parity: the batched engine equals an
    oracle that replays the exact cohort schedule (sort-on-admit at tick 0,
    then slot i sorts when tick % window == i % window) through the
    single-viewer phases.  3 slots with window 2 makes the scheduled cohort
    alternate between a full gather (slots 0,2) and a padded one (slot 1),
    so both the duplicate-index padding and the mode='drop' scatter are on
    the line."""
    cfg = LuminaConfig(capacity=256, window=2)
    s, frames = 3, 5
    trajs = _trajectories(s, frames)
    bat = BatchedStepper(small_scene, cfg, trajs[0][0], slots=s)
    for i in range(s):
        bat.admit(i)

    sortp = jax.jit(functools.partial(sort_phase, cfg=cfg))
    shadep = jax.jit(functools.partial(shade_phase, cfg=cfg))
    oracle = [init_viewer_state(small_scene, cfg, t[0]) for t in trajs]

    for tick in range(frames):
        out = bat.step({i: trajs[i][tick] for i in range(s)})
        for i in range(s):
            cam = trajs[i][tick]
            shared_o, priv_o = oracle[i].scene_shared, oracle[i].viewer
            if tick == 0 or tick % cfg.window == i % cfg.window:
                shared_o = sortp(small_scene, shared_o, priv_o, cam)
                expect_sorted = 1.0
            else:
                expect_sorted = 0.0
            shared_o, priv_o, img_o, st_o = shadep(small_scene, shared_o,
                                                   priv_o, cam)
            oracle[i] = ViewerState(scene_shared=shared_o, viewer=priv_o)
            img_b, st_b, _ = out[i]
            assert float(st_b.sorted_this_frame) == expect_sorted, \
                f'slot {i} tick {tick}'
            assert_images_ulp_close(img_b, img_o,
                                    err_msg=f'slot {i} tick {tick}')
            assert float(st_b.hit_rate) == pytest.approx(float(st_o.hit_rate),
                                                         abs=1e-6)
    for i in range(s):
        cache_b = jax.tree.map(lambda x: x[i], bat.shared.cache)
        for field in ('tags', 'age', 'clock'):
            np.testing.assert_array_equal(
                np.asarray(getattr(cache_b, field)),
                np.asarray(getattr(oracle[i].cache, field)),
                err_msg=f'slot {i} {field}')


def test_cohort_sort_bound_after_warmup(small_scene):
    """Satellite (b): with S viewers at steady state, at most ceil(S/window)
    slots run a speculative sort on any tick — the whole point of the cohort
    scheduler (the old per-lane cond sorted all S lanes every tick)."""
    s, frames = 5, 8
    cfg = LuminaConfig(capacity=256, window=3)
    trajs = _trajectories(s, frames)
    stepper = BatchedStepper(small_scene, cfg, trajs[0][0], slots=s)
    mgr = SessionManager(stepper, slots=s)
    for sid, t in enumerate(trajs):
        mgr.submit(ViewerSession(sid=sid, cams=t))
    mgr.run()
    bound = -(-s // cfg.window)
    # tick 0 carries the sort-on-admit burst (outside the scheduled cohort)
    steady = stepper.sort_log[1:]
    assert steady, 'run too short to observe steady state'
    assert all(e['admit'] == 0 for e in steady)
    assert max(e['scheduled'] for e in steady) <= bound
    # and the realised cadence amortizes to 1/window per viewer
    total_sorts = sum(e['scheduled'] + e['admit'] for e in stepper.sort_log)
    assert total_sorts <= s * (1 + frames / cfg.window)
    roll = tick_rollup(mgr.tick_log, warmup_ticks=1)
    assert roll['max_sorts_per_tick'] <= bound


def test_sort_on_admit_mid_flight(small_scene):
    """Satellite (c): a viewer admitted mid-flight (slot reuse) sorts on
    admit and its first frame matches a cold-start single-viewer render —
    no stale SortShared, no stale radiance cache."""
    trajs = _trajectories(3, 4)
    stepper = BatchedStepper(small_scene, CFG, trajs[0][0], slots=2)
    stepper.admit(0)
    stepper.admit(1)
    for f in range(3):
        stepper.step({0: trajs[0][f], 1: trajs[1][f]})
    # viewer 2 takes slot 0 mid-flight, off the shared sort cadence
    stepper.admit(0)
    out = stepper.step({0: trajs[2][0], 1: trajs[1][3]})
    img, st, timing = out[0]
    assert float(st.sorted_this_frame) == 1.0
    assert timing.sorted_slots >= 1
    ref = LuminSys(small_scene, CFG, trajs[2][0])
    img_ref, st_ref = ref.step(trajs[2][0])
    assert_images_ulp_close(img, img_ref, err_msg='sort-on-admit frame')
    assert float(st.hit_rate) == pytest.approx(float(st_ref.hit_rate),
                                               abs=1e-6)


def test_steppers_no_donation_warnings(small_scene):
    """Both engines donate their ViewerState buffers into the jitted calls;
    a 'donated buffer' warning means the donation silently degraded back to
    a full per-tick state copy."""
    trajs = _trajectories(2, 4)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        for engine in (BatchedStepper, SequentialStepper):
            stepper = engine(small_scene, CFG, trajs[0][0], slots=2)
            mgr = SessionManager(stepper, slots=2)
            for sid, t in enumerate(trajs):
                mgr.submit(ViewerSession(sid=sid, cams=t))
            mgr.run()
    donated = [w for w in caught if 'donat' in str(w.message).lower()]
    assert not donated, [str(w.message) for w in donated]


def test_session_manager_admit_evict_lifecycle(small_scene):
    """More viewers than slots: arrivals queue, slots are reused, everyone
    finishes with exactly their trajectory's frame count."""
    trajs = _trajectories(4, 3)
    stepper = BatchedStepper(small_scene, CFG, trajs[0][0], slots=2)
    mgr = SessionManager(stepper, slots=2)
    for sid, t in enumerate(trajs):
        mgr.submit(ViewerSession(sid=sid, cams=t, arrival_tick=sid))

    # tick 0: only viewer 0 has arrived
    mgr.run_tick()
    assert len(mgr.active_slots()) == 1
    # tick 1: viewer 1 arrives -> both slots busy, viewers 2/3 must queue
    mgr.run_tick()
    assert len(mgr.active_slots()) == 2
    assert len(mgr.pending) == 2

    finished = mgr.run()
    assert sorted(s.sid for s in finished) == [0, 1, 2, 3]
    for s in finished:
        assert s.telemetry.frames == 3
        assert s.telemetry.admitted_tick >= s.arrival_tick
        # every session's first frame rode a sort (scheduled or on-admit)
        assert s.telemetry.sorted_flags[0] == 1.0
    # late viewers could not be admitted on arrival: they queued for a slot
    late = [s for s in finished if s.sid >= 2]
    assert all(s.telemetry.summary()['queue_ticks'] > 0 for s in late)
    # slots were reused across sessions
    assert mgr.drained() and mgr.tick < 20
    # the manager kept per-tick phase attribution for every rendered tick
    assert mgr.tick_log and all(
        t['sort_ms'] >= 0.0 and t['shade_ms'] > 0.0 for t in mgr.tick_log)


def test_telemetry_summary():
    t = SessionTelemetry(sid=7, arrival_tick=1)
    t.admitted_tick = 3
    for i in range(10):
        t.observe_frame(latency_s=0.01 * (i + 1), hit_rate=0.5,
                        saved_frac=0.25, sorted_flag=float(i % 3 == 0),
                        sort_ms=2.0, shade_ms=8.0)
    s = t.summary()
    assert s['sid'] == 7 and s['frames'] == 10
    assert s['queue_ticks'] == 2
    assert s['hit_rate'] == pytest.approx(0.5)
    assert s['sorts_per_frame'] == pytest.approx(0.4)
    assert s['sort_ms'] == pytest.approx(2.0)
    assert s['shade_ms'] == pytest.approx(8.0)
    assert 0 < s['p50_ms'] < s['p99_ms'] <= 100.0
    agg = aggregate([s])
    assert agg['sessions'] == 1 and agg['frames'] == 10
    assert agg['mean_sort_ms'] == pytest.approx(2.0)
    assert agg['mean_shade_ms'] == pytest.approx(8.0)


def test_tick_rollup():
    log = [{'tick': 0, 'frames': 4, 'sorted_slots': 4, 'sort_ms': 9.0,
            'shade_ms': 20.0},
           {'tick': 1, 'frames': 4, 'sorted_slots': 1, 'sort_ms': 2.0,
            'shade_ms': 10.0},
           {'tick': 2, 'frames': 4, 'sorted_slots': 2, 'sort_ms': 4.0,
            'shade_ms': 12.0}]
    roll = tick_rollup(log, warmup_ticks=1)
    assert roll['ticks'] == 2
    assert roll['max_sorts_per_tick'] == 2
    assert roll['mean_sorts_per_tick'] == pytest.approx(1.5)
    assert roll['mean_sort_ms'] == pytest.approx(3.0)
    assert roll['mean_shade_ms'] == pytest.approx(11.0)


def test_serve_cli_smoke(capsys):
    from repro.serve import render as serve_render
    serve_render.main(['--viewers', '2', '--frames', '3', '--width', '64',
                       '--gaussians', '600', '--capacity', '128'])
    out = capsys.readouterr().out
    assert 'hit_rate' in out and 'batched (reference): 2 sessions' in out
    assert 'sort_ms' in out and 'sorts/tick' in out


def test_serve_cli_pallas_backend_with_profile(capsys, tmp_path):
    """--backend pallas serves end-to-end and --profile-dir records a
    jax.profiler trace holding the shade program's stage scopes (in the
    HLO of the programs it ran) and the server's lumina.* host spans."""
    from repro.serve import render as serve_render
    prof = tmp_path / 'profile'
    serve_render.main(['--viewers', '2', '--frames', '4', '--width', '64',
                       '--gaussians', '600', '--capacity', '128',
                       '--stagger', '0', '--backend', 'pallas',
                       '--profile-dir', str(prof)])
    out = capsys.readouterr().out
    assert 'batched (pallas): 2 sessions' in out
    assert f'-- profile: jax.profiler trace -> {prof}' in out
    (xplane,) = prof.rglob('*.xplane.pb')
    data = xplane.read_bytes()
    for stage in ('prep', 'raster', 'rc_probe', 'rc_insert'):
        assert f'shade/{stage}'.encode() in data
    for span in ('tick', 'step_dispatch', 'sort_wait', 'observe_tick',
                 'fetch'):
        assert f'lumina.{span}'.encode() in data
