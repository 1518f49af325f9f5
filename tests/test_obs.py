"""Observability layer (``repro.obs``) + bench regression gating.

Five contracts:

* **Tracer/export schema** — spans/instants/explicit device windows record
  with correct nesting depth and export as Chrome trace-event JSON that
  passes the loadability schema (tracks as named thread lanes, µs
  timestamps, ``M`` metadata);
* **Structure determinism** — under the virtual-clock ``SyncDriver`` the
  span *structure* (per-track (ph, name, depth, args) sequences, no
  timestamps) of two replays of the same traffic trace is identical, and a
  threaded run shows a ``host-worker`` plan span genuinely overlapping a
  ``device`` shade window — the plan(t+1) ∥ device(t) picture;
* **Metrics registry** — typed get-or-create instruments (kind conflicts
  raise), label keying, exact percentiles, JSON snapshots; and the
  registry's tick series reproduce ``tick_rollup`` **bit-identically** to
  the ``SessionManager.tick_log`` dict path on a real serving run;
* **On the profiler's clock** — a live tracer's spans land in a
  ``jax.profiler`` trace as ``lumina.<span>`` (NULL writes nothing), and
  the shade program's ops carry every ``shade/<stage>`` scope;
* **Bench history gating** — ``benchmarks.history.check_payloads`` passes a
  fresh payload equal to its baseline and fails degraded copies
  (fps collapse, p95 blow-up, host_overlap -> 0, chunk-savings sign flip).

Satellites ride along: ``aggregate``'s frame-weighted ``fleet_fps``,
heterogeneous ``format_table``, and the ``tick_rollup`` edge cases
(legacy logs, all-warmup slicing, overlap > 1 warning).
"""
import json
import warnings

import jax
import numpy as np
import pytest

from repro.core.pipeline import LuminaConfig
from repro.data.trajectory import orbit_trajectory
from repro.obs import (NULL, Registry, Tracer, TRACK_DEVICE, TRACK_HOST,
                       TRACK_WORKER, publish_tick, span_structure,
                       tick_log_from_registry, tick_rollup_from_metrics,
                       to_chrome_trace, track_spans, validate_chrome_trace,
                       write_trace)
from repro.serve.session import SessionManager, ViewerSession
from repro.serve.stepper import BatchedStepper
from repro.serve.telemetry import aggregate, format_table, tick_rollup

from benchmarks import history


# ---------------------------------------------------------------- tracer --

def test_tracer_span_nesting_depth_and_args():
    tr = Tracer()
    with tr.span('tick', tick=3):
        with tr.span('plan_tick', tick=3):
            pass
        tr.instant('admit', slot=1, sid=7)
    tr.complete('shade', 1.0, 1.5, tick=3, slots=2)
    structure = span_structure(tr.events)
    # children exit (and record) before parents; depth counts nesting
    assert structure[TRACK_HOST] == (
        ('X', 'plan_tick', 1, (('tick', 3),)),
        ('i', 'admit', 0, (('sid', 7), ('slot', 1))),
        ('X', 'tick', 0, (('tick', 3),)),
    )
    assert structure[TRACK_DEVICE] == (
        ('X', 'shade', 0, (('slots', 2), ('tick', 3))),)
    (ev,) = [e for e in tr.events if e.track == TRACK_DEVICE]
    assert ev.ts == 1.0 and ev.dur == pytest.approx(0.5)


def test_null_tracer_is_inert():
    with NULL.span('tick', tick=0):
        NULL.instant('admit')
        NULL.complete('shade', 0.0, 1.0)
    assert NULL.events == [] and not NULL.enabled


def _profiled_host_events(tmp_path, body):
    """Run ``body`` under a ``jax.profiler`` session; the host events of
    its trace as ``(name, start_ns, end_ns)``, read with ``ProfileData``."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob('*.xplane.pb')
    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith('/host:')
            for line in plane.lines for ev in line.events]


def test_live_tracer_spans_land_on_the_profiler_clock(tmp_path):
    """A live tracer's context-manager spans are profiler annotations
    ``lumina.<name>``, nested as they ran; explicit device windows and
    instants stay in the tracer alone, and the recorded structure is what
    it was without a profiler."""
    tr = Tracer()

    def body():
        with tr.span('tick', tick=0):
            with tr.span('plan_tick', tick=0):
                tr.instant('admit', slot=0, sid=0)
        tr.complete('shade', 1.0, 2.0, tick=0)

    events = _profiled_host_events(tmp_path, body)
    lumina = {name: (t0, t1) for name, t0, t1 in events
              if name.startswith('lumina.')}
    assert set(lumina) == {'lumina.tick', 'lumina.plan_tick'}
    (p0, p1), (c0, c1) = lumina['lumina.tick'], lumina['lumina.plan_tick']
    assert p0 <= c0 <= c1 <= p1
    assert span_structure(tr.events) == {
        TRACK_HOST: (('i', 'admit', 0, (('sid', 0), ('slot', 0))),
                     ('X', 'plan_tick', 1, (('tick', 0),)),
                     ('X', 'tick', 0, (('tick', 0),))),
        TRACK_DEVICE: (('X', 'shade', 0, (('tick', 0),)),)}


def test_null_tracer_writes_nothing_to_the_profiler(tmp_path):
    def body():
        with NULL.span('tick', tick=0):
            NULL.instant('admit')

    events = _profiled_host_events(tmp_path, body)
    assert not [name for name, _, _ in events if name.startswith('lumina.')]


def test_shade_program_carries_every_stage_scope(small_scene):
    """The pallas shade program, lowered at test size, names every stage
    of the shade step in its op metadata: prep, raster, rc_probe and
    rc_insert in the full-width program, and lanes in the lane-compacted
    one, which gathers and scatters the live lanes."""
    import re

    import jax.numpy as jnp

    from repro.core.camera import stack_cameras
    from repro.obs.trace import SHADE_STAGES
    cams0 = orbit_trajectory(1, width=64, height_px=64)
    cfg = LuminaConfig(capacity=128, window=2, backend='pallas')
    st = BatchedStepper(small_scene, cfg, cams0[0], slots=2,
                        viewers_per_scene=2)
    cam_b = stack_cameras(st._slot_cams)
    flags = jnp.zeros((2,), jnp.float32)

    def stages(fn, *args):
        text = fn.lower(st.scene, st.shared, st.priv, cam_b, flags,
                        *args).as_text(debug_info=True)
        return set(re.findall(r'shade/(\w+)', text))

    full = stages(st._shade, jnp.ones((2,), bool))
    one = jnp.zeros((1,), jnp.int32)
    lanes = stages(st._get_lane_jit(1), one, one, one, one,
                   jnp.ones((1,), bool))
    assert full == {'prep', 'raster', 'rc_probe', 'rc_insert'}
    assert lanes == set(SHADE_STAGES)


def test_chrome_trace_export_schema_and_tracks(tmp_path):
    tr = Tracer()
    with tr.span('tick', tick=0):
        pass
    tr.complete('shade', 2.0, 2.25, tick=0)
    tr.instant('arrival', sid=0)
    path = tmp_path / 'trace.json'
    write_trace(str(path), tr)
    payload = json.loads(path.read_text())
    events = validate_chrome_trace(payload)
    assert payload['displayTimeUnit'] == 'ms'
    # named thread lanes for every track, stable order host < device
    lanes = {e['args']['name']: e['tid'] for e in events
             if e['ph'] == 'M' and e['name'] == 'thread_name'}
    assert set(lanes) == {TRACK_HOST, TRACK_DEVICE}
    assert lanes[TRACK_HOST] < lanes[TRACK_DEVICE]
    # timestamps are µs relative to the earliest event; instants are
    # thread-scoped
    ts = [e['ts'] for e in events if e['ph'] != 'M']
    assert min(ts) == 0.0
    (shade,) = track_spans(payload, TRACK_DEVICE)
    assert shade[2] == 'shade' and shade[1] - shade[0] == \
        pytest.approx(0.25e6)
    (inst,) = [e for e in events if e['ph'] == 'i']
    assert inst['s'] == 't'


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match='traceEvents'):
        validate_chrome_trace({'events': []})
    bad = to_chrome_trace([])
    bad['traceEvents'].append({'ph': 'X', 'name': 'x', 'pid': 1, 'tid': 1,
                               'ts': 0.0})   # span without dur
    with pytest.raises(ValueError, match='dur'):
        validate_chrome_trace(bad)


# -------------------------------------------------------------- registry --

def test_registry_typed_instruments_and_labels():
    reg = Registry()
    c = reg.counter('sort.executed', scene=0, cell=17)
    c.inc()
    c.inc(2)
    # get-or-create: same (name, labels) -> same instrument; label order
    # in the call does not matter (keys are sorted)
    assert reg.counter('sort.executed', cell=17, scene=0) is c
    assert c.value == 3
    assert 'sort.executed{cell=17,scene=0}' in reg
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge('serve.queue_depth')
    g.set(3)
    g.set(1)
    assert (g.value, g.min, g.max) == (1, 1, 3)
    h = reg.histogram('serve.tick_latency_ms')
    samples = [5.0, 1.0, 9.0, 3.0]
    for s in samples:
        h.observe(s)
    assert h.count == 4 and h.sum == pytest.approx(18.0)
    assert h.percentile(50) == float(np.percentile(samples, 50))
    # a name is permanently typed
    with pytest.raises(TypeError, match='already registered as counter'):
        reg.gauge('sort.executed', scene=0, cell=17)


def test_registry_snapshot_is_json_serializable():
    reg = Registry()
    reg.counter('serve.frames').inc(4)
    reg.gauge('cache.occupancy').set(np.float32(0.5))   # device-ish scalar
    reg.histogram('serve.tick_latency_ms').observe(2.0)
    reg.series('tick.frames').record(0, np.int64(2))
    snap = json.loads(reg.to_json())
    assert snap['serve.frames']['value'] == 4
    assert snap['cache.occupancy']['value'] == pytest.approx(0.5)
    assert snap['tick.frames'] == {'type': 'series', 'ticks': 1, 'last': 2}


def test_publish_tick_roundtrip_and_rollup_bit_identity_synthetic():
    """The registry's tick series reconstruct the tick log (including the
    awkward shape of fields present on some ticks only) and the registry
    rollup equals the dict rollup exactly."""
    log = [
        {'tick': 0, 'frames': 2, 'sorted_slots': 1, 'sort_ms': 0.5,
         'shade_ms': 3.0},
        {'tick': 1, 'frames': 2, 'sorted_slots': 0, 'sort_ms': 0.0,
         'shade_ms': 2.5, 'latency_ms': 3.1, 'host_ms': 0.4,
         'overlap_ms': 0.2, 'occupancy': np.float32(0.25)},
        {'tick': 2, 'frames': 1, 'sorted_slots': 2, 'sort_ms': 0.9,
         'shade_ms': 2.0, 'latency_ms': 2.9, 'host_ms': 0.3,
         'overlap_ms': 0.1, 'occupancy': np.float32(0.5),
         'sort_pool_live': 2},
    ]
    reg = Registry()
    for entry in log:
        publish_tick(reg, entry)
    rebuilt = tick_log_from_registry(reg)
    assert [e['tick'] for e in rebuilt] == [0, 1, 2]
    assert 'latency_ms' not in rebuilt[0]
    assert 'sort_pool_live' not in rebuilt[1]
    for want, got in zip(log, rebuilt):
        assert got.keys() == want.keys()
        for key, val in want.items():
            assert got[key] is val or got[key] == val
    for warmup in (0, 1):
        assert tick_rollup_from_metrics(reg, warmup_ticks=warmup) == \
            tick_rollup(log, warmup_ticks=warmup)


# ------------------------------------------------- serving integration ----

CFG = LuminaConfig(capacity=192, window=3)
ARRIVALS = (0, 0, 2)
FRAMES = 3


def _sessions():
    return [ViewerSession(sid=sid,
                          cams=orbit_trajectory(FRAMES, width=64,
                                                height_px=64,
                                                start_deg=120.0 * sid),
                          arrival_tick=arrival)
            for sid, arrival in enumerate(ARRIVALS)]


@pytest.fixture(scope='module')
def obs_stepper(small_scene):
    cams0 = orbit_trajectory(1, width=64, height_px=64)
    return BatchedStepper(small_scene, CFG, cams0[0], slots=2)


def _run(stepper, driver):
    stepper.reset()
    tracer = Tracer()
    mgr = SessionManager(stepper, slots=stepper.slots, tracer=tracer)
    for s in _sessions():
        mgr.submit(s)
    mgr.run(driver=driver)
    return tracer, mgr


def test_sync_driver_span_structure_is_deterministic(obs_stepper):
    """Two SyncDriver replays of the same traffic trace record the same
    span structure per track — names, nesting, per-tick args; only the
    timestamps (excluded from the structure) differ."""
    tr_a, _ = _run(obs_stepper, 'sync')
    tr_b, _ = _run(obs_stepper, 'sync')
    sa, sb = span_structure(tr_a.events), span_structure(tr_b.events)
    assert sa == sb
    # and the structure is substantive: nested host spans + device windows
    host_names = {rec[1] for rec in sa[TRACK_HOST]}
    assert {'tick', 'plan_tick', 'apply_plan', 'observe_tick',
            'arrival', 'admit'} <= host_names
    assert any(rec[2] > 0 for rec in sa[TRACK_HOST])
    assert {'shade'} <= {rec[1] for rec in sa[TRACK_DEVICE]}


def test_metrics_rollup_bit_identical_on_real_run(obs_stepper):
    """Acceptance: ``tick_rollup`` computed from the metrics registry is
    bit-identical to the dict path on a recorded serving tick_log."""
    _, mgr = _run(obs_stepper, 'sync')
    assert mgr.tick_log, 'run recorded no ticks'
    for warmup in (0, 1):
        assert tick_rollup_from_metrics(mgr.metrics, warmup_ticks=warmup) \
            == tick_rollup(mgr.tick_log, warmup_ticks=warmup)
    # the traffic/scheduler counters landed
    frames = mgr.metrics['serve.frames'].value
    assert frames == sum(t['frames'] for t in mgr.tick_log)
    assert mgr.metrics['serve.admitted'].value == len(ARRIVALS)
    assert any(name.startswith('sort.executed')
               for name in mgr.metrics.names())


def test_threaded_trace_shows_worker_plan_overlapping_device(obs_stepper):
    """Acceptance: the exported threaded-driver trace has >= 2 tracks and a
    host-worker ``plan_tick`` span overlapping a ``device`` shade span —
    the plan(t+1) ∥ device(t) double-buffering, visible in Perfetto rather
    than inferred from a scalar."""
    tracer, _ = _run(obs_stepper, 'threaded')
    payload = to_chrome_trace(tracer.events)
    validate_chrome_trace(payload)
    worker = track_spans(payload, TRACK_WORKER)
    device = track_spans(payload, TRACK_DEVICE)
    assert worker and device
    assert all(name == 'plan_tick' for _, _, name, _ in worker)
    overlaps = [(w, d) for w in worker for d in device
                if max(w[0], d[0]) < min(w[1], d[1])]
    assert overlaps, 'no host-worker plan span overlapped a device span'


# ------------------------------------------------------- bench history ----

def _serve_payload(fps=30.0, p95=40.0, overlap=0.5, hit=0.8):
    return {'suite': 'serve', 'rows': [{
        'viewers': 2, 'mode': 'batched', 'backend': 'pallas',
        'viewers_per_scene': 1, 'driver': 'threaded', 'stagger': 0,
        'fps_per_viewer': fps, 'p95_frame_ms': p95,
        'host_overlap': overlap, 'hit_rate': hit,
    }]}


def _kernel_payload(savings=27.7):
    return {'suite': 'kernel', 'rows': [
        {'metric': 'chunk_savings_%', 'value': savings, 'note': ''},
        {'metric': 'hit_rate_mean', 'value': 0.94, 'note': ''},
    ]}


def test_history_passes_identical_payloads():
    for suite, payload in (('serve', _serve_payload()),
                           ('kernel', _kernel_payload())):
        violations, report = history.check_payloads(suite, payload, payload)
        assert violations == [] and report


def test_history_fails_degraded_copies():
    base = _serve_payload()
    cases = {
        'fps_per_viewer': _serve_payload(fps=10.0),      # < 50% of baseline
        'p95_frame_ms': _serve_payload(p95=140.0),       # > 2.5x baseline
        'host_overlap': _serve_payload(overlap=0.0),     # hard floor
        'hit_rate': _serve_payload(hit=0.5),             # structural drop
    }
    for metric, fresh in cases.items():
        violations, _ = history.check_payloads('serve', base, fresh)
        assert violations and metric in violations[0], (metric, violations)
    violations, _ = history.check_payloads(
        'kernel', _kernel_payload(), _kernel_payload(savings=-5.0))
    assert violations and 'chunk_savings_%' in violations[0]


def test_history_tolerates_noise_and_row_intersection():
    base = _serve_payload()
    # within-band wobble passes
    ok = _serve_payload(fps=20.0, p95=90.0, overlap=0.2, hit=0.75)
    violations, _ = history.check_payloads('serve', base, ok)
    assert violations == []
    # a fresh row with no baseline counterpart is skipped, not failed —
    # but it leaves the baseline row unmeasured (a missing-row regression)
    # and gating nothing at all fails too
    extra = _serve_payload()
    extra['rows'][0]['viewers'] = 64
    violations, report = history.check_payloads('serve', base, extra)
    assert any('MISSING' in line for line in violations)
    assert (f'serve: no gateable metric pairs between payloads'
            in violations)
    assert any('no baseline row' in line for line in report)


def test_history_fails_dropped_baseline_row():
    """A baseline row the fresh payload stopped producing is itself a
    regression — the dropped cell would otherwise silently un-gate every
    metric it carried."""
    base = _serve_payload()
    dropped = dict(base['rows'][0], backend='reference')
    base['rows'].append(dropped)
    fresh = _serve_payload()   # only the pallas row survives
    violations, report = history.check_payloads('serve', base, fresh)
    assert len(violations) == 1 and 'MISSING' in violations[0]
    assert 'backend=reference' in violations[0]


def test_history_missing_row_allowlists():
    base = _serve_payload()
    dropped = dict(base['rows'][0], backend='reference')
    base['rows'].append(dropped)
    fresh = _serve_payload()
    # programmatic allowlist: identity-subset match clears the violation
    violations, report = history.check_payloads(
        'serve', base, fresh,
        allow_missing=({'backend': 'reference'},))
    assert violations == []
    assert any('allow_missing' in line for line in report)
    # RETIRED_ROWS: the committed allowlist works the same way
    old = history.RETIRED_ROWS['serve']
    history.RETIRED_ROWS['serve'] = ({'backend': 'reference'},)
    try:
        violations, report = history.check_payloads('serve', base, fresh)
    finally:
        history.RETIRED_ROWS['serve'] = old
    assert violations == []
    assert any('retired' in line for line in report)
    # a non-matching spec does NOT clear it
    violations, _ = history.check_payloads(
        'serve', base, fresh, allow_missing=({'backend': 'cuda'},))
    assert len(violations) == 1 and 'MISSING' in violations[0]


def test_history_quick_fresh_skips_full_only_rows():
    """A --quick fresh payload may legitimately miss rows the full run
    stamped ``quick_row: false`` — but quick-measured rows must still be
    present."""
    base = _serve_payload()
    full_only = dict(base['rows'][0], backend='reference',
                     quick_row=False)
    base['rows'][0]['quick_row'] = True
    base['rows'].append(full_only)
    fresh = _serve_payload()
    fresh['quick'] = True
    violations, report = history.check_payloads('serve', base, fresh)
    assert violations == []
    assert any('full-run-only' in line for line in report)
    # ...but dropping a quick-measured row still fails under --quick
    fresh['rows'] = []
    violations, _ = history.check_payloads('serve', base, fresh)
    assert any('MISSING' in line for line in violations)
    # and a full fresh payload gets no quick carve-out at all
    full_fresh = _serve_payload()
    full_fresh['rows'][0]['backend'] = 'reference'
    violations, _ = history.check_payloads('serve', base, full_fresh)
    assert any('MISSING' in line and 'backend=pallas' in line
               for line in violations)


def test_history_cli_check(tmp_path):
    base, fresh = tmp_path / 'base.json', tmp_path / 'fresh.json'
    base.write_text(json.dumps(_serve_payload()))
    fresh.write_text(json.dumps(_serve_payload()))
    argv = ['--check', '--suite', 'serve', '--fresh', str(fresh),
            '--baseline', str(base)]
    assert history.main(argv) == 0
    fresh.write_text(json.dumps(_serve_payload(overlap=0.0)))
    assert history.main(argv) == 1


# -------------------------------------------- telemetry satellites --------

def _summary(fps, frames, **extra):
    out = {'frames': frames, 'fps': fps, 'hit_rate': 0.8, 'p99_ms': 10.0}
    out.update(extra)
    return out


def test_aggregate_fleet_fps_is_frame_weighted():
    agg = aggregate([_summary(10.0, 2), _summary(100.0, 198)])
    assert agg['fleet_fps'] == pytest.approx(np.average([10.0, 100.0],
                                                        weights=[2, 198]))
    # the deprecated unweighted mean_fps field is gone for good
    assert 'mean_fps' not in agg
    # zero-frame / non-finite sessions cannot poison the fleet rate
    agg = aggregate([_summary(float('inf'), 0), _summary(50.0, 10)])
    assert agg['fleet_fps'] == pytest.approx(50.0)


def test_format_table_tolerates_heterogeneous_summaries():
    table = format_table([{'sid': 0, 'fps': 30.0},
                          {'sid': 1, 'fps': 25.0, 'host_ms': 1.5}])
    lines = table.splitlines()
    assert lines[0].split() == ['sid', 'fps', 'host_ms']
    assert len(lines) == 3
    assert lines[1].split() == ['0', '30']          # missing cell is blank
    assert lines[2].split() == ['1', '25', '1.5']


def _tick(tick, **extra):
    entry = {'tick': tick, 'frames': 2, 'sorted_slots': 1, 'sort_ms': 0.2,
             'shade_ms': 2.0}
    entry.update(extra)
    return entry


def test_tick_rollup_legacy_logs_omit_async_keys():
    roll = tick_rollup([_tick(0), _tick(1)])
    for key in ('p50_frame_ms', 'p95_frame_ms', 'host_ms', 'host_overlap'):
        assert key not in roll
    assert roll['ticks'] == 2


def test_tick_rollup_warmup_slices_everything():
    roll = tick_rollup([_tick(0), _tick(1)], warmup_ticks=5)
    assert roll == {'ticks': 0, 'mean_sorts_per_tick': 0.0,
                    'max_sorts_per_tick': 0, 'mean_sort_ms': 0.0,
                    'mean_shade_ms': 0.0}


def test_tick_rollup_overlap_gt_one_warns_unclamped():
    """Satellite (b): overlap is a subset of host time, so ratio > 1 is an
    accounting bug — surfaced as a warning and an UNclamped value, not
    silently min()'d to 1.0."""
    log = [_tick(0, host_ms=1.0, overlap_ms=1.5),
           _tick(1, host_ms=1.0, overlap_ms=1.5)]
    with pytest.warns(RuntimeWarning, match='accounting bug'):
        roll = tick_rollup(log)
    assert roll['host_overlap'] == pytest.approx(1.5)
    # and the legitimate range stays warning-free
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        roll = tick_rollup([_tick(0, host_ms=2.0, overlap_ms=1.0)])
    assert roll['host_overlap'] == pytest.approx(0.5)
