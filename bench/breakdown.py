#!/usr/bin/env python3
"""Where a cell's time goes, by stage of the shade step and by span of the
server's host loop: one run of a benchmark cell with the program's own
tracing on.

    python3 bench/breakdown.py --workload lumina-1080p.cowatch2 --seed 7 \
        --seconds 45 --out bench_out/breakdown.json

Not part of the benchmark's command.  Sets the cell up as ``run.py`` does,
then ticks three windows of whole sharing cycles, the middle one traced
(with ``--control``, none).  The traced window runs under a
``jax.profiler`` trace with a live ``repro.obs.Tracer`` on the session
manager, so the trace holds the program's ``lumina.<span>`` host spans
beside the benchmark's ``bench.<span>`` ones, and every device operation
of the shade program its ``shade/<stage>`` scope (``harness.stages``).

Prints one JSON object (and writes it to ``--out``): frames/s of each
window (the traced window against a ``--control`` run's middle window, on
the same seed, is the cost of tracing); device ms per frame of each stage,
by the operations' own scopes and with the unscoped ones placed in the
stage they ran amid, of the shade runs and of their unscoped remainder,
with each stage's largest operations; host ms per tick of the program's
spans, ``dispatch`` being ``step_dispatch`` less the ``sort_wait`` it
holds; the idle gaps labelled by the innermost span; and the benchmark's
own per-layer metrics read from the same trace.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def serve_window(sut, cycle: int, seconds: float, last: dict) -> dict:
    """Tick one window of whole cycles inside a ``bench.window`` span.  A
    frame is due when its viewer's previous one was delivered, or when the
    window opened if that was later (the profiler's start and stop lie
    between windows)."""
    import jax
    import run as brun
    clock = time.perf_counter
    frames, latencies, ticks, sorts = [], [], 0, 0
    t0 = clock()
    for vid in last:
        last[vid] = max(last[vid], t0)
    with jax.profiler.TraceAnnotation('bench.window'):
        for got in brun.window(sut, cycle, seconds, clock):
            ticks += 1
            sorts += sut.last_sorts
            for f in got:
                latencies.append((f.delivered - last[f.vid]) * 1e3)
                last[f.vid] = f.delivered
            frames += got
    window_s = clock() - t0
    return {'frames': frames, 'ticks': ticks, 'sorts': sorts,
            'window_s': window_s,
            'frames_per_s': len(frames) / window_s,
            'frame_p95_ms': brun.p95(latencies) if latencies else None}


def per_tick_ms(spans: dict, lo: float, hi: float, ticks: int) -> dict:
    """Host ms per tick of each program span, clipped to the window."""
    from harness import stages, trace
    return {name[len(stages.PROGRAM_PREFIX):]:
            sum(e - s for s, e in trace.clip(ivs, lo, hi)) / ticks * 1e3
            for name, ivs in sorted(spans.items())
            if name.startswith(stages.PROGRAM_PREFIX)}


def breakdown(st, frames: int, ticks: int, top: int = 5) -> dict:
    """The reduced stages of the traced window, per frame and per tick."""
    from harness import stages
    ms = lambda s: s / frames * 1e3
    lo, hi = st.window
    by_stage = {}
    for (scope, where, name), sec in st.ops.items():
        key = scope or f'unscoped, amid {where}'
        by_stage.setdefault(key, []).append((sec, name))
    largest = {stage: [[name[:160], ms(sec)]
                       for sec, name in sorted(ops, reverse=True)[:top]]
               for stage, ops in sorted(by_stage.items())}
    placed = st.placed_s
    return {
        'stage_ms_per_frame': {k: ms(v) for k, v in
                               sorted(st.stage_s.items())},
        'placed_ms_per_frame': {k: ms(v) for k, v in sorted(placed.items())},
        'shade_ms_per_frame': ms(st.shade_s),
        'unscoped_ms_per_frame': ms(st.unscoped_s),
        'coverage': st.coverage,
        'placed_coverage': (sum(placed.values()) / st.shade_s
                            if st.shade_s > 0 else 0.0),
        'largest_ops_ms_per_frame': largest,
        'dispatch_ms_per_tick': stages.self_time(
            st.spans, 'lumina.step_dispatch', 'lumina.sort_wait', lo, hi)
        / ticks * 1e3,
        'span_ms_per_tick': per_tick_ms(st.spans, lo, hi, ticks),
        'idle_gaps': [[label, sec] for sec, label in st.gaps]}


def measure(workload: str, cfg: dict, traffic: dict, seed: int,
            seconds: float, *, require_chip: bool = True,
            keep_trace: str | None = None,
            control: bool = False) -> dict | None:
    """The run the module describes; with ``control`` its middle window
    runs untraced too, and only the windows are reported: the same seed's
    traced run against it is the cost of tracing, window for window."""
    import jax
    import run as brun
    from harness import check, scene as bscene, spec, stages, trace
    from harness import traffic as btraffic
    from harness.peaks import peaks_for
    from repro.obs import NULL, Tracer

    devs = jax.devices()
    if require_chip and devs[0].platform != 'tpu':
        print(f'breakdown: needs a TPU; JAX found {devs[0].platform}',
              file=sys.stderr)
        return None
    scene = bscene.make_scene(seed, cfg['num_gaussians'])
    viewers = btraffic.viewers(traffic, seed)
    intr = btraffic.intrinsics(cfg['width'], cfg['height'],
                               traffic['orbit']['fov_x_deg'])
    reference = spec.reference(cfg['reference'])
    g = reference.group_tiles(-(-intr.width // 16), -(-intr.height // 16),
                              int(cfg['group_tiles']))
    groups = check.sample_groups(intr.width, intr.height, g,
                                 int(traffic['check_groups']), seed)
    boxes = [(gy * g * 16, gx * g * 16, g * 16) for gx, gy in groups]
    sut = spec.system(cfg['system']).System(
        cfg, traffic, scene, viewers, intr, time.perf_counter, boxes)
    sut.setup(int(traffic.get('setup_ticks', 2)))
    del scene
    last = {f.vid: f.delivered for f in sut.frames_out}
    cycle = int(cfg['window'])

    before = serve_window(sut, cycle, seconds, last)
    if control:
        middle = serve_window(sut, cycle, seconds, last)
        after = serve_window(sut, cycle, seconds, last)
        sut.close()
        return {'workload': workload, 'seed': seed, 'control': True,
                'device': devs[0].device_kind,
                'windows': _windows(before, middle, after)}
    trace_dir = brun.OUT / f'breakdown-{workload}'
    shutil.rmtree(trace_dir, ignore_errors=True)
    sut.mgr.tracer = sut.stepper.tracer = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        traced = serve_window(sut, cycle, seconds, last)
    finally:
        jax.profiler.stop_trace()
        sut.mgr.tracer = sut.stepper.tracer = NULL
    after = serve_window(sut, cycle, seconds, last)

    path = trace.find_xplane(trace_dir)
    red = trace.reduce_file(path)
    st = stages.reduce_file(path)
    if keep_trace:
        Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
        with open(path, 'rb') as src, gzip.open(keep_trace, 'wb') as dst:
            shutil.copyfileobj(src, dst)
    shutil.rmtree(trace_dir, ignore_errors=True)
    frames, ticks = traced['frames'], traced['ticks']
    peaks = peaks_for(devs[0].device_kind) if require_chip else None
    reading = brun.Reading(red, frames, ticks, traced['sorts'], cfg, peaks)
    per_layer = {}
    for m in spec.metrics('per_layer', workload) if peaks else ():
        value = spec.reader(m['name'])(reading)
        if value is not None:
            per_layer[m['name']] = value
    sut.close()
    return {'workload': workload, 'seed': seed, 'device': devs[0].device_kind,
            'windows': _windows(before, traced, after),
            'busy_s': red.busy_s, 'window_s': red.window_s,
            **breakdown(st, len(frames), ticks),
            'per_layer': per_layer}


def _windows(before: dict, middle: dict, after: dict) -> dict:
    return {k: {key: w[key] for key in ('frames_per_s', 'frame_p95_ms',
                                        'window_s', 'ticks')}
            | {'frames': len(w['frames'])}
            for k, w in (('before', before), ('middle', middle),
                         ('after', after))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--out')
    ap.add_argument('--keep-trace', metavar='FILE.xplane.pb.gz',
                    help="keep the traced window's profile, gzipped")
    ap.add_argument('--control', action='store_true',
                    help='trace no window: the control for the cost of '
                         'tracing, on the same seed')
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / 'src'))
    sys.path.insert(0, str(BENCH))
    import run as brun
    from harness import spec
    brun.setup_jax()
    _, cfg, traffic = spec.cell(args.workload)
    result = measure(args.workload, cfg, traffic, args.seed, args.seconds,
                     keep_trace=args.keep_trace, control=args.control)
    if result is None:
        return 3
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + '\n')
    print(line, flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
