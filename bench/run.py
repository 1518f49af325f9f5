#!/usr/bin/env python3
"""On-chip benchmark of the Lumina render server.

    python3 bench/run.py --workload lumina-1080p.cowatch2 --seed 7 \
        --seconds 45 --trace 0

Runs one cell of ``BENCHMARK.json`` on the chip this process finds, from
the root of a checkout: makes the scene from ``--seed``, builds the render
server on it, warms every program the cell's traffic will call (set-up),
then serves the cell's closed-loop viewers and checks the pixels it
served, on cache groups drawn from the seed, against the plain Lumina
replay of ``references/<name>.py``.  The traffic file's ``setup_ticks``
(default 2) ticks run in set-up, the first admitting every viewer.

The window is made of whole sharing cycles: ``window`` ticks of the
configuration (its S2 sharing window), in which every sort group sorts
once.  It runs as many cycles as fit in ``--seconds``, judged by the
cycles so far, and at least one; so its rate and tail are those of the
cell's steady traffic, sort ticks included.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and reports its per-layer metrics, with the
device's busy time and a breakdown.  The last line of standard output is
the result as one JSON object; the numbers compared for ``correct`` are
the last lines of standard error.  Exits non-zero, printing no result,
when JAX finds no TPU, fewer chips than the cell asks for, or no program
beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / 'bench_out'


@dataclasses.dataclass
class Reading:
    """What a per-layer reader sees: the reduced trace of the window, the
    frames delivered in it, and the run's counts."""

    trace: object
    frames: list
    ticks: int
    sorts: int
    cfg: dict
    peaks: dict

    @property
    def pixels(self) -> int:
        return self.cfg['width'] * self.cfg['height']


def p95(values: list) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), 95))


def setup_jax():
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', str(ROOT / '.jax_cache'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return jax


def window(sut, cycle: int, seconds: float, clock):
    """Tick the server over whole cycles of ``cycle`` ticks, as many as fit
    in ``seconds`` by the length of the cycles so far, and at least one;
    yields the frames each tick delivered."""
    import jax
    t0, ticks = clock(), 0
    while True:
        for _ in range(cycle):
            with jax.profiler.TraceAnnotation('bench.tick'):
                got = sut.tick()
            ticks += 1
            yield got
        cycles = ticks // cycle
        if (clock() - t0) * (cycles + 1) / cycles > seconds:
            return


def run(workload: str, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, *, chips: int = 1, limits: dict | None = None,
        require_chip: bool = True, log=print) -> dict | None:
    """One run of a cell; returns the result dict, or None without a chip."""
    import jax
    from harness import check, scene as bscene, traffic as btraffic
    from harness.compile_log import CompileLog
    from harness.peaks import peaks_for
    from harness import spec

    devs = jax.devices()
    if require_chip and (devs[0].platform != 'tpu' or len(devs) < chips):
        print(f'bench: needs {chips} TPU chip(s); JAX found {len(devs)} x '
              f'{devs[0].platform} ({devs[0].device_kind})', file=sys.stderr)
        return None
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if require_chip else None
    compiles = CompileLog()
    system_mod = spec.system(cfg['system'])
    reference = spec.reference(cfg['reference'])

    # -- set-up: scene, server, warm-up, admission ---------------------------
    scene = bscene.make_scene(seed, cfg['num_gaussians'])
    viewers = btraffic.viewers(traffic, seed)
    fov = traffic['orbit']['fov_x_deg']
    intr = btraffic.intrinsics(cfg['width'], cfg['height'], fov)
    g = reference.group_tiles(-(-intr.width // 16), -(-intr.height // 16),
                              int(cfg['group_tiles']))
    groups = check.sample_groups(intr.width, intr.height, g,
                                 int(traffic['check_groups']), seed)
    boxes = [(gy * g * 16, gx * g * 16, g * 16) for gx, gy in groups]
    clock = time.perf_counter
    sut = system_mod.System(cfg, traffic, scene, viewers, intr, clock, boxes)
    sut.setup(int(traffic.get('setup_ticks', 2)))
    jax.block_until_ready(scene)
    del scene
    last = {}
    for f in sut.frames_out:
        last[f.vid] = f.delivered
    compiles_before = compiles.compiles

    # -- the measured window -------------------------------------------------
    trace_dir = OUT / f'trace-{workload}'
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    cycle = int(cfg['window'])
    t0 = clock()
    frames, latencies, ticks, sorts = [], [], 0, 0
    with jax.profiler.TraceAnnotation('bench.window'):
        for got in window(sut, cycle, seconds, clock):
            ticks += 1
            sorts += sut.last_sorts
            for f in got:
                latencies.append((f.delivered - last[f.vid]) * 1e3)
                last[f.vid] = f.delivered
            frames += got
    t1 = clock()
    if trace:
        jax.profiler.stop_trace()
    setup_s = t0 - T_START
    window_s = t1 - t0
    in_window = compiles.compiles - compiles_before
    # the TPU runtime keeps programs' scratch apart from buffers: the
    # device's peak is the buffers' peak plus what it reserved for scratch
    stats = dev.memory_stats() or {}
    mem_buffers = int(stats.get('peak_bytes_in_use', 0))
    mem_scratch = int(stats.get('peak_bytes_reserved', 0))
    mem_peak = mem_buffers + mem_scratch
    counters = sut.counters()
    log(f'window: {len(frames)} frames in {ticks} ticks ({ticks // cycle} '
        f'cycles) over {window_s:.6f} s ({sorts} sorts); {len(latencies)} '
        f'frame latencies behind the p95; '
        f'{in_window} compiles in the window; set-up {setup_s:.6f} s '
        f'({compiles.compiles} compiles, {compiles.seconds:.3f} s, cache '
        f'{compiles.hits} hits / {compiles.misses} misses); peak HBM '
        f'{mem_peak} B ({mem_buffers} B of buffers, {mem_scratch} B reserved '
        f'for program scratch); {counters}')

    # -- correctness: served pixels against the plain reference ------------
    served = list(sut.frames_out)
    window_frames = frames
    del frames
    sut.close()
    del sut
    gc.collect()
    t_ref = clock()
    numbers = compare(served, viewers, intr, cfg, groups, seed, reference)
    if limits is None:
        limits = check.load_limits(BENCH, workload)
    failed = sum(counters.values())
    correct = (bool(window_frames) and failed == 0
               and check.judge(numbers, limits))
    log(f'check: {len(served)} frames x {len(groups)} cache groups against '
        f'the reference in {clock() - t_ref:.3f} s')

    # -- metrics -------------------------------------------------------------
    result = {'correct': correct, 'attempted': len(window_frames) + failed,
              'failed': failed, 'metrics': {},
              'device': {'platform': dev.platform, 'kind': dev.device_kind,
                         'count': len(devs[:chips]),
                         'memory_peak_bytes': mem_peak}}
    entries = spec.metrics('per_layer' if trace else 'end_to_end', workload)
    if trace:
        from harness import trace as btrace
        red = btrace.reduce_file(btrace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        reading = Reading(red, window_frames, ticks, sorts, cfg, peaks)
        for m in entries:
            value = spec.reader(m['name'])(reading)
            if value is not None:
                result['metrics'][m['name']] = {'value': value,
                                                'unit': m['unit']}
        result['device'].update(busy_s=red.busy_s, window_s=red.window_s)
        top = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:10]
        result['breakdown'] = {
            'device_ops': [[k if len(k) <= 200 else k[:197] + '...', v]
                           for k, v in top],
            'idle_gaps': [[label, s] for s, label in red.gaps[:10]]}
    else:
        e2e = {'frames_per_s': len(window_frames) / window_s,
               'frame_p95_ms': p95(latencies) if latencies else None,
               'setup_s': setup_s}
        for m in entries:
            if e2e.get(m['name']) is not None:
                result['metrics'][m['name']] = {'value': e2e[m['name']],
                                                'unit': m['unit']}
    compared = {k: {'value': numbers[k], 'limit': limits[k]} for k in limits}
    compared['failed_frames'] = {'value': failed, 'limit': 0}
    result['compared'] = compared
    for k, v in compared.items():
        print(f'compared {k}: {v["value"]!r} (limit {v["limit"]!r})',
              file=sys.stderr)
    return result


def replay_frames(served: list, viewers: list, intr, cfg: dict, groups: list,
                  seed: int, reference, dtype=None):
    """Replay every served frame on the sampled groups with the reference;
    yields (frame, reference crops) tick by tick."""
    import jax.numpy as jnp
    from harness import scene as bscene
    ref_scene = bscene.make_scene(seed, cfg['num_gaussians'])
    rep = reference.Replay(ref_scene, intr, cfg, groups,
                           {v.vid: v.scene_block for v in viewers},
                           [v.vid for v in viewers],
                           dtype=dtype or jnp.float32)
    by_tick = {}
    for f in served:
        by_tick.setdefault(f.tick, []).append(f)
    window = int(cfg['window'])
    for tick in sorted(by_tick):
        todo = []
        for f in by_tick[tick]:
            orbit = viewers[f.sort[0]].orbit
            i = f.sort[1]
            sort_pose = reference.predict(orbit.pose(max(i - 1, 0)),
                                          orbit.pose(i), i == 0, window)
            todo.append((f.vid, viewers[f.vid].orbit.pose(f.index), f.sort,
                         sort_pose))
        out = rep.tick(todo)
        for f in by_tick[tick]:
            yield f, out[f.vid]


def compare(served, viewers, intr, cfg, groups, seed, reference) -> dict:
    from harness import check
    gaps = check.Gaps()
    for f, want in replay_frames(served, viewers, intr, cfg, groups, seed,
                                 reference):
        gaps.add(f.crops, want)
    return gaps.numbers()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / 'src' / 'repro').is_dir():
        print(f'bench: no program beside the benchmark ({ROOT / "src"})',
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / 'src'))
    sys.path.insert(0, str(BENCH))
    from harness import spec
    setup_jax()
    work, cfg, traffic = spec.cell(args.workload)
    result = run(args.workload, cfg, traffic, args.seed, args.seconds,
                 bool(args.trace), chips=int(work['chips']))
    if result is None:
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
