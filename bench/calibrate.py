#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, many seeds in one process.

    python3 bench/calibrate.py --workload blender-800.distinct4 \
        --seeds 101,102,103 --seconds 45 --out calib.jsonl

For each seed: serves the cell as ``run.py`` does (the first seed builds
and warms the server, later seeds restart it on a new scene with the
stepper's own cold start), then reads

* ``program``: the served pixels against the float32 reference replay;
* ``control``: the reference replayed in bfloat16, in the program's place,
  against the same float32 replay.

Prints one JSON line per seed (and appends it to ``--out``).  Not part of
the benchmark's command: the limits in ``bench/limits/`` are set from its
readings, as ``PERF.md`` records.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def read_seed(sut, workload, cfg, traffic, seed, seconds, reference):
    import jax.numpy as jnp
    import run as brun
    from harness import check, scene as bscene, traffic as btraffic
    scene = bscene.make_scene(seed, cfg['num_gaussians'])
    viewers = btraffic.viewers(traffic, seed)
    ticks = int(traffic.get('setup_ticks', 2))
    sut.restart(scene, viewers, ticks)
    for _ in brun.window(sut, int(cfg['window']), seconds, time.perf_counter):
        pass
    served = list(sut.frames_out)
    prog, ctrl = check.Gaps(), check.Gaps()
    args = (viewers, sut.intr, cfg, sut.groups, seed, reference)
    for (f, want), (_, low) in zip(
            brun.replay_frames(served, *args),
            brun.replay_frames(served, *args, dtype=jnp.bfloat16)):
        prog.add(f.crops, want)
        ctrl.add(low, want)
    return {'workload': workload, 'seed': seed, 'frames': len(served),
            'groups': len(sut.groups), 'program': prog.numbers(),
            'control': ctrl.numbers(),
            'hit_rate': sum(f.hit_rate for f in served) / len(served)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--out')
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / 'src'))
    sys.path.insert(0, str(BENCH))
    import run as brun
    from harness import check, scene as bscene, spec, traffic as btraffic
    jax = brun.setup_jax()
    if jax.devices()[0].platform != 'tpu':
        print('calibrate: no TPU', file=sys.stderr)
        return 3
    _, cfg, traffic = spec.cell(args.workload)
    reference = spec.reference(cfg['reference'])
    system_mod = spec.system(cfg['system'])
    seeds = [int(s) for s in args.seeds.split(',')]
    viewers = btraffic.viewers(traffic, seeds[0])
    intr = btraffic.intrinsics(cfg['width'], cfg['height'],
                               traffic['orbit']['fov_x_deg'])
    g = reference.group_tiles(-(-intr.width // 16), -(-intr.height // 16),
                              int(cfg['group_tiles']))
    sut = None
    for seed in seeds:
        groups = check.sample_groups(intr.width, intr.height, g,
                                     int(traffic['check_groups']), seed)
        boxes = [(gy * g * 16, gx * g * 16, g * 16) for gx, gy in groups]
        if sut is None:
            sut = system_mod.System(
                cfg, traffic, bscene.make_scene(seed, cfg['num_gaussians']),
                viewers, intr, time.perf_counter, boxes)
            sut.setup(0)
        sut.intr, sut.groups, sut.boxes = intr, groups, boxes
        row = read_seed(sut, args.workload, cfg, traffic, seed, args.seconds,
                        reference)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, 'a') as fh:
                fh.write(json.dumps(row) + '\n')
    return 0


if __name__ == '__main__':
    sys.exit(main())
