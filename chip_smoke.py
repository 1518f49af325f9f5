"""Smoke test of the render server's kernel path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip fleet phase only

One chip, three phases, one process:

1. **paper** — the paper configuration (``configs/lumina_3dgs.py``: 1M
   Gaussians, 1920x1080, capacity 1024, k_record 5, group_tiles 4,
   window 6, ``sort_method='sorted'``, the default cache) served through
   ``SessionManager`` -> ``BatchedStepper`` -> the Pallas kernels: 2 scenes
   x 2 viewers, 12 frames each (two sort windows).  The compiled shade must
   hold the native kernels (``tpu_custom_call``); no frame may be dropped,
   shed or quarantined, and every image must be finite.
2. **oracle** — the pure-JAX reference backend needs ~50 GB of HBM at
   paper size (the TPU compiler's own count), so the kernel path is held to
   it at ``LuminaArchConfig.reduced()`` on the same chip: per frame PSNR >=
   40 dB against the reference and hit rates within 0.02.
3. the compile time and persistent-cache use of the process.

``--chips 4`` runs only the fleet: a 4-worker fleet on 4 distinct chips,
then the same seeded trace on a 4-worker fleet oversubscribed onto chip 0.
Each worker's state must sit on its own chip; per-session frame counts,
hit rates and final cache tags must be equal between the two fleets, and
images must agree within the bound above.  It runs at the reduced
configuration: placement and equality are what it checks.

Any failed check exits non-zero before the last line.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and nothing else.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

PSNR_MIN_DB = 40.0
HIT_RATE_TOL = 0.02
FRAMES = 12              # two sort windows of the paper's window 6


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Seconds spent in backend compiles (or persistent-cache reads, which
    JAX times under the same event), and persistent-cache hits/misses."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(name, secs, **_):
            if name == '/jax/core/compile/backend_compile_duration':
                self.seconds += secs

        def on_event(name, **_):
            if name == '/jax/compilation_cache/cache_hits':
                self.hits += 1
            elif name == '/jax/compilation_cache/cache_misses':
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def record_frames(mgr, keep_images: bool, log: bool = False) -> dict:
    """Observe every frame the manager delivers: ``(sid, frame) ->
    (finite, hit_rate, image or None)``; ``log`` prints one line a tick."""
    import jax.numpy as jnp
    import numpy as np
    frames = {}
    observe = mgr.observe_tick

    def observe_tick(plan, outputs, host=None):
        for slot, (img, stats, _timing) in outputs.items():
            sess = mgr.slot_session[slot]
            frames[(sess.sid, sess.cursor)] = (
                bool(jnp.isfinite(img).all()), float(stats.hit_rate),
                np.asarray(img) if keep_images else None)
        n = observe(plan, outputs, host)
        if log and outputs:
            t = mgr.tick_log[-1]
            print(f'  tick {t["tick"]}: {t["frames"]} frames, '
                  f'{t["sorted_slots"]} sorts, {t["latency_ms"]:.1f} ms',
                  flush=True)
        return n

    mgr.observe_tick = observe_tick
    return frames


def counter(registry, name: str) -> int:
    return sum(registry[k].value for k in registry.names()
               if k == name or k.startswith(name + '{'))


def lumina_config(arch, backend: str):
    from repro.core.pipeline import LuminaConfig
    return LuminaConfig(capacity=arch.capacity, window=arch.window,
                        margin=arch.margin, k_record=arch.k_record,
                        group_tiles=arch.group_tiles,
                        sort_method=arch.sort_method, backend=backend)


def serve_once(arch, backend: str, *, seed: int, keep_images: bool,
               log: bool = False):
    """2 scenes x 2 viewers, FRAMES frames each, through the render
    server's own helpers.  Returns (frames, manager, stepper)."""
    import jax
    from repro.data.scenes import structured_scene
    from repro.serve.render import build_sessions
    from repro.serve.session import SessionManager
    from repro.serve.stepper import BatchedStepper
    cfg = lumina_config(arch, backend)
    scene = structured_scene(jax.random.PRNGKey(seed), arch.num_gaussians)
    sessions = build_sessions(4, FRAMES, width=arch.width,
                              height=arch.height, stagger=0,
                              viewers_per_scene=2)
    stepper = BatchedStepper(scene, cfg, sessions[0].cams[0], 4,
                             viewers_per_scene=2)
    mgr = SessionManager(stepper, 4)
    frames = record_frames(mgr, keep_images, log)
    for sess in sessions:
        check(mgr.submit(sess), f'session {sess.sid} was shed at submit')
    finished = mgr.run(driver='sync')
    check(sorted(s.sid for s in finished) == [0, 1, 2, 3],
          f'{backend}: finished sessions {[s.sid for s in finished]}')
    for sess in finished:
        check(sess.telemetry.frames == FRAMES,
              f'{backend}: sid {sess.sid} served {sess.telemetry.frames} '
              f'of {FRAMES} frames')
    check(len(frames) == 4 * FRAMES,
          f'{backend}: {len(frames)} frames delivered, want {4 * FRAMES}')
    for name in ('serve.quarantined', 'serve.shed', 'serve.degraded_ticks'):
        check(counter(mgr.metrics, name) == 0,
              f'{backend}: {name} = {counter(mgr.metrics, name)}')
    bad = [k for k, (finite, _, _) in frames.items() if not finite]
    check(not bad, f'{backend}: non-finite images at (sid, frame) {bad}')
    return frames, mgr, stepper


def shade_has_kernels(stepper) -> bool:
    """Compile the stepper's full-width shade (a persistent-cache hit after
    serving) and look for the Mosaic kernels in the compiled text."""
    import jax.numpy as jnp
    from repro.core.camera import stack_cameras
    s = stepper.slots
    lowered = stepper._shade.lower(
        stepper.scene, stepper.shared, stepper.priv,
        stack_cameras(stepper._slot_cams), jnp.zeros((s,), jnp.float32),
        jnp.ones((s,), bool))
    return 'tpu_custom_call' in lowered.compile().as_text()


def compare(got: dict, want: dict, what: str) -> tuple:
    """Per-frame PSNR and hit-rate agreement; returns (min PSNR, max |dhit|)."""
    import numpy as np
    check(sorted(got) == sorted(want), f'{what}: frame sets differ')
    worst_psnr, worst_hit = float('inf'), 0.0
    for key in sorted(got):
        _, h_a, img_a = got[key]
        _, h_b, img_b = want[key]
        mse = float(np.mean((img_a.astype(np.float64) - img_b) ** 2))
        psnr = 10.0 * np.log10(1.0 / max(mse, 1e-12))
        worst_psnr = min(worst_psnr, psnr)
        worst_hit = max(worst_hit, abs(h_a - h_b))
        check(psnr >= PSNR_MIN_DB,
              f'{what}: (sid, frame) {key} PSNR {psnr:.2f} dB < '
              f'{PSNR_MIN_DB}')
        check(abs(h_a - h_b) <= HIT_RATE_TOL,
              f'{what}: (sid, frame) {key} hit rate {h_a} vs {h_b}')
    return worst_psnr, worst_hit


def one_chip(dev, seed: int) -> None:
    from repro.configs.lumina_3dgs import CONFIG
    from repro.kernels import ops

    check(not ops.default_interpret(), 'kernels would be interpreted')
    arch = CONFIG
    print(f'paper config: {arch.num_gaussians} Gaussians, '
          f'{arch.width}x{arch.height}, capacity {arch.capacity}, k_record '
          f'{arch.k_record}, group_tiles {arch.group_tiles}, window '
          f'{arch.window}, sort {arch.sort_method}, 2 scenes x 2 viewers, '
          f'{FRAMES} frames each, backend pallas', flush=True)
    t0 = time.perf_counter()
    frames, mgr, stepper = serve_once(arch, 'pallas', seed=seed,
                                      keep_images=False, log=True)
    wall = time.perf_counter() - t0
    check(shade_has_kernels(stepper),
          'compiled shade has no tpu_custom_call: a kernel was interpreted '
          'or replaced')
    executed = sum(e['scheduled'] + e['admit'] for e in stepper.sort_log)
    windows = FRAMES / arch.window
    hit = [h for _, h, _ in frames.values()]
    print(f'paper: {len(frames)} frames served in {mgr.tick} ticks '
          f'({wall:.1f} s wall, compile included), 0 dropped/shed/'
          f'quarantined, all finite; native kernels in the compiled shade',
          flush=True)
    print(f'paper: {executed} sorts executed in {windows:g} windows '
          f'({executed / windows:.2f} per window for 2 scenes x 2 viewers), '
          f'mean hit rate {sum(hit) / len(hit):.4f} (last frame per viewer '
          f'{[round(frames[(s, FRAMES - 1)][1], 4) for s in range(4)]})',
          flush=True)
    stats = dev.memory_stats() or {}
    print(f'paper: peak HBM {stats.get("peak_bytes_in_use", "n/a")} bytes '
          f'of {stats.get("bytes_limit", "n/a")}', flush=True)
    del frames, mgr, stepper
    gc.collect()

    small = arch.reduced()
    print(f'oracle config: LuminaArchConfig.reduced() = '
          f'{small.num_gaussians} Gaussians, {small.width}x{small.height}, '
          f'capacity {small.capacity}, sort {small.sort_method} (the '
          f'reference backend does not fit 16 GB at paper size)', flush=True)
    got, _, _ = serve_once(small, 'pallas', seed=seed, keep_images=True)
    want, _, _ = serve_once(small, 'reference', seed=seed, keep_images=True)
    psnr, dhit = compare(got, want, 'kernels vs reference')
    print(f'oracle: {len(got)} frames, min PSNR vs reference {psnr:.2f} dB '
          f'(bound {PSNR_MIN_DB}), max |hit rate diff| {dhit:.4f} (bound '
          f'{HIT_RATE_TOL})', flush=True)


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from repro.configs.lumina_3dgs import CONFIG
    from repro.data.scenes import structured_scene
    from repro.serve.fleet import FleetManager, SyncFleetDriver
    from repro.serve.render import build_sessions

    devices = jax.devices()
    check(len(devices) >= 4, f'--chips 4 needs 4 devices, have {len(devices)}')
    arch = CONFIG.reduced()
    cfg = lumina_config(arch, 'pallas')
    scene = structured_scene(jax.random.PRNGKey(seed), arch.num_gaussians)
    print(f'fleet config: LuminaArchConfig.reduced() = '
          f'{arch.num_gaussians} Gaussians, {arch.width}x{arch.height}, '
          f'capacity {arch.capacity}; 4 workers x 1 slot, 4 viewers on 4 '
          f'scenes, {FRAMES} frames each, backend pallas', flush=True)

    def run(on):
        sessions = build_sessions(4, FRAMES, width=arch.width,
                                  height=arch.height, stagger=0)
        fm = FleetManager.build(scene, cfg, sessions[0].cams[0],
                                num_devices=4, slots_per_device=1,
                                devices=on)
        recs = [record_frames(w.mgr, keep_images=True) for w in fm.workers]
        for sess in sessions:
            check(fm.submit(sess), f'session {sess.sid} was shed')
        finished = SyncFleetDriver(fm).run()
        frames = {k: v for r in recs for k, v in r.items()}
        check(len(frames) == 4 * FRAMES,
              f'{len(frames)} frames delivered, want {4 * FRAMES}')
        check(all(f for f, _, _ in frames.values()), 'non-finite image')
        counts = {s.sid: s.telemetry.frames for s in finished}
        tags = [np.asarray(w.mgr.stepper.shared.cache.tags)
                for w in fm.workers]
        return fm, frames, counts, tags

    fm_a, got, counts_a, tags_a = run(devices[:4])
    placed = [(str(w.device), sorted(map(str, w.state_devices())))
              for w in fm_a.workers]
    print(f'fleet on 4 chips: worker state placement {placed}', flush=True)
    check(len({d for d, _ in placed}) == 4, 'workers share a chip')
    check(all(held == [d] for d, held in placed),
          'a worker\'s state left its chip')
    fm_b, want, counts_b, tags_b = run(devices[:1])
    check({str(w.device) for w in fm_b.workers} == {str(devices[0])},
          'oversubscribed fleet is not on chip 0')
    check(counts_a == counts_b, f'frame counts {counts_a} vs {counts_b}')
    check(all(np.array_equal(a, b) for a, b in zip(tags_a, tags_b)),
          'final cache tags differ between the fleets')
    hits_equal = all(got[k][1] == want[k][1] for k in got)
    check(hits_equal, 'per-frame hit rates differ between the fleets')
    psnr, _ = compare(got, want, '4 chips vs oversubscribed chip 0')
    print(f'fleet: frames per session {counts_a} on both fleets, hit rates '
          f'and cache tags equal, min PSNR 4-chip vs chip-0 {psnr:.2f} dB',
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1)
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != 'tpu':
        print(f'chip_smoke: no TPU (JAX platform {dev.platform!r})',
              file=sys.stderr)
        return 1
    src = Path(__file__).resolve().parent / 'src'
    sys.path.insert(0, str(src))
    try:
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as e:
        print(f'chip_smoke: the repro package is not beside this script '
              f'({e})', file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    entries = (sum(1 for _ in Path(cache_dir).iterdir())
               if Path(cache_dir).is_dir() else 0)
    compiles = CompileLog()
    print(f'jax {jax.__version__}, {len(devices)} x {dev.device_kind} '
          f'({dev.platform}); compile cache {cache_dir} ({entries} entries '
          f'at start)', flush=True)
    try:
        if args.chips == 4:
            four_chips(args.seed)
            devices = devices[:4]
        else:
            one_chip(dev, args.seed)
            devices = devices[:1]
    except SmokeFailure as e:
        print(f'chip_smoke: FAILED: {e}', file=sys.stderr)
        return 1
    print(f'compile: {compiles.seconds:.1f} s in backend compiles and cache '
          f'reads; persistent cache {compiles.hits} hits, '
          f'{compiles.misses} misses', flush=True)
    print(json.dumps({'ok': True, 'device': {
        'platform': dev.platform, 'kind': dev.device_kind,
        'count': len(devices)}}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
