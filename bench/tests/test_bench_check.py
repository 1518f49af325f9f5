"""The comparison that decides ``correct``, at a size a test run holds.

A run of a cell, with the harness's look for a chip skipped and the
render server on its pure-JAX backend, must come out correct; the same run
with the timed path broken underneath must not, once per fault the cells
can have; and the control (the reference replayed in bfloat16 in the
program's place) must fail the cell's limits.  The limits are the cells'
own, from ``bench/limits/``.
"""
import time

import jax.numpy as jnp
import pytest

import run as brun
from harness import check, scene as bscene, spec, traffic as btraffic

TINY = dict(num_gaussians=2000, width=96, height=64, capacity=128,
            backend='reference')
CELLS = [w['name'] for w in spec.benchmark()['workloads']]


def tiny_cell(name):
    _, cfg, mix = spec.cell(name)
    return dict(cfg, **TINY), mix


def serve(name, seed=2**33 + 7):
    cfg, mix = tiny_cell(name)
    return brun.run(name, cfg, mix, seed, 1.0, False, require_chip=False,
                    log=lambda *_: None)


@pytest.fixture
def broken_shade(monkeypatch):
    """Replace the server's shade step with a broken one."""
    import repro.serve.stepper as stepper_mod
    real = stepper_mod.batched_shade_phase

    def install(kind):
        def shade(scene, shared, priv, cams, sorted_flags, active, cfg,
                  viewers_per_scene=1):
            if kind == 'half_batch':
                s = active.shape[0]
                active = active & (jnp.arange(s) < s // 2)
            new_shared, new_priv, images, stats = real(
                scene, shared, priv, cams, sorted_flags, active, cfg,
                viewers_per_scene)
            if kind == 'state_unchanged':
                new_shared = shared
            if kind == 'answer_altered':
                images = images * 0.97
            return new_shared, new_priv, images, stats
        monkeypatch.setattr(stepper_mod, 'batched_shade_phase', shade)
    return install


@pytest.mark.parametrize('name', CELLS)
def test_sound_run_is_correct(name):
    res = serve(name)
    assert res['correct'], res['compared']
    assert res['failed'] == 0 and res['attempted'] > 0
    assert list(res)[-1] == 'compared'


@pytest.mark.parametrize('fault', ['state_unchanged', 'half_batch',
                                   'answer_altered'])
@pytest.mark.parametrize('name', CELLS)
def test_broken_timed_path_is_not_correct(name, fault, broken_shade):
    broken_shade(fault)
    res = serve(name)
    assert not res['correct'], (fault, res['compared'])


@pytest.mark.parametrize('name', CELLS)
def test_cache_that_never_hits_is_not_correct(name, monkeypatch):
    """The radiance cache's decisions shape the pixels: a cache whose
    probe never matches serves fresh colors where the replay hits."""
    import repro.core.radiance_cache as rc
    monkeypatch.setattr(rc, '_match',
                        lambda tags, ids: jnp.zeros(tags.shape[:-1], bool))
    res = serve(name)
    assert not res['correct'], res['compared']


@pytest.mark.parametrize('name', CELLS)
def test_bfloat16_control_fails_the_limits(name):
    cfg, mix = tiny_cell(name)
    seed = 31
    sys_mod = spec.system(cfg['system'])
    ref = spec.reference(cfg['reference'])
    intr = btraffic.intrinsics(cfg['width'], cfg['height'],
                               mix['orbit']['fov_x_deg'])
    g = ref.group_tiles(6, 4, cfg['group_tiles'])
    groups = check.sample_groups(96, 64, g, mix['check_groups'], seed)
    viewers = btraffic.viewers(mix, seed)
    sut = sys_mod.System(cfg, mix, bscene.make_scene(seed, TINY[
        'num_gaussians']), viewers, intr, time.perf_counter,
        [(gy * g * 16, gx * g * 16, g * 16) for gx, gy in groups])
    sut.setup(4)
    served = list(sut.frames_out)
    prog, ctrl = check.Gaps(), check.Gaps()
    args = (viewers, intr, cfg, groups, seed, ref)
    for (f, want), (_, low) in zip(
            brun.replay_frames(served, *args),
            brun.replay_frames(served, *args, dtype=jnp.bfloat16)):
        prog.add(f.crops, want)
        ctrl.add(low, want)
    limits = check.load_limits(spec.BENCH, name)
    assert check.judge(prog.numbers(), limits), prog.numbers()
    assert not check.judge(ctrl.numbers(), limits), ctrl.numbers()
