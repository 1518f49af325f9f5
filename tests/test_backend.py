"""The shade-backend switch: 'pallas' (chunked kernels, miss-compacted RC
resume) vs 'reference' (pure-JAX rasterizer + functional cache).

Contract: the two backends agree on every *integer* decision — cache tags,
LRU age/clock, hit masks, alpha-records — bitwise, across multi-frame runs
and under the serving ``live`` mask.  Images agree to a documented float32
ulp bound (the kernel evaluates alpha densely per chunk and accumulates in
a different association than the sequential reference).  Miss compaction
and all early-termination paths are pure compute savings: they may never
change any output.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import radiance_cache as rc
from repro.core.gaussians import TRANSMITTANCE_EPS
from repro.core.pipeline import (LuminaConfig, LuminSys, batched_shade_phase,
                                 init_fleet)
from repro.core.projection import project
from repro.core.sorting import sort_scene
from repro.core.tiling import gather_tile_features
from repro.core.camera import stack_cameras
from repro.data.trajectory import orbit_trajectory
from repro.kernels import ops
from repro.kernels import rasterize as rk

# images: kernel-vs-reference reassociation bound (see module docstring);
# matches the kernel suite's atol=3e-5 at unit magnitude
IMG_ULPS = 512


def _ulp_close(got, want, ulps=IMG_ULPS, msg=''):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.maximum(np.maximum(np.abs(got), np.abs(want)), 1.0)
    err = np.abs(got - want)
    assert (err <= ulps * np.finfo(np.float32).eps * scale).all(), (
        f'{msg}: max {(err / (np.finfo(np.float32).eps * scale)).max():.0f} '
        f'ulps (> {ulps})')


def test_backend_switch_validated():
    with pytest.raises(ValueError):
        LuminaConfig(backend='cuda')


def test_pallas_backend_matches_reference_luminsys(small_scene, cams64):
    """Full multi-frame LuminSys runs: identical hit rates and cache tags
    every frame, images within the documented ulp bound on both backends."""
    cfg_r = LuminaConfig(capacity=128, window=3)
    cfg_p = dataclasses.replace(cfg_r, backend='pallas')
    sys_r = LuminSys(small_scene, cfg_r, cams64[0])
    sys_p = LuminSys(small_scene, cfg_p, cams64[0])
    for f, cam in enumerate(cams64):
        img_r, st_r = sys_r.step(cam)
        img_p, st_p = sys_p.step(cam)
        _ulp_close(img_p, img_r, msg=f'frame {f}')
        assert float(st_p.hit_rate) == float(st_r.hit_rate), f'frame {f}'
    np.testing.assert_array_equal(np.asarray(sys_p.state.cache.tags),
                                  np.asarray(sys_r.state.cache.tags))
    np.testing.assert_array_equal(np.asarray(sys_p.state.cache.age),
                                  np.asarray(sys_r.state.cache.age))
    np.testing.assert_array_equal(np.asarray(sys_p.state.cache.clock),
                                  np.asarray(sys_r.state.cache.clock))


@pytest.mark.parametrize('backend', ['reference', 'pallas'])
def test_live_mask_idle_lane_contributes_nothing(small_scene, backend):
    """Batched shade with one idle lane: the dead lane reports zero iterated
    work on either backend (on the kernel path it also skips its chunk
    loops), and live lanes are bit-unaffected by the dead lane's presence."""
    cfg = LuminaConfig(capacity=128, window=3, backend=backend)
    traj = orbit_trajectory(2, width=64, height_px=64)
    s = 3
    shared, priv = init_fleet(small_scene, cfg, traj[0], slots=s)
    cams = stack_cameras([traj[0]] * s)
    shade = jax.jit(functools.partial(batched_shade_phase, cfg=cfg))
    ones = jnp.ones((s,), jnp.float32)
    _, _, img_all, _ = shade(small_scene, shared, priv, cams, ones,
                             jnp.ones((s,), bool))
    shared2, priv2 = init_fleet(small_scene, cfg, traj[0], slots=s)
    _, _, img_mask, stats = shade(small_scene, shared2, priv2, cams, ones,
                                  jnp.asarray([True, False, True]))
    # dead lane: zero iterated work, zero hits
    assert float(stats.mean_iterated[1]) == 0.0
    assert float(stats.sig_frac[1]) == 0.0
    # live lanes identical to the all-live run (same compiled program;
    # lanes are independent under vmap)
    np.testing.assert_array_equal(np.asarray(img_mask[0]),
                                  np.asarray(img_all[0]))
    np.testing.assert_array_equal(np.asarray(img_mask[2]),
                                  np.asarray(img_all[2]))


def _projected_feats(scene, cam, capacity=128):
    proj = project(scene, cam)
    lists = sort_scene(proj, cam.width, cam.height, capacity)
    return ops.pad_features(gather_tile_features(proj, lists), 32), lists


def test_miss_compaction_round_trip(small_scene, cams64):
    """gather -> compacted resume -> scatter == full-tile resume, for a
    scattered miss mask: integer state exactly, floats to reassociation
    tolerance — compaction is pure routing, never arithmetic."""
    feats, lists = _projected_feats(small_scene, cams64[0])
    st_a = ops.rasterize_prefix(feats, lists.tiles_x, chunk=32,
                                interpret=True)
    # scattered pseudo-random miss pattern (every 7th pixel + a full tile)
    t, p = st_a.trans.shape
    miss = (jnp.arange(t * p) % 7 == 0).reshape(t, p)
    miss = miss.at[1].set(True)

    colors_f, aux_f, _ = ops.rasterize_resume(
        feats, lists.tiles_x, st_a, miss, chunk=32, interpret=True)
    colors_c, aux_c, chunks_c = ops.rasterize_resume_compacted(
        feats, lists.tiles_x, st_a, miss, chunk=32, interpret=True)

    np.testing.assert_allclose(np.asarray(colors_c), np.asarray(colors_f),
                               atol=3e-5, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(aux_c.alpha_record),
                                  np.asarray(aux_f.alpha_record))
    np.testing.assert_array_equal(np.asarray(aux_c.n_significant),
                                  np.asarray(aux_f.n_significant))
    np.testing.assert_array_equal(np.asarray(aux_c.n_iterated),
                                  np.asarray(aux_f.n_iterated))
    np.testing.assert_array_equal(np.asarray(aux_c.iter_at_k),
                                  np.asarray(aux_f.iter_at_k))


def test_miss_compaction_chunks_scale_with_miss_count(small_scene, cams64):
    """The point of compaction: phase-B chunk work tracks the miss count.
    A single missing tile's worth of pixels must cost far fewer chunk
    iterations than the full-tile resume charges."""
    feats, lists = _projected_feats(small_scene, cams64[0])
    st_a = ops.rasterize_prefix(feats, lists.tiles_x, chunk=32,
                                interpret=True)
    t, p = st_a.trans.shape
    # one miss pixel per tile — the worst case for full-tile resume
    miss = (jnp.arange(t * p) % p == 0).reshape(t, p)
    _, _, chunks_full = ops.rasterize_resume(
        feats, lists.tiles_x, st_a, miss, chunk=32, interpret=True)
    _, _, chunks_cmp = ops.rasterize_resume_compacted(
        feats, lists.tiles_x, st_a, miss, chunk=32, interpret=True)
    full, cmp_ = int(jnp.sum(chunks_full)), int(jnp.sum(chunks_cmp))
    # T scattered misses fit in ceil(T/P) compacted tiles
    assert cmp_ < full, (cmp_, full)
    assert cmp_ <= int(jnp.max(ops.chunk_caps(feats.ids, 32))) * (
        (t + p - 1) // p + 1)


@pytest.mark.parametrize('body', ['dense', 'seq'])
def test_early_termination_never_changes_output(small_scene, cams64, body):
    """Count caps + transmittance-floor early exit are pure compute savings:
    the capped kernel equals an uncapped run on both body flavors, while
    processing strictly fewer chunks on short/terminated tiles."""
    feats, lists = _projected_feats(small_scene, cams64[0])
    t = feats.ids.shape[0]
    k_total = feats.ids.shape[1]
    state = (jnp.zeros((t, rk.P, 3), jnp.float32),
             jnp.ones((t, rk.P), jnp.float32),
             jnp.full((t, rk.P, 5), -1, jnp.int32),
             jnp.zeros((t, rk.P), jnp.int32),
             jnp.zeros((t, rk.P), jnp.int32),
             jnp.ones((t, rk.P), jnp.int32))
    args = dict(tiles_x=lists.tiles_x, k_record=5, chunk=32,
                stop_at_k=False, interpret=True, body=body)
    capped = rk.rasterize_pallas(
        feats.mean2d, feats.conic, feats.color, feats.opacity, feats.ids,
        *state, ncap=ops.chunk_caps(feats.ids, 32), **args)
    uncapped = rk.rasterize_pallas(
        feats.mean2d, feats.conic, feats.color, feats.opacity, feats.ids,
        *state, ncap=None, **args)
    for field in ('record', 'rec_cnt', 'n_sig', 'n_iter', 'iter_at_k'):
        np.testing.assert_array_equal(
            np.asarray(getattr(capped, field)),
            np.asarray(getattr(uncapped, field)), err_msg=field)
    np.testing.assert_allclose(np.asarray(capped.acc),
                               np.asarray(uncapped.acc), atol=3e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(capped.trans),
                               np.asarray(uncapped.trans), atol=3e-5,
                               rtol=1e-4)
    assert int(jnp.sum(capped.chunks)) <= int(jnp.sum(uncapped.chunks))


def test_seq_and_dense_bodies_agree(small_scene, cams64):
    """The two chunk-backend flavors implement one contract: integer state
    bitwise, floats to reassociation tolerance, chunk counts identical
    (the skip branch changes work, never the trip count)."""
    feats, lists = _projected_feats(small_scene, cams64[0])
    t = feats.ids.shape[0]
    state = (jnp.zeros((t, rk.P, 3), jnp.float32),
             jnp.ones((t, rk.P), jnp.float32),
             jnp.full((t, rk.P, 5), -1, jnp.int32),
             jnp.zeros((t, rk.P), jnp.int32),
             jnp.zeros((t, rk.P), jnp.int32),
             jnp.ones((t, rk.P), jnp.int32))
    outs = {}
    for body in ('dense', 'seq'):
        outs[body] = rk.rasterize_pallas(
            feats.mean2d, feats.conic, feats.color, feats.opacity, feats.ids,
            *state, tiles_x=lists.tiles_x, k_record=5, chunk=32,
            stop_at_k=True, interpret=True,
            ncap=ops.chunk_caps(feats.ids, 32), body=body)
    for field in ('record', 'rec_cnt', 'n_sig', 'n_iter', 'iter_at_k',
                  'chunks'):
        np.testing.assert_array_equal(
            np.asarray(getattr(outs['seq'], field)),
            np.asarray(getattr(outs['dense'], field)), err_msg=field)
    np.testing.assert_allclose(np.asarray(outs['seq'].acc),
                               np.asarray(outs['dense'].acc), atol=3e-5,
                               rtol=1e-4)


def test_slot_batched_shade_matches_per_slot(small_scene):
    """The slot-batched pallas serving shade (one program per tile covering
    every slot's lanes + cross-slot miss compaction) is bit-identical per
    lane to independent per-slot runs: hit rates and cache tags exactly,
    images to the kernel tolerance — the while-trip coupling across slots
    is pure skipped work."""
    s, frames = 3, 4
    cfg = LuminaConfig(capacity=128, window=2, backend='pallas')
    trajs = [orbit_trajectory(frames, width=64, height_px=64,
                              start_deg=120.0 * i) for i in range(s)]
    shared, priv = init_fleet(small_scene, cfg, trajs[0][0], slots=s)
    refs = [LuminSys(small_scene, cfg, t[0]) for t in trajs]
    from repro.core.pipeline import batched_sort_phase
    sortp = jax.jit(functools.partial(batched_sort_phase, cfg=cfg))
    shade = jax.jit(functools.partial(batched_shade_phase, cfg=cfg))
    sm = jnp.zeros((s,), jnp.float32)
    am = jnp.ones((s,), bool)
    for f in range(frames):
        cams = stack_cameras([t[f] for t in trajs])
        if f % cfg.window == 0:
            entries = sortp(small_scene, priv, cams)       # [S, ...]
            shared = dataclasses.replace(shared, pool=jax.tree.map(
                lambda p, e: p.at[:, 0].set(e), shared.pool, entries))
        shared, priv, images, stats = shade(small_scene, shared, priv, cams,
                                            sm, am)
        for v in range(s):
            img_r, st_r = refs[v].step(trajs[v][f])
            _ulp_close(images[v], img_r, msg=f'slot {v} frame {f}')
            assert float(stats.hit_rate[v]) == float(st_r.hit_rate)
    for v in range(s):
        np.testing.assert_array_equal(
            np.asarray(jax.tree.map(lambda x: x[v], shared.cache).tags),
            np.asarray(refs[v].state.cache.tags), err_msg=f'slot {v}')


def test_pallas_saved_frac_is_measured_not_modeled(small_scene, cams64):
    """On the pallas backend FrameStats.saved_frac is the *realized*
    chunk-level saving vs a count-capped full pass, not the reference
    path's modeled per-pixel saving.  Cold start pays phase A plus a
    near-full resume (strongly negative); once the cache warms, compaction
    shrinks phase B to the miss count and the measured saving must improve
    strictly.  (Whether it crosses zero depends on scene coverage — at
    benchmark scale it does, and CI gates on it via chunk_savings_%.)"""
    cfg = LuminaConfig(capacity=128, window=3, backend='pallas')
    sys_p = LuminSys(small_scene, cfg, cams64[0])
    saved, hits = [], []
    for cam in list(cams64) + list(cams64):
        _, st = sys_p.step(cam)
        saved.append(float(st.saved_frac))
        hits.append(float(st.hit_rate))
    assert hits[-1] > 0.5, hits
    assert saved[-1] > saved[0] + 0.2, saved


def test_compacted_insert_keeps_reference_cache_across_frames(small_scene):
    """The slot-batched kernel shade inserts through
    ``rc.insert_compacted``.  From a cold shared cache (two viewers of one
    scene) over several frames, its images stay within the kernel
    tolerance of the reference backend and its hit rates and cache tags,
    LRU ages and clock equal the reference's bit for bit; its counter
    reports the full-batch branch on the cold frame and a compacted branch
    once the cache is warm."""
    s, v, frames = 2, 2, 4
    cfg_p = LuminaConfig(capacity=128, window=frames, backend='pallas')
    cfg_r = dataclasses.replace(cfg_p, backend='reference')
    trajs = [orbit_trajectory(frames, width=64, height_px=64,
                              start_deg=3.0 * i) for i in range(s)]
    from repro.core.pipeline import batched_sort_phase
    shared, priv = init_fleet(small_scene, cfg_p, trajs[0][0], slots=s,
                              viewers_per_scene=v)
    cams = stack_cameras([t[0] for t in trajs])
    entries = jax.jit(functools.partial(batched_sort_phase, cfg=cfg_p))(
        small_scene, priv, cams)
    shared = dataclasses.replace(shared, pool=jax.tree.map(
        lambda p, e: p.at[0, 0].set(e[0]), shared.pool, entries))
    states = {'pallas': (shared, priv), 'reference': (shared, priv)}
    shades = {name: jax.jit(functools.partial(
        batched_shade_phase, cfg=cfg, viewers_per_scene=v))
        for name, cfg in (('pallas', cfg_p), ('reference', cfg_r))}
    sm = jnp.zeros((s,), jnp.float32)
    am = jnp.ones((s,), bool)
    n_records = s * 4096                     # one 64x64-pixel cache group
    full = len(rc.insert_capacities(n_records))
    branches = []
    for f in range(frames):
        cams = stack_cameras([t[f] for t in trajs])
        out = {}
        for name, shade in shades.items():
            sh, pr, images, stats = shade(small_scene, *states[name], cams,
                                          sm, am)
            states[name] = (sh, pr)
            out[name] = (images, stats)
        (img_p, st_p), (img_r, st_r) = out['pallas'], out['reference']
        _ulp_close(img_p, img_r, msg=f'frame {f}')
        np.testing.assert_array_equal(np.asarray(st_p.hit_rate),
                                      np.asarray(st_r.hit_rate))
        cache_p = states['pallas'][0].cache
        cache_r = states['reference'][0].cache
        for field in ('tags', 'age', 'clock'):
            np.testing.assert_array_equal(
                np.asarray(getattr(cache_p, field)),
                np.asarray(getattr(cache_r, field)),
                err_msg=f'{field}, frame {f}')
        _ulp_close(cache_p.values, cache_r.values, msg=f'values, frame {f}')
        assert st_r.insert_branch is None
        branches.append(int(st_p.insert_branch[0]))
        assert float(st_p.insert_pending[0]) == pytest.approx(
            1.0 - float(jnp.mean(st_p.hit_rate)))
    assert branches[0] == full, branches
    assert all(b < full for b in branches[1:]), branches
