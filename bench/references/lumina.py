"""Plain Lumina: 3DGS with sort sharing (S^2) and the radiance cache (RC),
replayed on sampled cache groups from the papers' descriptions (3DGS,
Kerbl et al. 2023; Lumina, arXiv:2506.05682, Sec. 3) and nothing of the
program.

3DGS.  EWA splatting of each Gaussian (3-sigma footprint, 0.3 px
low-pass, a 1.3x guard band on the frustum cull, near/far planes),
degree-1 spherical harmonics, 16x16-pixel tiles, and front-to-back
compositing in depth order: a Gaussian is skipped where alpha <= 1/255,
alpha is capped at 0.99, and a pixel stops once its transmittance has
fallen to 1e-4; black background.  At the configuration's budgets a
Gaussian is binned into at most a d x d window of tiles anchored at its
footprint's top-left tile (d*d = ``max_tiles_per_gaussian``), and each
tile keeps its ``capacity`` nearest Gaussians.

S^2.  A viewer's frames reuse a *speculative sort* made once per sharing
window at a predicted pose: constant-velocity extrapolation from the
sorting frame's previous pose ``p`` and own pose ``c`` to the window's
centre, ``p + (1 + window/2) (c - p)`` with the rotation slerped the same
way (the first frame of a viewer predicts its own pose).  The sort runs on
a viewport grown by ``margin`` pixels, rounded up to whole tiles, per side,
with every footprint inflated by ``margin`` pixels; the frame then keeps
those per-tile lists and their depth order, and re-projects geometry and
colors at its own pose.  A Gaussian culled at the sorting pose stays culled.

RC.  Each group of ``g x g`` tiles (``g`` = the largest divisor of both
tile counts not above ``group_tiles``) owns a set-associative cache of
``n_sets x n_ways`` entries.  A pixel's key is the ids of the first
``k_record`` significant Gaussians it composites (-1 padded); its set is a
multiplicative hash of the key; lookups and inserts of one tick run over
the pixels of every viewer sharing the cache, viewer by viewer in slot
order and, inside a tile group, tile by tile and pixel by pixel in raster
order.  A hit returns the cached color and refreshes the entry's LRU age;
a miss composites in full and is inserted in up to ``insert_rounds`` rounds
(the victim is the first empty way, else the least recently used; on a
collision the lowest pixel wins; a key already present is not inserted
again).  Ages and the group clock advance by the batch size per lookup
and per insert round.

Which sort a frame used is the server's scheduling decision, not a
result: the replay is told it (the viewer and tick of the sort), the way a
served model's tokens are fed to a reference, and recomputes everything
else.  ``dtype`` selects the arithmetic: float32 is the reference,
bfloat16 the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TILE = 16
SH_C0 = 0.28209479177387814
SH_C1 = 0.4886025119029199
ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
T_MIN = 1e-4
BLUR = 0.3
GUARD = 1.3


def _rot(q):
    """Quaternion(s) (w, x, y, z) [..., 4] -> rotation as nested lists of
    [...] arrays, ``r[i][j]``.  Every product below is written out, not a
    matrix product, so the arithmetic is plain float32 (or bfloat16)
    whatever the platform's matrix precision."""
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True))
    w, x, y, z = (q[..., i] for i in range(4))
    return [[1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
             2 * (x * z + w * y)],
            [2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
             2 * (y * z - w * x)],
            [2 * (x * z - w * y), 2 * (y * z + w * x),
             1 - 2 * (x * x + y * y)]]


def _project(s, pos, quat, fx, fy, cx, cy, w, h, near, far):
    """Screen-space Gaussians: mean [N,2], conic [N,3], radius, depth,
    color [N,3], opacity, and the cull mask."""
    r = _rot(quat)                                   # world-from-camera
    d = [s['means'][:, i] - pos[i] for i in range(3)]
    tx, ty, tz = (sum(d[i] * r[i][k] for i in range(3)) for k in range(3))
    tzs = jnp.where(tz > near, tz, near)
    limx, limy = GUARD * (w / 2) / fx, GUARD * (h / 2) / fy
    keep = ((tz > near) & (tz < far) & (jnp.abs(tx / tzs) < limx)
            & (jnp.abs(ty / tzs) < limy))
    u = fx * tx / tzs + cx
    v = fy * ty / tzs + cy
    txc = jnp.clip(tx / tzs, -limx, limx) * tzs
    tyc = jnp.clip(ty / tzs, -limy, limy) * tzs
    zero = jnp.zeros_like(tzs)
    jac = [[fx / tzs, zero, -fx * txc / (tzs * tzs)],
           [zero, fy / tzs, -fy * tyc / (tzs * tzs)]]
    rg = _rot(s['quats'])
    sc = jnp.exp(s['log_scales'])
    m = [[rg[i][j] * sc[:, j] for j in range(3)] for i in range(3)]
    cov = [[sum(m[i][j] * m[k][j] for j in range(3)) for k in range(3)]
           for i in range(3)]                                  # world
    t = [[sum(jac[a][i] * r[k][i] for i in range(3)) for k in range(3)]
         for a in range(2)]                                    # J R_cw
    tc = [[sum(t[a][k] * cov[k][l] for k in range(3)) for l in range(3)]
          for a in range(2)]
    c2 = [[sum(tc[a][l] * t[b][l] for l in range(3)) for b in range(2)]
          for a in range(2)]
    a, b, c = c2[0][0] + BLUR, c2[0][1], c2[1][1] + BLUR
    det = a * c - b * b
    keep = keep & (det > 1e-12)
    dets = jnp.where(keep, det, 1.0)
    conic = jnp.stack([c / dets, -b / dets, a / dets], -1)
    mid = 0.5 * (a + c)
    lam = mid + jnp.sqrt(jnp.maximum(mid * mid - det, 1e-12))
    radius = jnp.where(keep, jnp.ceil(3.0 * jnp.sqrt(lam)), 0.0)
    view = s['means'] - pos
    view = view / jnp.sqrt(jnp.sum(view * view, axis=-1, keepdims=True))
    sh = s['sh_rest']
    col = (SH_C0 * s['sh_dc'] - SH_C1 * view[:, 1:2] * sh[:, 0]
           + SH_C1 * view[:, 2:3] * sh[:, 1] - SH_C1 * view[:, 0:1] * sh[:, 2])
    col = jnp.maximum(col + 0.5, 0.0)
    opac = jnp.where(keep, jax.nn.sigmoid(s['opacity_logit']), 0.0)
    depth = jnp.where(keep, tz, jnp.inf)
    return jnp.stack([u, v], -1), conic, radius, depth, col, opac, keep


INVALID = -2
N_SETS, N_WAYS, INSERT_ROUNDS = 1024, 4, 4
MIX = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)


def group_tiles(tiles_x: int, tiles_y: int, want: int) -> int:
    g = want
    while tiles_x % g or tiles_y % g:
        g -= 1
    return g


def predict(prev: tuple, cur: tuple, first: bool, window: int) -> tuple:
    """The speculative sorting pose (position, quaternion), in float32."""
    f32 = np.float32
    if first:
        prev = cur
    t = f32(1.0 + window / 2.0)
    p0, q0 = np.asarray(prev[0], f32), np.asarray(prev[1], f32)
    p1, q1 = np.asarray(cur[0], f32), np.asarray(cur[1], f32)
    pos = p0 + t * (p1 - p0)
    q0 = q0 / (np.linalg.norm(q0) + f32(1e-12))
    q1 = q1 / (np.linalg.norm(q1) + f32(1e-12))
    dot = f32(np.sum(q0 * q1))
    if dot < 0:
        q1 = -q1
    dot = f32(min(abs(dot), 1.0))
    theta = f32(np.arccos(dot))
    s = f32(np.sin(theta))
    if s < 1e-5:
        w0, w1 = f32(1.0) - t, t
    else:
        w0 = f32(np.sin((f32(1.0) - t) * theta)) / s
        w1 = f32(np.sin(t * theta)) / s
    q = w0 * q0 + w1 * q1
    return pos.astype(f32), (q / (np.linalg.norm(q) + f32(1e-12))).astype(f32)


def _set_index(ids):
    """[..., k] int32 keys -> [...] set index."""
    h = (ids[..., 0] + 3).astype(jnp.uint32) * jnp.uint32(MIX[0])
    for i in range(1, ids.shape[-1]):
        m = (ids[..., i] + 3).astype(jnp.uint32) * jnp.uint32(MIX[i % 5])
        h = (h ^ m) * jnp.uint32(MIX[0])
    h = h ^ (h >> 15)
    return (h % jnp.uint32(N_SETS)).astype(jnp.int32)


def _cache_tick(tags, values, age, clock, ids, rgb):
    """One tick of one group's cache over a batch of B pixels (all viewers
    sharing it, in order): lookup, then insert the misses.  Returns the
    new state and the colors served."""
    b = ids.shape[0]
    pix = jnp.arange(b, dtype=jnp.int32)
    sidx = _set_index(ids)
    match = jnp.all(tags[sidx] == ids[:, None, :], axis=-1)      # [B, W]
    hit = jnp.any(match, axis=-1)
    way = jnp.argmax(match, axis=-1)
    served = jnp.where(hit[:, None], values[sidx, way], rgb)
    age = age.at[sidx, way].max(jnp.where(hit, clock + 1 + pix, -1))
    clock = clock + b
    pending = ~hit & jnp.all(jnp.isfinite(rgb), axis=-1)
    for _ in range(INSERT_ROUNDS):
        present = jnp.any(jnp.all(tags[sidx] == ids[:, None, :], axis=-1),
                          axis=-1)
        pending = pending & ~present
        empty = jnp.all(tags[sidx] == INVALID, axis=-1)
        victim = jnp.argmin(jnp.where(empty, jnp.iinfo(jnp.int32).min,
                                      age[sidx]), axis=-1)
        slot = sidx * N_WAYS + victim
        first = jnp.full((N_SETS * N_WAYS,), b, jnp.int32).at[slot].min(
            jnp.where(pending, pix, b))
        wins = pending & (first[slot] == pix)
        row = jnp.where(wins, sidx, N_SETS)
        tags = tags.at[row, victim].set(ids, mode='drop')
        values = values.at[row, victim].set(rgb, mode='drop')
        age = age.at[row, victim].set(clock + 1 + pix, mode='drop')
        clock = clock + b
    return tags, values, age, clock, served


cache_tick = jax.jit(jax.vmap(_cache_tick))


def empty_cache(groups: int, k: int):
    return (jnp.full((groups, N_SETS, N_WAYS, k), INVALID, jnp.int32),
            jnp.zeros((groups, N_SETS, N_WAYS, 3), jnp.float32),
            jnp.zeros((groups, N_SETS, N_WAYS), jnp.int32),
            jnp.zeros((groups,), jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    'w', 'h', 'margin_px', 'capacity', 'd', 'dtype', 'near', 'far'))
def sort_lists(scene, pos, quat, fx, fy, cx, cy, tiles, *, w, h, margin_px,
               capacity, d, dtype, near, far):
    """Speculative per-tile lists at a sorting pose for the render-grid
    ``tiles`` [M, 2] (x, y): [M, capacity] ids, nearest first, -1 pad."""
    s = {k: v.astype(dtype) for k, v in scene.items()}
    c = lambda x: jnp.asarray(x).astype(dtype)
    mt = -(-margin_px // TILE) if margin_px > 0 else 0
    we, he = w + 2 * mt * TILE, h + 2 * mt * TILE
    mean, _, radius, depth, _, _, keep = _project(
        s, c(pos), c(quat), c(fx), c(fy), c(cx) + mt * TILE,
        c(cy) + mt * TILE, we, he, near, far)
    radius = jnp.where(keep, radius + margin_px, radius)
    tx_n, ty_n = -(-we // TILE), -(-he // TILE)
    x0 = jnp.floor((mean[:, 0] - radius) / TILE).astype(jnp.int32)
    y0 = jnp.floor((mean[:, 1] - radius) / TILE).astype(jnp.int32)
    x1 = jnp.floor((mean[:, 0] + radius) / TILE).astype(jnp.int32)
    y1 = jnp.floor((mean[:, 1] + radius) / TILE).astype(jnp.int32)
    ax = jnp.clip(x0, 0, tx_n - 1)
    ay = jnp.clip(y0, 0, ty_n - 1)
    ok = keep & (radius > 0)
    depth = depth.astype(jnp.float32)

    def one(tile):
        tx, ty = tile[0] + mt, tile[1] + mt
        inx = (tx >= ax) & (tx < ax + d) & (tx >= x0) & (tx <= x1)
        iny = (ty >= ay) & (ty < ay + d) & (ty >= y0) & (ty <= y1)
        key = jnp.where(inx & iny & ok, depth, jnp.inf)
        neg, idx = jax.lax.top_k(-key, capacity)
        return jnp.where(jnp.isfinite(neg), idx, -1).astype(jnp.int32)

    return jax.lax.map(one, tiles)


@functools.partial(jax.jit, static_argnames=('w', 'h', 'k', 'dtype', 'near',
                                             'far'))
def shade(scene, sort_keep, pos, quat, fx, fy, cx, cy, tiles, ids, *, w, h, k,
          dtype, near, far):
    """Composite ``tiles`` [M, 2] with their lists ``ids`` [M, K] at a
    render pose: (colors [M, 256, 3], alpha-records [M, 256, k])."""
    s = {kk: v.astype(dtype) for kk, v in scene.items()}
    c = lambda x: jnp.asarray(x).astype(dtype)
    mean, conic, _, _, col, opac, keep = _project(
        s, c(pos), c(quat), c(fx), c(fy), c(cx), c(cy), w, h, near, far)
    opac = jnp.where(keep & sort_keep, opac, 0.0)

    def one(args):
        tile, tid = args
        safe = jnp.maximum(tid, 0)
        g_mean, g_conic, g_col = mean[safe], conic[safe], col[safe]
        g_op = jnp.where(tid >= 0, opac[safe], 0.0)
        py, px = jnp.meshgrid(jnp.arange(TILE), jnp.arange(TILE),
                              indexing='ij')
        pxx = (px.reshape(-1) + tile[0] * TILE).astype(dtype) + 0.5
        pyy = (py.reshape(-1) + tile[1] * TILE).astype(dtype) + 0.5
        dx = pxx[:, None] - g_mean[None, :, 0]
        dy = pyy[:, None] - g_mean[None, :, 1]
        power = (-0.5 * (g_conic[None, :, 0] * dx * dx
                         + g_conic[None, :, 2] * dy * dy)
                 - g_conic[None, :, 1] * dx * dy)
        alpha = jnp.minimum(ALPHA_MAX, g_op[None] * jnp.exp(power))
        sig = (power <= 0) & (alpha > ALPHA_MIN) & (tid[None] >= 0)
        a = jnp.where(sig, alpha, 0.0).astype(dtype)
        trans = jnp.cumprod(1.0 - a, axis=-1, dtype=dtype)
        before = jnp.concatenate([jnp.ones_like(trans[:, :1]),
                                  trans[:, :-1]], -1)
        contrib = sig & (before > T_MIN)
        wgt = jnp.where(contrib, before * a, 0.0).astype(dtype)
        color = jnp.sum(wgt[:, :, None] * g_col[None].astype(dtype), axis=1)
        rank = jnp.cumsum(contrib, axis=-1) - 1
        rec = jnp.stack([jnp.max(jnp.where(contrib & (rank == j), tid[None],
                                           -1), axis=-1)
                         for j in range(k)], -1)
        return color.astype(jnp.float32), rec.astype(jnp.int32)

    return jax.lax.map(one, (tiles, ids))


@functools.partial(jax.jit, static_argnames=('w', 'h', 'margin_px', 'dtype',
                                             'near', 'far'))
def sort_keep_mask(scene, pos, quat, fx, fy, cx, cy, *, w, h, margin_px,
                   dtype, near, far):
    """Which Gaussians survive the cull at the sorting pose (expanded
    viewport)."""
    s = {k: v.astype(dtype) for k, v in scene.items()}
    c = lambda x: jnp.asarray(x).astype(dtype)
    mt = -(-margin_px // TILE) if margin_px > 0 else 0
    we, he = w + 2 * mt * TILE, h + 2 * mt * TILE
    return _project(s, c(pos), c(quat), c(fx), c(fy),
                          c(cx) + mt * TILE, c(cy) + mt * TILE, we, he,
                          near, far)[-1]


class Replay:
    """Replays a session's frames on a set of sampled cache groups.

    ``groups``: list of (gx, gy) group coordinates; ``blocks``: viewer id ->
    cache domain (scene block); ``order``: viewer ids in slot order.
    """

    def __init__(self, scene: dict, intr, cfg: dict, groups: list,
                 blocks: dict, order: list, dtype=jnp.float32):
        self.scene, self.intr, self.cfg = scene, intr, cfg
        self.dtype = jnp.dtype(dtype)
        tx = -(-intr.width // TILE)
        ty = -(-intr.height // TILE)
        self.g = group_tiles(tx, ty, int(cfg['group_tiles']))
        g = self.g
        tiles = [(gx * g + i, gy * g + j) for gx, gy in groups
                 for j in range(g) for i in range(g)]
        self.tiles = jnp.asarray(tiles, jnp.int32)
        self.groups = list(groups)
        self.blocks, self.order = blocks, list(order)
        self.k = int(cfg['k_record'])
        self.caches = {b: empty_cache(len(groups), self.k)
                       for b in set(blocks.values())}
        self.lists = {}
        self._kw = dict(w=intr.width, h=intr.height, dtype=self.dtype,
                        near=intr.near, far=intr.far)
        self._cam = (intr.fx, intr.fy, intr.cx, intr.cy)

    def _sort(self, key, pose):
        got = self.lists.get(key)
        if got is None:
            d = int(round(self.cfg['max_tiles_per_gaussian'] ** 0.5))
            ids = sort_lists(
                self.scene, *pose, *self._cam, self.tiles,
                margin_px=int(self.cfg['margin']),
                capacity=int(self.cfg['capacity']), d=d, **self._kw)
            keep = sort_keep_mask(
                self.scene, *pose, *self._cam,
                margin_px=int(self.cfg['margin']), **self._kw)
            got = self.lists[key] = (ids, keep)
        return got

    def tick(self, frames: list) -> dict:
        """Render one tick: ``frames`` lists (viewer id, render pose, sort
        key, sorting pose).  Returns viewer id -> [G, h, w, 3] crops."""
        raw, rec = {}, {}
        for vid, pose, key, sort_pose in frames:
            ids, keep = self._sort(key, sort_pose)
            col, r = shade(self.scene, keep, *pose, *self._cam, self.tiles,
                           ids, k=self.k, **self._kw)
            n = len(self.groups)
            raw[vid] = col.reshape(n, -1, 3)
            rec[vid] = r.reshape(n, -1, self.k)
        out = {}
        for block in sorted(set(self.blocks[v] for v, *_ in frames)):
            vids = [v for v in self.order
                    if self.blocks[v] == block and v in raw]
            ids = jnp.concatenate([rec[v] for v in vids], axis=1)
            rgb = jnp.concatenate([raw[v] for v in vids], axis=1)
            *state, served = cache_tick(*self.caches[block], ids, rgb)
            self.caches[block] = tuple(state)
            per = served.shape[1] // len(vids)
            for i, v in enumerate(vids):
                out[v] = self.crops(served[:, i * per:(i + 1) * per])
        return out

    def crops(self, colors) -> np.ndarray:
        """[G, g*g*256, 3] group-major pixels -> [G, g*16, g*16, 3]."""
        g = self.g
        x = np.asarray(colors, np.float32).reshape(-1, g, g, TILE, TILE, 3)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, g * TILE, g * TILE,
                                                     3)
