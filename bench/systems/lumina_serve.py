"""The system under test: the Lumina render server's own serving path.

``SessionManager.run_tick`` under the server's default driver (``sync``,
``serve/events.py``) -> ``BatchedStepper.step_dispatch``/``step_finish``
-> ``batched_shade_phase`` -> the Pallas kernel path, with the defaults the
server runs (dynamic sort pool, ``profile_every=0``), at the matrix-product
precision the configuration states.  This adapter builds
it from a configuration file, a traffic file and the benchmark's scene,
warms every program the cell's traffic will call, and hands out the frames
each tick delivers.  Host spans (``jax.profiler.TraceAnnotation``) wrap the
manager's plan, apply, step and observe calls from the outside.

For the comparison that decides ``correct`` each delivered frame keeps
the pixels of the sampled cache groups and which sort it rendered from,
read from the stepper's pool bookkeeping (``_slot_pool``, ``_pool_owner``,
``_pool_tick``): the scheduler's decision, fed to the reference replay.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np

# JAX's precision for matrix products, from the configuration's stated
# ``precision``.  On a TPU the default rounds a float32 product's inputs to
# bfloat16; the program's float32 products (the camera transform and the
# EWA covariances of ``core/projection.py``, ``core/gaussians.py``) then
# compute in bfloat16.
MATMUL_PRECISION = {'float32': 'highest'}


@dataclasses.dataclass
class Frame:
    """One delivered frame: the viewer, its frame index, the pixels of the
    sampled cache groups (``crops`` [G, h, w, 3]), the frame's counters,
    the host time it was delivered, the server tick, and which sort it
    rendered from (the sorting viewer and that viewer's frame index)."""

    vid: int
    index: int
    crops: np.ndarray
    hit_rate: float
    mean_iterated: float
    delivered: float
    tick: int
    sort: tuple


class _Cams:
    """A session's camera stream, made on demand from its orbit: the orbit
    loops, so the stream is endless for any window."""

    LEN = 1 << 30

    def __init__(self, orbit, intr, camera_cls):
        self.orbit, self.intr, self.cls = orbit, intr, camera_cls

    def __len__(self):
        return self.LEN

    def __getitem__(self, i):
        pos, quat = self.orbit.pose(int(i))
        it = self.intr
        f = lambda x: jnp.asarray(x, jnp.float32)
        return self.cls(position=f(pos), quat=f(quat), fx=f(it.fx),
                        fy=f(it.fy), cx=f(it.cx), cy=f(it.cy),
                        width=it.width, height=it.height, near=it.near,
                        far=it.far)


def _annotated(name, fn):
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(f'bench.{name}'):
            return fn(*a, **k)
    return wrapped


class System:
    def __init__(self, cfg: dict, traffic: dict, scene: dict, viewers: list,
                 intr, clock, boxes: list):
        from repro.core.camera import Camera
        from repro.core.gaussians import GaussianScene
        from repro.core.pipeline import LuminaConfig
        self.cfg, self.traffic, self.viewers = cfg, traffic, viewers
        self.precision = MATMUL_PRECISION[cfg['precision']]
        self.clock = clock
        self.scene = GaussianScene(**scene)
        self.lcfg = LuminaConfig(
            capacity=cfg['capacity'], window=cfg['window'],
            margin=cfg['margin'], k_record=cfg['k_record'],
            group_tiles=cfg['group_tiles'], sort_method=cfg['sort_method'],
            max_tiles_per_gaussian=cfg['max_tiles_per_gaussian'],
            backend=cfg['backend'])
        self.cams = [_Cams(v.orbit, intr, Camera) for v in viewers]
        self.slots = len(viewers)
        self.vps = int(traffic['viewers_per_scene'])
        self.boxes = boxes          # (y0, x0, size) pixel boxes to keep
        self.frames_out: list[Frame] = []
        self.last_sorts = 0
        self.rendered = {}          # (viewer, tick) -> frame index

    # -- set-up --------------------------------------------------------------

    def _build(self):
        from repro.serve.stepper import BatchedStepper
        self.stepper = BatchedStepper(self.scene, self.lcfg, self.cams[0][0],
                                      self.slots,
                                      viewers_per_scene=self.vps)

    def _sessions(self):
        """A fresh session manager over the stepper, with every viewer
        submitted, its calls wrapped in host spans, and its delivered
        frames recorded."""
        from repro.serve.events import get_driver
        from repro.serve.session import SessionManager, ViewerSession
        mgr = SessionManager(self.stepper, self.slots)
        observe = mgr.observe_tick

        def observe_tick(plan, outputs, host=None):
            st = self.stepper
            tick = st.global_tick - 1
            for slot in outputs:
                sess = mgr.slot_session[slot]
                self.rendered[(sess.sid, tick)] = sess.cursor
            for slot, (img, stats, _timing) in outputs.items():
                sess = mgr.slot_session[slot]
                img = np.asarray(img)
                crops = np.stack([img[y:y + n, x:x + n]
                                  for y, x, n in self.boxes])
                scene_i = int(st._scene_of[slot])
                entry = int(st._slot_pool[slot])
                owner = mgr.slot_session[int(st._pool_owner[scene_i, entry])]
                sort_tick = int(st._pool_tick[scene_i, entry])
                self.frames_out.append(Frame(
                    sess.sid, sess.cursor, crops, float(stats.hit_rate),
                    float(stats.mean_iterated), self.clock(), tick,
                    (owner.sid, self.rendered[(owner.sid, sort_tick)])))
            return observe(plan, outputs, host)

        mgr.observe_tick = observe_tick
        mgr.plan_tick_hardened = _annotated('plan_tick',
                                            mgr.plan_tick_hardened)
        mgr.apply_plan = _annotated('apply_plan', mgr.apply_plan)
        mgr.step_hardened = _annotated('step', mgr.step_hardened)
        mgr.observe_tick = _annotated('observe_tick', mgr.observe_tick)
        self.mgr = mgr
        self.driver = get_driver(self.traffic.get('driver', 'sync'), mgr)
        for v in self.viewers:
            ok = mgr.submit(ViewerSession(
                sid=v.vid, cams=self.cams[v.vid], arrival_tick=0,
                scene_id=v.scene_block, pace=v.pace))
            if not ok:
                raise RuntimeError(f'viewer {v.vid} was shed at submit')

    def _warm_pool_buckets(self):
        """Run every sort-pool capacity the scene's live pose cells can
        need (powers of two up to ``viewers_per_scene``), and every resize
        between them, with all lanes idle; then cold-start the stepper.
        The server's dynamic pool otherwise compiles these in the window
        the first time the viewers' cells split or merge."""
        from repro.core.camera import stack_cameras
        st = self.stepper
        if not st.dynamic_pool:
            return
        caps = []
        c = 1
        while c <= st.pool_size:
            caps.append(c)
            c *= 2
        if caps[-1] < st.pool_size:
            caps.append(st.pool_size)
        cam_b = stack_cameras(st._slot_cams)
        idle = jnp.zeros((st.slots,), bool)
        flags = jnp.zeros((st.slots,), jnp.float32)
        w = st.cohort
        drop = jnp.full((w,), st.num_scenes, jnp.int32)
        zero = jnp.zeros((w,), jnp.int32)

        def run_at_cap():
            st.shared = st._sort_pool(st.scene, st.shared, st.priv, cam_b,
                                      zero, drop, zero, zero, jnp.int32(0))
            st.shared, st.priv, images, _ = st._shade(
                st.scene, st.shared, st.priv, cam_b, flags, idle)
            jax.block_until_ready(images)

        def resize_to(n):
            if n != st.pool_cap:
                st._resize_pool(n, keep=None if n > st.pool_cap else
                                [set(range(n))] * st.num_scenes)

        for a in caps:
            resize_to(a)
            run_at_cap()
            for b in caps:
                if b != a:
                    resize_to(b)
                    resize_to(a)
        jax.block_until_ready(st.shared)
        st.reset()

    def _warm_assignments(self):
        """The stepper points each sort group's members at their entry with
        eager scatters whose width is the number of members; run each
        width once so that none compiles in the window."""
        target = self.stepper.priv.pool_idx
        for n in range(1, self.slots + 1):
            idx = jnp.asarray([0] * n, jnp.int32)
            jax.block_until_ready(target.at[idx].set(idx))

    def setup(self, ticks: int):
        """Build the server, warm its programs, admit every viewer and run
        ``ticks`` ticks (the admission tick first)."""
        with jax.default_matmul_precision(self.precision):
            self._build()
            self._warm_pool_buckets()
            self._warm_assignments()
            self._sessions()
        for _ in range(ticks):
            self.tick()

    def restart(self, scene: dict, viewers: list, ticks: int):
        """Serve a new scene and viewers on the already-warm server: the
        stepper's own cold start (``reset``), a fresh session manager, and
        ``ticks`` set-up ticks.  Used to read many seeds in one process."""
        from repro.core.camera import Camera
        from repro.core.gaussians import GaussianScene
        self.scene = GaussianScene(**scene)
        self.viewers = viewers
        self.cams = [_Cams(v.orbit, self.cams[0].intr, Camera)
                     for v in viewers]
        self.stepper.scene = self.scene
        with jax.default_matmul_precision(self.precision):
            self.stepper.reset()
            self.frames_out, self.rendered = [], {}
            self._sessions()
        for _ in range(ticks):
            self.tick()

    # -- the window ------------------------------------------------------------

    def tick(self) -> list:
        """One tick of the server's driver; the frames it delivered."""
        n0 = len(self.frames_out)
        s0 = len(self.stepper.sort_log)
        with jax.default_matmul_precision(self.precision):
            self.driver.run_tick()
        self.last_sorts = sum(e['scheduled'] + e['admit']
                              for e in self.stepper.sort_log[s0:])
        return self.frames_out[n0:]

    def counters(self) -> dict:
        """Frames the server failed: sessions shed, slots quarantined,
        degraded ticks."""
        reg = self.mgr.metrics

        def total(name):
            return sum(reg[k].value for k in reg.names()
                       if k == name or k.startswith(name + '{'))
        return {k: total(f'serve.{k}') for k in ('shed', 'quarantined',
                                                 'degraded_ticks')}

    def close(self):
        for name in ('driver', 'mgr', 'stepper', 'scene'):
            setattr(self, name, None)
        self.frames_out = []
        gc.collect()
