"""Traffic: camera poses for each viewer, from a seed.

One general generator reads a traffic file (``bench/traffic/<name>.json``)
and yields, per viewer, an endless orbit of poses.  The orbit generator is
a copy of the program's ``data/trajectory.py`` (``orbit_trajectory``), so
that a change to the program cannot move the yardstick.  Viewers are
closed-loop: every one arrives at tick 0, is admitted during set-up, and
asks for a frame every ``pace`` ticks.

Poses are host-side float32 numpy: ``position`` [3] and a world-from-camera
quaternion ``quat`` [4] (w, x, y, z), camera convention +z forward, +x
right, +y down.  Intrinsics are a pinhole with square pixels.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

NEAR, FAR = 0.05, 100.0


@dataclasses.dataclass(frozen=True)
class Intrinsics:
    width: int
    height: int
    fx: float
    fy: float
    cx: float
    cy: float
    near: float = NEAR
    far: float = FAR


def intrinsics(width: int, height: int, fov_x_deg: float) -> Intrinsics:
    fx = (width / 2.0) / math.tan(math.radians(fov_x_deg) / 2.0)
    return Intrinsics(width, height, fx, fx, width / 2.0, height / 2.0)


def _rotmat_to_quat(r: np.ndarray) -> np.ndarray:
    """Rotation matrix -> unit quaternion (w, x, y, z), Shepperd's method."""
    tr = r[0, 0] + r[1, 1] + r[2, 2]
    cands = [tr, r[0, 0], r[1, 1], r[2, 2]]
    i = int(np.argmax(cands))
    if i == 0:
        w = math.sqrt(max(1 + tr, 1e-12)) / 2
        q = [w, (r[2, 1] - r[1, 2]) / (4 * w), (r[0, 2] - r[2, 0]) / (4 * w),
             (r[1, 0] - r[0, 1]) / (4 * w)]
    elif i == 1:
        x = math.sqrt(max(1 + r[0, 0] - r[1, 1] - r[2, 2], 1e-12)) / 2
        q = [(r[2, 1] - r[1, 2]) / (4 * x), x, (r[0, 1] + r[1, 0]) / (4 * x),
             (r[0, 2] + r[2, 0]) / (4 * x)]
    elif i == 2:
        y = math.sqrt(max(1 - r[0, 0] + r[1, 1] - r[2, 2], 1e-12)) / 2
        q = [(r[0, 2] - r[2, 0]) / (4 * y), (r[0, 1] + r[1, 0]) / (4 * y), y,
             (r[1, 2] + r[2, 1]) / (4 * y)]
    else:
        z = math.sqrt(max(1 - r[0, 0] - r[1, 1] + r[2, 2], 1e-12)) / 2
        q = [(r[1, 0] - r[0, 1]) / (4 * z), (r[0, 2] + r[2, 0]) / (4 * z),
             (r[1, 2] + r[2, 1]) / (4 * z), z]
    q = np.asarray(q, np.float64)
    return q / np.linalg.norm(q)


def look_at(position, target=(0.0, 0.0, 0.0), up=(0.0, 1.0, 0.0)):
    """Quaternion of a camera at ``position`` looking at ``target``."""
    p = np.asarray(position, np.float64)
    fwd = np.asarray(target, np.float64) - p
    fwd /= np.linalg.norm(fwd)
    right = np.cross(fwd, np.asarray(up, np.float64))
    right /= np.linalg.norm(right)
    down = np.cross(fwd, right)
    return _rotmat_to_quat(np.stack([right, down, fwd], axis=1))


@dataclasses.dataclass(frozen=True)
class Orbit:
    """A viewer's endless orbit: pose ``i`` is ``i / fps`` seconds along.

    The orbit loops after one revolution, so a session outlasts any window.
    ``offset`` shifts the position only; the view direction stays that of
    the orbit's own pose.
    """

    fps: float
    deg_per_sec: float
    radius: float
    height: float
    translate_per_sec: float
    start_deg: float = 0.0
    offset: tuple = (0.0, 0.0, 0.0)

    @property
    def loop(self) -> int:
        return max(1, int(round(360.0 / self.deg_per_sec * self.fps)))

    def pose(self, i: int) -> tuple:
        t = (i % self.loop) / self.fps
        ang = math.radians(self.start_deg + self.deg_per_sec * t)
        base = np.array([self.radius * math.sin(ang),
                         self.height + self.translate_per_sec * t,
                         self.radius * math.cos(ang)])
        quat = look_at(base)
        pos = base + np.asarray(self.offset, np.float64)
        return pos.astype(np.float32), quat.astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Viewer:
    vid: int
    scene_block: int
    orbit: Orbit
    pace: int


def viewers(traffic: dict, seed: int) -> list:
    """The viewers a traffic file describes, for ``seed``.

    Every seed gives the same number of viewers, the same orbits, arrivals
    and paces, and the same set of position ``offsets`` (one per viewer,
    none by default): the seed only deals them out to the viewers.  So the
    viewers' poses, and the pose cells the server sorts for, are the same
    work for every seed.
    """
    if traffic.get('arrival', 'setup') != 'setup':
        raise ValueError(f'arrival {traffic["arrival"]!r}: the closed-loop '
                         'runner serves only viewers admitted in set-up')
    rng = np.random.default_rng(int(seed))
    n = int(traffic['viewers'])
    vps = int(traffic['viewers_per_scene'])
    offsets = traffic.get('offsets') or [(0.0, 0.0, 0.0)] * n
    if len(offsets) != n:
        raise ValueError(f'{len(offsets)} offsets for {n} viewers')
    deal = rng.permutation(n)
    o = traffic['orbit']
    pace = int(traffic.get('pace', 1))
    out = []
    for v in range(n):
        block = v // vps
        offset = tuple(float(x) for x in offsets[deal[v]])
        orbit = Orbit(fps=o['fps'], deg_per_sec=o['deg_per_sec'],
                      radius=o['radius'], height=o['height'],
                      translate_per_sec=o['translate_per_sec'],
                      start_deg=block * float(traffic['start_deg_step']),
                      offset=offset)
        out.append(Viewer(v, block, orbit, pace))
    return out
