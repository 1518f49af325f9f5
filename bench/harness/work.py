"""Operations and bytes the algorithm needs, per frame, from the cell's
shapes and the frame's own counters (pixels, Gaussians, ``k_record``, the
frame's cache hit rate and its mean Gaussians walked per pixel).

They count the work of the algorithm, not of one implementation: a later
kernel that fuses or skips work is measured against the same numbers.

* ``EVAL_FLOPS``: one Gaussian tested and blended at one pixel: the offset
  (2), the conic quadratic (9), exp and the opacity product (2), the cap
  (1), the weight (1), the color update (6) and the transmittance update
  (2) -> 23, counted as 20 to stay a lower bound where a pixel skips the
  blend of an insignificant Gaussian.
* ``PROJECT_FLOPS``: one Gaussian projected for one frame: rotation from
  its quaternion (~30), covariance R S S^T R^T (~45), the camera transform
  (~18), the EWA Jacobian product (~60), conic and radius (~20), degree-1
  SH color (~27) -> 200.
* ``STATE_WORDS``: a pixel's shading state between the two phases: color
  (3), transmittance (1), alpha-record (``k_record``), and three counters.
"""
from __future__ import annotations

EVAL_FLOPS = 20
PROJECT_FLOPS = 200
WORD = 4


def state_bytes(k_record: int) -> int:
    return (7 + k_record) * WORD


def prefix_work(pixels: float, k_record: int, mean_iterated: float) -> tuple:
    """Phase A (walk each pixel to its alpha-record): (ops, bytes).  Each
    pixel evaluates up to ``k_record`` Gaussians and writes its state."""
    evals = pixels * min(float(k_record), mean_iterated)
    return evals * EVAL_FLOPS, pixels * state_bytes(k_record)


def resume_work(pixels: float, k_record: int, mean_iterated: float,
                hit_rate: float) -> tuple:
    """Phase B (cache misses finish their walk): (ops, bytes).  Each miss
    evaluates the Gaussians past its record and reads and writes its
    state."""
    misses = pixels * (1.0 - hit_rate)
    evals = misses * max(mean_iterated - k_record, 0.0)
    return evals * EVAL_FLOPS, 2 * misses * state_bytes(k_record)


def frame_ops(num_gaussians: int, pixels: float, mean_iterated: float) -> float:
    """One whole frame: projecting every Gaussian and compositing every
    pixel's walk."""
    return (num_gaussians * PROJECT_FLOPS
            + pixels * mean_iterated * EVAL_FLOPS)


def least_time(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, 'ops' or 'bytes'): the larger of the two bounds."""
    t_ops = ops / peaks['flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    return (t_ops, 'ops') if t_ops >= t_bytes else (t_bytes, 'bytes')
