"""Device (TPU): the whole frame's share of the chip's peak FLOP/s: frames
per second over the traced window times the operations one frame needs
(``harness.work.frame_ops``), over the published peak, in percent."""
from harness import work


def read(r):
    w = r.trace.window_s
    if not r.frames or w <= 0:
        return None
    ops = sum(work.frame_ops(r.cfg['num_gaussians'], r.pixels,
                             f.mean_iterated) for f in r.frames)
    return 100.0 * ops / w / r.peaks['flops_per_s']
