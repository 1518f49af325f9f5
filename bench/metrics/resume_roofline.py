"""Kernels (``kernels.rasterize``): phase B, the miss-compacted resume of
``_kernel_compact``.  The least time the chip needs for the window's
phase-B work (``harness.work.resume_work``) over the kernel's summed
device time, in percent."""
from harness import work


def read(r):
    t = r.trace.kernel_s.get('resume', 0.0)
    if not r.frames or t <= 0:
        return None
    least = 0.0
    for f in r.frames:
        ops, nbytes = work.resume_work(r.pixels, r.cfg['k_record'],
                                       f.mean_iterated, f.hit_rate)
        least += work.least_time(ops, nbytes, r.peaks)[0]
    return 100.0 * least / t if least > 0 else None
