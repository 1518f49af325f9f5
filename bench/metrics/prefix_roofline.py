"""Kernels (``kernels.rasterize``): phase A, the prefix pass of
``_kernel_slots`` up to each pixel's alpha-record.  The least time the
chip needs for the window's phase-A work (``harness.work.prefix_work``
over the frames delivered) over the kernel's summed device time, in
percent."""
from harness import work


def read(r):
    t = r.trace.kernel_s.get('prefix', 0.0)
    if not r.frames or t <= 0:
        return None
    least = 0.0
    for f in r.frames:
        ops, nbytes = work.prefix_work(r.pixels, r.cfg['k_record'],
                                       f.mean_iterated)
        least += work.least_time(ops, nbytes, r.peaks)[0]
    return 100.0 * least / t
