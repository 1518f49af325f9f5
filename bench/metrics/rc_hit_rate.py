"""Shade step (``core.pipeline``): mean radiance-cache hit rate of the
window's frames (``FrameStats.hit_rate``: pixels served from the cache
over pixels shaded), in percent."""


def read(r):
    if not r.frames:
        return None
    return 100.0 * sum(f.hit_rate for f in r.frames) / len(r.frames)
