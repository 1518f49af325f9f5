"""S^2 scheduler (``serve.stepper``): device milliseconds of the sort
programs (the stepper's ``_sort_pool_fn``) per frame delivered."""

PROGRAMS = ('_sort_pool_fn',)


def read(r):
    t = sum(v for k, v in r.trace.module_s.items()
            if any(p in k for p in PROGRAMS))
    if not r.frames or t <= 0:
        return None
    return t / len(r.frames) * 1e3
