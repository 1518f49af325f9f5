"""Shade step (``core.pipeline``): device milliseconds per frame delivered
of the shade programs, the programs that run the phase-A kernel
(``batched_shade_phase`` and the stepper's lane jits)."""


def read(r):
    t = sum(v for k, v in r.trace.module_s.items()
            if 'prefix' in r.trace.module_roles.get(k, ()))
    if not r.frames or t <= 0:
        return None
    return t / len(r.frames) * 1e3
