"""S^2 scheduler (``serve.stepper``): speculative sorts executed (scheduled
plus sort-on-admit, ``stepper.sort_log``) per frame delivered in the
window."""


def read(r):
    return r.sorts / len(r.frames) if r.frames else None
