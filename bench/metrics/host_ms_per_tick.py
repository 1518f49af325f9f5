"""Host loop (``serve.session`` + ``serve.events``): milliseconds per tick
the server's host spent planning, applying the plan and observing the
outputs, from the benchmark's host spans in the trace."""

SPANS = ('plan_tick', 'apply_plan', 'observe_tick')


def read(r):
    lo, hi = r.trace.window
    total = sum(min(e, hi) - max(s, lo)
                for name in SPANS for s, e in r.trace.spans.get(name, [])
                if e > lo and s < hi)
    if not r.ticks or total <= 0:
        return None
    return total / r.ticks * 1e3
