"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device-busy time, time per XLA program and per device operation,
the benchmark's host spans, and idle gaps labelled by the host span that
was open.

Read with ``jax.profiler.ProfileData``.  Device planes are named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run and ``XLA Modules`` one per program run; an operation
belongs to the program run whose interval holds it.  The benchmark's host
spans are the ``jax.profiler.TraceAnnotation`` events named
``bench.<span>`` on the host plane; ``bench.window`` spans the measured
window.  All times are in seconds on the profiler's clock (on a v5e the
device events sit about a millisecond early against the host spans).

Pallas kernels run as ``tpu_custom_call`` operations.  Their names are
whatever the kernel function was called (``_unknown_`` for a
``functools.partial``), so a kernel is known by its role: a kernel named
after ``_kernel_slots`` or ``_kernel_compact`` is phase A or phase B; an
unnamed one that writes per-pixel shading state (a ``f32[..., 3, 256]``
first result) is phase A if it is the first such kernel of its program
run and phase B otherwise; one whose first result is an ``s32`` hit mask
is the cache lookup.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
SPAN_PREFIX = 'bench.'


@dataclasses.dataclass
class Reduced:
    window: tuple                 # (start, end) of bench.window
    busy_s: float                 # union of op intervals in the window,
                                  # averaged over the devices
    devices: int
    op_s: dict                    # op name -> seconds in the window
    op_count: dict                # op name -> events in the window
    module_s: dict                # program name -> seconds in the window
    kernel_s: dict                # kernel role -> seconds in the window
    module_roles: dict            # program name -> kernel roles it ran
    spans: dict                   # span name -> [(start, end), ...]
    gaps: list                    # [(seconds, span label)], longest first

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def union_length(intervals) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def innermost(spans: dict, t: float) -> str:
    """The shortest benchmark span (other than the window) open at ``t``."""
    best, best_len = 'none', None
    for name, ivs in spans.items():
        if name == 'window':
            continue
        for s, e in ivs:
            if s <= t <= e and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
    return best


NAMED_ROLES = (('prefix', '_kernel_slots'), ('resume', '_kernel_compact'),
               ('lookup', 'rc_lookup'))
STATE_RESULT = re.compile(r'^\(?f32\[[\d,]*,3,256\]')
HIT_RESULT = re.compile(r'^\(?s32\[')


def kernel_role(text: str, first_in_run: bool) -> str | None:
    """The role of a device operation that is a Pallas kernel, else None.
    ``first_in_run``: no pixel-state kernel ran before it in its program
    run."""
    if 'tpu_custom_call' not in text:
        return None
    name, _, rest = text.partition('=')
    for role, key in NAMED_ROLES:
        if key in name:
            return role
    result = rest.strip()
    if STATE_RESULT.match(result):
        return 'prefix' if first_in_run else 'resume'
    if HIT_RESULT.match(result):
        return 'lookup'
    return 'kernel'


def reduce_events(device_ops: dict, device_modules: dict, spans: dict,
                  gaps_kept: int = 10) -> Reduced:
    """The reduction on plain data: ``device_ops``/``device_modules`` map a
    device to ``[(name, start_s, end_s)]``; ``spans`` maps a span name
    (without the ``bench.`` prefix) to ``[(start_s, end_s)]``."""
    if not spans.get('window'):
        raise ValueError('trace has no bench.window span')
    lo, hi = spans['window'][0]
    op_s, op_count, module_s = defaultdict(float), defaultdict(int), \
        defaultdict(float)
    busy, gaps = 0.0, []
    for dev, evs in device_ops.items():
        ivs = clip([(s, e) for _, s, e in evs], lo, hi)
        busy += union_length(ivs)
        for name, s, e in evs:
            c = clip([(s, e)], lo, hi)
            if c:
                op_s[name] += c[0][1] - c[0][0]
                op_count[name] += 1
        cursor = lo
        for s, e in merged(ivs) + [(hi, hi)]:
            if s > cursor:
                gaps.append((s - cursor, innermost(spans, (s + cursor) / 2)))
            cursor = max(cursor, e)
    kernel_s, module_roles = defaultdict(float), defaultdict(set)
    for dev, evs in device_modules.items():
        for name, s, e in evs:
            c = clip([(s, e)], lo, hi)
            if c:
                module_s[name] += c[0][1] - c[0][0]
        runs = sorted(evs, key=lambda m: m[1])
        starts = [m[1] for m in runs]
        seen_state = set()
        for name, s, e in sorted(device_ops.get(dev, []), key=lambda o: o[1]):
            i = bisect.bisect_right(starts, s) - 1
            run = i if i >= 0 and runs[i][2] >= e else None
            role = kernel_role(name, run not in seen_state)
            if role is None:
                continue
            if role in ('prefix', 'resume'):
                seen_state.add(run)
            if run is not None:
                module_roles[runs[run][0]].add(role)
            c = clip([(s, e)], lo, hi)
            if c:
                kernel_s[role] += c[0][1] - c[0][0]
    n = max(1, len(device_ops))
    gaps.sort(key=lambda g: -g[0])
    return Reduced(window=(lo, hi), busy_s=busy / n, devices=len(device_ops),
                   op_s=dict(op_s), op_count=dict(op_count),
                   module_s=dict(module_s), kernel_s=dict(kernel_s),
                   module_roles={k: sorted(v) for k, v in
                                 module_roles.items()},
                   spans=spans,
                   gaps=gaps[:gaps_kept])


def load(path: str) -> tuple:
    """(device_ops, device_modules, spans) out of an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, mods, spans = {}, {}, defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith('/device:TPU:'):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = ops if line.name == OPS_LINE else mods
                    dst[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans[ev.name[len(SPAN_PREFIX):]].append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    return ops, mods, dict(spans)


def reduce_file(path: str) -> Reduced:
    return reduce_events(*load(path))


def find_xplane(trace_dir) -> str:
    from pathlib import Path
    found = sorted(Path(trace_dir).rglob('*.xplane.pb'))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return str(found[-1])
