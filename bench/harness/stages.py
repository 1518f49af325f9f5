"""Where the shade program's device time goes, and the program's own host
spans, out of a profiler trace (``.xplane.pb``).

The program marks the stages of its shade step with ``jax.named_scope``
(``shade/prep``, ``shade/raster``, ``shade/rc_probe``, ``shade/rc_insert``,
``shade/lanes``); every operation the compiler makes of a stage carries
the scope in its HLO op name, which the trace keeps as the ``tf_op`` stat
of the operation's event metadata on a device plane.  A live
``repro.obs.Tracer`` writes its context-manager spans on the host planes
as ``lumina.<span>`` (``step_dispatch``, ``sort_wait``, ``observe_tick``,
``fetch``, ...), beside the benchmark's ``bench.<span>`` ones.

``jax.profiler.ProfileData`` does not expose event metadata, so this
module parses the trace with the vendored protocol buffer module
(``harness.xplane_pb2``).  Times are seconds on the profiler's clock, as
in ``harness.trace``; a program run is shade work when any operation in
it carries a stage scope.

The TPU compiler drops the op name of some operations it makes: the
scatters it expands (the cache insert's rounds), fusions of ops from two
stages, layout copies.  Such an operation is counted apart
(``unscoped_s``) and also placed in the stage it ran amid, by its place in
its program run's order on the device (``placed_s``).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

from harness import trace

STAGE = re.compile(r'(?:^|/)shade/(\w+)')
BENCH_PREFIX = 'bench.'
PROGRAM_PREFIX = 'lumina.'


def stage_of(tf_op: str) -> str | None:
    """The innermost ``shade/<stage>`` scope of an HLO op name, else None."""
    found = STAGE.findall(tf_op or '')
    return found[-1] if found else None


@dataclasses.dataclass
class Stages:
    window: tuple        # (start, end) of bench.window
    ops: dict            # (scope, place, op name) -> seconds of the shade
                         # runs' ops in the window: scope the op's own stage
                         # ('' for none), place its scope or, for an op the
                         # compiler left unscoped, the stage it ran amid
    shade_s: float       # seconds of the shade program runs in the window
    spans: dict          # 'bench.<span>' / 'lumina.<span>' -> [(s, e)]
    gaps: list           # [(seconds, innermost span)], longest first

    def _sum(self, key) -> dict:
        out = defaultdict(float)
        for k, sec in self.ops.items():
            out[key(k)] += sec
        out.pop('', None)
        return dict(out)

    @property
    def stage_s(self) -> dict:
        """Stage -> device seconds of the operations scoped to it."""
        return self._sum(lambda k: k[0])

    @property
    def placed_s(self) -> dict:
        """Stage -> device seconds of the operations scoped to it, and of
        the unscoped ones that ran amid it."""
        return self._sum(lambda k: k[1])

    @property
    def unscoped_s(self) -> float:
        """Seconds of the shade runs' operations under no stage scope."""
        return sum(sec for (scope, _, _), sec in self.ops.items()
                   if not scope)

    @property
    def coverage(self) -> float:
        """The share of the shade runs' time under a stage scope."""
        return sum(self.stage_s.values()) / self.shade_s \
            if self.shade_s > 0 else 0.0


def place(scopes: list) -> list:
    """Each op of one program run, in the order it ran, placed in a stage:
    its own scope, else the scope of the last scoped op before it (the
    first scoped op's, before any)."""
    first = next((s for s in scopes if s), '')
    out, last = [], first
    for s in scopes:
        last = s or last
        out.append(last)
    return out


def self_time(spans: dict, parent: str, child: str, lo: float,
              hi: float) -> float:
    """Seconds of the ``parent`` spans in [lo, hi] less the ``child`` spans
    each holds (host spans nest on their thread)."""
    kids = sorted(spans.get(child, []))
    starts = [s for s, _ in kids]
    total = 0.0
    for s, e in trace.clip(spans.get(parent, []), lo, hi):
        i = bisect.bisect_left(starts, s)
        inner = [(cs, ce) for cs, ce in kids[i:] if ce <= e]
        total += (e - s) - trace.union_length(inner)
    return total


def innermost(spans: dict, t: float) -> str:
    """The shortest span open at ``t`` other than ``bench.window``."""
    return trace.innermost({k: v for k, v in spans.items()
                            if k != BENCH_PREFIX + 'window'}, t)


def reduce_stages(device_ops: dict, device_modules: dict, spans: dict,
                  gaps_kept: int = 10) -> Stages:
    """The reduction on plain data: ``device_ops`` maps a device to
    ``[(name, start_s, end_s, tf_op)]``, ``device_modules`` to ``[(name,
    start_s, end_s)]``; ``spans`` maps a prefixed span name
    (``bench.window``, ``lumina.step_dispatch``, ...) to ``[(start_s,
    end_s)]``."""
    window = spans.get(BENCH_PREFIX + 'window')
    if not window:
        raise ValueError('trace has no bench.window span')
    lo, hi = window[0]
    op_s = defaultdict(float)
    shade_s, gaps = 0.0, []
    for dev, evs in device_modules.items():
        runs = sorted(evs, key=lambda m: m[1])
        starts = [m[1] for m in runs]
        ops_of = defaultdict(list)
        for op in device_ops.get(dev, []):
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and runs[i][2] >= op[2]:
                ops_of[i].append(op)
        for i, ops in ops_of.items():
            if not any(stage_of(op[3]) for op in ops):
                continue
            run = trace.clip([runs[i][1:3]], lo, hi)
            shade_s += sum(e - s for s, e in run)
            ops.sort(key=lambda op: op[1])
            scopes = [stage_of(op[3]) or '' for op in ops]
            for (name, s, e, _), scope, where in zip(ops, scopes,
                                                      place(scopes)):
                for cs, ce in trace.clip([(s, e)], lo, hi):
                    op_s[(scope, where, name)] += ce - cs
    for dev, evs in device_ops.items():
        cursor = lo
        ivs = trace.clip([(s, e) for _, s, e, _ in evs], lo, hi)
        for s, e in trace.merged(ivs) + [(hi, hi)]:
            if s > cursor:
                gaps.append((s - cursor, innermost(spans, (s + cursor) / 2)))
            cursor = max(cursor, e)
    gaps.sort(key=lambda g: -g[0])
    return Stages(window=(lo, hi), ops=dict(op_s), shade_s=shade_s,
                  spans=spans, gaps=gaps[:gaps_kept])


def load(path: str) -> tuple:
    """(device_ops, device_modules, spans) out of an ``.xplane.pb`` (or
    its gzip), in the shapes ``reduce_stages`` takes."""
    import gzip
    from harness import xplane_pb2
    space = xplane_pb2.XSpace()
    with (gzip.open if str(path).endswith('.gz') else open)(path, 'rb') as f:
        space.ParseFromString(f.read())
    ops, mods, spans = {}, {}, defaultdict(list)
    for plane in space.planes:
        meta = plane.event_metadata
        if plane.name.startswith('/device:TPU:'):
            tf_op = next((k for k, v in plane.stat_metadata.items()
                          if v.name == 'tf_op'), None)
            op_of = {k: next((s.str_value for s in m.stats
                              if s.metadata_id == tf_op), '')
                     for k, m in meta.items()}
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops[plane.name] = [
                        (meta[ev.metadata_id].name, *_interval(line, ev),
                         op_of[ev.metadata_id]) for ev in line.events]
                elif line.name == trace.MODULES_LINE:
                    mods[plane.name] = [
                        (meta[ev.metadata_id].name, *_interval(line, ev))
                        for ev in line.events]
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if name.startswith((BENCH_PREFIX, PROGRAM_PREFIX)):
                        spans[name].append(_interval(line, ev))
    return ops, mods, dict(spans)


def _interval(line, ev) -> tuple:
    start_ps = line.timestamp_ns * 1000 + ev.offset_ps
    return start_ps * 1e-12, (start_ps + ev.duration_ps) * 1e-12


def reduce_file(path: str) -> Stages:
    return reduce_stages(*load(path))
