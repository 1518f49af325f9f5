"""The trace reduction, on plain data and on a small trace recorded on a
TPU v5e, with every number worked by hand.

``data/tiny.xplane.pb``: two tiny jitted programs, each inside a
``bench.tick`` span, both inside one ``bench.window`` span.  Its events
(seconds on the profiler's clock):

* window span 0.045219678 .. 0.049764898; tick spans 0.045222518 ..
  0.046201458 and 0.048771568 .. 0.049762998;
* program 1 (one op) 0.044357586 .. 0.044368719: the device clock sits
  about 0.9 ms early, so it falls before the window;
* program 2 0.047916406 .. 0.047921567, eight ops back to back with 1-2 ns
  between them, of 1.943, 0.857, 0.007, 0.051, 0.007, 0.010, 0.223 and
  2.046 us.
"""
from pathlib import Path

import pytest

from harness import trace

TINY = Path(__file__).parent / 'data' / 'tiny.xplane.pb'


def test_union_length_merges_overlaps():
    # [0,2] u [1,3] u [5,6] covers 3 + 1
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4


def test_reduce_events_by_hand():
    # window 0..10 s; one device runs ops a (1..3), b (2..4), a (6..7):
    # busy = [1,4] u [6,7] = 4 s; idle gaps 0..1 (tick span open),
    # 4..6 (plan span open), 7..10 (no span).
    ops = {'/device:TPU:0': [('a', 1.0, 3.0), ('b', 2.0, 4.0),
                             ('a', 6.0, 7.0)]}
    mods = {'/device:TPU:0': [('jit_shade', 1.0, 4.0), ('jit_sort', 6.0,
                                                        7.0)]}
    spans = {'window': [(0.0, 10.0)], 'tick': [(0.0, 8.0)],
             'plan_tick': [(3.5, 6.5)]}
    red = trace.reduce_events(ops, mods, spans)
    assert red.window_s == 10.0
    assert red.busy_s == 4.0
    assert red.op_s == {'a': 3.0, 'b': 2.0}
    assert red.op_count == {'a': 2, 'b': 1}
    assert red.module_s == {'jit_shade': 3.0, 'jit_sort': 1.0}
    assert red.gaps == [(3.0, 'none'), (2.0, 'plan_tick'), (1.0, 'tick')]
    assert red.kernel_s == {} and red.module_roles == {}


def test_kernel_roles_by_name_and_by_place():
    kern = 'custom_call_target="tpu_custom_call"'
    state = f'%_unknown_.2 = (f32[4,2500,3,256], f32[4,2500,1,256]) custom-call(), {kern}'
    hits = f'%vmap__.1 = (s32[4,625,1,1024], f32[4,625,3,1024]) custom-call(), {kern}'
    named = f'%_kernel_compact.7 = (f32[4,3,256]) custom-call(), {kern}'
    assert trace.kernel_role(state, True) == 'prefix'
    assert trace.kernel_role(state, False) == 'resume'
    assert trace.kernel_role(hits, True) == 'lookup'
    assert trace.kernel_role(named, True) == 'resume'
    assert trace.kernel_role('%fusion.3 = f32[8] fusion()', True) is None
    # one program run: phase A, the lookup, then phase B, 1 s each
    ops = {'d': [(state, 1.0, 2.0), (hits, 2.0, 3.0), (state, 3.0, 4.0)]}
    mods = {'d': [('jit__unknown(7)', 0.5, 4.5)]}
    red = trace.reduce_events(ops, mods, {'window': [(0.0, 5.0)]})
    assert red.kernel_s == {'prefix': 1.0, 'lookup': 1.0, 'resume': 1.0}
    assert red.module_roles == {'jit__unknown(7)': ['lookup', 'prefix',
                                                    'resume']}


def test_recorded_v5e_trace_by_hand():
    red = trace.reduce_file(str(TINY))
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.049764898 - 0.045219678, abs=1e-12)
    # program 2's eight ops do not overlap: busy is their summed length
    busy_us = 1.943 + 0.857 + 0.007 + 0.051 + 0.007 + 0.010 + 0.223 + 2.046
    assert red.busy_s == pytest.approx(busy_us * 1e-6, abs=1e-12)
    assert sum(red.op_count.values()) == 8
    assert red.module_s == {'jit__lambda(468657029524672863)': pytest.approx(
        (0.047921567 - 0.047916406), abs=1e-12)}
    # window start .. program 2: its midpoint lies between the ticks;
    # program 2 .. window end: inside the second tick span
    (g1, l1), (g2, l2) = red.gaps[:2]
    assert g1 == pytest.approx(0.047916412 - 0.045219678, abs=1e-12)
    assert l1 == 'none'
    assert g2 == pytest.approx(0.049764898 - 0.047921566, abs=1e-12)
    assert l2 == 'tick'
