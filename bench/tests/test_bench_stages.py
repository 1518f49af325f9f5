"""The stage reduction (``harness.stages``): shade-stage scopes out of the
device operations' ``tf_op``, the program's own host spans, and the self
time of a span less the child spans it holds, on plain data and on a small
trace recorded on a TPU v5e, with every number worked by hand; and the
kernel roles of the named ``pallas_call``s.

``data/stages.xplane.pb``: one jitted program ``prog`` whose ``shade/prep``
scope holds an elementwise op and whose ``shade/raster`` scope holds a
Pallas kernel named ``_kernel_slots`` and a matrix product, run once inside
the benchmark's ``bench.window`` > ``bench.tick`` spans and a live
``repro.obs.Tracer``'s ``step_dispatch`` (holding ``sort_wait``, the block
on the result) and ``observe_tick`` (holding ``fetch``, the result's copy
to the host).  Its events (seconds on the profiler's clock, picoseconds
kept; the device's sit about 1.3 ms early against the host's):

* spans: window 0.044126006 .. 0.051534656, tick 0.044129816 ..
  0.049794646, step_dispatch 0.044177386 .. 0.048297146, sort_wait
  0.046504266 .. 0.047146276, observe_tick 0.048362426 .. 0.049790666,
  fetch 0.048368426 .. 0.049774716;
* the program run 0.045181470922 .. 0.04519202975 (10.558828 us), its
  five ops back to back: copy-start (no scope) 6.172 ns, the prep fusion
  7.249922 us, ``_kernel_slots`` 0.174922 us, copy-done (no scope)
  3.75 ns, the raster matrix product 3.11625 us, ending at
  0.045192029672.
"""
from pathlib import Path

import pytest

from harness import stages, trace

RECORDED = Path(__file__).parent / 'data' / 'stages.xplane.pb'

KERN = 'custom_call_target="tpu_custom_call"'


@pytest.mark.parametrize('tf_op, stage', [
    ('jit(batched_shade_phase)/shade/prep/vmap(jit(gather))/gather:',
     'prep'),
    ('jit(_shade_sub_fn)/shade/lanes/jit(batched_shade_phase)/shade/raster/'
     '_kernel_slots/pallas_call:', 'raster'),
    ('jit(f)/shade/rc_insert/scatter:', 'rc_insert'),
    ('jit(_sort_pool_fn)/sort:', None),
    ('reduce_window_sum:', None),
    ('', None),
])
def test_stage_of_takes_the_innermost_shade_scope(tf_op, stage):
    assert stages.stage_of(tf_op) == stage


def test_reduce_stages_by_hand():
    # window 0..10 s.  A shade run 1..5 s holds a prep op (1..2), a raster
    # op (2..3.5) and an op under no stage (3.5..4); a sort run 6..7 s
    # holds one unscoped op, so it is not shade work.  Shade runs: 4 s of
    # which 2.5 s staged: coverage 0.625.
    ops = {'d': [('p', 1.0, 2.0, 'jit(s)/shade/prep/gather:'),
                 ('r', 2.0, 3.5, 'jit(s)/shade/raster/pallas_call:'),
                 ('u', 3.5, 4.0, 'jit(s)/add:'),
                 ('q', 6.0, 7.0, 'jit(sort)/sort:')]}
    mods = {'d': [('jit_shade', 1.0, 5.0), ('jit_sort', 6.0, 7.0)]}
    spans = {'bench.window': [(0.0, 10.0)], 'bench.tick': [(0.0, 8.0)],
             'lumina.step_dispatch': [(4.5, 6.5)],
             'lumina.sort_wait': [(5.2, 6.2)]}
    st = stages.reduce_stages(ops, mods, spans)
    assert st.stage_s == {'prep': 1.0, 'raster': 1.5}
    assert st.unscoped_s == 0.5
    # the unscoped op ran after the raster op: placed in raster
    assert st.placed_s == {'prep': 1.0, 'raster': 2.0}
    assert st.ops[('', 'raster', 'u')] == 0.5
    assert not [k for k in st.ops if k[2] == 'q']
    assert st.shade_s == 4.0
    assert st.coverage == 0.625
    # idle gaps: 0..1 (only the tick open), 4..6 (midpoint 5.0: dispatch
    # open, the wait not yet), 7..10 (midpoint 8.5: past the tick)
    assert st.gaps == [(3.0, 'none'), (2.0, 'lumina.step_dispatch'),
                       (1.0, 'bench.tick')]


def test_gap_under_a_child_span_takes_the_child():
    ops = {'d': [('a', 0.0, 1.0, ''), ('b', 3.0, 4.0, '')]}
    spans = {'bench.window': [(0.0, 4.0)],
             'lumina.step_dispatch': [(0.5, 3.5)],
             'lumina.sort_wait': [(1.5, 2.8)]}
    st = stages.reduce_stages(ops, {}, spans)
    assert st.gaps == [(2.0, 'lumina.sort_wait')]
    assert st.shade_s == 0.0 and st.coverage == 0.0


def test_place_takes_the_stage_an_unscoped_op_ran_amid():
    assert stages.place(['', 'prep', '', '', 'raster', '', 'rc_insert']) \
        == ['prep', 'prep', 'prep', 'prep', 'raster', 'raster', 'rc_insert']
    assert stages.place(['', '']) == ['', '']
    assert stages.place([]) == []


def test_self_time_by_hand():
    # dispatch 0..3 holds a wait 1..2.5 (1.5 s self); dispatch 5..6 is cut
    # at the window's end 5.5 (0.5 s); the wait 7..8 is no dispatch's
    spans = {'lumina.step_dispatch': [(0.0, 3.0), (5.0, 6.0)],
             'lumina.sort_wait': [(1.0, 2.5), (7.0, 8.0)]}
    got = stages.self_time(spans, 'lumina.step_dispatch',
                           'lumina.sort_wait', 0.0, 5.5)
    assert got == pytest.approx(2.0, abs=1e-12)
    assert stages.self_time({}, 'lumina.step_dispatch', 'lumina.sort_wait',
                            0.0, 5.5) == 0.0


def test_no_window_is_an_error():
    with pytest.raises(ValueError, match='bench.window'):
        stages.reduce_stages({}, {}, {'lumina.tick': [(0.0, 1.0)]})


@pytest.mark.parametrize('text, first, role', [
    # each kernel where the shade program runs it: phase A first in its
    # program run, the probe next, the compacted phase B after them
    (f'%_kernel_slots.3 = (f32[2,8160,3,256], f32[2,8160,1,256]) '
     f'custom-call(), {KERN}', True, 'prefix'),
    (f'%vmap_rc_lookup_.1 = (s32[2,1020,1,1024], f32[2,1020,3,1024]) '
     f'custom-call(), {KERN}', False, 'lookup'),
    (f'%_kernel_compact.2 = (f32[16320,3,256], f32[16320,1,256]) '
     f'custom-call(), {KERN}', False, 'resume'),
    # the per-tile kernel of the unbatched wrappers, as phase A and B
    (f'%_kernel_tiles.4 = (f32[8160,3,256], f32[8160,1,256]) '
     f'custom-call(), {KERN}', True, 'prefix'),
    (f'%_kernel_tiles.4 = (f32[8160,3,256], f32[8160,1,256]) '
     f'custom-call(), {KERN}', False, 'resume'),
])
def test_kernel_names_keep_their_roles(text, first, role):
    """A named kernel gets the role the shape rule gave it unnamed
    (``_unknown_``) where the program runs it; the names of phase A, the
    compacted phase B and the probe decide it wherever it runs."""
    unnamed = '%_unknown_.9' + text[text.index(' ='):]
    assert trace.kernel_role(text, first) == role
    assert trace.kernel_role(unnamed, first) == role
    if '_kernel_tiles' not in text:
        assert trace.kernel_role(text, not first) == role


def test_recorded_v5e_trace_by_hand():
    st = stages.reduce_file(str(RECORDED))
    assert st.window == pytest.approx((0.044126006, 0.051534656), abs=1e-12)
    us = lambda x: pytest.approx(x * 1e-6, abs=1e-12)
    assert st.shade_s == us(10.558828)
    assert st.stage_s == {'prep': us(7.249922),
                          'raster': us(0.174922 + 3.11625)}
    assert st.unscoped_s == us(0.006172 + 0.00375)
    # copy-start ran before the first scoped op: placed with it in prep;
    # copy-done ran after the kernel: raster
    assert st.placed_s == {'prep': us(7.249922 + 0.006172),
                           'raster': us(0.174922 + 0.00375 + 3.11625)}
    assert st.coverage == pytest.approx((7.249922 + 3.291172) / 10.558828,
                                        abs=1e-9)
    # the longest idle gaps: window start .. the program (midpoint
    # 0.04465374 s, inside step_dispatch), the program .. window end
    # (midpoint 0.04836334 s, inside observe_tick, before fetch opens)
    (g1, l1), (g2, l2) = st.gaps[:2]
    assert (l1, l2) == ('lumina.observe_tick', 'lumina.step_dispatch')
    assert g1 == pytest.approx(0.051534656 - 0.045192029672, abs=1e-12)
    assert g2 == pytest.approx(0.04518147725 - 0.044126006, abs=1e-12)
    dispatch = stages.self_time(st.spans, 'lumina.step_dispatch',
                                'lumina.sort_wait', *st.window)
    assert dispatch == pytest.approx((0.048297146 - 0.044177386)
                                     - (0.047146276 - 0.046504266), abs=1e-12)


def test_recorded_kernel_name_gives_its_role():
    """The benchmark's reduction finds the named kernel of the recorded
    trace by its name, as phase A."""
    red = trace.reduce_file(str(RECORDED))
    (name,) = [k for k in red.op_s if 'tpu_custom_call' in k]
    assert name.startswith('%_kernel_slots.1 = ')
    assert set(red.kernel_s) == {'prefix'}
    assert red.kernel_s['prefix'] == pytest.approx(0.174e-6, abs=1.5e-9)
    assert list(red.module_roles.values()) == [['prefix']]


@pytest.mark.parametrize('control', [False, True])
def test_breakdown_serves_a_cell_at_test_size(tmp_path, control):
    """``bench/breakdown.py`` off the chip, on the pure-JAX backend: three
    whole-cycle windows; traced, the middle one holds the program's spans
    (host only: no device plane) and the kept trace reads back."""
    import breakdown
    from harness import spec
    _, cfg, mix = spec.cell('blender-800.distinct4')
    cfg = dict(cfg, num_gaussians=2000, width=96, height=64, capacity=128,
               backend='reference')
    kept = tmp_path / 'middle.xplane.pb.gz'
    res = breakdown.measure('blender-800.distinct4', cfg, mix, 2**33 + 7,
                            0.5, require_chip=False, control=control,
                            keep_trace=None if control else str(kept))
    assert [(w['ticks'], w['frames']) for w in res['windows'].values()] \
        == [(6, 24)] * 3
    if control:
        assert set(res) == {'workload', 'seed', 'control', 'device',
                            'windows'}
        return
    spans = res['span_ms_per_tick']
    assert {'tick', 'plan_tick', 'step_dispatch', 'sort_wait',
            'observe_tick', 'fetch'} <= set(spans)
    assert 0 < res['dispatch_ms_per_tick'] < spans['step_dispatch']
    assert res['shade_ms_per_frame'] == 0.0 and res['per_layer'] == {}
    _, _, kept_spans = stages.load(str(kept))
    assert len(kept_spans['lumina.tick']) == 6
