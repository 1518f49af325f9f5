"""Each configuration and traffic mix loads by name and gives the same
viewers for the same seed; seeds deal out offsets, never change the work."""
import json

import numpy as np
import pytest

from harness import spec, traffic

SPEC = spec.benchmark()
CELLS = [w['name'] for w in SPEC['workloads']]


@pytest.mark.parametrize('name', CELLS)
def test_cell_files_load_by_name(name):
    work, cfg, mix = spec.cell(name)
    assert cfg['name'] == work['config']
    assert mix['name'] == work['traffic']
    for key in ('num_gaussians', 'width', 'height', 'capacity', 'window',
                'margin', 'k_record', 'group_tiles', 'sort_method'):
        assert key in cfg
    entry = next(c for c in SPEC['configs'] if c['name'] == work['config'])
    assert set(entry['reduced']) == set(cfg['reduced'])
    assert spec.reference(cfg['reference']).Replay
    assert spec.system(cfg['system']).System


@pytest.mark.parametrize('name', CELLS)
def test_same_seed_same_viewers(name):
    _, _, mix = spec.cell(name)
    seed = 2**33 + 17
    a, b = traffic.viewers(mix, seed), traffic.viewers(mix, seed)
    assert a == b
    for va, vb in zip(a, b):
        for i in (0, 5, 999):
            pa, pb = va.orbit.pose(i), vb.orbit.pose(i)
            assert np.array_equal(pa[0], pb[0])
            assert np.array_equal(pa[1], pb[1])


@pytest.mark.parametrize('name', CELLS)
def test_seeds_change_offsets_not_work(name):
    _, _, mix = spec.cell(name)
    a, b = traffic.viewers(mix, 1), traffic.viewers(mix, 2)
    assert [(v.scene_block, v.pace) for v in a] == \
        [(v.scene_block, v.pace) for v in b]
    assert [v.orbit.start_deg for v in a] == [v.orbit.start_deg for v in b]
    offsets = sorted(tuple(o) for o in mix.get('offsets') or [])
    for got in (a, b):
        if offsets:
            assert sorted(v.orbit.offset for v in got) == offsets
        else:
            assert all(v.orbit.offset == (0.0, 0.0, 0.0) for v in got)


def test_orbit_loops_and_offsets_only_move_the_position():
    o = traffic.Orbit(90.0, 25.0, 2.2, 0.25, 0.05)
    moved = traffic.Orbit(90.0, 25.0, 2.2, 0.25, 0.05,
                          offset=(0.01, 0.0, -0.01))
    assert o.loop == 1296                   # 360 deg at 25 deg/s, 90 fps
    assert np.allclose(o.pose(3)[0], o.pose(3 + o.loop)[0])
    assert np.allclose(moved.pose(7)[0] - o.pose(7)[0], [0.01, 0, -0.01])
    assert np.array_equal(moved.pose(7)[1], o.pose(7)[1])


def test_only_setup_arrivals_are_served():
    mix = {'viewers': 3, 'viewers_per_scene': 1, 'pace': 2,
           'start_deg_step': 90.0, 'orbit': {
               'fps': 90.0, 'deg_per_sec': 25.0, 'radius': 2.2,
               'height': 0.25, 'translate_per_sec': 0.05}}
    got = traffic.viewers(mix, 9)
    assert [v.pace for v in got] == [2] * 3
    with pytest.raises(ValueError):
        traffic.viewers(dict(mix, arrival='poisson'), 9)


def test_benchmark_json_names_only_files_that_exist():
    for c in SPEC['configs']:
        assert (spec.ROOT / c['file']).is_file()
        json.loads((spec.ROOT / c['file']).read_text())
    for w in SPEC['workloads']:
        assert (spec.BENCH / 'traffic' / f'{w["traffic"]}.json').is_file()
        assert (spec.BENCH / 'limits' / f'{w["name"]}.json').is_file()
    for m in SPEC['per_layer']:
        assert callable(spec.reader(m['name']))
