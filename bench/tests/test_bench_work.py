"""Work counts and the peaks table, on small shapes worked by hand."""
import pytest

from harness import peaks, work


def test_prefix_work_counts_record_walk_and_state():
    # 10 pixels, k_record 5, 3 Gaussians walked per pixel: min(5, 3) = 3
    # evaluations of 20 flops each; state is (7 + 5) words of 4 bytes
    ops, nbytes = work.prefix_work(10, 5, 3.0)
    assert ops == 10 * 3 * 20
    assert nbytes == 10 * 12 * 4


def test_resume_work_counts_misses_past_the_record():
    # 100 pixels, 75% hits -> 25 misses, each walking 8 - 5 = 3 more
    ops, nbytes = work.resume_work(100, 5, 8.0, 0.75)
    assert ops == 25 * 3 * 20
    assert nbytes == 2 * 25 * 12 * 4
    assert work.resume_work(100, 5, 4.0, 0.5)[0] == 0


def test_frame_ops_projects_and_composites():
    assert work.frame_ops(1000, 64, 2.5) == 1000 * 200 + 64 * 2.5 * 20


def test_least_time_takes_the_binding_bound():
    pk = {'flops_per_s': 100.0, 'hbm_bytes_per_s': 10.0}
    assert work.least_time(1000.0, 50.0, pk) == (10.0, 'ops')
    assert work.least_time(100.0, 50.0, pk) == (5.0, 'bytes')


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks_for('TPU v5 lite')
    assert v5e['flops_per_s'] == 197e12
    assert v5e['hbm_bytes_per_s'] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match='no published peaks'):
        peaks.peaks_for('TPU v9 imaginary')
