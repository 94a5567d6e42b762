"""The traffic generator's schedule and sample, and the knee sweep."""
import numpy as np
import pytest

from chipbench import generator, sweep
from chipbench.readings import nearest_rank


def test_schedules_share_their_gaps_in_another_order():
    a = generator.arrival_gaps(8.0, 51.0, 2**31 + 99)
    b = generator.arrival_gaps(8.0, 51.0, 12)
    assert len(a) == 408
    assert np.array_equal(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, generator.arrival_gaps(8.0, 51.0, 2**31 + 99))
    assert a.sum() == pytest.approx(51.0, rel=0.02)


def test_reservoir_keeps_a_seeded_uniform_sample():
    def sample(seed):
        r = generator.Reservoir(5, generator.rng(seed, 3))
        for i in range(100):
            r.offer(i)
        return sorted(r.items)
    assert len(sample(1)) == 5 and sample(1) == sample(1)
    assert sample(1) != sample(2)
    assert max(max(sample(s)) for s in range(20)) > 50


def test_nearest_rank_counts_failures_as_never_answered():
    assert nearest_rank([3.0, 1.0, 2.0, 4.0], 0.5) == 2.0
    lat = [0.1] * 95 + [float("inf")] * 5
    assert nearest_rank(lat, 0.95) == 0.1
    assert nearest_rank(lat + [float("inf")], 0.95) == float("inf")


def test_backlog_compares_the_last_quarter_with_the_first():
    assert sweep.backlog([1.0] * 8) == 1.0
    assert sweep.backlog([1, 1, 1, 1, 2, 2, 4, 4]) == 4.0


def test_sweep_reports_each_rate(tiny):
    rows = sweep.sweep(tiny("gcn-s14-f128-closed1"), 4, [5.0, 20.0], 0.5)
    assert [r["rate_per_s"] for r in rows] == [5.0, 20.0]
    for r in rows:
        assert r["requests"] == round(r["rate_per_s"] * 0.5)
        assert r["failed"] == 0 and isinstance(r["sustained"], bool)
