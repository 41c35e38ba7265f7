"""The metric arithmetic: tails over every request, rates over the whole
window, and the spread the bounds are set from."""
import statistics

import numpy as np
import pytest

from bench import arrivals, measure
from bench.loops import LATE_S, Record
from bench.harness import Run, end_to_end


def test_percentile_is_nearest_rank_over_all_values():
    vals = list(range(1, 101))
    assert measure.percentile(vals, 99) == 99
    assert measure.percentile(vals, 50) == 50
    assert measure.percentile([5.0], 99) == 5.0
    # 200 values: the 99th percentile is the 198th smallest
    vals = list(range(200))[::-1]
    assert measure.percentile(vals, 99) == 197
    with pytest.raises(ValueError):
        measure.percentile([], 99)


def test_rate_counts_only_the_window():
    done = [0.5, 1.0, 2.0, 3.0, 10.5]
    assert measure.rate(done, 1.0, 11.0) == pytest.approx(4 / 10)


def test_spread_uses_statistics_quartiles():
    vals = [10.0, 10.5, 11.0, 9.0, 10.2, 10.1]
    q1, q2, q3 = statistics.quantiles(vals, n=4)
    assert measure.spread(vals) == pytest.approx((q3 - q1) / q2)


def _run(records, t0=100.0, t_end=110.0):
    return Run(cell="c", config={}, traffic={}, queries=[], n_tokens=[],
               records=records, stats={}, n_batches=64, device_kind="x",
               t0=t0, t_end=t_end)


def test_p99_counts_failures_as_over_every_limit():
    recs = [Record(0, 100.0 + i * 0.01, done=100.0 + i * 0.01 + 0.002)
            for i in range(99)]
    recs.append(Record(0, 105.0, error="TimeoutError"))
    p99 = end_to_end("query_p99_ms", _run(recs), {})
    assert p99 == pytest.approx(2.0)
    recs.append(Record(0, 106.0))           # never answered
    p99 = end_to_end("query_p99_ms", _run(recs), {})
    assert p99 == pytest.approx(LATE_S * 1e3)


def test_queries_per_s_is_answers_inside_window_over_window():
    recs = [Record(0, 100.0 + i, done=100.5 + i) for i in range(12)]
    recs[3].error = "boom"
    # answers at 100.5 .. 111.5; inside [100, 110]: 10, one failed
    assert end_to_end("queries_per_s", _run(recs), {}) == pytest.approx(0.9)


def test_open_schedule_offers_the_same_count_for_every_seed():
    for seed in (1, 2**31 + 5):
        due, order = arrivals.open_schedule(7, 50.0, 4.0, seed)
        assert len(due) == 200 and np.all(np.diff(due) >= 0)
        assert due[0] >= 0 and due[-1] < 4.0
        assert np.bincount(order, minlength=7).max() \
            - np.bincount(order, minlength=7).min() <= 1


def test_closed_sequences_take_equal_shares():
    scen = ["a"] * 4 + ["b"] * 4 + ["c"] * 4
    seqs = arrivals.closed_sequences(scen, 3, 30, seed=9)
    for seq in seqs:
        counts = np.bincount([ord(scen[i]) - 97 for i in seq])
        assert list(counts) == [10, 10, 10]
    assert seqs[0] != seqs[1]
