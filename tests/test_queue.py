"""serve/queue.py: nearest-rank percentile edge cases + admission order.

The percentile helper feeds the TTFT/TPOT numbers in serving_metrics()
and the stream-latency bench gates, so its edge behavior (empty, single
sample, p0/p100, duplicates, fractional q) is pinned here exactly —
nearest-rank means every reported latency is one some request actually
saw, never an interpolated value between two.
"""
import math

import pytest

from hypothesis import given, settings, strategies as st

from repro.serve.queue import AdmissionQueue, QueueFullError, percentile


class TestPercentile:
    def test_empty_returns_zero(self):
        assert percentile([], 50) == 0.0
        assert percentile([], 0) == 0.0
        assert percentile([], 100) == 0.0

    @pytest.mark.parametrize("q", [0, 1, 50, 99, 100])
    def test_single_element_is_that_element_at_any_q(self, q):
        assert percentile([7.25], q) == 7.25

    def test_p0_is_min_p100_is_max(self):
        xs = [9.0, 1.0, 5.0, 3.0]
        assert percentile(xs, 0) == 1.0
        assert percentile(xs, 100) == 9.0

    def test_does_not_mutate_input(self):
        xs = [3.0, 1.0, 2.0]
        percentile(xs, 50)
        assert xs == [3.0, 1.0, 2.0]

    def test_median_nearest_rank(self):
        # nearest-rank p50 of n=4 is the ceil(0.5*4)=2nd order statistic,
        # NOT the interpolated midpoint 2.5
        assert percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
        assert percentile([1.0, 2.0, 3.0], 50) == 2.0

    def test_duplicates(self):
        xs = [5.0] * 10
        for q in (0, 37, 50, 99, 100):
            assert percentile(xs, q) == 5.0
        # duplicated tail: p90 of ten samples is the 9th order statistic
        xs = [1.0] * 8 + [9.0, 9.0]
        assert percentile(xs, 90) == 9.0
        assert percentile(xs, 80) == 1.0

    def test_fractional_q(self):
        xs = list(range(1, 101))              # 1..100
        assert percentile(xs, 99.5) == 100    # ceil(99.5) = 100th
        assert percentile(xs, 0.5) == 1       # ceil(0.5) = 1st
        assert percentile(xs, 12.3) == 13

    def test_p99_small_samples(self):
        # with < 100 samples p99 is simply the max — the usual serving
        # dashboard surprise, pinned so nobody "fixes" it to interpolate
        assert percentile([1.0, 2.0, 3.0], 99) == 3.0
        assert percentile(list(range(100)), 99) == 98

    @settings(max_examples=200)
    @given(st.integers(0, 2 ** 31), st.integers(1, 50),
           st.floats(0.0, 100.0))
    def test_matches_nearest_rank_definition(self, seed, n, q):
        """percentile == the textbook nearest-rank formula
        s[clamp(ceil(q/100 * n), 1, n) - 1], and the result is always an
        element of the input."""
        import numpy as np
        rng = np.random.default_rng(seed)
        xs = [float(x) for x in rng.integers(0, 20, size=n)]
        got = percentile(xs, q)
        s = sorted(xs)
        rank = min(max(math.ceil(q / 100 * n), 1), n)
        assert got == s[rank - 1]
        assert got in xs


class TestAdmissionQueue:
    def test_fifo_within_priority(self):
        q = AdmissionQueue()
        for i in range(4):
            q.push({"id": i})
        assert [q.pop()["id"] for _ in range(4)] == [0, 1, 2, 3]

    def test_priority_order_then_fifo(self):
        q = AdmissionQueue()
        q.push({"id": 0})
        q.push({"id": 1, "priority": 2})
        q.push({"id": 2, "priority": 1})
        q.push({"id": 3, "priority": 2})
        assert [q.pop()["id"] for _ in range(4)] == [1, 3, 2, 0]

    def test_limit_rejects_then_drains(self):
        q = AdmissionQueue(limit=2)
        q.push({"id": 0})
        q.push({"id": 1})
        with pytest.raises(QueueFullError):
            q.push({"id": 2})
        assert len(q) == 2                     # rejected push left no trace
        assert q.pop()["id"] == 0
        q.push({"id": 3})                      # space freed → accepted
        assert [q.pop()["id"], q.pop()["id"]] == [1, 3]

    def test_zero_limit_is_unbounded(self):
        q = AdmissionQueue(limit=0)
        for i in range(64):
            q.push({"id": i})
        assert len(q) == 64

    def test_peek_clear_bool(self):
        q = AdmissionQueue()
        assert not q
        q.push({"id": 7})
        assert q.peek()["id"] == 7 and len(q) == 1   # peek doesn't pop
        assert bool(q)
        q.clear()
        assert not q and len(q) == 0
