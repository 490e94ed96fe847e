"""Median-of-chunks and due-time latency arithmetic on made-up timelines."""

import pytest

from chipbench import timeline


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert timeline.percentile(v, 50) == 50
    assert timeline.percentile(v, 95) == 95
    assert timeline.percentile(v, 99) == 99
    assert timeline.percentile([3.0], 95) == 3.0
    assert timeline.percentile([], 50) is None


def test_a_stalled_chunk_moves_the_mean_not_the_median():
    # 20 chunks of 1280 items, one second apart; the 11th takes 3 s more
    done, t = [], 0.0
    for i in range(21):
        t += 1.0 + (3.0 if i == 11 else 0.0)
        done.append(t)
    r = timeline.train_reading(done, 1280, done[0], 1e9)
    assert r["chunks"] == 20
    assert r["median_items_per_s"] == pytest.approx(1280.0)
    assert r["mean_items_per_s"] == pytest.approx(1280 * 20 / 23.0)
    assert r["slowest_chunk_s"] == pytest.approx(4.0)


def test_only_chunks_completed_inside_the_window_count():
    done = [float(i) for i in range(0, 60)]
    rates = timeline.chunk_rates(done, 100, t_open=10.0, seconds=20.0)
    assert len(rates) == 20           # completions 11..30, each from 10..29
    short = timeline.train_reading(done, 100, 10.0, 5.0)
    assert short["chunks"] == 5
    assert short["mean_items_per_s"] == pytest.approx(100.0)
    assert timeline.train_reading(done, 100, 100.0, 5.0) is None


def test_latency_runs_from_due_time_not_from_send_time():
    due = [1.0, 1.1, 1.2, 1.3]
    sent = [1.0, 1.1, 1.5, 1.5]          # the generator ran 300, 200 ms late
    done = [1.05, 1.15, 1.55, 1.55]      # the server took 50 ms each time
    r = timeline.serve_reading(due, sent, done, t_end=2.0, limit_ms=100.0)
    assert r["requests"] == 4
    assert r["p50_ms"] == pytest.approx(50.0)
    assert r["p95_ms"] == pytest.approx(350.0)     # charged from due time
    assert r["gen_late_p99_ms"] == pytest.approx(300.0)
    assert r["within_limit_share"] == pytest.approx(50.0)


def test_a_failed_request_counts_as_beyond_any_limit():
    due = [1.0 + 0.01 * i for i in range(10)]
    done = [d + 0.01 for d in due]
    done[4] = None
    r = timeline.serve_reading(due, due, done, t_end=3.0, limit_ms=50.0)
    assert r["within_limit_share"] == pytest.approx(90.0)
    assert r["p99_ms"] == pytest.approx((3.0 - due[4]) * 1000.0)


def test_first_second_is_left_out_and_per_second_rows():
    due = [0.5, 0.9, 1.5, 2.5, 2.6]
    done = [9.0, 9.0, 1.51, 2.52, 2.63]
    lat = timeline.due_latencies_ms(due, done, 10.0, skip_s=1.0)
    assert lat == pytest.approx([10.0, 20.0, 30.0])
    rows = timeline.per_second(due, done, 10.0, skip_s=1.0)
    assert [r[:2] for r in rows] == [[1, 1], [2, 2]]
    assert rows[1][2] == pytest.approx(20.0)


def test_backlog_growth_is_seen():
    due = [1.0 + 0.01 * i for i in range(1000)]
    steady = [d + 0.02 for d in due]
    growing = [d + 0.02 + 0.05 * (d - 1.0) for d in due]
    assert not timeline.backlog_grows(due, steady, 12.0)
    assert timeline.backlog_grows(due, growing, 12.0)


def test_quartile_spread_is_the_drivers():
    v = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4): Q1 = 100.75, Q3 = 104.25
    assert timeline.quartile_spread(v) == pytest.approx(3.5 / 102.5)
