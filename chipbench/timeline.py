"""The arithmetic that turns a run's raw times into its end-to-end numbers.

Every end-to-end number is taken over a window of whole readings: a
training rate is the items of whole
chunks over the time from the first chunk's completion to the last's (all
the work and all the time of the window; the median of the chunk rates is
kept beside it), a serving latency is a percentile over every request that
was due in the window. `chipbench/noise.py` feeds recorded runs through the
same functions under shorter windows, which is how `run_seconds` was chosen
(PERF.md, noise study).

No JAX here: the functions take lists of seconds from `time.perf_counter`.
"""

import math
import statistics


def percentile(values, q):
    """Nearest-rank percentile of `values` (q in 0..100): the smallest
    value with at least q% of the sample at or below it."""
    if not values:
        return None
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[min(k, len(s)) - 1]


def quartile_spread(values):
    """(Q3 - Q1) / median with `statistics.quantiles(n=4)`: the spread the
    driver reads. None under three values."""
    if len(values) < 3:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


# ---------------------------------------------------------------- training
def chunk_rates(done_s, items_per_chunk, t_open, seconds):
    """One reading per chunk: its items over the time since the chunk before
    it completed. `done_s` are the completion times of consecutive chunks
    (each taken after `block_until_ready`); the reading belongs to the
    window if its chunk completed in (t_open, t_open + seconds] and the one
    before it at or after t_open."""
    rates = []
    for prev, cur in zip(done_s, done_s[1:]):
        if prev >= t_open and cur <= t_open + seconds and cur > prev:
            rates.append(items_per_chunk / (cur - prev))
    return rates


def train_reading(done_s, items_per_chunk, t_open, seconds):
    """The readings of a window: `mean_items_per_s`, the items of its
    whole chunks over the time from the first completion to the last (the
    end-to-end rate: every stall counts), and beside it the median of the
    chunk rates (what a typical chunk did)."""
    rates = chunk_rates(done_s, items_per_chunk, t_open, seconds)
    if not rates:
        return None
    inside = [t for t in done_s if t_open <= t <= t_open + seconds]
    span = inside[-1] - inside[0]
    return {
        "median_items_per_s": statistics.median(rates),
        "mean_items_per_s": items_per_chunk * (len(inside) - 1) / span,
        "chunks": len(rates),
        "slowest_chunk_s": items_per_chunk / min(rates),
        "fastest_chunk_s": items_per_chunk / max(rates),
    }


# ----------------------------------------------------------------- serving
def due_latencies_ms(due_s, done_s, t_end, skip_s=1.0, until_s=None):
    """Latency of each request from when it was DUE (not from when the
    generator got round to sending it), in ms, over the requests due at
    `skip_s` or later (and before `until_s`). A request with no completion
    (failed, refused or still out at `t_end`) counts as having taken until
    `t_end`: beyond any limit a cell would set."""
    lat = []
    for due, done in zip(due_s, done_s):
        if due < skip_s or (until_s is not None and due >= until_s):
            continue
        lat.append(((t_end if done is None else done) - due) * 1000.0)
    return lat


def lateness_ms(due_s, sent_s, skip_s=1.0, until_s=None):
    """How late the generator sent each request, in ms (never negative)."""
    return [max(0.0, (sent - due) * 1000.0)
            for due, sent in zip(due_s, sent_s)
            if sent is not None and due >= skip_s
            and (until_s is None or due < until_s)]


def serve_reading(due_s, sent_s, done_s, t_end, limit_ms, skip_s=1.0,
                  until_s=None):
    lat = due_latencies_ms(due_s, done_s, t_end, skip_s, until_s)
    if not lat:
        return None
    late = lateness_ms(due_s, sent_s, skip_s, until_s)
    return {
        "p50_ms": percentile(lat, 50),
        "p95_ms": percentile(lat, 95),
        "p99_ms": percentile(lat, 99),
        "within_limit_share": 100.0 * sum(v <= limit_ms for v in lat)
        / len(lat),
        "gen_late_p50_ms": percentile(late, 50),
        "gen_late_p99_ms": percentile(late, 99),
        "requests": len(lat),
    }


def per_second(due_s, done_s, t_end, skip_s=1.0):
    """[second, requests, p50_ms, p95_ms] for each whole second of due
    time: the file the noise study reads."""
    rows, sec = [], int(skip_s)
    last = int(max(due_s)) if due_s else 0
    while sec <= last:
        lat = due_latencies_ms(due_s, done_s, t_end, float(sec),
                               float(sec + 1))
        if lat:
            rows.append([sec, len(lat), percentile(lat, 50),
                         percentile(lat, 95)])
        sec += 1
    return rows


def backlog_grows(due_s, done_s, t_end, skip_s=1.0):
    """True when the requests due in the last quarter of the window waited
    more than twice as long (median) as those of the first quarter and more
    than 5 ms longer: the queue is not in a steady state."""
    if not due_s:
        return False
    last = max(due_s)
    q = (last - skip_s) / 4.0
    first = due_latencies_ms(due_s, done_s, t_end, skip_s, skip_s + q)
    tail = due_latencies_ms(due_s, done_s, t_end, last - q, None)
    if not first or not tail:
        return False
    a, b = statistics.median(first), statistics.median(tail)
    return b > 2.0 * a and b > a + 5.0
