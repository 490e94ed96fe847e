"""The trace reduction, on two small traces recorded on the v5e (PR 23) and
on made-up planes.

`small.xplane.pb`: four runs of a two-convolution jit, host tracer on (its
host plane is not read any more). `markers.xplane.pb` + `markers.host.json`:
the same jit traced the way the harness traces, host tracer off, with the
harness's clock markers and its own spans (`executor_run`, `fetch_resolve`
and, standing in for a 3 ms wait on the input, `feeder_next`)."""

import json
import os

import pytest

from chipbench import xplane
from chipbench.xplane import Event, Line, Plane

DATA = os.path.join(os.path.dirname(__file__), "..", "data")
TRACE = os.path.join(DATA, "small.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return xplane.load(TRACE)


def test_planes_and_lines_of_the_recorded_trace(planes):
    dev = xplane.device_planes(planes)
    assert [p.name for p in dev] == ["/device:TPU:0"]
    assert len(dev[0].line("XLA Modules").events) == 4
    assert len(dev[0].line("XLA Ops").events) == 36
    # planes the reduction never reads are not parsed
    assert all(not p.lines for p in planes if p.name != "/device:TPU:0")
    every = xplane.load(TRACE, wanted=None)
    host = next(p for p in every if p.name == "/host:CPU")
    assert sum(len(ln.events) for ln in host.lines) == 142


def test_metadata_stats_reach_the_events(planes):
    ops = xplane.device_planes(planes)[0].line("XLA Ops").events
    conv = next(e for e in ops if xplane.op_base(e.name) == "fusion")
    assert conv.stats["hlo_category"] == "convolution fusion"
    assert conv.stats["flops"] == 7249330176
    assert xplane.stable_name(conv) == "convolution_fusion:fusion"
    assert xplane.op_code(conv.name) == "fusion"


def test_busy_union_idle_share_and_kernel_time_by_name(planes):
    r = xplane.reduce_trace(planes)
    ops = xplane.device_planes(planes)[0].line("XLA Ops").events
    by_hand = sum(e.dur_ps for e in ops) * 1e-12   # no op overlaps another
    assert r["busy_s"] == pytest.approx(by_hand, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.000808179766, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.013469300156, rel=1e-6)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.94, abs=0.001)
    top = dict(r["device_ops"])
    assert top["convolution_fusion:fusion"] == pytest.approx(
        0.000719285078, rel=1e-6)
    assert r["category_seconds"]["convolution fusion"] == pytest.approx(
        0.000808050078, rel=1e-6)
    # no host record: every gap is unattributed
    assert dict(r["idle_gaps"])[xplane.NO_SPAN] == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-3)


def test_gaps_go_to_the_spans_the_harness_recorded():
    with open(os.path.join(DATA, "markers.host.json")) as f:
        host = json.load(f)
    planes = xplane.load(os.path.join(DATA, "markers.xplane.pb"))
    r = xplane.reduce_trace(planes, host=host)
    assert r["window_s"] == pytest.approx(
        host["window"][1] - host["window"][0], rel=1e-6)
    # four runs of ~0.2 ms each; the markers are not counted as work
    assert r["busy_s"] == pytest.approx(0.00081, rel=0.02)
    assert not any(xplane.MARKER in k for k in r["op_seconds"])
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # four waits of 3 ms: most of the idle time, known to about a
    # millisecond an edge
    assert gaps["chipbench.feeder_next"] == pytest.approx(0.0125, abs=0.004)
    assert gaps["chipbench.feeder_next"] > 0.5 * sum(gaps.values())
    # the offset's two bounds: dispatch before start, end before wake-up
    marks = [e for e in xplane.device_planes(planes)[0].line(
        "XLA Modules").events if xplane.MARKER in e.name]
    c = r["host_minus_device_clock_s"]
    for (before, after), e in zip(host["syncs"], marks):
        assert before - 1e-12 * e.start_ps <= c <= after - 1e-12 * e.end_ps


def _ev(name, start_us, dur_us, **stats):
    return Event(name, int(start_us * 1e6), int(dur_us * 1e6), stats)


def test_made_up_planes_window_span_nesting_and_collectives():
    ops = [
        _ev("%while.1 = () while()", 100, 800),            # container
        _ev("%fusion.3 = f32[] fusion()", 100, 300,
            hlo_category="convolution fusion"),
        _ev("%all-reduce-start.1 = f32[] all-reduce-start()", 400, 10,
            hlo_category="all-reduce"),
        _ev("%fusion.4 = f32[] fusion()", 500, 100,
            hlo_category="loop fusion"),
        _ev("%all-reduce-done.1 = f32[] all-reduce-done()", 600, 300,
            hlo_category="all-reduce"),
    ]
    asyncs = [_ev("%all-reduce-start.1 = f32[] all-reduce-start()", 400,
                  500, hlo_category="all-reduce")]
    marker = [_ev("jit_chipbench_clock_marker(1)", 10, 2)]
    dev = Plane("/device:TPU:0", [Line("XLA Modules", marker),
                                  Line("XLA Ops", ops),
                                  Line("Async XLA Ops", asyncs)])
    # the host's clock reads 5 s more than the device's: the marker was
    # dispatched at 5.000008 and known done at 5.000014
    host = {"syncs": [[5.000008, 5.000014]], "window": [5.0, 5.002],
            "spans": [["chipbench.feeder_next", 5.0009, 5.0015, "main"],
                      ["chipbench.executor_run", 5.0015, 5.0016, "main"]]}
    r = xplane.reduce_trace([dev], host=host)
    assert r["host_minus_device_clock_s"] == pytest.approx(5.0, abs=1e-9)
    assert r["window_s"] == pytest.approx(2e-3)
    # the while loop covers 100..900: busy is its whole span
    assert r["busy_s"] == pytest.approx(800e-6)
    # but its time is its children's: it is not among the names
    assert "while:while" not in r["op_seconds"]
    assert r["op_seconds"]["convolution_fusion:fusion"] == pytest.approx(
        300e-6)
    # collective in flight 400..900; compute covers 500..600 of it
    assert r["collective_s"] == pytest.approx(500e-6)
    assert r["collective_exposed_s"] == pytest.approx(400e-6)
    gaps = dict(r["idle_gaps"])
    assert gaps["chipbench.feeder_next"] == pytest.approx(600e-6)
    assert gaps["chipbench.executor_run"] == pytest.approx(100e-6)
    assert gaps[xplane.NO_SPAN] == pytest.approx(500e-6)   # 0-100, 1600-2000


def test_interval_arithmetic():
    u = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert xplane.total(u) == 6
    assert xplane.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert xplane.clip(u, 2, 6) == [(2, 3), (5, 6)]
