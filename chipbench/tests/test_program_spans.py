"""The program's own spans in a traced run (`chipbench/traced.py`,
`chipbench/spans.py`, the eight readers that take them): each reader on a
small recorded span list, the rehearsal traced pipe cell with all of its
additions, the stock harness untouched by any of it, and a snapshot with
holes answering nothing. Counts and arithmetic only: no number here is a
timing."""

import copy
import io
import json
import os
import statistics

import pytest

from chipbench import harness, spans, traced, xplane

HERE = os.path.dirname(__file__)
READERS = ("exec_prepare_ms", "exec_enqueue_ms", "exec_writeback_ms",
           "next_wait_share", "handoff_ms", "decode_busy_share",
           "transfer_busy_share", "ticket_wait_share")
# what the readers made of data/program_spans.json when it was recorded
RECORDED = {"exec_prepare_ms": 4.972546001226874,
            "exec_enqueue_ms": 1.2358344993117498,
            "exec_writeback_ms": 0.2539115012041293,
            "next_wait_share": 0.012662846871382699,
            "handoff_ms": 0.3155585000058636,
            "decode_busy_share": 0.023466723310024723,
            "transfer_busy_share": 0.7924463503905067,
            "ticket_wait_share": 37.66616393973157}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "..", "data", "program_spans.json")) as f:
        return json.load(f)


def _read(name, obs):
    return harness.Files().metric_reader("pipe." + name).read(obs)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_spans(recorded, name):
    assert _read(name, recorded) == pytest.approx(RECORDED[name], rel=1e-9)


def test_readers_agree_with_plain_arithmetic_on_the_recorded_spans(recorded):
    sp, (t0, t1) = recorded["program_spans"], \
        recorded["program_spans_window"]
    steps = [s for s in sp if s["name"] == "executor.step"]
    assert len(steps) == 8 and all(s["attrs"]["cache"] == "hit"
                                   for s in steps)

    def phase_sum(step, names):
        return sum(p["t1"] - p["t0"] for p in sp
                   if p.get("parent") == step["span"] and p["name"] in names)

    for reader, names in (("exec_prepare_ms", ("feed_encode", "state_gather",
                                               "cache_lookup")),
                          ("exec_enqueue_ms", ("dispatch",)),
                          ("exec_writeback_ms", ("write_back",))):
        want = statistics.median(phase_sum(s, names) for s in steps) * 1e3
        assert _read(reader, recorded) == pytest.approx(want)
    # the phases tile each step: what the three readers split is the step
    for s in steps:
        whole = phase_sum(s, ("feed_encode", "state_gather", "cache_lookup",
                              "dispatch", "write_back"))
        assert whole == pytest.approx(s["t1"] - s["t0"], rel=0.05)
    nxt = sum(s["t1"] - s["t0"] for s in sp if s["name"] == "datapipe.next")
    assert _read("next_wait_share", recorded) == pytest.approx(
        100 * nxt / (t1 - t0))
    lanes = {s["thread"] for s in sp if s["name"] == "datapipe.transfer"}
    workers = {s["attrs"]["worker"] for s in sp
               if s["name"] == "datapipe.decode"}
    assert len(lanes) == 2 and workers == {0, 1}
    assert 0 < _read("transfer_busy_share", recorded) <= 100
    assert 0 < _read("ticket_wait_share", recorded) <= 100


@pytest.mark.parametrize("name", READERS)
def test_a_snapshot_with_holes_or_none_gives_no_metric(recorded, name):
    holes = dict(recorded, program_spans_dropped=3)
    assert _read(name, holes) is None
    # a program without the spans (the parent commit), a run that did not
    # take them (`python -m chipbench.run --trace 1`)
    assert _read(name, {"window_s": 1.0, "trace": None}) is None
    assert _read(name, dict(recorded, program_spans=[])) is None


def test_loop_thread_rows_name_idle_gaps_after_program_spans(recorded):
    sp = recorded["program_spans"]
    rows = spans.loop_thread_rows(sp, "MainThread")
    names = {r[0] for r in rows}
    assert {"executor.step", "executor.feed_encode",
            "executor.state_gather", "executor.cache_lookup",
            "executor.dispatch", "executor.write_back",
            "datapipe.next"} == names
    assert all(r[3] == "MainThread" for r in rows)
    # lanes and workers stay out: a lane's transfer is always open
    assert not any("transfer" in n or "decode" in n for n in names)
    # a gap inside run() goes to the innermost program span, not to the
    # harness's span around the call
    step = next(s for s in sp if s["name"] == "executor.step")
    gather = next(s for s in sp if s["name"] == "state_gather"
                  and s["parent"] == step["span"])
    ps = lambda t: int(t * 1e12)  # noqa: E731
    outer = ["chipbench.executor_run", ps(step["t0"]) - 5 * 10 ** 7,
             ps(step["t1"]) + 5 * 10 ** 7, "MainThread"]
    table = sorted([outer] + [[n, ps(a), ps(b), th]
                              for n, a, b, th in rows], key=lambda r: r[1])
    gap = (ps(gather["t0"]) + 1, ps(gather["t1"]) - 1)
    by = xplane.attribute_gaps([gap], table, short_ps=0)
    assert by == {"executor.state_gather": gap[1] - gap[0]}
    # a step and its first phase open on one stamp: the phase is named
    first = next(s for s in sp if s["name"] == "feed_encode"
                 and s["parent"] == step["span"])
    assert first["t0"] == step["t0"]
    gap = (ps(first["t0"]) + 1, ps(first["t1"]) - 1)
    assert xplane.attribute_gaps([gap], table, short_ps=0) \
        == {"executor.feed_encode": gap[1] - gap[0]}


@pytest.fixture(scope="module")
def tiny_pipe():
    with open(os.path.join(HERE, "tiny.json")) as f:
        tiny = json.load(f)
    tiny = copy.deepcopy(tiny)
    tiny["traffic"].update(trace_chunks=8, decode_workers=2)
    return tiny


def _line_of(run, *a, **kw):
    out = io.StringIO()
    line = run(*a, out=out, **kw)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_rehearsal_traced_pipe_cell_returns_the_eight_additions(tiny_pipe):
    from paddle_tpu import flags, trace

    line, lines = _line_of(traced.run_cell, "resnet50_train_pipe",
                           seed=2 ** 31 + 17, seconds=2.0, rehearsal=True,
                           override=tiny_pipe)
    m = line["metrics"]
    assert {"pipe." + n for n in READERS} <= set(m)
    # the stock readers still answer beside them
    assert {"pipe.input_wait_share", "pipe.host_dispatch_ms",
            "pipe.keepup_share"} <= set(m)
    for n in READERS:
        assert set(m["pipe." + n]) == {"value", "unit"}
        assert m["pipe." + n]["value"] >= 0
    three = sum(m["pipe." + n]["value"] for n in READERS[:3])
    assert three <= m["pipe.host_dispatch_ms"]["value"] * 1.10
    assert line["checks"]["window_compiles_zero"]
    assert line["checks"]["losses_finite"]
    assert json.loads(json.dumps(lines[-1])) == line
    # tracing was the run's alone, and the harness is as it was
    assert not flags.get("trace")
    assert harness.Tracer is not traced.ProgramSpanTracer
    assert "program_spans" not in lines[-1]
    trace.reset()


def test_stock_timed_run_is_unchanged_and_records_nothing(tiny_pipe):
    from paddle_tpu import trace

    trace.reset()
    line, lines = _line_of(harness.run_cell, "resnet50_train_pipe",
                           seed=2 ** 31 + 17, seconds=2.0, trace=False,
                           rehearsal=True, override=tiny_pipe)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "workload", "seed", "checks",
                         "rehearsal", "override", "metrics_missing"}
    assert line["metrics_missing"] == []
    assert set(line["metrics"]) == {"train_pipe_items_per_s", "setup_s"}
    assert [next(iter(v)) for v in lines[:2]] == ["chipbench_setup",
                                                  "chipbench_detail"]
    assert trace.snapshot() == ([], 0)


def test_proposed_entries_are_the_fourteen_and_have_readers():
    with open(traced.PROPOSED) as f:
        proposed = json.load(f)["per_layer"]
    bench = harness.Files().bench()
    assert len(proposed) == 14
    assert not {m["name"] for m in proposed} \
        & {m["name"] for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in proposed:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= cells and m["layer"] in layers
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert harness.Files().metric_reader(m["name"]) is not None
    merged = traced.TracedFiles().bench()["per_layer"]
    assert merged[:len(bench["per_layer"])] == bench["per_layer"]
    assert len(merged) == len(bench["per_layer"]) + 14
