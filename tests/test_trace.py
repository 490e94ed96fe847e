"""paddle_tpu.trace: span/context semantics, the off-by-default no-op
contract, the per-thread flight-recorder rings, cross-thread propagation
(ParallelMap workers, AsyncDeviceFeeder transfer threads, the serve
batcher's fan-in links), anomaly-triggered dumps (NaN guard, watchdog,
serve SLO), the dump formats, and per-op compile cost attribution —
including the acceptance check that a single HTTP serve request's full
lifecycle reconstructs as ONE trace from a flight-recorder dump."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, monitor, serve, trace
from paddle_tpu.datapipe.parallel_map import ParallelMap
from paddle_tpu.serve.http import make_http_server


@pytest.fixture(autouse=True)
def _fresh_recorder():
    monitor.reset()
    trace.reset()
    yield
    trace.reset()
    monitor.reset()


def _traced(**extra):
    """flag_guard with tracing on (plus overrides). Monitor is pinned on
    too: step/phase spans replay off monitor.StepRecord, and other test
    modules may leave FLAGS_monitor off."""
    return flags.flag_guard(trace=True, monitor=True, **extra)


# ---------------------------------------------------------------------------
# span + context primitives
# ---------------------------------------------------------------------------

def test_new_context_inherits_trace_id_under_attach():
    with _traced():
        root = trace.new_context(parent=None)
        with trace.attach(root):
            child = trace.new_context()
            assert child.trace_id == root.trace_id
            assert child.span_id != root.span_id
        orphan = trace.new_context()
        assert orphan.trace_id != root.trace_id


def test_nested_spans_parent_and_record_retroactive():
    with _traced():
        with trace.span("outer", kind="t") as outer:
            with trace.span("inner") as inner:
                assert inner.ctx.trace_id == outer.ctx.trace_id
            t0 = time.perf_counter()
            retro = trace.record("retro", t0, t0 + 0.5, parent=outer.ctx,
                                 attrs={"k": 1})
            assert retro.trace_id == outer.ctx.trace_id
    spans, dropped = trace.snapshot()
    assert dropped == 0
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"outer", "inner", "retro"}
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["retro"]["parent"] == by_name["outer"]["span"]
    assert by_name["retro"]["attrs"] == {"k": 1}
    # one trace across all three
    assert len({s["trace"] for s in spans}) == 1


def test_span_error_attr_on_exception():
    with _traced():
        with pytest.raises(RuntimeError):
            with trace.span("boom"):
                raise RuntimeError("x")
    spans, _ = trace.snapshot()
    assert spans[0]["attrs"]["error"] == "RuntimeError"


def test_off_by_default_is_noop():
    assert not trace.enabled()
    # span() hands back ONE shared no-op object — no allocation per call
    a, b = trace.span("x"), trace.span("y", k=1)
    assert a is b
    with a as h:
        h.set(ignored=True)
        assert h.ctx is None
    assert trace.record("x", 0.0, 1.0) is None
    assert trace.maybe_dump("anything") is None
    spans, dropped = trace.snapshot()
    assert spans == [] and dropped == 0


# ---------------------------------------------------------------------------
# flight recorder rings
# ---------------------------------------------------------------------------

def test_ring_wraps_and_counts_dropped():
    with _traced(trace_buffer=16):
        for i in range(40):
            trace.record(f"s{i}", float(i), float(i) + 0.5)
    spans, dropped = trace.snapshot()
    assert len(spans) == 16 and dropped == 24
    # oldest spans were overwritten: only the newest 16 survive, in order
    assert [s["name"] for s in spans] == [f"s{i}" for i in range(24, 40)]


def test_reset_forgets_rings_and_reregisters():
    with _traced():
        trace.record("before", 0.0, 1.0)
        trace.reset()
        assert trace.snapshot() == ([], 0)
        trace.record("after", 0.0, 1.0)  # stale TLS ring must re-register
        spans, _ = trace.snapshot()
        assert [s["name"] for s in spans] == ["after"]


def test_rings_are_per_thread():
    with _traced():
        trace.record("main", 0.0, 1.0)

        def worker():
            trace.record("worker", 0.0, 1.0)

        t = threading.Thread(target=worker, name="ring-worker")
        t.start()
        t.join()
    spans, _ = trace.snapshot()
    assert {s["thread"] for s in spans} == {"MainThread", "ring-worker"}


# ---------------------------------------------------------------------------
# dump formats
# ---------------------------------------------------------------------------

def test_dump_writes_manifest_jsonl_and_chrome(tmp_path):
    with _traced():
        with trace.span("a", kind="k", attr1="v"):
            trace.record("b", 1.0, 2.0)
        path = trace.dump(reason="unit test!", out_dir=str(tmp_path))
    assert trace.last_dump() == path
    # reason is sanitized into the directory name
    assert "trace_unit_test_" in path
    loaded = trace.load_dump(path)
    man, spans = loaded["manifest"], loaded["spans"]
    assert man["format"] == trace.FORMAT
    assert man["spans"] == len(spans) == 2
    assert man["names"] == {"a": 1, "b": 1}
    assert man["traces"] == 1
    # clock anchor pair lets a reader convert perf_counter -> epoch
    assert set(man["clock"]) == {"perf_counter", "epoch"}
    with open(f"{path}/trace.json") as f:
        chrome = json.load(f)
    evs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in evs} == {"a", "b"}
    assert all(e["pid"] == trace.CHROME_PID for e in evs)
    # dump counter landed in the registry under the sanitized reason
    snap = monitor.registry().snapshot()
    assert snap['trace_dumps_total{reason="unit_test_"}'] == 1.0


def test_maybe_dump_respects_per_reason_cooldown(tmp_path):
    with _traced(trace_dump_dir=str(tmp_path), trace_dump_cooldown_s=3600.0):
        trace.record("x", 0.0, 1.0)
        first = trace.maybe_dump("slo")
        assert first is not None
        assert trace.maybe_dump("slo") is None          # cooled down
        assert trace.maybe_dump("other") is not None    # per-reason


# ---------------------------------------------------------------------------
# cross-thread propagation: datapipe workers
# ---------------------------------------------------------------------------

def test_parallel_map_workers_inherit_consumer_context():
    with _traced():
        root = trace.new_context(parent=None)
        with trace.attach(root):
            pm = ParallelMap(range(8), lambda x: x * 2, num_workers=2)
            assert sorted(pm) == [0, 2, 4, 6, 8, 10, 12, 14]
    spans, _ = trace.snapshot()
    maps = [s for s in spans if s["name"] == "datapipe.map"]
    assert len(maps) == 8
    # every worker-thread span landed in the CONSUMER's trace
    assert {s["trace"] for s in maps} == {root.trace_id}
    assert any(s["thread"].startswith("datapipe-map") for s in maps)


def test_feeder_transfer_spans_inherit_consumer_context():
    with _traced():
        root = trace.new_context(parent=None)
        src = [{"x": np.ones((2, 3), np.float32)} for _ in range(3)]
        with trace.attach(root):
            fed = list(fluid.AsyncDeviceFeeder(src, place=fluid.CPUPlace()))
        assert len(fed) == 3
    spans, _ = trace.snapshot()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # what used to be one `datapipe.stack` span is three: the wait for
    # the source lock, the wait for upstream, and the copy alone
    for name in ("datapipe.lock_wait", "datapipe.upstream_wait",
                 "datapipe.stack", "datapipe.transfer", "datapipe.next"):
        chunks = sorted(s["attrs"]["chunk"] for s in by_name[name])
        # the pull that found the source exhausted waited too (chunk 3)
        assert chunks == [0, 1, 2] or (
            name.endswith("_wait") and chunks == [0, 1, 2, 3]), name
    assert {s["trace"] for s in by_name["datapipe.transfer"]} == \
        {root.trace_id}
    assert all(s["attrs"]["bytes"] > 0 for s in by_name["datapipe.transfer"])
    # the three parts follow one another on the lane that pulled the chunk
    for c in range(3):
        lw, uw, st, tr = (next(s for s in by_name[n]
                               if s["attrs"]["chunk"] == c)
                          for n in ("datapipe.lock_wait",
                                    "datapipe.upstream_wait",
                                    "datapipe.stack", "datapipe.transfer"))
        assert lw["t1"] <= uw["t0"] <= uw["t1"] <= st["t0"] <= st["t1"] \
            <= tr["t0"]
        assert len({lw["thread"], uw["thread"], st["thread"],
                    tr["thread"]}) == 1
    assert {s["thread"] for s in by_name["datapipe.next"]} == {"MainThread"}
    assert all(s["thread"].startswith("datapipe-feed")
               for s in by_name["datapipe.ticket_wait"])


# ---------------------------------------------------------------------------
# executor step + phase spans; compile cost attribution
# ---------------------------------------------------------------------------

def _tiny_program(size=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[size], dtype="float32")
        y = fluid.layers.fc(input=x, size=size)
        loss = fluid.layers.mean(y)
    return main, startup, loss


def test_executor_emits_step_and_phase_spans():
    main, startup, loss = _tiny_program()
    feed = {"x": np.ones((2, 4), np.float32)}
    scope = fluid.Scope()
    with _traced(), fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])   # compile miss
        exe.run(main, feed=feed, fetch_list=[loss])   # cache hit
    spans, _ = trace.snapshot()
    steps = [s for s in spans if s["name"] == "executor.step"]
    assert len(steps) >= 2
    hit = next(s for s in steps if s["attrs"].get("cache") == "hit")
    # the startup run is a miss too — match the miss by fingerprint
    miss = next(s for s in steps if s["attrs"].get("cache") == "miss"
                and s["attrs"]["fingerprint"]
                == hit["attrs"]["fingerprint"])
    # phase children parent under their step span, same trace (the miss
    # step's dispatch is folded into its compile phase, so dispatch shows
    # up on the hit step)
    miss_phases = [s for s in spans if s["kind"] == "phase"
                   and s["parent"] == miss["span"]]
    assert "compile" in {s["name"] for s in miss_phases}
    assert all(s["trace"] == miss["trace"] for s in miss_phases)
    hit_phases = {s["name"] for s in spans if s["kind"] == "phase"
                  and s["parent"] == hit["span"]}
    assert "dispatch" in hit_phases and "fetch_readback" in hit_phases


def test_op_costs_estimates_program_ops_from_shapes():
    """What is left of trace/costs.py: the planners' analytic weights. The
    fc matmul of the tiny program dominates, and nothing is measured (no
    step ran)."""
    main, _, _ = _tiny_program(size=8)
    rows = trace.op_costs(main, batch_size=4)
    assert [r["index"] for r in rows] == list(range(len(rows)))
    assert max(rows, key=lambda r: r["flops_est"])["op"] == "mul"
    assert not hasattr(trace, "slowest_ops")


# ---------------------------------------------------------------------------
# anomaly triggers -> dumps
# ---------------------------------------------------------------------------

def test_nan_guard_trip_dumps_flight_recorder(tmp_path):
    from paddle_tpu.resilience import NanGuard

    with _traced(trace_dump_dir=str(tmp_path), trace_dump_cooldown_s=0.0):
        trace.record("pre-nan", 0.0, 1.0)
        guard = NanGuard(policy="skip")
        assert guard.check({"loss": float("nan")}, step=3) == "skip"
    dumps = list(tmp_path.glob("trace_nan_guard_*"))
    assert len(dumps) == 1
    loaded = trace.load_dump(str(dumps[0]))
    assert loaded["manifest"]["reason"] == "nan_guard"
    assert any(s["name"] == "pre-nan" for s in loaded["spans"])


def test_watchdog_stack_dump_includes_flight_recorder(tmp_path):
    from paddle_tpu.resilience import watchdog

    with _traced(hang_dump_dir=str(tmp_path)):
        trace.record("pre-hang", 0.0, 1.0)
        watchdog.dump_stacks(label="unit")
    dumps = list(tmp_path.glob("trace_hang_unit_*"))
    assert len(dumps) == 1
    assert any(s["name"] == "pre-hang"
               for s in trace.load_dump(str(dumps[0]))["spans"])


# ---------------------------------------------------------------------------
# serve: fan-in links + the single-trace lifecycle acceptance check
# ---------------------------------------------------------------------------

def _fc_server(max_batch=4, feat=4, out=3, **cfg):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[feat], dtype="float32")
        y = fluid.layers.fc(input=x, size=out)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return serve.Server(prog, ["x"], [y], place=fluid.CPUPlace(),
                        scope=scope,
                        config=serve.ServeConfig(max_batch=max_batch, **cfg))


def test_batch_span_links_survive_coalescing():
    server = _fc_server(max_wait_ms=50.0)
    with _traced():
        with server:
            # two requests submitted inside the batching window coalesce
            # into ONE dispatch
            x = np.ones(4, np.float32)
            f1 = server.submit({"x": x})
            f2 = server.submit({"x": 2 * x})
            f1.result(timeout=30)
            f2.result(timeout=30)
        spans, _ = trace.snapshot()
    reqs = [s for s in spans if s["name"] == "serve.request"]
    batches = [s for s in spans if s["name"] == "serve.batch"
               and s["attrs"]["rows"] == 2]
    assert len(reqs) == 2 and len(batches) == 1
    batch = batches[0]
    # fan-in: the batch links to BOTH coalesced requests' identities...
    linked = {(l["trace"], l["span"]) for l in batch["links"]}
    assert linked == {(r["trace"], r["span"]) for r in reqs}
    # ...and each request links back to the batch that carried it
    for r in reqs:
        assert {(l["trace"], l["span"]) for l in r["links"]} == \
            {(batch["trace"], batch["span"])}
    # requests came from different submits: distinct traces, preserved
    # through the coalesced dispatch
    assert reqs[0]["trace"] != reqs[1]["trace"]
    # the executor's step span ran under the batch span (worker thread
    # context), so device work is attributed to the dispatch
    steps = [s for s in spans if s["name"] == "executor.step"
             and s["parent"] == batch["span"]]
    assert len(steps) == 1 and steps[0]["trace"] == batch["trace"]


def test_http_request_lifecycle_is_one_trace_in_dump(tmp_path):
    """Acceptance: POST /v1/infer -> queue -> batch -> dispatch ->
    readback reconstructs as ONE trace from a flight-recorder dump."""
    server = _fc_server()
    with _traced():
        with server:
            httpd = make_http_server(server, port=0)
            port = httpd.server_address[1]
            t = threading.Thread(target=httpd.serve_forever, daemon=True)
            t.start()
            try:
                body = json.dumps(
                    {"inputs": {"x": [1.0, 2.0, 3.0, 4.0]}}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/infer", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as resp:
                    assert resp.status == 200
            finally:
                httpd.shutdown()
                httpd.server_close()
        path = trace.dump(reason="lifecycle", out_dir=str(tmp_path))
    spans = trace.load_dump(path)["spans"]
    http = next(s for s in spans if s["name"] == "serve.http")
    lifecycle = [s for s in spans if s["trace"] == http["trace"]]
    names = {s["name"] for s in lifecycle}
    assert {"serve.http", "serve.request", "serve.queue", "serve.pad",
            "serve.dispatch", "serve.readback"} <= names
    req_span = next(s for s in lifecycle if s["name"] == "serve.request")
    # the request span roots under the HTTP span (same trace, parented)
    assert req_span["parent"] == http["span"]
    # child phases parent under the request span and nest inside it
    for name in ("serve.queue", "serve.dispatch", "serve.readback"):
        child = next(s for s in lifecycle if s["name"] == name)
        assert child["parent"] == req_span["span"]
        assert child["t0"] >= req_span["t0"] - 1e-6
        assert child["t1"] <= req_span["t1"] + 1e-6
    # the coalesced dispatch is reachable via the request's span link
    batch_link = req_span["links"][0]
    batch = next(s for s in spans if s["span"] == batch_link["span"])
    assert batch["name"] == "serve.batch"
    assert {(l["trace"], l["span"]) for l in batch["links"]} >= \
        {(req_span["trace"], req_span["span"])}


def test_serve_slo_violation_triggers_dump(tmp_path):
    server = _fc_server(slo_ms=0.000001)  # everything violates
    with _traced(trace_dump_dir=str(tmp_path)):
        with server:
            server.submit({"x": np.ones(4, np.float32)}).result(timeout=30)
            time.sleep(0.1)  # dump happens on the worker thread
    dumps = list(tmp_path.glob("trace_serve_slo_*"))
    assert len(dumps) == 1
    spans = trace.load_dump(str(dumps[0]))["spans"]
    req = next(s for s in spans if s["name"] == "serve.request")
    assert req["attrs"]["slo_violated"] is True


def test_tracing_off_serve_path_records_nothing():
    server = _fc_server()
    assert not trace.enabled()
    with server:
        out, = server.submit({"x": np.ones(4, np.float32)}).result(
            timeout=30)
        assert out.shape == (1, 3)
    assert trace.snapshot() == ([], 0)


# ---------------------------------------------------------------------------
# profiler merge
# ---------------------------------------------------------------------------

def test_profiler_chrome_export_includes_trace_lane(tmp_path):
    from paddle_tpu import profiler

    with _traced():
        profiler.reset_profiler()
        profiler.start_profiler()
        with profiler.record_event("host-side"):
            pass
        with trace.span("traced-side"):
            pass
        profiler.stop_profiler()
        out = str(tmp_path / "merged.json")
        profiler.export_chrome_trace(out)
    with open(out) as f:
        events = json.load(f)["traceEvents"]
    host = [e for e in events if e.get("name") == "host-side"]
    traced = [e for e in events if e.get("name") == "traced-side"]
    assert host and host[0]["pid"] == 0
    assert traced and traced[0]["pid"] == trace.CHROME_PID


# ---------------------------------------------------------------------------
# PR 24: FLAGS_trace alone; phases that tile the step; the chunk's chain
# ---------------------------------------------------------------------------

def _train_program(size=8):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[size], dtype="float32")
        h = fluid.layers.fc(input=x, size=size, act="relu")
        loss = fluid.layers.mean(fluid.layers.fc(input=h, size=1))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _trace_only(**extra):
    return flags.flag_guard(trace=True, monitor=False, **extra)


def _children(spans, step):
    return sorted((s for s in spans if s["kind"] == "phase"
                   and s["parent"] == step["span"]), key=lambda s: s["t0"])


def _assert_tiled(spans, step):
    """The phase children neither overlap nor leave more than 5% of the
    step between them."""
    kids = _children(spans, step)
    assert kids
    assert kids[0]["t0"] >= step["t0"] and kids[-1]["t1"] <= step["t1"]
    for a, b in zip(kids, kids[1:]):
        assert a["t1"] <= b["t0"], (a["name"], b["name"])
    covered = sum(k["t1"] - k["t0"] for k in kids)
    assert covered >= 0.95 * (step["t1"] - step["t0"]), \
        [(k["name"], k["t1"] - k["t0"]) for k in kids]
    return [k["name"] for k in kids]


def test_trace_alone_emits_step_spans_and_leaves_registry_empty():
    main, startup, loss = _train_program()
    feed = {"x": np.ones((4, 8), np.float32)}
    scope = fluid.Scope()
    with _trace_only(), fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[loss])
    spans, dropped = trace.snapshot()
    assert dropped == 0
    steps = [s for s in spans if s["name"] == "executor.step"]
    assert len(steps) == 3
    assert [s["attrs"]["cache"] for s in steps] == ["miss", "miss", "hit"]
    assert steps[-1]["attrs"]["cache_level"] == "l1"
    assert all(isinstance(s["attrs"]["fingerprint"], str) for s in steps)
    assert {"feed_encode", "state_gather", "cache_lookup", "dispatch",
            "write_back", "fetch_readback"} \
        <= {k["name"] for k in _children(spans, steps[-1])}
    # FLAGS_monitor=0: the registry, last_step and compile_info stay as
    # they were
    assert monitor.registry().snapshot() == {}
    assert monitor.last_step() is None
    assert monitor.compile_info() == {}


@pytest.mark.parametrize("case", ["miss", "hit", "l2"])
@pytest.mark.parametrize("iters", [None, 3])
def test_executor_step_children_tile_the_step(tmp_path, case, iters):
    main, startup, loss = _train_program()
    feed = {"x": np.ones((4, 8) if iters is None else (iters, 4, 8),
                         np.float32)}
    scope = fluid.Scope()
    with _trace_only(compile_cache_dir=str(tmp_path)), \
            fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)

        def run():
            exe.run(main, feed=feed, fetch_list=[loss], iters=iters)

        if case != "miss":
            run()
            if iters is not None:
                run()   # the second scan call sees its own donated outputs
        if case == "l2":
            exe._compile_cache.clear()   # a fresh process's L1 miss
        trace.reset()
        run()
    spans, _ = trace.snapshot()
    step, = [s for s in spans if s["name"] == "executor.step"]
    names = _assert_tiled(spans, step)
    assert step["attrs"]["cache"] == ("miss" if case == "miss" else "hit")
    assert step["attrs"].get("cache_level") == \
        {"miss": None, "hit": "l1", "l2": "l2"}[case]
    assert step["attrs"].get("iters") == iters
    want = {"miss": "compile", "hit": "dispatch", "l2": "cache_load"}[case]
    assert want in names
    assert ("compile" in names) == (case == "miss")
    assert {"feed_encode", "state_gather", "cache_lookup",
            "write_back"} <= set(names)


@pytest.mark.parametrize("case", ["miss", "hit", "l2"])
def test_parallel_executor_step_children_tile_the_step(tmp_path, case):
    import jax

    main, startup, loss = _train_program()
    feed = {"x": np.ones((8, 8), np.float32)}
    scope = fluid.Scope()
    with _trace_only(compile_cache_dir=str(tmp_path)), \
            fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main,
                                    devices=jax.devices()[:4])
        assert pe.device_count == 4
        if case != "miss":
            pe.run([loss], feed=feed)
            pe.run([loss], feed=feed)
        if case == "l2":
            pe._compile_cache.clear()
        trace.reset()
        pe.run([loss], feed=feed)
    spans, _ = trace.snapshot()
    step, = [s for s in spans if s["name"] == "parallel_executor.step"]
    names = _assert_tiled(spans, step)
    assert step["attrs"]["cache"] == ("miss" if case == "miss" else "hit")
    want = {"miss": "compile", "hit": "dispatch", "l2": "cache_load"}[case]
    assert want in names
    assert {"feed_encode", "state_gather", "cache_lookup", "write_back",
            "fetch_readback"} <= set(names)
    assert monitor.registry().snapshot() == {}


def _decode_sample(i):
    return {"x": np.full((4, 3), i % 251, np.uint8),
            "y": np.array([i], np.int32)}


def _chunk_chain(spans, chunk, K):
    """The spans of one chunk in time order: those that carry its chunk
    id, and the per-item ones (read, map) whose idx falls into it."""
    pipes = {s["attrs"]["pipe"] for s in spans
             if s["name"].startswith("datapipe.")}
    assert len(pipes) == 1, pipes
    out = []
    for s in spans:
        a = s.get("attrs", {})
        if a.get("chunk") == chunk or (
                "chunk" not in a and "idx" in a and a["idx"] // K == chunk):
            out.append(s)
    return sorted(out, key=lambda s: s["t0"])


def _first(chain, name):
    return next(s for s in chain if s["name"] == name)


@pytest.mark.parametrize("processes", [True, False])
def test_datapipe_chunk_chain_in_time_order(processes,
                                            no_datapipe_thread_leaks):
    from paddle_tpu import datapipe

    K = 4
    with _trace_only():
        t_before = time.perf_counter()
        pipe = (datapipe.DataPipe.from_reader(lambda: iter(range(3 * K)))
                .map(_decode_sample, num_workers=2, processes=processes)
                .prefetch_to_device(place=fluid.CPUPlace(), chunk=K,
                                    capacity=2, transfer_threads=2))
        assert len(list(pipe)) == 3
        t_after = time.perf_counter()
    spans, dropped = trace.snapshot()
    assert dropped == 0
    # nothing is reconstructed in the parent any more: every name is one
    # of the stages the chunk really passed
    assert {s["name"] for s in spans} <= {
        "datapipe." + n for n in (
            "read", "map", "handoff", "idle", "decode", "ring_put",
            "slot_wait", "ticket_wait", "lock_wait", "upstream_wait",
            "stack", "transfer", "next")}
    stages = ["datapipe.read", "datapipe.handoff", "datapipe.decode",
              "datapipe.ring_put", "datapipe.stack", "datapipe.transfer",
              "datapipe.next"] if processes else \
        ["datapipe.read", "datapipe.map", "datapipe.stack",
         "datapipe.transfer", "datapipe.next"]
    for chunk in (1, 2):   # chunk 0 holds the schema probe's row
        chain = _chunk_chain(spans, chunk, K)
        # each stage starts its first piece of the chunk after the stage
        # before it did (the consumer alone may be waiting already) ...
        firsts = [_first(chain, n) for n in stages]
        for a, b in zip(firsts[:-1], firsts[1:-1]):
            assert a["t0"] <= b["t0"], (a["name"], b["name"])
        # ... and is done with the chunk before the next stage is
        lasts = [max(s["t1"] for s in chain if s["name"] == n)
                 for n in stages]
        assert lasts == sorted(lasts), list(zip(stages, lasts))
        # the chain ends where the consumer has the chunk
        assert max(s["t1"] for s in chain) == lasts[-1]
        assert all(t_before <= s["t0"] <= s["t1"] <= t_after
                   for s in chain)
    if processes:
        workers = {s["thread"] for s in spans
                   if s["name"] == "datapipe.decode"}
        assert workers and all(w.startswith("datapipe-proc-")
                               for w in workers)
        assert sum(s["attrs"]["n"] for s in spans
                   if s["name"] == "datapipe.decode") == 3 * K


def test_worker_decode_stamps_lie_inside_parents_put_to_ack(
        no_datapipe_thread_leaks):
    """One clock for the parent and its forked workers: a worker's own
    `perf_counter` stamps of a decode fall between the parent's stamp of
    the item's put and the parent's receipt of the ack."""
    from paddle_tpu.datapipe.process_map import ProcessPoolMap

    acked = {}

    def decode(i):
        time.sleep(0.002)
        return i * 2

    with _trace_only():
        pm = ProcessPoolMap(range(8), decode, num_workers=2, pipe_id=7)
        out = []
        for i, v in enumerate(pm):
            acked[i] = time.perf_counter()   # emitted after its ack came
            out.append(v)
    assert out == [i * 2 for i in range(8)]
    spans, _ = trace.snapshot()
    by = {}
    for s in spans:
        assert s["attrs"]["pipe"] == 7
        by.setdefault(s["name"], {})[s["attrs"]["idx"]] = s
    assert set(by) == {"datapipe.handoff", "datapipe.idle",
                       "datapipe.decode"}
    for idx in range(8):
        put = by["datapipe.handoff"][idx]["t0"]       # parent's stamp
        got = by["datapipe.handoff"][idx]["t1"]       # worker's stamp
        dec = by["datapipe.decode"][idx]
        assert put <= got <= dec["t0"] < dec["t1"] <= acked[idx], idx
        assert dec["t1"] - dec["t0"] >= 0.002
        idle = by["datapipe.idle"][idx]
        assert idle["t1"] == got and idle["t0"] <= got
        assert dec["thread"] == f"datapipe-proc-{dec['attrs']['worker']}"


class _Conn:
    def __init__(self):
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)

    def close(self):
        pass


@pytest.mark.parametrize("tracing", [False, True])
def test_worker_ack_carries_span_payload_only_when_tracing(tracing):
    import queue

    from paddle_tpu.datapipe.process_map import _worker_main

    q, conn = queue.Queue(), _Conn()
    stamp = (time.perf_counter(),) if tracing else ()
    q.put(("task", 0, None, 0, 5) + stamp)
    q.put(("probe", 1, 6) + stamp)
    q.put(("stop",))
    _worker_main(0, lambda v: v + 1, q, conn, tracing)
    ok, probe = conn.sent
    assert ok[:3] == ("ok", 0, 6) and probe[:3] == ("probe_ok", 1, 7)
    if not tracing:
        # the messages FLAGS_trace=0 has always sent: nothing rides along
        assert len(ok) == 4 and len(probe) == 4
        return
    for msg in (ok, probe):
        assert len(msg) == 5
        t_put, t_free, t_got, d0, d1, t_w = msg[-1]
        assert t_put == stamp[0] and t_w is None
        assert t_free <= t_got <= d0 <= d1


def test_both_flags_off_no_step_record_no_spans(monkeypatch):
    from paddle_tpu import datapipe

    main, startup, loss = _train_program()
    feed = {"x": np.ones((4, 8), np.float32)}

    def boom(*a, **k):
        raise AssertionError("StepRecord made with both flags off")

    monkeypatch.setattr(monitor, "step_begin", boom)
    scope = fluid.Scope()
    with flags.flag_guard(trace=False, monitor=False), \
            fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed={"x": np.ones((2, 4, 8), np.float32)},
                fetch_list=[loss], iters=2)
        pipe = (datapipe.DataPipe.from_reader(lambda: iter(range(4)))
                .map(_decode_sample, num_workers=2)
                .prefetch_to_device(place=fluid.CPUPlace(), chunk=2))
        assert len(list(pipe)) == 2
    assert trace.snapshot() == ([], 0)
    assert monitor.registry().snapshot() == {}


def test_record_takes_the_lane_of_the_reporting_worker():
    with _trace_only():
        t = time.perf_counter()
        trace.record("datapipe.decode", t, t + 1.0, thread="datapipe-proc-3")
        trace.record("here", t, t + 1.0)
    spans, _ = trace.snapshot()
    assert {s["name"]: s["thread"] for s in spans} == {
        "datapipe.decode": "datapipe-proc-3",
        "here": threading.current_thread().name}
    ids = {s["span"] for s in spans} | {s["trace"] for s in spans}
    assert len(ids) == 4 and all(len(i) == 16 for i in ids)


@pytest.mark.parametrize("runner", ["executor", "executor_scan",
                                    "parallel_executor"])
def test_step_span_and_counter_carry_fused_bn_global_pool(runner):
    """A program with one SE-style squeeze: every step span says 1 pair
    was lowered together, the registry counts it once per program
    prepared, and the startup program's spans say 0."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[3, 4, 4], dtype="float32")
        y = fluid.layers.batch_norm(
            fluid.layers.conv2d(x, num_filters=4, filter_size=1))
        gate = fluid.layers.fc(fluid.layers.pool2d(
            y, pool_type="avg", global_pooling=True), size=4, act="sigmoid")
        loss = fluid.layers.mean(
            fluid.layers.elementwise_mul(y, gate, axis=0))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    iters = 2 if runner == "executor_scan" else None
    feed = {"x": np.ones(((8, 3, 4, 4) if iters is None
                          else (iters, 8, 3, 4, 4)), np.float32)}
    kind = "parallel_executor" if runner == "parallel_executor" \
        else "executor"
    scope = fluid.Scope()
    with _traced(), fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        startup_step, = [s for s in trace.snapshot()[0]
                         if s["name"] == "executor.step"]
        trace.reset()
        if kind == "parallel_executor":
            pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                        main_program=main)
            for _ in range(3):
                pe.run([loss.name], feed=feed)
        else:
            exe = fluid.Executor(fluid.CPUPlace())
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss], iters=iters)
    assert startup_step["attrs"]["fused_bn_global_pool"] == 0
    steps = [s for s in trace.snapshot()[0] if s["name"] == kind + ".step"]
    assert [s["attrs"]["fused_bn_global_pool"] for s in steps] == [1, 1, 1]
    prepared = sum(s["attrs"]["cache"] == "miss" for s in steps)
    assert monitor.registry().counter(
        "fused_bn_global_pool", cache=kind).value == prepared


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_start_profiler_leaves_jax_host_tracer_off(tmp_path, monkeypatch,
                                                   backend):
    """The profiler's host lane comes from its own events and the trace
    lane; JAX's host tracer logs every 48 bytes of a host-to-device copy
    on the chip's runtime (PERF.md section 6, PR 23). On XLA:CPU it is
    the only place the executions show, and stays on."""
    import jax

    from paddle_tpu import profiler

    seen = {}
    monkeypatch.setattr(jax, "default_backend", lambda: backend)

    def fake_start(log_dir, *a, **kw):
        seen["dir"] = log_dir
        seen["opts"] = kw.get("profiler_options")

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    profiler.start_profiler(trace_dir=str(tmp_path))
    profiler.stop_profiler()
    assert seen["dir"] == str(tmp_path)
    assert seen["opts"].python_tracer_level == 0
    assert (seen["opts"].host_tracer_level == 0) == (backend == "tpu")
