"""The build log (paddle_tpu/cache/builds.py, PR 51): every miss of an
executor's first cache level leaves one record of where its seconds went,
with FLAGS_monitor and FLAGS_trace off; a hit leaves none and enters no
listener. CPU; both flags off unless a test says otherwise."""

import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import cache, flags, monitor, trace
from paddle_tpu.cache import builds
from paddle_tpu.core import executor_core, registry


@pytest.fixture(autouse=True)
def _flags_off():
    builds.install()     # what the first CompileCache of a process does
    # the log is bounded: full of an earlier file's builds it would not
    # grow, and `_new_since` marks by its length
    builds.reset()
    with flags.flag_guard(monitor=False, trace=False):
        yield


def _program(sizes=(4,), optimizer=True, relu=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        h = fluid.layers.data(name="x", shape=[4], dtype="float32")
        for size in sizes:
            h = fluid.layers.fc(input=h, size=size)
            if relu:
                h = fluid.layers.relu(h)
        loss = fluid.layers.mean(h)
        if optimizer:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feedless_program():
    """A step that reads nothing but its state: the plain step's key and
    the scan's then differ in `iters` alone."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        w = fluid.layers.create_parameter(shape=[4], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square(w))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _started(startup):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    return exe


FEED = {"x": np.ones((2, 4), np.float32)}


def _new_since(mark):
    """The records the process's log gained since `mark = len(log)`."""
    return cache.build_log()[mark:]


def _tiles(record):
    assert all(s >= 0.0 for s in record["phases"].values())
    assert set(record["phases"]) == set(builds.PHASES)
    assert sum(record["phases"].values()) == pytest.approx(
        record["t1"] - record["t0"], abs=1e-4)


def test_a_miss_leaves_one_record_whose_phases_tile_its_wall():
    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())
        t0 = time.perf_counter()
        exe.run(main, feed=FEED, fetch_list=[loss])
        t1 = time.perf_counter()
    got = _new_since(mark)
    assert len(got) == 1
    rec, = got
    _tiles(rec)
    assert t0 <= rec["t0"] < rec["t1"] <= t1     # on perf_counter
    assert rec["kind"] == "executor" and rec["name"] == "step"
    assert rec["level"] is None and rec["iters"] is None
    assert rec["key_diff"] == [] and "cause" not in rec
    assert rec["phases"]["trace"] > 0.0 and rec["phases"]["lower"] > 0.0
    assert rec["phases"]["backend"] > 0.0
    # no second cache level in effect: its stretches hold nothing
    assert rec["phases"]["digest"] == rec["phases"]["l2_load"] == 0.0
    assert rec["phases"]["export"] == 0.0
    # the operator's documented call shows this executor's builds
    mine = exe.compile_cache_info()["builds"]
    assert [b["fingerprint"] for b in mine][-1] == rec["fingerprint"]
    assert mine[-1] == rec


def test_a_hit_leaves_nothing_and_enters_no_listener():
    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        exe.run(main, feed=FEED, fetch_list=[loss])
        exe.run(main, feed=FEED, fetch_list=[loss])
        mark, entries = len(cache.build_log()), builds.listener_entries()
        for _ in range(50):
            exe.run(main, feed=FEED, fetch_list=[loss])
        assert builds.listener_entries() == entries
        assert len(cache.build_log()) == mark
        assert builds.open_builds() == []


def test_a_nested_jit_is_counted_inside_trace_not_added(monkeypatch):
    """A kernel's `jax.jit(inline=True)` wrapper traced once an op: three
    relu ops of three widths trace it three times."""
    relu = registry.lookup("relu")
    plain = relu.fn

    def kernel_wrapper(x):
        return x * 1.0

    def wrapped(ctx, ins, attrs):
        ins = {slot: [jax.jit(kernel_wrapper, inline=True)(v) for v in vs]
               for slot, vs in ins.items()}
        return plain(ctx, ins, attrs)

    monkeypatch.setattr(relu, "fn", wrapped)
    main, startup, loss = _program(sizes=(5, 6, 7), optimizer=False,
                                   relu=True)
    with fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())
        exe.run(main, feed=FEED, fetch_list=[loss])
    rec, = _new_since(mark)
    count, seconds = rec["nested_traces"]["kernel_wrapper"]
    assert count == 3 and seconds > 0.0
    assert len(rec["nested_traces"]) <= builds.NESTED_KEPT
    # they lie INSIDE the step's own trace, and the phases still tile
    assert rec["phases"]["trace"] >= sum(
        s for _, s in rec["nested_traces"].values())
    _tiles(rec)


def test_a_second_build_of_a_program_says_which_part_of_the_key_changed():
    main, startup, loss = _feedless_program()
    with fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())
        exe.run(main, fetch_list=[loss])
        exe.run(main, fetch_list=[loss], iters=3)
    first, scan = _new_since(mark)[:2]
    assert first["name"] == "step" and first["key_diff"] == []
    assert scan["name"] == "multi" and scan["iters"] == 3
    assert scan["key_diff"] == ["iters"]
    assert scan["fingerprint"] != first["fingerprint"]
    assert scan["phases"]["trace"] > 0.0      # the name is the jit's own

    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())
        exe.run(main, feed=FEED, fetch_list=[loss])
        exe.run(main, feed={"x": np.ones((5, 4), np.float32)},
                fetch_list=[loss])
        exe.run(main, feed={"x": np.ones((5, 4), np.float32)},
                fetch_list=[])
    _, reshaped, refetched = _new_since(mark)
    assert reshaped["key_diff"] == ["feeds"]
    assert refetched["key_diff"] == ["fetches"]


def test_key_parts_name_every_part_of_the_content():
    main, _, loss = _program()
    _, content = executor_core.step_key(
        main, {"x": FEED["x"]}, [loss.name], ["w"], iters=2,
        extra=(("zero1", True),))
    parts = executor_core.key_parts(content)
    assert list(parts) == ["feeds", "fetches", "state", "amp", "debug_nans",
                           "iters", "wire", "donate_feeds", "health",
                           "extra"]
    assert parts["iters"] == ("iters", 2)
    assert parts["extra"] == (("zero1", True),)
    assert sum(len(p) if n == "extra" else 1
               for n, p in parts.items()) == len(content)


@pytest.mark.parametrize("how, name", [("plain", "step"), ("scan", "multi"),
                                       ("wire", "wired"),
                                       ("health", "health_step")])
def test_the_record_carries_the_jitted_wraps_name(how, name):
    """The name is worked out where the build opens; were it not the jit's
    own, no trace event would be filed under the build."""
    from paddle_tpu.datapipe import WIRE_KEY, WireFormat, WireSpec

    main, startup, loss = _program()
    feed, kw = dict(FEED), {}
    if how == "scan":
        feed, kw = {"x": np.ones((2, 2, 4), np.float32)}, {"iters": 2}
    if how == "wire":
        feed = {"x": np.ones((2, 4), np.uint8),
                WIRE_KEY: WireSpec({"x": WireFormat("uint8")})}
    with flags.flag_guard(health=int(how == "health"), health_interval=1), \
            fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())
        exe.run(main, feed=feed, fetch_list=[loss], **kw)
    rec, = _new_since(mark)
    assert rec["name"] == name
    assert rec["phases"]["trace"] > 0.0 and rec["phases"]["backend"] > 0.0
    _tiles(rec)


def test_a_parallel_executor_build_says_so():
    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        _started(startup)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main)
        mark = len(cache.build_log())
        pe.run([loss], feed={"x": np.ones((8, 4), np.float32)})
        pe.run([loss], feed={"x": np.ones((8, 4), np.float32)})
    built = [r for r in _new_since(mark) if "cause" not in r]
    assert len(built) == 1
    rec, = built
    assert rec["kind"] == "parallel_executor" and rec["name"] == "step"
    assert rec["phases"]["trace"] > 0.0
    _tiles(rec)
    assert pe.compile_cache_info()["builds"][0] == rec
    assert cache.build_log(kind="parallel_executor")[-1] == rec


def test_an_event_of_a_wrap_with_no_build_open_is_a_retrace():
    mark = len(cache.build_log())
    jax.monitoring.record_event_duration_secs(
        builds.TRACE, 0.25, fun_name="multi")
    jax.monitoring.record_event_duration_secs(
        builds.LOWER, 0.5, fun_name="jit(multi)")
    jax.monitoring.record_event_duration_secs(
        builds.BACKEND, 1.0, fun_name="jit(multi)")
    # another function's events are nobody's
    jax.monitoring.record_event_duration_secs(
        builds.TRACE, 9.0, fun_name="some_reference")
    rec, = _new_since(mark)
    assert rec["cause"] == "retrace" and rec["name"] == "multi"
    assert rec["fingerprint"] is None and rec["kind"] is None
    assert rec["phases"]["trace"] == 0.25 and rec["phases"]["lower"] == 0.5
    assert rec["phases"]["backend"] == 1.0
    assert rec["t1"] - rec["t0"] > 0.0
    # the next trace of a wrap is the next retrace
    jax.monitoring.record_event_duration_secs(
        builds.TRACE, 0.125, fun_name="multi")
    assert [r["phases"]["trace"] for r in _new_since(mark)] == [0.25, 0.125]


def test_a_build_left_open_by_an_exception_is_closed_as_failed(monkeypatch):
    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())

        def boom(*a, **k):
            raise RuntimeError("no step today")

        with monkeypatch.context() as m:
            m.setattr(executor_core, "build_step_fn", boom)
            with pytest.raises(RuntimeError, match="no step today"):
                exe.run(main, feed=FEED, fetch_list=[loss])
        assert len(builds.open_builds()) == 1
        assert _new_since(mark) == []
        exe.run(main, feed=FEED, fetch_list=[loss])
    failed, built = _new_since(mark)
    assert failed["cause"] == "failed" and "cause" not in built
    assert failed["fingerprint"] == built["fingerprint"]
    assert builds.open_builds() == []
    _tiles(failed)


def test_the_second_cache_level_shows_in_the_record(tmp_path):
    main, startup, loss = _program()
    with flags.flag_guard(compile_cache_dir=str(tmp_path)), \
            fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())
        exe.run(main, feed=FEED, fetch_list=[loss])
        exe._compile_cache.clear()           # a fresh process's L1 miss
        exe.run(main, feed=FEED, fetch_list=[loss])
    fresh, loaded = _new_since(mark)
    assert fresh["level"] is None and loaded["level"] == "l2"
    assert fresh["phases"]["digest"] > 0.0 and fresh["phases"]["export"] > 0.0
    assert fresh["phases"]["backend"] > 0.0
    assert loaded["phases"]["l2_load"] > 0.0
    assert loaded["phases"]["trace"] == loaded["phases"]["backend"] == 0.0
    assert loaded["fingerprint"] == fresh["fingerprint"]
    assert loaded["key_diff"] == []
    _tiles(fresh)
    _tiles(loaded)


def test_compile_info_reads_the_records_wall():
    main, startup, loss = _program()
    monitor.reset()
    with flags.flag_guard(monitor=True), fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        mark = len(cache.build_log())
        exe.run(main, feed=FEED, fetch_list=[loss])
        info = monitor.compile_info()
        gauges = monitor.registry().snapshot()
    monitor.reset()
    rec, = _new_since(mark)
    wall = rec["t1"] - rec["t0"]
    assert info[rec["fingerprint"]]["wall_s"] == wall
    assert gauges['compile_wall_seconds{fingerprint="%s"}'
                  % rec["fingerprint"]] == wall


def _phase_children(spans, parent):
    return sorted((s for s in spans if s["kind"] == "phase"
                   and s["parent"] == parent["span"]),
                  key=lambda s: s["t0"])


def test_under_flags_trace_the_compile_phase_has_its_children():
    main, startup, loss = _program()
    trace.reset()
    with flags.flag_guard(trace=True), fluid.scope_guard(fluid.Scope()):
        exe = _started(startup)
        trace.reset()
        mark = len(cache.build_log())
        exe.run(main, feed=FEED, fetch_list=[loss])
        exe.run(main, feed=FEED, fetch_list=[loss])
        spans, dropped = trace.snapshot()
    trace.reset()
    assert dropped == 0
    rec, = _new_since(mark)
    miss, hit = [s for s in spans if s["name"] == "executor.step"]
    stretches = [s for s in _phase_children(spans, miss)
                 if s["name"] == "compile"]
    assert len(stretches) == 2        # before the call, and the call
    named = {}
    for stretch in stretches:
        kids = _phase_children(spans, stretch)
        assert kids and all(k["name"].startswith("compile.") for k in kids)
        # they tile the stretch: end to start, first to last stamp
        assert kids[0]["t0"] == stretch["t0"]
        assert kids[-1]["t1"] == stretch["t1"]
        assert all(a["t1"] == b["t0"] for a, b in zip(kids, kids[1:]))
        for k in kids:
            assert k["attrs"] == {"fingerprint": rec["fingerprint"],
                                  "persistent_hit": rec["persistent_hit"]}
            named[k["name"]] = named.get(k["name"], 0.0) + k["t1"] - k["t0"]
    assert miss["attrs"]["fingerprint"] == rec["fingerprint"]
    for phase in ("trace", "lower", "backend"):
        assert named["compile." + phase] == pytest.approx(
            rec["phases"][phase], abs=1e-6)
    assert "compile.self" in named
    # a hit's dispatch has none
    assert not [s for s in spans if s["name"].startswith("compile.")
                and s["trace"] == hit["trace"]]


def test_a_dump_taken_inside_a_compile_shows_the_open_build(tmp_path):
    """A step span is recorded when its step ends; of a compile that hangs
    a flight-recorder dump shows the build that is open, and the phases
    that have arrived say which one it hangs in."""
    record = cache.CompileCache().open_build(
        "multi", "0badf00d", ident=None, parts=None, iters=4)
    jax.monitoring.record_event_duration_secs(
        builds.TRACE, 0.5, fun_name="multi")
    jax.monitoring.record_event_duration_secs(
        builds.LOWER, 0.25, fun_name="jit(multi)")
    try:
        with flags.flag_guard(trace=True, trace_dump_dir=str(tmp_path)):
            path = trace.dump("watchdog")
    finally:
        record.close(cause="failed")
    hung, = trace.load_dump(path)["manifest"]["open_builds"]
    assert hung["fingerprint"] == "0badf00d" and hung["t1"] is None
    assert hung["phases"]["trace"] == 0.5 and hung["phases"]["lower"] == 0.25
    assert hung["phases"]["backend"] == 0.0       # it hangs in the backend
    assert builds.open_builds() == []


def test_builds_on_many_threads_each_hear_their_own_events():
    """A build is open on ONE thread and JAX's events arrive on the thread
    that traces: eight threads building at once file nothing under one
    another's records and lose none."""
    import sys
    import threading

    caches = [cache.CompileCache("executor") for _ in range(8)]
    each = 20                                   # 160 records < LOG_CAP

    def work(i):
        for j in range(each):
            record = caches[i].open_build(
                "multi", f"{i:04d}{j:04d}", ident=None, parts=None, iters=i)
            for event, name in ((builds.TRACE, "multi"),
                                (builds.LOWER, "jit(multi)"),
                                (builds.BACKEND, "jit(multi)")):
                jax.monitoring.record_event_duration_secs(
                    event, float(i + 1), fun_name=name)
            record.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(len(caches))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    log = cache.build_log()
    assert len(log) == len(caches) * each
    assert len({r["fingerprint"] for r in log}) == len(log)
    for r in log:
        want = float(r["iters"] + 1)
        assert "cause" not in r
        assert [r["phases"][p] for p in ("trace", "lower", "backend")] \
            == [want] * 3
    for i, c in enumerate(caches):
        assert [b["iters"] for b in c.info()["builds"]] == [i] * each
    assert builds.open_builds() == []


def test_the_log_is_bounded():
    for _ in range(builds.LOG_CAP + 10):
        jax.monitoring.record_event_duration_secs(
            builds.TRACE, 0.001, fun_name="step")
    log = cache.build_log()
    assert len(log) == builds.LOG_CAP
    assert log[-1]["cause"] == "retrace"
    # an executor's own list is bounded the same way
    assert cache.CompileCache()._builds.maxlen == builds.LOG_CAP
