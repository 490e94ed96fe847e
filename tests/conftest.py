"""Test configuration: force an 8-device virtual CPU mesh.

Reference parity: the reference's multi-device tests require real GPUs
(guarded by core.get_cuda_device_count, SURVEY.md §4.5). Here every test runs
against XLA's host platform with 8 virtual devices so data/model-parallel
sharding paths (the ParallelExecutor equivalent) are exercised without TPU
hardware. Set BEFORE any jax import.
"""

import os

# Hard-set (NOT setdefault): the tests run on the CPU wherever they are
# started, and the ambient env may name an accelerator (the chip machine
# sets JAX_PLATFORMS=tpu,cpu). This is the explicit CPU pin that lets
# TPUPlace(i) mean host device i (core/places.py); test subprocesses
# inherit it. The config.update below covers a jax imported before this
# file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep op-test numerics deterministic and fast on CPU.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default main/startup programs and a fresh scope
    (the reference resets global state between unittest classes)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import framework, scope

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope.reset_global_scope()
    fluid.unique_name.switch()
    yield


@pytest.fixture
def rng():
    return np.random.RandomState(1234)


@pytest.fixture
def no_datapipe_thread_leaks():
    """Fail THE TEST (not the session) if it leaks datapipe workers:
    threads (datapipe-map-*/datapipe-feed-* — decode and transfer lanes),
    child PROCESSES (datapipe-proc-* — ProcessPoolMap decode workers) or
    shared-memory segments (the ptpipe_* staging rings). Stages reap
    their daemons on exhaustion and on close(); a survivor means a worker
    is wedged on a queue, and a surviving shm segment would accumulate in
    /dev/shm across runs. Opt in per module with pytest.mark.usefixtures
    so unrelated suites don't pay the drain wait."""
    import multiprocessing
    import threading
    import time

    from paddle_tpu.datapipe import shm as dp_shm

    def _datapipe_threads():
        return {t for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("datapipe-")}

    def _datapipe_procs():
        return {p for p in multiprocessing.active_children()
                if p.name.startswith("datapipe-") and p.is_alive()}

    before = _datapipe_threads()
    before_p = _datapipe_procs()
    before_s = set(dp_shm.live_segments())
    yield
    deadline = time.time() + 5.0

    def _leaks():
        return (_datapipe_threads() - before,
                _datapipe_procs() - before_p,
                set(dp_shm.live_segments()) - before_s)

    leaked_t, leaked_p, leaked_s = _leaks()
    while (leaked_t or leaked_p or leaked_s) and time.time() < deadline:
        time.sleep(0.05)
        leaked_t, leaked_p, leaked_s = _leaks()
    msgs = []
    if leaked_t:
        msgs.append(f"threads: {sorted(t.name for t in leaked_t)}")
    if leaked_p:
        msgs.append(
            f"processes: {sorted(p.name for p in leaked_p)}")
    if leaked_s:
        msgs.append(f"shm segments: {sorted(leaked_s)}")
    if msgs:
        pytest.fail("leaked datapipe workers — " + "; ".join(msgs),
                    pytrace=False)
