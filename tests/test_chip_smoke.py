"""chip_smoke.py on the CPU: the phase functions at TINY sizes under the
explicit CPU pin (kernels interpreted, result labelled a rehearsal), the
script's refusal to run without a chip, and where the compile cache goes.
The chip itself is checked by running `python chip_smoke.py` there."""

import json
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def rehearsal():
    """One TINY run of every phase; the tests below read its summary."""
    return chip_smoke.run_phases(fluid, chip_smoke.TINY, jax.devices()[0],
                                 rehearsal=True)


def test_rehearsal_is_labelled_and_names_its_device(rehearsal):
    assert rehearsal["rehearsal"] is True
    assert rehearsal["platform"] == "cpu"
    assert rehearsal["device"] == {"platform": "cpu", "kind": "cpu",
                                   "count": len(jax.devices())}
    assert rehearsal["claim"] is None


def test_last_stdout_line_is_the_verdict_and_nothing_else(rehearsal, capsys):
    """The driver reads the last line and refuses any key beyond "ok" and
    "device" {"platform", "kind", "count"}; the report is the line before."""
    chip_smoke.emit(rehearsal)
    report, verdict = map(json.loads, capsys.readouterr().out.splitlines())
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}}
    assert report["phases"].keys() == {"train", "serve", "kernels",
                                       "share_model", "multichip"}


def test_rehearsal_train_phase(rehearsal):
    train = rehearsal["phases"]["train"]
    assert all(train["checks"].values()), train["checks"]
    # 2 warm-up calls, then no compile; uint8 wire on the shm path
    assert train["step_compiles_per_call"][2:] == [0, 0]
    assert train["plain_step_compiles"][1] == 0
    assert train["pipe"]["wire"] == {"data_u8": "WireFormat(uint8)"}
    assert train["recordio_lib"]["file"].startswith("librecordio-")


def test_rehearsal_serve_phase(rehearsal):
    serve = rehearsal["phases"]["serve"]
    assert all(serve["checks"].values()), serve["checks"]
    assert serve["requests"] == chip_smoke.TINY.serve_requests


def test_rehearsal_kernels_are_interpreted_off_the_chip(rehearsal):
    kernels = rehearsal["phases"]["kernels"]
    assert kernels["mosaic_expected"] is False
    assert all(kernels["checks"].values()), kernels["checks"]
    assert not any(c["mosaic"] for c in kernels["flash_attention"])
    assert kernels["checks"].keys() == {"flash_attention", "grouped_matmul"}
    case, = kernels["grouped_matmul"]
    assert not case["mosaic"] and case["largest_over_mean"] > 1.5
    assert case["tiles"][0] == 128       # the longest that divides 384 rows


def test_rehearsal_share_model_phase(rehearsal):
    """The small share-holding model's step ran under bf16 AMP against
    its float32 reference; off the chip neither kernel family engages."""
    share = rehearsal["phases"]["share_model"]
    assert all(share["checks"].values()), share["checks"]
    assert share["lowered"]["moe_ffn_held_experts"] == 2
    assert "grouped_matmul_kernel" not in share["lowered"]
    assert sum(share["tokens_per_expert"]) == 2 * 128
    assert max(share["rel_err"]) <= chip_smoke.SHARE_LOSS_TOL


def test_rehearsal_multichip_phase_on_the_virtual_mesh(rehearsal):
    multi = rehearsal["phases"]["multichip"]
    n = len(jax.devices())
    assert n > 1, "tests/conftest.py forces 8 host devices"
    assert all(multi["checks"].values()), multi["checks"]
    assert multi["n_devices"] == n
    assert multi["params_on_n_distinct_devices"] == n
    assert multi["all_reduce_ops_in_compiled_step"] > 0
    assert multi["dpmp"]["mesh"] == {"dp": n // 2, "mp": 2}
    assert rehearsal["ok"] is True


def _run(args, env_drop=(), env_add=None, cwd=REPO):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_add or {})
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("pinned", [False, True])
def test_chip_smoke_exits_nonzero_and_prints_no_result_without_a_chip(
        pinned):
    """No TPU, with or without the CPU pin: exit 2, nothing on stdout, the
    missing device named on stderr."""
    r = _run([os.path.join(REPO, "chip_smoke.py")],
             env_drop=() if pinned else ("JAX_PLATFORMS", "XLA_FLAGS"))
    assert r.returncode == 2, (r.returncode, r.stderr[-800:])
    assert r.stdout == ""
    assert "no TPU" in r.stderr


_CACHE_DIR_CODE = (
    "import jax\n"
    "import paddle_tpu as fluid\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "fluid.Executor(fluid.CPUPlace())\n"
    "fluid.ParallelExecutor(use_tpu=False, main_program=fluid.Program())\n"
    "print(repr(before), repr(jax.config.jax_compilation_cache_dir))\n")


def test_compile_cache_dir_left_alone_when_set_from_outside(tmp_path):
    where = str(tmp_path / "outside_cache")
    r = _run(["-c", _CACHE_DIR_CODE],
             env_add={"JAX_COMPILATION_CACHE_DIR": where})
    assert r.returncode == 0, r.stderr[-800:]
    assert r.stdout.split() == [repr(where), repr(where)]


def test_compile_cache_dir_defaults_to_the_checkout(tmp_path):
    # from another cwd: the path comes from the checkout, not from where
    # the process happens to start
    r = _run(["-c", _CACHE_DIR_CODE],
             env_drop=("JAX_COMPILATION_CACHE_DIR",), cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-800:]
    assert r.stdout.split() == [
        "None", repr(os.path.join(REPO, ".jax_cache"))]


def _check_reference(sizes):
    from paddle_tpu import amp

    try:
        got = chip_smoke.reference_losses(fluid, sizes, fluid.CPUPlace())
    finally:
        amp.disable()
    assert got == pytest.approx(list(sizes.first_losses_ref), abs=2e-3)


def test_tiny_reference_losses_are_the_float32_cpu_values():
    _check_reference(chip_smoke.TINY)


@pytest.mark.slow
def test_full_width_reference_losses_are_the_float32_cpu_values():
    """What chip_smoke.py holds the chip's first two losses against:
    ResNet-50, batch 128, float32 on the CPU (~2.5 min here)."""
    _check_reference(chip_smoke.FULL)
