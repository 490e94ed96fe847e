"""The driver's contract for a result line, held on the CPU before a chip
run meets it: for EVERY cell of BENCHMARK.json a rehearsal-size traced line
holds exactly the metrics the file lists for that cell (what reads a device
trace may be missing on the CPU, and the line says so); the roofline
readers give a number whatever the count of kernel events in the trace; a
reader that returns None on a real run ends the run with no result, naming
the reader and the cell. PR 47 was refused for a traced line that lacked
`share.grouped_matmul_roofline`."""

import io
import json
import os
import types
from unittest import mock

import pytest

from chipbench import check_line, harness

HERE = os.path.dirname(os.path.abspath(__file__))
# the cell tests' own rehearsal sizes: (test module, its override's name)
TINY_OF = {
    "olmoe_1b_7b_train_packed4k": "test_tokens_cell.py",
    "xing4_0_29b_a4b_train_packed4k": "test_share_cell.py",
    "laguna_xs_2_train_packed8k": "test_window_share_cell.py",
    "smallthinker_21b_a3b_train_packed8k": "test_early_route_cell.py",
    "lfm2_8b_a1b_train_packed8k": "test_short_conv_cell.py",
    "qwen3_next_80b_a3b_train_packed8k": "test_delta_cell.py",
    "keye_vl_2_0_30b_a3b_train_packed8k": "test_sparse_attn_cell.py",
    "nemotron_3_nano_30b_a3b_train_packed4k": "test_ssd_cell.py",
}
# the image cells, which `tiny.json` sizes
TINY_JSON = ("resnet50_train_pipe", "se_resnext50_train_resident",
             "resnet50_train_dp4", "resnet50_train_resident")
BENCH = harness.Files().bench()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _override(cell):
    if cell in TINY_OF:
        return harness.load_module(os.path.join(HERE, TINY_OF[cell])).TINY
    # a cell this file does not know would be built at its FULL size on
    # the CPU (PR 49's and PR 54's cells were: 148 GB asked at once)
    assert cell in TINY_JSON, (
        f"{cell}: no rehearsal size on record; name the cell test's file "
        "in TINY_OF (its `TINY` is the override)")
    with open(os.path.join(HERE, "tiny.json")) as f:
        return json.load(f)


def _rehearse(cell, trace):
    from paddle_tpu.parallel import delta_rule

    out = io.StringIO()
    # the delta rule's chunk is the lowering's constant: the delta cell's
    # rehearsal shortens it to its rows of 32 (`test_delta_cell.py`)
    with mock.patch.object(delta_rule, "CHUNK", 8):
        return harness.run_cell(cell, seed=2 ** 31 + 48, seconds=2.0,
                                trace=trace, rehearsal=True,
                                override=_override(cell),
                                files=harness.Files(), out=out)


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_holds_exactly_the_metrics_its_cell_lists(cell):
    line = _rehearse(cell, True)
    assert check_line.problems(line, BENCH, rehearsal=True) == []
    want = check_line.listed(BENCH, cell, True)
    assert set(line["metrics"]) | set(line["metrics_missing"]) == set(want)
    assert not set(line["metrics"]) & set(line["metrics_missing"])
    # held to the real contract the same line lacks what the CPU cannot
    # read, and nothing else is wrong with it
    real = check_line.problems(line, BENCH)
    assert sorted(p for p in real if p.startswith("metrics lacks ")) == [
        f"metrics lacks {n}" for n in sorted(line["metrics_missing"])]
    # the comparisons that pair their numbers with the limits (`compared`)
    # say each number they held beside its limit, as the line's last key
    if cell.startswith(("xing4_0_29b_a4b", "qwen3_next_80b_a3b",
                        "keye_vl_2_0_30b_a3b", "nemotron_3_nano_30b_a3b",
                        "smallthinker_21b_a3b")):
        assert list(line)[-1] == "compared"
        failed = line["compared"].pop("failed")
        assert bool(failed) != line["checks"]["reference"]
        assert all(len(pair) == 2 and pair[1] is not None
                   for pair in line["compared"].values())
    elif cell.startswith(("olmoe_1b_7b", "laguna_xs_2", "lfm2_8b_a1b")):
        # a comparison that pairs no numbers yet: the failing checks by
        # name, its scalar readings and its limits
        assert list(line)[-1] == "compared"
        assert set(line["compared"]) == {"failed", "readings", "limits"}
        assert bool(line["compared"]["failed"]) \
            != line["checks"]["reference"]
        assert line["compared"]["readings"] and line["compared"]["limits"]
    else:
        assert "compared" not in line


def test_every_per_layer_entry_lists_its_cells_and_finds_a_reader():
    files = harness.Files()
    for m in BENCH["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS), m
        assert files.metric_reader(m["name"]) is not None, m["name"]
    for cell in CELLS:
        assert check_line.listed(BENCH, cell, True), cell
        assert "setup_s" in check_line.listed(BENCH, cell, False)


def _made_line(cell, metrics, traced=True):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    if traced:
        device.update(busy_s=1.0, window_s=2.0)
    return {"correct": True, "attempted": 1, "failed": 0, "device": device,
            "workload": cell, "metrics": metrics}


def test_check_line_tells_what_the_driver_would_refuse():
    cell = "xing4_0_29b_a4b_train_packed4k"
    want = check_line.listed(BENCH, cell, True)
    full = {n: {"value": 1.0, "unit": m["unit"]} for n, m in want.items()}
    assert check_line.problems(_made_line(cell, full), BENCH) == []
    lacking = dict(full)
    del lacking["share.grouped_matmul_roofline"]
    assert check_line.problems(_made_line(cell, lacking), BENCH) == [
        "metrics lacks share.grouped_matmul_roofline"]
    over = dict(full, **{"share.attention_roofline": {"value": 106.0,
                                                      "unit": "%"}})
    assert any("of a roofline" in p for p in check_line.problems(
        _made_line(cell, over), BENCH))
    extra = dict(full, **{"tokens.moe_share": {"value": 1.0, "unit": "%"}})
    assert any("does not list" in p for p in check_line.problems(
        _made_line(cell, extra), BENCH))
    idle = _made_line(cell, full)
    idle["device"]["busy_s"] = 0.0
    assert any("busy_s" in p for p in check_line.problems(idle, BENCH))
    untraced = {n: {"value": 1.0, "unit": m["unit"]}
                for n, m in check_line.listed(BENCH, cell, False).items()}
    assert set(untraced) == {"train_items_per_s", "setup_s"}
    assert check_line.problems(_made_line(cell, untraced, False),
                               BENCH) == []


def test_a_reader_that_returns_none_ends_a_real_run(tmp_path):
    """Not a rehearsal: the harness's look for a chip skipped, the rest of
    the run driven; one reader made to find nothing."""
    cell = "olmoe_1b_7b_train_packed4k"
    out = io.StringIO()
    real = harness.Files.metric_reader

    def reader(self, name):
        found = real(self, name)
        if name == "host_dispatch_ms":
            return types.SimpleNamespace(read=lambda obs: None,
                                         __file__=found.__file__)
        return found

    def cpu_devices(chips, rehearsal):
        import jax
        return jax.devices()[:chips]

    from chipbench import costs

    v5e = costs.peaks_for("TPU v5 lite")
    with mock.patch.object(harness, "pick_devices", cpu_devices), \
            mock.patch.object(harness.Files, "metric_reader", reader), \
            mock.patch.object(costs, "peaks_for", lambda kind: v5e):
        with pytest.raises(harness.Refused) as refused:
            harness.run_cell(cell, seed=7, seconds=2.0, trace=True,
                             rehearsal=False, override=_override(cell),
                             files=harness.Files(), out=out)
    said = str(refused.value)
    assert cell in said and "'host_dispatch_ms'" in said
    assert os.path.join("layer_metrics", "host_dispatch_ms.py") in said
    # the device-trace readers found nothing on the CPU either: each named
    assert "grouped_matmul_roofline" in said
    # and no result line was printed
    assert not any('"correct"' in ln for ln in out.getvalue().splitlines())
