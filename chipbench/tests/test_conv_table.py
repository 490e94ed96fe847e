"""`chipbench/conv_table.py` on a reduction recorded on the v5e (PR 34):
`chipbench/data/convs_resnet50.json` holds, of one traced window of
`resnet50_train_resident` (40 steps), the rows of the stem, stage 1's
first block, stage 2's first block, the head, the optimizer's updates of
those and everything XLA made itself, as `conv_table.reduce_file` gave
them, and the all-reduces of a `resnet50_train_dp4` window (128 steps)."""

import json
import os

import pytest

from chipbench import conv_table, costs, harness

DATA = os.path.join(os.path.dirname(__file__), "..", "data",
                    "convs_resnet50.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def _plan(config):
    files = harness.Files()
    cell = next(w["name"] for w in files.bench()["workloads"]
                if w["config"] == config)
    _, _, cfg, _, builder, _ = files.cell(cell)
    return cfg, builder.reference.layer_plan(cfg)


@pytest.mark.parametrize("config, first, count", [
    ("resnet50", ["stem", "stage1/block0/conv1", "stage1/block0/conv2",
                  "stage1/block0/conv3", "stage1/block0/shortcut",
                  "stage1/block1/conv1"], 54),
    ("se_resnext50", ["stem", "stage1/block0/conv0", "stage1/block0/conv1",
                      "stage1/block0/conv2", "stage1/block0/se",
                      "stage1/block0/se", "stage1/block0/shortcut"], 86)])
def test_every_plan_entry_has_the_scope_its_model_gives_it(config, first,
                                                           count):
    cfg, plan = _plan(config)
    labels = conv_table.plan_labels(cfg, plan)
    assert labels[:len(first)] == first and labels[-1] == "head"
    assert len(labels) == len(plan) == count
    # a label's entry is the layer the model builds under that scope
    at = dict(zip(labels, plan))
    assert at["stem"]["k"] == 7 and at["stem"]["cin"] == 3
    assert at["stage4/block0/shortcut"]["stride"] == 2
    if config == "se_resnext50":
        assert at["stage1/block0/conv1"]["groups"] == 32


@pytest.mark.parametrize("key, name, filed", [
    ("stage1/block0/conv1/conv2d", "fusion",
     ("stage1/block0/conv1", "forward")),
    ("stage1/block0/conv1/batch_norm", "fusion",
     ("stage1/block0/conv1", "forward")),
    ("stage1/block0/conv1/conv2d_grad", "fusion",
     ("stage1/block0/conv1", "backward")),
    # the gradient to the filter with Momentum's update fused behind it
    ("stage1/block0/conv1/conv2d_grad", "multiply_subtract_fusion",
     ("stage1/block0/conv1", "update")),
    ("stage1/block0/conv1/conv2d_grad", "copy_subtract_fusion",
     ("stage1/block0/conv1", "update")),
    ("optimizer/momentum(stage1.block0.conv1)", "fusion",
     ("stage1/block0/conv1", "update")),
    ("sum(stage1.block0.conv1)", "fusion",
     ("stage1/block0/conv1", "backward")),
    ("stage1/block0/elementwise_add", "fusion",
     ("stage1/block0", "forward")),
    ("stage1/block0/elementwise_add_grad", "fusion",
     ("stage1/block0", "backward")),
    ("sum(stage1.block0)", "fusion", ("stage1/block0", "backward")),
    ("stem/pool2d_grad", "fusion", ("stem", "backward")),
    ("head/mul_grad", "multiply_subtract_fusion", ("head", "update")),
    ("optimizer/momentum", "fusion", ("optimizer", "update")),
    ("optimizer/momentum(head)", "fusion", ("head", "update")),
    ("cast", "fusion", ("cast", "forward")),
    ("[xla]copy-done", "copy-done", ("[xla]copy-done", "forward"))])
def test_a_key_is_filed_under_its_layer_and_pass(key, name, filed):
    cfg, plan = _plan("resnet50")
    labels = set(conv_table.plan_labels(cfg, plan))
    assert conv_table.file_under(key, name, labels) == filed


def test_table_of_the_recorded_window(recorded):
    cfg, plan = _plan("resnet50")
    peaks = costs.peaks_for("TPU v5 lite")
    rows, summary = conv_table.table(recorded, cfg, plan, recorded["steps"],
                                     cfg["batch_per_chip"], peaks)
    by_row = {r["row"]: r for r in rows}
    # plan rows first, in plan order, every one of them; then the others
    labels = list(dict.fromkeys(conv_table.plan_labels(cfg, plan)))
    assert [r["row"] for r in rows[:len(labels)]] == labels
    assert all("layers" not in r for r in rows[len(labels):])
    # every second of a `convolution` category lies on a row of the plan
    convolution = sum(s for _, cat, _, s, _ in recorded["ops"]
                      if "convolution" in cat) * 1e3 / recorded["steps"]
    assert summary["convolution_ms"] == pytest.approx(convolution)
    assert summary["on_plan_rows_share"] == pytest.approx(100.0)
    # and the columns of a row are the recorded seconds of its keys
    conv1 = by_row["stage1/block0/conv1"]
    assert conv1["layers"] == [[64, 64, 1, 1, 1, 55]]
    for column in ("forward", "backward", "update"):
        assert conv1[column] > 0
    assert conv1["least_ms"] == pytest.approx(
        costs.step_least_seconds([plan[1]], 128, True, peaks)[0] * 1e3)
    assert conv1["over_ms"] == pytest.approx(
        conv1["forward"] + conv1["backward"] + conv1["update"]
        - conv1["least_ms"])
    # the kept rows' time is all there: nothing is dropped, nothing twice
    total = sum(r[c] for r in rows
                for c in ("forward", "backward", "update", "other"))
    assert total == pytest.approx(
        sum(s for *_, s, _ in recorded["ops"]) * 1e3 / recorded["steps"])
    # layers the recording left out read zero and no roofline
    assert by_row["stage3/block2/conv2"]["roofline"] is None
    # what XLA made itself has rows of its own, after the plan's
    assert by_row["[xla]copy-done"]["other"] > 0
    assert "stage1/block0" in by_row          # the residual add
    text = conv_table.format_rows(rows, summary)
    assert text.count("\n") == len(rows) + 1 and "summary steps=" in text


def test_rows_of_one_shape_are_merged(recorded):
    cfg, plan = _plan("resnet50")
    rows, _ = conv_table.table(recorded, cfg, plan, recorded["steps"], 128,
                               costs.peaks_for("TPU v5 lite"))
    merged = {r["row"]: r for r in conv_table.by_shape(rows)}
    assert list(merged)[:4] == ["stem", "stage1/block0/conv1",
                                "stage1/block0-2/conv2",
                                "stage1/block0-2/conv3"]
    assert merged["stage3/block1-5/conv1"]["blocks"] == 5
    assert merged["stage3/block0/conv1"]["blocks"] == 1   # stride 2
    group = [r for r in rows if r["row"].startswith("stage1/block")
             and r["row"].endswith("/conv2")]
    assert merged["stage1/block0-2/conv2"]["forward"] == pytest.approx(
        sum(r["forward"] for r in group) / 3)
    assert merged["stage1/block0-2/conv2"]["over_ms"] == pytest.approx(
        sum(r["over_ms"] for r in group))
    assert sum(r["blocks"] for r in merged.values()) == len(
        [r for r in rows if "layers" in r])


def test_all_reduces_by_the_op_they_came_from(recorded):
    """The data-parallel step's collectives (`collective_ops`: the rows of
    a `resnet50_train_dp4` window that are all-reduces), statistics of the
    batch norms against gradients: by the Fluid op type the key ends in."""
    steps = recorded["collective_steps"]
    found = conv_table.collectives_by_op(
        {"ops": recorded["collective_ops"]}, steps)
    assert found == sorted(found, key=lambda r: -r["ms_a_step"])
    by_op = {r["op"]: r for r in found}
    assert set(by_op) == {"conv2d_grad", "batch_norm", "batch_norm_grad"}
    assert all(r["category"] == "all-reduce" for r in found)
    # one all-reduce of the gradients a step, 98 of statistics
    assert by_op["conv2d_grad"]["events_a_step"] == 1
    assert by_op["batch_norm"]["events_a_step"] \
        + by_op["batch_norm_grad"]["events_a_step"] == 98
    assert sum(r["ms_a_step"] for r in found) == pytest.approx(
        sum(s for *_, s, _ in recorded["collective_ops"]) * 1e3 / steps)
    # a window on one chip has none
    assert conv_table.collectives_by_op(recorded, recorded["steps"]) == []


def test_a_window_without_the_scopes_reads_as_xla_s_own():
    """The parent's program: no key names a layer, so no row of the plan
    holds a second and the share on the plan's rows is 0, not an error."""
    cfg, plan = _plan("resnet50")
    red = {"busy_s": 1.0, "window_s": 1.0, "ops": [
        ["[xla]fusion", "convolution fusion", "fusion", 0.5, 40],
        ["[xla]multiply_subtract_fusion", "convolution fusion",
         "multiply_subtract_fusion", 0.25, 40],
        ["[xla]copy-done", "copy-done", "copy-done", 0.1, 40]]}
    rows, summary = conv_table.table(red, cfg, plan, 40, 128,
                                     costs.peaks_for("TPU v5 lite"))
    assert summary["on_plan_rows_share"] == 0.0
    assert summary["rows_roofline"] is None
    assert summary["conv_roofline"] == pytest.approx(
        100 * summary["least_ms"] / 18.75)
    assert conv_table.collectives_by_op(red, 40) == []
    assert conv_table.reduce_planes([]) is None
