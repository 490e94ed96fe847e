"""The harness is driven by data: a made-up configuration, traffic mix and
per-layer metric, written as files into a temporary directory with one
BENCHMARK.json entry each, run through the harness on the CPU rehearsal
path with no file of chipbench/ edited. Also the last line's key set, and
the shipped cells' files."""

import io
import json
import os
import textwrap

import pytest

from chipbench import harness

HERE = os.path.dirname(__file__)

BUILDER = '''
    """tinynet: two conv + bn + relu, average pool, one fc."""
    import jax
    import jax.numpy as jnp

    from chipbench import programs
    from chipbench.reference import convnet as cn


    def model(fluid, cfg, img):
        x = img
        for width in cfg["widths"]:
            x = fluid.layers.conv2d(input=x, num_filters=width, filter_size=3,
                                    padding=1, act=None, bias_attr=False,
                                    data_format="NHWC")
            x = fluid.layers.batch_norm(input=x, act="relu",
                                        data_layout="NHWC")
        x = fluid.layers.pool2d(input=x, pool_type="avg", global_pooling=True,
                                data_format="NHWC")
        return fluid.layers.fc(input=x, size=cfg["num_classes"],
                               act="softmax")


    def build(fluid, cfg, seed, for_compare=False):
        return programs.build_image_program(fluid, cfg, model, seed)


    def reference_order(layers, cfg):
        return list(layers)


    class reference:
        @staticmethod
        def network(cfg, tape, x, train):
            for _ in cfg["widths"]:
                x = cn.conv_bn(x, tape, 1, 1, train)
            return cn.dense(jnp.mean(x, axis=(1, 2)), tape)

        @staticmethod
        def layer_plan(cfg):
            cin, plan = cfg["channels"], []
            for i, w in enumerate(cfg["widths"]):
                plan.append(cn.conv_entry(cin, w, 3, 1, 1, cfg["image_size"],
                                          first=i == 0))
                cin = w
            return plan + [cn.dense_entry(cin, cfg["num_classes"])]
'''

READER = '''
    def read(obs):
        return float(obs["reading"]["chunks"])
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


@pytest.fixture(scope="module")
def made_up(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("madeup"))
    base = os.path.join(root, "bench")
    cfg = {"name": "tinynet", "builder": "bench/configs/tinynet.py",
           "widths": [8, 16], "num_classes": 10, "image_size": 8,
           "channels": 3, "layout": "NHWC", "input_scale": 1 / 255,
           "batch_per_chip": 4, "amp": None,
           "optimizer": {"type": "momentum", "learning_rate": 0.01,
                         "momentum": 0.9},
           "reference": {"file": "bench/configs/tinynet.py", "batch": 4}}
    _write(os.path.join(base, "configs", "tinynet.json"), json.dumps(cfg))
    _write(os.path.join(base, "configs", "tinynet.py"), BUILDER)
    _write(os.path.join(base, "traffic", "tiny_resident.json"), json.dumps({
        "kind": "train", "input": "resident",
        "end_to_end": {"tiny_items_per_s": "median_items_per_s"},
        "steps_per_chunk": 2, "warmup_chunks": 2, "drain_chunks": 0,
        "trace_chunks": 3}))
    _write(os.path.join(base, "layer_metrics", "tiny.chunks_read.py"),
           READER)
    bench = {
        "command": ["python3", "-m", "chipbench.run"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tinynet", "source": "made up",
                     "file": "bench/configs/tinynet.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tinynet_resident", "config": "tinynet",
                       "traffic": "tiny_resident", "chips": 1,
                       "why": "test"}],
        "end_to_end": [
            {"name": "tiny_items_per_s", "unit": "items/s",
             "better": "higher", "bound": 0.03, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "tiny.chunks_read", "unit": "chunks",
             "better": "higher", "source": "program_counter",
             "layer": "made up", "moves": "tiny_items_per_s"},
            {"name": "tiny.host_dispatch_ms", "unit": "ms",
             "better": "lower", "source": "host_clock",
             "layer": "step (executor)", "moves": "tiny_items_per_s"},
            {"name": "tiny.no_reader_for_this", "unit": "ms",
             "better": "lower", "source": "host_clock", "layer": "made up",
             "moves": "tiny_items_per_s"}]}
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(bench))
    return harness.Files(root=root,
                         bench_path=os.path.join(root, "BENCHMARK.json"),
                         extra_base=base)


def _run(files, trace):
    out = io.StringIO()
    line = harness.run_cell("tinynet_resident", seed=2 ** 31 + 5,
                            seconds=1.0, trace=trace, files=files,
                            rehearsal=True, out=out)
    return line, out.getvalue().strip().splitlines()


def test_a_cell_added_as_files_runs_end_to_end(made_up):
    line, lines = _run(made_up, trace=False)
    assert json.loads(lines[-1]) == line
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"tiny_items_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["correct"], line["checks"]
    assert line["rehearsal"] is True and line["failed"] == 0
    setup = json.loads(lines[0])["chipbench_setup"]
    names = [n for n, _ in setup["items"]]
    assert "reference_comparison" in names and "startup_program" in names
    assert abs(setup["not_itemised_s"]) < 0.5


def test_traced_run_reads_the_made_up_metric_and_skips_a_missing_reader(
        made_up):
    line, _ = _run(made_up, trace=True)
    # found by its full name; by its base name after the traffic prefix;
    # a metric with no reader is left out of the line
    assert line["metrics"]["tiny.chunks_read"]["value"] == 3.0
    assert line["metrics"]["tiny.host_dispatch_ms"]["value"] > 0
    assert "tiny.no_reader_for_this" not in line["metrics"]
    assert "setup_s" not in line["metrics"]


def test_no_accelerator_is_refused_without_a_rehearsal(made_up):
    with pytest.raises(harness.Refused):
        harness.run_cell("tinynet_resident", 1, 1.0, False, files=made_up,
                         out=io.StringIO())


def test_shipped_benchmark_file_names_files_that_exist():
    files = harness.Files()
    bench = files.bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) <= 5
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    with open(os.path.join(HERE, "serving_cell.json")) as f:
        proposed = json.load(f)
    e2e |= {m["name"] for m in proposed["end_to_end"]}
    bench["per_layer"] += proposed["per_layer"]
    files.bench = lambda: dict(bench, workloads=bench["workloads"]
                               + proposed["workloads"])
    for w in files.bench()["workloads"]:
        _, cell, cfg, traffic, builder, kind = files.cell(w["name"])
        assert os.path.exists(os.path.join(files.root,
                                           cfg["reference"]["file"]))
        assert set(traffic["end_to_end"]) <= e2e
        assert hasattr(kind, "run") and hasattr(builder, "build")
    for m in bench["per_layer"]:
        assert files.metric_reader(m["name"]) is not None, m["name"]
        assert m["moves"] in e2e


def test_the_result_line_s_compared_key_is_strict_json():
    """A run that is not `correct` may hold a reading that is not finite
    (no token routed alike: inf); the line's last key says it by name, so
    a parser that refuses `Infinity` still reads the line."""
    def refuse(word):
        raise ValueError(word)

    compared = {"LOGITS_TOL": [float("inf"), 0.03], "failed": ["logits"],
                "readings": {"x": float("nan"), "y": 1.5}}
    assert json.loads(json.dumps(harness._strict(compared)),
                      parse_constant=refuse) == {
        "LOGITS_TOL": ["inf", 0.03], "failed": ["logits"],
        "readings": {"x": "nan", "y": 1.5}}
