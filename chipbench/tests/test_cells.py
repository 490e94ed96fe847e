"""The shipped kinds end to end on the CPU rehearsal path, at tiny sizes
(`tiny.json`): the pipe cell traced (datapipe, drain, traced window,
keep-up window) and the serving cell (generator, server, sampled
responses). Counts and control flow only: no number here is a timing."""

import io
import json
import os

import pytest

from chipbench import harness

HERE = os.path.dirname(__file__)


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "tiny.json")) as f:
        return json.load(f)


def _run(workload, trace, tiny, seconds=2.0, bench=None):
    out = io.StringIO()
    files = harness.Files(bench_path=bench)
    line = harness.run_cell(workload, seed=2 ** 31 + 17, seconds=seconds,
                            trace=trace, rehearsal=True, override=tiny,
                            files=files, out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_pipe_cell_traced(tiny):
    line, lines = _run("resnet50_train_pipe", True, tiny)
    m = line["metrics"]
    # trace metrics need a device plane, which XLA:CPU does not write
    assert {"pipe.input_wait_share", "pipe.keepup_share",
            "pipe.median_chunk_items_per_s", "pipe.host_dispatch_ms",
            "pipe.model_flops_util", "setup_compile_s"} <= set(m)
    assert line["checks"]["window_compiles_zero"]
    assert line["checks"]["losses_finite"]
    detail = lines[1]["chipbench_detail"]
    assert detail["keepup"]["compiles"] == []     # the pipe's own scan ran
    assert detail["records_made_this_run"] in (True, False)
    names = [n for n, _ in lines[0]["chipbench_setup"]["items"]]
    assert "feeder_prefetch_drained" in names and "records_made" in names
    assert line["override"] == tiny and line["rehearsal"] is True


def test_serve_cell(tiny):
    """The serving cell is not in BENCHMARK.json (PERF.md section 7 says
    why); `serving_cell.json` holds the entries that would add it."""
    line, lines = _run("resnet50_serve_poisson", False, tiny, seconds=3.0,
                       bench=os.path.join(HERE, "serving_cell.json"))
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                    "setup_s"}
    assert line["attempted"] == 150 and line["failed"] == 0
    assert line["checks"]["responses_match_executor_run"]
    assert line["checks"]["window_compiles_zero"]
    detail = lines[1]["chipbench_detail"]
    assert detail["reading"]["gen_late_p99_ms"] is not None
    assert detail["buckets_warmed"] == [2, 4]
