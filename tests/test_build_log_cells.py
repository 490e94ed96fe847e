"""The four `setup_*` metrics that read the program's build log (PR 51)
on the lines of two rehearsed cells: one image cell and one token cell at
their tiny sizes, traced, on the CPU. Tier-1 runs `tests/` only, so the
benchmark's own `chipbench/tests/test_contract.py` adds nothing to its
count; the sizes are that directory's (`tiny.json`, `test_tokens_cell.py:
TINY`)."""

import io
import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import build_log, check_line, harness  # noqa: E402

HERE = os.path.join(REPO, "chipbench", "tests")
NEW = ("setup_trace_s", "setup_lower_s", "setup_build_self_s",
       "setup_builds")
BENCH = harness.Files().bench()


def _tiny(cell):
    if cell.startswith("olmoe"):
        return harness.load_module(
            os.path.join(HERE, "test_tokens_cell.py")).TINY
    with open(os.path.join(HERE, "tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["resnet50_train_resident",
                                        "olmoe_1b_7b_train_packed4k"])
def rehearsed(request):
    from paddle_tpu.cache import builds

    builds.reset()       # one cell a process, as the benchmark runs them
    out = io.StringIO()
    line = harness.run_cell(request.param, seed=2 ** 31 + 51, seconds=2.0,
                            trace=True, rehearsal=True,
                            override=_tiny(request.param),
                            files=harness.Files(), out=out)
    return line, harness.json_objects(out.getvalue())


def test_the_traced_line_holds_the_four_build_metrics(rehearsed):
    line, printed = rehearsed
    setup_s = next(o["chipbench_setup"]["setup_s"] for o in printed
                   if "chipbench_setup" in o)
    got = {n: line["metrics"][n] for n in NEW}
    assert all(math.isfinite(m["value"]) and m["value"] >= 0.0
               for m in got.values())
    assert [got[n]["unit"] for n in NEW] == ["s", "s", "s", "count"]
    assert got["setup_builds"]["value"] >= 2
    assert got["setup_trace_s"]["value"] > 0.0
    assert got["setup_lower_s"]["value"] > 0.0
    # the builds lie inside set-up, and so do their seconds
    assert sum(got[n]["value"] for n in NEW[:3]) <= setup_s
    assert not set(NEW) & set(line["metrics_missing"])


def test_check_line_is_content_with_the_line(rehearsed):
    line, _ = rehearsed
    assert check_line.problems(line, BENCH, rehearsal=True) == []
    assert all(n in check_line.listed(BENCH, line["workload"], True)
               for n in NEW)


def test_the_detail_carries_the_build_table(rehearsed):
    line, printed = rehearsed
    detail = next(o["chipbench_detail"] for o in printed
                  if "chipbench_detail" in o)
    builds = detail["layer_metric_notes"]["setup_builds"]["builds"]
    assert len(builds) == line["metrics"]["setup_builds"]["value"]
    for b in builds:
        assert {"name", "fingerprint", "phases", "key_diff",
                "persistent_hit", "nested_traces", "wall_s"} <= set(b)
        assert sum(b["phases"].values()) == pytest.approx(b["wall_s"],
                                                          abs=1e-3)
    # the K-step scan is among them, and the backend's seconds the
    # program files do not pass what the harness hears from outside
    assert "multi" in {b["name"] for b in builds}
    backend = sum(b["phases"]["backend"] for b in builds)
    assert backend <= line["metrics"]["setup_compile_s"]["value"] + 1e-6


def test_a_program_without_the_log_reads_zero_and_says_so(monkeypatch):
    """The parent of PR 51 under these files: no `build_log` to import. A
    reader that gave None would end the run with no line."""
    import paddle_tpu.cache as cache

    monkeypatch.delattr(cache, "build_log")
    obs = {"t_open": 1.0}
    assert build_log.records(obs) is None
    for name in NEW:
        reader = harness.Files().metric_reader(name)
        assert reader.read(obs) == 0.0
    note = harness.Files().metric_reader("setup_builds").note(obs)
    assert note == {"build_log": build_log.NO_LOG}
