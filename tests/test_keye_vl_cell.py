"""The cases of `chipbench/tests/test_sparse_attn_cell.py` where tier-1
counts them (tier-1 runs `tests/` only: PERF.md section 7): the
`keye_vl_2_0_30b_a3b_train_packed8k` cell's files found by name, its
rehearsal on the CPU at a tiny size, the costs against hand counts, every
`dsa.` reader on a made observation, `BENCHMARK.json`'s entries, a tree
without the model `Refused`, `check_line` on the recorded lines, and the
study's plants. The functions are that file's own, loaded by path
(`chipbench/tests` is no package) and not copied."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402

_cases = harness.load_module(
    os.path.join(REPO, "chipbench", "tests", "test_sparse_attn_cell.py"),
    "chipbench_tests_test_sparse_attn_cell")
globals().update({name: value for name, value in vars(_cases).items()
                  if name.startswith("test_")})


# One case is held here in another form. The recorded lines
# (`chipbench/tests/keye_vl_lines.jsonl`, PR 49) are held by the
# benchmark's own case to `BENCHMARK.json` AS IT STANDS, so every per-layer
# entry a later PR appends for this cell makes the old traced line "lack" a
# metric; neither file is this directory's to edit (PERF.md section 7 row
# 57). The same checks, through the same `check_line`, against the entries
# the cell had when its lines were recorded:
APPENDED_SINCE = ("setup_trace_s", "setup_lower_s", "setup_build_self_s",
                  "setup_builds")          # PR 51


def test_check_line_holds_the_recorded_lines_of_the_cell(monkeypatch):
    import json

    import pytest

    from chipbench import check_line

    bench = harness.Files().bench()
    assert {m["name"] for m in bench["per_layer"]} >= set(APPENDED_SINCE)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in APPENDED_SINCE]
    monkeypatch.setattr(harness.Files, "bench", lambda self: bench)
    with open(_cases.RECORDED) as f:
        lines = [json.loads(ln)["line"] for ln in f if ln.strip()]
    assert {("busy_s" in ln["device"]) for ln in lines} == {False, True}
    for line in lines:
        assert line["workload"] == _cases.CELL and line["correct"]
        assert check_line.problems(line, bench) == []
    traced = next(ln for ln in lines if "busy_s" in ln["device"])
    assert set(traced["metrics"]) == set(
        check_line.listed(bench, _cases.CELL, True))
    assert traced["metrics"]["dsa.selected_pairs_share"]["value"] \
        == pytest.approx(43.7, abs=0.05)
    for name in ("dsa.sparse_attention_roofline", "dsa.indexer_roofline",
                 "dsa.grouped_matmul_roofline", "dsa.model_flops_util"):
        assert 0 < traced["metrics"][name]["value"] <= 100, name
    assert traced["metrics"]["dsa.peak_hbm_gb"]["value"] > 0.25 * 16
    # the command's own entry point, in this process (a child would read
    # the file as it stands)
    assert check_line.main([_cases.RECORDED]) == 0
    # and against the entries as they stand the traced line lacks exactly
    # what was appended since
    monkeypatch.undo()
    assert sorted(check_line.problems(traced, harness.Files().bench())) == \
        sorted(f"metrics lacks {n}" for n in APPENDED_SINCE)
