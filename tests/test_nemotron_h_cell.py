"""The cases of `chipbench/tests/test_ssd_cell.py` where tier-1 counts
them (tier-1 runs `tests/` only: PERF.md section 7): the
`nemotron_3_nano_30b_a3b_train_packed4k` cell's files found by name, its
rehearsal on the CPU at a tiny size, the costs against hand counts, every
`ssd.` reader on a made observation, `BENCHMARK.json`'s entries, `source`
the catalog's, a tree without the model `Refused`, `check_line` on the
recorded lines, and the study's plants. The functions are that file's own,
loaded by path (`chipbench/tests` is no package) and not copied."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402

_cases = harness.load_module(
    os.path.join(REPO, "chipbench", "tests", "test_ssd_cell.py"),
    "chipbench_tests_test_ssd_cell")
globals().update({name: value for name, value in vars(_cases).items()
                  if name.startswith("test_")})


# One case is held here in another form. The recorded lines
# (`chipbench/tests/nemotron_lines.jsonl`, PR 54) are held by the
# benchmark's own case to `BENCHMARK.json` AS IT STANDS, so every per-layer
# entry a later PR appends for this cell makes the old traced line "lack" a
# metric; neither file is this directory's to edit (PERF.md section 7 row
# 57, `tests/test_keye_vl_cell.py`). The same checks, through the same
# `check_line`, against the entries the cell had when its lines were
# recorded: whatever lists the cell and is not among them was appended
# since.
RECORDED_WITH = {"ssd." + n for n in _cases.SSD_METRICS} \
    | set(_cases.FOLDED_METRICS)


def test_check_line_holds_the_recorded_lines_of_the_cell(monkeypatch):
    import json

    from chipbench import check_line

    bench = harness.Files().bench()
    listed = {m["name"] for m in bench["per_layer"]
              if _cases.CELL in m.get("workloads", ())}
    assert listed >= RECORDED_WITH
    appended_since = sorted(listed - RECORDED_WITH)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in appended_since]
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
    for name in ("ssd.scan_roofline", "ssd.attention_roofline",
                 "ssd.grouped_matmul_roofline", "ssd.model_flops_util"):
        assert 0 < traced["metrics"][name]["value"] <= 100, name
    assert traced["metrics"]["ssd.peak_hbm_gb"]["value"] > 0.25 * 16
    # the command's own entry point, in this process (a child would read
    # the file as it stands)
    assert check_line.main([_cases.RECORDED]) == 0
    # and against the entries as they stand the traced line lacks exactly
    # what was appended since
    monkeypatch.undo()
    assert sorted(check_line.problems(traced, harness.Files().bench())) == \
        sorted(f"metrics lacks {n}" for n in appended_since)
