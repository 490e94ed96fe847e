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
