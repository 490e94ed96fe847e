"""The reader of `share.row_bound_hit_share` / `swa.row_bound_hit_share`
(chipbench/layer_metrics/row_bound_hit_share.py) on made-up observations:
the share of a window's (step, sparse layer) pairs whose held experts'
rows were within `lm_ops.row_bound` of the cell's shapes."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness                               # noqa: E402
from paddle_tpu.ops import lm_ops                           # noqa: E402

# (metric, configuration file, tokens a step, the bound at its shapes)
CELLS = [("share.row_bound_hit_share", "xing4_0_29b_a4b", 4096, 4096),
         ("swa.row_bound_hit_share", "laguna_xs_2", 8192, 16384)]


def _obs(config, tokens, by_layer):
    with open(os.path.join(REPO, "chipbench", "configs",
                           config + ".json")) as f:
        return {"cfg": json.load(f), "tokens_per_step": tokens,
                "held_rows_by_layer": by_layer}


@pytest.mark.parametrize("name,config,tokens,bound", CELLS)
@pytest.mark.parametrize("over,want", [
    (0, 100.0),             # every layer of every step within the bound
    (3, 75.0),              # 3 of the 12 pairs took the overflow branch
    (12, 0.0)])
def test_share_of_the_pairs_within_the_bound(name, config, tokens, bound,
                                             over, want):
    reader = harness.Files().metric_reader(name)
    rows = [bound + 1] * over + [bound, bound // 2, 0] * 4
    by_layer = [rows[i:i + 4] for i in range(0, 12, 4)]     # 3 steps
    assert reader.read(_obs(config, tokens, by_layer)) == want


@pytest.mark.parametrize("name,config,tokens,bound", CELLS)
def test_a_program_without_a_row_bound_reads_nothing(monkeypatch, name,
                                                     config, tokens, bound):
    """The parent's program: no `row_bound` to import, and a result line
    without the metric; a run that fetched no rows reads nothing too."""
    reader = harness.Files().metric_reader(name)
    assert reader.read(_obs(config, tokens, None)) is None
    assert reader.read(_obs(config, tokens, [])) is None
    monkeypatch.delattr(lm_ops, "row_bound")
    assert reader.read(_obs(config, tokens, [[1, 2], [3, 4]])) is None


def test_both_cells_list_the_metric():
    per_layer = {m["name"]: m for m in harness.Files().bench()["per_layer"]}
    for name, config, _, _ in CELLS:
        entry = per_layer[name]
        assert entry["moves"] == "train_items_per_s"
        assert entry["source"] == "program_counter"
        assert [w.startswith(config) for w in entry["workloads"]] == [True]
