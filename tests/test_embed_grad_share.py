"""The reader of `tokens.` / `share.` / `swa.` / `early.embed_grad_share`
(chipbench/layer_metrics/embed_grad_share.py) on made-up reductions of a
traced window: % of busy time under every scope key that has the Fluid op
`lookup_table_grad` among its components."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness, scopes                       # noqa: E402

CELLS = [("tokens.embed_grad_share", "olmoe_1b_7b"),
         ("share.embed_grad_share", "xing4_0_29b_a4b"),
         ("swa.embed_grad_share", "laguna_xs_2"),
         ("early.embed_grad_share", "smallthinker_21b_a3b")]


def _obs(by_scope, busy_s=2.0):
    return {"scopes": {"busy_s": busy_s, "window_s": busy_s,
                       "by_scope": by_scope}}


@pytest.mark.parametrize("name,config", CELLS)
@pytest.mark.parametrize("by_scope,want", [
    # XLA's sort, gather and scatter under the op's scope (the parent)
    ({"embed/lookup_table_grad": 0.2, "embed/lookup_table": 0.1,
      "lm_head/mul_grad": 0.5}, 10.0),
    # the kernel's key beneath it, and the table read a second time
    ({"embed/lookup_table_grad": 0.02,
      "embed/lookup_table_grad/row_tile_sum": 0.03,
      "mtp/embed/lookup_table_grad": 0.01,
      "mtp/embed/lookup_table_grad/row_tile_sum": 0.04,
      "optimizer/adam(embed)": 0.3, "sum(embed)": 0.1}, 5.0),
    # a window without the op, and one that was not traced
    ({"embed/lookup_table": 0.1, "lm_head/mul": 0.4}, None)])
def test_share_of_busy_time_under_the_op(name, config, by_scope, want):
    reader = harness.Files().metric_reader(name)
    got = reader.read(_obs(by_scope))
    assert got == want if want is None else got == pytest.approx(want)
    assert reader.read({}) is None
    assert reader.read({"scopes": None}) is None
    assert reader.read(_obs(by_scope, busy_s=0.0)) is None


def test_the_op_name_of_a_compiled_step_gives_the_keys():
    """What `scopes.scope_of` makes of the two op_names a v5e compile of
    the steps writes (tests/test_tpu_compile.py asserts those)."""
    assert scopes.scope_of(
        "jit(step)/embed/lookup_table_grad/row_tile_sum/pallas_call:"
    ) == "embed/lookup_table_grad/row_tile_sum"
    assert scopes.scope_of(
        "jit(multi)/while/body/mtp/embed/lookup_table_grad/scatter-add:"
    ) == "mtp/embed/lookup_table_grad"
    assert scopes.in_scope("mtp/embed/lookup_table_grad/row_tile_sum",
                           "lookup_table_grad")
    assert not scopes.in_scope("embed/lookup_table", "lookup_table_grad")


def test_each_token_cell_lists_the_metric():
    per_layer = {m["name"]: m for m in harness.Files().bench()["per_layer"]}
    for name, config in CELLS:
        entry = per_layer[name]
        assert (entry["moves"], entry["source"], entry["better"],
                entry["layer"]) == ("train_items_per_s", "device_trace",
                                    "lower", "kernels")
        assert [w.startswith(config) for w in entry["workloads"]] == [True]
