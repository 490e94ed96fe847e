"""The four readers PR 34 added over `obs["scopes"]` (`unscoped_share`,
`expert_cast_share`, `expert_move_share`, `expert_route_share`) on a
reduction recorded on the v5e: `chipbench/data/scopes_laguna.json` is
`scopes.reduce_file`'s output for one traced window of
`laguna_xs_2_train_packed8k` (40 steps; PR 34's program, whose expert
layer names its parts), with the window's steps and tokens a step."""

import copy
import json
import os

import pytest

from chipbench import costs, harness, scopes

DATA = os.path.join(os.path.dirname(__file__), "..", "data",
                    "scopes_laguna.json")
CELL = "laguna_xs_2_train_packed8k"
PARTS = ("expert_cast_share", "expert_move_share", "expert_route_share")
PREFIXES = {"tokens": "olmoe_1b_7b_train_packed4k",
            "share": "xing4_0_29b_a4b_train_packed4k", "swa": CELL}


@pytest.fixture(scope="module")
def obs():
    with open(DATA) as f:
        recorded = json.load(f)
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    return {"scopes": recorded["scopes"], "cfg": cfg,
            "steps_in_window": recorded["steps_in_window"],
            "tokens_per_step": recorded["tokens_per_step"],
            "peaks": costs.peaks_for("TPU v5 lite")}


def _read(obs, name):
    return harness.Files().metric_reader("swa." + name).read(obs)


def _without_parts(obs):
    """The same window as the parent's program would have named it: the
    parts' time under the op's own key, no sub-scope."""
    red = copy.deepcopy(obs["scopes"])
    for field in ("by_scope", "events"):
        merged = {}
        for key, v in red[field].items():
            kept = "/".join(p for p in key.split("/") if p not in (
                "route", "dispatch", "cast", "combine"))
            merged[kept] = merged.get(kept, 0) + v
        red[field] = merged
    return dict(obs, scopes=red)


def test_the_parts_and_the_kernels_add_up_to_the_expert_layer(obs):
    """cast + move + route + the kernels' share = `moe_share` of the same
    window, within 5% of it: the check the hand splits never had. What is
    under the op's scopes and under no part and no kernel is under 5%."""
    got = {n: _read(obs, n) for n in PARTS}
    assert all(v is not None and 0 < v < 100 for v in got.values()), got
    red = obs["scopes"]
    kernels = harness.Files().metric_reader(
        "swa.grouped_matmul_roofline").kernel_seconds(red, obs)
    assert kernels
    # the expert layer's share as `tokens.moe_share` reads it from a
    # program with its own kernels: the op's two scopes
    moe = 100 * scopes.seconds(red, *scopes.MOE_OPS) / red["busy_s"]
    total = sum(got.values()) + 100 * kernels / red["busy_s"]
    assert total <= moe * (1 + 1e-9)
    assert total == pytest.approx(moe, rel=0.05)
    # each part is the seconds of the keys that name it
    by_hand = sum(s for k, s in red["by_scope"].items()
                  if ("moe_ffn" in k.split("/") or "moe_ffn_grad"
                      in k.split("/")) and "cast" in k.split("/"))
    assert got["expert_cast_share"] == pytest.approx(
        100 * by_hand / red["busy_s"])
    assert got["expert_move_share"] == pytest.approx(100 * sum(
        s for k, s in red["by_scope"].items()
        if scopes.in_scope(k, *scopes.MOE_OPS)
        and scopes.in_scope(k, "dispatch", "combine")) / red["busy_s"])


def test_a_cast_outside_the_expert_layer_is_not_the_expert_layer_s(obs):
    red = obs["scopes"]
    elsewhere = [k for k in red["by_scope"] if "cast" in k.split("/")
                 and not scopes.in_scope(k, *scopes.MOE_OPS)]
    more = copy.deepcopy(red)
    more["by_scope"]["attn_full/mul/cast"] = 1.0
    assert _read(dict(obs, scopes=more), "expert_cast_share") == _read(
        obs, "expert_cast_share"), elsewhere


def test_unscoped_share_is_the_detail_line_s(obs):
    got = _read(obs, "unscoped_share")
    assert got == scopes.unscoped_share(obs["scopes"])
    assert 0 < got < 10
    # with no event outside a scope it reads 0, a number
    none = dict(obs, scopes=dict(obs["scopes"], unscoped_ops={}))
    assert _read(none, "unscoped_share") == 0.0


def test_a_program_without_the_parts_reads_nothing(obs):
    """The parent: `null` for the three parts, and never an error; the
    accepted readers of the same window read what they read."""
    parent = _without_parts(obs)
    assert {n: _read(parent, n) for n in PARTS} == dict.fromkeys(PARTS)
    assert _read(parent, "unscoped_share") == _read(obs, "unscoped_share")
    for name in ("expert_other_share", "head_share", "optimizer_share",
                 "attention_share"):
        assert _read(parent, name) == pytest.approx(_read(obs, name))
        assert _read(obs, name) is not None
    for empty in ({"scopes": None}, {}, {"scopes": {
            "busy_s": 0.0, "by_scope": {}, "events": {},
            "unscoped_ops": {}}}):
        for name in PARTS + ("unscoped_share",):
            assert _read(dict(obs, **empty) if empty else {}, name) is None


@pytest.mark.parametrize("prefix", sorted(PREFIXES))
def test_benchmark_lists_the_twelve_entries(prefix):
    files = harness.Files()
    per_layer = {m["name"]: m for m in files.bench()["per_layer"]}
    for base in PARTS + ("unscoped_share",):
        # one entry a quantity where one reader serves every prefix (PR
        # 48); `expert_cast_share` has prefix-named readers and entries
        folded = f"{prefix}.{base}" not in per_layer
        entry = per_layer[base if folded else f"{prefix}.{base}"]
        assert {k: v for k, v in entry.items() if k != "workloads"} == {
            "name": base if folded else f"{prefix}.{base}", "unit": "%",
            "better": "lower", "source": "device_trace", "layer": "kernels",
            "moves": "train_items_per_s"}
        assert (PREFIXES[prefix] in entry["workloads"] if folded
                else entry["workloads"] == [PREFIXES[prefix]])
        assert files.metric_reader(entry["name"]).read({}) is None
