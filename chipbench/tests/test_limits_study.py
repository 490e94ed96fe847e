"""The readings the comparisons' limits rest on (`chipbench/data/
limits_study.json`, reduced from chip runs by `chipbench.limits_study`)
replayed against the limits as `compare_lm_delta_share` and
`compare_lm_share` hold them today: every `stated` row passes every limit,
every plant row fails the check named for it, each limit set again stands
the factor `m` from the readings on either side, and `m` is what the file
says. No chip and no JAX: the rows hold the numbers a `verdict` reads."""

import importlib
import os

import pytest

from chipbench import limits_study

STUDY = limits_study.load()
ROWS = [(module, row) for module in sorted(STUDY["rows"])
        for row in STUDY["rows"][module]]
# the check a plant's row has to fail (others may fail beside it)
NAMED = {"state_bf16": "delta_precision", "g_bf16": "delta_precision",
         "mixers": "mixers", "router": "gradients",
         "res_grad_transposed": "gradients",
         "pre_grad_dropped": "gradients", "post_grad_dropped": "gradients"}
# a fault planted in the reference that reads UNDER the sound runs' worst
# on every number: no limit can tell it, and the row says so
# (`compare_lm_share`'s comment on `POOLED_LIMITS`)
TOLD_BY_NONE = ("coefficient_path_dropped",)
TABLE = limits_study.table(STUDY)


def _id(param):
    if isinstance(param, dict):
        return "%s-%d-%s" % (param["variant"], param["seed"],
                             os.path.basename(param["source"]))
    return str(param)


def _failed(module, row):
    """The checks a recorded run fails under today's limits. What a record
    older than PR 48 does not hold (`lacks`) is taken out of the verdict,
    which has no switch for it, and the check is held on what the record
    does hold."""
    mod = importlib.import_module("chipbench." + module)
    numbers, lacks = row["numbers"], row["lacks"]
    if row.get("planted_in") == "reference":
        # the gradients' numbers alone, the reference against itself
        return [] if mod.gradients_held(numbers) else ["gradients"]
    if module == "compare_lm_delta_share":
        failed = set(mod.verdict(numbers, row["timed"]))
        if "timed_last" in lacks:
            failed.discard("timed_steps_second_build")
            if not numbers["timed_steps"]["err_second_build"][0] \
                    <= mod.TIMED_TWIN_TOL:
                failed.add("timed_steps_second_build")
        return sorted(failed)
    failed = set(mod.verdict(numbers))
    if lacks:
        failed.discard("gradients")
        held = mod.pooled_held(numbers)
        if not all(mod._grad_held(k, v)
                   for k, v in numbers["by_param"].items()) or not all(
                held[kind] for kind in held if kind + "_pooled" not in lacks):
            failed.add("gradients")
    return sorted(failed)


@pytest.mark.parametrize("module,row", ROWS, ids=_id)
def test_a_recorded_run_replays(module, row):
    failed = _failed(module, row)
    if row["variant"] == "stated" or row["variant"] in TOLD_BY_NONE:
        assert failed == [], (row["seed"], row["source"])
    else:
        assert NAMED[row["variant"]] in failed


def test_the_record_holds_the_seeds_the_limits_were_set_from():
    delta = STUDY["rows"]["compare_lm_delta_share"]
    stated = {r["seed"] for r in delta if r["variant"] == "stated"}
    assert len(stated) >= 24
    planted = {(r["variant"], r["seed"]) for r in delta
               if r["variant"] != "stated"}
    assert len({s for v, s in planted if v == "state_bf16"}) >= 3
    assert len({s for v, s in planted if v == "g_bf16"}) >= 3
    share = STUDY["rows"]["compare_lm_share"]
    seeds = {r["seed"] for r in share if r["variant"] == "stated"}
    # the seed PR 47's second form was refused on, and its three others
    assert {1672760455, 2147487211, 918273645, 2147484102} <= seeds
    assert {r["variant"] for r in share} >= {"stated", "mixers", "router"}
    # the last step of the chunk is this PR's statistic: rows that hold it
    assert sum(1 for r in delta if r["variant"] == "stated"
               and "timed_last" not in r["lacks"] and r["timed"]) >= 6


def test_m_is_what_the_file_says():
    from chipbench import compare_lm_delta_share, compare_lm_share

    assert STUDY["m"] == compare_lm_delta_share.M == compare_lm_share.M


@pytest.mark.parametrize("entry", TABLE, ids=lambda e: f"{e[0]}-{e[1]}")
def test_a_limit_set_again_keeps_m_on_both_sides(entry):
    module, name, limit, worst, seeds, least, planted = entry
    assert worst is not None and seeds >= 3, "no stated reading on record"
    assert limit >= STUDY["m"] * worst, (name, limit, worst)
    if least is not None:
        assert limit <= least / STUDY["m"], (name, limit, least)
    # a limit that names its plants has their readings on record, on three
    # seeds or more; the mixers' pooled limits each name one
    if name in limits_study.PLANTS[module] or name.startswith("POOLED"):
        assert least is not None and planted >= 3, name


def test_the_statistic_that_was_replaced_had_no_room():
    """Step 1 of the timed scan against the second build: over the recorded
    seeds its worst sound reading times m passes the least reading of a
    second step that carried nothing over m, so no limit had m on both
    sides (`TIMED_TWIN_TOL`'s comment)."""
    rows = [r["numbers"] for r in STUDY["rows"]["compare_lm_delta_share"]
            if r["variant"] == "stated" and r["timed"]]
    (_, sound, unmoved), = limits_study.REPLACED["compare_lm_delta_share"]
    worst = max(sound(n) for n in rows)
    least = min(unmoved(n) for n in rows)
    assert worst * STUDY["m"] > least / STUDY["m"]
    assert worst > 9e-5 and least < 1.2e-4
