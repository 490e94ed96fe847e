"""The readings the comparisons' limits rest on (`chipbench/data/
limits_study.json`, reduced from chip runs by `chipbench.limits_study`)
replayed against the limits as `compare_lm_delta_share`,
`compare_lm_share`, `compare_lm_sparse_attn_share` and (since the driver's
check of PR 56 refused its cell) `compare_lm_early_route_share` hold them
today:
every `stated` row passes every limit (a row read against the plain
reference: every limit that does not follow the reference's routing),
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
         "pre_grad_dropped": "gradients", "post_grad_dropped": "gradients",
         "router_bf16": "gradients", "indexer_reads_u": "gradients"}
# a fault planted in the reference that reads UNDER the sound runs' worst
# on every number: no limit can tell it, and the row says so
# (`compare_lm_share`'s comment on `POOLED_LIMITS`)
TOLD_BY_NONE = ("coefficient_path_dropped",)
TABLE = limits_study.table(STUDY)


def _id(param):
    if isinstance(param, dict):
        return "%s-%d-%s%s" % (param["variant"], param["seed"],
                               os.path.basename(param["source"]),
                               "-routed" if param.get("routed") else "")
    return str(param)


def _failed(module, row):
    """The checks a recorded run fails under today's limits. What a record
    older than PR 48 does not hold (`lacks`) is left out of the verdict,
    and so is, of a `stated` row read against the PLAIN reference (every
    row before PR 56, and the census' second reading), what reads
    otherwise against a reference routed as the system
    (`FOLLOWS_ROUTING`): those limits were set from the routed rows."""
    mod = importlib.import_module("chipbench." + module)
    numbers = row["numbers"]
    if row.get("planted_in") == "reference":
        # the gradients' numbers alone, the reference against itself
        return [] if mod.gradients_held(numbers) else ["gradients"]
    without = tuple(p for k in row["lacks"]
                    for p in limits_study.LACKS_NAMES[k])
    if row["variant"] == "stated" and not row.get("routed"):
        without += tuple(mod.FOLLOWS_ROUTING)
    return mod.verdict(numbers, row["timed"], without)


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
    # a limit that names its plants has their readings on record: PR 48's
    # (the delta rule's precision, the mixers' pooled limits) on three
    # seeds or more, PR 56's (the routers' and experts' gradients on the
    # routed reference) on as many as its chip time reached
    if name in limits_study.PLANTS.get(module, {}) \
            or name.startswith("POOLED"):
        assert least is not None and planted >= (
            3 if name.startswith(("POOLED", "DELTA_F32")) else 1), name


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


# the census of PR 56 (`python -m chipbench.census` on the chip): each seed
# read twice off one system side, against the reference routed as the
# system routed and against the plain one
CENSUS = "chiprun_out/census/"


def _census(module, routed):
    return {r["seed"]: r for r in STUDY["rows"][module]
            if r["variant"] == "stated" and r["source"].startswith(CENSUS)
            and bool(r.get("routed")) == routed}


# the three modules whose reference PR 56's census read both ways (the
# early-route module's rows are whole runs and a diagnostic: below)
CENSUS_MODULES = ("compare_lm_delta_share", "compare_lm_share",
                  "compare_lm_sparse_attn_share")


@pytest.mark.parametrize("module", CENSUS_MODULES)
def test_the_census_read_every_seed_both_ways(module):
    routed, plain = _census(module, True), _census(module, False)
    assert len(routed) >= 3 and set(plain) <= set(routed)
    # seeds no record held before this PR's calls (its census, its
    # studies' rows and its final tree's whole runs)
    before = {r["seed"] for r in STUDY["rows"][module]
              if not r["source"].startswith((CENSUS, "chiprun_out/pr56/",
                                             "chiprun_out/final/"))}
    assert len(set(routed) - before) >= 3
    mod = importlib.import_module("chipbench." + module)
    for seed, row in routed.items():
        # the logits were read over a good share of the row (Qwen3-Next:
        # its inference program sends 55 - 60% of the tokens elsewhere
        # than its training step somewhere in four layers of top-10 of 512)
        assert row["numbers"]["tokens_routed_alike_everywhere"] > 0.35
        assert _failed(module, row) == [], seed
    # what a plain row holds of the first-hand checks is the routed row's
    follows = tuple(mod.FOLLOWS_ROUTING)
    for seed, row in plain.items():
        a = mod.numbers_held(row["numbers"])
        b = mod.numbers_held(routed[seed]["numbers"])
        assert {n: v for n, v in a.items() if not n.startswith(follows)} \
            == {n: v for n, v in b.items() if not n.startswith(follows)}


def test_the_seeds_that_refused_the_accepted_program_pass_routed():
    """PR 53's two seeds of the Keye-VL cell (parent and change alike not
    `correct`, through the first router's gradient-norm ratio): against the
    plain reference they fail `gradients` by that number alone under the
    limit as it stood and stands, against the routed one they pass it with
    almost three times of room (0.0045, 0.0050 | 0.015)."""
    mod = importlib.import_module("chipbench.compare_lm_sparse_attn_share")
    routed = _census("compare_lm_sparse_attn_share", True)
    plain = _census("compare_lm_sparse_attn_share", False)
    for seed in (1999000444, 1853000777):
        numbers = mod.numbers_held(plain[seed]["numbers"])
        assert mod.verdict(plain[seed]["numbers"]) == ["gradients"]
        reading, limit = numbers["GRAD[router] ratio"]
        assert reading > limit == 0.015
        reading, limit = mod.numbers_held(routed[seed]["numbers"])[
            "GRAD[router] ratio"]
        assert 2.9 * reading <= limit
        assert mod.verdict(routed[seed]["numbers"]) == []


def test_the_seed_the_driver_refused_fails_by_the_logits_max_alone():
    """The driver's check of PR 56 drew seed 601926867 for the smallthinker
    cell: against the PLAIN reference (the tree as refused, run again on
    the chip) `logits` fails through the max alone, and the timed scan's
    second step stands a tenth under its limit; against the reference
    sent where the inference program went, its second step's biases
    moved on the system's choices (the committed files, the same seed),
    both stand with three times of room and more, and the plain masked
    reading is still on that row's record."""
    mod = importlib.import_module("chipbench.compare_lm_early_route_share")
    rows = [r for r in STUDY["rows"]["compare_lm_early_route_share"]
            if r["seed"] == 601926867 and r["timed"]]
    plain, = (r["numbers"] for r in rows if not r["routed"])
    routed, = (r["numbers"] for r in rows if r["routed"])
    assert mod.verdict(plain, True) == ["logits"]
    held = mod.numbers_held(plain)
    assert [n for n, v in held.items() if v[0] > v[1]] == ["LOGITS_TOL"]
    assert held["LOGITS_TOL"][0] > 0.0325
    assert 0.85 * held["TIMED LOSS_TOL"][1] < held["TIMED LOSS_TOL"][0]
    assert mod.verdict(routed, True) == []
    held = mod.numbers_held(routed)
    assert 3.0 * held["LOGITS_TOL"][0] < held["LOGITS_TOL"][1] == 0.03
    assert 6.0 * held["TIMED LOSS_TOL"][0] < held["TIMED LOSS_TOL"][1]
    assert routed["logits_plain_err_max_rms"][0] == plain["logits_err_max"]
    # the biases the reference's own choices would have moved otherwise
    assert routed["timed_steps"][
        "experts_the_free_choices_move_otherwise"] == [[], [4, 31], [], []]


def test_the_early_route_rows_were_read_off_the_routed_reference():
    rows = [r for r in STUDY["rows"]["compare_lm_early_route_share"]
            if r["variant"] == "stated" and r["routed"]]
    assert len({r["seed"] for r in rows}) >= 3 and all(
        r["timed"] for r in rows)
    names = {e[1] for e in TABLE if e[0] == "compare_lm_early_route_share"}
    assert names == {"LOGITS_TOL", "LOGITS_RMS_TOL", "TIMED LOSS_TOL"}
