"""The reference routed as the system routed (PR 56), at the three share
cells' tiny presets on the CPU: `loss_and_grads(routing=)` given the
reference's OWN free top-k is the plain reference; given a choice with
planted near-tie flips it goes where that choice went and still returns its
own free top-k beside, so the flips are judged once, as choices; against a
program that computes as the reference does but fell the other way at those
near-ties the PLAIN reference's router gradient moves and the routed one's
does not. And a comparison's `compared` names the failing number first when
one limit is set to zero, through `judge` and through a whole run's last
lines on standard error."""

import importlib
import io
import os
from unittest import mock

import numpy as np
import pytest

import paddle_tpu as fluid
from chipbench import census, harness, held, limits_study
from chipbench.compare_lm import _cos_ratio

HERE = os.path.dirname(os.path.abspath(__file__))
CELL_TESTS = {"compare_lm_share": "test_share_cell.py",
              "compare_lm_delta_share": "test_delta_cell.py",
              "compare_lm_sparse_attn_share": "test_sparse_attn_cell.py"}
SEED = 2 ** 31 + 56


def _cell(module):
    """(the comparison's module, cfg at the cell test's tiny size, traffic,
    builder, kind)."""
    case = harness.load_module(os.path.join(HERE, CELL_TESTS[module]))
    _, _, cfg, traffic, builder, kind = harness.Files().cell(case.CELL)
    cfg = dict(cfg, **case.TINY["config"])
    traffic = dict(traffic, **case.TINY["traffic"])
    assert limits_study.MODULES[cfg["name"]] == module
    return (importlib.import_module("chipbench." + module), cfg, traffic,
            builder, kind, case)


def _system(compare, cfg, traffic, builder, kind):
    from paddle_tpu import amp
    from paddle_tpu.parallel import delta_rule

    tok, lab = census.rows_of(cfg, traffic, kind, SEED, compare)
    if cfg.get("amp"):
        amp.enable(cfg["amp"])
    try:
        # the delta cell's rehearsal shortens the lowering's chunk to its
        # rows of 32 (`test_delta_cell.py`)
        with mock.patch.object(delta_rule, "CHUNK", 8):
            got = compare.system_side(fluid, cfg, builder,
                                      fluid.TPUPlace(0), SEED, tok, lab)
    finally:
        amp.disable()
    return got, tok, lab


def _pass(compare, cfg, builder, got, tok, lab, sent):
    """The reference's pass sent where `sent` says ([T, k] a layer; None:
    the plain reference)."""
    return compare.reference_of(cfg, builder, dict(got, ids=sent), tok, lab,
                                routed=sent is not None, whole=False)


def _near_ties_flipped(routing, share=0.25):
    """The free choices [T, k] a layer with, in EVERY layer, the `share` of
    tokens whose k-th and (k + 1)-th experts lie closest sent to the
    (k + 1)-th instead of the k-th."""
    flipped = []
    for chosen_by, top in routing:
        k = top.shape[1]
        order = np.argsort(-chosen_by, axis=1)
        ranked = np.take_along_axis(chosen_by, order, axis=1)
        gap = ranked[:, k - 1] - ranked[:, k]
        tokens = np.argsort(gap)[:max(1, int(share * len(gap)))]
        sent = np.array(order[:, :k])
        sent[tokens, k - 1] = order[tokens, k]
        flipped.append(sent.astype(top.dtype))
    return flipped


@pytest.mark.parametrize("module", sorted(CELL_TESTS))
def test_the_reference_goes_where_it_is_sent_and_says_where_it_would_have(
        module):
    compare, cfg, traffic, builder, kind, _ = _cell(module)
    got, tok, lab = _system(compare, cfg, traffic, builder, kind)
    router = next(k for k in builder.sampled_params(cfg)
                  if k.startswith("router"))
    plain = _pass(compare, cfg, builder, got, tok, lab, None)
    free = [top for _, top in plain["routing"]]
    # sent where it goes by itself, it is the plain reference
    alike = _pass(compare, cfg, builder, got, tok, lab, free)
    assert alike["loss"] == pytest.approx(plain["loss"], rel=1e-6)
    np.testing.assert_allclose(alike["grads"][router],
                               plain["grads"][router], rtol=1e-4, atol=1e-9)
    for (by_a, top_a), (by_p, top_p) in zip(alike["routing"],
                                            plain["routing"]):
        assert np.array_equal(np.sort(top_a, 1), np.sort(top_p, 1))
    # a program that computes as the reference does, but whose top-k fell
    # the other way at a quarter of each layer's nearest ties
    flipped = _near_ties_flipped(plain["routing"])
    system = _pass(compare, cfg, builder, got, tok, lab, flipped)
    # ... went elsewhere (the router's gradient below says so) and still
    # names its own first-layer choice, so the flips show as flips
    assert system["loss"] != plain["loss"]
    assert np.array_equal(np.sort(system["routing"][0][1], 1),
                          np.sort(free[0], 1))
    differs = (np.sort(flipped[0], 1) != np.sort(free[0], 1)).any(axis=1)
    assert 0.2 <= differs.mean() <= 0.3
    # against that program the PLAIN reference's router gradient moves ...
    cos, ratio = _cos_ratio(system["grads"][router], plain["grads"][router])
    assert max(1.0 - cos, abs(ratio - 1.0)) > 1e-3
    # ... and the reference sent where the program went does not
    routed = _pass(compare, cfg, builder, got, tok, lab,
                   [np.array(v) for v in flipped])
    cos, ratio = _cos_ratio(system["grads"][router],
                            routed["grads"][router])
    assert max(1.0 - cos, abs(ratio - 1.0)) < 1e-6
    assert routed["loss"] == pytest.approx(system["loss"], rel=1e-6)


@pytest.mark.parametrize("module", sorted(CELL_TESTS))
def test_compared_names_the_failing_number_first(module, monkeypatch):
    compare, cfg, traffic, builder, kind, _ = _cell(module)
    got, tok, lab = _system(compare, cfg, traffic, builder, kind)
    ref = compare.reference_of(cfg, builder, got, tok, lab)
    report = compare.judge(cfg, builder, got, ref)
    assert report["reference_routed_as_the_system"]
    # every number the verdict reads, each beside a limit
    assert set(report["compared"]) == set(compare.numbers_held(report))
    assert all(len(v) == 2 and v[1] is not None
               for v in report["compared"].values())
    assert report["ok"], report["failed"]
    monkeypatch.setattr(compare, "LOGITS_RMS_TOL", 0.0)
    again = compare.judge(cfg, builder, got, ref)
    assert again["failed"] == ["logits"] and not again["ok"]
    first, (reading, limit) = next(iter(again["compared"].items()))
    assert first == "LOGITS_RMS_TOL" and reading > 0.0 and limit == 0.0
    assert [n for n, v in again["compared"].items()
            if held.fails(*v)] == ["LOGITS_RMS_TOL"]


def test_a_run_that_is_not_correct_ends_in_the_failing_number(monkeypatch,
                                                              capsys):
    """A whole run of the Keye-VL cell at its tiny size with one limit at
    zero: not `correct`, and standard error ENDS in the failing number
    beside its limit (the driver keeps that end)."""
    compare, _, _, _, _, case = _cell("compare_lm_sparse_attn_share")
    monkeypatch.setattr(compare, "LOGITS_RMS_TOL", 0.0)
    line = harness.run_cell(case.CELL, seed=SEED, seconds=2.0, trace=False,
                            rehearsal=True, override=case.TINY,
                            files=harness.Files(), out=io.StringIO())
    assert line["correct"] is False and list(line)[-1] == "compared"
    assert "logits" in line["compared"]["failed"]
    assert next(iter(line["compared"])).endswith("LOGITS_RMS_TOL")
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("[chipbench compared] ")]
    assert len(said) > len(line["compared"])
    assert said[-1].startswith("[chipbench compared] FAILS ")
    assert "LOGITS_RMS_TOL" in said[-1] and "(limit 0.0)" in said[-1]
    # failing ones first, too: right behind nothing
    assert "LOGITS_RMS_TOL" in said[0]


# ---- the early-route comparison (`smallthinker_21b_a3b`): the driver's
# check of PR 56 drew a seed on which its logits' max passed its limit, so
# the inference program's logits are held against the reference SENT where
# that program went, and the second step's biases move on the system's
# choices
EARLY = "test_early_route_cell.py"


def _early():
    from chipbench import compare_lm_early_route_share as compare

    case = harness.load_module(os.path.join(HERE, EARLY))
    _, _, cfg, traffic, builder, kind = harness.Files().cell(case.CELL)
    cfg = dict(cfg, **case.TINY["config"])
    traffic = dict(traffic, **case.TINY["traffic"])
    assert limits_study.MODULES[cfg["name"]] == compare.__name__.split(".")[1]
    rows = int(cfg["reference"]["rows"])
    tok, lab, _ = kind.token_rows(cfg, traffic, SEED, 2 * rows)
    got = compare.system_side(fluid, cfg, builder, fluid.TPUPlace(0), SEED,
                              tok[:rows], lab[:rows])
    return compare, cfg, builder, got, tok, lab, rows


def test_a_flipped_token_moves_its_neighbours_logits_and_not_the_sent_ones():
    compare, cfg, builder, got, tok, lab, rows = _early()
    ref = compare.reference_side(
        cfg, builder, got["w0"], tok, lab, [u for u, _ in got["attention"]],
        got["ids"], got["ids_eval"])
    free = [top for _, top in ref["routing"]]
    # sent where it goes by itself, the reference is the plain reference
    np.testing.assert_allclose(
        compare.reference_logits_sent(cfg, builder, got["w0"], tok[:rows],
                                      free),
        ref["logits"], rtol=1e-5, atol=1e-6)
    # a program that computes as the reference does, but whose top-k fell
    # the other way for TOKEN 0 in every layer behind the first (whose
    # choices are exact: `early_route`)
    flipped = [np.array(f) for f in free]
    for (chosen_by, top), sent in list(zip(ref["routing"], flipped))[1:]:
        order = np.argsort(-chosen_by[0])
        sent[0, -1] = order[top.shape[1]]
    program = compare.reference_logits_sent(cfg, builder, got["w0"],
                                            tok[:rows], flipped)
    _, same = compare._routing_by_layer(flipped, ref["routing"])
    assert not same[0] and same[1:].all()
    # the plain reference, over the tokens routed alike: token 1 attends
    # to two keys, token 0 among them, and reads what the flip did ...
    err = np.abs(program - ref["logits"]).max(axis=1)
    assert err[1] > 1e-4 * np.abs(ref["logits"]).max()
    assert compare._logits_errors(program, ref["logits"], same)[0] > 1e-4
    # ... the reference sent where the program went reads nothing, on
    # every token
    sent = compare.reference_logits_sent(cfg, builder, got["w0"],
                                         tok[:rows], flipped)
    assert compare._logits_errors(
        program, sent, np.ones(len(same), bool))[0] < 1e-6


def test_the_early_route_compared_names_the_failing_number_first(
        monkeypatch):
    compare, cfg, builder, got, tok, lab, rows = _early()
    ref = compare.reference_side(
        cfg, builder, got["w0"], tok, lab, [u for u, _ in got["attention"]],
        got["ids"], got["ids_eval"])
    report = compare.judge(cfg, builder, got, ref)
    assert report["reference_routed_as_the_system"]
    assert set(report["compared"]) == set(compare.numbers_held(report))
    assert all(len(v) == 2 and v[1] is not None
               for v in report["compared"].values())
    assert report["ok"], report["failed"]
    # the statistic PR 36 had is still reported
    assert len(report["logits_plain_err_max_rms"]) == 2
    monkeypatch.setattr(compare, "LOGITS_RMS_TOL", 0.0)
    again = compare.judge(cfg, builder, got, ref)
    assert again["failed"] == ["logits"] and not again["ok"]
    first, (reading, limit) = next(iter(again["compared"].items()))
    assert first == "LOGITS_RMS_TOL" and reading > 0.0 and limit == 0.0
    assert [n for n, v in again["compared"].items()
            if held.fails(*v)] == ["LOGITS_RMS_TOL"]


def test_the_second_step_s_biases_move_on_the_system_s_choices():
    """The rule is a sign of load - mean load: on choices that differ in a
    few tokens an expert near the mean moves the other way, and the
    reference's second step is then routed under another bias than the
    system's. Given the system's choices the reference moves as the system
    moved."""
    compare, cfg, builder, got, tok, lab, rows = _early()
    inputs = [u for u, _ in got["attention"]]
    plain = compare.reference_side(cfg, builder, got["w0"], tok, lab, inputs,
                                   got["ids"], got["ids_eval"])
    free = [top for _, top in plain["routing"]]
    # float32 at this size: the system chose as the reference chooses
    assert all(np.array_equal(np.sort(a, 1), np.sort(b, 1))
               for a, b in zip(got["ids"], free))
    # a system whose last layer sent every token's k-th choice to the
    # least loaded expert: loads cross the mean
    n_all = cfg["deployment"]["moe_num_primary_experts"]
    sent = [np.array(f) for f in free]
    load = np.bincount(sent[-1].ravel(), minlength=n_all)
    unused = int(np.argmin(load))
    sent[-1][:, -1] = np.where((sent[-1][:, :-1] == unused).any(axis=1),
                               sent[-1][:, -1], unused)
    moved = np.bincount(sent[-1].ravel(), minlength=n_all)
    assert (np.sign(load.mean() - load)
            != np.sign(moved.mean() - moved)).any()
    other = compare.reference_side(cfg, builder, got["w0"], tok, lab, inputs,
                                   sent, got["ids_eval"])
    assert other["second_step"][0] != plain["second_step"][0]
    # the state left as it was does not depend on whose choices
    assert other["second_step"][1] == plain["second_step"][1]
    report = compare.judge(
        cfg, builder, dict(got, ids=sent), other,
        timed={"losses": [plain["loss"], other["second_step"][0]]})
    steps = report["timed_steps"]
    assert steps["experts_the_free_choices_move_otherwise"][-1]
    assert steps["err"][1] == 0.0
