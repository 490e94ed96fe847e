"""The readings the comparisons' limits rest on, as a committed file.

`chipbench/data/limits_study.json` holds, per comparison module, one row a
chip run: the seed, the variant (`stated`, or the name of the plant of the
`lower_precision_lm_*` study), where the run's output lay, and THE NUMBERS
THE MODULE'S `verdict` READS, cut out of the run's report. The test
`chipbench/tests/test_limits_study.py` replays every row against the
limits as the modules hold them today: a `stated` row passes, a plant row
fails the check named for it, each limit set again keeps the factor `m`
from the readings on either side. So a session that sets a limit starts
from rows, not from comments, and a limit moved without its readings
fails a test.

    python -m chipbench.limits_study add <file> [<file> ...]
    python -m chipbench.limits_study table
    python -m chipbench.limits_study census <routed 0|1> <source prefix>
        [...] [--limits-of <another checkout's chipbench/>]

`table` prints every limit set again beside the readings on either side.
`census` prints, for the `stated` rows of the named sources (the outputs of
`python -m chipbench.census`, PR 56) read against the reference routed as
the system (1) or the plain one (0): on how many seeds each check fails,
and every number's worst reading beside its limit and its room; with
`--limits-of` under the limits another checkout holds (the parent's).
`add` reads outputs of chip runs (`chiprun_out/` is not committed) and adds
their rows: a `.jsonl` of several runs of a cell (one object a run, the
result line under `line` and the comparison's report under `reference`:
PR 47's and PR 48's four-worker runs), the `.jsonl` / `.out` of a
`lower_precision_lm_*` study (`seed`, `variant`, `report`; a report with
`planted_in: reference` holds the gradients' numbers alone), or the
standard output of
`chipbench.run` (its second line holds the report, its last the seed). A
row already there (same module, seed, variant, source) is replaced.
"""

import json
import os
import sys

from chipbench import harness

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "limits_study.json")
# which module judges a cell's report, and the numbers its verdict reads
MODULES = {"qwen3_next_80b_a3b": "compare_lm_delta_share",
           "xing4_0_29b_a4b": "compare_lm_share",
           "keye_vl_2_0_30b_a3b": "compare_lm_sparse_attn_share",
           "smallthinker_21b_a3b": "compare_lm_early_route_share"}
KEPT = {
    "compare_lm_delta_share": (
        "operator_branch_err_max_rms", "delta_rule_op_err_max_rms",
        "delta_rule_final_state_err_max_rms",
        "delta_rule_in_float32_op_err_max_rms",
        "delta_rule_in_float32_final_state_err_max_rms",
        "delta_rule_in_float32_err_rms_by_part", "conv_op_err_max_rms",
        "operator_input_err_rms_rowscale", "timed_steps",
        "product_rows_written_held_chosen", "routing", "routing_inference",
        "tokens_routed_alike_everywhere", "logits_err_max",
        "logits_err_rms", "train_loss_err", "global_grad_norm_err",
        "clip_scale_err", "by_param", "routing_judged_where_sent"),
    "compare_lm_share": (
        "first_mixer_err", "sinkhorn_column_err", "first_norm_scale_err",
        "product_rows_written_held_chosen", "router_bias_moved_by_the_rule",
        "routing", "routing_inference", "tokens_routed_alike_everywhere",
        "logits_err_max", "logits_err_rms", "mtp_logits_err_max",
        "mtp_logits_err_rms", "train_loss_err", "cross_entropy_err",
        "mtp_cross_entropy_err", "global_grad_norm_err", "clip_scale_err",
        "by_param", "phi_res_pooled", "alpha_pooled",
        "routing_judged_where_sent"),
    "compare_lm_sparse_attn_share": (
        "first_hand", "gradient_sets", "timed_steps",
        "product_rows_written_held_chosen", "routing", "routing_inference",
        "tokens_routed_alike_everywhere", "logits_err_max",
        "logits_err_rms", "train_loss_err", "cross_entropy_err",
        "indexer_loss_err", "global_grad_norm_err", "clip_scale_err",
        "by_param", "routing_judged_where_sent"),
    "compare_lm_early_route_share": (
        "attention_branch_err_max_rms",
        "window_branch_err_rms_by_reference_window",
        "attention_input_err_rms_rowscale", "timed_steps",
        "layer_0_choices_same_share_train_inference",
        "product_rows_written_held_chosen", "router_bias_moved_by_the_rule",
        "routing", "routing_inference", "tokens_routed_alike_everywhere",
        "logits_err_max", "logits_err_rms", "logits_plain_err_max_rms",
        "train_loss_err", "global_grad_norm_err", "clip_scale_err",
        "by_param"),
}
ROUTING_KEPT = ("tokens", "flipped_share", "worst_gap_in_spreads", "ok",
                "worst_gap")
PARAM_KEPT = ("grad_cos", "grad_norm_ratio", "update_err")
# numbers a report saved before PR 48 does not hold: a row says which
# (`lacks`), and the replay judges it on what it does hold
def _routing_not_where_sent(n, timed):
    """A routed row of PR 56's first two calls: each layer's routing was
    still judged on the tokens that went the reference's FREE way so far,
    not where the reference was sent (the inference program's choices then
    read margins of 0.05 - 0.23 in the Xing cell: tokens that arrived as
    other tokens)."""
    return bool(n.get("reference_routed_as_the_system")
                and not n.get("routing_judged_where_sent"))


LACKABLE = {
    "compare_lm_delta_share": {
        "timed_last": lambda n, timed: timed and "err_second_build_last"
        not in (n.get("timed_steps") or {}),
        "routing_where_sent": _routing_not_where_sent},
    "compare_lm_share": {
        "phi_res_pooled": lambda n, timed: "phi_res_pooled" not in n,
        "alpha_pooled": lambda n, timed: "alpha_pooled" not in n,
        "routing_where_sent": _routing_not_where_sent},
    # there the logits are the training step's own, read over the tokens
    # `routing` leaves: with it they went over every token
    "compare_lm_sparse_attn_share": {
        "routing_where_sent": _routing_not_where_sent,
        "logits_where_sent": _routing_not_where_sent},
    "compare_lm_early_route_share": {}}
# what a row that `lacks` a number leaves out of the verdict: the names'
# prefixes in the module's `numbers_held`
LACKS_NAMES = {"timed_last": ("TIMED_TWIN_LAST_TOL",),
               "routing_where_sent": ("ROUTING",),
               "logits_where_sent": ("LOGITS",),
               "phi_res_pooled": ("POOLED_LIMITS[phi_res]",),
               "alpha_pooled": ("POOLED_LIMITS[alpha]",)}


def numbers_of(module, report):
    """What `module.verdict` reads of a `judge` report, and no more."""
    kept = {k: report[k] for k in KEPT[module] + (
        "reference_routed_as_the_system",) if k in report}
    for key in ("routing", "routing_inference"):
        if key in kept:
            kept[key] = [{k: r[k] for k in ROUTING_KEPT if k in r}
                         for r in kept[key]]
    kept["by_param"] = {name: {k: v[k] for k in PARAM_KEPT if k in v}
                        for name, v in kept["by_param"].items()}
    for kind in ("phi_res_pooled", "alpha_pooled"):
        if kind in kept:
            kept[kind] = {k: v for k, v in kept[kind].items()
                          if k != "by_mixer"}
    return kept


def _module_of(report):
    return MODULES.get(str(report.get("config")))


def rows_from(path):
    """The rows a file of chip output holds: [(module, row)]."""
    found = []
    with open(path) as f:
        objs = harness.json_objects(f.read())
    source = os.path.relpath(os.path.abspath(path), os.path.dirname(
        os.path.dirname(os.path.dirname(PATH))))

    def add(seed, variant, report, timed):
        module = _module_of(report or {})
        if module is None:
            return
        numbers = numbers_of(module, report)
        row = {"seed": int(seed), "variant": variant, "source": source,
               "timed": bool(timed),
               # whether the reference went where the system's experts
               # went (PR 56): a row read against the plain reference
               # says nothing of the limits of what `FOLLOWS_ROUTING`
               "routed": bool(report.get("reference_routed_as_the_system")),
               "lacks": sorted(k for k, f in LACKABLE[module].items()
                               if f(numbers, timed)),
               "numbers": numbers}
        if report.get("planted_in"):
            row["planted_in"] = report["planted_in"]
        found.append((module, row))

    last_report = None
    for o in objs:
        if "variant" in o and "report" in o:            # a study's line
            add(o["seed"], o["variant"], o["report"], False)
        elif "line" in o and o.get("reference"):        # several runs
            add(o["line"]["seed"], "stated", o["reference"], True)
        elif "reference" in o and "chipbench_detail" in o:
            last_report = o["reference"]                # chipbench.run
        elif "correct" in o and "seed" in o and last_report:
            add(o["seed"], "stated", last_report, True)
            last_report = None
    return found


def _timed(key, index=None):
    def read(n):
        v = (n.get("timed_steps") or {}).get(key)
        return v if index is None or v is None else v[index]
    return read


# THE LIMITS SET AGAIN IN PR 48 are named, read and held by each module's
# `numbers_set_again`; here only WHICH PLANTS EACH ALONE IS THERE TO CATCH
# (a limit not named here has none on record: it is coarse, held from
# below only) and, for a plant no study runs, the reading of a `stated`
# row that stands in for it.
_EXPERT_COS = tuple(f"GRAD[expert_{m}] 1 - cos" for m in ("gate", "up",
                                                            "down"))
PLANTS = {
    # PR 56, on the routed reference (`chiprun_out/pr56/plants_*.jsonl`):
    # the indexer reading u with its gradient moves the first router's
    "compare_lm_sparse_attn_share": {
        "GRAD[router] 1 - cos": ("indexer_reads_u",),
        "GRAD[router] ratio": ("indexer_reads_u",)},
    "compare_lm_delta_share": {
        # PR 56, on the routed reference: a bf16 router's own gradients
        # and the held expert's behind it
        **{name: ("router_bf16",) for name in (
            "GRAD[router] 1 - cos", "GRAD[router_attn] 1 - cos")
           + _EXPERT_COS},
        "DELTA_F32_OP_HEAD_MEDIAN_TOL": ("state_bf16", "g_bf16"),
        "DELTA_F32_STATE_HEAD_MEDIAN_TOL": ("state_bf16", "g_bf16"),
        "DELTA_F32_OP_HEAD_QUARTILE_TOL": ("state_bf16", "g_bf16"),
        "DELTA_F32_STATE_HEAD_QUARTILE_TOL": ("state_bf16", "g_bf16"),
    },
    # faults of the mixers' backward, planted in the reference
    # (`lower_precision_lm_share --reference-faults`)
    "compare_lm_share": {
        # PR 56, on the routed reference: a bf16 router
        **{name: ("router",) for name in ("GRAD[router] 1 - cos",)
           + _EXPERT_COS},
        "POOLED_LIMITS[phi_res] 1 - cos": ("res_grad_transposed",),
        "POOLED_LIMITS[phi_res] ratio": ("res_grad_transposed",),
        "POOLED_LIMITS[alpha] 1 - cos": ("pre_grad_dropped",
                                         "post_grad_dropped"),
        "POOLED_LIMITS[alpha] ratio": ("post_grad_dropped",),
    },
}
STAND_INS = {"compare_lm_delta_share": {
    "TIMED_TWIN_LAST_TOL": _timed("err_last_had_nothing_carried")}}
# the step-1 statistic PR 48 replaced: kept among the rows' numbers
# (`timed_steps.err_second_build[1]` | `err_had_nothing_carried`), so that
# the readings that made it unusable stay on record
REPLACED = {"compare_lm_delta_share": [
    ("TIMED_TWIN_TOL at step 1 (replaced)", _timed("err_second_build", 1),
     _timed("err_had_nothing_carried"))]}


def table(study=None):
    """[(module, limit's name, its value, the stated rows' worst reading,
    the seeds that hold it, the least plant reading or None, the plants'
    rows)] of every limit set again."""
    import importlib

    study = study or load()
    found = []
    for module in MODULES.values():
        mod = importlib.import_module("chipbench." + module)
        rows = study["rows"].get(module, [])
        read = [(r, mod.numbers_set_again(r["numbers"])) for r in rows]
        names = {n: lim for _, again in read for n, (_, lim) in again.items()}

        def says(row, name):
            """Whether a row's reading of `name` is the statistic the limit
            holds: what follows the reference's routing, only off a
            reference routed as the system (or planted in the reference
            itself, which routes as itself)."""
            lacked = tuple(p for k in row["lacks"] for p in LACKS_NAMES[k])
            return not (lacked and name.startswith(lacked)) and bool(
                row.get("routed") or row.get("planted_in") == "reference"
                or not name.startswith(mod.FOLLOWS_ROUTING))

        for name, limit in names.items():
            plants = PLANTS.get(module, {}).get(name, ())
            stand_in = STAND_INS.get(module, {}).get(name)
            stated = [(r["seed"], again[name][0]) for r, again in read
                      if r["variant"] == "stated" and says(r, name)
                      and again.get(name, (None,))[0] is not None]
            planted = [again[name][0] for r, again in read
                       if r["variant"] in plants and says(r, name)
                       and again.get(name, (None,))[0] is not None]
            if stand_in is not None:
                planted += [v for v in (stand_in(r["numbers"]) for r in rows
                                        if r["variant"] == "stated")
                            if v is not None]
            found.append((module, name, limit,
                          max(v for _, v in stated) if stated else None,
                          len({s for s, _ in stated}),
                          min(planted) if planted else None, len(planted)))
    return found


def load():
    with open(PATH) as f:
        return json.load(f)


def _module_with_limits_of(module, tree=None):
    """The comparison's module as it stands, its limits (the upper-case
    constants) those of the same module under `tree` (another checkout's
    `chipbench/`: the parent's), or its own."""
    import importlib
    import importlib.util

    mod = importlib.import_module("chipbench." + module)
    if tree is None:
        return mod
    spec = importlib.util.spec_from_file_location(
        f"_limits_of_{module}", os.path.join(tree, module + ".py"))
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    spec = importlib.util.spec_from_file_location(
        f"_{module}_with_other_limits", mod.__file__)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    for name in dir(other):
        if name.isupper() and hasattr(copy, name) and name not in (
                "CHECKS", "TIMED_CHECKS", "SET_AGAIN", "FOLLOWS_ROUTING"):
            setattr(copy, name, getattr(other, name))
    return copy


def census(sources, routed, tree=None, study=None):
    """{module: (seeds, {check: [seeds that fail it]}, {number's name:
    (worst reading, its seed, limit)})} over the `stated` rows whose source
    starts with one of `sources` and whose reference was routed as
    `routed` says, under the limits of `tree` (`_module_with_limits_of`).
    The timed checks are a whole run's: rows that are not `timed` are
    judged without them."""
    from chipbench import held

    study = study or load()
    found = {}
    for module, rows in study["rows"].items():
        mod = _module_with_limits_of(module, tree)
        rows = [r for r in rows if r["variant"] == "stated"
                and bool(r.get("routed")) == routed
                and r["source"].startswith(tuple(sources))]
        fails, worst = {}, {}
        for r in rows:
            numbers = mod.numbers_held(r["numbers"], timed=r["timed"])
            checks = dict(mod.CHECKS, **(getattr(mod, "TIMED_CHECKS", {})
                                         if r["timed"] else {}))
            lacked = tuple(p for k in r["lacks"] for p in LACKS_NAMES[k])
            for check in held.failed_checks(numbers, checks, lacked):
                fails.setdefault(check, []).append(r["seed"])
            for name, (reading, limit) in numbers.items():
                if lacked and name.startswith(lacked):
                    continue
                if reading is not None and (
                        name not in worst or reading > worst[name][0]):
                    worst[name] = (reading, r["seed"], limit)
        found[module] = (sorted({r["seed"] for r in rows}), fails, worst)
    return found


def add_files(paths):
    from chipbench.compare_lm_delta_share import M

    study = load() if os.path.exists(PATH) else {"rows": {}}
    study["m"] = M
    for path in paths:
        for module, row in rows_from(path):
            rows = study["rows"].setdefault(module, [])
            rows[:] = [r for r in rows if (
                r["seed"], r["variant"], r["source"],
                bool(r.get("routed"))) != (
                    row["seed"], row["variant"], row["source"],
                    row["routed"])]
            rows.append(row)
            print(f"{module}: seed {row['seed']} {row['variant']} "
                  f"({row['source']})", file=sys.stderr)
    # one row a line: a row added or replaced is one line of a diff
    with open(PATH, "w") as f:
        f.write('{\n "m": %s,\n "rows": {\n' % json.dumps(study["m"]))
        for i, module in enumerate(sorted(study["rows"])):
            f.write('  %s: [\n' % json.dumps(module) + ",\n".join(
                "   " + json.dumps(row, sort_keys=True)
                for row in study["rows"][module]) + "\n  ]"
                + ("," if i + 1 < len(study["rows"]) else "") + "\n")
        f.write(" }\n}\n")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "add":
        add_files(sys.argv[2:])
    elif len(sys.argv) >= 4 and sys.argv[1] == "census":
        # census <routed 0|1> <source prefix> [...] [--limits-of <dir>]
        args, tree = sys.argv[3:], None
        if "--limits-of" in args:
            tree = args[args.index("--limits-of") + 1]
            args = args[:args.index("--limits-of")]
        for module, (seeds, fails, worst) in census(
                args, bool(int(sys.argv[2])), tree).items():
            if not seeds:
                continue
            print(f"{module}: {len(seeds)} seeds; failing: "
                  + (json.dumps({k: len(v) for k, v in fails.items()})
                     if fails else "none"))
            for check, bad in sorted(fails.items()):
                print(f"  {check}: {len(bad)} of {len(seeds)}: {bad}")
            for name, (reading, seed, limit) in worst.items():
                room = limit / reading if reading else float("inf")
                print(f"  {name}: worst {reading:.4g} (seed {seed}) | limit "
                      f"{limit:.4g} (x{room:.2f})")
    elif sys.argv[1:] == ["table"]:
        for mod, name, limit, worst, n, least, n_planted in table():
            print(f"{mod} {name}: limit {limit:.4g} | stated worst "
                  f"{worst if worst is None else format(worst, '.4g')} "
                  f"over {n} seeds"
                  + (f" (x{limit / worst:.2f})" if worst else "")
                  + (f" | least plant {least:.4g} of {n_planted} "
                     f"(x{least / limit:.2f})" if least else " | coarse"))
    else:
        sys.exit(__doc__)
