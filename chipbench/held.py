"""How a comparison holds its numbers, spelt once for the share comparisons
(PR 56): a module's `numbers_held(report)` lists EVERY number its verdict
reads as {name: (reading, limit)}, each entry reading `reading <= limit` (a
floor is written as `1 - cos` or as its negative, an exact check as a count
against 0); its `CHECKS` says which names a check holds, by prefix; and the
three functions here turn the table into the verdict, into what a run
prints last (`compared`: the failing numbers first), and into the part of
it whose limits were set again from rows on record."""

import math


def fails(reading, limit):
    """A reading the report does not hold, or one that is not finite,
    fails."""
    return reading is None or not math.isfinite(reading) or reading > limit


def gradients(by_param, limits_of):
    """{`GRAD[key] 1 - cos` | `GRAD[key] ratio`: (reading, limit)} of the
    sampled parameters' gradients; `limits_of(key)` -> (least cosine,
    largest |norm ratio - 1| or None: reported, not compared)."""
    found = {}
    for key, v in by_param.items():
        cos_min, ratio_tol = limits_of(key)
        cos, ratio = v.get("grad_cos"), v.get("grad_norm_ratio")
        found[f"GRAD[{key}] 1 - cos"] = (
            None if cos is None else 1.0 - cos, 1.0 - cos_min)
        if ratio_tol is not None:
            found[f"GRAD[{key}] ratio"] = (
                None if ratio is None else abs(ratio - 1.0), ratio_tol)
    return found


def product_rows(report):
    """{the exact check's name: (the expert layers whose down product's
    non-zero rows, `RowsHeld` and the choices on the held experts are not
    one number, and the layers not reported at all; 0)}."""
    rows = report["product_rows_written_held_chosen"]
    return {"product_rows not written = held = chosen": (
        sum(not w == h == c for w, h, c in rows)
        + abs(len(rows) - len(report["routing_inference"])), 0)}


def _of(numbers, prefixes, without):
    return [n for n in numbers if n.startswith(tuple(prefixes))
            and not (without and n.startswith(tuple(without)))]


def failed_checks(numbers, checks, without=()):
    """The checks of `checks` ({check: name prefixes}) one of whose numbers
    fails, sorted; `without`: name prefixes left out (what a record does
    not hold, or holds of another statistic), a check ALL of whose numbers
    are left out with them. A check that has no number at all fails: it
    holds nothing."""
    failed = []
    for check, prefixes in checks.items():
        names = _of(numbers, prefixes, without)
        if not names and _of(numbers, prefixes, ()):
            continue
        if not names or any(fails(*numbers[n]) for n in names):
            failed.append(check)
    return sorted(failed)


def compared(numbers):
    """{name: [reading, limit]}, the failing numbers first (each group in
    the table's own order): what the harness prints last and puts last
    into the result's line."""
    order = sorted(numbers, key=lambda n: not fails(*numbers[n]))
    return {n: [numbers[n][0], numbers[n][1]] for n in order}


def set_again(numbers, names):
    """The part of the table `limits_study` lays over the rows on record."""
    return {n: numbers[n] for n in names if n in numbers}
