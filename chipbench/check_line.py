"""python -m chipbench.check_line <file> [<file> ...]

Holds the LAST line a run of `chipbench.run` printed to what the driver's
contract wants of it, before the driver does: the keys `correct`,
`attempted`, `failed`, `metrics`, `device`; `metrics` EXACTLY the metrics
BENCHMARK.json lists for the run's cell (`end_to_end` for an untraced run,
`per_layer` for a traced one: an entry without a `workloads` list belongs
to every cell that reports the metric it moves), each with a finite value
and the listed unit; `device` with platform, kind, count, memory_peak_bytes
and, traced, 0 < busy_s <= window_s; no share of a roofline or of a peak
(`*_roofline`, `*mfu*`, `*model_flops_util`) above 105%; a `breakdown` of
at most ten entries a list. PR 47 was refused for a traced line that lacked
one metric of its cell: run this on every chip output before handing in.

A file is the standard output of one run (its last line is read) or a
`.jsonl` of several runs (one object a run, the result line under `line`:
what PR 48's four-worker runs wrote). Exits 1 and
says what is wrong where any line fails; a line that is not `correct` is
reported too.
"""

import math
import sys

from chipbench import harness

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def listed(bench, cell, trace):
    """{name: entry} of the metrics the cell's line has to hold."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    reported = {m["name"] for m in bench["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}
    return {m["name"]: m for m in entries
            if (cell in m["workloads"] if "workloads" in m
                else not trace or m["moves"] in reported)}


def problems(line, bench, rehearsal=False):
    """What the contract would refuse of a result line: a list of
    sentences, empty where the line stands."""
    found = [f"the line lacks {k!r}" for k in KEYS if k not in line]
    if found:
        return found
    cell, device = line.get("workload"), line["device"]
    if cell not in {w["name"] for w in bench["workloads"]}:
        return [f"no workload {cell!r} in BENCHMARK.json"]
    trace = "busy_s" in device or "breakdown" in line or any(
        m["name"] in line["metrics"] for m in bench["per_layer"])
    want = listed(bench, cell, trace)
    got = dict(line["metrics"])
    absent = sorted(set(want) - set(got))
    if rehearsal:
        # the CPU writes no device plane: what reads one may be left out,
        # and the line has to say so
        said = set(line.get("metrics_missing", ()))
        found += [f"metrics lacks {n} and `metrics_missing` does not say so"
                  for n in absent if n not in said]
        # (nor has the CPU a `memory_stats()` for `*peak_hbm_gb`)
        found += [f"metrics lacks {n}, which reads no device trace "
                  f"(source {want[n]['source']})" for n in absent
                  if want[n]["source"] != "device_trace"
                  and not n.endswith("peak_hbm_gb")]
    else:
        found += [f"metrics lacks {n}" for n in absent]
    found += [f"metrics holds {n}, which the cell does not list"
              for n in sorted(set(got) - set(want))]
    for name, m in got.items():
        value = m.get("value") if isinstance(m, dict) else None
        if not isinstance(value, (int, float)) or not math.isfinite(value):
            found.append(f"{name}: no finite value ({m!r})")
            continue
        if name in want and m.get("unit") != want[name]["unit"]:
            found.append(f"{name}: unit {m.get('unit')!r}, listed "
                         f"{want[name]['unit']!r}")
        if value > 105.0 and m.get("unit") == "%" and (
                name.endswith("_roofline") or "mfu" in name
                or name.endswith("model_flops_util")):
            found.append(f"{name}: {value:.2f}% of a roofline or a peak")
    found += [f"device lacks {k!r}" for k in DEVICE_KEYS if k not in device]
    if trace and not rehearsal:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not (isinstance(busy, (int, float)) and isinstance(
                window, (int, float)) and 0 < busy <= window):
            found.append(f"device busy_s {busy!r}, window_s {window!r}: "
                         "wanted 0 < busy_s <= window_s")
    for key, entries in (line.get("breakdown") or {}).items():
        if len(entries) > 10:
            found.append(f"breakdown.{key} holds {len(entries)} entries")
    return found


def lines_of(path):
    """The result lines a file holds: the last line of a run's standard
    output, or the `line` of each object of a side-by-side `.jsonl`."""
    with open(path) as f:
        objs = harness.json_objects(f.read())
    if objs and all("rc" in o and "name" in o for o in objs):
        return [(f"{path}: {o['name']}", o.get("line"), o["rc"])
                for o in objs]
    return [(path, objs[-1] if objs else None, 0)]


def main(argv=None):
    paths = sys.argv[1:] if argv is None else argv
    if not paths:
        sys.exit(__doc__)
    bench, bad = harness.Files().bench(), 0
    for path in paths:
        for what, line, rc in lines_of(path):
            found = ["no result line (exit code %s)" % rc] \
                if not isinstance(line, dict) or rc else problems(
                    line, bench, bool(line.get("rehearsal")))
            if isinstance(line, dict) and line.get("correct") is False:
                found.append("`correct` is false: checks "
                             f"{line.get('checks')}")
            bad += bool(found)
            print(f"{what}: " + ("ok, %d metrics" % len(line["metrics"])
                                 if not found else "; ".join(found)))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
