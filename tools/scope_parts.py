"""What a scope of a traced window is made of, operation by operation: the
device events of `python -m chipbench.run --trace 1 --dump <dir>` whose
scope key (chipbench/scopes.py) holds one of the named components, by the
event's own name, in ms a step. PERF.md section 7 row 17 (PR 31) is this
for `moe_ffn moe_ffn_grad` in the two language-model cells.

    python tools/scope_parts.py <dir> --steps 40 moe_ffn moe_ffn_grad

With `--convs <cell>` it prints the image cell's convolutions instead, one
row a layer of the configuration's plan (`chipbench/conv_table.py`), and
what the window's collectives (a data-parallel step's all-reduces) took,
by Fluid op; `--out` keeps the
reduction and the rows as JSON.

    python tools/scope_parts.py <dir> --steps 40 --convs resnet50_train_resident
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parts(dump, names, steps):
    from chipbench import scopes, xplane

    planes = xplane.load(os.path.join(dump, "window.xplane.pb"))
    with open(os.path.join(dump, "window.host.json")) as f:
        host = json.load(f)
    line = xplane.device_planes(planes)[0].line(xplane.OPS_LINE)
    clock = xplane.marker_offset_ps(planes, host["syncs"])
    lo, hi = (int(v * 1e12) - clock for v in host["window"])
    events = [e for e in line.events if e.end_ps > lo and e.start_ps < hi
              and xplane.MARKER not in e.name]
    rows = {}
    for e, self_ps in zip(events, xplane.self_times(events)):
        key = scopes.event_scope(e)
        if scopes.in_scope(key, *names):
            row = rows.setdefault((key, xplane.stable_name(e)), [0, 0])
            row[0] += self_ps
            row[1] += 1
    return [{"scope": k, "op": op, "ms_a_step": ps * 1e-9 / steps,
             "events_a_step": n / steps}
            for (k, op), (ps, n) in sorted(rows.items(),
                                           key=lambda kv: -kv[1][0])]


def convs(dump, name, steps, device_kind, out=None):
    from chipbench import conv_table, costs, harness

    _, cell, cfg, _, builder, _ = harness.Files().cell(name)
    with open(os.path.join(dump, "window.host.json")) as f:
        red = conv_table.reduce_file(
            os.path.join(dump, "window.xplane.pb"), host=json.load(f))
    rows, summary = conv_table.table(
        red, cfg, builder.reference.layer_plan(cfg), steps,
        int(cfg["batch_per_chip"]), costs.peaks_for(device_kind))
    merged = conv_table.by_shape(rows)
    collectives = conv_table.collectives_by_op(red, steps)
    print(conv_table.format_rows(rows, summary))
    print("\nby stage, role and shape:")
    print(conv_table.format_rows(merged))
    for row in collectives:
        print(json.dumps(row))
    if out:
        with open(out, "w") as f:
            json.dump({"workload": cell["name"], "reduction": red,
                       "rows": rows, "by_shape": merged,
                       "summary": summary, "collectives": collectives}, f)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("dump")
    ap.add_argument("names", nargs="*")
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--convs", metavar="CELL", default=None)
    ap.add_argument("--device-kind", default="TPU v5 lite")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    if a.convs:
        convs(a.dump, a.convs, a.steps, a.device_kind, a.out)
    for row in parts(a.dump, a.names, a.steps) if a.names else ():
        print(json.dumps(row))
