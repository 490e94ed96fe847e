"""What a scope of a traced window is made of, operation by operation: the
device events of `python -m chipbench.run --trace 1 --dump <dir>` whose
scope key (chipbench/scopes.py) holds one of the named components, by the
event's own name, in ms a step. PERF.md section 7 row 17 (PR 31) is this
for `moe_ffn moe_ffn_grad` in the two language-model cells.

    python tools/scope_parts.py <dir> --steps 40 moe_ffn moe_ffn_grad
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


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("dump")
    ap.add_argument("names", nargs="+")
    ap.add_argument("--steps", type=int, required=True)
    a = ap.parse_args()
    for row in parts(a.dump, a.names, a.steps):
        print(json.dumps(row))
