"""The convolutions of an image cell's traced window, one row a layer of
the configuration's plan (`reference.layer_plan(cfg)`): the device time
the trace files under the layer's scope, forward, backward and in the
weight's update, beside the least time `chipbench/costs.py` gives the
same layer, and their ratio.

The models write the scopes (`paddle_tpu/models/resnet.py`,
`se_resnext.py`: `stem`, `stage<s>/block<b>/<role>`, `head`, one scope a
whole conv + batch norm + activation), the executor lowers every op under
them, and an operation of the trace carries the scope of the op its
fusion was named after (`chipbench/scopes.py`): a fusion around a
convolution carries the CONVOLUTION's op_name whatever is fused in front
of it or behind it (a v5e compile of the ResNet-50 step: every layer has
one `fusion` under `conv2d`, one `fusion` and one `*_subtract_fusion`
under `conv2d_grad`), any other fusion its root's. So the columns are
what XLA made of the step, not the program's ops one for one:

  forward   keys `<label>/<op type>`: the convolution with the batch
            norm's statistics in its epilogue.
  backward  keys `<label>/<op type>_grad` and `sum(<label>)`: the gradient
            to the input with the batch norm's reductions around it, and
            the gradient to the filter where no update is fused behind it.
  update    the gradient to the filter with the parameter's update fused
            behind it: under a gradient op's key, an operation whose own
            name ends `subtract_fusion` (XLA names a fusion after its last
            instructions, and Momentum's are `param - lr * velocity`;
            the ledger's `convolution_fusion:multiply_subtract_fusion`);
            and what lies under `optimizer/<type>(<label, its / as .>)`.

The three columns hold the seconds of operations XLA files under a
`convolution` category, the ones `conv_roofline` counts; `other` is the
rest under the same scope (normalisation passes, standalone reductions,
copies). A row can read above 100%: the least time prices every pass at
the HBM's peak, and XLA keeps activations of up to ~50 MB in the chip's
fast memory (layout `S(1)`; the transfers are the `copy-start` /
`copy-done` pairs of `[xla]copy-done`), and it counts the whole input of
a stride-2 1x1 convolution, of which a quarter is read. Everything
the plan does not name has a row of its own: a block's residual add, the
optimizer's updates of parameters outside every layer, bare op types (the
input's cast and scale, the loss), XLA's own operations (`[xla]...`).

    python -m chipbench.run --workload <cell> --trace 1 --dump <dir>
    python tools/scope_parts.py --convs <cell> <dir> --steps <n>
"""

import re

from chipbench import costs, scopes, xplane

ROLES = {False: ("conv1", "conv2", "conv3"),
         True: ("conv0", "conv1", "conv2", "se", "se")}
_OWNED = re.compile(r"^([^(]+)\((.*)\)$")
UPDATE = "optimizer"
UPDATE_ROOT = "subtract_fusion"
# as `xplane.is_collective` tells them
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective",
               "all-to-all")


# ---------------------------------------------------------------- reduction
def reduce_planes(planes, host=None):
    """{"window_s", "busy_s", "ops": [[scope key, hlo category, the
    operation's name without its numbering, seconds, events]]} over the
    window, chip 0, each operation's own time. `host` as
    `scopes.reduce_planes` takes it. None without a device plane or the
    markers."""
    devs = xplane.device_planes(planes)
    ln = devs[0].line(xplane.OPS_LINE) if devs else None
    if ln is None or not ln.events:
        return None
    lo, hi = ln.events[0].start_ps, max(e.end_ps for e in ln.events)
    if host is not None:
        clock = xplane.marker_offset_ps(planes, host["syncs"])
        if clock is None:
            return None
        lo, hi = (int(v * 1e12) - clock for v in host["window"])
    events = [e for e in ln.events if e.end_ps > lo and e.start_ps < hi
              and xplane.MARKER not in e.name]
    found = {}
    for e, ps in zip(events, xplane.self_times(events)):
        if xplane.op_code(e.name) in xplane.CONTAINERS:
            continue
        key = (scopes.event_scope(e), str(
            e.stats.get("hlo_category") or xplane.op_code(e.name)),
            xplane.op_base(e.name))
        cell = found.setdefault(key, [0, 0])
        cell[0] += ps
        cell[1] += 1
    busy = xplane.total(xplane.clip(
        xplane.union((e.start_ps, e.end_ps) for e in events), lo, hi))
    return {"window_s": (hi - lo) * 1e-12, "busy_s": busy * 1e-12,
            "ops": [[*key, ps * 1e-12, n]
                    for key, (ps, n) in sorted(found.items())]}


def reduce_file(path, host=None):
    return reduce_planes(xplane.load(path), host=host)


# ------------------------------------------------------------------- labels
def plan_labels(cfg, plan):
    """The scope of every plan entry, in plan order: `stem`, then a block's
    roles as the model names them (an SE block's two fully connected
    layers share `se`; `shortcut` where the block projects), `head`."""
    se = "se_reduction" in cfg
    out, cin = ["stem"], cfg["stem_width"]
    for s, (count, width) in enumerate(zip(cfg["blocks"], cfg["widths"]), 1):
        for b in range(count):
            cout = width * cfg["expansion"]
            out += [f"stage{s}/block{b}/{r}" for r in ROLES[se]]
            if cin != cout:
                out.append(f"stage{s}/block{b}/shortcut")
            cin = cout
    out.append("head")
    if len(out) != len(plan):
        raise ValueError(f"conv_table: {len(plan)} plan entries, "
                         f"{len(out)} scopes")
    return out


def file_under(key, name, labels):
    """(row, column) of the operations called `name` under one scope key:
    the row is a plan label where the key lies under one, else what the
    key itself names."""
    parts = scopes._split(key)
    column = "forward"
    if any(p.split("(")[0].endswith("_grad") for p in parts):
        column = "update" if name.endswith(UPDATE_ROOT) else "backward"
    owned = next((m for m in map(_OWNED.match, parts) if m), None)
    if owned:
        column = "update" if parts[0] == UPDATE else "backward"
        parts = owned.group(2).split(".") + [owned.group(1)]
    elif parts[0] == UPDATE:
        return UPDATE, "update"
    for n in (3, 1):
        if "/".join(parts[:n]) in labels and len(parts) > n:
            return "/".join(parts[:n]), column
    if parts[0].startswith("stage") and len(parts) > 2:
        return "/".join(parts[:2]), column       # the residual add
    return parts[0], column


# -------------------------------------------------------------------- table
def table(red, cfg, plan, steps, batch, peaks):
    """-> (rows, summary). A row: `row`, for a plan label its `layers` (the
    plan entries' cin, cout, k, stride, groups, size), ms a step `forward`,
    `backward`, `update` (convolution operations) and `other` (the rest),
    `least_ms`, `over_ms` = the convolution operations' time over the
    least, and `roofline` = least / measured convolution time, %.
    Plan rows in plan order, then the others by time."""
    labels = plan_labels(cfg, plan)
    known = set(labels)
    per_ms = 1e3 / steps
    rows = {}
    for key, category, name, seconds, _n in red["ops"]:
        row, column = file_under(key, name, known)
        cell = rows.setdefault(row, dict.fromkeys(
            ("forward", "backward", "update", "other"), 0.0))
        cell[column if "convolution" in category else "other"] += \
            seconds * per_ms
    out = []
    for label in dict.fromkeys(labels):
        layers = [c for c, name in zip(plan, labels) if name == label]
        least = costs.step_least_seconds(layers, batch, True, peaks)[0] * 1e3
        cell = rows.pop(label, None) or dict.fromkeys(
            ("forward", "backward", "update", "other"), 0.0)
        conv = cell["forward"] + cell["backward"] + cell["update"]
        out.append(dict(
            cell, row=label, least_ms=least, over_ms=conv - least,
            roofline=100.0 * least / conv if conv else None,
            layers=[[c["cin"], c["cout"], c["k"], c["stride"], c["groups"],
                     c["h_out"]] for c in layers]))
    on_plan = sum(r["forward"] + r["backward"] + r["update"] for r in out)
    least = sum(r["least_ms"] for r in out)
    rest = sorted(rows.items(), key=lambda kv: -sum(kv[1].values()))
    out += [dict(cell, row=label) for label, cell in rest]
    conv_all = on_plan + sum(
        c["forward"] + c["backward"] + c["update"] for _, c in rest)
    return out, {
        "steps": steps, "busy_ms": red["busy_s"] * per_ms,
        "convolution_ms": conv_all, "on_plan_rows_ms": on_plan,
        "on_plan_rows_share": 100.0 * on_plan / conv_all if conv_all
        else None,
        "least_ms": least,
        "rows_roofline": 100.0 * least / on_plan if on_plan else None,
        "conv_roofline": 100.0 * least / conv_all if conv_all else None}


def by_shape(rows):
    """The plan's rows with the rows of one stage, role and shape merged
    (`stage3/block1-5/conv2`): `blocks` of them, the columns their mean,
    `over_ms` their sum (what the shape costs a step over its least)."""
    merged = {}
    for r in rows:
        if "layers" not in r:
            continue
        stage, _, role = r["row"].partition("/block")
        key = (stage, role.partition("/")[2], str(r["layers"]))
        merged.setdefault(key, []).append(r)
    out = []
    for (stage, role, _), group in merged.items():
        blocks = [r["row"].split("/")[1][len("block"):] for r in group
                  if role]
        label = stage if not role else "%s/block%s/%s" % (
            stage, blocks[0] if len(blocks) == 1
            else f"{blocks[0]}-{blocks[-1]}", role)
        mean = {c: sum(r[c] for r in group) / len(group)
                for c in ("forward", "backward", "update", "other",
                          "least_ms")}
        conv = mean["forward"] + mean["backward"] + mean["update"]
        out.append(dict(mean, row=label, blocks=len(group),
                        layers=group[0]["layers"],
                        over_ms=sum(r["over_ms"] for r in group),
                        roofline=100.0 * mean["least_ms"] / conv
                        if conv else None))
    return out


def collectives_by_op(red, steps):
    """ms a step chip 0 spent in the window's collectives (on this runtime
    they are operations of the ops line: time in one is time no other
    operation runs), by category and by the Fluid op type their scope key
    ends in: a data-parallel step's all-reduces of batch-norm statistics
    against those of gradients."""
    found = {}
    for key, category, name, seconds, n in red["ops"]:
        if not any(w in category + " " + name for w in COLLECTIVES):
            continue
        op = scopes._split(key)[-1].split("(")[0]
        cell = found.setdefault((category, op), [0.0, 0])
        cell[0] += seconds * 1e3 / steps
        cell[1] += n / steps
    return [{"category": c, "op": op, "ms_a_step": ms, "events_a_step": n}
            for (c, op), (ms, n) in sorted(found.items(),
                                           key=lambda kv: -kv[1][0])]


def format_rows(rows, summary=None):
    lines = ["row | blocks | cin,cout,k,stride,groups,size | forward | "
             "backward | update | other | least | over | roofline %"]
    for r in rows:
        shape = "; ".join(",".join(map(str, c)) for c in r.get("layers", ()))
        lines.append(
            "%s | %s | %s | %.3f | %.3f | %.3f | %.3f | %s | %s | %s" % (
                r["row"], r.get("blocks", ""), shape, r["forward"],
                r["backward"], r["update"], r["other"],
                "%.3f" % r["least_ms"] if "least_ms" in r else "",
                "%.3f" % r["over_ms"] if "over_ms" in r else "",
                "%.1f" % r["roofline"] if r.get("roofline") else ""))
    if summary:
        lines.append("summary " + " ".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in summary.items()))
    return "\n".join(lines)
