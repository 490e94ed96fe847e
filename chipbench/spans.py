"""The program's own spans (`paddle_tpu.trace`) in a traced window, as the
readers of `chipbench/layer_metrics/` see them.

A traced run that reads the program's spans puts three keys into what the
readers are handed:

    obs["program_spans"]          trace.snapshot()'s list, taken at the
                                  window's close (the recorder was reset
                                  at its opening)
    obs["program_spans_dropped"]  spans the rings overwrote; not 0 means
                                  the list has holes and no reader answers
    obs["program_spans_window"]   [t0, t1] on `perf_counter`, the clock of
                                  every span

Where the keys are absent (a program without the spans, a run that did not
ask for them) every function here returns None and nothing raises.
"""

import statistics


def taken(obs):
    """(spans, (t0, t1)) of the window, or None."""
    spans = obs.get("program_spans")
    window = obs.get("program_spans_window")
    if not spans or not window or obs.get("program_spans_dropped"):
        return None
    return spans, (float(window[0]), float(window[1]))


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def clipped_s(s, window):
    """Seconds of span `s` inside the window."""
    return max(0.0, min(s["t1"], window[1]) - max(s["t0"], window[0]))


def steps(spans):
    """[(step span, {phase: seconds})] of every executor step, a phase's
    intervals summed (a miss has two `compile` stretches)."""
    by_parent = {}
    for s in spans:
        if s["kind"] == "phase":
            by_parent.setdefault(s["parent"], []).append(s)
    out = []
    for st in spans:
        if st["kind"] != "step":
            continue
        phases = {}
        for p in by_parent.get(st["span"], ()):
            phases[p["name"]] = phases.get(p["name"], 0.0) \
                + p["t1"] - p["t0"]
        out.append((st, phases))
    return out


def median_phase_ms(obs, phases):
    """Median over the window's steps of the summed `phases`, in ms."""
    got = taken(obs)
    if got is None:
        return None
    sums = [sum(ph.get(n, 0.0) for n in phases)
            for st, ph in steps(got[0])
            if got[1][0] <= st["t0"] and st["t1"] <= got[1][1]]
    return statistics.median(sums) * 1000.0 if sums else None


def median_ms(obs, name):
    """Median duration of the window's spans called `name`, in ms."""
    got = taken(obs)
    if got is None:
        return None
    d = [s["t1"] - s["t0"] for s in named(got[0], name)
         if s["t0"] >= got[1][0]]
    return statistics.median(d) * 1000.0 if d else None


def shares(obs, name, lane=lambda s: s["thread"]):
    """{lane: % of the window inside spans called `name`}; a lane is the
    recording thread unless `lane` says otherwise (a worker's number)."""
    got = taken(obs)
    if got is None:
        return None
    spans, window = got
    length = window[1] - window[0]
    by = {}
    for s in named(spans, name):
        by[lane(s)] = by.get(lane(s), 0.0) + clipped_s(s, window)
    return {k: 100.0 * v / length for k, v in by.items()} if length > 0 \
        else None


def loop_thread_rows(spans, thread):
    """The spans of one thread as rows of `host["spans"]` ([name, t0, t1,
    thread]) for `xplane.attribute_gaps`; a phase takes its step's kind
    for a prefix (`executor.state_gather`)."""
    kinds = {s["span"]: s["name"].rsplit(".", 1)[0]
             for s in spans if s["kind"] == "step"}
    rows = []
    for s in spans:
        if s["thread"] != thread:
            continue
        name = s["name"]
        if s["kind"] == "phase":
            name = f"{kinds.get(s['parent'], 'step')}.{name}"
        rows.append([name, s["t0"], s["t1"], s["thread"]])
    # a step and its first phase open on the same stamp, and among spans
    # opened together `attribute_gaps` takes the one listed first: inner
    # (shorter) ones go first
    return sorted(rows, key=lambda r: (r[1], r[2]))
