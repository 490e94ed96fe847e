"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy time (union of the intervals in which an
operation ran), idle share, time by operation under stable names, idle gaps
attributed to the host spans `chipbench` itself opened, and the part of the
collectives' time that no compute hid.

The file is read with a protobuf wire-format reader kept here, because the
operation's category (`hlo_category`: "convolution fusion", "all-reduce",
...) is a stat of the event's *metadata*, which `jax.profiler.ProfileData`
does not expose, and because the yardstick should not change with a library.
Field numbers are those of tsl/profiler/protobuf/xplane.proto.

Looked at by hand first (my chip runs, PR 23, jax 0.9.0, TPU v5 lite): one
plane `/device:TPU:<i>` per chip with lines `XLA Modules` (one event per
program run), `XLA Ops`, `Async XLA Ops`; event names are the whole HLO
instruction text (`%fusion.2 = bf16[...] fusion(...), kind=kOutput, ...`).
The profiler's HOST tracer is switched off when `chipbench` traces (see
`harness.SpanLog` for why), so the host's side of the story, the spans
around each call into a layer, comes from the harness's own clock and is
laid over the device's through clock markers (`marker_offset_ps`).
"""

import bisect
import dataclasses
import re
import struct

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "_no_chipbench_span_"
SHORT_GAPS = "_between_ops_under_20_us_each_"
SHORT_GAP_PS = 20_000_000
# instructions that only hold other instructions: their time is their
# children's, so they are left out of the time by name (never of the union)
CONTAINERS = ("while", "conditional", "call")


# ------------------------------------------------------------ wire format
def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) for every field of one message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val = buf[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield num, wt, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Event:
    name: str
    start_ps: int
    dur_ps: int
    stats: dict

    @property
    def end_ps(self):
        return self.start_ps + self.dur_ps


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list

    def line(self, name):
        for ln in self.lines:
            if ln.name == name:
                return ln
        return None


def _stat(buf, stat_names):
    """One XStat -> (name, value); a ref_value is looked up by the caller."""
    name, value, ref = None, None, None
    for num, wt, val in _fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _signed(val)
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            ref = val
    return name, value, ref


def _plane(buf, wanted=None):
    name = ""
    raw_lines, raw_emeta, stat_names = [], [], {}
    for num, wt, val in _fields(buf):
        if num == 2:
            name = bytes(val).decode()
            if wanted is not None and not wanted(name):
                return Plane(name, [])
        elif num == 3:
            raw_lines.append(val)
        elif num == 4:
            raw_emeta.append(val)
        elif num == 5:          # map<int64, XStatMetadata>
            for n2, _, v2 in _fields(val):
                if n2 == 2:
                    sid, sname = None, ""
                    for n3, _, v3 in _fields(v2):
                        if n3 == 1:
                            sid = v3
                        elif n3 == 2:
                            sname = bytes(v3).decode()
                    stat_names[sid] = sname

    def stats_of(raws):
        out = {}
        for raw in raws:
            k, v, ref = _stat(raw, stat_names)
            out[k] = stat_names.get(ref, ref) if ref is not None else v
        return out

    emeta = {}                   # id -> (name, metadata stats)
    for raw in raw_emeta:        # map<int64, XEventMetadata>
        for n2, _, v2 in _fields(raw):
            if n2 != 2:
                continue
            mid, mname, mstats = None, "", []
            for n3, _, v3 in _fields(v2):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    mname = bytes(v3).decode("utf-8", "replace")
                elif n3 == 5:
                    mstats.append(v3)
            emeta[mid] = (mname, stats_of(mstats))

    lines = []
    for raw in raw_lines:
        lname, t0_ns, raw_events = "", 0, []
        for num, wt, val in _fields(raw):
            if num == 2:
                lname = bytes(val).decode()
            elif num == 3:
                t0_ns = _signed(val)
            elif num == 4:
                raw_events.append(val)
        events = []
        for rawe in raw_events:
            mid, off, dur = None, 0, 0
            for num, wt, val in _fields(rawe):
                if num == 1:
                    mid = val
                elif num == 2:
                    off = _signed(val)
                elif num == 3:
                    dur = _signed(val)
            # an event's own stats (device offsets, run ids) are not read:
            # what the reduction needs is in the metadata, shared by all
            # events of one instruction (a four-chip trace holds a million)
            mname, mstats = emeta.get(mid, (str(mid), {}))
            events.append(Event(mname, t0_ns * 1000 + off, dur, mstats))
        events.sort(key=lambda e: (e.start_ps, -e.dur_ps))
        lines.append(Line(lname, events))
    return Plane(name, lines)


def wanted_plane(name):
    return bool(DEVICE_PLANE.match(name))


def load(path, wanted=wanted_plane):
    """The planes of the file the reduction reads (others come back empty:
    a plane's name precedes its lines), events in picoseconds on the
    trace's clock."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(val, wanted) for num, wt, val in _fields(buf) if num == 1]


# -------------------------------------------------------------- intervals
def union(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of union `a` that union `b` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ------------------------------------------------------------------ names
def op_base(text):
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion`: the instruction's
    name without `%` and without the numbering a recompile may change."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    head = re.sub(r"[.\-_]\d+$", "", head)
    return re.sub(r"\.\d+", "", head)


def op_code(text):
    """The HLO opcode of the instruction text (`fusion`, `while`, `copy`)."""
    m = re.search(r"\}?\)?\s([a-z][a-z0-9\-]*)\(", text.split(" = ", 1)[-1])
    return m.group(1) if m else op_base(text)


def stable_name(ev):
    cat = str(ev.stats.get("hlo_category") or op_code(ev.name))
    return f"{cat.replace(' ', '_')}:{op_base(ev.name)}"


def is_collective(ev):
    key = (str(ev.stats.get("hlo_category", "")) + " " + op_base(ev.name))
    return any(w in key for w in ("all-reduce", "all-gather",
                                  "reduce-scatter", "collective", "all-to-all"))


def self_times(events):
    """Per event, its duration minus what events nested in it cover
    (events sorted by start, longest first)."""
    out = [e.dur_ps for e in events]
    stack = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end_ps <= e.start_ps:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(e.dur_ps,
                                  events[stack[-1]].end_ps - e.start_ps)
        stack.append(i)
    return out


# -------------------------------------------------------------- reduction
def device_planes(planes):
    found = [(int(DEVICE_PLANE.match(p.name).group(1)), p)
             for p in planes if DEVICE_PLANE.match(p.name)]
    return [p for _, p in sorted(found, key=lambda t: t[0])]


MARKER = "chipbench_clock_marker"


def marker_offset_ps(planes, syncs_s):
    """Host clock minus device clock, from the harness's own markers: it
    ran a tiny jitted program named MARKER a few times inside the trace
    and took the host's clock right before each dispatch and right after
    each `block_until_ready` (`syncs_s`: [[before, after], ...], seconds).
    The k-th marker cannot start on the device before its dispatch nor end
    after its wait returned, so the offset lies between the largest
    (before - device start) and the smallest (after - device end); the
    middle is taken. The two are 1 to 2.5 ms apart on this runtime (the
    host learns of a completion ~1.7 ms late), so a gap's edge is known to
    about a millisecond. None without markers."""
    devs = device_planes(planes)
    ln = devs[0].line(MODULES_LINE) if devs else None
    marks = [e for e in (ln.events if ln else []) if MARKER in e.name]
    pairs = list(zip(syncs_s, marks))
    if not pairs:
        return None
    low = max(int(b * 1e12) - e.start_ps for (b, _), e in pairs)
    high = min(int(a * 1e12) - e.end_ps for (_, a), e in pairs)
    return (low + high) // 2


def attribute_gaps(gaps, spans, short_ps=SHORT_GAP_PS):
    """Idle time by what the host was doing: each gap's picoseconds go to
    the `chipbench.*` span open at that moment (the one opened last, where
    threads overlap), else to NO_SPAN; gaps under `short_ps` are the
    device's own pauses between operations and are summed apart."""
    starts = [s[1] for s in spans]
    by = {}

    def add(name, ps):
        if ps > 0:
            by[name] = by.get(name, 0) + ps

    for g0, g1 in gaps:
        if g1 - g0 < short_ps:
            add(SHORT_GAPS, g1 - g0)
            continue
        hi = bisect.bisect_left(starts, g1)
        live = [s for s in spans[:hi] if s[2] > g0]
        cuts = sorted({g0, g1, *(min(max(t, g0), g1)
                                 for s in live for t in (s[1], s[2]))})
        for a, b in zip(cuts, cuts[1:]):
            over = [s for s in live if s[1] <= a and s[2] >= b]
            add(max(over, key=lambda s: s[1])[0] if over else NO_SPAN, b - a)
    return by


def reduce_trace(planes, top=10, host=None):
    """The numbers of one traced window. Times are seconds; device numbers
    are averaged over the chips that ran anything, gaps are chip 0's.

    `host` is what the harness recorded on its own clock: {"spans":
    [[name, t0, t1, thread]], "window": [t0, t1], "syncs": [[before,
    after], ...]} in `perf_counter` seconds, mapped onto the device's
    clock through the markers. Without it the window is the extent of the
    device's operations and every gap is unattributed."""
    devs = device_planes(planes)
    spans, window, clock = [], None, None
    if host is not None:
        clock = marker_offset_ps(planes, host["syncs"])
        if clock is None:
            return None
        spans = sorted(((n, int(a * 1e12) - clock, int(b * 1e12) - clock, th)
                        for n, a, b, th in host["spans"]),
                       key=lambda v: v[1])
        window = tuple(int(v * 1e12) - clock for v in host["window"])
    chips = []
    for p in devs:
        ln = p.line(OPS_LINE)
        events = ln.events if ln else []
        if not events:
            continue
        lo, hi = window or (events[0].start_ps,
                            max(e.end_ps for e in events))
        events = [e for e in events if e.end_ps > lo and e.start_ps < hi
                  and MARKER not in e.name]
        busy = clip(union((e.start_ps, e.end_ps) for e in events), lo, hi)
        selfs = self_times(events)
        by_name, by_cat = {}, {}
        for e, st in zip(events, selfs):
            if op_code(e.name) in CONTAINERS:
                continue
            by_name[stable_name(e)] = by_name.get(stable_name(e), 0) + st
            cat = str(e.stats.get("hlo_category") or op_code(e.name))
            by_cat[cat] = by_cat.get(cat, 0) + st
        aln = p.line(ASYNC_LINE)
        coll = union(
            [(e.start_ps, e.end_ps) for e in events if is_collective(e)]
            + [(e.start_ps, e.end_ps) for e in (aln.events if aln else [])
               if is_collective(e)])
        compute = union((e.start_ps, e.end_ps) for e in events
                        if not is_collective(e)
                        and op_code(e.name) not in CONTAINERS)
        chips.append(dict(
            plane=p.name, window_ps=hi - lo, busy_ps=total(busy),
            gaps=subtract([(lo, hi)], busy), by_name=by_name, by_cat=by_cat,
            collective_ps=total(clip(coll, lo, hi)),
            collective_exposed_ps=total(clip(subtract(coll, compute),
                                             lo, hi))))
    if not chips:
        return None
    n = len(chips)
    names = {}
    for c in chips:
        for k, v in c["by_name"].items():
            names[k] = names.get(k, 0) + v / n
    cats = {}
    for c in chips:
        for k, v in c["by_cat"].items():
            cats[k] = cats.get(k, 0) + v / n
    gaps = attribute_gaps(chips[0]["gaps"], spans)
    worst = max(chips, key=lambda c: c["collective_exposed_ps"])
    ps = 1e-12
    return {
        "chips": n,
        "window_s": sum(c["window_ps"] for c in chips) / n * ps,
        "busy_s": sum(c["busy_ps"] for c in chips) / n * ps,
        "host_minus_device_clock_s": None if clock is None else clock * ps,
        "op_seconds": {k: v * ps for k, v in names.items()},
        "category_seconds": {k: v * ps for k, v in cats.items()},
        "device_ops": [[k, v * ps] for k, v in sorted(
            names.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ps] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]],
        "collective_s": worst["collective_ps"] * ps,
        "collective_exposed_s": worst["collective_exposed_ps"] * ps,
        "worst_chip_window_s": worst["window_ps"] * ps,
    }


def reduce_file(path, top=10, host=None):
    return reduce_trace(load(path), top=top, host=host)
