"""python -m chipbench.traced --workload <cell> --seed <n> [--override f]

The traced run of one cell WITH the program's own spans: `harness.run_cell`
as `python -m chipbench.run --trace 1` drives it, and around it

  * FLAGS_trace (and a FLAGS_trace_buffer sized for the window) set right
    after `import paddle_tpu`, before the cell builds anything: datapipe
    snapshots the flag when its iterator is made;
  * `trace.reset()` where `Tracer.start` clears the harness's own spans,
    `trace.snapshot()` where `Tracer.stop` closes the window;
  * the spans of the thread that drives the loop appended to
    `host["spans"]` under their own names, so that `attribute_gaps` files
    each idle gap under `executor.state_gather`, `datapipe.next`, ...
    (innermost, latest-opened span wins). Lanes and workers go to the
    readers only: a lane's `datapipe.transfer` is always open and would
    swallow the gaps;
  * `obs["program_spans"]`, `["program_spans_dropped"]`,
    `["program_spans_window"]` for the readers (`chipbench/spans.py`), and
    the per-layer entries of `chipbench/tests/span_metrics.json` laid over
    BENCHMARK.json's.

It is a file beside the harness and not an edit of it because a PR that is
not a `benchmark` PR may only add files here; `harness.py`, `run.py` and
BENCHMARK.json are as they were, and `--trace 0` never imports this.
PERF.md section 7 names the edit that would fold it in.
"""

import argparse
import json
import os
import sys
import threading
import time

T_START = time.perf_counter()

from chipbench import harness, spans  # noqa: E402

PROPOSED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "span_metrics.json")


def buffer_for(traffic):
    """Spans one thread's ring has to hold for the window: a serving
    window records five spans a request and a batch's worth of phases on
    top; a training window a few hundred in all."""
    per_window = float(traffic.get("rate_per_s", 0)) \
        * float(traffic.get("trace_seconds", 0))
    return max(65536, int(16 * per_window))


class ProgramSpanTracer(harness.Tracer):
    """`harness.Tracer` whose window also holds the program's spans."""

    program_spans = None
    program_spans_dropped = None
    program_spans_window = None

    def start(self):
        super().start()
        if self.enabled:
            from paddle_tpu import trace

            trace.reset()
            self._loop_thread = threading.current_thread().name

    def stop(self):
        if self.enabled:
            from paddle_tpu import trace

            t1 = time.perf_counter()
            self.program_spans, self.program_spans_dropped = \
                trace.snapshot()
            self.program_spans_window = [self._t0, t1]
            self.host["spans"].extend(spans.loop_thread_rows(
                self.program_spans, self._loop_thread))
        super().stop()


class _KindWithSpans:
    """The cell's kind, with tracing switched on before it builds
    anything and the window's spans added to what it returns."""

    def __init__(self, kind):
        self.kind = kind

    def run(self, ctx):
        from paddle_tpu import flags

        flags.set("trace_buffer", buffer_for(ctx.traffic))
        # an anomaly (a request over its limit) dumps the recorder once
        flags.set("trace_dump_dir", os.path.join(ctx.workdir, "dumps"))
        flags.set("trace", True)
        try:
            res = self.kind.run(ctx)
        finally:
            flags.set("trace", False)
        tr = ctx.tracer
        taken = dict(program_spans=tr.program_spans,
                     program_spans_dropped=tr.program_spans_dropped,
                     program_spans_window=tr.program_spans_window)
        if ctx.dump:    # beside the raw device trace `Tracer.stop` keeps
            os.makedirs(ctx.dump, exist_ok=True)
            with open(os.path.join(ctx.dump, "program_spans.json"),
                      "w") as f:
                json.dump(taken, f)
        return dict(res, **taken)


class TracedFiles(harness.Files):
    def bench(self):
        bench = super().bench()
        with open(PROPOSED) as f:
            proposed = json.load(f)["per_layer"]
        have = {m["name"] for m in bench["per_layer"]}
        return dict(bench, per_layer=bench["per_layer"] + [
            m for m in proposed if m["name"] not in have])

    def cell(self, name):
        bench, cell, cfg, traffic, builder, kind = super().cell(name)
        return bench, cell, cfg, traffic, builder, _KindWithSpans(kind)


def run_cell(workload, seed, seconds=None, files=None, **kw):
    """`harness.run_cell(..., trace=True)` with the program's spans."""
    files = files or TracedFiles()
    stock, harness.Tracer = harness.Tracer, ProgramSpanTracer
    try:
        return harness.run_cell(
            workload, seed, files.bench()["run_seconds"]
            if seconds is None else seconds, True, files=files, **kw)
    finally:
        harness.Tracer = stock


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.traced")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bench", default=None)
    ap.add_argument("--override", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--dump", default=None,
                    help="directory for the raw device trace, the host "
                         "spans and the window's program spans")
    args = ap.parse_args(argv)
    import faulthandler

    faulthandler.dump_traceback_later(1150, exit=True)
    override = None
    if args.override:
        with open(args.override) as f:
            override = json.load(f)
    try:
        run_cell(args.workload, args.seed,
                 files=TracedFiles(bench_path=args.bench), t_start=T_START,
                 rehearsal=args.rehearsal, override=override,
                 dump=args.dump)
    except harness.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    faulthandler.cancel_dump_traceback_later()
    harness.leave(grace_s=15.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
