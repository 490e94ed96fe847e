"""Traffic kind `serve`: the configuration's inference program behind
`serve.Server`, under an OPEN loop: arrivals at one fixed rate with
exponential gaps, one image a request, each timed from when it was DUE.

The generator is part of the yardstick. Arrival times and payloads are made
before the window: the gaps are one fixed set (drawn from the traffic
file's `base_seed`) in an order the run's `--seed` picks, so every seed
offers the same work; payloads come from a seeded pool. The generator
thread sleeps, then spins with the GIL released (`time.sleep(0)`) to each
due time, submits, and does nothing else; completion times are taken by
the future's done-callback on the server's own worker thread. How late the
generator ran is reported in every run (`serve.gen_late_p99_ms`).

It stays in this process: `serve/http.py` takes a request as nested JSON
lists, about 600 KB of text for one 224x224x3 image, which no client can
write nor the server parse at a thousand requests a second.
"""

import os
import threading
import time

import numpy as np

from chipbench import compare, programs, timeline
from chipbench.harness import SPANS

# A served row goes through a bucket's program (16 to 128 rows),
# Executor.run through a one-row program: other tilings, so other bf16
# roundings in every layer. The seeded weights are untrained and the
# inference pass uses the initial moving statistics, so the logits are in
# the hundreds and the softmax is saturated: the same row's PROBABILITIES
# differed by 0.999 between two bucket sizes (my chip run, PR 23), which
# says nothing. The sampled responses are therefore compared on the LOGITS
# the server returns beside the probabilities, relative to the row's
# largest |logit|; two tilings measured under 1% apart (PERF.md), the
# float32 reference is held to 4% (chipbench/compare.py), and a wrong row,
# a stale weight or a mixed-up slice moves this by the order of 1.
SERVE_LOGITS_TOL = 0.03


def arrivals(rate, seconds, base_seed, seed):
    """Due times (seconds from the window's start) of a Poisson process at
    `rate`: a fixed set of exponential gaps, shuffled by `seed`, cut to the
    window. Every seed gets the same gaps, so the same count and span."""
    n = int(rate * seconds)
    gaps = np.random.default_rng(int(base_seed)).exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()      # the set spans the window exactly
    np.random.default_rng(int(seed)).shuffle(gaps)
    return np.cumsum(gaps)


def in_server_spans(sent, done, t0, t1):
    """The window cut into stretches with at least one request inside the
    server (sent, not yet answered) and stretches with none, as spans for
    the gap attribution: a device idle while requests are inside is
    waiting for `serve/engine.py`'s host path (queue and batch wait,
    concatenate and pad, dispatch, read-back); idle with none inside, it
    has no work. Spans inside `paddle_tpu` would split the first further."""
    edges = sorted([(s, 1) for s in sent if s is not None]
                   + [((t1 if d is None else d), -1)
                      for s, d in zip(sent, done) if s is not None])
    spans, inside, since = [], 0, t0
    for at, step in edges:
        if inside == 0 and step == 1:
            if at > since:
                spans.append(["chipbench.no_request_in_server", since, at,
                              "post-hoc"])
            since = at
        inside += step
        if inside == 0:
            spans.append(["chipbench.requests_in_server", since, at,
                          "post-hoc"])
            since = at
    if t1 > since:
        spans.append(["chipbench.requests_in_server" if inside
                      else "chipbench.no_request_in_server", since, t1,
                      "post-hoc"])
    return spans


class Generator(threading.Thread):
    def __init__(self, server, due, payloads, order, sample, margin_s):
        super().__init__(name="chipbench-generator", daemon=True)
        n = len(due)
        self.server, self.due, self.payloads = server, due, payloads
        self.order, self.margin_s = order, margin_s
        self.sent = [None] * n
        self.done = [None] * n
        self.kept = {int(i): None for i in sample}
        self.refused = 0
        self.t0 = None
        self.t_finished = None

    def _on_done(self, j, fut):
        t = time.perf_counter()
        if fut.exception() is None:
            self.done[j] = t
            if j in self.kept:
                self.kept[j] = fut.result()

    def run(self):
        from functools import partial

        pc, sleep = time.perf_counter, time.sleep
        feed_name, submit = "data_u8", self.server.submit
        t0 = self.t0
        for j, d in enumerate(self.due):
            due = t0 + d
            wait = due - pc() - self.margin_s
            if wait > 0:
                sleep(wait)
            while pc() < due:
                sleep(0)
            self.sent[j] = pc()
            try:
                fut = submit({feed_name: self.payloads[self.order[j]]})
            except Exception:  # noqa: BLE001 refused: counted as failed
                self.refused += 1
                continue
            fut.add_done_callback(partial(self._on_done, j))
        self.t_finished = pc()


def run(ctx):
    fluid, jax, t, cfg = ctx.fluid, ctx.jax, ctx.traffic, ctx.cfg
    from paddle_tpu import amp, serve

    setup, log = ctx.setup, ctx.log
    place = fluid.TPUPlace(0)
    if cfg.get("amp"):
        amp.enable(cfg["amp"])
    server = None
    try:
        with setup.item("reference_comparison"):
            ref = compare.against_reference(fluid, cfg, ctx.builder, place,
                                            ctx.seed, parts=("inference",))
        with setup.item("program_build"):
            built = ctx.builder.build(fluid, cfg, ctx.seed)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            with setup.item("startup_program"):
                exe = fluid.Executor(place)
                exe.run(built["startup"])
        with setup.item("traffic_made"):
            seconds = float(t["trace_seconds"]) if ctx.trace else ctx.seconds
            due = arrivals(float(t["rate_per_s"]), seconds, t["base_seed"],
                           ctx.seed)
            rs = np.random.default_rng(ctx.seed)
            pool = programs.to_system(cfg, programs.seeded_images(
                cfg, ctx.seed, 1, int(t["payload_pool"]))[0][0])
            order = rs.integers(0, len(pool), len(due))
            sample = rs.choice(len(due), int(t["checked_responses"]),
                               replace=False)
        mark = log.mark()
        t_w = time.perf_counter()
        config = serve.ServeConfig(
            max_batch=int(t["max_batch"]), max_wait_ms=float(t["max_wait_ms"]),
            buckets=[int(b) for b in t["buckets"]],
            max_queue_rows=int(t["max_queue_rows"]),
            dispatch_depth=int(t["dispatch_depth"]),
            slo_ms=float(t["latency_limit_ms"]))
        logits = next(op.input("X")[0] for op in reversed(
            built["test_prog"].global_block().ops) if op.type == "softmax")
        server = serve.Server(built["test_prog"], [built["image_feed"]],
                              [built["predict"], logits], place=place,
                              scope=scope, config=config)
        server.start()                    # warms exactly t["buckets"]
        warm = log.since(mark)
        setup.add("bucket_warmup_compile_or_cache_load", warm["seconds"])
        setup.add("bucket_warmup_lowering_and_run",
                  time.perf_counter() - t_w - warm["seconds"])
        with setup.item("first_requests"):
            for i in range(int(t["first_requests"])):
                server.infer({built["image_feed"]: pool[i % len(pool)]},
                             timeout=60.0)
        setup_compile = log.since(0)
        stats0 = server.stats()
        hist0 = _queue_hist()

        gen = Generator(server, due, pool, order, sample,
                        float(t["spin_margin_ms"]) / 1000.0)
        old_switch = None
        if t.get("switch_interval_s"):
            import sys

            old_switch = sys.getswitchinterval()
            sys.setswitchinterval(float(t["switch_interval_s"]))
        ctx.tracer.start()
        mark = log.mark()
        t_open = time.perf_counter()
        gen.t0 = t_open
        gen.start()
        gen.join()
        deadline = time.perf_counter() + float(t["drain_timeout_s"])
        while (any(d is None for d in gen.done[-64:])
               and time.perf_counter() < deadline):
            time.sleep(0.005)
        time.sleep(0.02)
        t_end = time.perf_counter()
        if ctx.trace:
            SPANS.spans.extend(in_server_spans(gen.sent, gen.done, t_open,
                                               t_end))
        ctx.tracer.stop()
        if old_switch is not None:
            import sys

            sys.setswitchinterval(old_switch)
        window_compiles = log.since(mark)["requests"]
        stats1 = server.stats()
        hist1 = _queue_hist()

        # a seeded sample of responses against Executor.run on the same rows
        worst = 0.0
        with fluid.scope_guard(scope):
            for j, got in gen.kept.items():
                if got is None:
                    worst = float("inf")
                    continue
                want, = exe.run(built["test_prog"], feed={
                    built["image_feed"]: pool[order[j]][None],
                    built["label_feed"]: np.zeros((1, 1), np.int32)},
                    fetch_list=[logits])
                got = np.asarray(got[1], np.float32)
                want = np.asarray(want, np.float32)
                worst = max(worst, float(np.max(np.abs(got - want))
                                         / np.max(np.abs(want))))
    finally:
        if server is not None:
            server.stop()
        amp.disable()

    due_l = [float(d) for d in due]
    sent = [None if s is None else s - t_open for s in gen.sent]
    done = [None if d is None else d - t_open for d in gen.done]
    skip = float(t["skip_first_s"]) if not ctx.trace else 0.0
    reading = timeline.serve_reading(due_l, sent, done, t_end - t_open,
                                     float(t["latency_limit_ms"]), skip)
    failed = sum(1 for d in done if d is None)
    rows = stats1["rows"] - stats0["rows"]
    padded = stats1["padded_rows"] - stats0["padded_rows"]
    qn, qs = hist1[0] - hist0[0], hist1[1] - hist0[1]
    if ctx.dump:
        _dump(ctx, due_l, sent, done, t_end - t_open, skip)
    checks = {
        "reference": bool(ref["ok"]),
        "responses_match_executor_run": worst <= SERVE_LOGITS_TOL,
        "window_compiles_zero": window_compiles == 0
        and stats1["steady_state_compiles"] == 0,
        "none_failed": failed == 0,
    }
    grows = timeline.backlog_grows(due_l, done, t_end - t_open, skip)
    return {
        "t_open": t_open, "correct": all(checks.values()), "checks": checks,
        "attempted": len(due_l), "failed": failed,
        "end_to_end": {k: reading[v] for k, v in t["end_to_end"].items()},
        "reference": ref, "setup_compile": setup_compile,
        "window_s": t_end - t_open, "reading": reading, "train": False,
        "pad_share": 100.0 * padded / max(1, rows + padded),
        "batch_wait_ms": qs / qn if qn else None,
        "backlog_grows": grows,
        "plan": ctx.builder.reference.layer_plan(cfg), "batch": 1,
        "detail": {"reading": reading, "rate_per_s": t["rate_per_s"],
                   "refused": gen.refused, "failed": failed,
                   "backlog_grows": grows,
                   "batch_wait_ms": qs / qn if qn else None,
                   "pad_share": 100.0 * padded / max(1, rows + padded),
                   "max_rel_diff_vs_executor": worst,
                   "window_compiles": window_compiles,
                   "generator_finished_s": gen.t_finished - t_open,
                   "rows": rows, "padded_rows": padded,
                   "buckets_warmed": list(config.buckets)},
    }


def _queue_hist():
    """(count, sum in ms) of the server's queue phase: submit until the
    batcher picked the request up (`serve_request_phase_ms{phase=queue}`)."""
    from paddle_tpu import monitor
    from paddle_tpu.serve.engine import SERVE_MS_BUCKETS

    snap = monitor.registry().histogram(
        "serve_request_phase_ms", buckets=SERVE_MS_BUCKETS,
        phase="queue").snapshot()
    return snap.get("count", 0), snap.get("sum", 0.0)


def _dump(ctx, due, sent, done, t_end, skip):
    import json

    os.makedirs(ctx.dump, exist_ok=True)
    path = os.path.join(
        ctx.dump, f"{ctx.cell['name']}-seed{ctx.seed}-seconds.json")
    late = [None if s is None else (s - d) * 1000.0
            for s, d in zip(sent, due)]
    rows = timeline.per_second(due, done, t_end, skip)
    with open(path, "w") as f:
        json.dump({"workload": ctx.cell["name"], "seed": ctx.seed,
                   "rate_per_s": ctx.traffic["rate_per_s"],
                   "seconds": ctx.seconds, "per_second": rows,
                   "due_s": [round(d, 4) for d in due],
                   "lat_ms": [None if c is None else round((c - d) * 1e3, 3)
                              for c, d in zip(done, due)],
                   "gen_late_ms_by_second": [
                       [s, timeline.percentile(
                           [v for v, d in zip(late, due)
                            if v is not None and s <= d < s + 1], 99)]
                       for s in range(int(skip), int(max(due)) + 1)]}, f)
