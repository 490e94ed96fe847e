"""Traffic kind `train_tokens`: K-step scans of a language model's
training program on packed rows of tokens resident on the device,
dispatched one chunk ahead as the `train` kind does (its `run_chunks`
and `timeline.train_reading` are used, not copied). An item is a token.

Rows: documents with log-normal lengths are concatenated with the EOS id
and cut into rows of `sequence_length`; token ids are Zipf over the
vocabulary; a row's labels are its next tokens. `distinct_chunks` chunks
of K steps are made from the seed in set-up, put on the device and
cycled, so no step of the window repeats a batch.

`correct` = the reference comparison (`compare_lm`) and losses finite and
no compile in the window and every token routed: in each step fetched the
group sizes handed to the grouped products (`TokensPerExpert`) sum to
top_k x tokens. The lowering has no capacity, so today that sum holds by
construction; the check is there for a lowering that clips a group. That
no token's expert output is missing is what the comparison holds: the
reference is dense over the experts, and a dropped token's logits differ
from it by the whole expert branch.
"""

import os
import shutil
import statistics
import time

import numpy as np

from chipbench import compare_lm, scopes, timeline
from chipbench.harness import load_json, note
from chipbench.kinds import train as train_kind


def token_stream(cfg, t, seed, n_tokens):
    """`n_tokens` int32 token ids: documents of log-normal length (median,
    sigma, clipped) of Zipf ids (rank r has weight r^-exponent; ranks are
    laid over the vocabulary by a seeded permutation), each followed by
    the EOS id."""
    rs = np.random.default_rng(int(seed))
    V, eos = cfg["vocab_size"], cfg["eos_token_id"]
    mean_len = np.exp(np.log(t["doc_len_median"])
                      + 0.5 * t["doc_len_sigma"] ** 2)
    lengths = []
    while sum(lengths) + len(lengths) < n_tokens:
        more = rs.lognormal(np.log(t["doc_len_median"]), t["doc_len_sigma"],
                            int(n_tokens / mean_len) + 16)
        lengths += list(np.clip(more, t["doc_len_min"],
                                t["doc_len_max"]).astype(np.int64))
    ends = np.cumsum(np.asarray(lengths) + 1) - 1        # EOS positions
    ends = ends[ends < n_tokens]
    weight = np.arange(1, V, dtype=np.float64) ** -float(t["zipf_exponent"])
    vocab = rs.permutation(V)
    vocab = vocab[vocab != eos]                          # V - 1 ids by rank
    stream = vocab[rs.choice(V - 1, size=n_tokens, p=weight / weight.sum())]
    stream[ends] = eos
    return stream.astype(np.int32), len(ends)


def token_rows(cfg, t, seed, n_rows):
    """(tokens, labels) int32 [n_rows, S]: consecutive rows of one stream,
    labels the next token (a row's last label is the next row's first)."""
    S = int(cfg["sequence_length"])
    stream, docs = token_stream(cfg, t, seed, n_rows * S + 1)
    return (stream[:-1].reshape(n_rows, S), stream[1:].reshape(n_rows, S),
            docs)


class TokenSource:
    """`distinct_chunks` chunks of stacked feeds [K, rows, S] on the
    device, handed out in turn."""

    def __init__(self, ctx, built, K, rows):
        jax, t = ctx.jax, ctx.traffic
        n = int(t["distinct_chunks"])
        tok, lab, self.documents = token_rows(ctx.cfg, t, ctx.seed,
                                              n * K * rows)
        shape = (n, K, rows, tok.shape[1])
        dev = ctx.devices[0]
        self.chunks = [
            {built["token_feed"]: jax.device_put(a, dev),
             built["label_feed"]: jax.device_put(b, dev)}
            for a, b in zip(tok.reshape(shape), lab.reshape(shape))]
        jax.block_until_ready(self.chunks)
        self.handed = 0

    def next(self):
        chunk = self.chunks[self.handed % len(self.chunks)]
        self.handed += 1
        return chunk

    def stats_delta(self):
        return None


def run(ctx):
    from paddle_tpu import amp

    if ctx.cfg.get("amp"):
        amp.enable(ctx.cfg["amp"])
    try:
        return _run(ctx)
    finally:
        amp.disable()


def _run(ctx):
    fluid, jax, t, cfg = ctx.fluid, ctx.jax, ctx.traffic, ctx.cfg
    setup, log = ctx.setup, ctx.log
    K, rows = int(t["steps_per_chunk"]), int(cfg["rows_per_step"])
    S, top_k = int(cfg["sequence_length"]), int(cfg["num_experts_per_tok"])
    place = fluid.TPUPlace(0)     # host device 0 under the tests' CPU pin
    with setup.item("reference_comparison"):
        # another seed's stream than the window's chunks
        tok, lab, _ = token_rows(cfg, t, ctx.seed + 1,
                                 int(cfg["reference"]["rows"]))
        ref = compare_lm.against_reference(fluid, cfg, ctx.builder, place,
                                           ctx.seed, tok, lab)
    with setup.item("program_build"):
        built = ctx.builder.build(fluid, cfg, ctx.seed)
    load_var = built["routing"][0][1]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with setup.item("startup_program"):
            exe = fluid.Executor(place)
            exe.run(built["startup"])
        loads = []

        def run_fn(feed):
            loss, load = exe.run(built["prog"], feed=feed,
                                 fetch_list=[built["loss"], load_var],
                                 iters=K, async_fetch=True)
            loads.append(load)
            return loss

        with setup.item("feeds_made_on_device"):
            source = TokenSource(ctx, built, K, rows)
        mark = log.mark()
        t_w = time.perf_counter()
        warm = train_kind.run_chunks(
            run_fn, source, lambda n, _t: n >= int(t["warmup_chunks"]), jax)
        warm_compile = log.since(mark)
        setup.add("warmup_compile_or_cache_load", warm_compile["seconds"])
        setup.add("warmup_chunks_lowering_and_run",
                  time.perf_counter() - t_w - warm_compile["seconds"])
        setup_compile = log.since(0)

        # -------------------------------------------------------- window
        n_trace = int(t["trace_chunks"])
        keep = None
        if ctx.trace and ctx.tracer.keep is None:
            # the raw window, for the reduction by scope; deleted below
            keep = ctx.tracer.keep = os.path.join(ctx.workdir,
                                                  "tokens_window")
        loads_before = len(loads)
        ctx.tracer.start()
        mark = log.mark()
        t_open = time.perf_counter()
        if ctx.trace:
            win = train_kind.run_chunks(
                run_fn, source, lambda n, _t: n >= max(1, n_trace - 1), jax)
        else:
            win = train_kind.run_chunks(
                run_fn, source, lambda n, td: td - t_open >= ctx.seconds,
                jax)
        note("window done", ctx.t_start)
        ctx.tracer.stop()
        window_compiles = log.since(mark)["requests"]
    by_scope = None
    if ctx.trace and ctx.tracer.keep:
        raw = os.path.join(ctx.tracer.keep, "window.xplane.pb")
        if os.path.exists(raw):
            by_scope = scopes.reduce_file(raw, host=load_json(
                os.path.join(ctx.tracer.keep, "window.host.json")))
        if keep:
            shutil.rmtree(keep, ignore_errors=True)
    seconds = (win["t_last"] - t_open) if ctx.trace else ctx.seconds
    items = K * rows * S
    done = [t_open] + win["done"] + [win["t_last"]]
    reading = timeline.train_reading(
        done if ctx.trace else win["done"], items,
        t_open if ctx.trace else win["done"][0], 1e9)
    losses = train_kind.losses_of(warm["futs"] + win["futs"])
    # [steps, E] of the window's chunks
    window_loads = np.concatenate(
        [np.asarray(f.result()).reshape(K, -1)
         for f in loads[loads_before:]])
    routed = window_loads.sum(axis=1)
    peak = np.asarray(window_loads.max(axis=1) / window_loads.mean(axis=1))
    steps = K * (len(win["done"]) + 1)
    checks = {"reference": bool(ref["ok"]),
              "losses_finite": bool(np.all(np.isfinite(losses))),
              "window_compiles_zero": window_compiles == 0,
              "every_token_routed": bool(
                  len(routed) == steps
                  and (routed == top_k * rows * S).all())}
    name = next(iter(t["end_to_end"]))
    return {
        "t_open": t_open, "correct": all(checks.values()), "checks": checks,
        "attempted": len(win["done"]) + 1, "failed": 0,
        "end_to_end": {k: reading[v] for k, v in t["end_to_end"].items()},
        "reference": ref, "setup_compile": setup_compile,
        "window_s": seconds, "items_per_chunk": items, "reading": reading,
        "rate_items_per_s": reading[t["end_to_end"][name]],
        "steps_in_window": steps, "tokens_per_step": rows * S,
        "host_dispatch_s": win["dispatch"], "scopes": by_scope,
        "expert_load_max_over_mean": float(statistics.median(peak)),
        "detail": {"reading": reading, "window_compiles": window_compiles,
                   "first_loss": losses[0], "last_loss": losses[-1],
                   "documents_in_chunks": source.documents,
                   "distinct_chunks": len(source.chunks),
                   "chunks_handed": source.handed,
                   "expert_load_max_over_mean": [float(peak.min()),
                                                 float(peak.max())],
                   "scopes": _scope_detail(by_scope)},
    }


def _scope_detail(red, top=24):
    """The by-scope table of the traced window for the detail line: the
    largest scopes, the share of busy time outside every named scope, and
    that share itemised by operation name."""
    if not red:
        return None
    order = sorted(red["by_scope"].items(), key=lambda kv: -kv[1])
    return {"window_s": red["window_s"], "busy_s": red["busy_s"],
            "by_scope_s": order[:top],
            "unscoped_share": scopes.unscoped_share(red),
            "unscoped_ops_s": sorted(red["unscoped_ops"].items(),
                                     key=lambda kv: -kv[1])[:12]}
