"""Traffic kind `train_tokens_share`: the `train_tokens` loop (its
`token_rows` and `TokenSource`, the `train` kind's `run_chunks`,
`timeline.train_reading`: imported, not copied) for a language model that
holds ONE CHIP'S SHARE of each layer: some of the experts its router
scores, some of the heads, a slice of the vocabulary, several blocks deep,
with a second cross-entropy from a multi-token-prediction module. An item
is a token.

What differs from `train_tokens` is what is fetched and held to account.
Each chunk fetches, beside the loss, the first expert layer's router
counts over ALL experts and the rows the grouped products of EVERY expert
layer took (each layer routes on its own: the kernels' least time and the
operations counted are each layer's own rows, not the first layer's).
`correct` = the reference comparison (`compare_lm_share`) and losses
finite and no compile in the window and, in every step fetched: the
router's counts sum to top_k x tokens (every token chose top_k experts),
and the rows the products took are the counts of the held experts (the
products ran over the held groups' rows, all of them and no others). What
the absent experts would have added is left out, in the program and in the
reference alike: that no HELD expert's output is missing is what the
comparison holds.
"""

import os
import shutil
import statistics
import time

import numpy as np

from chipbench import compare_lm_share, scopes, timeline
from chipbench.harness import load_json, note
from chipbench.kinds import train as train_kind
from chipbench.kinds import train_tokens as tokens_kind
from chipbench.kinds.train_tokens import TokenSource, token_rows  # noqa: F401


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
    first = int(cfg["deployment"]["first_expert"])
    held = int(cfg["n_routed_experts"])
    place = fluid.TPUPlace(0)     # host device 0 under the tests' CPU pin
    with setup.item("reference_comparison"):
        # another seed's stream than the window's chunks
        tok, lab, _ = token_rows(cfg, t, ctx.seed + 1,
                                 int(cfg["reference"]["rows"]))
        ref = compare_lm_share.against_reference(
            fluid, cfg, ctx.builder, place, ctx.seed, tok, lab)
    with setup.item("program_build"):
        built = ctx.builder.build(fluid, cfg, ctx.seed)
    load_var = built["routing"][0][1]
    rows_vars = [r[2] for r in built["routing"]]    # the first layer's first
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with setup.item("startup_program"):
            exe = fluid.Executor(place)
            exe.run(built["startup"])
        fetched = []

        def run_fn(feed):
            loss, load, *taken = exe.run(
                built["prog"], feed=feed,
                fetch_list=[built["loss"], load_var] + rows_vars, iters=K,
                async_fetch=True)
            fetched.append((load, taken))
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
        before = len(fetched)
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
    # [steps, E] and [steps, expert layers] of the window's chunks
    loads = np.concatenate([np.asarray(l.result()).reshape(K, -1)
                            for l, _ in fetched[before:]])
    by_layer = np.concatenate([
        np.stack([np.asarray(r.result()).reshape(K) for r in rs], axis=1)
        for _, rs in fetched[before:]])
    taken = by_layer[:, 0]
    peak = np.asarray(loads.max(axis=1) / loads.mean(axis=1))
    steps = K * (len(win["done"]) + 1)
    checks = {"reference": bool(ref["ok"]),
              "losses_finite": bool(np.all(np.isfinite(losses))),
              "window_compiles_zero": window_compiles == 0,
              "every_token_routed": bool(
                  len(loads) == steps
                  and (loads.sum(axis=1) == top_k * rows * S).all()),
              "products_took_the_held_rows": bool(
                  len(taken) == steps
                  and (taken == loads[:, first:first + held].sum(axis=1))
                  .all())}
    held_share = taken / float(top_k * rows * S)
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
        "held_rows_share": float(statistics.median(held_share)),
        "held_rows_by_layer": by_layer.tolist(),
        "detail": {"reading": reading, "window_compiles": window_compiles,
                   "first_loss": losses[0], "last_loss": losses[-1],
                   "documents_in_chunks": source.documents,
                   "distinct_chunks": len(source.chunks),
                   "chunks_handed": source.handed,
                   "expert_load_max_over_mean": [float(peak.min()),
                                                 float(peak.max())],
                   "held_rows_share": [float(held_share.min()),
                                       float(held_share.max())],
                   "held_rows_share_by_layer": (
                       by_layer.mean(axis=0) / (top_k * rows * S)).tolist(),
                   "scopes": tokens_kind._scope_detail(by_scope)},
    }
