"""Traffic kind `train_tokens_window_share`: K-step scans of the training
program of a language model that holds one chip's share of each layer AND
whose layers alternate between full and sliding-window attention with
different head counts (`laguna_xs_2`), on packed rows of tokens resident
on the device, dispatched one chunk ahead. `token_rows`, `TokenSource`,
the `train` kind's `run_chunks` / `losses_of`, `timeline.train_reading`
and `scopes.reduce_file` are imported, not copied. An item is a token.

The loop is this kind's own and not `train_tokens_share._run`: that loop
compares before it builds and keeps neither its scope nor its losses, so
it cannot hold what it times to the reference. What differs from that
kind, so that a fold of the token kinds (a `benchmark` PR: `_run(ctx,
compare, experts_key)`) has it in one place:

* THE ORDER. The timed program is built, warmed and timed FIRST; the
  comparison runs after the window, when the timed program's scope is
  gone. So the device's high-water mark read as the window closes
  (`window_peak_bytes`, the reader `swa.peak_hbm_gb`) is what the traffic
  holds and not the comparison's float32 reference, and `setup_s` (process
  start to the window's opening) holds no comparison;
* THE TIMED EXECUTABLE IS HELD TO THE REFERENCE: the comparison
  (`compare_lm_window_share`) is made on the rows of the window's own
  chunk 0, steps 0 and 1, with the weights the same seed draws, and the
  losses the K-step scan fetched for those two steps are set against the
  reference's first step and its second after its own AdamW update;
* one exact check more of the scan's carried state: the first sparse
  layer's router bias as the scope holds it when the window closes is the
  rule replayed over the router counts of EVERY step the executable ran
  (warm-up and window), in order;
* one more program counter, static, of the program the window times
  (`paddle_tpu.ops.lm_ops.window_blocks`): the score blocks the window
  layers' flash kernels compute a step against those of a full causal
  grid of the same block size.

Fetched a chunk, beside the loss: the first sparse layer's router counts
over ALL experts and the rows the grouped products of EVERY sparse layer
took. `correct` = the reference comparison (with the timed steps) and
losses finite and no compile in the window and, in every step fetched: the
router's counts sum to top_k x tokens, and the rows the products took are
the counts of the held experts; and the bias check above.
"""

import gc
import os
import shutil
import statistics
import time

import numpy as np

from chipbench import compare_lm_window_share, scopes, timeline
from chipbench.harness import load_json, memory_peak, note
from chipbench.kinds import train as train_kind
from chipbench.kinds import train_tokens as tokens_kind
from chipbench.kinds.train_tokens import TokenSource, token_rows  # noqa: F401


def run(ctx):
    from paddle_tpu import amp

    if ctx.cfg.get("amp"):
        amp.enable(ctx.cfg["amp"])
    try:
        res, rows, timed = _timed(ctx)
        gc.collect()        # the timed program's scope, feeds and futures
        res["reference"] = ref = compare_lm_window_share.against_reference(
            ctx.fluid, ctx.cfg, ctx.builder, ctx.fluid.TPUPlace(0), ctx.seed,
            *rows, timed=timed)
        res["checks"] = dict(reference=bool(ref["ok"]), **res["checks"])
        res["correct"] = all(res["checks"].values())
        return res
    finally:
        amp.disable()


def _bias_by_the_rule(before, loads, speed):
    """`before` after one move a step: + speed x sign(mean load - load),
    float32 as the program adds it."""
    bias = np.asarray(before, np.float32).copy()
    for load in loads.astype(np.float64):
        bias = bias + np.float32(speed) * np.sign(
            load.mean() - load).astype(np.float32)
    return bias


def _timed(ctx):
    """The timed program from build to the window's close. Returns (the
    result without the comparison, (tokens, labels) int32 [2 x rows, S] of
    steps 0 and 1 as the executable was fed them, {"losses": its losses
    of those steps, "chunk_losses": of all K steps of its first chunk,
    "chunk_rows": (tokens, labels) [K x rows, S] of that chunk}); its
    scope is gone when this returns."""
    from paddle_tpu.ops.lm_ops import window_blocks

    fluid, jax, t, cfg = ctx.fluid, ctx.jax, ctx.traffic, ctx.cfg
    setup, log = ctx.setup, ctx.log
    K, rows = int(t["steps_per_chunk"]), int(cfg["rows_per_step"])
    S, top_k = int(cfg["sequence_length"]), int(cfg["num_experts_per_tok"])
    first = int(cfg["deployment"]["first_expert"])
    held = int(cfg["num_experts"])
    place = fluid.TPUPlace(0)     # host device 0 under the tests' CPU pin
    with setup.item("program_build"):
        built = ctx.builder.build(fluid, cfg, ctx.seed)
        visited, whole = window_blocks(built["prog"])
    load_var = built["routing"][0][1]
    rows_vars = [r[2] for r in built["routing"]]    # the first layer's first
    bias_name = next(op.input("Bias")[0]
                     for op in built["prog"].global_block().ops
                     if op.type == "moe_ffn")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with setup.item("startup_program"):
            exe = fluid.Executor(place)
            exe.run(built["startup"])
            bias_before = np.array(scope.find_var(bias_name), np.float32)
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
        # what the traffic holds: nothing but this program has run yet
        window_peak = memory_peak(ctx.devices)
        bias_after = np.array(scope.find_var(bias_name), np.float32)
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
    # [steps, E] and [steps, sparse layers], of every chunk run
    all_loads = np.concatenate([np.asarray(l.result()).reshape(K, -1)
                                for l, _ in fetched])
    by_layer = np.concatenate([
        np.stack([np.asarray(r.result()).reshape(K) for r in rs], axis=1)
        for _, rs in fetched[before:]])
    loads = all_loads[before * K:]
    taken = by_layer[:, 0]
    peak = np.asarray(loads.max(axis=1) / loads.mean(axis=1))
    steps = K * (len(win["done"]) + 1)
    checks = {"losses_finite": bool(np.all(np.isfinite(losses))),
              "window_compiles_zero": window_compiles == 0,
              "every_token_routed": bool(
                  len(loads) == steps
                  and (loads.sum(axis=1) == top_k * rows * S).all()),
              "products_took_the_held_rows": bool(
                  len(taken) == steps
                  and (taken == loads[:, first:first + held].sum(axis=1))
                  .all()),
              "router_bias_carried": bool(
                  len(all_loads) == K * source.handed and np.array_equal(
                      bias_after, _bias_by_the_rule(
                          bias_before, all_loads,
                          cfg["optimizer"]["router_bias_update_speed"])))}
    held_share = taken / float(top_k * rows * S)
    name = next(iter(t["end_to_end"]))
    blocks = {"visited": visited, "full_causal": whole} if whole else None
    chunk0 = source.chunks[0]
    # the executable's first chunk, whole: what a comparison that follows
    # the scan to its LAST step replays (`compare_lm_delta_share`, PR 48)
    chunk_rows = tuple(
        np.asarray(chunk0[built[k]])[:K].reshape(K * rows, S)
        for k in ("token_feed", "label_feed"))
    fed = tuple(v[:2 * rows] for v in chunk_rows)
    return {
        "t_open": t_open, "checks": checks,
        "attempted": len(win["done"]) + 1, "failed": 0,
        "end_to_end": {k: reading[v] for k, v in t["end_to_end"].items()},
        "setup_compile": setup_compile,
        "window_s": seconds, "items_per_chunk": items, "reading": reading,
        "rate_items_per_s": reading[t["end_to_end"][name]],
        "steps_in_window": steps, "tokens_per_step": rows * S,
        "host_dispatch_s": win["dispatch"], "scopes": by_scope,
        "expert_load_max_over_mean": float(statistics.median(peak)),
        "held_rows_share": float(statistics.median(held_share)),
        "held_rows_by_layer": by_layer.tolist(),
        "window_blocks": blocks, "window_peak_bytes": int(window_peak),
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
                   "window_blocks": blocks,
                   "window_peak_bytes": int(window_peak),
                   "steps_run": int(len(all_loads)),
                   "scopes": tokens_kind._scope_detail(by_scope)},
    }, fed, {"losses": losses[:2], "chunk_losses": losses[:K],
             "chunk_rows": chunk_rows}
