"""Traffic kind `train`: K-step scans of a training program, dispatched one
chunk ahead (depth-1 fencing: chunk i+1 is dispatched before chunk i's loss
is waited for), fed either through `datapipe` from a RecordIO file
(`"input": "pipe"`) or from stacked feeds already on the device(s)
(`"input": "resident"`). One chip runs `Executor`, several run
`ParallelExecutor` over all of them (dp). Every number that `cpu_count` or
an `auto` would otherwise pick is a key of the traffic file.

One reading per chunk: its images over the time since the chunk before it
completed (`chipbench/timeline.py`); the cell's rate is their median.
"""

import os
import time

import numpy as np

from chipbench import compare, programs, timeline
from chipbench.harness import note, span


# ------------------------------------------------------------------ records
def decode_record(rec):
    """One RecordIO record -> one pre-batched feed dict (runs in the
    datapipe's decode worker processes, which never touch jax). The record
    describes itself: int32 [n, a, b, c], the uint8 pixels, int32 labels."""
    n, a, b, c = (int(v) for v in np.frombuffer(rec[:16], np.int32))
    size = n * a * b * c
    return {"data_u8": np.frombuffer(rec[16:16 + size], np.uint8).reshape(
                n, a, b, c),
            "label": np.frombuffer(rec[16 + size:], np.int32).reshape(n, 1)}


def records_file(ctx, batch, shape):
    """The RecordIO file of `distinct_records` pre-batched uint8 records,
    made from the seed once and kept under the checkout (keyed by seed and
    shape); the pipe loops over it with `pass_num`. One seeded block of
    pixels is rolled by a seeded offset per record: distinct records for
    the price of a copy each."""
    from paddle_tpu import recordio

    n = int(ctx.traffic["distinct_records"])
    tag = "x".join(str(v) for v in (batch, *shape))
    keep = os.path.join(ctx.workdir, "records")
    os.makedirs(keep, exist_ok=True)
    path = os.path.join(keep, f"seed{ctx.seed}-{tag}-n{n}.recordio")
    made = False
    if not os.path.exists(path):
        rs = np.random.default_rng(ctx.seed)
        size = batch * int(np.prod(shape))
        block = rs.integers(0, 256, size, dtype=np.uint8)
        offsets = rs.integers(1, size, n)
        head = np.asarray((batch, *shape), np.int32).tobytes()
        tmp = path + ".tmp"
        if os.path.exists(tmp):
            os.remove(tmp)     # the native writer appends
        with recordio.Writer(tmp, max_num_records=2) as w:
            for off in offsets:
                lbl = rs.integers(0, ctx.cfg["num_classes"],
                                  (batch, 1)).astype(np.int32)
                w.write(head + np.roll(block, int(off)).tobytes()
                        + lbl.tobytes())
        os.replace(tmp, path)
        made = True
        old = sorted((os.path.join(keep, f) for f in os.listdir(keep)
                      if f.endswith(".recordio")), key=os.path.getmtime)
        for stale in old[:-int(ctx.traffic.get("records_kept", 3))]:
            os.remove(stale)
    return path, made


# ------------------------------------------------------------------ sources
class PipeSource:
    """Chunks through datapipe: RecordIO -> process decode workers -> shm
    ring -> AsyncDeviceFeeder (uint8 wire, donated chunks)."""

    def __init__(self, ctx, place, batch, shape):
        from paddle_tpu import datapipe

        t = ctx.traffic
        self.path, self.made = records_file(ctx, batch, shape)
        self.pipe = (datapipe.DataPipe
                     .from_recordio(self.path, pass_num=int(t["pass_num"]),
                                    batch_read=int(t["batch_read"]))
                     .map(decode_record, num_workers=int(t["decode_workers"]),
                          processes=True)
                     .prefetch_to_device(
                         place=place, chunk=int(t["steps_per_chunk"]),
                         capacity=int(t["feeder_capacity"]),
                         transfer_threads=int(t["transfer_lanes"])))
        self.it = iter(self.pipe)
        self.markers = None

    def next(self):
        chunk = next(self.it)
        if self.markers is None:
            from paddle_tpu.datapipe.transfer import DONATE_KEY, WIRE_KEY

            self.markers = {k: chunk[k] for k in (WIRE_KEY, DONATE_KEY)
                            if k in chunk}
        return chunk

    def stats_delta(self):
        return self.pipe.stats_delta()

    def stats(self):
        return self.pipe.stats()

    def close(self):
        self.it.close()
        self.pipe.close()


class ResidentSource:
    """The same stacked feeds every chunk, made on the device(s) from the
    seed in one jitted call; with `fresh=True` each chunk is a device-side
    copy carrying the pipe's markers, so the pipe's own compiled scan
    (donated, uint8 wire) runs on it: the keep-up window."""

    def __init__(self, ctx, K, batch, shape, sharding=None, markers=None):
        import jax
        import jax.numpy as jnp

        classes = ctx.cfg["num_classes"]

        def make(key):
            k1, k2 = jax.random.split(key)
            x = jax.random.bits(k1, (K, batch, *shape), jnp.uint8)
            y = jax.random.randint(k2, (K, batch, 1), 0, classes,
                                   dtype=jnp.int32)
            return {"data_u8": x, "label": y}

        kw = {} if sharding is None else {"out_shardings": sharding}
        key = jax.random.PRNGKey(ctx.seed % (2 ** 31))
        self.feeds = jax.block_until_ready(jax.jit(make, **kw)(key))
        self.markers = markers
        # committed outputs, like the chunks the feeder device_put
        out = sharding or jax.sharding.SingleDeviceSharding(ctx.devices[0])
        self.copy = jax.jit(lambda d: {k: jnp.copy(v) for k, v in d.items()},
                            out_shardings=out)
        if markers is not None:
            jax.block_until_ready(self.copy(self.feeds))

    def next(self):
        if self.markers is None:
            return self.feeds
        return dict(self.copy(self.feeds), **self.markers)

    def stats_delta(self):
        return None

    def close(self):
        pass


# --------------------------------------------------------------------- loop
def run_chunks(run_fn, source, until, jax):
    """Depth-1 loop. `until(n_done, t_last_done)` says when to stop.
    Returns completion times, per-chunk feeder waits, host dispatch times,
    the pipe's per-chunk stage deltas and the loss futures."""
    done, waits, disp, deltas, futs = [], [], [], [], []
    pending = None
    while True:
        with span("feeder_next"):
            t = time.perf_counter()
            feed = source.next()
            waits.append(time.perf_counter() - t)
        with span("executor_run"):
            t = time.perf_counter()
            fut = run_fn(feed)
            disp.append(time.perf_counter() - t)
        if pending is not None:
            with span("fetch_resolve"):
                jax.block_until_ready(pending.value)
                done.append(time.perf_counter())
            futs.append(pending)
            deltas.append(source.stats_delta())
        pending = fut
        if done and until(len(done), done[-1]):
            break
    with span("fetch_resolve"):
        jax.block_until_ready(pending.value)
        t_last = time.perf_counter()
    futs.append(pending)
    return dict(done=done, waits=waits, dispatch=disp, deltas=deltas,
                futs=futs, t_last=t_last)


def losses_of(futs):
    return [float(v) for f in futs
            for v in np.asarray(f.result()).reshape(-1)]


def run(ctx):
    fluid, jax, t, cfg = ctx.fluid, ctx.jax, ctx.traffic, ctx.cfg
    from paddle_tpu import amp

    K = int(t["steps_per_chunk"])
    batch = int(cfg["batch_per_chip"]) * ctx.chips
    shape = programs.image_shape(cfg)
    # under the tests' explicit CPU pin TPUPlace(0) is host device 0
    place = fluid.TPUPlace(0)
    if cfg.get("amp"):
        amp.enable(cfg["amp"])
    try:
        return _run(ctx, fluid, jax, t, cfg, K, batch, shape, place)
    finally:
        amp.disable()


def _run(ctx, fluid, jax, t, cfg, K, batch, shape, place):
    setup, log = ctx.setup, ctx.log
    with setup.item("reference_comparison"):
        ref = compare.against_reference(fluid, cfg, ctx.builder, place,
                                        ctx.seed)
    with setup.item("program_build"):
        built = ctx.builder.build(fluid, cfg, ctx.seed)
    scope = fluid.Scope()
    source = keepup = None
    try:
        with fluid.scope_guard(scope):
            with setup.item("startup_program"):
                exe = fluid.Executor(place)
                exe.run(built["startup"])
            sharding = None
            if ctx.chips > 1:
                pe = fluid.ParallelExecutor(
                    use_tpu=True, loss_name=built["loss"].name,
                    main_program=built["prog"], devices=ctx.devices)
                from jax.sharding import Mesh, NamedSharding, PartitionSpec

                mesh = Mesh(np.array(ctx.devices), ("dp",))
                sharding = NamedSharding(mesh, PartitionSpec(None, "dp"))

                def run_fn(feed):
                    return pe.run([built["loss"]], feed=feed, iters=K,
                                  async_fetch=True)[0]
            else:
                def run_fn(feed):
                    return exe.run(built["prog"], feed=feed,
                                   fetch_list=[built["loss"]], iters=K,
                                   async_fetch=True)[0]
            with setup.item("records_made" if t["input"] == "pipe"
                            else "feeds_made_on_device"):
                if t["input"] == "pipe":
                    source = PipeSource(ctx, place, batch, shape)
                else:
                    source = ResidentSource(ctx, K, batch, shape, sharding)
            records_made = getattr(source, "made", None)
            # the first TWO scan calls each compile (the second sees its
            # own donated outputs as inputs); then the feeder's prefetch is
            # drained so that no chunk staged during set-up is timed
            mark = log.mark()
            t_w = time.perf_counter()
            warm = run_chunks(run_fn, source,
                              lambda n, _t: n >= int(t["warmup_chunks"]),
                              jax)
            warm_compile = log.since(mark)
            setup.add("warmup_compile_or_cache_load",
                      warm_compile["seconds"])
            setup.add("warmup_chunks_lowering_and_run",
                      time.perf_counter() - t_w - warm_compile["seconds"])
            drain = int(t.get("drain_chunks", 0))
            if drain:
                with setup.item("feeder_prefetch_drained"):
                    run_chunks(run_fn, source,
                               lambda n, _t: n >= drain, jax)
            setup_compile = log.since(0)

            # ---------------------------------------------------- window
            n_trace = int(t["trace_chunks"])
            ctx.tracer.start()
            mark = log.mark()
            t_open = time.perf_counter()
            if ctx.trace:
                # n_trace chunks in all: the last is fenced after the loop
                win = run_chunks(run_fn, source,
                                 lambda n, _t: n >= max(1, n_trace - 1), jax)
            else:
                win = run_chunks(
                    run_fn, source,
                    lambda n, td: td - t_open >= ctx.seconds, jax)
            note("window done", ctx.t_start)
            ctx.tracer.stop()
            window_compiles = log.since(mark)["requests"]
            seconds = (win["t_last"] - t_open) if ctx.trace else ctx.seconds
            items = K * batch
            done = [t_open] + win["done"] + [win["t_last"]]
            # the traced window starts idle and is short, so its first
            # chunk counts; the timed window reads from its first
            # completion on
            reading = timeline.train_reading(
                done if ctx.trace else win["done"], items,
                t_open if ctx.trace else win["done"][0], 1e9)
            losses = losses_of(warm["futs"] + win["futs"])
            pipe_stats = source.stats() if t["input"] == "pipe" else None

            # ------------------------ keep-up window (traced pipe run only)
            keep = None
            if ctx.trace and t["input"] == "pipe" and t.get("keepup_chunks"):
                markers = source.markers
                source.close()
                source = None
                keepup = ResidentSource(ctx, K, batch, shape, sharding,
                                        markers=markers)
                nk = int(t["keepup_chunks"])
                note("keep-up window", ctx.t_start)
                mark = log.mark()
                kw = run_chunks(run_fn, keepup, lambda n, _t: n >= nk + 1,
                                jax)
                keep = timeline.train_reading(
                    kw["done"], items, kw["done"][0], 1e9)
                keep["compiles"] = log.since(mark)["slowest"]
                losses += losses_of(kw["futs"])
    finally:
        for s in (source, keepup):
            if s is not None:
                s.close()
    if ctx.dump:
        _dump(ctx, t_open, win, items)
    finite = bool(np.all(np.isfinite(losses)))
    checks = {"reference": bool(ref["ok"]), "losses_finite": finite,
              "window_compiles_zero": window_compiles == 0}
    name = next(iter(t["end_to_end"]))
    return {
        "t_open": t_open, "correct": all(checks.values()), "checks": checks,
        "attempted": len(win["done"]) + 1, "failed": 0,
        "end_to_end": {k: reading[v] for k, v in t["end_to_end"].items()},
        "reference": ref, "setup_compile": setup_compile,
        "window_s": seconds, "items_per_chunk": items, "reading": reading,
        "rate_items_per_s": reading[t["end_to_end"][name]],
        "steps_in_window": K * (len(win["done"]) + 1),
        "input_wait_s": sum(win["waits"]), "host_dispatch_s": win["dispatch"],
        "keepup": keep, "pipe_stats": pipe_stats,
        "plan": ctx.builder.reference.layer_plan(cfg), "train": True,
        "batch": batch,
        "detail": {"reading": reading, "keepup": keep,
                   "window_compiles": window_compiles,
                   "first_loss": losses[0], "last_loss": losses[-1],
                   "pipe_deltas_first": win["deltas"][:2],
                   "records_made_this_run": records_made,
                   "bottleneck_stage": (pipe_stats or {}).get(
                       "bottleneck_stage")},
    }


def _dump(ctx, t_open, win, items):
    import json

    os.makedirs(ctx.dump, exist_ok=True)
    path = os.path.join(
        ctx.dump, f"{ctx.cell['name']}-seed{ctx.seed}-chunks.json")
    with open(path, "w") as f:
        json.dump({"workload": ctx.cell["name"], "seed": ctx.seed,
                   "seconds": ctx.seconds, "items_per_chunk": items,
                   "done_s": [d - t_open for d in win["done"]],
                   "feeder_wait_s": win["waits"],
                   "host_dispatch_s": win["dispatch"],
                   "pipe_deltas": win["deltas"]}, f)
