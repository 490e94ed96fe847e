#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of ResNet-50 (depth 50, 1000 classes, 224x224x3 NHWC, batch 128
per chip, bf16 AMP with fp32 masters, Momentum; weights random from a seed):

  train      a seeded RecordIO file -> datapipe (native reader built from
             recordio.cc, process decode workers, shm ring, uint8 wire,
             async device feeder) -> Executor(TPUPlace(0)).run(feed=pipe,
             iters=K), then two plain single-step runs. Device first, pipe
             second: the decode workers are forked from a parent that
             already holds the TPU client, the order bench.py uses.
  serve      save_inference_model of the for_test clone ->
             serve.Server.from_inference_model behind make_http_server ->
             POST /v1/infer, compared with Executor.run on the same rows.
  kernels    the flash-attention Pallas kernels (forward, dK/dV, dQ)
             compiled by Mosaic (the lowered text must hold the TPU custom
             calls) and compared with a float32 jax.numpy reference; the
             grouped-matmul kernels (forward, d lhs, d rhs) at reduced
             rows with uneven groups, compared with lax.ragged_dot.
  multichip  with more than one local chip: the same ResNet-50 through
             ParallelExecutor over all of them, then one dp x mp + ZeRO-1
             step. On one chip the result says "not run: 1 device".

One process, no child that needs the chip, JAX_PLATFORMS never set here. A
phase that raises ends the run with a traceback and a non-zero code; a
failed check is reported in the result and exits 1. Without a TPU the
script exits 2 before it prints any result. Stdout is two lines, one JSON
object each: the report (device, per-phase checks and compile counts,
persistent-cache hits, peak memory; "claim" is null, it measures nothing
it could claim), then the verdict the driver reads, which holds "ok" and
"device" {"platform", "kind", "count"} and no other key.

tests/test_chip_smoke.py runs the same phase functions at TINY sizes under
the explicit CPU pin, kernels interpreted, labelled "rehearsal": true.
"""

import contextlib
import dataclasses
import faulthandler
import http.client
import json
import math
import multiprocessing
import os
import sys
import tempfile
import threading
import time

import numpy as np

T_START = time.time()

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_WRITE = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass(frozen=True)
class Sizes:
    depth: int = 50
    classes: int = 1000
    image: int = 224
    batch: int = 128          # per chip
    k: int = 3                # steps per scan dispatch (iters=K)
    chunks: int = 4           # 2 warm-up calls, 2 that must not compile
    distinct: int = 2         # distinct batches, repeated, so loss can fall
    serve_requests: int = 8
    # The first two losses of this seeded program in float32 without AMP,
    # computed on the CPU (XLA:CPU, jax 0.9.0, PR 21; reference_losses()
    # below regenerates them, tests/test_chip_smoke.py holds them). The
    # Xavier-initialised 1000-way head starts 0.56 above ln 1000 = 6.908,
    # so ln(classes) itself is not the reference. bf16 AMP on the v5e
    # landed 0.016 and 0.020 below these (PR 21); the bound is 5x that.
    first_losses_ref: tuple = (7.4719, 7.5962)
    first_loss_tol: float = 0.1
    # (shape, block): the olmoe_1b_7b cell's attention at the blocks its
    # op picks (ops/lm_ops.py), and a row that the kernel's default block
    # does not divide (padded queries and keys, the last key block masked)
    flash: tuple = (((2, 16, 4096, 128), 1024), ((2, 16, 1000, 128), 256))
    # (rows, K, M, groups): the cell's gate / up and down products at an
    # eighth of its rows, tiles as `grouped.tiles_for` picks them
    grouped: tuple = ((8192, 2048, 1024, 64), (8192, 1024, 2048, 64))
    # overrides of chipbench/configs/xing4_0_29b_a4b.json for the
    # share_model phase: a small model of that kind (latent attention with
    # keys of 192 and values of 128, 4 residual streams, 2 of 8 experts
    # held, a shared expert, the module; 1 dense + 1 expert layer) at
    # sizes both kernel families take:
    # 512 tokens x top-2 = 1024 rows, K = 256 and 128
    share_model: dict = dataclasses.field(default_factory=lambda: dict(
        hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
        num_hidden_layers=2, n_routed_experts=2, num_experts_per_tok=2,
        num_attention_heads=2, num_key_value_heads=2, q_lora_rank=128,
        kv_lora_rank=128, vocab_size=512, sequence_length=512,
        deployment=dict(n_routed_experts=8, first_expert=2)))


FULL = Sizes()
# the CPU rehearsal (tests/test_chip_smoke.py): every phase, nothing at
# width. With 8 images per batch bf16 AMP drifts further from float32
# (0.17 at the second step), hence the wider bound.
TINY = Sizes(depth=18, classes=16, image=32, batch=8, k=2, chunks=4,
             distinct=2, serve_requests=8,
             first_losses_ref=(4.1299, 3.4761), first_loss_tol=0.3,
             flash=(((1, 2, 128, 64), 64), ((1, 2, 100, 64), 32)),
             grouped=((384, 128, 256, 8),),
             share_model=dict(FULL.share_model, sequence_length=128))


def say(msg):
    print(f"[chip_smoke +{time.time() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


class CompileLog:
    """XLA backend compiles as JAX itself reports them (jax.monitoring).

    The repo's L1 counters see one miss per cache key; they cannot see a
    second backend compile of the same jit (a layout re-specialisation) or
    a compile served from JAX's persistent cache. One backend_compile
    event is one compile request that reached the backend; a cache_hits
    event just before it means the persistent cache served it."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._pending = threading.local()
        self.events = []   # (fun_name, seconds, served_from_persistent)
        self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_kw):
        if event == CACHE_HIT:
            self._pending.hit = True
        elif event == CACHE_WRITE:
            with self._lock:
                self.writes += 1

    def _on_duration(self, event, secs, **kw):
        if event != BACKEND_COMPILE:
            return
        hit = getattr(self._pending, "hit", False)
        self._pending.hit = False
        with self._lock:
            self.events.append((str(kw.get("fun_name", "?")), secs, hit))

    def close(self):
        import jax

        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def mark(self):
        with self._lock:
            return len(self.events), self.writes

    def since(self, mark, names=None):
        """Compile counts since mark(); names restricts to those jitted
        function names (the executors' step functions)."""
        n0, w0 = mark
        with self._lock:
            ev = [e for e in self.events[n0:]
                  if names is None or e[0] in names]
            writes = self.writes - w0
        hits = sum(1 for e in ev if e[2])
        slow = sorted(ev, key=lambda e: -e[1])[:3]
        return {"requests": len(ev), "persistent_hits": hits,
                "backend_compiles": len(ev) - hits,
                "persistent_writes": writes,
                "compile_s": round(sum(e[1] for e in ev), 2),
                "slowest": [[e[0], round(e[1], 2)] for e in slow]}


# the jitted functions that are an executor's compiled step: step and the
# K-step scan multi (core/executor_core.py), or the wrapper a single step
# was last given (datapipe/transfer.py wired, health/stats.py health_step)
STEP_NAMES = ("jit(step)", "jit(multi)", "jit(wired)", "jit(health_step)")


@contextlib.contextmanager
def captured_steps(compiled=False):
    """Texts of the steps the executors compile inside the block, taken at
    the executors' own probe hook (executor_core.compile_step_fn(probe=):
    the one point where the jitted step and its live arguments coexist):
    the lowered StableHLO, or with compiled=True the compiled HLO, which
    is where the collectives GSPMD inserts are visible."""
    from paddle_tpu import flags, monitor

    texts = []
    real = monitor.compile_probe

    def capturing(_fingerprint):
        def probe(jitted, args):
            lowered = jitted.lower(*args)
            texts.append(lowered.compile().as_text() if compiled
                         else lowered.as_text())

        return probe

    was = flags.get("monitor_hlo_cost")
    flags.set("monitor_hlo_cost", True)
    monitor.compile_probe = capturing
    try:
        yield texts
    finally:
        monitor.compile_probe = real
        flags.set("monitor_hlo_cost", was)


def build_program(fluid, sizes):
    """The program bench.py's headline builds: ResNet fed RAW uint8 pixels,
    cast + normalized on device, int32 labels, Momentum. Plus a per-image
    checksum of the uint8 batch as it arrived on the device, and the
    for_test clone taken before the optimizer is appended."""
    from paddle_tpu.models.resnet import resnet_imagenet

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        raw = fluid.layers.data(
            name="data_u8", shape=[sizes.image, sizes.image, 3],
            dtype="uint8")
        img = fluid.layers.scale(
            fluid.layers.cast(raw, "float32"), scale=1.0 / 255.0)
        label = fluid.layers.data(name="label", shape=[1], dtype="int32")
        csum = fluid.layers.reduce_sum(
            fluid.layers.cast(raw, "int32"), dim=[1, 2, 3])
        predict = resnet_imagenet(img, sizes.classes, depth=sizes.depth,
                                  layout="NHWC")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=predict, label=label))
        test_prog = prog.clone(for_test=True)
        fluid.optimizer.Momentum(
            learning_rate=0.01, momentum=0.9).minimize(loss)
        prog.random_seed = startup.random_seed = 21
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                csum=csum, predict=predict)


def seeded_batches(sizes, n, batch):
    rs = np.random.RandomState(21)
    shape = (batch, sizes.image, sizes.image, 3)
    xs = [rs.randint(0, 256, shape, dtype=np.uint8) for _ in range(n)]
    ys = [rs.randint(0, sizes.classes, (batch, 1)).astype(np.int32)
          for _ in range(n)]
    return xs, ys


def host_checksum(x):
    return x.reshape(x.shape[0], -1).sum(axis=1, dtype=np.int64)


def decode_record(rec):
    """One RecordIO record -> one pre-batched feed dict (runs in the
    datapipe's decode worker processes). The record describes itself:
    int32 [batch, h, w, c], the uint8 pixels, the int32 labels."""
    b, h, w, c = np.frombuffer(rec[:16], np.int32)
    n = int(b) * int(h) * int(w) * int(c)
    return {"data_u8": np.frombuffer(rec[16:16 + n], np.uint8).reshape(
                b, h, w, c),
            "label": np.frombuffer(rec[16 + n:], np.int32).reshape(b, 1)}


def reference_losses(fluid, sizes, place):
    """Sizes.first_losses_ref: the same seeded program and batches in
    float32 without AMP, one plain step per loss."""
    from paddle_tpu import amp

    amp.disable()
    built = build_program(fluid, sizes)
    xs, ys = seeded_batches(sizes, sizes.distinct, sizes.batch)
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(place)
        exe.run(built["startup"])
        for s in range(len(sizes.first_losses_ref)):
            lv, = exe.run(built["prog"], fetch_list=[built["loss"]], feed={
                "data_u8": xs[s % sizes.distinct],
                "label": ys[s % sizes.distinct]})
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    return losses


def param_count(prog):
    from paddle_tpu.core.framework import Parameter

    return int(sum(np.prod(v.shape) for v in prog.global_block().vars.values()
                   if isinstance(v, Parameter)))


# --------------------------------------------------------------------- train
def phase_train(fluid, sizes, place, log, workdir):
    from paddle_tpu import amp, datapipe, recordio
    from paddle_tpu.core.places import jax_device_for
    from paddle_tpu.native import build as native_build

    t0 = time.time()
    amp.enable("bfloat16")
    built = build_program(fluid, sizes)
    prog, loss, csum = built["prog"], built["loss"], built["csum"]
    dev = jax_device_for(place)
    K, R = sizes.k, sizes.distinct
    steps = K * sizes.chunks

    xs, ys = seeded_batches(sizes, R, sizes.batch)
    sums = [host_checksum(x) for x in xs]
    path = os.path.join(workdir, "train.recordio")
    head = np.asarray(xs[0].shape, np.int32).tobytes()
    with recordio.Writer(path, max_num_records=2) as w:
        for s in range(steps):
            w.write(head + xs[s % R].tobytes() + ys[s % R].tobytes())
    lib = native_build.recordio_lib()
    say(f"train: wrote {steps} records "
        f"({os.path.getsize(path) / 1e6:.0f} MB) with {os.path.basename(lib)}")

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        mark = log.mark()
        exe.run(built["startup"])           # device first ...
        startup_compiles = log.since(mark)
        pipe = (datapipe.DataPipe           # ... pipe second
                .from_recordio(path, batch_read=2)
                .map(decode_record, num_workers=2, processes=True)
                .prefetch_to_device(place=place, chunk=K, capacity=4,
                                    transfer_threads=4))
        losses, per_call, sums_ok = [], [], True
        for c in range(sizes.chunks):
            mark = log.mark()
            lv, sv = exe.run(prog, feed=pipe, fetch_list=[loss, csum])
            per_call.append(log.since(mark, STEP_NAMES))
            losses += [float(v) for v in np.asarray(lv).reshape(K, -1)[:, 0]]
            sv = np.asarray(sv).reshape(K, -1)
            for i in range(K):
                sums_ok &= np.array_equal(sv[i], sums[(c * K + i) % R])
            say(f"train: chunk {c} losses {losses[-K:]} "
                f"compiles {per_call[-1]['requests']}")
        stats = pipe.stats()
        wire = pipe.wire_spec
        pipe.close()
        time.sleep(0.2)
        workers_left = [p.name for p in multiprocessing.active_children()
                        if p.name.startswith("datapipe-")]
        plain = []
        for j in range(2):
            mark = log.mark()
            lv, sv = exe.run(
                prog, feed={"data_u8": xs[j % R], "label": ys[j % R]},
                fetch_list=[loss, csum])
            plain.append(log.since(mark, STEP_NAMES))
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
            sums_ok &= np.array_equal(np.asarray(sv).reshape(-1), sums[j % R])
        params = [scope.find_var(n) for n, v in
                  prog.global_block().vars.items()
                  if v.persistable and scope.find_var(n) is not None]
        on_device = [p.devices() == {dev} for p in params
                     if hasattr(p, "devices")]

    warm = per_call[:2]
    checks = {
        "first_losses_match_float32_reference": all(
            abs(got - want) <= sizes.first_loss_tol
            for got, want in zip(losses, sizes.first_losses_ref)),
        "loss_finite": bool(np.all(np.isfinite(losses))),
        "loss_falls": float(np.mean(losses[-K:])) < losses[0],
        "params_on_device": bool(on_device) and all(on_device),
        "device_checksum_equals_host": bool(sums_ok),
        "no_backend_compile_after_warmup":
            all(c["backend_compiles"] == 0 for c in per_call[2:])
            and plain[1]["backend_compiles"] == 0,
        "decode_workers_stopped": not workers_left,
        "no_leaked_shm": len(datapipe.live_segments()) == 0,
    }
    return {
        "wall_s": round(time.time() - t0, 1),
        "checks": checks,
        "losses": [round(v, 4) for v in losses],
        "steps": len(losses),
        "startup_compiles": startup_compiles,
        "warmup_step_compiles": {
            "requests": sum(c["requests"] for c in warm),
            "backend_compiles": sum(c["backend_compiles"] for c in warm),
            "persistent_hits": sum(c["persistent_hits"] for c in warm),
            "compile_s": round(sum(c["compile_s"] for c in warm), 2)},
        "step_compiles_per_call": [c["requests"] for c in per_call],
        "plain_step_compiles": [c["requests"] for c in plain],
        "params_checked": len(on_device),
        "recordio_lib": {
            "file": os.path.basename(lib),
            "built_this_run": os.path.getmtime(lib) >= T_START - 1.0},
        "pipe": {"wire": wire.describe() if wire is not None else None,
                 "bottleneck_stage": stats.get("bottleneck_stage")},
    }, dict(built, scope=scope, exe=exe, xs=xs)


# --------------------------------------------------------------------- serve
# The server pads 1-4 rows to its bucket and Executor.run takes them as
# they are, so the same row may go through convolutions of another batch
# size, and under bf16 AMP (8 mantissa bits) two tilings may round
# differently. On the v5e the two were bitwise equal (PR 21); the bound
# leaves room for five bf16 roundings, 5 * 2^-8 of a softmax probability,
# plus 1e-5.
SERVE_RTOL, SERVE_ATOL = 2e-2, 1e-5


def phase_serve(fluid, sizes, place, log, workdir, trained):
    from paddle_tpu import serve
    from paddle_tpu.serve.http import make_http_server

    t0 = time.time()
    scope, exe = trained["scope"], trained["exe"]
    test_prog, predict = trained["test_prog"], trained["predict"]
    model_dir = os.path.join(workdir, "inference_model")
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(
            model_dir, ["data_u8"], [predict], exe, main_program=test_prog)
    server = serve.Server.from_inference_model(
        model_dir, place=place, config=serve.ServeConfig(max_batch=8))
    mark = log.mark()
    server.start()
    warm = log.since(mark, STEP_NAMES)
    httpd = make_http_server(server, port=0)
    thread = threading.Thread(target=httpd.serve_forever,
                              name="chip-smoke-http", daemon=True)
    thread.start()
    statuses, worst = [], 0.0
    rows_all = np.concatenate(trained["xs"], axis=0)
    try:
        port = httpd.server_address[1]
        for i in range(sizes.serve_requests):
            n = 1 + i % 4
            rows = rows_all[(3 * i) % 16:(3 * i) % 16 + n]
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
            try:
                conn.request("POST", "/v1/infer", body=json.dumps(
                    {"inputs": {"data_u8": rows.tolist()}}))
                resp = conn.getresponse()
                body = resp.read()
            finally:
                conn.close()
            statuses.append(resp.status)
            if resp.status != 200:
                say(f"serve: request {i} -> {resp.status} {body[:200]!r}")
                continue
            got = np.asarray(json.loads(body)["outputs"][0], np.float32)
            with fluid.scope_guard(scope):
                want, = exe.run(test_prog, feed={"data_u8": rows},
                                fetch_list=[predict])
            want = np.asarray(want, np.float32)
            if got.shape != want.shape:
                worst = float("inf")
                continue
            worst = max(worst, float(np.max(
                np.abs(got - want) / (np.abs(want) + SERVE_ATOL))))
        stats = server.stats()
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()
        thread.join(timeout=10.0)
    checks = {
        "all_200": statuses == [200] * sizes.serve_requests,
        "matches_executor_run": worst <= SERVE_RTOL,
        "steady_state_compiles_zero": stats["steady_state_compiles"] == 0,
        "http_thread_stopped": not thread.is_alive(),
    }
    return {"wall_s": round(time.time() - t0, 1), "checks": checks,
            "requests": len(statuses), "buckets": stats["buckets"],
            "warmup_step_compiles": warm,
            "max_rel_diff_vs_executor": round(worst, 5),
            "tolerance": {"rtol": SERVE_RTOL, "atol": SERVE_ATOL}}


# ------------------------------------------------------------------- kernels
# flash_attention: q/k/v and the output are bf16 and the kernel rounds the
# softmax weights to bf16 before p @ v, so against a float32 reference the
# forward carries a few roundings of 2^-9 relative to the largest value;
# the backward is two more Pallas kernels (dK/dV and dQ) that recompute the
# scores from the saved logsumexp in float32 and round p and ds to bf16 as
# they enter the MXU. Errors are max |a - ref| over max |ref|; on the v5e
# they were 0.0024 forward and 0.0063 backward at worst (PR 21, and again
# with these kernels, PR 27: 0.0051 at 4096, 0.0063 at the padded 1000)
# and the bounds are 4x that.
FLASH_FWD_TOL, FLASH_GRAD_TOL = 1e-2, 2.5e-2


def _dense_attention(q, k, v, causal):
    """Plain float32 attention on one head, [S, D]."""
    import jax.numpy as jnp

    s = (q @ k.T) / math.sqrt(q.shape[-1])
    if causal:
        i = jnp.arange(s.shape[0])
        s = jnp.where(i[:, None] >= i[None, :], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return (p / jnp.sum(p, axis=-1, keepdims=True)) @ v


def _rel_err(a, b):
    """max |a - b| over max |b|, in float32."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _flash_case(shape, block, want_mosaic):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.flash import flash_attention

    B, H, S, D = shape
    rs = np.random.RandomState(S)
    q, k, v = (jnp.asarray(rs.randn(*shape), jnp.bfloat16) for _ in range(3))
    ct = jnp.asarray(rs.randn(*shape), jnp.float32)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=block,
                              block_k=block)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    def ref_loss(q, k, v):
        # one head at a time: the dense [S, S] scores of all B*H heads at
        # S=4096 would not leave room for their gradients
        heads = [t.astype(jnp.float32).reshape(B * H, S, D)
                 for t in (q, k, v)]
        out = jax.lax.map(lambda t: _dense_attention(*t, causal=True),
                          tuple(heads)).reshape(shape)
        return jnp.sum(out * ct), out

    flash_vg = jax.jit(jax.value_and_grad(flash_loss, (0, 1, 2),
                                          has_aux=True))
    # the forward, dK/dV and dQ: three Mosaic calls on the chip, none off it
    calls = flash_vg.lower(q, k, v).as_text().count("@tpu_custom_call")
    (_, out), grads = flash_vg(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = jax.jit(jax.value_and_grad(
            ref_loss, (0, 1, 2), has_aux=True))(q, k, v)

    fwd = _rel_err(out, ref)
    bwd = max(_rel_err(g, r) for g, r in zip(grads, ref_grads))
    return {"shape": list(shape), "block": block, "mosaic": calls > 0,
            "mosaic_calls": calls,
            "fwd_err": round(fwd, 5), "grad_err": round(bwd, 5),
            "ok": (calls >= 3 if want_mosaic else calls == 0)
            and fwd <= FLASH_FWD_TOL and bwd <= FLASH_GRAD_TOL}


# grouped_matmul: bf16 operands, float32 sums, one rounding to bf16, as
# `lax.ragged_dot(..., preferred_element_type=bf16)` has: the two differ
# by the order of the float32 sums, at most an ulp of bf16 (2^-8) of a
# value. Errors are max |a - ref| over max |ref|.
GROUPED_TOL = 1e-2


def _grouped_case(n_rows, k, m, groups, want_mosaic):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.parallel import grouped

    rs = np.random.RandomState(k)
    # uneven groups: a few experts own most rows, some own none, and the
    # boundaries fall inside row tiles
    share = rs.pareto(0.7, groups) * (rs.rand(groups) > 0.1)
    counts = np.floor(share / share.sum() * n_rows).astype(np.int32)
    counts[np.argmax(counts)] += n_rows - counts.sum()
    counts = jnp.asarray(counts)
    lhs, rhs, ct = (jnp.asarray(rs.randn(*shape), jnp.bfloat16) for shape in
                    ((n_rows, k), (groups, k, m), (n_rows, m)))
    tiles = grouped.tiles_for(n_rows, k, m, lhs.dtype)

    def value_and_grads(fn):
        def loss(a, b):
            out = fn(a, b)
            return jnp.sum(out.astype(jnp.float32) * ct), out

        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))

    ours = value_and_grads(
        lambda a, b: grouped.grouped_matmul(a, b, counts, None, tiles))
    # forward, d lhs and d rhs: three Mosaic calls on the chip, none off it
    calls = ours.lower(lhs, rhs).as_text().count("@tpu_custom_call")
    (_, out), grads = ours(lhs, rhs)
    (_, ref), ref_grads = value_and_grads(lambda a, b: lax.ragged_dot(
        a, b, group_sizes=counts, preferred_element_type=a.dtype))(lhs, rhs)

    fwd = _rel_err(out, ref)
    bwd = max(_rel_err(g, r) for g, r in zip(grads, ref_grads))
    return {"shape": [n_rows, k, m, groups], "tiles": list(tiles),
            "empty_groups": int(np.sum(np.asarray(counts) == 0)),
            "largest_over_mean": round(float(counts.max()) * groups
                                       / n_rows, 2),
            "mosaic": calls > 0, "mosaic_calls": calls,
            "fwd_err": round(fwd, 5), "grad_err": round(bwd, 5),
            "ok": (calls >= 3 if want_mosaic else calls == 0)
            and fwd <= GROUPED_TOL and bwd <= GROUPED_TOL}


def phase_kernels(sizes, want_mosaic):
    t0 = time.time()
    out = {"flash_attention": [], "grouped_matmul": []}
    for shape, block in sizes.flash:
        out["flash_attention"].append(_flash_case(shape, block, want_mosaic))
        say(f"kernels: flash {out['flash_attention'][-1]}")
    for shape in sizes.grouped:
        out["grouped_matmul"].append(_grouped_case(*shape, want_mosaic))
        say(f"kernels: grouped {out['grouped_matmul'][-1]}")
    checks = {name: all(c["ok"] for c in cases)
              for name, cases in out.items()}
    return dict(out, wall_s=round(time.time() - t0, 1), checks=checks,
                mosaic_expected=want_mosaic,
                tolerance={"flash_fwd": FLASH_FWD_TOL,
                           "flash_grad": FLASH_GRAD_TOL,
                           "grouped": GROUPED_TOL})


# --------------------------------------------------------------- share_model
SHARE_LOSS_TOL = 0.02       # bf16 AMP against the float32 reference


def phase_share_model(fluid, sizes, place, device):
    """One training step of a small `models/xing4.py` under bf16 AMP on
    the place, through the Executor: both kernel families engaged on the
    chip (the flash kernels at keys of 192 and values of 128, the grouped
    kernels over the held groups' rows), both losses against the plain
    float32 reference on the same weights, the router's counts and the
    rows the products took."""
    import jax.numpy as jnp
    from chipbench.configs import xing4_0_29b_a4b as builder
    from paddle_tpu import amp
    from paddle_tpu.core import executor_core

    t0 = time.time()
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chipbench", "configs",
                           "xing4_0_29b_a4b.json")) as f:
        cfg = json.load(f)
    cfg.update(sizes.share_model)
    S, k = cfg["sequence_length"], cfg["num_experts_per_tok"]
    first, held = cfg["deployment"]["first_expert"], cfg["n_routed_experts"]
    rs = np.random.RandomState(7)
    feed = {"tokens": rs.randint(0, cfg["vocab_size"], (1, S)).astype(
                np.int32),
            "labels": rs.randint(0, cfg["vocab_size"], (1, S)).astype(
                np.int32)}
    built = builder.build(fluid, cfg, 7)
    lowered = executor_core.lowered_counts(built["prog"], device)
    _, load_var, rows_var = built["routing"][0]
    scope = fluid.Scope()
    amp.enable("bfloat16")
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor(place)
            exe.run(built["startup"])
            w0 = {p.name: np.asarray(scope.find_var(p.name), np.float32)
                  for p in built["prog"].global_block().all_parameters()}
            loss, ce, ce_mtp, load, rows = exe.run(
                built["prog"], feed=feed,
                fetch_list=[built["loss"], built["ce"], built["ce_mtp"],
                            load_var, rows_var])
    finally:
        amp.disable()
    ref_loss, rest, _ = builder.reference.loss_and_grads(
        cfg, {n: jnp.asarray(v) for n, v in w0.items()},
        jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]))
    got = [float(np.asarray(v).reshape(-1)[0]) for v in (loss, ce, ce_mtp)]
    want = [float(ref_loss), float(rest[0]), float(rest[1])]
    errs = [abs(a - b) / abs(b) for a, b in zip(got, want)]
    load = np.asarray(load).reshape(-1)
    on_chip = device.platform == "tpu"
    kernels = {n: lowered.get(n, 0) for n in (
        "grouped_matmul_kernel", "flash_attention", "flash_attention_bwd")}
    checks = {
        "losses_match_reference": max(errs) <= SHARE_LOSS_TOL,
        "every_token_routed": int(load.sum()) == k * S,
        "products_took_the_held_rows": int(np.asarray(rows).reshape(-1)[0])
        == int(load[first:first + held].sum()),
        # two expert blocks (a layer and the module), each lowered with a
        # share; three attentions (the dense layer's too)
        "lowered_as_a_share": lowered.get("moe_ffn_held_experts") == 2,
        "kernel_families_engaged": (
            kernels == {"grouped_matmul_kernel": 2, "flash_attention": 3,
                        "flash_attention_bwd": 3}) if on_chip
        else not any(kernels.values())}
    return {"losses": got, "reference": want, "rel_err": errs,
            "tokens_per_expert": load.tolist(),
            "rows_held": int(np.asarray(rows).reshape(-1)[0]),
            "lowered": lowered, "tolerance": SHARE_LOSS_TOL,
            "wall_s": round(time.time() - t0, 1), "checks": checks}


# ----------------------------------------------------------------- multichip
def _distinct_devices(arr):
    return len({s.device for s in arr.addressable_shards})


def phase_multichip(fluid, sizes, log, n_devices):
    """Data parallel over every local chip through ParallelExecutor, then
    one step of the dp x mp + ZeRO-1 layout."""
    from paddle_tpu import amp
    from paddle_tpu.core.framework import Parameter
    from paddle_tpu.parallel_executor import BuildStrategy

    t0 = time.time()
    amp.enable("bfloat16")
    N = n_devices
    xs, ys = seeded_batches(sizes, 1, sizes.batch * N)
    feed = {"data_u8": xs[0], "label": ys[0]}

    # -- dp over all chips, three steps on one repeated global batch
    built = build_program(fluid, sizes)
    prog, loss, csum = built["prog"], built["loss"], built["csum"]
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.TPUPlace(0)).run(built["startup"])
        pe = fluid.ParallelExecutor(use_tpu=True, loss_name=loss.name,
                                    main_program=prog)
        mark = log.mark()
        with captured_steps(compiled=True) as texts:
            lv, sv = pe.run([loss, csum], feed=feed, return_numpy=False)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
        feed_shard_devices = _distinct_devices(sv)
        feed_shard_rows = sorted({s.data.shape[0]
                                  for s in sv.addressable_shards})
        sums_ok = np.array_equal(np.asarray(sv), host_checksum(xs[0]))
        all_reduces = sum(t.count("all-reduce") for t in texts)
        for _ in range(2):
            lv, = pe.run([loss], feed=feed)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        compiles = log.since(mark, STEP_NAMES)
        params = [scope.find_var(n) for n, v in
                  prog.global_block().vars.items()
                  if isinstance(v, Parameter)]
        replicated = [
            _distinct_devices(p) == N
            and all(s.data.shape == p.shape for s in p.addressable_shards)
            for p in params]
    say(f"multichip: dp={N} losses {losses} all-reduce x{all_reduces}")

    # -- dp x mp + ZeRO-1, one step
    mp = 2
    dp = N // mp
    built2 = build_program(fluid, sizes)
    prog2, loss2 = built2["prog"], built2["loss"]
    gb = prog2.global_block()
    fc_w = next(v for v in gb.vars.values() if isinstance(v, Parameter)
                and len(v.shape) == 2 and v.shape[1] == sizes.classes)
    fluid.parallel.set_sharding(fc_w, (None, "mp"))
    bs = BuildStrategy()
    bs.sharded_weight_update = True
    scope2 = fluid.Scope()
    xs2, ys2 = seeded_batches(sizes, 1, sizes.batch * dp)
    with fluid.scope_guard(scope2):
        fluid.Executor(fluid.TPUPlace(0)).run(built2["startup"])
        pe2 = fluid.ParallelExecutor(
            use_tpu=True, loss_name=loss2.name, main_program=prog2,
            build_strategy=bs, mesh_shape={"dp": dp, "mp": mp})
        lv2, = pe2.run([loss2], feed={"data_u8": xs2[0], "label": ys2[0]})
        loss_dpmp = float(np.asarray(lv2).reshape(-1)[0])
        w = scope2.find_var(fc_w.name)
        fc_shard_cols = sorted({s.data.shape[1]
                                for s in w.addressable_shards})
        # zero1 keeps optimizer accumulators in a [dp, shard] layout
        state = [scope2.find_var(n) for n, v in gb.vars.items()
                 if v.persistable and not isinstance(v, Parameter)]
        sharded_state = sum(
            1 for a in state if hasattr(a, "addressable_shards")
            and any(s.data.shape != a.shape for s in a.addressable_shards))
    say(f"multichip: dp={dp} x mp={mp} + zero1 loss {loss_dpmp}")

    checks = {
        "feeds_sharded_over_all_devices":
            feed_shard_devices == N and feed_shard_rows == [sizes.batch],
        "device_checksum_equals_host": bool(sums_ok),
        "params_replicated_on_distinct_devices":
            bool(replicated) and all(replicated),
        "compiled_step_has_all_reduce": all_reduces > 0,
        "loss_finite": bool(np.all(np.isfinite(losses))),
        "loss_falls": losses[-1] < losses[0],
        "dpmp_zero1_loss_finite": bool(np.isfinite(loss_dpmp)),
        "dpmp_fc_weight_split_over_mp":
            fc_shard_cols == [sizes.classes // mp],
        "dpmp_zero1_state_sharded": sharded_state > 0,
    }
    return {"wall_s": round(time.time() - t0, 1), "checks": checks,
            "n_devices": N, "losses": [round(v, 4) for v in losses],
            "params_on_n_distinct_devices": N if all(replicated) else None,
            "params_checked": len(replicated),
            "all_reduce_ops_in_compiled_step": all_reduces,
            "step_compiles": compiles,
            "dpmp": {"mesh": {"dp": dp, "mp": mp}, "zero1": True,
                     "loss": round(loss_dpmp, 4),
                     "fc_weight_shard_cols": fc_shard_cols,
                     "optimizer_state_vars_sharded": sharded_state}}


# ----------------------------------------------------------------------- run
def run_phases(fluid, sizes, device, rehearsal):
    """All phases at `sizes` on `device`; the summary main() prints."""
    import jax

    from paddle_tpu import amp
    from paddle_tpu.cache import place_jax_cache

    log = CompileLog()
    place = fluid.TPUPlace(0)
    want_mosaic = device.platform == "tpu"
    n = len(jax.local_devices())
    phases = {}
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
            say("phase train")
            phases["train"], trained = phase_train(
                fluid, sizes, place, log, workdir)
            say("phase serve")
            phases["serve"] = phase_serve(
                fluid, sizes, place, log, workdir, trained)
            n_params = param_count(trained["prog"])
            del trained
        say("phase kernels")
        phases["kernels"] = phase_kernels(sizes, want_mosaic)
        say("phase share_model")
        phases["share_model"] = phase_share_model(fluid, sizes, place,
                                                  device)
        if n > 1:
            say("phase multichip")
            phases["multichip"] = phase_multichip(fluid, sizes, log, n)
        else:
            phases["multichip"] = "not run: 1 device"
    finally:
        amp.disable()
        log.close()
    total = log.since((0, 0))
    mem = device.memory_stats() or {}
    ok = all(all(p["checks"].values())
             for p in phases.values() if isinstance(p, dict))
    return {
        "ok": bool(ok),
        # emit() repeats "ok" and "device" as the verdict line; ISSUE 21
        # and bench.py name the same facts platform / device_kind / n_devices
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_devices": n,
        "rehearsal": bool(rehearsal),
        "model": {"name": "resnet", "depth": sizes.depth,
                  "classes": sizes.classes, "image": sizes.image,
                  "batch_per_chip": sizes.batch, "params": n_params,
                  "amp": "bfloat16"},
        "phases": phases,
        "compile_cache": {
            "dir": place_jax_cache(),
            "dir_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "compile_requests": total["requests"],
            "hits": total["persistent_hits"],
            "misses": total["persistent_writes"]},
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "wall_s": round(time.time() - T_START, 1),
        "claim": None,
    }


def emit(report):
    """The report, then as the LAST line the verdict with exactly the keys
    the driver's contract names: it refuses a last line that holds more."""
    print(json.dumps(report), flush=True)
    print(json.dumps({"ok": report["ok"], "device": report["device"]}),
          flush=True)


def main():
    # a hang must not outlive the driver's limit holding the chip
    faulthandler.dump_traceback_later(1150, exit=True)
    import jax

    device = jax.devices()[0]
    say(f"device: platform={device.platform} kind={device.device_kind} "
        f"local={len(jax.local_devices())}")
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {jax.devices()}. This script "
              "checks the chip and prints no result without one.",
              file=sys.stderr)
        return 2
    import paddle_tpu as fluid
    from paddle_tpu import monitor

    if monitor.chip_peak_flops(device) is None:
        print(f"chip_smoke: device_kind {device.device_kind!r} is not in "
              "monitor/mfu.py CHIP_PEAK_TFLOPS", file=sys.stderr)
        return 2
    report = run_phases(fluid, FULL, device, rehearsal=False)
    emit(report)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
