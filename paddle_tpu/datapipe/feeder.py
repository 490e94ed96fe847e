"""AsyncDeviceFeeder: background host->device staging with double buffering.

Subsumes pipeline.DeviceChunkFeeder (now a thin shim over this): K batches
are stacked into one [K, ...] array per feed name sized for
Executor.run(feed=chunk, iters=K) — one jit dispatch per chunk, so the
per-dispatch cost is paid once per K steps.

What's new over DeviceChunkFeeder:
  * transfer_threads parallel device_put workers: T concurrent streams
    each moving a WHOLE chunk, without adding device-side concat
    dispatches (default auto = min(capacity, 2); sized on an older stack,
    not re-measured on the current host). Emission order stays
    deterministic via a reorder buffer keyed on chunk index.
  * chunks are stacked into per-worker preallocated staging buffers (no
    per-chunk allocation) and the copy happens under the pull lock, which
    is the synchronous-copy boundary that makes an upstream zero-copy
    Batcher ring safe.
  * capacity tickets bound staged-chunks-in-flight (transferring + queued),
    so a stalled consumer holds at most `capacity` chunk-sized device
    buffers — backpressure all the way to the source.
  * per-stage stats (stack/transfer busy, consumer starvation) and
    profiler counter tracks.

Transfer engine (see transfer.py): with `wire=WireSpec(...)` each batch is
encoded into the staging buffer in its WIRE dtype (uint8 pixels, bf16
floats) so the device_put moves the compressed representation; staged
chunks carry the spec (WIRE_KEY) so the executor fuses the decode into the
compiled step. Chunks the feeder staged itself are marked single-use
(DONATE_KEY) so the executor may donate their buffers back to XLA. Each
transfer thread is one LINK LANE: pass `link_stats` (index -> StageStats)
to get per-lane bytes/busy in DataPipe.stats() and the profiler host lane.
"""

import threading

import numpy as np

from .. import trace as _trace
from ..flags import define, get as get_flag
from .shm import SHM_SLOT_KEY
from .transfer import DONATE_KEY, WIRE_KEY

__all__ = ["AsyncDeviceFeeder"]

define("datapipe_transfer_threads", int, 0,
       "Parallel host->device transfer threads for datapipe "
       "AsyncDeviceFeeder (0 = auto: min(capacity, 2)).")
define("datapipe_prefetch_depth", int, 0,
       "Default staged-chunks-in-flight capacity for AsyncDeviceFeeder "
       "when the pipe doesn't pass one explicitly (0 = 2: double "
       "buffer). Deeper prefetch rides out decode jitter at the cost of "
       "one chunk of device memory per extra level.")


class _End:
    pass


def _device_put_copies(dev):
    """True when jax.device_put copies OUT of an aligned host buffer (any
    real accelerator, where the put is a DMA across a link). XLA:CPU
    instead zero-copy ALIASES 64-byte-aligned numpy arrays — staged chunks
    would alias the feeder's reusable staging buffers and be silently
    overwritten by the next refill, so buffer reuse must be disabled.
    The 256-byte probe's answer holds at chunk size on the v5e host: 20,
    193 and 770 MB buffers refilled right after block_until_ready left
    the device copy intact (PR 21), and chip_smoke.py checksums every
    batch on the device."""
    import jax

    raw = np.zeros(128, np.uint8)
    off = (-raw.ctypes.data) % 64
    probe = raw[off:off + 64].view(np.float32)
    staged = jax.device_put(probe, dev)
    jax.block_until_ready(staged)
    probe[:] = 1.0
    return not bool(np.asarray(staged)[0] == 1.0)


class AsyncDeviceFeeder:
    """Iterate device-resident feed dicts off background transfer thread(s).

    source:           iterable of per-step feed dicts {name: ndarray}, or a
                      reader creator (callable returning an iterator)
    chunk:            K steps stacked per staged item ([K, ...] arrays for
                      Executor.run(iters=K)); None = stage items as-is
    place:            paddle_tpu Place to stage to (default jax device)
    capacity:         staged chunks buffered ahead (>= 2: double buffer)
    transfer_threads: parallel device_put workers (None = FLAGS
                      datapipe_transfer_threads, 0 = auto)
    stage_fn:         override for the staging step, stage_fn(idx, stacked)
                      -> {name: device_array}; disables buffer reuse since
                      the callee may keep host references
    wire:             optional transfer.WireSpec — covered feeds are staged
                      and shipped in their wire dtype; emitted chunks carry
                      the spec under WIRE_KEY so the executor fuses the
                      decode into the compiled step
    donate:           mark emitted chunks single-use (DONATE_KEY) so the
                      executor may donate their device buffers; None = auto
                      (on unless stage_fn, whose chunks the callee owns and
                      may hand out again)
    stack_stats /     optional StageStats receiving the stack-copy and
    transfer_stats:   transfer/starvation counters
    link_stats:       per-transfer-thread lane stats — a callable
                      (thread index -> StageStats) or a list; each lane
                      records its own bytes/busy

    A partial tail chunk is dropped (odd [K', ...] shapes would force an
    extra XLA compile), matching DeviceChunkFeeder.
    """

    def __init__(self, source, chunk=None, place=None, capacity=None,
                 transfer_threads=None, stage_fn=None, wire=None,
                 donate=None, stack_stats=None, transfer_stats=None,
                 link_stats=None, wire_cb=None, pipe_id=None):
        if chunk is not None and int(chunk) < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if capacity is None:
            capacity = get_flag("datapipe_prefetch_depth") or 2
        if int(capacity) < 2:
            raise ValueError(
                f"capacity must be >= 2 (double buffer), got {capacity}")
        self._source = source
        self._chunk = None if chunk is None else int(chunk)
        self._place = place
        self._cap = int(capacity)
        if transfer_threads is None:
            transfer_threads = get_flag("datapipe_transfer_threads")
        if int(transfer_threads) == 0:  # auto
            transfer_threads = min(self._cap, 2)
        if int(transfer_threads) < 1:
            raise ValueError(
                f"transfer_threads must be >= 1, got {transfer_threads}")
        self._threads = min(int(transfer_threads), self._cap)
        self._stage_fn = stage_fn
        self._wire = wire
        self._donate = bool(stage_fn is None) if donate is None \
            else bool(donate)
        self._stack_stats = stack_stats
        self._transfer_stats = transfer_stats
        self._link_stats = link_stats
        self._wire_cb = wire_cb  # called once with a resolved "auto" spec
        self._pipe_id = pipe_id  # the `pipe` attr of this stage's spans
        self._active = None  # stop flag of the live iteration (for close())

    def _device(self):
        if self._place is None:
            return None
        from ..core.places import jax_device_for

        return jax_device_for(self._place)

    def close(self):
        """Stop the live iteration's workers (idempotent)."""
        state = self._active
        if state is not None:
            state["stop"] = True
            with state["cond"]:
                state["cond"].notify_all()

    def join_workers(self, timeout=2.0):
        """Join the live iteration's transfer threads (after close());
        True when none is left running. Workers poll their stop flag at
        0.2s granularity, so a closed pipeline drains within ~2 polls."""
        state = self._active
        if state is None:
            return True
        import time

        ok = True
        deadline = time.monotonic() + timeout
        for t in state.get("threads", ()):
            t.join(max(0.0, deadline - time.monotonic()))
            ok = ok and not t.is_alive()
        return ok

    def __iter__(self):
        import time

        import jax

        src = self._source() if callable(self._source) \
            else iter(self._source)
        dev = self._device()
        K = self._chunk
        src_lock = threading.Lock()
        tickets = threading.Semaphore(self._cap)
        cond = threading.Condition()
        done = {}  # chunk idx -> staged dict
        state = {"next_in": 0, "next_out": 0, "eof_at": None,
                 "error": None, "stop": False, "ended": 0, "cond": cond,
                 "threads": ()}
        self._active = state
        sst, tst = self._stack_stats, self._transfer_stats
        # wire may be "auto": resolved from the first pulled item (under
        # the source lock, so exactly once) via transfer.auto_wire
        wire_state = {"wire": self._wire,
                      "pending": self._wire == "auto"}

        def eff_wire(item):
            if wire_state["pending"]:
                from .transfer import auto_wire

                wire_state["wire"] = auto_wire(item)
                wire_state["pending"] = False
                if self._wire_cb is not None:
                    try:
                        self._wire_cb(wire_state["wire"])
                    except Exception:
                        pass
            return wire_state["wire"]
        # consumer-thread trace context, attached inside each transfer
        # worker (explicit cross-thread propagation); snapshot of the
        # flag so workers don't re-read it per chunk
        tracing = _trace.enabled()
        tctx = _trace.current() if tracing else None

        def tspan(name, t0, t1, **attrs):
            # callers gate on `tracing`
            attrs["pipe"] = self._pipe_id
            _trace.record(name, t0, t1, kind="datapipe", attrs=attrs)

        puts_copy = self._stage_fn is not None or _device_put_copies(dev)
        reuse_buffers = self._stage_fn is None and puts_copy

        def link_stat(i):
            ls = self._link_stats
            if ls is None:
                return None
            if callable(ls):
                return ls(i)
            return ls[i] if i < len(ls) else None

        def fail(e):
            with cond:
                if state["error"] is None:
                    state["error"] = e
                cond.notify_all()

        def pull_chunk(buf_holder):
            """Under the source lock: pull K batches, copy them into this
            worker's staging buffers. Returns (idx, stacked, lease, w) or
            None at EOF/stop — `lease` is the upstream shm SlotLease when
            the item came out of a fused ProcessPoolMap (released by the
            caller once the transfer is done), `w` the effective WireSpec
            for the emitted chunk's markers. The copy-under-lock is the
            zero-copy ring boundary."""
            lease = None
            tl = time.perf_counter() if tracing else None
            with src_lock:
                if state["eof_at"] is not None or state["error"] is not None \
                        or state["stop"]:
                    return None
                idx = state["next_in"]  # moves under this lock only
                if tracing:
                    tspan("datapipe.lock_wait", tl, time.perf_counter(),
                          chunk=idx)
                try:
                    if K is None:
                        t0 = time.perf_counter()
                        item = next(src, _End)
                        tb = time.perf_counter()
                        if sst:
                            sst.add_wait_in(tb - t0)
                        if tracing:
                            tspan("datapipe.upstream_wait", t0, tb,
                                  chunk=idx)
                        if item is _End:
                            state["eof_at"] = state["next_in"]
                            with cond:
                                cond.notify_all()
                            return None
                        w = eff_wire(item)
                        if isinstance(item, dict) and SHM_SLOT_KEY in item:
                            # fused upstream: arrays are shm views already
                            # in wire dtype; hold the slot until the
                            # device owns the bytes
                            lease = item.pop(SHM_SLOT_KEY)
                            w = item.pop(WIRE_KEY, None) or w
                        elif w is not None:
                            item = w.encode_feed(item)
                        # copy when device_put would alias the host array
                        # (the upstream reader may reuse it between items)
                        stacked = {n: np.asarray(a) if puts_copy
                                   else np.array(a)
                                   for n, a in item.items()}
                        if sst:
                            sst.add_item(nbytes=sum(
                                a.nbytes for a in stacked.values()))
                        if tracing:
                            tspan("datapipe.stack", tb, time.perf_counter(),
                                  chunk=idx)
                    else:
                        got = 0
                        buf = buf_holder[0]
                        w = wire_state["wire"]
                        while got < K:
                            t0 = time.perf_counter()
                            item = next(src, _End)
                            tb = time.perf_counter()
                            if sst:
                                sst.add_wait_in(tb - t0)
                            if tracing:
                                tspan("datapipe.upstream_wait", t0, tb,
                                      chunk=idx)
                            if item is _End:
                                # partial tail: drop (DeviceChunkFeeder
                                # semantics — no odd-shape recompile)
                                state["eof_at"] = state["next_in"]
                                with cond:
                                    cond.notify_all()
                                return None
                            w = eff_wire(item)
                            if buf is None:
                                # __valid__ (the Batcher's pad mask) is a
                                # real [bs] bool array and rides the chunk;
                                # other __ metadata stays host-side
                                buf = buf_holder[0] = {}
                                for n, a in item.items():
                                    if n.startswith("__") \
                                            and n != "__valid__":
                                        continue
                                    a = np.asarray(a)
                                    dt = w.wire_dtype(n, a) \
                                        if w is not None else a.dtype
                                    buf[n] = np.empty((K,) + a.shape, dt)
                            for n, b in buf.items():
                                v = item[n]
                                if w is not None and n in w:
                                    v = w[n].encode(v)
                                b[got] = v
                            got += 1
                            tc = time.perf_counter()
                            if sst:
                                # wire bytes: what the link will move
                                sst.add_item(
                                    busy_s=tc - tb,
                                    nbytes=sum(b[0].nbytes
                                               for b in buf.values()))
                            if tracing:
                                tspan("datapipe.stack", tb, tc, chunk=idx)
                        if reuse_buffers:
                            stacked = buf
                        else:
                            stacked = {n: b.copy() for n, b in buf.items()}
                except BaseException as e:
                    if lease is not None:
                        lease.release()
                    fail(e)
                    return None
                state["next_in"] += 1
                return idx, stacked, lease, w

        def work(lst):
            if tracing:
                with _trace.attach(tctx):
                    work_loop(lst)
            else:
                work_loop(lst)

        def work_loop(lst):
            # buf_holder: this worker's private staging buffers — safe to
            # refill once its previous transfer has completed (we block on
            # the transfer below before looping)
            buf_holder = [None]
            try:
                while not state["stop"]:
                    tw = time.perf_counter()
                    waited = False
                    while not tickets.acquire(timeout=0.2):
                        if state["stop"]:
                            return
                        waited = True
                    if waited and tst:
                        # prefetch budget full: downstream backpressure
                        tst.add_bp_wait(time.perf_counter() - tw)
                    if tracing:
                        tspan("datapipe.ticket_wait", tw,
                              time.perf_counter())
                    nxt = pull_chunk(buf_holder)
                    if nxt is None:
                        tickets.release()
                        return
                    idx, stacked, lease, w = nxt
                    try:
                        t0 = time.perf_counter()

                        def stage():
                            if self._stage_fn is not None:
                                return self._stage_fn(idx, stacked)
                            staged = {n: jax.device_put(a, dev)
                                      for n, a in stacked.items()}
                            # wait for the copy out of our staging buffer
                            # (also what makes transfer busy_s honest)
                            jax.block_until_ready(staged)
                            return staged

                        if lst is not None:
                            with lst.span():
                                staged = stage()
                        else:
                            staged = stage()
                        dt = time.perf_counter() - t0
                        nb = sum(a.nbytes for a in stacked.values())
                        if tracing:
                            tspan("datapipe.transfer", t0, t0 + dt,
                                  chunk=idx, bytes=nb)
                        if tst:
                            tst.add_item(busy_s=dt, nbytes=nb)
                        if lst is not None:
                            lst.add_item(busy_s=dt, nbytes=nb)
                            from .. import profiler

                            profiler.record_bytes(
                                f"datapipe/{lst.name}", nb)
                        # transfer-engine metadata: the executor pops both
                        # (pop_markers); stage_fn chunks are callee-owned,
                        # so copy before annotating and never mark donate
                        if w is not None or self._donate:
                            if self._stage_fn is not None:
                                staged = dict(staged)
                            if w is not None:
                                staged[WIRE_KEY] = w
                            if self._donate:
                                staged[DONATE_KEY] = True
                    except BaseException as e:
                        fail(e)
                        return
                    finally:
                        if lease is not None:
                            # device owns the bytes (block_until_ready in
                            # stage(), or the host copy when puts_copy is
                            # False): the shm slot may be refilled
                            lease.release()
                    with cond:
                        done[idx] = staged
                        cond.notify_all()
            finally:
                with cond:
                    state["ended"] += 1
                    cond.notify_all()

        threads = [threading.Thread(target=work, args=(link_stat(i),),
                                    daemon=True,
                                    name=f"datapipe-feed-{i}")
                   for i in range(self._threads)]
        state["threads"] = tuple(threads)
        for t in threads:
            t.start()

        def next_staged():
            t0 = time.perf_counter()
            with cond:
                while True:
                    if state["error"] is not None:
                        raise state["error"]
                    if state["next_out"] in done:
                        res = done.pop(state["next_out"])
                        state["next_out"] += 1
                        if tst:
                            tst.add_wait_out(time.perf_counter() - t0)
                            tst.sample_depth(len(done) + 1)
                        if tracing:
                            # the consumer's wait for this chunk; `depth`:
                            # staged chunks left behind it
                            tspan("datapipe.next", t0, time.perf_counter(),
                                  chunk=state["next_out"] - 1,
                                  depth=len(done))
                        return res
                    if state["eof_at"] is not None and \
                            state["next_out"] >= state["eof_at"]:
                        return _End
                    if state["ended"] == self._threads:
                        # workers gone and next_out wasn't in `done` above:
                        # EOF, error, or a stop that left a gap in the
                        # reorder buffer — nothing more can arrive
                        if state["error"] is not None:
                            raise state["error"]
                        return _End
                    cond.wait(0.2)

        try:
            while True:
                res = next_staged()
                if res is _End:
                    return
                tickets.release()
                yield res
        finally:
            state["stop"] = True
            with cond:
                cond.notify_all()
            if self._active is state:
                self._active = None
