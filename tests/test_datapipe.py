"""paddle_tpu.datapipe: the parallel prefetching input-pipeline subsystem.

Covers the subsystem's contract surface: shard disjointness across mesh
workers, order preservation under parallel decode, bounded memory via
backpressure, drop-remainder vs pad-to-batch tail handling, clean worker
shutdown, and the legacy-reader adapter feeding Executor.run end to end.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import datapipe, recordio

# every test in this module must reap its datapipe workers (see conftest)
pytestmark = pytest.mark.usefixtures("no_datapipe_thread_leaks")


def _write_recordio(path, payloads):
    with recordio.Writer(str(path), max_num_records=4) as w:
        for p in payloads:
            w.write(p)


def _wait_threads(base, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if threading.active_count() <= base:
            return
        time.sleep(0.05)
    assert threading.active_count() <= base, \
        [t.name for t in threading.enumerate()]


# -- sharded sources -------------------------------------------------------
def test_recordio_shards_disjoint_and_complete(tmp_path):
    """Record i belongs to shard i % num_shards, the stride spans file
    boundaries, and the shards partition the record stream exactly."""
    p1, p2 = tmp_path / "a.recordio", tmp_path / "b.recordio"
    all_recs = [b"rec-%03d" % i for i in range(23)]
    _write_recordio(p1, all_recs[:13])
    _write_recordio(p2, all_recs[13:])
    shards = [list(datapipe.RecordIOSource([str(p1), str(p2)], num_shards=3,
                                           shard_index=idx, batch_read=4))
              for idx in range(3)]
    for idx, got in enumerate(shards):
        assert got == all_recs[idx::3]
    union = sorted(b for s in shards for b in s)
    assert union == sorted(all_recs)  # disjoint AND complete


def test_generator_source_shard_override():
    """DataPipe.shard() re-keys a generator source to an explicit
    (num_shards, index); sample i -> shard i % num_shards."""
    pipe = datapipe.DataPipe.from_reader(lambda: iter(range(10)))
    assert list(pipe.shard(2, 0)) == [0, 2, 4, 6, 8]
    assert list(pipe.shard(2, 1)) == [1, 3, 5, 7, 9]
    assert list(pipe) == list(range(10))  # original pipe untouched


# -- parallel map ----------------------------------------------------------
def test_parallel_map_preserves_order():
    """4 workers with skewed per-item cost must still emit results in
    input order (the reorder buffer, not completion order)."""
    delays = np.random.RandomState(0).uniform(0., 0.004, 60)

    def slow_sq(i):
        time.sleep(delays[i])
        return i * i

    out = list(datapipe.ParallelMap(range(60), slow_sq, num_workers=4))
    assert out == [i * i for i in range(60)]


def test_parallel_map_unordered_completes():
    out = list(datapipe.ParallelMap(range(40), lambda i: i,
                                    num_workers=4, order=False))
    assert sorted(out) == list(range(40))


def test_parallel_map_backpressure_bounds_inflight():
    """A slow consumer must stall the SOURCE after at most buffer_size
    in-flight items — bounded memory by construction, not by luck."""
    pulled = []

    def src():
        for i in range(60):
            pulled.append(i)
            yield i

    pm = datapipe.ParallelMap(src(), lambda i: i, num_workers=2,
                              buffer_size=4)
    it = iter(pm)
    consumed = 0
    max_excess = 0
    for _ in it:
        consumed += 1
        time.sleep(0.003)  # slow consumer
        max_excess = max(max_excess, len(pulled) - consumed)
        if consumed >= 25:
            break
    it.close()
    # tickets bound in-flight to buffer_size; +1 for the racing pull a
    # just-released ticket may admit before this thread samples
    assert max_excess <= 5, max_excess


def test_parallel_map_worker_error_propagates():
    def boom(i):
        if i == 7:
            raise ValueError("decode failed on 7")
        return i

    it = iter(datapipe.ParallelMap(range(20), boom, num_workers=3))
    try:
        for _ in it:
            pass
        raise AssertionError("worker error did not propagate")
    except ValueError as e:
        assert "decode failed" in str(e)


# -- batcher tail modes ----------------------------------------------------
def test_batcher_drop_remainder_vs_pad():
    samples = [{"x": np.full((3,), i, np.float32)} for i in range(10)]

    dropped = list(datapipe.Batcher(iter(samples), batch_size=4))
    assert len(dropped) == 2  # 10 = 2 full batches + dropped tail of 2
    for bi, b in enumerate(dropped):
        np.testing.assert_array_equal(
            b["x"][:, 0], np.arange(bi * 4, bi * 4 + 4, dtype=np.float32))
        assert b["x"].flags["C_CONTIGUOUS"]

    padded = list(datapipe.Batcher(iter(samples), batch_size=4,
                                   pad_to_batch=True))
    assert len(padded) == 3
    # __valid__ is a [batch_size] bool_ row mask (True = real row), usable
    # directly as masked-loss weights on device
    for b in padded:
        assert b["__valid__"].dtype == np.bool_
        assert b["__valid__"].shape == (4,)
    assert [int(b["__valid__"].sum()) for b in padded] == [4, 4, 2]
    np.testing.assert_array_equal(padded[2]["__valid__"],
                                  [True, True, False, False])
    # pad rows repeat the last real sample; shape stays [batch_size, ...]
    np.testing.assert_array_equal(
        padded[2]["x"][:, 0], np.array([8, 9, 9, 9], np.float32))


def test_pad_to_batch_mask_excludes_pad_rows_from_mean_loss():
    """The point of the bool mask: a padded tail batch's mean-reduced loss
    must equal the mean over REAL rows only, computed on device through the
    executor (mask cast to 0/1 weights, masked sum / valid count)."""
    samples = [{"x": np.full((1,), float(i), np.float32)} for i in range(6)]
    pipe = (datapipe.DataPipe.from_reader(lambda: iter(samples))
            .batch(4, drop_remainder=False, pad_to_batch=True)
            .prefetch_to_device(place=fluid.CPUPlace(), chunk=1,
                                capacity=2))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[1], dtype="float32")
        valid = fluid.layers.data(name="__valid__", shape=[-1],
                                  append_batch_size=False, dtype="bool")
        w = fluid.layers.cast(valid, "float32")
        per_row = fluid.layers.reduce_sum(x, dim=1)
        masked_mean = fluid.layers.elementwise_div(
            fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(per_row, w)),
            fluid.layers.reduce_sum(w))
    exe = fluid.Executor(fluid.CPUPlace())
    s = fluid.Scope()
    means = []
    with fluid.scope_guard(s):
        exe.run(startup)
        while True:
            try:
                out, = exe.run(main, feed=pipe, fetch_list=[masked_mean])
            except StopIteration:
                break
            means.extend(np.asarray(out).ravel().tolist())
    pipe.close()
    # batch 0: rows 0..3; batch 1: rows 4,5 + two pad repeats of row 5 —
    # the naive unmasked mean would be (4+5+5+5)/4 = 4.75, not 4.5
    np.testing.assert_allclose(means, [1.5, 4.5], rtol=1e-6)


def test_batcher_ring_reuse_does_not_alias_emitted_batches():
    """Default (non-zero-copy) mode: emitted batches must stay valid after
    the ring slot is refilled more than `ring` batches later."""
    samples = [{"x": np.full((2,), i, np.float32)} for i in range(12)]
    batches = list(datapipe.Batcher(iter(samples), batch_size=2, ring=2))
    assert len(batches) == 6
    for bi, b in enumerate(batches):
        np.testing.assert_array_equal(b["x"][:, 0], [2 * bi, 2 * bi + 1])


# -- device staging + shutdown --------------------------------------------
def test_full_pipe_order_shutdown_and_stats():
    """map -> batch -> prefetch_to_device end to end: chunks arrive in
    order as [K, ...] arrays, worker threads are reaped on exhaustion AND
    on early close, and every stage shows up in stats()."""
    base = threading.active_count()

    def make_pipe():
        return (datapipe.DataPipe
                .from_reader(lambda: iter(
                    {"x": np.full((2,), i, np.float32)} for i in range(64)))
                .map(lambda s: {"x": s["x"] + 1.0}, num_workers=3)
                .batch(4)
                .prefetch_to_device(place=fluid.CPUPlace(), chunk=2,
                                    capacity=2, transfer_threads=2))

    # full exhaustion: 64 samples -> 16 batches -> 8 chunks, in order
    pipe = make_pipe()
    chunks = list(pipe)
    assert len(chunks) == 8
    for ci, ch in enumerate(chunks):
        assert np.asarray(ch["x"]).shape == (2, 4, 2)
        np.testing.assert_array_equal(
            np.asarray(ch["x"])[:, :, 0].reshape(-1),
            np.arange(ci * 8, ci * 8 + 8, dtype=np.float32) + 1.0)
    _wait_threads(base)
    st = pipe.stats()
    assert st["map"]["items"] == 64
    assert st["batch"]["items"] == 16
    assert st["stack"]["items"] == 16   # batches copied into chunk buffers
    assert st["transfer"]["items"] == 8
    assert "fractions" in st

    # early close mid-stream also reaps every stage's workers
    pipe2 = make_pipe()
    it = iter(pipe2)
    next(it)
    next(it)
    it.close()
    _wait_threads(base)


def test_feeder_backpressure_capacity_bound():
    """A stalled consumer holds at most `capacity` chunks in flight: the
    source must not be drained ahead of consumption."""
    pulled = []

    def src():
        for i in range(40):
            pulled.append(i)
            yield {"x": np.full((2,), i, np.float32)}

    feeder = datapipe.AsyncDeviceFeeder(src(), chunk=2,
                                        place=fluid.CPUPlace(),
                                        capacity=2, transfer_threads=2)
    it = iter(feeder)
    next(it)  # one chunk consumed
    time.sleep(0.3)  # let workers run as far ahead as the tickets allow
    # consumed 1 chunk (2 items) + at most capacity staged/in-pull chunks
    # + one chunk admitted by the just-released ticket
    assert len(pulled) <= 2 * (1 + 2 + 1), pulled
    it.close()


def test_pipe_next_feed_reset():
    """next_feed() pulls off a persistent iterator; reset() restarts the
    pass from the source."""
    pipe = (datapipe.DataPipe
            .from_reader(lambda: iter(
                {"x": np.full((2,), i, np.float32)} for i in range(8)))
            .batch(2)
            .prefetch_to_device(place=fluid.CPUPlace(), chunk=2))
    assert pipe.feed_iters == 2
    first = np.asarray(pipe.next_feed()["x"])
    second = np.asarray(pipe.next_feed()["x"])
    assert first[0, 0, 0] == 0.0 and second[0, 0, 0] == 4.0
    try:
        pipe.next_feed()
        raise AssertionError("exhausted pipe must raise StopIteration")
    except StopIteration:
        pass
    pipe.reset()
    again = np.asarray(pipe.next_feed()["x"])
    np.testing.assert_array_equal(again, first)
    pipe.close()


# -- legacy adapter through the Executor -----------------------------------
def test_legacy_reader_adapter_through_executor():
    """fluid.reader.to_datapipe adapts a positional-tuple reader; the
    Executor accepts the pipe as feed= and defaults iters to
    pipe.feed_iters."""

    def reader():
        for i in range(16):
            yield (np.full((3,), i, np.float32),)

    pipe = (fluid.reader.to_datapipe(reader, ["x"])
            .batch(4)
            .prefetch_to_device(place=fluid.CPUPlace(), chunk=2,
                                capacity=2))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[3], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    s = fluid.Scope()
    outs = []
    with fluid.scope_guard(s):
        exe.run(startup)
        while True:
            try:
                out, = exe.run(main, feed=pipe, fetch_list=[y])
            except StopIteration:
                break
            outs.append(np.asarray(out))
    # 16 samples -> 4 batches of 4 -> 2 chunks of K=2; fetches stack [K,...]
    assert len(outs) == 2 and outs[0].shape == (2, 4, 3)
    flat = np.concatenate([o.reshape(-1, 3) for o in outs])
    np.testing.assert_allclose(flat[:, 0], 2.0 * np.arange(16))
    pipe.close()


def test_feeder_staged_items_do_not_alias_reused_host_buffers():
    """XLA:CPU device_put zero-copy ALIASES 64-byte-aligned host arrays: a
    staged item must survive the upstream reader (or the feeder's own
    staging buffer) being refilled afterwards."""

    def aligned(shape, dtype=np.float32, align=64):
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        raw = np.empty(n + align, np.uint8)
        off = (-raw.ctypes.data) % align
        return raw[off:off + n].view(dtype).reshape(shape)

    buf = aligned((16,))

    def src():
        for i in range(6):
            buf[:] = float(i)  # legacy reader idiom: ONE reused buffer
            yield {"x": buf}

    staged = list(datapipe.AsyncDeviceFeeder(
        src(), place=fluid.CPUPlace(), capacity=2, transfer_threads=1))
    vals = [float(np.asarray(s["x"])[0]) for s in staged]
    assert vals == [0., 1., 2., 3., 4., 5.], vals


# -- process-pool decode (ProcessPoolMap + shm staging) --------------------
# map fns live at module level so they pickle under every start method
# (fork ships them for free; spawn/forkserver re-import this module)


def _pm_slow_sq(i):
    time.sleep((i * 37 % 10) / 2500.0)  # deterministic skewed cost
    return i * i


def _pm_ident(i):
    return i


def _pm_boom(i):
    if i == 7:
        raise ValueError("decode failed on 7")
    return i


def _pm_decode(i):
    return {"data": np.full((4, 6), i % 251, np.uint8),
            "label": np.full((4, 1), i % 10, np.int64)}


def test_process_map_preserves_order():
    """Worker PROCESSES with skewed per-item cost must still emit in
    input order (the reorder buffer spans the IPC boundary)."""
    out = list(datapipe.ProcessPoolMap(range(40), _pm_slow_sq,
                                       num_workers=3))
    assert out == [i * i for i in range(40)]


def test_process_map_unordered_completes():
    out = list(datapipe.ProcessPoolMap(range(30), _pm_ident,
                                       num_workers=3, order=False))
    assert sorted(out) == list(range(30))


def test_process_map_worker_error_propagates():
    """A decode exception in a worker process re-raises in the parent as
    its original type, carrying the worker traceback in the message."""
    it = iter(datapipe.ProcessPoolMap(range(20), _pm_boom, num_workers=2))
    with pytest.raises(ValueError, match="decode failed on 7"):
        for _ in it:
            pass
    it.close()


def test_process_map_backpressure_bounds_inflight():
    """The dispatcher pulls the source in the PARENT, gated by tickets:
    a slow consumer stalls the pull after at most buffer_size items."""
    pulled = []

    def src():
        for i in range(60):
            pulled.append(i)
            yield i

    pm = datapipe.ProcessPoolMap(src(), _pm_ident, num_workers=2,
                                 buffer_size=4)
    it = iter(pm)
    consumed = 0
    max_excess = 0
    for _ in it:
        consumed += 1
        time.sleep(0.003)
        max_excess = max(max_excess, len(pulled) - consumed)
        if consumed >= 25:
            break
    it.close()
    assert max_excess <= 5, max_excess


def test_process_map_close_mid_stream_reaps_workers():
    pm = datapipe.ProcessPoolMap(range(200), _pm_ident, num_workers=3)
    it = iter(pm)
    next(it)
    it.close()  # the no_datapipe_thread_leaks fixture asserts the reap


def test_process_pipe_fused_shm_end_to_end():
    """map(processes=True) fused with prefetch_to_device(chunk=K): decoded
    chunks cross via the shared-memory ring (zero parent-side copies),
    arrive device-resident in order with the auto-resolved uint8 wire
    marker, and close() unlinks every segment."""
    from paddle_tpu.datapipe.transfer import pop_markers

    pipe = (datapipe.DataPipe(range(24))
            .map(_pm_decode, num_workers=2, processes=True)
            .prefetch_to_device(place=fluid.CPUPlace(), chunk=4,
                                capacity=2))
    chunks = list(pipe)
    assert len(chunks) == 6
    for ci, ch in enumerate(chunks):
        feed, wire, _donate = pop_markers(dict(ch))
        data = np.asarray(feed["data"])
        assert data.shape == (4, 4, 6) and data.dtype == np.uint8
        np.testing.assert_array_equal(
            data[:, 0, 0], [(ci * 4 + k) % 251 for k in range(4)])
        assert wire is not None and "data" in wire  # uint8 stays on wire
    assert pipe.wire_spec is not None and "data" in pipe.wire_spec
    st = pipe.stats()
    assert st["map"]["items"] == 24
    assert st.get("bottleneck_stage") in st  # attribution names a stage
    assert "occupancy" in st["map"] and "bp_wait_s" in st["map"]
    pipe.close()
    assert datapipe.live_segments() == []


def test_shm_ring_close_keeps_leased_slots_mapped():
    """close() while a chunk is still leased (the feeder is moving it to
    the device from another transfer lane) unlinks the names but must not
    unmap that chunk's memory: numpy views do not pin the mapping, and on
    the TPU host the unmap was a SIGSEGV inside device_put. The segment
    goes when its last lease is released."""
    from paddle_tpu.datapipe.shm import ShmRing

    ring = ShmRing(3, {"x": ((4, 8), "uint8")}, coalesce=1)
    assert ring.mapped == 3
    busy, idle = ring.acquire(), ring.acquire()
    lease = ring.lease(busy)
    view = ring.views(busy)["x"]
    view[...] = 7
    ring.release(idle)
    ring.close()
    assert datapipe.live_segments() == []   # every name is unlinked now
    assert ring.mapped == 1                 # only the leased slot's segment
    assert int(view.sum()) == 7 * 32        # still readable: not unmapped
    assert ring.acquire(0.01) is None       # a closed ring hands out nothing
    lease.release()
    assert ring.mapped == 0
    lease.release()                         # idempotent
    ring.close()


def test_process_pipe_plain_feeds_batcher():
    """Unfused process decode (no chunk fusion) feeds the downstream
    thread stages like ParallelMap — leases (if any) released, order
    kept."""
    pipe = (datapipe.DataPipe(range(16))
            .map(_pm_decode, num_workers=2, processes=True)
            .batch(2))
    vals = [b["data"][0, 0, 0] for b in pipe]
    assert [int(v) for v in vals] == [i % 251 for i in range(0, 16, 2)]
    pipe.close()
    assert datapipe.live_segments() == []
