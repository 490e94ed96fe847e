"""Multi-step dispatch + device-staged input pipeline (r3 VERDICT task 2).

Reference parity: create_double_buffer_reader_op.cc:34-69 stages batches to
device off the compute path; fluid_benchmark.py's feed loop is the end-to-end
methodology. TPU adaptation: Executor.run(iters=K) compiles K steps into ONE
lax.scan dispatch; DeviceChunkFeeder stacks + stages [K, ...] chunks on a
prefetch thread.
"""

import numpy as np
import pytest

import paddle_tpu as fluid


def _build_train(seed=7, optimizer=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        p = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=p, label=label))
        (optimizer or fluid.optimizer.SGD(learning_rate=0.1)).minimize(loss)
    return main, startup, loss


def _feeds(k, bs=8, seed=0):
    rs = np.random.RandomState(seed)
    return [
        {"x": rs.randn(bs, 8).astype("float32"),
         "label": rs.randint(0, 4, (bs, 1)).astype("int64")}
        for _ in range(k)
    ]


_OPTIMIZERS = {
    "sgd": lambda: fluid.optimizer.SGD(learning_rate=0.1),
    "momentum": lambda: fluid.optimizer.Momentum(learning_rate=0.1,
                                                 momentum=0.9),
    "adagrad": lambda: fluid.optimizer.Adagrad(learning_rate=0.1),
    "adam": lambda: fluid.optimizer.Adam(learning_rate=0.01),
    "adamax": lambda: fluid.optimizer.Adamax(learning_rate=0.01),
    "decayed_adagrad": lambda: fluid.optimizer.DecayedAdagrad(
        learning_rate=0.1),
    "rmsprop": lambda: fluid.optimizer.RMSProp(learning_rate=0.01),
    "ftrl": lambda: fluid.optimizer.Ftrl(learning_rate=0.1),
    "adamw": lambda: fluid.optimizer.Adam(learning_rate=0.01,
                                          weight_decay=0.1),
}


def _persistables(main, scope):
    """Every persistable var of `main` that the scope holds, on the host
    (parameters, optimizer accumulators, beta powers, the learning
    rate)."""
    return {n: np.asarray(scope.find_var(n))
            for n, v in main.global_block().vars.items()
            if v.persistable and scope.has_var(n)}


@pytest.mark.parametrize(
    "opt,amp_dtype",
    [(o, None) for o in _OPTIMIZERS]
    + [("momentum", "bfloat16"), ("adam", "bfloat16")],
    ids=list(_OPTIMIZERS) + ["momentum-bf16_amp", "adam-bf16_amp"])
def test_iters_matches_sequential_steps(opt, amp_dtype):
    """K steps in one scan dispatch == K sequential exe.run calls: the
    same per-step losses and the same value in EVERY persistable var
    (weights, each optimizer's accumulators), per optimizer; under bf16
    AMP the float32 master weights and accumulators across the scan."""
    from paddle_tpu import amp

    K = 5
    feeds = _feeds(K)

    def run(scan):
        main, startup, loss = _build_train(optimizer=_OPTIMIZERS[opt]())
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            if scan:
                out, = exe.run(main, feed=feeds, fetch_list=[loss], iters=K)
                losses = np.asarray(out, np.float32).reshape(-1)
            else:
                losses = np.asarray(
                    [np.asarray(exe.run(main, feed=f, fetch_list=[loss])[0],
                                np.float32).item() for f in feeds])
            return losses, _persistables(main, sc)

    if amp_dtype:
        amp.enable(amp_dtype)
    try:
        seq_losses, seq_state = run(scan=False)
        scan_losses, scan_state = run(scan=True)
    finally:
        amp.disable()

    assert scan_losses.shape[0] == K
    np.testing.assert_allclose(scan_losses, seq_losses, rtol=2e-4, atol=1e-5)
    assert set(scan_state) == set(seq_state) and len(seq_state) >= 5
    for n in seq_state:
        # masters and accumulators stay float32, under AMP too
        assert scan_state[n].dtype == seq_state[n].dtype == np.float32, n
        np.testing.assert_allclose(scan_state[n], seq_state[n], rtol=2e-4,
                                   atol=1e-5, err_msg=n)


def test_iters_prestacked_device_feed():
    """A single dict with a leading [K] axis (pre-stacked, possibly already
    on device) is accepted; fetches come back stacked [K, ...]."""
    import jax

    K = 3
    feeds = _feeds(K, seed=3)
    stacked = {
        n: jax.device_put(np.stack([f[n] for f in feeds], 0))
        for n in feeds[0]
    }
    main, startup, loss = _build_train()
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out, = exe.run(main, feed=stacked, fetch_list=[loss], iters=K)
    assert np.asarray(out).reshape(-1).shape[0] == K
    assert np.isfinite(np.asarray(out)).all()


def test_iters_one_prestacked_dict():
    """iters=1 with a pre-stacked [1, ...] dict must scan, not feed the
    stacked array (with its bogus leading axis) into the ops."""
    feeds = _feeds(1, seed=9)
    stacked = {n: np.stack([feeds[0][n]], 0) for n in feeds[0]}
    main, startup, loss = _build_train()
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out, = exe.run(main, feed=stacked, fetch_list=[loss], iters=1)
    assert np.asarray(out).reshape(-1).shape[0] == 1
    assert np.isfinite(np.asarray(out)).all()


def test_chunk_feeder_releases_worker_on_early_stop():
    """A consumer that stops iterating (train step raised) must not leave
    the prefetch thread blocked holding staged device chunks."""
    import threading

    produced = []

    def reader():
        for i in range(100):
            produced.append(i)
            yield {"x": np.zeros((2, 4), "float32")}

    n0 = threading.active_count()
    it = iter(fluid.DeviceChunkFeeder(reader, chunk=2, capacity=2))
    next(it)
    it.close()  # consumer abandons mid-stream
    for _ in range(50):
        if threading.active_count() <= n0:
            break
        import time

        time.sleep(0.1)
    assert threading.active_count() <= n0, "prefetch thread still alive"
    assert len(produced) < 100, "worker kept reading after consumer stopped"


def test_iters_rejects_reader_programs():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        r = fluid.layers.io.random_data_generator(
            0.0, 1.0, shapes=[[4, 3]], lod_levels=[0])
        img = fluid.layers.io.read_file(r)
        fluid.layers.mean(img)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(ValueError, match="compilable"):
        exe.run(main, feed=[{}, {}], fetch_list=[], iters=2)


def test_iters_feed_length_mismatch():
    main, startup, loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(ValueError, match="iters"):
        exe.run(main, feed=_feeds(2), fetch_list=[loss], iters=3)


def test_device_chunk_feeder_stacks_and_stages():
    K = 4

    def reader():
        rs = np.random.RandomState(0)
        for _ in range(10):  # 10 batches -> 2 chunks of 4, tail dropped
            yield {"x": rs.randn(2, 8).astype("float32"),
                   "label": rs.randint(0, 4, (2, 1)).astype("int64")}

    chunks = list(fluid.DeviceChunkFeeder(
        reader, chunk=K, place=fluid.CPUPlace()))
    assert len(chunks) == 2
    for ch in chunks:
        assert set(ch) == {"x", "label"}
        assert ch["x"].shape == (K, 2, 8)
        assert ch["label"].shape == (K, 2, 1)
        # staged: already a committed device array, not host numpy
        devs = ch["x"].devices()
        assert len(devs) == 1 and next(iter(devs)).platform == "cpu"


def test_device_chunk_feeder_propagates_reader_errors():
    def reader():
        yield {"x": np.zeros((2, 8), "float32")}
        raise RuntimeError("boom in reader")

    with pytest.raises(RuntimeError, match="boom in reader"):
        list(fluid.DeviceChunkFeeder(reader, chunk=1))


def test_chunk_feeder_end_to_end_train():
    """The full pipeline: reader -> chunk feeder -> iters=K scan; loss
    decreases across chunks."""
    K = 4
    rs = np.random.RandomState(1)
    W = rs.randn(8, 4).astype("float32")

    def reader():
        for _ in range(3 * K):
            x = rs.randn(16, 8).astype("float32")
            y = np.argmax(x @ W, 1).astype("int64")[:, None]
            yield {"x": x, "label": y}

    main, startup, loss = _build_train()
    sc = fluid.Scope()
    losses = []
    with fluid.scope_guard(sc):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for chunk in fluid.DeviceChunkFeeder(
                reader, chunk=K, place=fluid.CPUPlace()):
            out, = exe.run(main, feed=chunk, fetch_list=[loss], iters=K)
            losses.extend(np.asarray(out).reshape(-1).tolist())
    assert len(losses) == 3 * K
    assert losses[-1] < losses[0], losses


def test_double_buffer_reader_stages_to_device():
    """ops/reader_ops.DoubleBufferReader device_puts dense slots on its
    prefetch thread (the reference GPU tensor cache role)."""
    import jax

    from paddle_tpu.ops.reader_ops import DoubleBufferReader, ReaderBase

    class TwoBatches(ReaderBase):
        def __init__(self):
            self.n = 0

        def read_next(self):
            if self.n >= 2:
                return None
            self.n += 1
            return [(np.ones((3, 4), "float32"), None)]

        def reset(self):
            self.n = 0

    dev = jax.devices("cpu")[0]
    r = DoubleBufferReader(TwoBatches(), device=dev)
    s = r.read_next()
    arr, lod = s[0]
    assert lod is None
    assert hasattr(arr, "devices") and arr.devices() == {dev}
    assert r.read_next() is not None
    assert r.read_next() is None


def test_iters_running_stats_match_sequential_steps():
    """Batch-norm running statistics ride the scan carry: after one
    iters=5 dispatch they equal those of 5 sequential run() calls."""

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[3, 6, 6], dtype="float32")
            c = fluid.layers.conv2d(x, num_filters=4, filter_size=3,
                                    padding=1, bias_attr=False)
            b = fluid.layers.batch_norm(c, act="relu", momentum=0.8)
            loss = fluid.layers.mean(b)
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        return main, startup, loss

    feeds = [{"x": np.random.RandomState(i).randn(4, 3, 6, 6)
              .astype("float32")} for i in range(5)]
    main, startup, loss = build()
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        e = fluid.Executor(fluid.CPUPlace())
        e.run(startup)
        seq = [np.asarray(e.run(main, feed=f, fetch_list=[loss])[0])
               for f in feeds]
        stats1 = {n: np.asarray(s1.find_var(n))
                  for n in s1.local_var_names() if "batch_norm" in n}

    main2, startup2, loss2 = build()
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        e = fluid.Executor(fluid.CPUPlace())
        e.run(startup2)
        out, = e.run(main2, feed=feeds, fetch_list=[loss2], iters=5)
        stats2 = {n: np.asarray(s2.find_var(n))
                  for n in s2.local_var_names() if "batch_norm" in n}
    np.testing.assert_allclose(
        np.asarray(seq).ravel(), np.asarray(out).ravel(), rtol=2e-5)
    for n in stats1:
        np.testing.assert_allclose(stats1[n], stats2[n], rtol=1e-4,
                                   atol=1e-6, err_msg=n)


def test_bucketed_seq_tensor_parity_and_iters():
    """LoD -> dense bridge (r4 VERDICT task 3): tail-padded bucket feeds
    (create_bucketed_seq_tensor) must match exact ragged feeds numerically
    — lod_aware kernels mask the tail — and K bucketed batches must ride
    ONE iters=K dispatch with the same losses."""
    import paddle_tpu as fluid

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 4
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            data = fluid.layers.data(name="words", shape=[1], lod_level=1,
                                     dtype="int64")
            emb = fluid.layers.embedding(input=data, size=[50, 8])
            proj = fluid.layers.fc(input=emb, size=32, bias_attr=False)
            hidden, _ = fluid.layers.dynamic_lstm(
                input=proj, size=32, use_peepholes=False, max_len=16)
            last = fluid.layers.sequence_pool(hidden, "last")
            label = fluid.layers.data(name="label", shape=[1], dtype="int64")
            logit = fluid.layers.fc(input=last, size=2, act="softmax")
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(input=logit, label=label))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        return main, startup, loss

    rs = np.random.RandomState(0)
    batches = []
    for _ in range(3):
        seqs = [rs.randint(0, 50, (rs.randint(3, 9),)) for _ in range(4)]
        lbl = rs.randint(0, 2, (4, 1)).astype("int64")
        batches.append((seqs, lbl))

    main, startup, loss = build()
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        e = fluid.Executor(fluid.CPUPlace())
        e.run(startup)
        exact = []
        for seqs, lbl in batches:
            lt = fluid.create_lod_tensor(
                [list(map(int, s)) for s in seqs], None, fluid.CPUPlace())
            l, = e.run(main, feed={"words": lt, "label": lbl},
                       fetch_list=[loss])
            exact.append(float(np.asarray(l).reshape(-1)[0]))

    main3, startup3, loss3 = build()
    s3 = fluid.Scope()
    with fluid.scope_guard(s3):
        e = fluid.Executor(fluid.CPUPlace())
        e.run(startup3)
        feed_list = [
            {"words": fluid.create_bucketed_seq_tensor(seqs, bucket=32),
             "label": lbl} for seqs, lbl in batches]
        out, = e.run(main3, feed=feed_list, fetch_list=[loss3], iters=3)
        k_losses = [float(v) for v in np.asarray(out).reshape(-1)]
    np.testing.assert_allclose(exact, k_losses, rtol=2e-5)


def test_two_scan_chunks_match_six_sequential_steps():
    """Two chunks of iters=3 equal six sequential steps on a conv + BN +
    Momentum net: the losses and EVERY scope var (the second chunk starts
    from the state the first one wrote back)."""

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[3, 6, 6],
                                  dtype="float32")
            c = fluid.layers.conv2d(x, num_filters=4, filter_size=3,
                                    padding=1, bias_attr=False)
            b = fluid.layers.batch_norm(c, act="relu", momentum=0.8)
            c2 = fluid.layers.conv2d(b, num_filters=4, filter_size=3,
                                     padding=1)
            loss = fluid.layers.mean(c2)
            fluid.optimizer.Momentum(
                learning_rate=0.01, momentum=0.9).minimize(loss)
        return main, startup, loss

    feeds = [{"x": np.random.RandomState(i).randn(4, 3, 6, 6)
              .astype("float32")} for i in range(6)]

    def run(chunked):
        main, startup, loss = build()
        s = fluid.Scope()
        with fluid.scope_guard(s):
            e = fluid.Executor(fluid.CPUPlace())
            e.run(startup)
            if chunked:
                out1, = e.run(main, feed=feeds[:3], fetch_list=[loss],
                              iters=3)
                out2, = e.run(main, feed=feeds[3:], fetch_list=[loss],
                              iters=3)
                vals = list(np.asarray(out1).reshape(-1)) + \
                    list(np.asarray(out2).reshape(-1))
            else:
                vals = [np.asarray(e.run(main, feed=f,
                                         fetch_list=[loss])[0]).item()
                        for f in feeds]
            state = {n: np.asarray(s.find_var(n))
                     for n in s.local_var_names()
                     if hasattr(s.find_var(n), "shape")}
        return vals, state

    v0, st0 = run(False)
    v1, st1 = run(True)
    np.testing.assert_allclose(v0, v1, rtol=2e-5)
    assert set(st0) == set(st1) and len(st0) >= 8
    for n in st0:
        np.testing.assert_allclose(st0[n], st1[n], rtol=1e-4, atol=1e-6,
                                   err_msg=n)
