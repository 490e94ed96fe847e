"""Parallelism tests: ring attention exactness, mesh helpers, collective
ops, ParallelExecutor convergence parity.

Reference: unittests/parallel_executor_test_base.py:24
check_network_convergence (Executor vs ParallelExecutor loss comparison);
ring attention is this build's new sequence-parallel capability.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.framework import Program, program_guard
from paddle_tpu.parallel import make_mesh, mesh_scope, ring_attention


def reference_attention(q, k, v, causal=False):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        S = q.shape[2]
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_ring_attention_exact(causal):
    B, H, S, D = 2, 4, 64, 16
    rs = np.random.RandomState(0)
    q = rs.randn(B, H, S, D).astype("float32")
    k = rs.randn(B, H, S, D).astype("float32")
    v = rs.randn(B, H, S, D).astype("float32")

    mesh = make_mesh({"sp": 8})
    out = ring_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         mesh, axis_name="sp", causal=causal)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=1e-4)


def test_ring_attention_jit_sharded():
    """ring attention under jit with sequence-sharded inputs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    B, H, S, D = 1, 2, 32, 8
    rs = np.random.RandomState(1)
    q = rs.randn(B, H, S, D).astype("float32")
    k = rs.randn(B, H, S, D).astype("float32")
    v = rs.randn(B, H, S, D).astype("float32")
    mesh = make_mesh({"sp": 8})
    sh = NamedSharding(mesh, P(None, None, "sp", None))
    qd, kd, vd = (jax.device_put(x, sh) for x in (q, k, v))

    fn = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, "sp",
                                                causal=True))
    out = fn(qd, kd, vd)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=1e-4)


def test_mesh_helpers():
    m = make_mesh()
    assert m.devices.size == 8
    m2 = make_mesh({"dp": 4, "mp": 2})
    assert m2.axis_names == ("dp", "mp")
    with mesh_scope(m2) as mm:
        from paddle_tpu.parallel.mesh import current_mesh
        assert current_mesh() is mm


_PE_OPTIMIZERS = {
    "sgd": lambda: fluid.optimizer.SGD(learning_rate=0.05),
    "momentum": lambda: fluid.optimizer.Momentum(learning_rate=0.05,
                                                 momentum=0.9),
    "adam": lambda: fluid.optimizer.Adam(learning_rate=0.01),
}


@pytest.mark.parametrize("opt", list(_PE_OPTIMIZERS))
def test_parallel_executor_matches_single_device(opt):
    """reference parallel_executor_test_base.check_network_convergence:
    same net, Executor vs ParallelExecutor, losses must track, whatever
    per-parameter update the optimizer appends."""

    def build():
        img = fluid.layers.data(name="img", shape=[32], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=img, size=32, act="relu")
        p = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=p, label=label))
        _PE_OPTIMIZERS[opt]().minimize(loss)
        return loss

    rs = np.random.RandomState(0)
    W = rs.randn(32, 4).astype("float32")
    xs = rs.rand(20, 64, 32).astype("float32")
    ys = np.stack([np.argmax(x @ W, 1).reshape(-1, 1) for x in xs]).astype(
        "int64")

    losses = {}
    for mode in ("single", "parallel"):
        with program_guard(Program(), Program()):
            loss = build()
            main, startup = fluid.default_main_program(), \
                fluid.default_startup_program()
            main.random_seed = startup.random_seed = 7
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            seq = []
            if mode == "single":
                for x, y in zip(xs, ys):
                    out, = exe.run(main, feed={"img": x, "label": y},
                                   fetch_list=[loss])
                    seq.append(float(np.asarray(out).item()))
            else:
                pe = fluid.ParallelExecutor(
                    use_cuda=False, loss_name=loss.name, main_program=main)
                assert pe.device_count == 8
                for x, y in zip(xs, ys):
                    out, = pe.run([loss], feed={"img": x, "label": y})
                    seq.append(float(np.asarray(out).mean()))
            losses[mode] = seq
    # same init (seeded) + same data -> numerically close loss curves
    np.testing.assert_allclose(losses["single"], losses["parallel"],
                               rtol=2e-2, atol=2e-3)
    assert losses["parallel"][-1] < losses["parallel"][0]


def test_collective_ops_single_device_identity():
    # outside a mapped axis all_reduce is identity
    from paddle_tpu.core import registry
    from paddle_tpu.core.executor_core import OpContext
    opdef = registry.lookup("all_reduce")
    xv = jnp.arange(4.0)
    res = registry.run_kernel(opdef, OpContext(), {"X": [xv]}, {})
    np.testing.assert_allclose(np.asarray(res["Out"][0]), np.arange(4.0))


@pytest.mark.parametrize("opt", list(_PE_OPTIMIZERS))
def test_parallel_executor_iters_scan(opt):
    """PE(iters=K): K data-parallel steps in one mesh dispatch must match
    K sequential PE.run calls: same losses, and the same value in every
    persistable var (the weights and the optimizer's accumulators)."""
    import paddle_tpu as fluid

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            p = fluid.layers.fc(input=x, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=p, label=y))
            _PE_OPTIMIZERS[opt]().minimize(loss)
        return main, startup, loss

    def persistables(main, scope):
        return {n: np.asarray(fluid.executor._ensure_addressable(
                    scope.find_var(n)))
                for n, v in main.global_block().vars.items()
                if v.persistable and scope.has_var(n)}

    K = 4
    rs = np.random.RandomState(2)
    feeds = [{"x": rs.randn(16, 6).astype("float32"),
              "y": rs.randn(16, 1).astype("float32")} for _ in range(K)]

    main, startup, loss = build()
    sc1 = fluid.Scope()
    with fluid.scope_guard(sc1):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main)
        seq = [float(np.asarray(pe.run([loss.name], feed=f)[0]).mean())
               for f in feeds]
        state_seq = persistables(main, sc1)

    main2, startup2, loss2 = build()
    sc2 = fluid.Scope()
    with fluid.scope_guard(sc2):
        fluid.Executor(fluid.CPUPlace()).run(startup2)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss2.name,
                                    main_program=main2)
        out, = pe.run([loss2.name], feed=feeds, iters=K)
        scan = np.asarray(out).reshape(-1)
        state_scan = persistables(main2, sc2)

    np.testing.assert_allclose(scan, seq, rtol=2e-4, atol=1e-5)
    assert set(state_scan) == set(state_seq) and len(state_seq) >= 3
    for n in state_seq:
        np.testing.assert_allclose(state_scan[n], state_seq[n], rtol=2e-4,
                                   atol=1e-5, err_msg=n)
