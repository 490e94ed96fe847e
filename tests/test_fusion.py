"""Cost-guided operator fusion (paddle_tpu/fusion + ops/fused_ops).

Parity is BITWISE by contract: the fused kernels replay the exact
expression tree of the scalar ops over a concat of the members, and
elementwise arithmetic is per-element — so fused-vs-unfused loss curves
must agree to the bit on the Executor AND the ParallelExecutor (zero1
off and on). Hazardous programs must be REFUSED (PTA03x raised), never
fused; one seeded mutation per hazard class proves it. Bucket packing
mirrors test_collective_edge.py's edge sizes: non-divisible, prime,
scalar, bf16.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import flags, fusion
from paddle_tpu.analysis import ProgramVerificationError
from paddle_tpu.core import executor_core, registry
from paddle_tpu.core.framework import Operator
from paddle_tpu.ops import fused_ops
from paddle_tpu.parallel import zero1

OPTS = {
    "sgd": lambda: fluid.optimizer.SGD(learning_rate=0.1),
    "momentum": lambda: fluid.optimizer.Momentum(learning_rate=0.1,
                                                 momentum=0.9),
    "adam": lambda: fluid.optimizer.Adam(learning_rate=0.01),
}


def _build(opt_name, seed=7):
    """3 fc layers -> 6 parameters: enough members for a real bucket."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=6, act="relu")
        h2 = fluid.layers.fc(input=h, size=5, act="relu")
        p = fluid.layers.fc(input=h2, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        OPTS[opt_name]().minimize(loss)
        main.random_seed = startup.random_seed = seed
    return main, startup, loss


def _data(n=16, seed=1):
    rs = np.random.RandomState(seed)
    xs = rs.randn(n, 8).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) * 0.3).astype(np.float32)
    return xs, ys


# ---------------------------------------------------------------------------
# bitwise parity: Executor
# ---------------------------------------------------------------------------
def _exe_losses(opt_name, fuse, steps=4):
    with flags.flag_guard(fuse=fuse):
        main, startup, loss = _build(opt_name)
        exe = fluid.Executor(fluid.CPUPlace())
        xs, ys = _data()
        losses = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(steps):
                (lv,) = exe.run(main, feed={"x": xs, "y": ys},
                                fetch_list=[loss])
                losses.append(np.asarray(lv).copy())
        return np.stack(losses)


@pytest.mark.parametrize("opt_name", sorted(OPTS))
def test_executor_parity_bitwise(opt_name):
    ref = _exe_losses(opt_name, fuse=False)
    got = _exe_losses(opt_name, fuse=True)
    np.testing.assert_array_equal(got, ref)


def test_executor_applies_and_caches_plan():
    with flags.flag_guard(fuse=True):
        main, startup, loss = _build("adam")
        exe = fluid.Executor(fluid.CPUPlace())
        xs, ys = _data()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(2):
                exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss])
        plans = [plan for _, plan in exe._fusion_cache.values()
                 if plan is not None]
        assert len(plans) == 1  # startup caches too, but fuses nothing
        plan = plans[0]
        assert plan.buckets
        assert plan.buckets[0]["opt"] == "adam"
        assert plan.buckets[0]["n"] == 6  # all six params in one bucket


# ---------------------------------------------------------------------------
# bitwise parity: ParallelExecutor (dp mesh), zero1 off and on
# ---------------------------------------------------------------------------
def _pe_losses(opt_name, fuse, z1, steps=3):
    # dp=4 x mp=2 — the config the CI dryrun gates under verify=full.
    # (Recompiling a different graph can shift XLA's reduction fusion by
    # an ulp at other mesh shapes; the parity contract is per-config.)
    with flags.flag_guard(fuse=fuse, zero1=z1):
        main, startup, loss = _build(opt_name)
        xs, ys = _data(n=16)
        losses = []
        with fluid.scope_guard(fluid.Scope()):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            pe = fluid.ParallelExecutor(use_cuda=False, main_program=main,
                                        loss_name=loss.name,
                                        mesh_shape={"dp": 4, "mp": 2})
            for _ in range(steps):
                (lv,) = pe.run([loss.name], feed={"x": xs, "y": ys})
                losses.append(np.asarray(lv).copy())
        return np.stack(losses)


@pytest.mark.parametrize("z1", [False, True], ids=["plain", "zero1"])
@pytest.mark.parametrize("opt_name", sorted(OPTS))
def test_parallel_executor_parity_bitwise(opt_name, z1):
    ref = _pe_losses(opt_name, fuse=False, z1=z1)
    got = _pe_losses(opt_name, fuse=True, z1=z1)
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# vertical elementwise chains
# ---------------------------------------------------------------------------
def test_vertical_chain_fuses_and_matches_bitwise():
    main = fluid.Program()
    with fluid.unique_name.guard(), \
            fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        a = fluid.layers.relu(x)
        b = fluid.layers.tanh(a)
        c = fluid.layers.sigmoid(b)
        d = fluid.layers.scale(c, scale=2.0, bias=0.5)
    fused, plan = fusion.apply(main, feed_names=["x"],
                               fetch_names=[d.name])
    assert plan is not None and len(plan.chains) == 1
    assert plan.chains[0]["types"] == ["relu", "tanh", "sigmoid", "scale"]
    assert plan.n_ops_after == plan.n_ops_before - 3

    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.random.RandomState(0).randn(2, 64).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        (ref,) = exe.run(main, feed={"x": xs}, fetch_list=[d.name])
    with fluid.scope_guard(fluid.Scope()):
        (got,) = exe.run(fused, feed={"x": xs}, fetch_list=[d.name])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_vertical_skips_types_with_live_grads():
    """Training programs pair each forward with a grad op (PTA007 type
    pairing) — the vertical pass must leave those chains alone."""
    main, _startup, loss = _build("sgd")
    fused, plan = fusion.apply(main, feed_names=["x", "y"],
                               fetch_names=[loss.name])
    assert plan is None or not plan.chains


# ---------------------------------------------------------------------------
# bucket packing edge cases (mirrors test_collective_edge.py sizes)
# ---------------------------------------------------------------------------
def test_pack_unpack_round_trip_odd_sizes():
    rs = np.random.RandomState(3)
    for dtype in (jnp.float32, jnp.bfloat16):
        vals = [jnp.asarray(rs.randn(*s), dtype)
                for s in [(13, 3), (17,), (1,), (5, 7)]]
        buf = fused_ops._pack(vals, 0)
        assert buf.shape == (sum(int(v.size) for v in vals),)
        assert buf.dtype == dtype
        for got, want in zip(fused_ops._unpack(buf, vals, 0), vals):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))


def test_pack_unpack_shard_layout_axis1():
    """zero1 members are (parts, shard) lanes: packing joins the shard
    axis and never touches dim 0 (which keeps its dp sharding)."""
    rs = np.random.RandomState(4)
    vals = [jnp.asarray(rs.randn(4, w).astype(np.float32))
            for w in (3, 1, 5)]
    buf = fused_ops._pack(vals, 4)
    assert buf.shape == (4, 9)
    for got, want in zip(fused_ops._unpack(buf, vals, 4), vals):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _run_kernel(op_type, ins, attrs):
    d = registry.lookup(op_type)
    ctx = executor_core.OpContext(eager=True)
    return registry.run_kernel(d, ctx, ins, attrs)


def test_fused_sgd_kernel_bf16_parity():
    """The packed update equals N scalar sgd ops member by member — on
    bf16 too (cast positions preserved)."""
    rs = np.random.RandomState(5)
    shapes = [(13, 3), (17,), (1,)]
    for dtype in (jnp.float32, jnp.bfloat16):
        ps = [jnp.asarray(rs.randn(*s), dtype) for s in shapes]
        gs = [jnp.asarray(rs.randn(*s), dtype) for s in shapes]
        lr = jnp.asarray([0.1], jnp.float32)
        got = _run_kernel(
            "fused_sgd_update",
            {"Param": ps, "Grad": gs, "LearningRate": [lr]},
            {"shard_rows": 0})["ParamOut"]
        for p, g, want in zip(ps, gs, got):
            ref = _run_kernel(
                "sgd", {"Param": [p], "Grad": [g], "LearningRate": [lr]},
                {})["ParamOut"][0]
            assert np.asarray(want).dtype == np.asarray(ref).dtype
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(ref))


# References for the direct pallas-kernel tests replay the kernel's
# expression tree on identically padded (rows, 128) tiles AND under one
# jit: the interpreted kernel body is a single XLA computation, so
# mul+add pairs contract to FMAs — an eager op-by-op reference rounds
# the intermediate and drifts an ulp for n >= ~16.
@jax.jit
def _mom_ref(p, g, v, lr, mu):
    v_out = mu * v + g
    return p - lr * v_out, v_out


@jax.jit
def _adam_ref(p, g, m1, m2, lr_t, b1, omb1, b2, omb2, eps):
    m1o = b1 * m1 + omb1 * g
    m2o = b2 * m2 + omb2 * jnp.square(g)
    return p - lr_t * m1o / (jnp.sqrt(m2o) + eps), m1o, m2o


@pytest.mark.parametrize("n", [1, 17, 1029])
def test_pallas_momentum_bucket_bitwise(n):
    from paddle_tpu.fusion import kernels as fk

    rs = np.random.RandomState(n)
    p, g, v = (jnp.asarray(rs.randn(n).astype(np.float32))
               for _ in range(3))
    lr = jnp.float32(0.1)
    po, vo = fk.momentum_bucket(p, g, v, lr, 0.9, False)
    p_ref, v_ref = _mom_ref(fk._pad2d(p), fk._pad2d(g), fk._pad2d(v),
                            lr, jnp.float32(0.9))
    np.testing.assert_array_equal(
        np.asarray(vo), np.asarray(v_ref).reshape(-1)[:n])
    np.testing.assert_array_equal(
        np.asarray(po), np.asarray(p_ref).reshape(-1)[:n])


@pytest.mark.parametrize("n", [1, 17, 1029])
def test_pallas_adam_bucket_parity(n):
    """What parity holds (fusion/kernels.py): the moments, which only
    multiply and add, are bitwise everywhere. The parameter goes through
    sqrt and a divide; interpreted on the CPU the kernel's per-block loop
    body and the whole-array reference are two XLA:CPU compilations whose
    vectorised divide differs by a few ulp (14 of 1029 elements here), so
    the parameter is held to 32 eps of the terms of its final subtraction,
    |update| + |result| (measured worst case 13.4 eps at n=1e6). Compiled
    by Mosaic on a v5e the parameter is bitwise too (chip_smoke.py checks
    it there)."""
    from paddle_tpu.fusion import kernels as fk

    rs = np.random.RandomState(n)
    p, g = (jnp.asarray(rs.randn(n).astype(np.float32))
            for _ in range(2))
    m1 = jnp.asarray(np.abs(rs.randn(n)).astype(np.float32))
    m2 = jnp.asarray(np.abs(rs.randn(n)).astype(np.float32))
    b1, b2, eps = 0.9, 0.999, 1e-8
    lr_t = jnp.float32(0.01)
    po, m1o, m2o = fk.adam_bucket(p, g, m1, m2, lr_t, b1, b2, eps)
    # (1-b1)/(1-b2) in python doubles then f32 — where the kernel (and
    # the scalar op) evaluate them
    p_ref, m1_ref, m2_ref = _adam_ref(
        fk._pad2d(p), fk._pad2d(g), fk._pad2d(m1), fk._pad2d(m2),
        lr_t, jnp.float32(b1), jnp.float32(1 - b1),
        jnp.float32(b2), jnp.float32(1 - b2), jnp.float32(eps))
    np.testing.assert_array_equal(
        np.asarray(m1o), np.asarray(m1_ref).reshape(-1)[:n])
    np.testing.assert_array_equal(
        np.asarray(m2o), np.asarray(m2_ref).reshape(-1)[:n])
    p_ref = np.asarray(p_ref).reshape(-1)[:n]
    bound = 32 * np.finfo(np.float32).eps * (
        np.abs(np.asarray(p) - p_ref) + np.abs(p_ref))
    assert np.all(np.abs(np.asarray(po) - p_ref) <= bound)


def test_bucket_splitting_respects_budget_and_partitions():
    """Small budgets split the update into several buckets; every bucket
    holds >= 2 members, no param lands twice, and the fused program still
    reproduces the unfused one bitwise when run directly."""
    main, _startup, loss = _build("adam")
    fused, plan = fusion.apply(main, feed_names=["x", "y"],
                               fetch_names=[loss.name],
                               bucket_bytes=160)  # ~40 f32 elems
    assert plan is not None and len(plan.buckets) >= 2
    seen = []
    for b in plan.buckets:
        assert b["n"] >= 2
        seen.extend(b["params"])
    assert len(seen) == len(set(seen))

    exe = fluid.Executor(fluid.CPUPlace())
    xs, ys = _data()

    def run(prog):
        main2, startup2, loss2 = _build("adam")
        del main2
        out = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup2)
            for _ in range(3):
                (lv,) = exe.run(prog, feed={"x": xs, "y": ys},
                                fetch_list=[loss.name])
                out.append(np.asarray(lv).copy())
        return np.stack(out)

    np.testing.assert_array_equal(run(fused), run(main))


def test_zero1_bucket_is_shard_aware():
    """After the zero1 rewrite the bucket packs (parts, shard) lanes —
    shard_rows records the parts dim and gathers stay behind the fused
    update in program order."""
    main, _startup, loss = _build("adam")
    sharded, _zplan = zero1.apply(main, 4)
    fused, plan = fusion.apply(sharded, feed_names=["x", "y"],
                               fetch_names=[loss.name])
    assert plan is not None and plan.buckets
    assert all(b["shard_rows"] == 4 for b in plan.buckets)
    types = [op.type for op in fused.global_block().ops]
    upd = types.index("fused_adam_update")
    scatters = [i for i, t in enumerate(types) if t == "zero1_scatter"]
    gathers = [i for i, t in enumerate(types) if t == "zero1_gather"]
    assert all(i < upd for i in scatters)
    assert all(i > upd for i in gathers)


# ---------------------------------------------------------------------------
# hazard refusal: one seeded illegal mutation per PTA03x class
# ---------------------------------------------------------------------------
def _refused_with(prog, loss, code, feeds=("x", "y")):
    with pytest.raises(ProgramVerificationError) as ei:
        fusion.apply(prog, feed_names=list(feeds),
                     fetch_names=[loss.name])
    assert code in ei.value.report.codes()


def test_refuses_cyclic_source_pta030():
    main, _startup, loss = _build("sgd")
    gb = main.global_block()
    for nm in ("cyc_a", "cyc_b"):
        gb.create_var(name=nm, shape=[1], dtype="float32")
    gb.append_op(type="scale", inputs={"X": ["cyc_b"]},
                 outputs={"Out": ["cyc_a"]}, attrs={"scale": 1.0})
    gb.append_op(type="scale", inputs={"X": ["cyc_a"]},
                 outputs={"Out": ["cyc_b"]}, attrs={"scale": 1.0})
    _refused_with(main, loss, "PTA030")


def test_refuses_clobbered_forward_pta031():
    """In-place overwrite of a forward activation between forward and
    backward: the grad op now reads a later SSA version (WAR)."""
    main, _startup, loss = _build("sgd")
    gb = main.global_block()
    for i, op in enumerate(gb.ops):
        if not op.type.endswith("_grad"):
            continue
        base = op.type[:-len("_grad")]
        grad_reads = {n for ns in op.inputs.values() for n in ns
                      if not n.endswith("@GRAD")}
        for j in range(i - 1, -1, -1):
            fwd = gb.ops[j]
            if fwd.type != base:
                continue
            shared = [n for ns in fwd.inputs.values() for n in ns
                      if n in grad_reads
                      and not gb.vars[n].persistable]
            if not shared:
                continue
            clobber = Operator(gb, "scale", {"X": [shared[0]]},
                               {"Out": [shared[0]]}, {"scale": 1.0})
            gb.ops.insert(j + 1, clobber)
            main._mutation += 1
            _refused_with(main, loss, "PTA031")
            return
    pytest.fail("found no forward/grad pair sharing a non-persistable "
                "input to clobber")


def test_refuses_double_weight_write_pta032():
    main, _startup, loss = _build("sgd")
    gb = main.global_block()
    w = next(n for n, v in gb.vars.items()
             if getattr(v, "persistable", False) and n.endswith(".w_0"))
    gb.append_op(type="scale", inputs={"X": [w]}, outputs={"Out": [w]},
                 attrs={"scale": 1.0})
    _refused_with(main, loss, "PTA032")


def test_refuses_zero1_gather_rewire_pta033():
    main, _startup, loss = _build("momentum")
    sharded, _zplan = zero1.apply(main, 4)
    gb = sharded.global_block()
    gat = next(op for op in gb.ops if op.type == "zero1_gather")
    upd = gat.input("X")[0]
    gat.rename_input(upd, upd.replace("@zero1_upd", "@zero1_shard"))
    sharded._mutation += 1
    _refused_with(sharded, loss, "PTA033")


def test_refuses_stale_donated_view_pta034():
    """A reshape view of a weight captured before the optimizer update,
    read after it: stale alias of a donated buffer."""
    main, _startup, loss = _build("sgd")
    gb = main.global_block()
    w = next(n for n, v in gb.vars.items()
             if getattr(v, "persistable", False) and n.endswith(".w_0"))
    numel = int(np.prod(gb.vars[w].shape))
    gb.create_var(name="w_view", shape=[numel], dtype="float32")
    gb.create_var(name="w_stale", shape=[numel], dtype="float32")
    view = Operator(gb, "reshape", {"X": [w]}, {"Out": ["w_view"]},
                    {"shape": [numel]})
    gb.ops.insert(0, view)
    reader = Operator(gb, "scale", {"X": ["w_view"]},
                      {"Out": ["w_stale"]}, {"scale": 1.0})
    gb.ops.append(reader)
    main._mutation += 1
    _refused_with(main, loss, "PTA034")


def test_fused_program_passes_full_verify():
    from paddle_tpu import analysis

    main, _startup, loss = _build("adam")
    sharded, _zplan = zero1.apply(main, 4)
    fused, plan = fusion.apply(sharded, feed_names=["x", "y"],
                               fetch_names=[loss.name])
    assert plan is not None
    rep = analysis.verify(fused, feed_names=["x", "y"],
                          fetch_names=[loss.name], level="full")
    assert rep.ok and not rep.errors(), rep.render()
