"""Static ProgramDesc verification (paddle_tpu.analysis).

Covers the PTA code catalog end to end: clean book-style programs must
verify with zero errors, and targeted mutations — deleted producer op,
reordered collective, collective under control flow, non-divisible shard,
read-after-donate, write-after-read — must each surface their stable code.
Plus the liveness peak-HBM estimate (gated against measured live bytes on
the 8-virtual-device mesh), the FLAGS_verify executor wiring, and the
`check` CLI.
"""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, flags
from paddle_tpu.analysis import (ProgramVerificationError, dataflow,
                                 schedule)
from paddle_tpu.core.framework import (OpRole, OP_ROLE_ATTR_NAME, Operator,
                                       Program, program_guard)
from paddle_tpu.parallel import zero1
from paddle_tpu.parallel import autoshard


# ---------------------------------------------------------------------------
# program builders
# ---------------------------------------------------------------------------
def _mlp():
    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        p = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, ["x", "y"], [loss.name]


def _conv():
    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                dtype="float32")
        lab = fluid.layers.data(name="lab", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(img, num_filters=4, filter_size=3,
                                act="tanh")
        p = fluid.layers.pool2d(c, pool_size=2, pool_type="max",
                                pool_stride=2)
        f = fluid.layers.fc(p, size=10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(f, lab))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, ["img", "lab"], [loss.name]


def _embedding():
    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        emb = fluid.layers.embedding(ids, size=[32, 16])
        h = fluid.layers.fc(emb, size=32, act="relu")
        p = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, ["ids", "y"], [loss.name]


def _while_loop():
    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64",
                                           value=5)
        acc = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                         value=0.0)
        cond = fluid.layers.less_than(x=i, y=limit)
        w = fluid.layers.While(cond=cond)
        with w.block():
            new_acc = fluid.layers.elementwise_add(
                acc, fluid.layers.fill_constant(
                    shape=[1], dtype="float32", value=2.0))
            fluid.layers.assign(new_acc, acc)
            fluid.layers.increment(x=i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
    return main, [], [acc.name]


def _zero1_program(parts=8):
    main, feeds, fetches = _mlp()
    rewritten, plan = zero1.apply(main, parts)
    return rewritten, plan, feeds, fetches


# ---------------------------------------------------------------------------
# clean-program sweep: book-style programs verify with zero errors
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("builder", [_mlp, _conv, _embedding, _while_loop],
                         ids=["mlp", "conv", "embedding", "while"])
def test_clean_programs_verify_with_zero_errors(builder):
    main, feeds, fetches = builder()
    r = analysis.verify(main, level="full", feed_names=feeds,
                        fetch_names=fetches)
    assert r.ok and r.rc == 0, [str(d) for d in r.errors()]
    assert not r.warnings(), [str(d) for d in r.warnings()]
    assert r.summary["n_ops"] > 0


def test_zero1_rewritten_program_verifies_clean():
    rewritten, plan, feeds, fetches = _zero1_program()
    r = analysis.verify(rewritten, level="full", feed_names=feeds,
                        fetch_names=fetches, mesh_axes={"dp": 8},
                        zplan=plan)
    assert r.ok, [str(d) for d in r.errors()]


def test_verify_rejects_unknown_level():
    main, feeds, fetches = _mlp()
    with pytest.raises(ValueError, match="level"):
        analysis.verify(main, level="paranoid")


# ---------------------------------------------------------------------------
# mutation tests: each corruption class surfaces its stable PTA code
# ---------------------------------------------------------------------------
def test_mutation_deleted_producer_is_pta001():
    main, feeds, fetches = _mlp()
    ops = main.global_block().ops
    del ops[next(i for i, op in enumerate(ops) if op.type == "mul")]
    r = analysis.verify(main, level="basic", feed_names=feeds,
                        fetch_names=fetches)
    assert "PTA001" in r.codes() and r.rc == 1
    d = next(d for d in r.errors() if d.code == "PTA001")
    # location quality: op index, op type and the var name are all present
    assert d.op_idx is not None and d.op_type and d.var


def test_mutation_duplicate_output_is_pta002():
    main, feeds, fetches = _mlp()
    gb = main.global_block()
    op = next(op for op in gb.ops if op.type == "mul")
    op.outputs["Out"] = [op.outputs["Out"][0], op.outputs["Out"][0]]
    r = analysis.verify(main, level="basic", feed_names=feeds,
                        fetch_names=fetches)
    assert "PTA002" in r.codes()


def test_mutation_bad_weight_shape_is_pta004():
    main, feeds, fetches = _mlp()
    gb = main.global_block()
    # corrupt a LEAF shape (a parameter: nothing re-infers it), breaking
    # the mul contract's inner-dim check on replay
    w = next(n for n, v in gb.vars.items() if v.shape == (16, 1))
    gb.vars[w].shape = (999, 1)
    r = analysis.verify(main, level="basic", feed_names=feeds,
                        fetch_names=fetches)
    assert "PTA004" in r.codes() and r.rc == 1


def test_mutation_reordered_collective_is_pta012():
    rewritten, plan, feeds, fetches = _zero1_program()
    ops = rewritten.global_block().ops
    gi = next(i for i, op in enumerate(ops) if op.type == "zero1_gather")
    # issue the gather BEFORE the shard update it must consume
    ops.insert(0, ops.pop(gi))
    r = analysis.verify(rewritten, level="full", feed_names=feeds,
                        fetch_names=fetches, mesh_axes={"dp": 8},
                        zplan=plan)
    assert "PTA012" in r.codes() and r.rc == 1


def test_mutation_collective_under_control_flow_is_pta013():
    main, feeds, fetches = _while_loop()
    gb = main.global_block()
    wh = next(op for op in gb.ops if op.type == "while")
    sub = next(v for v in wh.attrs.values()
               if v.__class__.__name__ == "Block")
    name = next(n for op in sub.ops for n in op.input_arg_names() if n)
    sub.append_op(type="all_reduce", inputs={"X": [name]},
                  outputs={"Out": [name]}, attrs={})
    r = analysis.verify(main, level="full", feed_names=feeds,
                        fetch_names=fetches)
    assert "PTA013" in r.codes() and r.rc == 1


def test_mutation_nondivisible_shard_is_pta021():
    main, feeds, fetches = _mlp()
    gb = main.global_block()
    w = next(n for n, v in gb.vars.items() if v.shape == (8, 16))
    fluid.parallel.set_sharding(gb.var(w), ("dp", None))
    r = analysis.verify(main, level="full", feed_names=feeds,
                        fetch_names=fetches, mesh_axes={"dp": 3})
    assert "PTA021" in r.codes() and r.rc == 1


def test_mutation_unknown_mesh_axis_is_pta020():
    main, feeds, fetches = _mlp()
    gb = main.global_block()
    w = next(n for n, v in gb.vars.items() if v.shape == (8, 16))
    fluid.parallel.set_sharding(gb.var(w), ("mp", None))
    r = analysis.verify(main, level="full", feed_names=feeds,
                        fetch_names=fetches, mesh_axes={"dp": 8})
    assert "PTA020" in r.codes()


def test_mutation_read_after_donate_is_pta010():
    main, feeds, fetches = _mlp()
    gb = main.global_block()
    w = next(n for n, v in gb.vars.items()
             if getattr(v, "persistable", False) and v.shape == (8, 16))
    out = gb.create_var(name="late_read", dtype="float32", shape=(8, 16))
    gb.append_op(type="scale", inputs={"X": [w]}, outputs={"Out": [out]},
                 attrs={"scale": 1.0,
                        OP_ROLE_ATTR_NAME: int(OpRole.Forward)})
    r = analysis.verify(main, level="full", feed_names=feeds,
                        fetch_names=fetches + ["late_read"])
    assert "PTA010" in r.codes() and r.rc == 1


def test_mutation_write_after_read_is_pta011():
    main, feeds, fetches = _mlp()
    gb = main.global_block()
    # clobber relu's input between the forward consume and relu_grad's read
    g = next(i for i, op in enumerate(gb.ops) if op.type == "relu_grad")
    name = gb.ops[g].inputs["X"][0]
    boundary = next(i for i, op in enumerate(gb.ops)
                    if int(op.attrs.get(OP_ROLE_ATTR_NAME, 0))
                    & int(OpRole.Backward))
    gb.append_op(type="scale", inputs={"X": [name]},
                 outputs={"Out": [name]},
                 attrs={"scale": 2.0,
                        OP_ROLE_ATTR_NAME: int(OpRole.Forward)})
    gb.ops.insert(boundary, gb.ops.pop())
    r = analysis.verify(main, level="full", feed_names=feeds,
                        fetch_names=fetches)
    assert "PTA011" in r.codes() and r.rc == 1


# ---------------------------------------------------------------------------
# plan validation
# ---------------------------------------------------------------------------
def test_zero1_plan_geometry_tamper_is_pta021():
    rewritten, plan, feeds, fetches = _zero1_program()
    plan.entries[0].shard += 1  # shard * parts no longer covers padded
    r = analysis.verify(rewritten, level="full", feed_names=feeds,
                        fetch_names=fetches, mesh_axes={"dp": 8},
                        zplan=plan)
    assert "PTA021" in r.codes() and r.rc == 1


def test_autoshard_plan_validates_and_audits_edges():
    main, feeds, fetches = _embedding()
    gb = main.global_block()
    embw = next(n for n, v in gb.vars.items()
                if getattr(v, "persistable", False) and v.shape == (32, 16))
    fluid.parallel.set_sharding(gb.var(embw), ("mp", None))
    plan = autoshard.build_plan(main, {"dp": 4, "mp": 2})
    r = analysis.verify(main, level="full", feed_names=feeds,
                        fetch_names=fetches, mesh_axes={"dp": 4, "mp": 2},
                        aplan=plan)
    assert r.ok, [str(d) for d in r.errors()]
    assert "PTA023" not in r.codes()
    if plan.reshard_edges:  # tampered edge bytes must fail the audit
        plan.reshard_edges[0]["bytes"] = \
            int(plan.reshard_edges[0].get("bytes", 0)) * 10 + 12345
        r2 = analysis.verify(main, level="full", feed_names=feeds,
                             fetch_names=fetches,
                             mesh_axes={"dp": 4, "mp": 2}, aplan=plan)
        assert "PTA023" in r2.codes()


# ---------------------------------------------------------------------------
# peak-HBM estimate
# ---------------------------------------------------------------------------
def test_hbm_estimate_accounts_params_exactly():
    main, feeds, fetches = _mlp()
    est = analysis.estimate_peak_hbm(main, fetch_names=fetches)
    # fc weights/biases: 8*16 + 16 + 16*1 + 1 floats
    want = (8 * 16 + 16 + 16 * 1 + 1) * 4
    assert est["param_bytes"] == want
    assert est["peak_bytes_per_replica"] >= want
    assert est["peak_transient_bytes"] > 0
    assert est["peak_op_type"] is not None


def test_hbm_estimate_divides_sharded_vars():
    main, feeds, fetches = _mlp()
    gb = main.global_block()
    w = next(n for n, v in gb.vars.items() if v.shape == (8, 16))
    base = analysis.estimate_peak_hbm(main, mesh_axes={"dp": 8},
                                      fetch_names=fetches)
    fluid.parallel.set_sharding(gb.var(w), ("dp", None))
    sharded = analysis.estimate_peak_hbm(main, mesh_axes={"dp": 8},
                                         fetch_names=fetches)
    # the 8x16 weight now costs 1/8th per replica
    assert base["param_bytes"] - sharded["param_bytes"] \
        == (8 * 16) * 4 - (8 * 16) * 4 // 8


def test_hbm_estimate_within_2x_of_measured_on_mesh():
    """Acceptance gate: FLAGS_verify=full sets both gauges and the static
    estimate lands within 2x of the measured live bytes per replica."""
    from paddle_tpu import monitor

    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[32], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=64, act="relu")
        p = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    analysis.reset()
    with fluid.scope_guard(scope), flags.flag_guard(verify="full"):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main)
        xs = np.random.RandomState(0).randn(64, 32).astype("float32")
        ys = (xs[:, :1] * 0.5).astype("float32")
        pe.run([loss], feed={"x": xs, "y": ys})
    snap = monitor.registry().snapshot()
    est = next(v for k, v in snap.items()
               if k.startswith("analysis_peak_hbm_bytes_per_replica"))
    measured = snap["hbm_live_bytes_per_replica"]
    assert measured > 0 and est > 0
    assert est <= 2.0 * measured and measured <= 2.0 * est, \
        (est, measured)


# ---------------------------------------------------------------------------
# executor wiring (FLAGS_verify)
# ---------------------------------------------------------------------------
def test_flags_verify_full_clean_run_and_broken_raise():
    scope = fluid.Scope()
    xs = np.random.RandomState(0).randn(4, 8).astype("float32")
    ys = np.zeros((4, 1), "float32")
    analysis.reset()
    with fluid.scope_guard(scope), flags.flag_guard(verify="full"):
        exe = fluid.Executor(fluid.CPUPlace())
        main2, startup2 = Program(), Program()
        with fluid.unique_name.guard(), program_guard(main2, startup2):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=16, act="relu")
            p = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(p, y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe.run(startup2)
        out, = exe.run(main2, feed={"x": xs, "y": ys}, fetch_list=[loss])
        assert np.isfinite(np.asarray(out)).all()
        # a corrupted clone must refuse to compile, naming the code
        broken = main2.clone()
        ops = broken.global_block().ops
        del ops[next(i for i, op in enumerate(ops) if op.type == "mul")]
        broken._mutation += 1
        with pytest.raises(ProgramVerificationError) as ei:
            exe.run(broken, feed={"x": xs, "y": ys},
                    fetch_list=[loss.name])
        assert "PTA001" in ei.value.report.codes()
        assert "PTA001" in str(ei.value)


def test_ensure_verified_memoizes_per_program_config():
    main, feeds, fetches = _mlp()
    analysis.reset()
    with flags.flag_guard(verify="basic"):
        r1 = analysis.ensure_verified(main, feed_names=feeds,
                                      fetch_names=fetches)
        r2 = analysis.ensure_verified(main, feed_names=feeds,
                                      fetch_names=fetches)
        assert r1 is r2  # memo hit: the same Report object comes back
        main._mutation += 1
        r3 = analysis.ensure_verified(main, feed_names=feeds,
                                      fetch_names=fetches)
        assert r3 is not r1
    assert analysis.ensure_verified(main) is None  # level off -> no-op


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_check_selftest_ok(capsys):
    from paddle_tpu.cli import main as cli_main
    rc = cli_main(["check", "--selftest"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "check selftest: OK" in out and "PTA001" in out


def test_cli_check_model_dir_and_json(tmp_path, capsys):
    from paddle_tpu.cli import main as cli_main
    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        p = fluid.layers.fc(h, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [p], exe, main_program=main)
    rc = cli_main(["check", "--model-dir", d, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0 and rep["ok"] and rep["n_errors"] == 0
    assert rep["hbm"]["peak_bytes_per_replica"] > 0
    # corrupt the saved program: drop an op, expect rc 1 + PTA001
    path = os.path.join(d, "__model__")
    with open(path) as f:
        payload = json.load(f)
    blk = payload["program"]["blocks"][0]
    blk["ops"] = [op for op in blk["ops"] if op["type"] != "mul"]
    with open(path, "w") as f:
        json.dump(payload, f)
    rc = cli_main(["check", "--model-dir", d, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert "PTA001" in {dd["code"] for dd in rep["diagnostics"]}


def test_cli_check_usage_errors(capsys):
    from paddle_tpu.cli import main as cli_main
    assert cli_main(["check"]) == 2
    assert cli_main(["check", "--model-dir", "/nonexistent-dir-xyz"]) == 2
    assert cli_main(["check", "--selftest", "--mesh", "dp=oops"]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# SSA dataflow graph (analysis.dataflow)
# ---------------------------------------------------------------------------
def _hazards(program, feeds):
    r = analysis.Report(level="full")
    dataflow.check_hazards(program, r, feed_names=feeds)
    return r


def test_dataflow_graph_structure_on_mlp():
    main, feeds, _ = _mlp()
    g = dataflow.build_graph(main, feed_names=feeds)
    s = g.summary()
    assert s["n_nodes"] == len(main.global_block().ops)
    assert not s["has_cycle"] and s["n_edges"] > 0
    assert s["edge_kinds"]["raw"] > 0
    # sgd param updates are donating writes: donation-tagged WAR edges
    assert s["edge_kinds"]["donation"] > 0
    for name, (first, last) in g.live_ranges().items():
        if first is not None:
            assert first <= last, name


def test_dataflow_summarizes_while_bodies():
    main, feeds, _ = _while_loop()
    g = dataflow.build_graph(main, feed_names=feeds)
    assert g.summary()["n_summarized"] >= 1
    wh = next(n for n in g.nodes if n.op.type == "while")
    # the body's escaping reads/writes landed on the summarizing node
    assert wh.summarized and wh.reads and wh.writes
    assert _hazards(main, feeds).ok


def test_dataflow_zero1_groups_and_aliases():
    rewritten, _, feeds, _ = _zero1_program()
    g = dataflow.build_graph(rewritten, feed_names=feeds)
    groups = g.zero1_groups()
    full = [gr for gr in groups.values()
            if {"rs", "pshard", "upd", "gather"} <= set(gr)]
    assert len(full) == 4  # two fc layers x (weight, bias)
    # scatter outputs are tracked as views of their persistable roots
    assert any(n.endswith("@zero1_shard") for n in g.alias_of)
    assert _hazards(rewritten, feeds).ok


def test_dataflow_topo_orders_distinct_and_edge_valid():
    rewritten, _, feeds, _ = _zero1_program()
    g = dataflow.build_graph(rewritten, feed_names=feeds)
    orders = g.topo_orders(3)
    assert len(orders) >= 2
    assert len({tuple(o) for o in orders}) == len(orders)
    assert orders[0] == list(range(len(g.nodes)))  # program order first
    for order in orders:
        pos = {op_i: p for p, op_i in enumerate(order)}
        for u in range(len(g.nodes)):
            for v in g.succs[u]:
                assert pos[u] < pos[v], (u, v)


# ---------------------------------------------------------------------------
# dataflow mutation tests: one per PTA03x code
# ---------------------------------------------------------------------------
def test_mutation_cyclic_def_use_is_pta030():
    main, feeds, _ = _mlp()
    gb = main.global_block()
    for nm in ("a_cyc", "b_cyc"):
        gb.create_var(name=nm, shape=[1], dtype="float32")
    role = {"scale": 1.0, OP_ROLE_ATTR_NAME: int(OpRole.Forward)}
    gb.append_op(type="scale", inputs={"X": ["b_cyc"]},
                 outputs={"Out": ["a_cyc"]}, attrs=dict(role))
    gb.append_op(type="scale", inputs={"X": ["a_cyc"]},
                 outputs={"Out": ["b_cyc"]}, attrs=dict(role))
    r = _hazards(main, feeds)
    assert "PTA030" in r.codes() and r.rc == 1
    g = dataflow.build_graph(main, feed_names=feeds)
    assert g.has_cycle and len(g.cycle_nodes()) == 2
    with pytest.raises(ValueError, match="cyclic"):
        g.topo_order()


def test_mutation_grad_reads_overwritten_version_is_pta031():
    main, feeds, _ = _mlp()
    gb = main.global_block()
    relu = next(op for op in gb.ops if op.type == "relu")
    name = relu.input_arg_names()[0]
    k = next(i for i, op in enumerate(gb.ops) if op.type == "relu_grad")
    # clobber relu's input (in place) between forward and backward
    gb.append_op(type="scale", inputs={"X": [name]},
                 outputs={"Out": [name]},
                 attrs={"scale": 2.0,
                        OP_ROLE_ATTR_NAME: int(OpRole.Forward)})
    gb.ops.insert(k, gb.ops.pop())
    r = _hazards(main, feeds)
    assert "PTA031" in r.codes() and r.rc == 1
    d = next(d for d in r.errors() if d.code == "PTA031")
    assert d.var == name and "version" in d.message


def test_mutation_double_param_update_is_pta032():
    main, feeds, _ = _mlp()
    gb = main.global_block()
    sgd = next(op for op in gb.ops if op.type == "sgd")
    pname = sgd.input("Param")[0]
    gb.append_op(type="scale", inputs={"X": [pname]},
                 outputs={"Out": [pname]},
                 attrs={"scale": 1.0,
                        OP_ROLE_ATTR_NAME: int(OpRole.Optimize)})
    r = _hazards(main, feeds)
    assert "PTA032" in r.codes() and r.rc == 1
    assert next(d for d in r.errors()
                if d.code == "PTA032").var == pname


def test_mutation_gather_rewire_is_pta033():
    """The gather is rewired to consume the PRE-update shard: flat index
    order stays valid (PTA012-clean), only the dependence path breaks."""
    rewritten, _, feeds, fetches = _zero1_program()
    gb = rewritten.global_block()
    gat = next(op for op in gb.ops if op.type == "zero1_gather")
    pupd = gat.input("X")[0]
    gat.rename_input(pupd, pupd.replace("@zero1_upd", "@zero1_shard"))
    rewritten._mutation += 1
    r = _hazards(rewritten, feeds)
    assert "PTA033" in r.codes() and r.rc == 1
    # the full verify pipeline surfaces it too, and PTA012 alone would not
    full = analysis.verify(rewritten, level="full", feed_names=feeds,
                           fetch_names=fetches, mesh_axes={"dp": 8})
    assert "PTA033" in full.codes()
    assert "PTA012" not in full.codes()


def test_mutation_stale_shard_view_read_is_pta034():
    rewritten, _, feeds, _ = _zero1_program()
    gb = rewritten.global_block()
    # read a pre-update param-shard view AFTER the gather rewrote the root
    pshard = next(n for n in gb.vars if n.endswith("@zero1_shard"))
    gb.create_var(name="stale_view_read", shape=[1], dtype="float32")
    gb.append_op(type="scale", inputs={"X": [pshard]},
                 outputs={"Out": ["stale_view_read"]},
                 attrs={"scale": 1.0,
                        OP_ROLE_ATTR_NAME: int(OpRole.Forward)})
    r = _hazards(rewritten, feeds)
    assert "PTA034" in r.codes() and r.rc == 1
    d = next(d for d in r.errors() if d.code == "PTA034")
    assert d.var == pshard and "view" in d.message


def test_donated_param_read_inside_while_body_is_pta010():
    """Sub-block propagation regression: a while body that reads a param
    AFTER the optimizer updated it observes the donated buffer — the flat
    block-0 scan cannot see the read, the sub-block walk must."""
    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        p = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        w = next(v for n, v in main.global_block().vars.items()
                 if getattr(v, "persistable", False) and v.shape == (8, 16))
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64",
                                           value=1)
        cond = fluid.layers.less_than(x=i, y=limit)
        wh = fluid.layers.While(cond=cond)
        with wh.block():
            fluid.layers.elementwise_add(w, w)  # stale donated-buffer read
            fluid.layers.increment(x=i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
    r = analysis.verify(main, level="full", feed_names=["x", "y"],
                        fetch_names=[loss.name])
    # the in-body read is flagged AT the body block, not just at the
    # summarizing while op
    d = next(dd for dd in r.errors() if dd.code == "PTA010"
             and dd.block_idx is not None and dd.block_idx > 0)
    assert "sub-block" in d.message


# ---------------------------------------------------------------------------
# diagnostics ordering (Report.sorted_diagnostics)
# ---------------------------------------------------------------------------
def test_report_orders_diagnostics_by_block_op_code():
    r = analysis.Report(level="full")
    r.add("PTA011", "later op", block_idx=0, op_idx=9, op_type="scale")
    r.add("PTA010", "sub-block read", block_idx=1, op_idx=0,
          op_type="scale")
    r.add("PTA010", "same op, higher code", block_idx=0, op_idx=2,
          op_type="mul")
    r.add("PTA001", "same op, lower code", block_idx=0, op_idx=2,
          op_type="mul")
    got = [(d.block_idx, d.op_idx, d.code)
           for d in r.sorted_diagnostics()]
    assert got == [(0, 2, "PTA001"), (0, 2, "PTA010"),
                   (0, 9, "PTA011"), (1, 0, "PTA010")]
    assert [d["code"] for d in r.to_dict()["diagnostics"]] \
        == ["PTA001", "PTA010", "PTA011", "PTA010"]
    lines = r.render().splitlines()[1:]
    assert [ln.split()[0] for ln in lines] \
        == ["PTA001", "PTA010", "PTA011", "PTA010"]


# ---------------------------------------------------------------------------
# schedule-equivalence property: any hazard-free topological order of the
# graph computes bitwise-identical losses and params
# ---------------------------------------------------------------------------
def test_hazard_free_topo_orders_are_bitwise_equivalent():
    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        p = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        main.random_seed = startup.random_seed = 7
    feeds, fetches = ["x", "y"], [loss.name]
    g = dataflow.build_graph(main, feed_names=feeds)
    orders = g.topo_orders(3)
    assert len(orders) >= 2
    rs = np.random.RandomState(0)
    xs = rs.randn(16, 8).astype("float32")
    ys = (xs @ rs.randn(8, 1) + 0.3).astype("float32")
    pnames = [n for n, v in main.global_block().vars.items()
              if getattr(v, "persistable", False)]

    def run(order):
        prog = main.clone()
        gb = prog.global_block()
        gb.ops = [gb.ops[i] for i in order]
        prog._mutation += 1
        assert _hazards(prog, feeds).ok  # reorder introduced no hazard
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)  # random_seed=7: identical init every run
            losses = []
            for _ in range(3):
                out, = exe.run(prog, feed={"x": xs, "y": ys},
                               fetch_list=fetches)
                losses.append(np.asarray(out).copy())
            params = {n: np.asarray(scope.find_var(n)).copy()
                      for n in pnames if scope.find_var(n) is not None}
        return losses, params

    base_losses, base_params = run(orders[0])
    assert np.isfinite(base_losses[-1]).all() and base_params
    for order in orders[1:]:
        losses, params = run(order)
        for a, b in zip(base_losses, losses):
            assert np.array_equal(a, b)  # bitwise, not allclose
        for name in base_params:
            assert np.array_equal(base_params[name], params[name]), name


# ---------------------------------------------------------------------------
# overlap scheduler (analysis.schedule)
# ---------------------------------------------------------------------------
def test_schedule_analyze_reports_critical_path_and_buckets():
    rewritten, _, feeds, _ = _zero1_program()
    sched = schedule.analyze(rewritten, mesh_axes={"dp": 8},
                             feed_names=feeds)
    assert sched.critical_path_ms > 0
    assert sched.serial_ms >= sched.critical_path_ms
    assert sched.comm_ms > 0  # the zero1 collectives are costed
    assert len(sched.plan.buckets) > 0 and len(sched.plan.moves) > 0
    d = sched.to_dict()
    assert d["overlap"]["hoistable_bytes"] > 0
    assert "critical path" in sched.render()


def test_schedule_apply_plan_reorders_and_reverifies():
    rewritten, _, feeds, fetches = _zero1_program()
    sched = schedule.analyze(rewritten, mesh_axes={"dp": 8},
                             feed_names=feeds)
    reordered, plan = schedule.apply_plan(rewritten, sched.plan,
                                          feed_names=feeds)
    assert reordered is not rewritten
    old = [op.type for op in rewritten.global_block().ops]
    new = [op.type for op in reordered.global_block().ops]
    assert sorted(old) == sorted(new) and old != new
    # hoisted scatters moved ahead of the optimizer section
    first_opt = next(i for i, op in enumerate(reordered.global_block().ops)
                     if op.type == "sgd")
    n_scatter_before = sum(1 for op in
                           reordered.global_block().ops[:first_opt]
                           if op.type == "zero1_scatter")
    assert n_scatter_before >= len(plan.moves)
    # the reordered program still verifies completely clean
    full = analysis.verify(reordered, level="full", feed_names=feeds,
                           fetch_names=fetches, mesh_axes={"dp": 8})
    assert full.ok and not full.warnings(), \
        [str(dd) for dd in full.diagnostics]


# one seeded illegal mutation per PTA03x class; each returns the program
def _seed_cycle_pta030():
    main, _, _ = _mlp()
    gb = main.global_block()
    for nm in ("cyc_a", "cyc_b"):
        gb.create_var(name=nm, shape=[1], dtype="float32")
    gb.append_op(type="scale", inputs={"X": ["cyc_b"]},
                 outputs={"Out": ["cyc_a"]}, attrs={"scale": 1.0})
    gb.append_op(type="scale", inputs={"X": ["cyc_a"]},
                 outputs={"Out": ["cyc_b"]}, attrs={"scale": 1.0})
    return main


def _seed_clobbered_forward_pta031():
    """In-place overwrite of a forward activation between forward and
    backward: the grad op now reads a later SSA version (WAR)."""
    main, _, _ = _mlp()
    gb = main.global_block()
    for i, op in enumerate(gb.ops):
        if not op.type.endswith("_grad"):
            continue
        grad_reads = {n for ns in op.inputs.values() for n in ns
                      if not n.endswith("@GRAD")}
        for j in range(i - 1, -1, -1):
            fwd = gb.ops[j]
            if fwd.type != op.type[:-len("_grad")]:
                continue
            shared = [n for ns in fwd.inputs.values() for n in ns
                      if n in grad_reads and not gb.vars[n].persistable]
            if shared:
                gb.ops.insert(j + 1, Operator(
                    gb, "scale", {"X": [shared[0]]}, {"Out": [shared[0]]},
                    {"scale": 1.0}))
                main._mutation += 1
                return main
    pytest.fail("found no forward/grad pair sharing a non-persistable "
                "input to clobber")


def _weight(gb):
    return next(n for n, v in gb.vars.items()
                if getattr(v, "persistable", False) and n.endswith(".w_0"))


def _seed_double_weight_write_pta032():
    main, _, _ = _mlp()
    gb = main.global_block()
    w = _weight(gb)
    gb.append_op(type="scale", inputs={"X": [w]}, outputs={"Out": [w]},
                 attrs={"scale": 1.0})
    return main


def _seed_gather_rewire_pta033():
    rewritten, _, _, _ = _zero1_program()
    gat = next(op for op in rewritten.global_block().ops
               if op.type == "zero1_gather")
    pupd = gat.input("X")[0]
    gat.rename_input(pupd, pupd.replace("@zero1_upd", "@zero1_shard"))
    rewritten._mutation += 1
    return rewritten


def _seed_stale_donated_view_pta034():
    """A reshape view of a weight captured before the optimizer update,
    read after it: stale alias of a donated buffer."""
    main, _, _ = _mlp()
    gb = main.global_block()
    w = _weight(gb)
    numel = int(np.prod(gb.vars[w].shape))
    gb.create_var(name="w_view", shape=[numel], dtype="float32")
    gb.create_var(name="w_stale", shape=[numel], dtype="float32")
    gb.ops.insert(0, Operator(gb, "reshape", {"X": [w]},
                              {"Out": ["w_view"]}, {"shape": [numel]}))
    gb.ops.append(Operator(gb, "scale", {"X": ["w_view"]},
                           {"Out": ["w_stale"]}, {"scale": 1.0}))
    main._mutation += 1
    return main


@pytest.mark.parametrize("code,seed", [
    ("PTA030", _seed_cycle_pta030),
    ("PTA031", _seed_clobbered_forward_pta031),
    ("PTA032", _seed_double_weight_write_pta032),
    ("PTA033", _seed_gather_rewire_pta033),
    ("PTA034", _seed_stale_donated_view_pta034),
], ids=["PTA030", "PTA031", "PTA032", "PTA033", "PTA034"])
def test_overlap_refuses_hazardous_source(code, seed):
    """The program rewrite the executors apply (the overlap schedule) never
    touches a program that carries a dataflow hazard: `analyze` has no
    schedule for it and `apply_plan` refuses to reorder it."""
    program = seed()
    for rewrite in (
            lambda: schedule.analyze(program, mesh_axes={"dp": 8},
                                     feed_names=["x", "y"]),
            lambda: schedule.apply_plan(program, feed_names=["x", "y"])):
        with pytest.raises(ProgramVerificationError) as ei:
            rewrite()
        assert code in ei.value.report.codes()


def test_schedule_bucket_bytes_knob_changes_plan():
    rewritten, _, feeds, _ = _zero1_program()
    g = dataflow.build_graph(rewritten, feed_names=feeds)
    one_big = schedule.build_overlap_plan(g, bucket_bytes=4 << 20)
    tiny = schedule.build_overlap_plan(g, bucket_bytes=1)
    assert len(tiny.buckets) > len(one_big.buckets)
    assert tiny.digest() != one_big.digest()
    assert sorted(i for b in tiny.buckets for i in b["ops"]) \
        == sorted(i for b in one_big.buckets for i in b["ops"])


def test_schedule_record_gauges_roundtrip():
    from paddle_tpu import monitor

    rewritten, _, feeds, _ = _zero1_program()
    sched = schedule.analyze(rewritten, mesh_axes={"dp": 8},
                             feed_names=feeds)
    schedule.record_gauges(sched)
    reg = monitor.registry()
    assert reg.gauge("dataflow_critical_path_ms").value \
        == pytest.approx(sched.critical_path_ms)
    assert reg.gauge("overlap_hoistable_bytes").value \
        == float(sched.plan.hoistable_bytes)
    assert reg.gauge("overlap_bucket_count").value \
        == float(len(sched.plan.buckets))


# ---------------------------------------------------------------------------
# analyze CLI
# ---------------------------------------------------------------------------
def test_cli_analyze_graph_selftest_ok(capsys):
    from paddle_tpu.cli import main as cli_main
    rc = cli_main(["analyze", "graph", "--selftest"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "analyze graph selftest: OK" in out and "PTA030" in out


def test_cli_analyze_schedule_selftest_ok(capsys):
    from paddle_tpu.cli import main as cli_main
    rc = cli_main(["analyze", "schedule", "--selftest", "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0 and rep["ok"]
    assert rep["schedule"]["critical_path_ms"] > 0
    assert rep["schedule"]["overlap"]["n_buckets"] > 0
    assert rep["seeded_rejected"] and "PTA033" in rep["seeded_codes"]


def test_cli_analyze_usage_errors(capsys):
    from paddle_tpu.cli import main as cli_main
    assert cli_main(["analyze", "graph"]) == 2
    assert cli_main(["analyze", "schedule",
                     "--model-dir", "/nonexistent-dir-xyz"]) == 2
    assert cli_main(["analyze", "schedule", "--selftest",
                     "--mesh", "dp=oops"]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# catalog stability
# ---------------------------------------------------------------------------
def test_catalog_codes_are_stable():
    """Append-only contract: these codes and their meanings are shipped;
    a rename or renumber here breaks green_gate and downstream tooling."""
    want = {"PTA001", "PTA002", "PTA003", "PTA004", "PTA005", "PTA006",
            "PTA007", "PTA008", "PTA010", "PTA011", "PTA012", "PTA013",
            "PTA020", "PTA021", "PTA022", "PTA023",
            "PTA030", "PTA031", "PTA032", "PTA033", "PTA034"}
    assert want <= set(analysis.CATALOG)
    with pytest.raises(ValueError, match="unknown diagnostic code"):
        analysis.Diagnostic("PTA999", "nope")


def test_catalog_synced_with_docs_and_tests():
    """Every shipped PTA code must be documented in docs/analysis.md's
    tables and exercised by at least one test under tests/ — the catalog,
    the docs, and the suite move together or not at all."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "analysis.md")) as f:
        doc = f.read()
    test_dir = os.path.join(root, "tests")
    corpus = ""
    for fn in sorted(os.listdir(test_dir)):
        if fn.endswith(".py"):
            with open(os.path.join(test_dir, fn)) as f:
                corpus += f.read()
    missing_doc = [c for c in analysis.CATALOG if c not in doc]
    missing_test = [c for c in analysis.CATALOG if c not in corpus]
    assert not missing_doc, f"codes undocumented in docs/analysis.md: " \
                            f"{missing_doc}"
    assert not missing_test, f"codes with no test referencing them: " \
                             f"{missing_test}"
