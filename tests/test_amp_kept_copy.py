"""The low-precision copy an update keeps beside a float32 master that a
Pallas kernel reads (`amp.KERNEL_SLOTS`: `moe_ffn`'s Gate, Up, Down):
written by `adam` from the value it has just computed, handed to the op in
place of the policy's cast. Same numbers, fewer bytes: every loss, master
and moment is bit for bit what the step that ignores the copies gives, and
after every step the copy is exactly `master.astype(bfloat16)`.

Which parameters get one is decided by what the optimizer can observe as
it appends the update (the slot table, the policy, no other writer), and
which a step reads by what the lowering can (the update is the only
writer): no flag, no argument, no model's name."""

import contextlib
import hashlib
import importlib
import json
import os
import re
import sys
import types
import unittest.mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp, analysis
from paddle_tpu.core import executor_core
from paddle_tpu.core.framework import Program, program_guard
from paddle_tpu.ops import lm_ops
from paddle_tpu.parallel import grouped

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import test_device_scopes as lowering  # noqa: E402
import test_laguna  # noqa: E402
import test_olmoe  # noqa: E402
import test_xing4  # noqa: E402

K = 3
CPU = types.SimpleNamespace(platform="cpu")


@pytest.fixture(autouse=True)
def policy_on():
    with amp.auto_cast(True):
        yield


def _file(config):
    with open(os.path.join(REPO, "chipbench", "configs",
                           config + ".json")) as f:
        return json.load(f)


# each model at the widths of its own test and the depth of its cell
# (the file's): 1, 4 and 5 expert layers
MODELS = {
    "olmoe_1b_7b": lambda: dict(
        _file("olmoe_1b_7b"), **dict(
            test_olmoe.SMALL, num_hidden_layers=_file(
                "olmoe_1b_7b")["num_hidden_layers"])),
    "laguna_xs_2": lambda: test_laguna._cfg(
        num_hidden_layers=_file("laguna_xs_2")["num_hidden_layers"],
        num_attention_heads_per_layer=[3, 4, 4, 3, 4]),
    "xing4_0_29b_a4b": lambda: test_xing4._cfg(
        num_hidden_layers=_file("xing4_0_29b_a4b")["num_hidden_layers"]),
}
EXPERT_LAYERS = {"olmoe_1b_7b": 1, "laguna_xs_2": 4, "xing4_0_29b_a4b": 5}


@pytest.fixture(scope="module")
def built():
    """{config: (its configuration, what its builder built)}, under the
    policy as the cells build."""
    out = {}
    with amp.auto_cast(True):
        for config in MODELS:
            builder = importlib.import_module("chipbench.configs." + config)
            cfg = MODELS[config]()
            out[config] = cfg, builder.build(fluid, cfg, 5)
    return out


def _moe_program(held=None, second_writer=False, optimizer=None):
    """Tokens [64, 64] through a projection and a `moe_ffn` of top-2
    experts of width 32: OLMoE's shape (8 experts, all held, softmax), or
    with `held` = (first, count) a share of 8 behind the `noaux_tc` router,
    AdamW with a global clip after it."""
    prog, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        h = fluid.layers.fc(x, 64, bias_attr=False)
        share = dict(score_func="sigmoid", norm_topk=True, held=held,
                     bias_attr=fluid.ParamAttr(name="b")) if held else {}
        outs = fluid.layers.moe_ffn(
            h, 8, 32, 2, gate_attr=fluid.ParamAttr(name="gate"),
            up_attr=fluid.ParamAttr(name="up"),
            down_attr=fluid.ParamAttr(name="down"), **share)
        loss = fluid.layers.mean(fluid.layers.square(outs[0] - x))
        if second_writer:
            gate = prog.global_block().var("gate")
            fluid.layers.assign(fluid.layers.scale(gate, 0.5), gate)
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(1.0), program=prog)
        (optimizer or fluid.optimizer.Adam(
            learning_rate=1e-2, weight_decay=0.1)).minimize(loss)
    fetch = [loss] + ([outs[5]] if held else [])
    return prog, startup, fetch


def _x(steps, seed=0):
    return {"x": np.random.default_rng(seed).standard_normal(
        (steps, 64, 64)).astype(np.float32)}


def _state(scope, prog):
    """Every persistable of the program in the scope but the copies."""
    copies = amp.kept_copies(prog)[1]
    return {v.name: np.asarray(scope.find_var(v.name))
            for v in prog.list_vars()
            if v.persistable and v.name not in copies
            and scope.find_var(v.name) is not None}


def _copies_are_casts(scope, prog):
    kept, names = amp.kept_copies(prog)
    assert kept and set(kept.values()) == set(names)
    for param, copy in kept.items():
        got = scope.find_var(copy)
        assert str(got.dtype) == "bfloat16"
        want = jnp.asarray(scope.find_var(param)).astype(jnp.bfloat16)
        assert np.array_equal(np.asarray(got).view(np.uint16),
                              np.asarray(want).view(np.uint16)), param


def _ignoring_copies():
    """The test's own switch: the lowering's map read as empty, so every
    op gets the master and the policy casts it, as before the copies (the
    updates write them all the same)."""
    return unittest.mock.patch.object(
        amp, "kept_copies",
        lambda program, real=amp.kept_copies: ({}, real(program)[1]))


def _train(held, ignore_copies, bias=None):
    """Two scans of K steps: what each fetched, and the state after each
    (with the copies read: each copy held to its master's cast)."""
    prog, startup, fetch = _moe_program(held)
    feeds = _x(2 * K)
    scope, fetched, states = fluid.Scope(), [], []
    with fluid.scope_guard(scope), (
            _ignoring_copies() if ignore_copies
            else contextlib.nullcontext()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if bias is not None:
            scope.set_var("b", jnp.asarray(bias, jnp.float32))
        for lo in (0, K):
            got = exe.run(prog, feed={"x": feeds["x"][lo:lo + K]},
                          fetch_list=fetch, iters=K)
            fetched.append([np.asarray(g) for g in got])
            states.append(_state(scope, prog))
            if not ignore_copies:
                _copies_are_casts(scope, prog)
    return fetched, states


def _same(a, b):
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


# (held, the router's bias): every expert held (no bound, no `cond`); 2 of
# 8 held with a row bound of 64 of the 128 rows (row tile 32) and a bias
# that sends the held experts about half the rows, so that some of the six
# steps take the bounded branch and some the overflow branch
HELD_BIAS = 0.3
SHAPES = {"olmoe_shaped": (None, None),
          "share_held": ((2, 2), [0, 0, HELD_BIAS, HELD_BIAS, 0, 0, 0, 0])}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_steps_are_bit_equal_to_the_steps_without_copies(shape):
    """Every loss, every master and both moments over two scans of three
    steps through `Executor.run(iters=3)`, against the same program
    lowered with the copies ignored; after each scan every copy is the
    bf16 cast of its master, bit for bit."""
    held, bias = SHAPES[shape]
    with unittest.mock.patch.object(grouped, "ROW_TILES", (32,)):
        bound = lm_ops.row_bound(128, 2, 8)
        with_copies = _train(held, False, bias)
        without = _train(held, True, bias)
    for got, want in zip(with_copies[0], without[0]):
        assert all(_same(g, w) for g, w in zip(got, want)), (got, want)
    for got, want in zip(with_copies[1], without[1]):
        assert sorted(got) == sorted(want)
        assert sum("moment" in n for n in got) == 2 * 5
        for name in got:
            assert _same(got[name], want[name]), name
    first, last = with_copies[1]
    assert all(not _same(first[p], last[p]) for p in ("gate", "up", "down"))
    if held:
        rows = np.concatenate([f[1].ravel() for f in with_copies[0]])
        assert bound == 64 and rows.min() <= bound < rows.max(), rows


def _expert_casts(prog):
    """Matches a float32 -> bf16 convert of an array of the shape of one of
    the program's kept parameters in lowered text."""
    gb = prog.global_block()
    shapes = {tuple(gb.vars[p].shape) for p in amp.kept_copies(prog)[0]}
    return re.compile(
        r"stablehlo\.convert.*tensor<(%s)xf32>\) -> tensor<\1xbf16>"
        % "|".join("x".join(map(str, s)) for s in sorted(shapes)))


@pytest.mark.parametrize("held", [None, (2, 2)], ids=["all", "share"])
def test_the_lowered_step_casts_no_expert_weight(held):
    """The only float32 -> bf16 converts of an [E', H, F] (or [E', F, H])
    array in the lowered step are the updates' own (bf16 of ParamOut, one
    a copy); the step that ignores the copies has the policy's three
    besides, under the forward op and again under the backward op."""
    with unittest.mock.patch.object(grouped, "ROW_TILES", (32,)):
        prog, _, fetch = _moe_program(held)
        casts = _expert_casts(prog)
        feeds = {"x": ((64, 64), "float32")}
        text = lowering._lower(prog, [fetch[0].name], feeds, False)
        with _ignoring_copies():
            ignored = lowering._lower(prog, [fetch[0].name], feeds, False)
    assert len(casts.findall(text)) == 3
    assert len(casts.findall(ignored)) == 3 + 2 * 3


@pytest.mark.parametrize("config", sorted(MODELS))
def test_the_builders_keep_a_copy_of_every_expert_weight(built, config):
    """1 / 4 / 5 expert layers at the cells' depths, each reading all
    three weights from copies: the counter, in the step spans and the
    registry beside `moe_ffn_row_bound`; none in the inference program,
    none with the policy off."""
    _, b = built[config]
    prog, layers = b["prog"], EXPERT_LAYERS[config]
    kept, names = amp.kept_copies(prog)
    assert len(kept) == len(names) == 3 * layers
    assert lm_ops.lowered_counts(prog, CPU)["moe_ffn_kept_copies"] == layers
    assert executor_core.lowered_counts(prog, CPU)[
        "moe_ffn_kept_copies"] == layers
    assert "moe_ffn_kept_copies" not in lm_ops.lowered_counts(
        b["test_prog"], CPU)
    with amp.auto_cast(False):
        assert "moe_ffn_kept_copies" not in lm_ops.lowered_counts(prog, CPU)
    # in the startup program each copy is cast from the initialised master
    casts = {op.output("Out")[0]: op.input("X")[0]
             for op in b["startup"].global_block().ops if op.type == "cast"}
    assert {c: p for p, c in kept.items()} == casts


def _copy_names(prog):
    return sorted(n for n in prog.global_block().vars if "low_copy" in n)


def test_a_parameter_with_a_second_writer_gets_no_copy():
    """An `assign` onto Gate in the main program: the update is not its
    only writer, so it keeps no copy; Up and Down keep theirs, and the
    layer is not counted as reading all three from copies. A writer
    appended AFTER the update leaves the copy written and unread."""
    prog = _moe_program()[0]
    assert len(_copy_names(prog)) == 3
    assert sorted(amp.kept_copies(prog)[0]) == ["down", "gate", "up"]
    assert lm_ops.lowered_counts(prog, CPU)["moe_ffn_kept_copies"] == 1
    prog = _moe_program(second_writer=True)[0]
    assert len(_copy_names(prog)) == 2
    assert sorted(amp.kept_copies(prog)[0]) == ["down", "up"]
    assert "moe_ffn_kept_copies" not in lm_ops.lowered_counts(prog, CPU)
    prog = _moe_program()[0]
    with program_guard(prog):
        up = prog.global_block().var("up")
        fluid.layers.assign(fluid.layers.scale(up, 0.5), up)
    kept, names = amp.kept_copies(prog)
    assert sorted(kept) == ["down", "gate"] and len(names) == 3


def test_the_policy_off_or_another_update_keeps_no_copy():
    with amp.auto_cast(False):
        prog = _moe_program()[0]
    assert _copy_names(prog) == [] and amp.kept_copies(prog) == ({}, set())
    assert all("ParamLowOut" not in op.outputs
               for op in prog.global_block().ops)
    prog = _moe_program(optimizer=fluid.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9))[0]
    assert _copy_names(prog) == [] and amp.kept_copies(prog) == ({}, set())
    # only what a kernel reads as it stands: the projection's weight, whose
    # cast XLA fuses into the product, and the float32 router keep none
    prog = _moe_program()[0]
    assert sorted(n.split("_low_copy")[0] for n in _copy_names(prog)) == [
        "down", "gate", "up"]


@pytest.mark.parametrize("runner", ["executor", "executor_scan",
                                    "parallel_executor",
                                    "parallel_executor_scan"])
def test_a_master_written_from_outside_the_step_is_cast_again(runner):
    """`scope.set_var` on a master (a load and a restored checkpoint go
    through it, and so does another program's update): the next step reads
    the cast of THAT value, as the step without copies does. Whichever way
    the step is run: the refresh stands once, in front of every one."""
    iters = 2 if runner.endswith("_scan") else None
    feed = {"x": _x(2)["x"] if iters else _x(1)["x"][0]}
    new = np.random.default_rng(1).standard_normal((8, 64, 32)).astype(
        np.float32)
    losses = []
    for ignore in (False, True):
        prog, startup, (loss,) = _moe_program()
        scope = fluid.Scope()
        with fluid.scope_guard(scope), (
                _ignoring_copies() if ignore else contextlib.nullcontext()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            if runner.startswith("parallel_executor"):
                pe = fluid.ParallelExecutor(
                    use_cuda=False, loss_name=loss.name, main_program=prog)

                def run():
                    return pe.run([loss.name], feed=feed, iters=iters)[0]
            else:
                def run():
                    return exe.run(prog, feed=feed, fetch_list=[loss],
                                   iters=iters)[0]
            got = [run()]
            scope.set_var("gate", jnp.asarray(new))
            got += [run() for _ in range(2)]
            if not ignore:
                _copies_are_casts(scope, prog)
        losses.append(np.asarray(got))
    assert _same(*losses) and (losses[0][0] != losses[0][1]).all()


@pytest.mark.parametrize("copies_saved", [False, True],
                         ids=["absent", "present"])
def test_a_loaded_scope_steps_as_the_one_that_never_left_memory(
        tmp_path, copies_saved):
    """save_persistables -> fresh scope -> load_persistables -> one step:
    bit for bit the step of the scope that stayed. The copies are derived
    state: not written, not read back (a file of that name in the
    directory is left alone), cast from the loaded masters."""
    prog, startup, (loss,) = _moe_program()
    feeds = _x(2)["x"]
    stayed = fluid.Scope()
    with fluid.scope_guard(stayed):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(prog, feed={"x": feeds[:1]}, fetch_list=[loss], iters=1)
        fluid.io.save_persistables(exe, str(tmp_path), prog)
        names = amp.kept_copies(prog)[1]
        assert len(names) == 3 and not any(
            os.path.exists(os.path.join(str(tmp_path), n + ".npy"))
            for n in names)
        assert os.path.exists(os.path.join(str(tmp_path), "gate.npy"))
        if copies_saved:    # stale ones, as another writer might leave
            for n in names:
                np.save(os.path.join(str(tmp_path), n + ".npy"),
                        np.zeros(prog.global_block().vars[n].shape,
                                 np.float32))
    loaded = fluid.Scope()
    with fluid.scope_guard(loaded):
        exe2 = fluid.Executor(fluid.CPUPlace())
        fluid.io.load_persistables(exe2, str(tmp_path), prog)
    got = []
    for scope, e in ((stayed, exe), (loaded, exe2)):
        with fluid.scope_guard(scope):
            out, = e.run(prog, feed={"x": feeds[1:]}, fetch_list=[loss],
                         iters=1)
            _copies_are_casts(scope, prog)
            got.append((np.asarray(out), _state(scope, prog)))
    assert _same(got[0][0], got[1][0])
    assert sorted(got[0][1]) == sorted(got[1][1])
    assert all(_same(got[0][1][n], got[1][1][n]) for n in got[0][1])


def test_a_checkpoint_holds_no_copy_and_restores_without(tmp_path):
    from paddle_tpu.resilience import CheckpointManager

    prog, startup, (loss,) = _moe_program()
    feed = {"x": _x(1)["x"][0]}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[loss])
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        snap = mgr.snapshot_vars(scope, prog)
        assert "gate" in snap and not set(snap) & amp.kept_copies(prog)[1]
        mgr.save(1, scope=scope, program=prog, block=True)
        want, = exe.run(prog, feed=feed, fetch_list=[loss])
    fresh = fluid.Scope()
    with fluid.scope_guard(fresh):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        mgr.restore(scope=fresh, program=prog)
        got, = exe.run(prog, feed=feed, fetch_list=[loss])
        _copies_are_casts(fresh, prog)
    mgr.close()
    assert _same(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("config", sorted(MODELS))
def test_the_verifier_passes_the_program(built, config):
    _, b = built[config]
    for prog in (b["prog"], b["startup"]):
        report = analysis.verify(prog, level="full",
                                 fetch_names=[b["loss"].name]
                                 if prog is b["prog"] else None)
        assert report.ok, [str(d) for d in report.diagnostics]


def test_zero1_leaves_such_an_update_whole():
    """The rewrite that shards updates keeps one that writes a copy of the
    whole parameter on the replicated path, and says why."""
    from paddle_tpu.parallel import zero1

    prog = _moe_program()[0]
    plan = zero1.build_plan(prog, 2)
    skipped = dict(plan.skipped)
    assert sorted(n for n, why in skipped.items()
                  if "low-precision copy" in why) == ["down", "gate", "up"]
    assert {e.param for e in plan.entries}.isdisjoint(skipped)
    assert plan.entries


@pytest.mark.parametrize("zero1", [False, True], ids=["plain", "zero1"])
def test_the_parallel_executor_keeps_the_copies(zero1):
    """Data-parallel steps over the host's devices write and read the
    copies as the Executor's do, with the sharded-update rewrite off and
    on (it leaves these three updates whole): the same losses, and each
    copy its master's cast after every step."""
    from paddle_tpu import flags

    x = _x(3)["x"]
    losses = []
    was = flags.get("zero1")
    try:
        for rewrite in (False, zero1):
            flags.set("zero1", rewrite)
            prog, startup, (loss,) = _moe_program()
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                fluid.Executor(fluid.CPUPlace()).run(startup)
                pe = fluid.ParallelExecutor(
                    use_cuda=False, loss_name=loss.name, main_program=prog,
                    scope=scope)
                got = []
                for step in x:
                    got.append(np.asarray(
                        pe.run([loss.name], feed={"x": step})[0]))
                    _copies_are_casts(scope, prog)
            losses.append(np.concatenate([g.ravel() for g in got]))
    finally:
        flags.set("zero1", was)
    assert losses[0][0] != losses[0][-1]
    np.testing.assert_allclose(losses[1], losses[0], rtol=1e-6)


@pytest.mark.parametrize("prefix", ["tokens", "share", "swa"])
def test_the_cast_share_reads_a_number_where_no_cast_is_left(prefix):
    """`<prefix>.expert_cast_share` on a recorded window of the Laguna
    cell: the base reader's number as recorded (what the parent reads);
    0.0 with the `cast` keys gone from under `moe_ffn*` (a step that
    reads kept copies), where the base reader gives None and the line
    would lack the metric; None where the window names no part of the
    layer, or no expert layer."""
    from chipbench import harness, scopes

    with open(os.path.join(REPO, "chipbench", "data",
                           "scopes_laguna.json")) as f:
        red = json.load(f)["scopes"]
    files = harness.Files()
    read = files.metric_reader(prefix + ".expert_cast_share").read
    base = files.metric_reader("expert_cast_share").read
    assert files.find("layer_metrics", prefix + ".expert_cast_share.py")
    assert read({"scopes": red}) == base({"scopes": red}) > 1

    def without(*parts):
        return {"scopes": dict(red, by_scope={
            k: s for k, s in red["by_scope"].items()
            if not (scopes.in_scope(k, *scopes.MOE_OPS)
                    and scopes.in_scope(k, *parts))})}

    assert base(without("cast")) is None
    assert read(without("cast")) == 0.0
    assert read(without("cast", "route", "dispatch", "combine")) is None
    assert read({"scopes": dict(red, by_scope={
        k: s for k, s in red["by_scope"].items()
        if not scopes.in_scope(k, *scopes.MOE_OPS)})}) is None
    assert read({}) is None and read({"scopes": None}) is None


# The lowered steps of a ResNet bottleneck and an SE-ResNeXt block
# (Momentum, the policy on, no debug info: tests/test_device_scopes.py) as
# the parent of the PR that brought the kept copies (PR 35) lowered them:
# the image models run none of this mechanism, and their text is the
# parent's byte for byte. A change to the lowering of convolutions, batch
# norms or Momentum changes these on purpose; the failure prints the new.
PARENT_SHA256 = {
    "resnet_bottleneck":
        "ef1aa4ee129f3cbea767552bb9571e12d7f41561d7b01ffc4612c676b79c999e",
    "se_block":
        "9023a3d4dfdb147a48984cf30e6fb4996ce852f533f2dd0cebddab8baa80c584",
}


@pytest.mark.parametrize("name", sorted(PARENT_SHA256))
def test_an_image_step_is_the_parent_s_byte_for_byte(name):
    prog, fetch, feeds = lowering.STEPS[name]()
    assert _copy_names(prog) == [] and amp.kept_copies(prog) == ({}, set())
    assert "moe_ffn_kept_copies" not in executor_core.lowered_counts(
        prog, CPU)
    text = lowering._lower(prog, fetch, feeds, False)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_SHA256[name]
