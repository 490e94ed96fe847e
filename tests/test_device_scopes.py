"""Every device operation of a traced step names the Fluid op, and the part
of its lowering, it came from (docs/observability.md, "The Fluid op in a
device trace"): a default scope for every op, block scopes in the two
image models, the optimizer's update naming its parameter's scope inside
one path component, sub-scopes inside the expert layer. All of it is
metadata: the lowered text without debug info is what it is with every
scope off.

What a trace would show is read here from the debug locations of the
lowered module, through the reader the benchmark uses
(`chipbench/scopes.py: scope_of`)."""

import contextlib
import glob
import json
import os
import re
import sys
import unittest.mock

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp
from paddle_tpu.core import executor_core
from paddle_tpu.core.framework import Program, op_scope, program_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import scopes  # noqa: E402

PARTS = ("route", "cast", "dispatch", "combine")


def _lower(prog, fetch, feeds, debug, use_amp=True):
    """The step of `prog` as the Executor lowers it (state donated in, the
    optimizer's writes out), as StableHLO text."""
    gb = prog.global_block()
    wrote = {n for op in gb.ops for n in op.output_arg_names()}
    state = {n: jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype))
             for n, v in gb.vars.items() if v.persistable}
    mut = {n: s for n, s in state.items() if n in wrote}
    const = {n: s for n, s in state.items() if n not in wrote}
    step = executor_core.build_step_fn(prog, fetch, sorted(mut))
    feeds = {n: jax.ShapeDtypeStruct(shape, np.dtype(dt))
             for n, (shape, dt) in feeds.items()}
    with amp.auto_cast(use_amp):
        return jax.jit(step).lower(
            mut, const, feeds,
            jax.ShapeDtypeStruct((2,), np.uint32)).as_text(debug_info=debug)


def _keys(text):
    """The scope keys a device trace of this module would file its
    operations under, and the raw paths behind each."""
    found = {}
    for path in set(re.findall(r'loc\("(jit\([^"]*)"', text)):
        found.setdefault(scopes.scope_of(path), set()).add(path)
    return found


# ------------------------------------------------------------- the programs
def _bottleneck_step():
    from paddle_tpu.models.resnet import bottleneck

    prog, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[8, 8, 8], dtype="float32")
        with op_scope("stage1/block0"):
            out = bottleneck(x, 4, 1)
        loss = fluid.layers.mean(out)
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    return prog, [loss.name], {"x": ((2, 8, 8, 8), "float32")}


def _se_block_step():
    from paddle_tpu.models.se_resnext import bottleneck_block

    prog, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[16, 8, 8], dtype="float32")
        x.stop_gradient = False
        with op_scope("stage1/block0"):
            out = bottleneck_block(x, 16, 1, 4, 4)
        loss = fluid.layers.mean(out)
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    # the gradient to x is fetched: the `sum` of its two parts stays
    return (prog, [loss.name, x.name + "@GRAD"],
            {"x": ((2, 16, 8, 8), "float32")})


def _moe_step():
    """A `moe_ffn` that holds 2 of 8 experts, behind a projection (so that
    the gradient to its input is wanted); with `grouped.ROW_TILES` short
    it has a row bound below its rows, and so a `cond`."""
    prog, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[128], dtype="float32")
        h = fluid.layers.fc(x, 128, bias_attr=False)
        with fluid.name_scope("moe"):
            y = fluid.layers.moe_ffn(
                h, 8, 128, 2, score_func="sigmoid", norm_topk=True,
                bias_attr=fluid.ParamAttr(name="b"), held=(2, 2))[0]
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return prog, [loss.name], {"x": ((64, 128), "float32")}


STEPS = {"resnet_bottleneck": _bottleneck_step, "se_block": _se_block_step,
         "moe_ffn_with_a_row_bound": _moe_step}


def _lowered(name, debug):
    from paddle_tpu.parallel import grouped

    with unittest.mock.patch.object(grouped, "ROW_TILES", (32,)):
        return _lower(*STEPS[name](), debug)


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.fixture(scope="module")
def moe_keys():
    return _keys(_lowered("moe_ffn_with_a_row_bound", True))


# -------------------------------------------------------------------- tests
def test_an_op_outside_every_scope_lowers_under_its_type():
    """`mean` is appended under no scope: its operations, and its gradient
    op's, carry the op's type; the attr itself is not made up."""
    prog, fetch, feeds = _bottleneck_step()
    ops = prog.global_block().ops
    assert all("op_namescope" not in op.attrs for op in ops
               if op.type in ("mean", "mean_grad"))
    text = _lower(prog, fetch, feeds, True)
    assert "/mean/" in text and "/mean_grad/" in text
    keys = _keys(text)
    assert "mean" in keys and "mean_grad" in keys
    # nothing of the program lies outside a scope: only JAX's own glue
    assert not [p for p in keys.get("", ()) if "/" in p.split("/", 1)[-1]]


@pytest.mark.parametrize("name", sorted(STEPS))
def test_scopes_are_metadata(name, monkeypatch):
    """The text without debug info is byte for byte the lowering with
    every scope of the executor off (and with them on the locations do
    name scopes: the comparison is of something)."""
    assert "loc(" in _lowered(name, True)
    plain = _lowered(name, False)
    assert "loc(" not in plain and "stage1/block0" not in plain \
        and "moe/moe_ffn/" not in plain
    monkeypatch.setattr(executor_core, "_device_scope",
                        lambda op, ctx: contextlib.nullcontext())
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    assert _lowered(name, False) == plain


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("op", ["moe_ffn", "moe_ffn_grad"])
def test_the_expert_layer_names_its_parts(moe_keys, op, part):
    """Each of the four parts has a key of its own under the forward op's
    scope and under the backward op's, read as the benchmark reads it;
    the row movement lies inside the branches of the row bound's `cond`,
    the router and the casts before it."""
    key = f"moe/{op}/{part}"
    assert key in moe_keys, sorted(k for k in moe_keys if "moe" in k)
    assert scopes.in_scope(key, op) and scopes.in_scope(key, part)
    inside = [p for p in moe_keys[key] if f"/{op}/cond/branch_" in p]
    if part in ("dispatch", "combine"):
        assert {p.split("/cond/")[1].split("/")[0] for p in inside} == {
            "branch_0_fun", "branch_1_fun"}
    else:
        assert not inside


def test_nothing_of_the_expert_layer_s_row_movement_is_left_unnamed(
        moe_keys):
    """Off a TPU place the products are `lax.ragged_dot` under the op's own
    key; gathers, scatters and sorts are under a part's."""
    for op in ("moe_ffn", "moe_ffn_grad"):
        bare = {p.rsplit("/", 1)[-1] for p in moe_keys[f"moe/{op}"]}
        assert not bare & {"gather", "scatter", "sort", "top_k"}, bare


def test_an_update_names_its_parameter_s_scope_in_one_component():
    prog, fetch, feeds = _bottleneck_step()
    conv1 = next(op for op in prog.global_block().ops
                 if op.type == "conv2d"
                 and op.attrs["op_namescope"] == "stage1/block0/conv1")
    update = next(op for op in prog.global_block().ops
                  if op.type == "momentum"
                  and op.input("Param") == conv1.input("Filter"))
    assert update.attrs["op_namescope"] == "optimizer"
    assert update.attrs["owner_namescope"] == "stage1/block0/conv1"
    key = "optimizer/momentum(stage1.block0.conv1)"
    keys = _keys(_lower(prog, fetch, feeds, True))
    assert key in keys
    assert scopes.in_scope(key, "optimizer")
    assert not scopes.in_scope(key, "stage1", "block0", "conv1", "momentum")
    # a parameter made under no scope: the update's key as it always was
    prog, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    keys = _keys(_lower(prog, [loss.name], {"x": ((2, 4), "float32")}, True))
    assert "optimizer/sgd" in keys


def test_a_gradient_sum_names_the_scope_it_sums_for():
    """The `sum` backward.py appends where a value has two readers carries
    the scope of the op whose gradient it adds, inside its own component:
    no reader of a model's scope starts counting it."""
    prog, fetch, feeds = _se_block_step()
    sums = [op for op in prog.global_block().ops if op.type == "sum"]
    assert sums and all("op_namescope" not in op.attrs for op in sums)
    owners = {op.attrs.get("owner_namescope") for op in sums}
    assert owners <= {"stage1/block0/conv0", "stage1/block0/conv2",
                      "stage1/block0/se", "stage1/block0/shortcut",
                      "stage1/block0"}, owners
    keys = _keys(_lower(prog, fetch, feeds, True))
    key = next(k for k in keys if k.startswith("sum("))
    assert not scopes.in_scope(key, "stage1", "se", "conv0")


def _reader_components():
    """The path components the benchmark's readers match: the literal
    arguments of `scopes.seconds(` / `in_scope(` under
    chipbench/layer_metrics/, and the tuples they splat."""
    found = set(scopes.MOE_OPS)
    for path in glob.glob(os.path.join(REPO, "chipbench", "layer_metrics",
                                       "*.py")):
        with open(path) as f:
            text = f.read()
        for args in re.findall(r"(?:scopes\.seconds|in_scope)\(([^)]*)\)",
                               text):
            found.update(re.findall(r'"([\w\-]+)"', args))
            for name in re.findall(r"\*(\w+)", args):
                m = re.search(r"^%s = \(([^)]*)\)" % name, text, re.M)
                found.update(re.findall(r'"([\w\-]+)"', m.group(1))
                             if m else ())
    return found


def _benchmark_programs():
    from chipbench.harness import load_module

    for config in ("resnet50", "se_resnext50", "olmoe_1b_7b",
                   "xing4_0_29b_a4b", "laguna_xs_2"):
        base = os.path.join(REPO, "chipbench", "configs", config)
        with open(base + ".json") as f:
            cfg = json.load(f)
        yield config, load_module(base + ".py").build(fluid, cfg, 7)["prog"]


def test_no_default_scope_is_a_component_a_reader_matches():
    """An op appended under no scope becomes a key of its own type: none
    of those types may be a component an accepted reader sums over, or
    that reader would start counting it."""
    matched = _reader_components()
    assert {"optimizer", "lm_head", "mhc", "moe_ffn",
            "grouped_matmul"} <= matched
    for config, prog in _benchmark_programs():
        bare = {op.type for b in prog.blocks for op in b.ops
                if not op.attrs.get("op_namescope")}
        assert bare and not bare & matched, (config, bare & matched)
        # and the image models leave nothing of theirs outside a scope
        assert not bare & {"conv2d", "batch_norm", "pool2d", "mul",
                           "moe_ffn", "causal_attention", "rms_norm"}, config


@pytest.mark.parametrize("model", ["resnet", "se_resnext"])
def test_block_scopes_change_no_name(model, monkeypatch):
    """`op_scope` sets the ops' attr alone: every variable of the model,
    parameters first, is named as it is with the scopes off (the
    reference comparison's tape, checkpoints and the persistent cache read
    names)."""
    import importlib

    module = importlib.import_module("paddle_tpu.models." + model)

    def build():
        prog, startup = Program(), Program()
        with fluid.unique_name.guard(), program_guard(prog, startup):
            x = fluid.layers.data(name="x", shape=[3, 32, 32],
                                  dtype="float32")
            if model == "resnet":
                module.resnet_imagenet(x, 10, depth=50)
            else:
                module.se_resnext(x, 10, depth=50)
        gb = prog.global_block()
        return (list(gb.vars), [(op.type, op.input_arg_names(),
                                 op.output_arg_names()) for op in gb.ops],
                {op.attrs.get("op_namescope") for op in gb.ops})

    names, ops, scoped = build()
    assert {"stem", "stage1/block0/conv1", "stage1/block0/shortcut",
            "stage4/block2/conv2", "stage4/block2", "head"} | (
        {"stage3/block5/se", "stage1/block0/conv0"} if model == "se_resnext"
        else {"stage3/block5/conv3"}) <= scoped
    assert None not in scoped
    monkeypatch.setattr(module, "op_scope",
                        lambda name: contextlib.nullcontext())
    names_off, ops_off, scoped_off = build()
    assert scoped_off == {None}
    assert names_off == names and ops_off == ops
