"""Compiles for a TPU v5e that is described, not attached: what the chip's
compiler makes of the program's lowering, at real sizes, at no chip time.
Nothing runs, so nothing here is a result or a time.

Every test that needs the TPU compiler lives in THIS file and reaches it
through the module-scoped fixture below: only one process may load the
TPU's library, and under pytest-xdist only the worker that is given this
file does (/opt/skills/guides/on-chip-measurement, section 2).
"""

import os
import re

import numpy as np
import pytest


# ---------------------------------------------------------------- HLO text
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_ARRAY = re.compile(r"\b(%s)\[([0-9,]*)\]" % "|".join(_BYTES))
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w\-]*)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$")


def shape_bytes(shape):
    """Bytes of every array in a shape string (a tuple's are summed)."""
    return sum(int(np.prod([int(d) for d in dims.split(",") if d]))
               * _BYTES[dt] for dt, dims in _ARRAY.findall(shape))


_ELEMENTWISE = {"add", "subtract", "multiply", "divide", "select", "convert",
                "maximum", "minimum", "negate", "exponential", "logistic"}


def fusions(hlo_text, unfused=False):
    """The fusion instructions of `compiled.as_text()` that run as device
    operations (those nested inside another fusion's computation are part
    of it): name, kind, result shape (layouts dropped), op_name, bytes
    written (the result) and read (the operands, looked up where they are
    defined; their shapes as `operand_shapes`). `unfused`: the element-wise
    instructions XLA left outside every fusion as well (kind None)."""
    shapes, out, inside, nested = {}, [], None, set()
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        shapes[name] = re.sub(r"\{[^}]*\}", "", shape)
        nested.update(re.findall(r"(?:calls|to_apply)=%([^\s,]+)", rest))
        if opcode == "fusion" or (unfused and opcode in _ELEMENTWISE):
            kind = re.search(r"kind=(\w+)", rest)
            op_name = re.search(r'op_name="([^"]*)"', rest)
            out.append(dict(
                name=name, kind=kind.group(1) if kind else None,
                shape=shapes[name], inside=inside, write=shape_bytes(shape),
                op_name=op_name.group(1) if op_name else "",
                operands=re.findall(r"%([^\s,()]+)", rest.split(")", 1)[0])))
    out = [f for f in out if f["inside"] not in nested]
    for f in out:
        f["operand_shapes"] = [shapes.get(o, "") for o in f["operands"]]
        f["read"] = sum(shape_bytes(s) for s in f["operand_shapes"])
    return out


def ragged_dots(hlo_text):
    """Instructions that are XLA's own grouped product: `ragged-dot` by
    opcode or by name (`%ragged-dot-none.3 = ... custom-call(`)."""
    return [m.group(1) for m in map(_INSTR.match, hlo_text.splitlines())
            if m and "ragged-dot" in m.group(1) + " " + m.group(3)]


def reduction_passes(hlo_text, min_read=20e6, max_write=2e6):
    """Standalone reduction passes: loop fusions that read a whole
    activation (more than `min_read` bytes) to write only a reduction of
    it (less than `max_write`). PERF.md section 6 (PR 25) counts them: 59
    in the se_resnext50 step before the squeeze rode the batch-norm sums,
    43 after, 1 in resnet50's."""
    return [f for f in fusions(hlo_text) if f["kind"] == "kLoop"
            and f["read"] > min_read and f["write"] < max_write]


# ---------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without the chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# ------------------------------------------------------------------- tests
def _se_bottleneck_step_hlo(one_chip):
    """One stage-1 SE-ResNeXt bottleneck (128 x 256 x 56 x 56 in and out,
    cardinality 32, SE reduction 16), forward + backward + SGD under bf16
    AMP, as the Executor lowers it, compiled for one v5e chip."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.core import executor_core
    from paddle_tpu.models.se_resnext import bottleneck_block

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[256, 56, 56],
                              dtype="float32")
        x.stop_gradient = False     # a block inside a network: dx as well
        out = bottleneck_block(x, 128, 1, 32, 16)
        loss = fluid.layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    gb = main.global_block()
    wrote = {n for op in gb.ops for n in op.output_arg_names()}
    read = {n for op in gb.ops for n in op.input_arg_names()}
    state = {n: jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype),
                                     sharding=one_chip)
             for n, v in gb.vars.items()
             if v.persistable and n in wrote | read}
    mut = {n: s for n, s in state.items() if n in wrote}
    const = {n: s for n, s in state.items() if n not in wrote}
    feeds = {"x": jax.ShapeDtypeStruct((128, 256, 56, 56), np.float32,
                                       sharding=one_chip)}
    rng = jax.ShapeDtypeStruct((2,), np.uint32, sharding=one_chip)
    step = executor_core.build_step_fn(
        main, [loss.name, x.name + "@GRAD"], sorted(mut))
    amp.enable("bfloat16")
    try:
        return jax.jit(step, donate_argnums=(0,)).lower(
            mut, const, feeds, rng).compile().as_text()
    finally:
        amp.disable()


def _squeeze_passes(hlo_text):
    return [f for f in reduction_passes(hlo_text, min_read=50e6)
            if re.fullmatch(r"(bf16|f32)\[128,256\]", f["shape"])]


def test_se_squeeze_has_no_pass_of_its_own(one_chip, no_compile_cache,
                                           monkeypatch):
    """The squeeze of an SE block is algebra on the per-sample sums the
    batch-norm statistics take anyway, and the v5e compiler emits those
    from the fusion that makes the block's widest tensor: no loop fusion
    reads >= 50 MB to write a [128, 256] result. With the pair lowered
    apart there is one (205 MB in), which is what the counter must see."""
    from paddle_tpu.ops import bn_pool

    together = _se_bottleneck_step_hlo(one_chip)
    assert _squeeze_passes(together) == []
    monkeypatch.setattr(bn_pool, "match", lambda ops: {})
    apart = _se_bottleneck_step_hlo(one_chip)
    squeeze = _squeeze_passes(apart)
    assert len(squeeze) == 1 and squeeze[0]["read"] >= 200e6
    # the backward: sum(dy), sum(dy * x_hat) of the SE batch norm follow
    # from per-sample sums of d_out, so that pass (411 MB in) goes too;
    # what stays is the grouped convolution's batch norm and the first's
    assert len(reduction_passes(apart)) == 4
    assert len(reduction_passes(together)) == 2


def _resnet50_step(one_chip):
    """The ResNet-50 training step of the `resnet50` configuration (batch
    128, bf16 AMP, Momentum) as the Executor lowers it, compiled for one
    v5e chip."""
    import importlib
    import json

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.core import executor_core

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    if repo not in sys.path:
        sys.path.insert(0, repo)
    builder = importlib.import_module("chipbench.configs.resnet50")
    with open(os.path.join(repo, "chipbench", "configs",
                           "resnet50.json")) as f:
        cfg = json.load(f)
    built = builder.build(fluid, cfg, 7)
    gb = built["prog"].global_block()
    wrote = {n for op in gb.ops for n in op.output_arg_names()}
    state = {n: jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype),
                                     sharding=one_chip)
             for n, v in gb.vars.items() if v.persistable}
    mut = {n: s for n, s in state.items() if n in wrote}
    const = {n: s for n, s in state.items() if n not in wrote}
    size, batch = cfg["image_size"], cfg["batch_per_chip"]
    feeds = {"data_u8": jax.ShapeDtypeStruct(
                 (batch, size, size, cfg["channels"]), np.uint8,
                 sharding=one_chip),
             "label": jax.ShapeDtypeStruct((batch, 1), np.int32,
                                           sharding=one_chip)}
    rng = jax.ShapeDtypeStruct((2,), np.uint32, sharding=one_chip)
    step = executor_core.build_step_fn(built["prog"], [built["loss"].name],
                                       sorted(mut))
    amp.enable(cfg["amp"])
    try:
        return jax.jit(step, donate_argnums=(0,)).lower(
            mut, const, feeds, rng).compile()
    finally:
        amp.disable()


def test_resnet50_step_compiles_the_same_with_and_without_scopes(
        one_chip, no_compile_cache, monkeypatch):
    """The model's block scopes, the default scope of every op and the
    owner's scope on the updates are metadata to the chip's compiler too:
    the v5e executable of the ResNet-50 step has the same instructions,
    memory and code size with all of them off; with them on, every
    convolution fusion names a layer of the model."""
    import contextlib

    from paddle_tpu.core import executor_core
    from paddle_tpu.models import resnet

    def measure(compiled):
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        return (len([ln for ln in text.splitlines() if _INSTR.match(ln)]),
                mem.temp_size_in_bytes, mem.argument_size_in_bytes,
                mem.output_size_in_bytes,
                mem.generated_code_size_in_bytes), text

    scoped, text = measure(_resnet50_step(one_chip))
    named = [ln for ln in text.splitlines()
             if "kind=kOutput" in ln and "op_name=" in ln]
    assert named and all(re.search(
        r'op_name="jit\(step\)/(stem|head|stage\d/block\d|optimizer/'
        r'momentum\((stem|head|stage\d\.block\d)|sum\()', ln)
        for ln in named if "convolution" in ln), [
            ln[:200] for ln in named if "convolution" in ln][:3]
    monkeypatch.setattr(resnet, "op_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(executor_core, "_device_scope",
                        lambda op, ctx: contextlib.nullcontext())
    plain, text = measure(_resnet50_step(one_chip))
    assert "stage1" not in text
    assert plain == scoped


@pytest.mark.parametrize("q_shape,k_shape,dtype,causal,blocks", [
    ((2, 16, 4096, 128), (2, 16, 4096, 128), "bfloat16", True, (1024, 1024)),
    ((2, 16, 4096, 128), (2, 16, 4096, 128), "bfloat16", True, (512, 1024)),
    ((2, 16, 1000, 128), (2, 16, 1000, 128), "bfloat16", True, (256, 256)),
    ((1, 2, 300, 64), (1, 2, 520, 64), "float32", False, (128, 256)),
], ids=["cell", "unequal_blocks", "padded", "cross_length"])
def test_flash_backward_compiles_for_v5e(one_chip, no_compile_cache,
                                         monkeypatch, q_shape, k_shape,
                                         dtype, causal, blocks):
    """Mosaic takes the two backward kernels at the `olmoe_1b_7b` cell's
    shape and blocks, with blocks the diagonal crosses off their corners,
    with padded rows and a masked last key block, and at unequal lengths:
    two custom calls, no loop around them, and nothing of the size of a
    score block among the temporaries."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import flash

    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def bwd(q, k, v, o, lse, do):
        return flash.flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                         block_q=blocks[0], block_k=blocks[1])

    compiled = jax.jit(bwd).lower(
        sds(q_shape, dtype), sds(k_shape, dtype), sds(k_shape, dtype),
        sds(q_shape, dtype), sds(q_shape[:3], "float32"),
        sds(q_shape, dtype)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not any(m.group(3) == "while"
                   for m in map(_INSTR.match, text.splitlines()) if m)
    B, H, Sq, D = q_shape
    operands = 4 * B * H * max(Sq, k_shape[2]) * D * 4
    assert compiled.memory_analysis().temp_size_in_bytes < operands


@pytest.mark.parametrize("kind", ["forward", "d_lhs", "d_rhs"])
@pytest.mark.parametrize("k,m", [(2048, 1024), (1024, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                         monkeypatch, k, m, kind):
    """Mosaic takes the grouped-matmul kernels at the `olmoe_1b_7b` cell's
    shapes ([65536, K] x [64, K, M] bf16) with the tiles `tiles_for`
    chooses (whole-K weight tiles past the default scoped VMEM): one
    custom call, the weight read in the orientation it was given in both
    directions (no transposed copy of it), and d rhs reading the rows as
    stored."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import grouped

    monkeypatch.setattr(grouped, "pallas_interpret", lambda: False)
    N, E, bf = 65536, 64, jnp.bfloat16

    def sds(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(lhs, rhs, counts, g):
        out, vjp = jax.vjp(
            lambda a, b: grouped.grouped_dot(a, b, counts), lhs, rhs)
        return {"forward": out, "d_lhs": vjp(g)[0], "d_rhs": vjp(g)[1]}[kind]

    tiles = grouped.tiles_for(N, k, m, bf)
    assert tiles[0] == 512 and tiles[1] == (k, m) and tiles[2] == (m, k)
    compiled = jax.jit(fn).lower(sds((N, k)), sds((E, k, m)),
                                 sds((E,), jnp.int32), sds((N, m))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ragged_dots(text) == []
    # nothing of an operand's size beside the operands and the result
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20


# what `fusions(text, unfused=True)` under the scopes `moe_ffn` and
# `moe_ffn_grad` read and wrote in the OLMoE step below at commit 54f7845
# (PR 30): the gathers, the combine, the weights' three casts, SiLU * up
# (0.40 GB), its backward (0.67) and `add_any` of two d xs products (0.81)
_PARENT_MOE_PASSES = 7_612_436_708


@pytest.mark.parametrize("n,e,h,f,rows_past", [
    (65536, 64, 2048, 1024, False), (16384, 8, 3584, 1024, True)],
    ids=["olmoe_1b_7b", "xing4_0_29b_a4b"])
def test_grouped_mlp_compiles_for_v5e(one_chip, no_compile_cache,
                                      monkeypatch, n, e, h, f, rows_past):
    """Mosaic takes `grouped_mlp`'s nine kernels at both cells' shapes
    with their side tiles (the widest, d h at K = 3584: rows of 3.5 MB, a
    whole-K weight tile of 7 MB, a, b in and d a, d b out, all twice):
    nine custom calls, the second d xs product written over the first (no
    copy of it), and no pass over the rows between them but the zeroing
    of ys and d xs where the groups end before the rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import grouped

    monkeypatch.setattr(grouped, "pallas_interpret", lambda: False)
    bf = jnp.bfloat16

    def sds(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(xs, gate, up, down, counts, g):
        (ys, a, b), vjp = jax.vjp(lambda *w: grouped.grouped_mlp(
            *w, counts, None, rows_past), xs, gate, up, down)
        return ys, a, b, vjp((g, jnp.zeros_like(a), jnp.zeros_like(b)))

    assert grouped.mlp_takes(n, h, f)
    compiled = jax.jit(fn).lower(
        sds((n, h)), sds((e, h, f)), sds((e, h, f)), sds((e, f, h)),
        sds((e,), jnp.int32), sds((n, h))).compile()
    text = compiled.as_text()
    assert _custom_calls(text) == (
        ["grouped_matmul"] * 3 + ["grouped_matmul_nt"] * 3
        + ["grouped_matmul_tn"] * 3)
    over_rows = [f_ for f_ in fusions(text, unfused=True)
                 if "bf16[%d," % n in f_["shape"]]
    assert len(over_rows) == (1 if rows_past else 0)
    assert not [m.group(1) for m in map(_INSTR.match, text.splitlines())
                if m and m.group(3) == "copy"
                and m.group(2).startswith("bf16[%d," % n)]


def _olmoe_step(one_chip, monkeypatch, rows=2):
    """The one-layer OLMoE training step of the `olmoe_1b_7b` configuration
    (published widths, `rows` rows of 4096 tokens, bf16 AMP, AdamW, global
    clip) as the Executor lowers it on a TPU place, compiled for one v5e
    chip."""
    return _lm_step(one_chip, monkeypatch, "olmoe_1b_7b", rows,
                    lambda built: [built["routing"][0][1].name])


def _lm_step(one_chip, monkeypatch, config, rows, fetches):
    """A language-model configuration's training step (published widths,
    `rows` rows of its sequence length, bf16 AMP, AdamW, global clip) as
    the Executor lowers it on a TPU place, compiled for one v5e chip. The
    place is the CPU here, so the test steers the two questions the
    lowering asks of it."""
    import importlib
    import json

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.core import executor_core
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import (delta_parts, flash, gated_norm,
                                     grouped, index_loss, index_select,
                                     row_sum, short_conv)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    if repo not in sys.path:
        sys.path.insert(0, repo)
    builder = importlib.import_module("chipbench.configs." + config)
    with open(os.path.join(repo, "chipbench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    monkeypatch.setattr(grouped, "pallas_interpret", lambda: False)
    monkeypatch.setattr(row_sum, "pallas_interpret", lambda: False)
    monkeypatch.setattr(short_conv, "pallas_interpret", lambda: False)
    monkeypatch.setattr(delta_parts, "pallas_interpret", lambda: False)
    monkeypatch.setattr(gated_norm, "pallas_interpret", lambda: False)
    monkeypatch.setattr(index_loss, "pallas_interpret", lambda: False)
    monkeypatch.setattr(index_select, "pallas_interpret", lambda: False)
    # the policy on before the build, as the cells have it: the optimizer
    # then keeps the bf16 copies of the expert weights
    amp.enable("bfloat16")
    try:
        built = builder.build(fluid, cfg, 7)
    finally:
        amp.disable()
    gb = built["prog"].global_block()
    wrote = {n for op in gb.ops for n in op.output_arg_names()}
    read = {n for op in gb.ops for n in op.input_arg_names()}
    state = {n: jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype),
                                     sharding=one_chip)
             for n, v in gb.vars.items()
             if v.persistable and n in wrote | read}
    mut = {n: s for n, s in state.items() if n in wrote}
    const = {n: s for n, s in state.items() if n not in wrote}
    S = cfg["sequence_length"]
    feeds = {n: jax.ShapeDtypeStruct((rows, S), np.int32, sharding=one_chip)
             for n in ("tokens", "labels")}
    rng = jax.ShapeDtypeStruct((2,), np.uint32, sharding=one_chip)
    step = executor_core.build_step_fn(
        built["prog"], [built["loss"].name] + fetches(built), sorted(mut))
    amp.enable("bfloat16")
    try:
        return cfg, jax.jit(step, donate_argnums=(0,)).lower(
            mut, const, feeds, rng).compile()
    finally:
        amp.disable()


def test_olmoe_step_writes_no_scores_and_no_all_experts_tensor(
        one_chip, no_compile_cache, monkeypatch):
    """At 2 x 4096 tokens the compiled step holds the flash kernels (the
    forward, dK/dV and dQ) and the nine grouped products as the program's
    own kernels (three forward, three d lhs, three d rhs: none run twice,
    none is XLA's `ragged-dot`), no float32 array whose trailing dims are
    [S, S] or [S, 256] (attention scores, whole or by key block), no
    `while` under the attention backward, no [T, 64, 1024] (every token
    through every expert), and no `copy` that writes a bf16 [64, ., .]
    array (a second orientation of an expert weight: the kernels read the
    one plain cast in place, forward and backward): attention never writes
    its scores to HBM, and the expert products run over the rows routed.
    It fits the chip."""
    cfg, compiled = _olmoe_step(one_chip, monkeypatch)
    text = compiled.as_text()
    S, E, F = (cfg["sequence_length"], cfg["num_experts"],
               cfg["intermediate_size"])
    T = 2 * S
    arrays = {(dt, tuple(int(d) for d in dims.split(",") if d))
              for dt, dims in _ARRAY.findall(text)}
    shapes = {s for _, s in arrays}
    scores = [s for s in shapes if len(s) >= 2 and s[-2:] == (S, S)]
    assert scores == []
    blocks = [s for dt, s in arrays
              if dt == "f32" and len(s) >= 2 and s[-2:] == (S, 256)]
    assert blocks == []
    loops = [ln for ln in text.splitlines()
             if "causal_attention_grad" in ln and _INSTR.match(ln)
             and _INSTR.match(ln).group(3) == "while"]
    assert loops == []
    all_experts = [s for s in shapes
                   if len(s) >= 3 and s[-3:] in ((T, E, F), (E, T, F))]
    assert all_experts == []
    # the routed rows are there: [T * 8, F] and [T * 8, H]
    k, H = cfg["num_experts_per_tok"], cfg["hidden_size"]
    assert (T * k, F) in shapes and (T * k, H) in shapes
    # 9 grouped products, the flash forward, dK/dV and dQ
    calls = [m.group(1) for m in map(_INSTR.match, text.splitlines())
             if m and 'custom_call_target="tpu_custom_call"' in m.group(4)]
    names = sorted(re.sub(r"\.\d+$", "", n) for n in calls)
    assert names == (["flash_dkv", "flash_dq", "flash_fwd"]
                     + ["grouped_matmul"] * 3 + ["grouped_matmul_nt"] * 3
                     + ["grouped_matmul_tn"] * 3 + ["row_tile_sum"])
    assert ragged_dots(text) == []
    # the embedding's gradient (PR 38): 412 MB do not fit `S(1)`, so the
    # row-tile kernel writes the table and XLA scatters nothing into it
    assert _embedding_gradients(text, cfg["vocab_size"], H) == (
        ["embed/lookup_table_grad/row_tile_sum/pallas_call"], [])
    # the flash kernels' names (PR 32) come after the op's scope, which is
    # what the attention readers of chipbench find them by
    flash_ops = {re.search(r'op_name="([^"]*)"', ln).group(1).split("/", 1)[1]
                 for ln in text.splitlines() if re.match(r"\s*%flash_", ln)}
    assert flash_ops == {
        "attn/causal_attention/flash_fwd/pallas_call",
        "attn/causal_attention_grad/flash_dkv/pallas_call",
        "attn/causal_attention_grad/flash_dq/pallas_call"}
    # what chipbench/scopes.py will make of the kernels' events: their
    # names come AFTER the component JAX wraps in jvp(...) and the reader
    # drops, so they are in the scope key in both directions
    op_names = {re.search(r'op_name="([^"]*)"', ln).group(1)
                for ln in text.splitlines()
                if re.match(r"\s*%grouped_matmul", ln)}
    assert {n.split("/", 1)[1] for n in op_names} == {
        "moe/moe_ffn/grouped/grouped_matmul/pallas_call",
        "moe/moe_ffn_grad/transpose(moe/moe_ffn_grad)/jvp(vjp)/grouped/"
        "grouped_matmul_nt/pallas_call",
        "moe/moe_ffn_grad/transpose(moe/moe_ffn_grad)/jvp(vjp)/grouped/"
        "grouped_matmul_tn/pallas_call"}
    copies = [m.group(2) for m in map(_INSTR.match, text.splitlines())
              if m and m.group(3) in ("copy", "copy-start", "transpose")
              and re.match(r"\(?bf16\[%d,\d+,\d+\]" % E, m.group(2))]
    assert copies == []
    # the expert MLP's element-wise work rides in the kernels (PR 31):
    # outside them nothing under the op's two scopes touches a [T * k, F]
    # array (SiLU * up and its backward) or takes two [T * k, H] arrays
    # (the sum of the two d xs products), and those scopes' passes move
    # 1.8 GB less than at the parent (the same rule on commit 54f7845)
    passes = [f for f in fusions(text, unfused=True)
              if re.search(r"/moe_ffn(_grad)?(/|$)", f["op_name"])]
    rows_f, rows_h = ("bf16[%d,%d]" % (T * k, w) for w in (F, H))
    assert [f["name"] for f in passes
            if rows_f in f["shape"] or rows_f in f["operand_shapes"]] == []
    assert [f["name"] for f in passes
            if f["operand_shapes"].count(rows_h) > 1] == []
    moved = sum(f["read"] + f["write"] for f in passes)
    print("bytes a step the passes under moe_ffn / moe_ffn_grad move: "
          "%.3f GB at the parent, %.3f GB now" % (_PARENT_MOE_PASSES / 1e9,
                                                  moved / 1e9))
    assert _PARENT_MOE_PASSES - moved >= 1.8e9
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 15.5e9, held


def _expert_weights_come_cast_from_their_update(compiled, name, weights):
    """`weights`: {an expert weight's [E', ., .] shape: how many the step
    has}. No operation of the step writes a bf16 array of such a shape but
    the `adam` fusion that writes the same weight's float32 master and its
    two moments, one a weight (the kept copy is a result of the one pass
    over the state: `amp.KERNEL_SLOTS`); in particular no standalone
    `convert` does (the policy's cast, a pass of its own before a Pallas
    kernel). Prints what the step holds; returns arguments + temporaries +
    code, the bytes the chip must have."""
    found = {}
    for f in fusions(compiled.as_text(), unfused=True):
        for shape in weights:
            dims = "[%s]" % ",".join(map(str, shape))
            if "bf16" + dims in f["shape"]:
                assert f["kind"] == "kLoop" \
                    and "/optimizer/adam(" in f["op_name"] \
                    and f["shape"].count("f32" + dims) == 3, (
                        f["name"], f["shape"], f["op_name"])
                found[shape] = found.get(shape, 0) + 1
    assert found == weights, found
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    code = mem.generated_code_size_in_bytes
    print("%s step on a v5e: arguments %.3f GB + temporaries %.3f GB + "
          "code %.3f GB = %.3f GB" % (
              name, (held - mem.temp_size_in_bytes) / 1e9,
              mem.temp_size_in_bytes / 1e9, code / 1e9,
              (held + code) / 1e9))
    return held + code


def _custom_calls(text):
    calls = [m.group(1) for m in map(_INSTR.match, text.splitlines())
             if m and 'custom_call_target="tpu_custom_call"' in m.group(4)]
    return sorted(re.sub(r"\.\d+$", "", n) for n in calls)


def _embedding_gradients(text, V, H):
    """What forms the [V, H] gradient of the embedding in a compiled step:
    (the op_names of the row-tile kernel's calls, the result shapes with
    their layouts of XLA's scatters of that table). PR 38: the kernel
    where the table is too large for `S(1)`, XLA's sorted scatter with its
    result in `S(1)` where it is not."""
    kernels = [name for name in _row_tile_sums(text)
               if "/lookup_table_grad/" in name]
    scatters = [m.group(2) for m in map(_INSTR.match, text.splitlines())
                if m and m.group(3) == "scatter"
                and m.group(2).startswith("f32[%d,%d]" % (V, H))]
    return kernels, scatters


def _row_tile_sums(text):
    """The op_names (after the jit's own component) of the row-tile
    kernel's calls in a compiled step, sorted."""
    return sorted(
        re.search(r'op_name="([^"]*)"', ln).group(1).split("/", 1)[1]
        for ln in text.splitlines() if re.match(r"\s*%row_tile_sum", ln))


def _cond_branches(text, scope):
    """[(instruction lines of the overflow branch, of the bounded branch)]
    of the `lax.cond`s whose branches hold instructions of the Fluid op
    `scope` (branch_0 is the false one: the overflow)."""
    comps, cur = {}, None
    for ln in text.splitlines():
        m = _COMPUTATION.match(ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln)
    found = []
    for lines in comps.values():
        for ln in lines:
            if " conditional(" not in ln:
                continue
            names = re.search(r"branch_computations=\{([^}]*)\}", ln)
            names = ([n.strip().lstrip("%") for n in names.group(1).split(",")]
                     if names else re.search(
                         r"true_computation=%(\S+?), false_computation=%(\S+?)"
                         r"[,\s]", ln).groups()[::-1])
            branches = [comps[n] for n in names]
            if any("/%s/cond/branch_" % scope in b for br in branches
                   for b in br):
                found.append(tuple(branches))
    return found


def _arrays_of_rows(lines, rows, width):
    """Names of the instructions in `lines` that WRITE an array of `rows`
    rows of `width` (fusions, gathers, selects, pads, kernels; not the
    parameters and tuple elements that only hand one on)."""
    made = []
    for ln in lines:
        m = _INSTR.match(ln)
        if m and m.group(3) not in ("parameter", "get-tuple-element",
                                    "bitcast", "tuple") and re.search(
                r"\[%d,%d(,\d+)?\]|\[\d+,%d,%d\]" % (rows, width, rows // 8,
                                                       width), m.group(2)):
            made.append(m.group(1))
    return made


def _bounded_branches_move_the_bound_s_rows(text, rows, bound, width,
                                            layers):
    """Each sparse layer has a `cond` in `moe_ffn` and one in
    `moe_ffn_grad`. The bounded branch of the forward writes all `rows`
    rows of `width` once (`DownOut`'s zero tail behind the down
    product), that of the backward never: the dispatch, the zeroing and d
    ys are over `bound` rows and the bounded sums gather [tokens, width] a
    choice or, summed by token (PR 40), `bound` rows and a chunk's tail
    once; the overflow branches move all `rows`."""
    for scope, most in (("moe_ffn", 1), ("moe_ffn_grad", 0)):
        conds = _cond_branches(text, scope)
        assert len(conds) == layers, (scope, len(conds))
        for overflow, bounded in conds:
            full = _arrays_of_rows(bounded, rows, width)
            assert len(full) <= most, (scope, full)
            assert all("concatenate" in ln for ln in bounded
                       if any("%" + n + " = " in ln for n in full)), full
            assert len(_arrays_of_rows(bounded, bound, width)) >= 3
            assert len(_arrays_of_rows(overflow, rows, width)) >= 4


def test_xing_step_runs_both_kernel_families_over_its_share(
        one_chip, no_compile_cache, monkeypatch):
    """The `xing4_0_29b_a4b` step at 1 x 4096 tokens (6 blocks) compiles for one v5e chip with the flash kernels at
    queries and keys of 192 and values of 128 over the 4 held heads (a
    forward, dK/dV and dQ a block) and the grouped kernels over the 8
    held groups, at K = 3584 and K = 1024: nine an expert block in the
    branch that works on the row bound's 4,096 of the 16,384 choice rows
    and twelve in the overflow branch over all of them (the backward op
    computes the three forward products again there), of which a step
    runs one; no XLA `ragged-dot`, no [S, S] scores, no
    [T, 64, .] tensor (every token through every expert of the router's
    width); inside the bounded branches nothing writes an array of all
    16,384 rows of 3584 but the zero tail of `DownOut` (both combines
    gather [4096, 3584] a choice); and it fits the chip: the compiler
    itself refuses a step past 15.75 GiB."""
    cfg, compiled = _lm_step(
        one_chip, monkeypatch, "xing4_0_29b_a4b", 1,
        lambda built: [built["routing"][0][1].name]
        + [r[2].name for r in built["routing"]])
    text = compiled.as_text()
    assert _custom_calls(text) == (
        ["flash_dkv"] * 6 + ["flash_dq"] * 6 + ["flash_fwd"] * 6
        + ["grouped_matmul"] * (15 + 30) + ["grouped_matmul_nt"] * 30
        + ["grouped_matmul_tn"] * 30 + ["row_tile_sum"] * 2)
    assert ragged_dots(text) == []
    # the embedding is read twice (the prediction module): two gradients
    # of 235 MB, each by the row-tile kernel (PR 38); the expert blocks'
    # bounded sums keep their gathers: four choices a token
    # (`row_sum.takes_choices`, PR 40)
    assert _embedding_gradients(text, cfg["vocab_size"], 3584) == (
        ["embed/lookup_table_grad/row_tile_sum/pallas_call",
         "mtp/embed/lookup_table_grad/row_tile_sum/pallas_call"], [])
    k, T = cfg["num_experts_per_tok"], cfg["sequence_length"]
    _bounded_branches_move_the_bound_s_rows(text, T * k, 4096, 3584, 5)
    arrays = {(dt, tuple(int(d) for d in dims.split(",") if d))
              for dt, dims in _ARRAY.findall(text)}
    shapes = {s for _, s in arrays}
    S, T = cfg["sequence_length"], cfg["sequence_length"]
    assert [s for s in shapes if len(s) >= 2 and s[-2:] == (S, S)] == []
    # the kernels' operands: 4 heads of 192 and of 128; the held experts'
    # stacked matrices in bf16, in one orientation each
    assert (4, S, 192) in shapes and (4, S, 128) in shapes
    assert (8, 3584, 1024) in shapes and (8, 1024, 3584) in shapes
    assert (T * k, 1024) in shapes and (T * k, 3584) in shapes
    assert (4096, 1024) in shapes and (4096, 3584) in shapes
    assert [s for s in shapes if len(s) >= 3 and 64 in s[-3:]
            and s[-1] in (1024, 3584) and T in s] == []
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 12.6e9 < held < 16.4e9, held
    # every expert block's three (4 + the prediction module's), and the
    # step with its code under what the chip holds (16.9 GB): 15.87 GB,
    # the parent's 16.30 less the casts' temporaries XLA kept apart
    assert _expert_weights_come_cast_from_their_update(
        compiled, "xing4_0_29b_a4b",
        {(8, 3584, 1024): 10, (8, 1024, 3584): 5}) < 16.9e9


def test_olmoe_kernel_counts_are_unchanged(one_chip, no_compile_cache,
                                           monkeypatch):
    """What this file asserts of the OLMoE step above, at one row (a
    quicker compile): `moe_ffn`'s and `causal_attention`'s new attributes
    at their defaults lower to the same twelve kernels."""
    _, compiled = _olmoe_step(one_chip, monkeypatch, rows=1)
    text = compiled.as_text()
    assert _custom_calls(text) == (
        ["flash_dkv", "flash_dq", "flash_fwd"]
        + ["grouped_matmul"] * 3 + ["grouped_matmul_nt"] * 3
        + ["grouped_matmul_tn"] * 3 + ["row_tile_sum"])
    assert ragged_dots(text) == []
    # 10.196 GB + 0.040 of code at one row, the parent's to the byte: the
    # copies take the place of the casts' temporaries
    assert _expert_weights_come_cast_from_their_update(
        compiled, "olmoe_1b_7b", {(64, 2048, 1024): 2, (64, 1024, 2048): 1}
    ) < 10.4e9


@pytest.mark.parametrize("window,heads,blocks", [
    (512, (8, 1), (256, 256)), (512, (8, 1), (512, 512)),
    (512, (8, 1), (128, 128)), (None, (6, 1), (1024, 1024)),
    (4096, (7, 1), (1024, 1024)), (4096, (7, 1), (512, 512)),
    (None, (7, 1), (1024, 1024))],
    ids=["band_256", "band_512", "band_128", "group_of_6",
         "wide_band_1024_group_of_7", "wide_band_512_group_of_7",
         "group_of_7"])
def test_flash_band_and_head_groups_compile_for_v5e(
        one_chip, no_compile_cache, monkeypatch, window, heads, blocks):
    """Mosaic takes the three kernels at the `laguna_xs_2` cell's shapes:
    a band of 512 over a row of 8192 at each block size swept, 8 query
    heads on one key/value head read in place, and the triangle with 6 on
    one; and at the `smallthinker_21b_a3b` cell's: a band of 4096 (4-5 key
    blocks a query block at 1024 x 1024, of which the inner ones carry no
    mask; 8-9 at 512 x 512) and the triangle, 7 query heads on one
    key/value head: a forward, a dK/dV and a dQ custom call, no loop, and
    no copy of K or V the size of the query heads'."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import flash

    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    q_shape, k_shape = (1, heads[0], 8192, 128), (1, heads[1], 8192, 128)

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def both(q, k, v, do):
        o, lse = flash.flash_attention_fwd(
            q, k, v, causal=True, window=window, block_q=blocks[0],
            block_k=blocks[1])
        return o, flash.flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, window=window,
            block_q=blocks[0], block_k=blocks[1])

    compiled = jax.jit(both).lower(sds(q_shape), sds(k_shape), sds(k_shape),
                                   sds(q_shape)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert not any(m.group(3) == "while"
                   for m in map(_INSTR.match, text.splitlines()) if m)
    # q, o, do, dq and their float32 companions; never K or V repeated
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 3 * heads[0] * 8192 * 128 * 4


def test_laguna_step_runs_window_and_full_kernels_over_its_share(
        one_chip, no_compile_cache, monkeypatch):
    """The `laguna_xs_2` step at 1 x 8192 tokens (5 layers) compiles for
    one v5e chip with the flash kernels at both head counts (6 and 8 query
    heads on the one key/value head held: a forward, dK/dV and dQ a
    layer) and the grouped kernels over the 32 held groups of width 512:
    nine a sparse layer in the branch that works on the row bound's
    16,384 of the 65,536 choice rows and twelve in the overflow branch
    over all of them (the backward op computes the three forward products
    again there), of which a step runs one; no XLA `ragged-dot`, no [S,
    S] scores, no K or V repeated for the query heads; inside the bounded
    branches nothing writes an array of all 65,536 rows of 2048 but the
    zero tail of `DownOut`; and it fits the chip: the compiler itself
    refuses a step past 15.75 GiB."""
    cfg, compiled = _lm_step(
        one_chip, monkeypatch, "laguna_xs_2", 1,
        lambda built: [built["routing"][0][1].name]
        + [r[2].name for r in built["routing"]])
    text = compiled.as_text()
    assert _custom_calls(text) == (
        ["flash_dkv"] * 5 + ["flash_dq"] * 5 + ["flash_fwd"] * 5
        + ["grouped_matmul"] * (12 + 24) + ["grouped_matmul_nt"] * 24
        + ["grouped_matmul_tn"] * 24 + ["row_tile_sum"] * 8)
    assert ragged_dots(text) == []
    # why `row_sum.takes` has a threshold (PR 38): this table's 103 MB are
    # the one gradient XLA assigns to the chip's fast memory, where its
    # sorted scatter costs 0.13 us a row; no kernel there. The eight calls
    # are the four sparse layers' bounded sums by token (PR 40)
    kernels, scatters = _embedding_gradients(text, cfg["vocab_size"], 2048)
    assert kernels == [] and len(scatters) == 1
    assert all(n.startswith("moe/moe_ffn") for n in _row_tile_sums(text))
    assert "S(1)" in scatters[0] and "indices_are_sorted=true" in text
    S, k = cfg["sequence_length"], cfg["num_experts_per_tok"]
    _bounded_branches_move_the_bound_s_rows(text, S * k, 16384, 2048, 4)
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for _, dims in _ARRAY.findall(text)}
    # [S, S] here is also [tokens, the dense MLP's width]: no such array
    # under either attention scope
    assert [ln for ln in text.splitlines()
            if "%d,%d]" % (S, S) in ln and "/attn_" in ln] == []
    flash_ops = {re.search(r'op_name="([^"]*)"', ln).group(1).split("/", 1)[1]
                 for ln in text.splitlines() if re.match(r"\s*%flash_", ln)}
    assert flash_ops == {
        scope + "/" + kernel + "/pallas_call"
        for scope in ("attn_full", "attn_window") for kernel in (
            "causal_attention/flash_fwd", "causal_attention_grad/flash_dkv",
            "causal_attention_grad/flash_dq")}
    assert (6, S, 128) in shapes and (8, S, 128) in shapes
    assert (1, S, 128) in shapes
    assert (32, 2048, 512) in shapes and (32, 512, 2048) in shapes
    assert (S * k, 512) in shapes and (S * k, 2048) in shapes
    assert (16384, 512) in shapes and (16384, 2048) in shapes
    assert [s for s in shapes if len(s) >= 3 and 256 in s[-3:]
            and s[-1] in (512, 2048) and S in s] == []
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 8.65e9 < held < 16.4e9, held
    assert _expert_weights_come_cast_from_their_update(
        compiled, "laguna_xs_2",
        {(32, 2048, 512): 8, (32, 512, 2048): 4}) < 16.9e9


def test_smallthinker_step_routes_early_and_gates_by_relu(
        one_chip, no_compile_cache, monkeypatch):
    """The `smallthinker_21b_a3b` step at 1 x 8192 tokens (4 layers, each
    sparse) compiles for one v5e chip with the flash kernels at 7 query
    heads on the one key/value head held (a forward, dK/dV and dQ a
    layer: one full layer, three with a band of 4096) and the grouped
    kernels over the 16 held groups at K 2560 / F 768: nine a layer in
    the branch that works on the row bound's 24,576 of the 49,152 choice
    rows and twelve in the overflow branch; the kernels that carry ReLU
    stand under `relu_mul` / `relu_mul_grad` on their op_name (the up
    product and d down's lhs, the d h product), and nothing under a
    `silu` name; no XLA `ragged-dot`; the step reads kept bf16 copies of
    the twelve expert matrices; and it fits the chip with room: arguments
    + temporaries + code under 15.5 GB."""
    cfg, compiled = _lm_step(
        one_chip, monkeypatch, "smallthinker_21b_a3b", 1,
        lambda built: [built["routing"][0][1].name]
        + [r[2].name for r in built["routing"]])
    text = compiled.as_text()
    assert _custom_calls(text) == (
        ["flash_dkv"] * 4 + ["flash_dq"] * 4 + ["flash_fwd"] * 4
        + ["grouped_matmul"] * (12 + 24) + ["grouped_matmul_nt"] * 24
        + ["grouped_matmul_tn"] * 24 + ["row_tile_sum"] * (1 + 8))
    assert ragged_dots(text) == []
    # the 389 MB gradient of the embedding by the row-tile kernel (PR 38),
    # as it was before the kernel had a second caller
    assert _embedding_gradients(text, cfg["vocab_size"], 2560) == (
        ["embed/lookup_table_grad/row_tile_sum/pallas_call"], [])
    # the bounded sums by token (PR 40): a layer's combine and the
    # dispatch's backward, in the bounded branch of each op's `cond`
    assert [n for n in _row_tile_sums(text) if n.startswith("moe/")] == [
        "moe/moe_ffn/cond/branch_1_fun/combine/row_tile_sum/pallas_call"
    ] * 4 + ["moe/moe_ffn_grad/cond/branch_1_fun/transpose(jvp(vjp))/"
             "dispatch/row_tile_sum/pallas_call"] * 4
    S, k = cfg["sequence_length"], cfg["moe_num_active_primary_experts"]
    _bounded_branches_move_the_bound_s_rows(text, S * k, 24576, 2560, 4)
    kernels = [re.search(r'op_name="([^"]*)"', ln).group(1)
               for ln in text.splitlines()
               if re.match(r"\s*%grouped_matmul", ln)]
    assert len(kernels) == 84

    def under(scope):
        return sum(("/" + scope + "/") in name for name in kernels)

    # per layer: forward up (bounded, overflow, and the overflow's
    # backward computing the products again) 3; d down's lhs formed from a
    # and b 2; the d h product 2
    assert under("relu_mul") == 4 * 5 and under("relu_mul_grad") == 4 * 2
    assert not any("silu" in name for name in kernels)
    assert all("/moe/moe_ffn" in name for name in kernels)
    flash_ops = {re.search(r'op_name="([^"]*)"', ln).group(1).split("/", 1)[1]
                 for ln in text.splitlines() if re.match(r"\s*%flash_", ln)}
    assert flash_ops == {
        scope + "/" + kernel + "/pallas_call"
        for scope in ("attn_full", "attn_window") for kernel in (
            "causal_attention/flash_fwd", "causal_attention_grad/flash_dkv",
            "causal_attention_grad/flash_dq")}
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for _, dims in _ARRAY.findall(text)}
    assert (7, S, 128) in shapes and (1, S, 128) in shapes
    assert (16, 2560, 768) in shapes and (16, 768, 2560) in shapes
    assert (24576, 768) in shapes and (24576, 2560) in shapes
    assert [ln for ln in text.splitlines()
            if "%d,%d]" % (S, S) in ln and "/attn_" in ln] == []
    assert _expert_weights_come_cast_from_their_update(
        compiled, "smallthinker_21b_a3b",
        {(16, 2560, 768): 8, (16, 768, 2560): 4}) < 15.5e9


@pytest.mark.parametrize("blocks", [(1024, 1024), (512, 512)],
                         ids=["blocks_1024", "blocks_512"])
def test_flash_heads_of_64_compile_for_v5e(one_chip, no_compile_cache,
                                           monkeypatch, blocks):
    """Mosaic takes the three kernels at the `lfm2_8b_a1b` cell's head
    shape, [1, 32 on 8, 8192, 64] bf16 over the whole triangle: a block's
    last dimension is half a lane tile and the score product contracts
    over 64. A forward, a dK/dV and a dQ custom call, no loop, and no copy
    of K or V the size of the query heads'."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import flash

    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    q_shape, k_shape = (1, 32, 8192, 64), (1, 8, 8192, 64)

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def both(q, k, v, do):
        o, lse = flash.flash_attention_fwd(
            q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1])
        return o, flash.flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, block_q=blocks[0],
            block_k=blocks[1])

    compiled = jax.jit(both).lower(sds(q_shape), sds(k_shape), sds(k_shape),
                                   sds(q_shape)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert not any(m.group(3) == "while"
                   for m in map(_INSTR.match, text.splitlines()) if m)
    # q, o, do, dq and their float32 companions; never K or V repeated.
    # A head of 64 fills half a lane tile: the temporaries (302 MB) are
    # those of heads of 128, not half of them
    assert 3 * 32 * 8192 * 64 * 4 \
        < compiled.memory_analysis().temp_size_in_bytes \
        < 3 * 32 * 8192 * 128 * 4


@pytest.mark.parametrize("heads,d,dv,S,window", [
    ((32, 8), 64, 64, 8192, None), ((7, 1), 128, 128, 8192, None),
    ((4, 4), 192, 128, 4096, None), ((7, 1), 128, 128, 8192, 4096),
    ((2, 2), 128, 128, 1000, None)],
    ids=["d64_32_on_8", "d128_7_on_1", "keys_192_values_128",
         "d128_band_4096", "d128_padded_keys"])
def test_flash_forward_alone_compiles_for_v5e(one_chip, no_compile_cache,
                                              monkeypatch, heads, d, dv, S,
                                              window):
    """Mosaic takes the forward kernel ALONE at 1024 x 1024 blocks, bf16,
    with its running max and normaliser as [block_q, 128] float32 scratch
    (PR 41) and both of its bodies, the masked one and the unguarded one:
    at heads of 64 (half a lane tile: the accumulator's rescale takes the
    first 64 lanes of the correction), of 128, with keys of 192 beside
    values of 128, over a band, and with padded keys (a bias channel makes
    the contraction 129 wide). One custom call, no loop, `Out` and `Lse`
    the only results, nothing of a score block's size outside VMEM."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import flash

    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def fwd(q, k, v):
        return flash.flash_attention_fwd(q, k, v, causal=True, window=window,
                                         block_q=1024, block_k=1024)

    operands = (sds((1, heads[0], S, d)), sds((1, heads[1], S, d)),
                sds((1, heads[1], S, dv)))
    compiled = jax.jit(fwd).lower(*operands).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not any(m.group(3) == "while"
                   for m in map(_INSTR.match, text.splitlines()) if m)
    out, lse = jax.eval_shape(fwd, *operands)
    assert out.shape == (1, heads[0], S, dv) and out.dtype == jnp.bfloat16
    assert lse.shape == (1, heads[0], S) and lse.dtype == jnp.float32
    # the padded operands, the folded results and the [8, S] tile of lse
    # at most: a float32 score block a head would be 4 S^2 bytes
    assert compiled.memory_analysis().temp_size_in_bytes \
        < heads[0] * S * 1024 * 4


@pytest.mark.parametrize("kind", ["forward", "backward"])
def test_short_conv_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                            monkeypatch, kind):
    """Mosaic takes the two kernels of `parallel/short_conv.py` at the
    `lfm2_8b_a1b` cell's shape, X [8192, 6144] bf16 with 3 float32 taps:
    one custom call each, no temporary of an activation's size beside it
    (the sublane rotations, the halo views and the float32 accumulator of
    d Filter live in VMEM)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import short_conv

    monkeypatch.setattr(short_conv, "pallas_interpret", lambda: False)
    S, C = 8192, 2048

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    x, w, g = (sds((S, 3 * C), "bfloat16"), sds((3, C), "float32"),
               sds((S, C), "bfloat16"))
    if kind == "forward":
        compiled = jax.jit(
            lambda x, w: short_conv.short_conv_fwd(x, w, S)).lower(
                x, w).compile()
    else:
        compiled = jax.jit(
            lambda x, w, g: short_conv.short_conv_bwd(x, w, g, S)).lower(
                x, w, g).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def test_lfm2_step_runs_conv_kernels_flash_at_64_and_a_tied_table(
        one_chip, no_compile_cache, monkeypatch):
    """The `lfm2_8b_a1b` step at 1 x 8192 tokens (5 layers: conv + dense,
    attention + experts, three conv + experts) compiles for one v5e chip
    with the conv kernels (a forward and a backward a conv layer, named
    under `conv/short_conv/`), the flash kernels at 32 query heads on 8
    key/value heads of 64 (a forward, dK/dV and dQ), the grouped kernels
    over the 8 held groups at K 2048 / F 1792 (nine a sparse layer in the
    branch that works on the row bound's 16,384 of the 32,768 choice rows
    and twelve in the overflow branch), the tied table's lookup gradient
    by the row-tile kernel (134 MB, PR 38) and NO XLA convolution (no
    grouped convolution of 2048 feature groups, no transpose to [rows, C,
    S]); the step reads kept bf16 copies of the twelve expert matrices;
    and it fits the chip: arguments + temporaries + code under 13 GB."""
    cfg, compiled = _lm_step(
        one_chip, monkeypatch, "lfm2_8b_a1b", 1,
        lambda built: [built["routing"][0][1].name]
        + [r[2].name for r in built["routing"]])
    text = compiled.as_text()
    assert _custom_calls(text) == (
        ["flash_dkv", "flash_dq", "flash_fwd"]
        + ["grouped_matmul"] * (12 + 24) + ["grouped_matmul_nt"] * 24
        + ["grouped_matmul_tn"] * 24 + ["row_tile_sum"]
        + ["short_conv_bwd"] * 4 + ["short_conv_fwd"] * 4)
    assert ragged_dots(text) == []
    assert _embedding_gradients(text, cfg["vocab_size"], 2048) == (
        ["embed/lookup_table_grad/row_tile_sum/pallas_call"], [])
    S, k = cfg["sequence_length"], cfg["num_experts_per_tok"]
    _bounded_branches_move_the_bound_s_rows(text, S * k, 16384, 2048, 4)
    assert "feature_group_count=2048" not in text
    names = {re.search(r'op_name="([^"]*)"', ln).group(1).split("/", 1)[1]
             for ln in text.splitlines()
             if re.match(r"\s*%(flash_|short_conv_)", ln)}
    assert names == {
        "attn/causal_attention/flash_fwd/pallas_call",
        "attn/causal_attention_grad/flash_dkv/pallas_call",
        "attn/causal_attention_grad/flash_dq/pallas_call",
        "conv/short_conv/short_conv/short_conv_fwd/pallas_call",
        "conv/short_conv/short_conv_grad/short_conv_bwd/pallas_call"}
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for _, dims in _ARRAY.findall(text)}
    assert (32, S, 64) in shapes and (8, S, 64) in shapes
    assert (8, 2048, 1792) in shapes and (8, 1792, 2048) in shapes
    assert (16384, 1792) in shapes and (16384, 2048) in shapes
    # no [rows, C, S] layout of the conv's channels, no [S, S] scores
    assert (1, 2048, S) not in shapes and (2048, S) not in shapes
    assert [ln for ln in text.splitlines()
            if "%d,%d]" % (S, S) in ln and "/attn/" in ln] == []
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # the file's `arithmetic`: 6.80 GB of arguments (weights, two moments,
    # the kept copies), ~4.9 GB of temporaries (gradients, activations)
    assert 11.0e9 < held < 12.6e9, held
    assert _expert_weights_come_cast_from_their_update(
        compiled, "lfm2_8b_a1b",
        {(8, 2048, 1792): 8, (8, 1792, 2048): 4}) < 13.0e9
