"""`gated_rms_norm`: the output norm of a Gated DeltaNet layer as one op, Y
= rms_norm(X; Scale) * silu(Gate) over heads of D numbers, float32 inside
and rounded once: against the three ops it replaces (`rms_norm`, `swish`,
`elementwise_mul`), its hand-written backward against `jax.vjp` of the
plain form, what the op is under AMP, and the Pallas kernels of
`parallel/gated_norm.py`, interpreted, against the plain form."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp
from paddle_tpu.ops import lm_ops
from paddle_tpu.parallel import gated_norm as kernels

EPS = 1e-6


def _inputs(T, H, D, dtype, seed=0, flat_gate=True):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = (3.0 * jax.random.normal(k[0], (T, H, D), jnp.float32)).astype(dtype)
    z = (2.0 * jax.random.normal(k[1], (T, H * D) if flat_gate
                                 else (T, H, D), jnp.float32)).astype(dtype)
    w = 1.0 + 0.5 * jax.random.normal(k[2], (D,), jnp.float32)
    g = jax.random.normal(k[3], (T, H, D), jnp.float32).astype(dtype)
    return x, z, w, g


def _f32(a):
    return np.asarray(a, np.float32)


def _three_ops(x, z, w):
    """What the model's program held before the op: `rms_norm` on [T, H,
    D], `swish` of the gate, `elementwise_mul`, each as its op lowers."""
    y = lm_ops.rms_norm_op(None, {"X": [x], "Scale": [w]},
                           {"epsilon": EPS})["Y"][0]
    s = z * jax.nn.sigmoid(z)
    return y * s.reshape(y.shape)


@pytest.mark.parametrize("flat_gate", [True, False], ids=["gate_2d",
                                                          "gate_3d"])
@pytest.mark.parametrize("T,H,D", [(24, 4, 16), (7, 2, 128)])
def test_the_plain_form_is_the_three_ops_in_float32(T, H, D, flat_gate):
    x, z, w, _ = _inputs(T, H, D, "float32", flat_gate=flat_gate)
    got = lm_ops.gated_rms_norm(x, z, w, EPS)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(_f32(got), _f32(_three_ops(x, z, w)),
                               rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("T,H,D", [(24, 4, 16), (7, 2, 128)])
def test_the_backward_is_the_plain_form_s_vjp(T, H, D, dtype):
    """d X, d Gate and d Scale of `gated_rms_norm_grad` against `jax.vjp`
    of the float32 plain form on the same (rounded) inputs."""
    x, z, w, g = _inputs(T, H, D, dtype, seed=1)
    d_x, d_z, d_w = lm_ops.gated_rms_norm_grad(x, z, w, g, EPS)
    assert (d_x.shape, d_x.dtype) == (x.shape, x.dtype)
    assert (d_z.shape, d_z.dtype) == (z.shape, z.dtype)
    assert (d_w.shape, d_w.dtype) == (w.shape, jnp.float32)
    up = [a.astype(jnp.float32) for a in (x, z, w)]
    _, vjp = jax.vjp(lambda *a: lm_ops.gated_rms_norm(*a, EPS), *up)
    want = vjp(g.astype(jnp.float32))
    tol = 1e-5 if dtype == "float32" else 1e-2     # bf16: d x, d z rounded
    for got, ref in zip((d_x, d_z), want):
        np.testing.assert_allclose(_f32(got), _f32(ref), rtol=tol,
                                   atol=tol * float(jnp.max(jnp.abs(ref))))
    # d Scale is float32 whatever the inputs: never rounded
    np.testing.assert_allclose(_f32(d_w), _f32(want[2]), rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(want[2]))))


def _program(T, H, D, flat_gate=True, grad=True):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[H, D], dtype="float32",
                              stop_gradient=False)
        z = fluid.layers.data(name="z", shape=[H * D] if flat_gate
                              else [H, D], dtype="float32",
                              stop_gradient=False)
        y = fluid.layers.gated_rms_norm(
            x, z, epsilon=EPS, param_attr=fluid.ParamAttr(name="scale"))
        loss = fluid.layers.mean(fluid.layers.square(y))
        if grad:
            fluid.backward.append_backward(loss)
    return prog, startup, y, loss


def test_the_program_s_gradients_are_the_plain_form_s():
    """Through the Executor: `append_backward` appends ONE
    `gated_rms_norm_grad` (the hand-written maker), which gives X's, the
    gate's and the scale's gradients."""
    T, H, D = 12, 4, 16
    prog, startup, y, _ = _program(T, H, D)
    kinds = [o.type for o in prog.global_block().ops]
    assert kinds.count("gated_rms_norm") == 1
    assert kinds.count("gated_rms_norm_grad") == 1
    x, z, _, _ = _inputs(T, H, D, "float32", seed=2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w = np.array(scope.find_var("scale"))
        assert w.shape == (D,) and np.all(w == 1.0)
        w = (1.0 + 0.1 * np.arange(D)).astype(np.float32)
        scope.set_var("scale", w)
        got = exe.run(prog, feed={"x": np.asarray(x), "z": np.asarray(z)},
                      fetch_list=[y, "x@GRAD", "z@GRAD", "scale@GRAD"])
    np.testing.assert_allclose(got[0], _f32(_three_ops(x, z, jnp.asarray(w))),
                               rtol=1e-5, atol=1e-6)
    want = jax.grad(lambda *a: jnp.mean(_three_ops(*a) ** 2),
                    argnums=(0, 1, 2))(x, z, jnp.asarray(w))
    for g, ref in zip(got[1:], want):
        np.testing.assert_allclose(g, _f32(ref), rtol=1e-4, atol=1e-7)


def test_under_amp_the_op_is_neutral_and_rounds_once():
    """Off both of AMP's lists, as `rms_norm`: bf16 X and Gate arrive as
    they are, Scale stays the float32 master; Y is the float32 form
    rounded ONCE, which the three ops (a bf16 normed X, a bf16 SiLU, a
    bf16 product) are not."""
    assert "gated_rms_norm" not in amp.WHITE_LIST | amp.BLACK_LIST
    x, z, w, g = _inputs(32, 4, 16, "bfloat16", seed=3)
    amp.enable("bfloat16")
    try:
        ins = amp.apply_policy("gated_rms_norm",
                               {"X": [x], "Gate": [z], "Scale": [w]})
        grad_ins = amp.apply_policy(
            "gated_rms_norm_grad",
            {"X": [x], "Gate": [z], "Scale": [w], "Y@GRAD": [g]})
    finally:
        amp.disable()
    assert ins["X"][0].dtype == ins["Gate"][0].dtype == jnp.bfloat16
    assert ins["Scale"][0].dtype == grad_ins["Scale"][0].dtype == jnp.float32
    got = lm_ops.gated_rms_norm_op(None, ins, {"epsilon": EPS})["Y"][0]
    up = [a.astype(jnp.float32) for a in (x, z)]
    once = _three_ops(*up, w).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_f32(got), _f32(once))
    thrice = (lm_ops.rms_norm_op(None, {"X": [x], "Scale": [w]},
                                 {"epsilon": EPS})["Y"][0]
              * (z * jax.nn.sigmoid(z)).reshape(x.shape))
    assert not np.array_equal(_f32(thrice), _f32(got))
    grads = lm_ops.gated_rms_norm_grad_op(None, grad_ins, {"epsilon": EPS})
    assert grads["X@GRAD"][0].dtype == grads["Gate@GRAD"][0].dtype \
        == jnp.bfloat16
    assert grads["Scale@GRAD"][0].dtype == jnp.float32


def test_shape_inference_and_the_cost_estimate():
    from paddle_tpu.trace import costs

    prog, _, y, _ = _program(12, 4, 16, grad=False)
    assert tuple(y.shape) == (-1, 4, 16)
    scale, = prog.global_block().all_parameters()
    assert tuple(scale.shape) == (16,)
    row, = (r for r in costs.op_costs(prog, batch_size=12)
            if r["op"] == "gated_rms_norm")
    assert row["flops_est"] == 12 * 4 * 16 * 11
    with pytest.raises(Exception):       # a gate of another width
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[4, 16], dtype="float32")
            z = fluid.layers.data(name="z", shape=[48], dtype="float32")
            fluid.layers.gated_rms_norm(x, z)


@pytest.mark.parametrize("D,counted", [(16, False), (128, True)])
def test_lowered_counts_name_the_op_s_lowering(D, counted):
    prog, _, _, _ = _program(32, 2, D)
    want = {"gated_norm_one_op": 1, "gated_norm_grad_by_hand": 1}
    cpu = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform="cpu"))
    assert cpu == want
    tpu = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform="tpu"))
    assert tpu == (dict(want, gated_norm_kernel=1, gated_norm_grad_kernel=1)
                   if counted else want)


# ------------------------------------------------------------ the kernels
# (T, H, D, block): one block, whole blocks, tokens the largest blocks do not
# divide (the kernels then take a shorter one: 80 = 5 x 16, 96 = 3 x 32),
# heads of two lane tiles
KERNEL_CASES = [(32, 2, 128, None), (64, 4, 128, 32), (80, 2, 128, None),
                (96, 3, 128, None), (32, 2, 256, 16)]
KERNEL_IDS = ["one_block", "two_blocks", "five_blocks_of_16",
              "three_blocks_of_32_three_heads", "heads_of_256"]


def _ulp_bf16(a):
    """The spacing of bfloat16 at |a| (8 bits of precision)."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 1e-30))) - 7)


def _held_to_the_plain_form(got, want, dtype):
    """float32 operands: float32 round-off (the lane sum in another order).
    bf16 operands: the same float32 numbers rounded once, so a result
    differs from the plain form's by one bf16 ulp at most (d x is a
    difference: beside float32 round-off of the largest where it cancels),
    and on few elements; a bf16 intermediate (a rounded normed X or SiLU)
    passes neither."""
    got, want = _f32(got), _f32(want)
    assert got.shape == want.shape
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=2e-6,
                                   atol=2e-6 * np.max(np.abs(want)))
        return
    apart = np.abs(got - want)
    assert np.all(apart <= _ulp_bf16(want) * 1.001
                  + 1e-6 * np.max(np.abs(want)))
    assert np.mean(apart > 0) < 0.002, np.mean(apart > 0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("T,H,D,block", KERNEL_CASES, ids=KERNEL_IDS)
def test_the_forward_kernel_is_the_plain_form(T, H, D, block, dtype):
    x, z, w, _ = _inputs(T, H, D, dtype, seed=4)
    assert kernels.fits(x.shape, dtype)
    assert T % (block or kernels._block(T, H * D, x.dtype.itemsize)) == 0
    got = kernels.gated_norm_fwd(x, z, w, EPS, block=block)
    assert got.dtype == x.dtype
    _held_to_the_plain_form(got, lm_ops.gated_rms_norm(x, z, w, EPS), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("T,H,D,block", KERNEL_CASES, ids=KERNEL_IDS)
def test_the_backward_kernel_is_the_plain_form_s(T, H, D, block, dtype):
    x, z, w, g = _inputs(T, H, D, dtype, seed=5)
    got = kernels.gated_norm_bwd(x, z, w, g, EPS, block=block)
    want = lm_ops.gated_rms_norm_grad(x, z, w, g, EPS)
    assert [a.dtype for a in got] == [x.dtype, z.dtype, jnp.float32]
    _held_to_the_plain_form(got[0], want[0], dtype)
    _held_to_the_plain_form(got[1], want[1], dtype)
    # d Scale: float32 sums over T x H numbers in another order
    np.testing.assert_allclose(_f32(got[2]), _f32(want[2]), rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(want[2]))))


def test_a_bf16_intermediate_would_not_pass():
    """The hold on the kernels is tighter than the three ops' own rounding:
    the plain form with its normed X rounded to bf16 on the way (what
    `rms_norm` then `elementwise_mul` do) fails it."""
    x, z, w, _ = _inputs(64, 4, 128, "bfloat16", seed=6)
    want = lm_ops.gated_rms_norm(x, z, w, EPS)
    normed = lm_ops.rms_norm_op(None, {"X": [x], "Scale": [w]},
                                {"epsilon": EPS})["Y"][0]     # bf16
    zf = z.astype(jnp.float32).reshape(x.shape)
    rounded_twice = (normed.astype(jnp.float32)
                     * (zf * jax.nn.sigmoid(zf))).astype(jnp.bfloat16)
    with pytest.raises(AssertionError):
        _held_to_the_plain_form(rounded_twice, want, "bfloat16")


def test_the_kernels_take_whole_lane_tiles_that_fit_vmem():
    assert kernels.fits((8192, 32, 128), "bfloat16")
    assert kernels.fits((8192, 32, 128), "float32")
    assert kernels.fits((48, 2, 256), "bfloat16")
    assert kernels._block(8192, 4096, 2) == 128      # the delta rule's chunk
    assert kernels._block(80, 256, 2) == 16 and kernels._block(96, 256, 2) == 32
    assert not kernels.fits((8192, 4, 16), "bfloat16")     # no lane tile
    assert not kernels.fits((8192, 32, 64), "bfloat16")    # half a lane tile
    assert not kernels.fits((40, 2, 128), "bfloat16")      # half a sublane tile
    assert not kernels.fits((8192, 32, 128), "float16")
    assert not kernels.fits((8192, 4096), "bfloat16")      # no heads
    # 2048 heads of 128 in float32: not even 16 tokens' five arrays fit
    assert not kernels.fits((8192, 2048, 128), "float32")


def _spies(monkeypatch):
    calls = []
    for mod, name in ((kernels, "gated_norm_fwd"), (kernels, "gated_norm_bwd"),
                      (lm_ops, "gated_rms_norm"),
                      (lm_ops, "gated_rms_norm_grad")):
        real = getattr(mod, name)

        def spy(*a, real=real, name=name, **kw):
            calls.append(name)
            return real(*a, **kw)

        monkeypatch.setattr(mod, name, spy)
    return calls


def test_the_op_takes_the_kernels_on_a_tpu_place_and_not_elsewhere(
        monkeypatch):
    """Forward op and grad op decide alike: the kernels (here interpreted)
    under a TPU place at a shape `fits` holds for, with the plain form's
    numbers; the plain form on the CPU, and on a TPU place with the op's
    inner precision turned down (a study's variant)."""
    calls = _spies(monkeypatch)
    x, z, w, g = _inputs(32, 2, 128, "float32", seed=7)
    ins = {"X": [x], "Gate": [z], "Scale": [w]}
    attrs = {"epsilon": EPS}
    want = lm_ops.gated_rms_norm_grad(x, z, w, g, EPS)
    for tpu, names in ((True, ["gated_norm_fwd", "gated_norm_bwd"]),
                       (False, ["gated_rms_norm", "gated_rms_norm_grad"])):
        del calls[:]
        monkeypatch.setattr(lm_ops, "on_tpu", lambda tpu=tpu: tpu)
        y = lm_ops.gated_rms_norm_op(None, ins, attrs)["Y"][0]
        grads = lm_ops.gated_rms_norm_grad_op(
            None, dict(ins, **{"Y@GRAD": [g]}), attrs)
        assert calls == names
        _held_to_the_plain_form(y, _three_ops(x, z, w), "float32")
        for slot, ref in zip(("X@GRAD", "Gate@GRAD"), want):
            _held_to_the_plain_form(grads[slot][0], ref, "float32")
        np.testing.assert_allclose(_f32(grads["Scale@GRAD"][0]),
                                   _f32(want[2]), rtol=1e-5, atol=1e-5)
    del calls[:]
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(lm_ops, "F32", jnp.bfloat16)
    lm_ops.gated_rms_norm_op(None, ins, attrs)
    assert calls == ["gated_rms_norm"]


@pytest.mark.parametrize("refused", ["head_of_16", "ragged_tokens",
                                     "float16", "gate_in_float32"])
def test_a_shape_the_kernels_refuse_runs_the_plain_form(monkeypatch,
                                                        refused):
    """On a TPU place the op hands itself to the kernels only where `fits`
    holds and X and the gate are of one dtype; every other call gives the
    plain form's results bit for bit."""
    T, D, dtype = {"head_of_16": (32, 16, "float32"),
                   "ragged_tokens": (40, 128, "float32"),
                   "float16": (32, 128, "float16"),
                   "gate_in_float32": (32, 128, "bfloat16")}[refused]
    x, z, w, g = _inputs(T, 2, D, dtype, seed=8)
    if refused == "gate_in_float32":
        z = z.astype(jnp.float32)
    else:
        assert not kernels.fits(x.shape, dtype)
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(kernels, "gated_norm_fwd", None)   # a call would raise
    monkeypatch.setattr(kernels, "gated_norm_bwd", None)
    ins = {"X": [x], "Gate": [z], "Scale": [w]}
    y = lm_ops.gated_rms_norm_op(None, ins, {"epsilon": EPS})["Y"][0]
    grads = lm_ops.gated_rms_norm_grad_op(
        None, dict(ins, **{"Y@GRAD": [g]}), {"epsilon": EPS})
    np.testing.assert_array_equal(
        _f32(y), _f32(lm_ops.gated_rms_norm(x, z, w, EPS)))
    want = lm_ops.gated_rms_norm_grad(x, z, w, g, EPS)
    for slot, ref in zip(("X@GRAD", "Gate@GRAD", "Scale@GRAD"), want):
        assert grads[slot][0].dtype == ref.dtype
        np.testing.assert_array_equal(_f32(grads[slot][0]), _f32(ref))
