"""`short_conv`: the operator of a gated short-convolution layer, Out = C *
causal_depthwise_conv_L(B * z) on channels-last rows, against the plain
form (three shifted slices in float32) written out here; its hand-written
backward against `jax.grad` of that form; what the op is under AMP; and
the Pallas kernels of `parallel/short_conv.py`, interpreted, against the
plain form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp
from paddle_tpu.ops import lm_ops


def plain(x, w, seq_len):
    """x [T, 3C] = [B | C | z], w [L, C] -> [T, C], float32: token t of a
    row sums w[j] * v[t - (L - 1 - j)], v = B * z, zero before the row."""
    L, C = w.shape
    x = x.astype(jnp.float32).reshape(-1, seq_len, 3 * C)
    b, c, z = x[..., :C], x[..., C:2 * C], x[..., 2 * C:]
    v = b * z
    conv = jnp.zeros_like(v)
    for j in range(L):
        back = L - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(v[:, :back]), v[:, :seq_len - back]], axis=1) \
            if back else v
        conv = conv + w[j].astype(jnp.float32) * shifted
    return (c * conv).reshape(-1, C)


def _inputs(rows, S, C, L, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k[0], (rows * S, 3 * C), jnp.float32).astype(dtype)
    w = jax.random.normal(k[1], (L, C), jnp.float32)
    g = jax.random.normal(k[2], (rows * S, C), jnp.float32).astype(dtype)
    return x, w, g


CASES = [(1, 16, 3), (2, 16, 3), (2, 13, 3), (1, 16, 4), (3, 7, 2),
         (2, 5, 1)]
IDS = ["one_row", "two_rows", "odd_length", "four_taps", "two_taps",
       "one_tap"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,S,L", CASES, ids=IDS)
def test_the_op_is_the_shifted_slices(rows, S, L, dtype):
    x, w, _ = _inputs(rows, S, 8, L, dtype)
    got = lm_ops.short_conv(x, w, S)
    want = plain(x, w, S)
    assert got.dtype == x.dtype and got.shape == (rows * S, 8)
    # float32 inside whatever X's dtype: one rounding on the output
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want.astype(x.dtype),
                                                np.float32),
        rtol=1e-6 if dtype == "float32" else 0, atol=1e-6
        if dtype == "float32" else 0)


def test_a_row_s_first_tokens_see_zeros_not_the_row_before():
    """Rows of a batch are separate sequences: the second row's output is
    what it would be alone."""
    x, w, _ = _inputs(2, 9, 4, 3, "float32", seed=3)
    both = lm_ops.short_conv(x, w, 9)
    alone = lm_ops.short_conv(x[9:], w, 9)
    np.testing.assert_array_equal(np.asarray(both[9:]), np.asarray(alone))
    # and it is NOT the convolution of the 18 tokens as one sequence
    joined = lm_ops.short_conv(x, w, 18)
    assert not np.allclose(np.asarray(joined[9:11]), np.asarray(both[9:11]))
    np.testing.assert_allclose(np.asarray(joined[11:]),
                               np.asarray(both[11:]), rtol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,S,L", CASES, ids=IDS)
def test_the_backward_is_the_plain_form_s_gradient(rows, S, L, dtype):
    x, w, g = _inputs(rows, S, 8, L, dtype, seed=1)
    d_x, d_w = lm_ops.short_conv_grad(x, w, g, S)
    want_x, want_w = jax.grad(
        lambda x_, w_: jnp.sum(plain(x_, w_, S) * g.astype(jnp.float32)),
        argnums=(0, 1))(x.astype(jnp.float32), w)
    assert d_x.dtype == x.dtype and d_x.shape == x.shape
    assert d_w.dtype == jnp.float32 and d_w.shape == w.shape
    tol = 1e-5 if dtype == "float32" else 0.02
    np.testing.assert_allclose(np.asarray(d_x, np.float32),
                               np.asarray(want_x), rtol=tol, atol=tol)
    # the reduction over the tokens is float32 whatever X's dtype
    np.testing.assert_allclose(np.asarray(d_w), np.asarray(want_w),
                               rtol=1e-5, atol=1e-5)


def _program(rows, S, C, L, grad=True):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[3 * C], dtype="float32")
        x.stop_gradient = False
        y = fluid.layers.short_conv(
            x, S, kernel_size=L, param_attr=fluid.ParamAttr(
                name="taps", initializer=fluid.initializer.Normal(0., 1.)))
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(y, y))
        if grad:
            fluid.backward.append_backward(loss)
    return prog, startup, y, loss


@pytest.mark.parametrize("L", [3, 4])
def test_the_program_s_gradients_are_the_plain_form_s(L):
    """Through the Executor: `append_backward` appends `short_conv_grad`
    (the hand-written maker), which gives X's and the taps' gradients."""
    rows, S, C = 2, 11, 8
    prog, startup, y, loss = _program(rows, S, C, L)
    assert [o.type for o in prog.global_block().ops].count(
        "short_conv_grad") == 1
    x = np.random.RandomState(0).randn(rows * S, 3 * C).astype("float32")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w = np.array(scope.find_var("taps"))
        out, d_x, d_w = exe.run(prog, feed={"x": x},
                                fetch_list=[y, "x@GRAD", "taps@GRAD"])
    np.testing.assert_allclose(out, plain(jnp.asarray(x), jnp.asarray(w), S),
                               rtol=1e-5, atol=1e-6)
    want = jax.grad(lambda x_, w_: jnp.mean(plain(x_, w_, S) ** 2),
                    argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(d_x, want[0], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(d_w, want[1], rtol=1e-4, atol=1e-6)


def test_under_amp_the_taps_stay_float32_and_their_sum_is_float32():
    """On AMP's white list with `Filter` a float32 slot: X arrives in
    bf16, the taps as the float32 master; the output is bf16 and is the
    float32 form rounded ONCE (a bf16 sum of three products differs)."""
    assert "short_conv" in amp.WHITE_LIST
    assert amp.FLOAT32_SLOTS["short_conv"] == frozenset({"Filter", "Bias"})
    x, w, _ = _inputs(2, 16, 8, 3, "float32", seed=5)
    amp.enable("bfloat16")
    try:
        ins = amp.apply_policy("short_conv", {"X": [x], "Filter": [w]})
        grad_ins = amp.apply_policy(
            "short_conv_grad", {"X": [x], "Filter": [w], "Out@GRAD": [x]})
    finally:
        amp.disable()
    assert ins["X"][0].dtype == jnp.bfloat16
    assert ins["Filter"][0].dtype == jnp.float32
    assert grad_ins["Filter"][0].dtype == jnp.float32
    got = lm_ops.short_conv(ins["X"][0], ins["Filter"][0], 16)
    want = plain(ins["X"][0], w, 16).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # the same sum with every product and partial sum rounded to bf16
    was, lm_ops.F32 = lm_ops.F32, jnp.bfloat16
    try:
        low = lm_ops.short_conv(ins["X"][0], ins["Filter"][0], 16)
    finally:
        lm_ops.F32 = was
    assert not np.array_equal(np.asarray(low, np.float32),
                              np.asarray(got, np.float32))


def test_shape_inference_and_the_cost_estimate():
    from paddle_tpu.trace import costs

    prog, _, y, _ = _program(2, 11, 8, 3, grad=False)
    assert tuple(y.shape) == (-1, 8)
    op, = (o for o in prog.global_block().ops if o.type == "short_conv")
    assert op.attrs["seq_len"] == 11
    row, = (r for r in costs.op_costs(prog, batch_size=22)
            if r["op"] == "short_conv")
    assert row["flops_est"] == 22 * 8 * (2 * 3 + 2)
    with pytest.raises(Exception):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data(name="x", shape=[25], dtype="float32")
            fluid.layers.short_conv(x, 5)


def test_lowered_counts_name_the_op_s_lowering():
    import types

    prog, _, _, _ = _program(2, 11, 8, 3)
    for place in ("cpu", "tpu"):
        got = lm_ops.lowered_counts(prog,
                                    types.SimpleNamespace(platform=place))
        assert got == {"short_conv_gated": 1,
                       "short_conv_grad_by_hand": 1}


# ------------------------------------------------------------ the kernels
# (rows, S, C, L): one block a row, blocks of 16 with halos on both sides,
# a row no larger block divides, channels worked through in two chunks,
# four taps, one tap (no halo read at all)
KERNEL_CASES = [(1, 16, 128, 3), (2, 48, 128, 3), (2, 64, 256, 3),
                (1, 512, 640, 3), (2, 32, 128, 4), (2, 32, 128, 1)]
KERNEL_IDS = ["one_block", "three_blocks_of_16", "two_rows_of_64",
              "blocks_of_256_two_chunks", "four_taps", "one_tap"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,S,C,L", KERNEL_CASES, ids=KERNEL_IDS)
def test_the_forward_kernel_is_the_plain_form(rows, S, C, L, dtype):
    from paddle_tpu.parallel import short_conv as kernels

    x, w, _ = _inputs(rows, S, C, L, dtype, seed=7)
    assert kernels.takes(rows * S, C, S, L, dtype)
    got = kernels.short_conv_fwd(x, w, S)
    want = plain(x, w, S).astype(x.dtype)
    assert got.dtype == x.dtype and got.shape == (rows * S, C)
    # bf16: the one rounding may fall on either side where the float32
    # sums differ in their last bit
    tol = 1e-5 if dtype == "float32" else 0.01
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,S,C,L", KERNEL_CASES, ids=KERNEL_IDS)
def test_the_backward_kernel_is_the_plain_form_s_gradient(rows, S, C, L,
                                                          dtype):
    from paddle_tpu.parallel import short_conv as kernels

    x, w, g = _inputs(rows, S, C, L, dtype, seed=8)
    d_x, d_w = kernels.short_conv_bwd(x, w, g, S)
    want_x, want_w = jax.grad(
        lambda x_, w_: jnp.sum(plain(x_, w_, S) * g.astype(jnp.float32)),
        argnums=(0, 1))(x.astype(jnp.float32), w)
    assert d_x.dtype == x.dtype and d_x.shape == x.shape
    assert d_w.dtype == jnp.float32 and d_w.shape == w.shape
    tol = 1e-5 if dtype == "float32" else 0.02
    np.testing.assert_allclose(np.asarray(d_x, np.float32),
                               np.asarray(want_x), rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(d_w), np.asarray(want_w), rtol=1e-4,
        atol=1e-5 * float(np.abs(np.asarray(want_w)).max()))


def test_the_kernels_take_whole_lane_tiles_and_whole_blocks():
    from paddle_tpu.parallel import short_conv as kernels

    assert kernels.takes(8192, 2048, 8192, 3, "bfloat16")
    assert kernels.takes(2 * 4096, 2048, 4096, 3, "float32")
    assert not kernels.takes(64, 64, 32, 3, "bfloat16")      # half a lane tile
    assert not kernels.takes(26, 128, 13, 3, "bfloat16")     # no block fits
    assert not kernels.takes(64, 128, 32, 18, "bfloat16")    # taps past a halo
    assert not kernels.takes(64, 128, 32, 3, "float16")


def test_a_tpu_place_hands_the_op_to_the_kernels(monkeypatch):
    """Where the trace is for a TPU place and the shapes fit, the op and
    its grad run the kernels (here interpreted) and give the plain form's
    numbers; with the op's inner precision turned down (the study's
    variant) the plain form runs."""
    from paddle_tpu.parallel import short_conv as kernels

    calls = []
    real_fwd, real_bwd = kernels.short_conv_fwd, kernels.short_conv_bwd
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(kernels, "short_conv_fwd",
                        lambda *a: calls.append("fwd") or real_fwd(*a))
    monkeypatch.setattr(kernels, "short_conv_bwd",
                        lambda *a: calls.append("bwd") or real_bwd(*a))
    x, w, g = _inputs(2, 32, 128, 3, "float32", seed=2)
    out_ = lm_ops.short_conv_op(None, {"X": [x], "Filter": [w]},
                                {"seq_len": 32})["Out"][0]
    grads = lm_ops.short_conv_grad_op(
        None, {"X": [x], "Filter": [w], "Out@GRAD": [g]}, {"seq_len": 32})
    assert calls == ["fwd", "bwd"]
    np.testing.assert_allclose(np.asarray(out_), np.asarray(plain(x, w, 32)),
                               rtol=1e-5, atol=1e-5)
    want = lm_ops.short_conv_grad(x, w, g, 32)
    np.testing.assert_allclose(np.asarray(grads["X@GRAD"][0]),
                               np.asarray(want[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["Filter@GRAD"][0]),
                               np.asarray(want[1]), rtol=1e-4, atol=1e-4)
    monkeypatch.setattr(lm_ops, "F32", jnp.bfloat16)
    lm_ops.short_conv_op(None, {"X": [x], "Filter": [w]}, {"seq_len": 32})
    assert calls == ["fwd", "bwd"]
    # shapes the kernels do not take run the plain form on any place
    monkeypatch.setattr(lm_ops, "F32", jnp.float32)
    x8, w8, _ = _inputs(2, 13, 8, 3, "float32")
    lm_ops.short_conv_op(None, {"X": [x8], "Filter": [w8]}, {"seq_len": 13})
    assert calls == ["fwd", "bwd"]


def test_lowered_counts_name_the_kernels_on_a_tpu_place():
    import types

    prog, _, _, _ = _program(2, 32, 128, 3)
    tpu = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform="tpu"))
    assert tpu == {"short_conv_gated": 1, "short_conv_grad_by_hand": 1,
                   "short_conv_kernel": 1, "short_conv_grad_kernel": 1}
    cpu = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform="cpu"))
    assert cpu == {"short_conv_gated": 1, "short_conv_grad_by_hand": 1}
