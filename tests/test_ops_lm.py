"""The four ops of a sparse-expert decoder layer (rms_norm,
rotary_embedding, causal_attention, moe_ffn) against their `jax.numpy`
formulas, values AND gradients, through the Executor and the generic vjp
of core/registry.py; how AMP classifies them; AdamW on the adam path.

Tolerances: everything here is float32 on the CPU, where XLA's dot is a
true float32 product. The op and the formula differ only in the order of
float32 sums (a grouped product adds an expert's rows in another order
than the dense masked einsum), so values and gradients agree to a few
float32 roundings of the largest term: 2e-5 relative to the largest
element. A wrong mask, weight, permutation or scale is off by orders of
magnitude more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp, backward
from paddle_tpu.core.framework import Program, program_guard

TOL = 2e-5


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, np.abs(a - b).max() / scale


def run_op(op_type, inputs, attrs, outputs, other_outputs=(), seed=0):
    """One op through the Executor: `inputs` {slot: array}, `outputs`
    {slot: shape} (float, each weighted by a seeded cotangent into the
    loss), `other_outputs` the slots that carry no gradient. Returns (values {slot: array}, gradients {slot: array} of every
    float input, cotangents {slot: array})."""
    rs = np.random.RandomState(seed)
    cot = {s: rs.randn(*shape).astype("float32")
           for s, shape in outputs.items()}
    prog = Program()
    with program_guard(prog):
        block = prog.global_block()
        for slot, arr in inputs.items():
            block.create_var(name=slot, shape=list(arr.shape),
                             dtype=str(arr.dtype), stop_gradient=False)
        for slot, shape in list(outputs.items()) + [
                (s, None) for s in other_outputs]:
            block.create_var(name=slot, shape=shape, dtype="float32"
                             if shape is not None else "int32")
        block.append_op(
            type=op_type, inputs={s: [s] for s in inputs},
            outputs={s: [s] for s in list(outputs) + list(other_outputs)},
            attrs=dict(attrs))
        terms = []
        for slot, c in cot.items():
            w = block.create_var(name=slot + "@W", shape=list(c.shape),
                                 dtype="float32")
            prod = block.create_var(name=slot + "@P", shape=list(c.shape),
                                    dtype="float32")
            block.append_op(type="elementwise_mul",
                            inputs={"X": [slot], "Y": [w.name]},
                            outputs={"Out": [prod.name]}, attrs={})
            term = block.create_var(name=slot + "@S", shape=[1],
                                    dtype="float32")
            block.append_op(type="reduce_sum", inputs={"X": [prod.name]},
                            outputs={"Out": [term.name]},
                            attrs={"reduce_all": True})
            terms.append(term.name)
        loss = block.create_var(name="loss", shape=[1], dtype="float32")
        block.append_op(type="sum", inputs={"X": terms},
                        outputs={"Out": ["loss"]}, attrs={})
        wrt = [s for s, a in inputs.items() if a.dtype.kind == "f"]
        grads = backward.calc_gradient([loss], [block.var(s) for s in wrt])
    feed = dict(inputs, **{s + "@W": c for s, c in cot.items()})
    fetch = list(outputs) + list(other_outputs) + list(grads)
    got = fluid.Executor(fluid.CPUPlace()).run(prog, feed=feed,
                                               fetch_list=fetch)
    n = len(outputs) + len(other_outputs)
    values = dict(zip(list(outputs) + list(other_outputs), got[:n]))
    return values, dict(zip(wrt, got[n:])), cot


def check(op_type, inputs, attrs, formula, other_outputs=()):
    """`formula(**inputs)` -> {slot: value} of the float outputs."""
    want = formula(**{k: jnp.asarray(v) for k, v in inputs.items()})
    got, grads, cot = run_op(op_type, inputs, attrs,
                             {s: tuple(v.shape) for s, v in want.items()},
                             other_outputs)
    for slot, v in want.items():
        close(got[slot], v)
    wrt = list(grads)

    def loss(*vals):
        out = formula(**dict(inputs, **dict(zip(wrt, vals))))
        return sum(jnp.sum(out[s] * cot[s]) for s in cot)

    ref = jax.grad(loss, argnums=tuple(range(len(wrt))))(
        *(jnp.asarray(inputs[s]) for s in wrt))
    for s, g in zip(wrt, ref):
        close(grads[s], g)
    return got


# ----------------------------------------------------------------- formulas
def rms_norm(X, Scale, eps=1e-5):
    return {"Y": X * jax.lax.rsqrt(jnp.mean(X * X, -1, keepdims=True) + eps)
            * Scale}


def rotary(X, theta=10000.0):
    S, D = X.shape[1], X.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-X[..., D // 2:], X[..., :D // 2]], -1)
    return {"Out": X * cos + half * sin}


def attention(Q, K, V):
    S, D = Q.shape[1], Q.shape[3]
    s = jnp.einsum("bqhd,bkhd->bhqk", Q, K) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    return {"Out": jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), V)}


def moe(X, Router, Gate, Up, Down, top_k):
    """Dense over all experts, masked by the top-k weights."""
    T, E = X.shape[0], Router.shape[1]
    logits = X @ Router
    lse = jax.nn.logsumexp(logits, -1)
    p = jnp.exp(logits - lse[:, None])
    _, idx = jax.lax.top_k(p, top_k)
    chosen = jax.nn.one_hot(idx, E).sum(1)
    hid = (jax.nn.silu(jnp.einsum("th,ehf->tef", X, Gate))
           * jnp.einsum("th,ehf->tef", X, Up) * (p * chosen)[:, :, None])
    share = jax.lax.stop_gradient(chosen.sum(0)) / (T * top_k)
    return {"Out": jnp.einsum("tef,efh->th", hid, Down),
            "AuxLoss": (E * jnp.sum(share * p.mean(0))).reshape(1),
            "ZLoss": jnp.mean(lse * lse).reshape(1)}


# -------------------------------------------------------------------- tests
def test_rms_norm():
    rs = np.random.RandomState(1)
    check("rms_norm", {"X": rs.randn(3, 5, 16).astype("float32"),
                       "Scale": rs.rand(16).astype("float32") + 0.5},
          {"epsilon": 1e-5}, rms_norm)


def test_rotary_embedding():
    rs = np.random.RandomState(2)
    x = rs.randn(2, 7, 3, 8).astype("float32")
    got = check("rotary_embedding", {"X": x}, {"theta": 10000.0}, rotary)
    # position 0 is not rotated; a rotation keeps every pair's length
    close(got["Out"][:, 0], x[:, 0])
    pair = lambda v: v[..., :4] ** 2 + v[..., 4:] ** 2   # noqa: E731
    close(pair(got["Out"]), pair(x))


@pytest.mark.parametrize("shape", [(2, 8, 2, 4), (1, 13, 3, 8)])
def test_causal_attention(shape):
    rs = np.random.RandomState(3)
    ins = {s: rs.randn(*shape).astype("float32") for s in ("Q", "K", "V")}
    check("causal_attention", ins, {}, attention, other_outputs=("Lse",))


def test_causal_attention_is_causal():
    """A later position's K and V move no earlier position's output."""
    rs = np.random.RandomState(4)
    ins = {s: rs.randn(1, 6, 2, 4).astype("float32") for s in ("Q", "K", "V")}
    a, _, _ = run_op("causal_attention", ins, {}, {"Out": (1, 6, 2, 4)},
                     other_outputs=("Lse",))
    ins["K"][:, 4:] += 1.0
    ins["V"][:, 4:] -= 2.0
    b, _, _ = run_op("causal_attention", ins, {}, {"Out": (1, 6, 2, 4)},
                     other_outputs=("Lse",))
    np.testing.assert_array_equal(a["Out"][:, :4], b["Out"][:, :4])
    assert np.abs(a["Out"][:, 4:] - b["Out"][:, 4:]).max() > 1e-3


def test_causal_attention_kernel_path(monkeypatch):
    """The lowering a TPU place takes (the Pallas flash kernel and its two
    backward kernels, here in interpret mode) against the plain
    composition every other place takes: same op, same contract."""
    from paddle_tpu.ops import lm_ops

    rs = np.random.RandomState(5)
    ins = {s: rs.randn(2, 16, 2, 8).astype("float32")
           for s in ("Q", "K", "V")}
    plain = run_op("causal_attention", ins, {}, {"Out": (2, 16, 2, 8)},
                   other_outputs=("Lse",))
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)
    kernel = run_op("causal_attention", ins, {}, {"Out": (2, 16, 2, 8)},
                   other_outputs=("Lse",))
    close(kernel[0]["Out"], plain[0]["Out"])
    close(kernel[0]["Lse"], plain[0]["Lse"])
    for s in ("Q", "K", "V"):
        close(kernel[1][s], plain[1][s])


def _moe_inputs(T, H, E, F, seed, router_bias=None):
    rs = np.random.RandomState(seed)
    ins = {"X": rs.randn(T, H).astype("float32"),
           "Router": (rs.randn(H, E) * 0.3).astype("float32"),
           "Gate": (rs.randn(E, H, F) * 0.3).astype("float32"),
           "Up": (rs.randn(E, H, F) * 0.3).astype("float32"),
           "Down": (rs.randn(E, F, H) * 0.3).astype("float32")}
    if router_bias is not None:
        # one feature is constant, so its router row acts as a bias
        ins["X"][:, 0] = 1.0
        ins["Router"][0] = router_bias
    return ins


def _check_moe(ins, top_k):
    import functools

    got = check("moe_ffn", ins, {"top_k": top_k},
                functools.partial(moe, top_k=top_k),
                other_outputs=("ExpertIds", "TokensPerExpert"))
    T, E = ins["X"].shape[0], ins["Router"].shape[1]
    p = jax.nn.softmax(jnp.asarray(ins["X"] @ ins["Router"]), -1)
    _, idx = jax.lax.top_k(p, top_k)
    ids = np.asarray(got["ExpertIds"])
    assert ids.shape == (T, top_k) and ids.dtype == np.int32
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(idx, 1))
    load = np.asarray(got["TokensPerExpert"])
    np.testing.assert_array_equal(load, np.bincount(ids.ravel(),
                                                    minlength=E))
    assert load.sum() == T * top_k        # every token routed, none dropped
    return load


def test_moe_ffn_even():
    _check_moe(_moe_inputs(T=24, H=8, E=4, F=6, seed=6), top_k=2)


def test_moe_ffn_odd_token_count():
    """T a multiple of nothing (no tile, no expert count divides it)."""
    _check_moe(_moe_inputs(T=37, H=8, E=5, F=6, seed=7), top_k=3)


def test_moe_ffn_skewed_routing():
    """One expert receives no token, one receives every token."""
    bias = np.array([9.0, 0.0, 0.0, 0.0, 0.0, -9.0], "float32")
    load = _check_moe(_moe_inputs(T=29, H=8, E=6, F=4, seed=8,
                                  router_bias=bias), top_k=2)
    assert load[5] == 0 and load[0] == 29


def test_moe_ffn_top1():
    _check_moe(_moe_inputs(T=16, H=4, E=3, F=4, seed=9), top_k=1)


def test_amp_classification_and_router_stays_float32(monkeypatch):
    """Under bf16 AMP the grouped products and the attention take bf16
    operands; moe_ffn's Router slot and the norms' inputs are left alone."""
    assert {"causal_attention", "moe_ffn"} <= amp.WHITE_LIST
    assert not {"rms_norm", "rotary_embedding"} & (amp.WHITE_LIST
                                                   | amp.BLACK_LIST)
    f32 = {s: [jnp.ones((2, 2), jnp.float32)]
           for s in ("X", "Router", "Gate", "AuxLoss@GRAD", "Out@GRAD")}
    with amp.auto_cast():
        got = amp.apply_policy("moe_ffn_grad", f32)
        norm = amp.apply_policy("rms_norm", {"X": f32["X"]})
    assert {s: str(v[0].dtype) for s, v in got.items()} == {
        "X": "bfloat16", "Gate": "bfloat16", "Out@GRAD": "bfloat16",
        "Router": "float32", "AuxLoss@GRAD": "float32"}
    assert norm["X"][0].dtype == jnp.float32


def test_moe_ffn_routes_in_float32_under_amp():
    """Router logits 1e-3 apart (under bf16's step at 1.0) still pick the
    larger: the discrete choice is made on float32 logits."""
    from paddle_tpu.ops import lm_ops

    x = jnp.ones((4, 2), jnp.bfloat16)
    router = jnp.asarray([[0.5, 0.5005, 0.3], [0.5, 0.5, 0.3]], jnp.float32)
    w = jnp.ones((3, 2, 2), jnp.bfloat16)
    *_, ids, load = lm_ops.moe_ffn(x, router, w, w, jnp.swapaxes(w, 1, 2), 1)
    np.testing.assert_array_equal(np.asarray(ids).ravel(), [1, 1, 1, 1])
    np.testing.assert_array_equal(np.asarray(load), [0, 4, 0])


@pytest.mark.parametrize("decay", [0.0, 0.1])
def test_adamw_is_an_attribute_of_adam(decay):
    """weight_decay on the adam op: ParamOut moves by the extra
    -lr * weight_decay * Param, the moments are untouched; 0 is plain adam."""
    rs = np.random.RandomState(10)
    p, g = rs.randn(5, 3).astype("float32"), rs.randn(5, 3).astype("float32")
    m1, m2 = np.zeros_like(p), np.zeros_like(p)
    one = lambda v: np.asarray([v], "float32")   # noqa: E731
    prog = Program()
    with program_guard(prog):
        block = prog.global_block()
        for n, v in (("p", p), ("g", g), ("m1", m1), ("m2", m2),
                     ("lr", one(0)), ("b1", one(0)), ("b2", one(0))):
            block.create_var(name=n, shape=list(v.shape), dtype="float32")
        for n in ("po", "m1o", "m2o"):
            block.create_var(name=n, shape=list(p.shape), dtype="float32")
        block.append_op(
            type="adam",
            inputs={"Param": ["p"], "Grad": ["g"], "LearningRate": ["lr"],
                    "Moment1": ["m1"], "Moment2": ["m2"], "Beta1Pow": ["b1"],
                    "Beta2Pow": ["b2"]},
            outputs={"ParamOut": ["po"], "Moment1Out": ["m1o"],
                     "Moment2Out": ["m2o"]},
            attrs={"beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
                   "weight_decay": decay})
    po, m1o, m2o = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed={"p": p, "g": g, "m1": m1, "m2": m2, "lr": one(0.01),
                    "b1": one(0.9), "b2": one(0.95)},
        fetch_list=["po", "m1o", "m2o"])
    # first step: m1 = 0.1 g, m2 = 0.05 g^2, lr_t = lr sqrt(.05) / .1
    step = 0.01 * g / (np.abs(g) + 1e-8 / np.sqrt(0.05))
    close(po, p - step - 0.01 * decay * p, tol=1e-6)
    close(m1o, 0.1 * g, tol=1e-6)
    close(m2o, 0.05 * g * g, tol=1e-6)


def test_adam_optimizer_decays_only_what_it_is_told_to():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.rms_norm(
        fluid.layers.fc(x, 4, bias_attr=False,
                        param_attr=fluid.ParamAttr(name="w")),
        param_attr=fluid.ParamAttr(name="a_norm"))
    fluid.optimizer.Adam(
        learning_rate=0.1, weight_decay=0.2,
        apply_decay_param_fun=lambda n: not n.endswith("_norm")).minimize(
            fluid.layers.mean(y))
    decay = {op.input("Param")[0]: op.attrs.get("weight_decay")
             for op in fluid.default_main_program().global_block().ops
             if op.type == "adam"}
    assert decay == {"w": 0.2, "a_norm": None}


def test_name_scope_marks_ops_and_their_gradients():
    """`fluid.name_scope` lands in `op_namescope` of the ops appended
    inside and of the gradient ops derived from them; the clip's ops are
    under `gradient_clip`, the optimizer's under `optimizer`; ops outside
    any scope carry nothing."""
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    with fluid.name_scope("outer"):
        with fluid.name_scope("inner"):
            h = fluid.layers.fc(x, 4, bias_attr=False)
    loss = fluid.layers.mean(h)
    fluid.clip.set_gradient_clip(fluid.clip.GradientClipByGlobalNorm(1.0))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scopes = {}
    for op in fluid.default_main_program().global_block().ops:
        scopes.setdefault(op.attrs.get("op_namescope"), set()).add(op.type)
    assert scopes["outer/inner"] == {"mul", "mul_grad"}
    assert "squared_l2_norm" in scopes["gradient_clip"]
    assert scopes["optimizer"] == {"sgd"}
    assert "mean" in scopes[None]


def test_scoped_ops_lower_under_their_name():
    """The lowering of an op under a name_scope carries `<scopes>/<op
    type>` in the debug locations of the lowered module, its gradient op's
    too; the text without locations is what it is with every scope off."""
    from paddle_tpu.core import executor_core

    def lowered(debug):
        prog, startup = Program(), Program()
        with fluid.unique_name.guard(), program_guard(prog, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            with fluid.name_scope("blk"):
                h = fluid.layers.fc(x, 8, bias_attr=False)
                h = fluid.layers.rms_norm(h)
            loss = fluid.layers.mean(h)
            grads = [g.name for _, g in backward.append_backward(loss)]
        gb = prog.global_block()
        const = {n: jax.ShapeDtypeStruct(tuple(v.shape), np.float32)
                 for n, v in gb.vars.items() if v.persistable}
        step = executor_core.build_step_fn(prog, [loss.name] + grads, [])
        low = jax.jit(step).lower(
            {}, const, {"x": jax.ShapeDtypeStruct((2, 8), np.float32)},
            jax.ShapeDtypeStruct((2,), np.uint32))
        return low.as_text(debug_info=debug)

    text = lowered(True)
    for scope in ("blk/mul", "blk/mul_grad", "blk/rms_norm",
                  "blk/rms_norm_grad"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    plain = lowered(False)
    assert "rms_norm" not in plain and "blk" not in plain


def test_adamw_through_the_optimizer():
    """Four steps of a small net with Adam(weight_decay): the decay is
    really applied (the weights end smaller than without), and a second
    run from the same seed reproduces the first."""

    def weights(decay):
        main, startup = Program(), Program()
        with fluid.unique_name.guard(), program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            h = fluid.layers.fc(x, 6, act="relu",
                                param_attr=fluid.ParamAttr(name="w1"))
            loss = fluid.layers.mean(fluid.layers.fc(
                h, 1, param_attr=fluid.ParamAttr(name="w2")))
            fluid.optimizer.Adam(learning_rate=0.01,
                                 weight_decay=decay).minimize(loss)
            main.random_seed = startup.random_seed = 3
        xs = np.random.RandomState(1).randn(16, 8).astype("float32")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for _ in range(4):
                exe.run(main, feed={"x": xs}, fetch_list=[loss])
            return {n: np.asarray(scope.find_var(n)) for n in ("w1", "w2")}

    plain, got, none = weights(0.5), weights(0.5), weights(0.0)
    for n in plain:
        np.testing.assert_array_equal(got[n], plain[n])
        assert np.abs(got[n] - none[n]).max() > 1e-3
