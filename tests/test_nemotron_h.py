"""`paddle_tpu.models.nemotron_h` at a small size with every published
RATIO kept (hidden 64; 8 Mamba heads of 8 in 2 groups with a state of 16, a
convolution of 4 taps with a bias; 8 query heads on 1 key/value head of 16,
no positions; top-6 of 32 routed un-gated relu^2 experts of which 8 held, a
shared expert of twice their width; the published first nine layers
MEMEM*EME; 2 x 32 tokens in chunks of 8) against the plain float32
reference of `chipbench/reference/nemotron_3_nano_30b_a3b.py`, whose scan
runs TOKEN BY TOKEN, on seeded weights read out of the scope; the chunked
op and its hand-written gradient alone against that recurrence and
`jax.grad` of it; what the model forced (a bias in the convolution's silu
variant, un-gated experts in `moe_ffn` and in the grouped kernels, a
grouped RMSNorm); and the tests that tie a chip's share to the model.

Tolerance: float32 against float32 on the CPU; the two differ in the order
of float32 sums only: 1e-5 of the largest element, as tests/test_lfm2.py
has it (the per-head scalars' gradients sum 64 tokens of exponentials:
1e-4). The first AdamW step is judged on the gradients the system itself
produced, for the reason given in tests/test_xing4.py.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SMALL = dict(
    hidden_size=64, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, num_attention_heads=8,
    num_key_value_heads=1, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, n_routed_experts=8, vocab_size=256,
    sequence_length=32, chunk_size=8,
    deployment=dict(n_routed_experts=32, first_expert=8,
                    num_hidden_layers=52))
PEAK_RATE = 3e-4     # a recipe's (the file's `assumed.optimizer`)
T, E_ALL, P = 64, 32, "nemotronh."
KINDS = ["mamba", "experts", "mamba", "experts", "mamba", "attention",
         "experts", "mamba", "experts"]
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _file():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron_3_nano_30b_a3b.json")) as f:
        return json.load(f)


def _cfg(**changes):
    cfg = dict(_file(), **dict(SMALL, **changes))
    cfg["optimizer"] = dict(cfg["optimizer"], learning_rate=PEAK_RATE)
    return cfg


def _close(got, want, tol=1e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= floor + tol * max(
        np.max(np.abs(want)), 1e-30)


def _ref():
    from chipbench.reference import nemotron_3_nano_30b_a3b as ref

    return ref


def _run_small(cfg, seed=5):
    """The system's numbers on one seeded batch: weights as drawn but the
    routers' (std 0.5: scores far enough apart that float32 sums in another
    order do not flip a choice), the convolution's taps and bias (std 0.5:
    a convolution and a bias that matter) and the matrices that write into
    the residual stream (std 0.02, not 0.02 / sqrt(52): branches that
    matter)."""
    from chipbench.configs import nemotron_3_nano_30b_a3b as builder

    built = builder.build(fluid, cfg, seed)
    ref = builder.reference
    rs = np.random.default_rng(0)
    feed = {"tokens": rs.integers(0, 256, (2, 32)).astype(np.int32),
            "labels": rs.integers(0, 256, (2, 32)).astype(np.int32)}
    params = built["prog"].global_block().all_parameters()
    names = [p.name for p in params]
    shapes = {p.name: tuple(p.shape) for p in params}
    trained = [n for n in names if ref.trained(n)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        drawn = {n: np.asarray(scope.find_var(n)) for n in names}
        for n in names:
            if n.endswith(("router", "conv_taps", "conv_bias")):
                scope.set_var(n, rs.normal(0, 0.5, shapes[n]).astype(
                    np.float32))
            elif n.endswith(("w_out", "w_o", "down")):
                scope.set_var(n, rs.normal(0, 0.02, shapes[n]).astype(
                    np.float32))
        w0 = {n: np.asarray(scope.find_var(n)) for n in names}
        branches = [v for _, u, o in built["operators"] for v in (u, o)]
        own = [v for i in sorted(built["mamba_ops"])
               for v in built["mamba_ops"][i]]
        logits, *ops = exe.run(built["test_prog"], feed=feed,
                               fetch_list=[built["logits"]] + branches + own)
        ops, own_got = ops[:len(branches)], ops[len(branches):]
        routing = [v for r in built["routing"] for v in r]
        got = exe.run(built["prog"], feed=feed,
                      fetch_list=[built["loss"]] + routing
                      + [n + "@GRAD" for n in trained])
        w1 = {n: np.asarray(scope.find_var(n)) for n in names}
    n_r = len(routing)
    return dict(
        cfg=cfg, ref=ref, builder=builder, built=built, feed=feed,
        names=names, drawn=drawn, w0=w0, w1=w1, logits=logits, loss=got[0],
        operators=list(zip(ops[::2], ops[1::2])),
        mamba_ops={i: own_got[5 * j:5 * j + 5]
                   for j, i in enumerate(sorted(built["mamba_ops"]))},
        routing=[got[1 + 3 * i:4 + 3 * i] for i in range(n_r // 3)],
        grads=dict(zip(trained, got[1 + n_r:])))


@pytest.fixture(scope="module")
def small():
    s = _run_small(_cfg())
    ref, cfg, feed = s["ref"], s["cfg"], s["feed"]
    loss, rest, grads = ref.loss_and_grads(
        cfg, {k: jnp.asarray(v) for k, v in s["w0"].items()},
        jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]))
    s["want"] = dict(loss=loss, logits=rest[0], routing=rest[1], grads=grads)
    o = cfg["optimizer"]
    delta, _ = ref.adamw_first_update(
        cfg, s["w0"], {k: jnp.asarray(v) for k, v in s["grads"].items()},
        epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    s["want"]["delta"] = delta
    return s


# ------------------------------------------------------------ the model
def test_parameters_are_the_reference_s(small):
    prog = small["built"]["prog"]
    got = {p.name: tuple(p.shape)
           for p in prog.global_block().all_parameters()}
    assert got == {k: tuple(v) for k, v in
                   small["ref"].param_shapes(small["cfg"]).items()}
    assert set(small["builder"].sampled_params(small["cfg"]).values()) \
        <= set(got)
    assert small["built"]["expert_layers"] == [1, 3, 6, 8]
    assert sorted(small["built"]["mamba_ops"]) == [0, 2, 4, 7]


@pytest.mark.parametrize("pattern,kinds", [
    ("MEMEM*EME", KINDS), (PUBLISHED, None), ("*", ["attention"]),
    ("EM", ["experts", "mamba"])])
def test_the_pattern_string_gives_the_layer_kinds(pattern, kinds):
    from paddle_tpu.models import nemotron_h

    cfg = dict(hybrid_override_pattern=pattern,
               num_hidden_layers=len(pattern))
    got = nemotron_h.layer_kinds(cfg)
    assert got == _ref().layer_kinds(cfg)
    if kinds is None:
        assert [got.count(k) for k in ("mamba", "experts", "attention")] \
            == [23, 23, 6] and got[:9] == KINDS
    else:
        assert got == kinds


@pytest.mark.parametrize("pattern,n", [("MEM", 4), ("ME-", 3), ("MXE", 3)])
def test_a_pattern_the_model_cannot_build_is_refused(pattern, n):
    from paddle_tpu.models import nemotron_h

    with pytest.raises(ValueError, match="pattern"):
        nemotron_h.layer_kinds(dict(hybrid_override_pattern=pattern,
                                    num_hidden_layers=n))


def test_the_file_s_parameter_count_is_the_program_s():
    """At the published widths (the program is only built, nothing runs):
    every trained parameter of the program, against `parameters` and the
    parts the file gives, and the issue's arithmetic."""
    from chipbench.configs import nemotron_3_nano_30b_a3b as builder

    cfg = _file()
    prog = builder.build(fluid, cfg, 1)["prog"]
    sizes = {p.name: int(np.prod(p.shape))
             for p in prog.global_block().all_parameters()
             if builder.reference.trained(p.name)}
    parts = cfg["parameters_by_part"]
    assert sum(sizes.values()) == cfg["parameters"] == 666962944 \
        == 4 * parts["expert_layer"] + 4 * parts["mamba_layer"] \
        + parts["attention_layer"] + parts["table"] + parts["head"] \
        + parts["final_norm"]

    def of(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert sizes[P + "embed"] == sizes[P + "head"] == 16384 * 2688
    assert {of(f"{P}l{i}.") for i in (0, 2, 4, 7)} == {38744896}
    assert {of(f"{P}l{i}.") for i in (1, 3, 6, 8)} == {100125312}
    assert of(P + "l5.") == parts["attention_layer"] == 23399040
    assert sizes[P + "l0.w_in"] == parts["mamba_w_in"] == 2688 * 10304
    assert sizes[P + "l0.w_out"] == parts["mamba_w_out"] == 4096 * 2688
    assert sizes[P + "l0.conv_taps"] == 4 * 6144
    assert sizes[P + "l0.conv_bias"] == 6144
    assert sizes[P + "l1.up"] == sizes[P + "l1.down"] == 8 * 2688 * 1856
    assert parts["one_expert"] == 2 * 2688 * 1856 == 9977856
    assert parts["router_shared_and_norm"] == 2688 * 128 \
        + 2 * 2688 * 3712 + 2688 == 20302464
    assert not [n for n in sizes if n.endswith(".gate")]
    # no width differs from the published config; the floors are kept
    for key, want in dict(
            hidden_size=2688, head_dim=128, num_attention_heads=32,
            num_key_value_heads=2, mamba_num_heads=64, mamba_head_dim=64,
            n_groups=8, ssm_state_size=128, conv_kernel=4, chunk_size=128,
            moe_intermediate_size=1856, intermediate_size=1856,
            moe_shared_expert_intermediate_size=3712, num_experts_per_tok=6,
            routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
            norm_topk_prob=True, use_conv_bias=True, mlp_hidden_act="relu2",
            expand=2).items():
        assert cfg[key] == want, key
    assert cfg["n_routed_experts"] == 8 and cfg["vocab_size"] == 16384
    dep = cfg["deployment"]
    assert dep["n_routed_experts"] == 128 and dep["vocab_size"] == 131072
    assert dep["hybrid_override_pattern"] == PUBLISHED
    assert PUBLISHED[:9] == cfg["hybrid_override_pattern"]
    assert dep["first_expert"] == dep["chip"] * 8 > 0
    assert dep["first_vocab_row"] == (dep["chip"] % 8) * 16384
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "hybrid_override_pattern", "vocab_size"}


def test_the_initialisers_are_the_file_s():
    """As drawn: A_log in log [1, 16), softplus(dt_bias) in [0.001, 0.1],
    D = 1, the convolution's bias and the bias of the choice 0, norm scales
    1, and the matrices that write into the residual stream narrower than
    the others by the root of the PUBLISHED depth."""
    s = _run_small(_cfg(hidden_size=256, vocab_size=512))
    w = s["drawn"]
    a = w[P + "l0.A_log"]
    assert (a >= 0).all() and (a < np.log(16)).all() and a.std() > 0
    dt = np.log1p(np.exp(w[P + "l0.dt_bias"].astype(np.float64)))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert (w[P + "l0.D"] == 1).all() and (w[P + "l0.conv_bias"] == 0).all()
    assert (w[P + "l1.e_score_correction_bias"] == 0).all()
    assert (w[P + "l0.gated_norm"] == 1).all()
    from paddle_tpu.models import nemotron_h

    narrow = nemotron_h.residual_std(s["cfg"])
    assert abs(narrow - 0.02 / np.sqrt(52)) < 1e-12
    for name, std in (("l0.w_in", 0.02), ("l0.w_out", narrow),
                      ("l5.w_q", 0.02), ("l5.w_o", narrow),
                      ("l1.up", 0.02), ("l1.down", narrow),
                      ("l1.shared_down", narrow), ("embed", 0.02)):
        assert abs(w[P + name].std() / std - 1) < 0.05, name


def test_logits_and_loss(small):
    _close(np.asarray(small["logits"]).reshape(2, 32, -1),
           small["want"]["logits"])
    _close(small["loss"], np.asarray(small["want"]["loss"]).reshape(1))


def test_routing_is_the_reference_s_and_counts_the_held_rows(small):
    first, held = 8, 8
    for (ids, load, rows), (_, top) in zip(small["routing"],
                                           small["want"]["routing"]):
        assert np.array_equal(np.sort(ids, 1), np.sort(np.asarray(top), 1))
        assert load.sum() == 6 * T
        assert int(rows[0]) == int(load[first:first + held].sum())


def test_gradients(small):
    for name, want in small["want"]["grads"].items():
        tol = 1e-4 if name.endswith(("A_log", "dt_bias")) else 2e-5
        _close(small["grads"][name], want, tol)
    assert not [n for n in small["grads"] if "e_score" in n]


def test_first_adamw_update_and_what_decays(small):
    from paddle_tpu.models import nemotron_h

    ref, cfg = small["ref"], small["cfg"]
    for name, want in small["want"]["delta"].items():
        _close(small["w1"][name] - small["w0"][name], want, 2e-3)
        assert nemotron_h.decays(name) == ref.decays(name)
    kept = [n.rsplit(".", 1)[1] for n in small["names"]
            if not ref.decays(n) and ref.trained(n)]
    assert set(kept) == {"norm", "gated_norm", "final_norm", "A_log",
                         "dt_bias", "D", "conv_bias"}
    # the bias of the choice moved by the rule alone: one step of 0.01
    speed = cfg["optimizer"]["router_bias_update_speed"]
    for (ids, load, _), i in zip(small["routing"], (1, 3, 6, 8)):
        name = f"{P}l{i}.e_score_correction_bias"
        _close(small["w1"][name],
               speed * np.sign(load.mean() - load.astype(np.float64)), 1e-6)


def test_branches_and_ops_first_hand(small):
    """Every layer's branch on the system's own normed input, and the two
    ops of every mixer on the ops' own inputs, against the reference."""
    ref, cfg, w = small["ref"], small["cfg"], small["w0"]
    wj = {k: jnp.asarray(v) for k, v in w.items()}
    for i, (u, o) in enumerate(small["operators"]):
        want = ref.layer_branch(cfg, wj, i, jnp.asarray(u).reshape(2, 32, -1))
        _close(o, np.asarray(want).reshape(T, -1), 2e-5)
    for i, (x, mixed, dt, y, last) in small["mamba_ops"].items():
        p = f"{P}l{i}."
        with jax.default_matmul_precision("highest"):
            conv = ref.silu_conv(jnp.asarray(x).reshape(2, 32, -1),
                                 wj[p + "conv_taps"], wj[p + "conv_bias"])
            y_ref, last_ref = ref.ssm_scan(*ref.scan_inputs(
                jnp.asarray(mixed).reshape(2, 32, -1),
                jnp.asarray(dt).reshape(2, 32, -1), wj, p, cfg))
        _close(mixed, np.asarray(conv).reshape(T, -1))
        _close(y, np.asarray(y_ref).reshape(T, -1))
        _close(last, last_ref)


def test_the_program_s_scopes_and_counters(small):
    from paddle_tpu.ops.lm_ops import lowered_counts

    prog = small["built"]["prog"]
    scopes = {str(op.attrs.get("op_namescope", "")).strip("/")
              for op in prog.global_block().ops}
    assert {"embed", "mamba/norm", "mamba/in_proj", "mamba/conv",
            "mamba/scan", "mamba/gated_norm", "mamba/out_proj", "attn",
            "attn/norm", "moe", "moe/norm", "moe/shared", "lm_head"} <= scopes
    counts = lowered_counts(prog, jax.devices()[0])
    assert counts["ssd_scan_chunked"] == counts["ssd_scan_grad_by_hand"] == 4
    # a mixer's chunks a ROW: the program leaves the rows of a batch open
    assert counts["ssd_scan_chunks"] == 4 * (32 // 8)
    assert counts["short_conv_silu_bias"] == 4
    assert counts["moe_ffn_relu2"] == counts["moe_ffn_held_experts"] == 4
    assert "moe_ffn_relu" not in counts
    types = [op.type for op in prog.global_block().ops]
    assert types.count("ssd_scan_grad") == types.count("short_conv_grad") == 4
    assert "rotary_embedding" not in types


# ------------------------------------------------------------- the scan op
def _scan_case(seq_len, rows=2, heads=4, head_dim=8, groups=2, state=16,
               seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = rows * seq_len
    args = (jax.random.normal(k[0], (n, heads * head_dim)),
            jax.random.normal(k[1], (n, groups * state)),
            jax.random.normal(k[2], (n, groups * state)),
            jax.random.normal(k[3], (n, heads)),
            jnp.log(jax.random.uniform(k[4], (heads,), minval=1, maxval=16)),
            jax.random.normal(k[5], (heads,)) - 2.0,
            jax.random.normal(k[6], (heads,)) + 1.0)
    shape = dict(seq_len=seq_len, heads=heads, head_dim=head_dim,
                 groups=groups, state=state)
    return args, shape, jax.random.normal(k[7], (n, heads * head_dim))


def _recurrence(args, shape):
    """The reference's token-by-token scan on the op's own inputs."""
    ref = _ref()
    x, b, c, dt, a_log, dt_bias, d = args
    S, H, G = shape["seq_len"], shape["heads"], shape["groups"]
    rows = x.shape[0] // S
    b, c = (jnp.repeat(t.reshape(rows, S, G, -1), H // G, axis=2)
            for t in (b, c))
    y, last = ref.ssm_scan(
        x.reshape(rows, S, H, -1), b, c,
        jax.nn.softplus(dt.reshape(rows, S, H) + dt_bias), -jnp.exp(a_log),
        d)
    return y.reshape(x.shape), last


SCAN_CASES = [(24, 8), (24, 12), (20, 8), (16, 16)]


@pytest.mark.parametrize("seq_len,chunk", SCAN_CASES)
def test_the_scan_is_the_recurrence(seq_len, chunk):
    """Output and final state, whole chunks, one chunk a row, and a row
    that is NOT whole chunks (20 tokens in chunks of 8)."""
    from paddle_tpu.parallel import ssd

    args, shape, _ = _scan_case(seq_len)
    with jax.default_matmul_precision("highest"):
        y, starts, last = ssd.ssd_fwd(*args, chunk=chunk, **shape)
        y_ref, last_ref = _recurrence(args, shape)
    _close(y, y_ref)
    _close(last, last_ref)
    assert starts.shape == ssd.states_shape(
        2, seq_len, shape["heads"], shape["head_dim"], shape["groups"],
        shape["state"], chunk)
    assert not np.asarray(starts[0]).any()      # a row starts from zero


@pytest.mark.parametrize("seq_len,chunk", SCAN_CASES)
def test_the_scan_s_gradient_by_hand_is_the_recurrence_s(seq_len, chunk):
    """d x, d B, d C, d dt, d A_log, d dt_bias, d D of the hand-written
    backward against `jax.grad` of the token-by-token recurrence."""
    from paddle_tpu.parallel import ssd

    args, shape, dy = _scan_case(seq_len, seed=1)
    with jax.default_matmul_precision("highest"):
        _, starts, _ = ssd.ssd_fwd(*args, chunk=chunk, **shape)
        got = ssd.ssd_bwd(*args, starts, dy, chunk=chunk, **shape)
        want = jax.grad(lambda *a: jnp.sum(_recurrence(a, shape)[0] * dy),
                        argnums=tuple(range(7)))(*args)
    for name, g, w in zip("x B C dt A_log dt_bias D".split(), got, want):
        _close(g, w, 2e-5), name


def test_the_scan_op_in_a_program_and_its_grad_op():
    """`layers.ssd_scan` through the Executor: the op's outputs and the
    gradients its grad op writes, the three per-head parameters among
    them."""
    args, shape, _ = _scan_case(16, rows=2)
    x, b, c, dt, a_log, dt_bias, d = (np.asarray(a) for a in args)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        L = fluid.layers
        ins = [L.data(name=n, shape=[v.shape[1]], dtype="float32")
               for n, v in zip("xbct", (x, b, c, dt))]
        for v in ins:
            v.stop_gradient = False
        y, last = L.ssd_scan(
            *ins, 16, 4, 8, 2, 16, chunk=8,
            a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"),
            d_attr=fluid.ParamAttr(name="D"))
        loss = L.reduce_sum(L.square(y))
        fluid.backward.append_backward(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        assert (np.asarray(scope.find_var("D")) == 1).all()
        for n, v in (("A_log", a_log), ("dt_bias", dt_bias), ("D", d)):
            scope.set_var(n, v)
        names = ["x", "b", "c", "t", "A_log", "dt_bias", "D"]
        got = exe.run(prog, feed=dict(zip("xbct", (x, b, c, dt))),
                      fetch_list=[y, last] + [n + "@GRAD" for n in names])
    with jax.default_matmul_precision("highest"):
        y_ref, last_ref = _recurrence(args, shape)
        want = jax.grad(
            lambda *a: jnp.sum(jnp.square(_recurrence(a, shape)[0])),
            argnums=tuple(range(7)))(*args)
    _close(got[0], y_ref)
    _close(got[1], last_ref)
    for g, w in zip(got[2:], want):
        _close(g, w, 5e-5)


def test_a_scan_of_the_wrong_shape_is_refused():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        L = fluid.layers
        x = L.data(name="x", shape=[32], dtype="float32")
        bc = L.data(name="b", shape=[32], dtype="float32")
        dt = L.data(name="t", shape=[5], dtype="float32")
        with pytest.raises(Exception, match="Dt"):
            L.ssd_scan(x, bc, bc, dt, 16, 4, 8, 2, 16)


# --------------------------------------------- the scan's kernel path
KERNEL_SHAPE = dict(heads=4, head_dim=64, groups=2, state=128)
GRADS = "x B C dt A_log dt_bias D".split()


def _kernel_case(seq_len, dtype, rows=1, seed=2):
    """`_scan_case` at the kernels' widths with steps the size a trained
    mixer has (delta ~ 0.01, so a chunk's log-decay stays within tens: the
    kernels form the running sum in another order than `jnp.cumsum`, and a
    float32 sum of 128 steps near -500 is an ulp of 3e-5 either way)."""
    args, _, dy = _scan_case(seq_len, rows=rows, seed=seed, **KERNEL_SHAPE)
    args = tuple(a.astype(dtype) for a in args[:3]) + (
        args[3], args[4], args[5] - 3.0, args[6])
    return args, dict(KERNEL_SHAPE, seq_len=seq_len), dy.astype(dtype)


def _both_passes(fwd, bwd, args, dy, **shape):
    """((y, states, final state), the seven gradients) of one path."""
    with jax.default_matmul_precision("highest"):
        res = jax.jit(lambda *a: fwd(*a, **shape))(*args)
        return res, jax.jit(lambda *a: bwd(*a, **shape))(*args, res[1], dy)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("seq_len,rows", [(256, 2), (512, 1)])
def test_the_scan_s_kernels_interpreted_are_the_plain_form(seq_len, rows,
                                                           dtype):
    """`ssd.kernels_fwd` / `kernels_bwd` (the Pallas kernels of
    `parallel/ssd_parts.py`, interpreted on the CPU) against the plain
    chunked form on the same operands: y, `States`, `FinalState` and all
    seven gradients, in chunks of 128 with 2 heads of 64 a group of state
    128. Float32: the order of float32 sums; bf16: one ulp of the largest
    element besides."""
    from paddle_tpu.parallel import ssd

    args, shape, dy = _kernel_case(seq_len, jnp.dtype(dtype), rows)
    assert ssd.takes(rows, chunk=128, dtype=args[0].dtype, **shape)
    want, want_grads = _both_passes(ssd.ssd_fwd, ssd.ssd_bwd, args, dy,
                                    chunk=128, **shape)
    got, grads = _both_passes(ssd.kernels_fwd, ssd.kernels_bwd, args, dy,
                              chunk=128, **shape)
    assert got[1].shape == ssd.states_shape(rows, chunk=128, **shape)
    # bf16: y, d x, d B and d C leave the kernel path ROUNDED to bf16 (the
    # plain form leaves the gradients float32 for the op to round), and x
    # delta w is rounded before the state's product: a float32 sum in
    # another order may land on the bf16 neighbour
    rounded = 2.0 ** -7 if dtype == "bfloat16" else 0.0
    _close(got[0], want[0], 1e-5 + rounded)
    for g, w in zip(got[1:], want[1:]):
        _close(g, w, 1e-5 + rounded / 64)
    for name, g, w in zip(GRADS, grads, want_grads):
        assert g.shape == w.shape, name
        _close(g, w.astype(g.dtype),
               1e-5 + (rounded if name in GRADS[:3] else rounded / 64)), name


def _lowers_kernels(monkeypatch, on_tpu, seq_len, chunk):
    """Whether `ssd_scan` and its grad op, traced for a place that is (not)
    a TPU, hold a Pallas call."""
    from paddle_tpu.ops import lm_ops

    monkeypatch.setattr(lm_ops, "on_tpu", lambda: on_tpu)
    attrs = dict(seq_len=seq_len, num_heads=4, head_dim=64, num_groups=2,
                 state_size=128, chunk=chunk)

    def zeros(*shape):
        return [jnp.zeros(shape, jnp.float32)]

    ins = dict(X=zeros(seq_len, 256), B=zeros(seq_len, 256),
               C=zeros(seq_len, 256), Dt=zeros(seq_len, 4), ALog=zeros(4),
               DtBias=zeros(4), D=zeros(4))
    states = jax.eval_shape(
        lambda i: lm_ops.ssd_scan_op(None, i, attrs), ins)["States"][0]
    grad_ins = dict(ins, States=zeros(*states.shape),
                    **{"Out@GRAD": zeros(seq_len, 256)})
    found = ["pallas_call" in str(jax.make_jaxpr(
        lambda i: op(None, i, attrs))(given))
        for op, given in ((lm_ops.ssd_scan_op, ins),
                          (lm_ops.ssd_scan_grad_op, grad_ins))]
    assert found[0] == found[1]
    return found[0]


@pytest.mark.parametrize("what,on_tpu,seq_len,chunk,kernels", [
    ("whole_chunks_on_a_tpu_place", True, 256, 128, True),
    ("a_ragged_row", True, 200, 128, False),
    ("a_chunk_of_64", True, 256, 64, False),
    ("the_cpu_place", False, 256, 128, False)])
def test_who_takes_the_scan_s_kernels_is_read_from_the_call(
        monkeypatch, what, on_tpu, seq_len, chunk, kernels):
    """One predicate (`ssd.takes` behind `lm_ops._ssd_kernels_take`): a TPU
    place, rows of whole chunks of 128, lane-tile widths; a ragged row, a
    chunk the kernels were not swept at and the CPU place lower the plain
    form, forward and backward alike (`States` means the same on both)."""
    assert _lowers_kernels(monkeypatch, on_tpu, seq_len, chunk) == kernels


@pytest.mark.parametrize("plant", ["state_bf16", "decays_bf16",
                                   "state_one_pass"])
def test_every_plant_of_the_study_bites_on_the_ssd_kernel_path(plant):
    """`chipbench/lower_precision_lm_ssd_share` lowers a precision by
    wrapping `ssd.carried`, `ssd.steps`, `ssd.decay` and
    `ssd.STATE_PRECISION` from outside: the kernel path looks each up as it
    is traced (`ssd.decay` INSIDE the kernel bodies, the state's products
    split into as many bf16 passes as `STATE_PRECISION` says), so y and d x
    move under every plant at least as far as the plain form's do (on the
    CPU the plain form's products ignore a pass count: there it moves
    nothing, the kernels' written-out passes do)."""
    from chipbench.lower_precision_lm_ssd_share import _planted
    from paddle_tpu.parallel import ssd

    args, shape, dy = _kernel_case(256, jnp.float32)
    paths = {"plain": (ssd.ssd_fwd, ssd.ssd_bwd),
             "kernels": (ssd.kernels_fwd, ssd.kernels_bwd)}

    def readings():
        return {k: _both_passes(*v, args, dy, chunk=128, **shape)
                for k, v in paths.items()}

    def moved(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2))

    stated = readings()
    with _planted(plant):
        planted = readings()
    by = {k: (moved(planted[k][0][0], stated[k][0][0]),
              moved(planted[k][1][0], stated[k][1][0])) for k in paths}
    for kernel, plain in zip(by["kernels"], by["plain"]):
        assert kernel > 3e-5, by
        assert kernel >= 0.9 * plain, by


# ------------------------------------------------ short_conv with a bias
def _conv_program(with_bias, x, seq_len):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        L = fluid.layers
        xin = L.data(name="x", shape=[x.shape[1]], dtype="float32")
        xin.stop_gradient = False
        y = L.short_conv(
            xin, seq_len, kernel_size=4, gating="silu",
            param_attr=fluid.ParamAttr(name="taps"),
            bias_attr=fluid.ParamAttr(name="bias") if with_bias else None)
        fluid.backward.append_backward(L.reduce_sum(L.square(y)))
    return prog, startup, y


@pytest.mark.parametrize("with_bias", [True, False],
                         ids=["bias", "as_today"])
def test_short_conv_with_a_bias_is_four_shifted_sums(with_bias):
    """Output and the gradients of X, the taps and the bias against the
    reference's four shifted sums; WITHOUT `bias_attr` the op is appended
    as it has always been: no Bias slot on it or on its grad op."""
    ref = _ref()
    rs = np.random.default_rng(2)
    x = rs.normal(0, 1, (2 * 12, 24)).astype(np.float32)
    taps = rs.normal(0, 0.5, (4, 24)).astype(np.float32)
    bias = rs.normal(0, 0.5, (24,)).astype(np.float32)
    prog, startup, y = _conv_program(with_bias, x, 12)
    ops = {op.type: op for op in prog.global_block().ops}
    assert bool(ops["short_conv"].input("Bias")) == with_bias
    assert bool(ops["short_conv_grad"].input("Bias")) == with_bias
    assert bool(ops["short_conv_grad"].output("Bias@GRAD")) == with_bias
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if with_bias:
            assert (np.asarray(scope.find_var("bias")) == 0).all()
            scope.set_var("bias", bias)
        scope.set_var("taps", taps)
        names = ["x", "taps"] + (["bias"] if with_bias else [])
        got = exe.run(prog, feed={"x": x},
                      fetch_list=[y] + [n + "@GRAD" for n in names])

    def plain(x_, taps_, bias_):
        return ref.silu_conv(x_.reshape(2, 12, 24), taps_,
                             bias_ if with_bias else None).reshape(x_.shape)

    args = tuple(jnp.asarray(a) for a in (x, taps, bias))
    _close(got[0], plain(*args))
    want = jax.grad(lambda *a: jnp.sum(jnp.square(plain(*a))),
                    argnums=(0, 1, 2))(*args)
    for g, w in zip(got[1:], want):
        _close(g, w, 2e-5)


def test_short_conv_says_what_each_variant_takes():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.layers.data(name="x", shape=[24], dtype="float32")
        with pytest.raises(ValueError, match="only the variant gating"):
            fluid.layers.short_conv(x, 12, bias_attr=fluid.ParamAttr("b"))
        y = fluid.layers.data(name="y", shape=[25], dtype="float32")
        with pytest.raises(ValueError, match=r"gating 'silu' takes \[T, C\]"):
            fluid.layers.short_conv(y, 12)


# ------------------------------------------------------- grouped rms_norm
def test_rms_norm_over_groups_is_the_reference_s():
    ref = _ref()
    rs = np.random.default_rng(3)
    x = rs.normal(0, 1, (10, 24)).astype(np.float32)
    scale = rs.normal(1, 0.3, (24,)).astype(np.float32)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        xin = fluid.layers.data(name="x", shape=[24], dtype="float32")
        y = fluid.layers.rms_norm(xin, epsilon=1e-5, group_size=8,
                                  param_attr=fluid.ParamAttr(name="s"))
        whole = fluid.layers.rms_norm(xin, epsilon=1e-5,
                                      param_attr=fluid.ParamAttr(name="s"))
        with pytest.raises(ValueError, match="group_size"):
            fluid.layers.rms_norm(xin, group_size=7)
    assert "group_size" not in prog.global_block().ops[1].attrs
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set_var("s", scale)
        got, got_whole = exe.run(prog, feed={"x": x}, fetch_list=[y, whole])
    _close(got, ref.grouped_rms_norm(jnp.asarray(x), scale, 1e-5, 3))
    _close(got_whole, ref.rms_norm(jnp.asarray(x), scale, 1e-5))
    assert np.abs(got - got_whole).max() > 1e-2


# ------------------------------------------------------- un-gated experts
def _experts_program(held, x, cfg, weights):
    from paddle_tpu.models import nemotron_h

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        xin = fluid.layers.data(name="x", shape=[64], dtype="float32")
        xin.stop_gradient = False
        c = dict(cfg, n_routed_experts=held[1], deployment=dict(
            cfg["deployment"], first_expert=held[0]))
        y, (ids, load, rows) = nemotron_h.experts(xin, c, P + "l1.")
        fluid.backward.append_backward(
            fluid.layers.reduce_sum(fluid.layers.square(y)))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = [p.name for p in prog.global_block().all_parameters()]
        for n in params:
            scope.set_var(n, np.asarray(weights[n]))
        trained = [n for n in params if "e_score" not in n]
        got = exe.run(prog, feed={"x": x}, fetch_list=[y, rows] + [
            n + "@GRAD" for n in ["x"] + trained])
    return prog, got, trained


@pytest.mark.parametrize("held", [(0, 32), (8, 8), (30, 2)],
                         ids=["all_held", "a_share", "two_held"])
def test_relu2_experts_are_a_loop_over_experts(held):
    """`moe_ffn(activation="relu2")` (no Gate parameter, no GateOut) with
    the shared expert beside it: output, rows held and every gradient
    against the reference's loop over the held experts."""
    ref, cfg = _ref(), _cfg()
    rs = np.random.default_rng(4)
    p = P + "l1."
    c = dict(cfg, n_routed_experts=held[1], deployment=dict(
        cfg["deployment"], first_expert=held[0]))
    shapes = {k: v for k, v in ref.param_shapes(
        dict(c, num_hidden_layers=2, hybrid_override_pattern="ME")).items()
        if k.startswith(p) and not k.endswith(".norm")}
    w = {n: jnp.asarray(
        np.zeros(s) if "e_score" in n else rs.normal(0, 0.3, s), jnp.float32)
        for n, s in shapes.items()}
    x = rs.normal(0, 1, (T, 64)).astype(np.float32)
    prog, got, trained = _experts_program(held, x, cfg, w)
    op = next(o for o in prog.global_block().ops if o.type == "moe_ffn")
    assert not op.input("Gate") and not op.output("GateOut")
    assert op.output("UpOut") and op.attrs["activation"] == "relu2"
    assert sorted(n.rsplit(".", 1)[1] for n in trained) == [
        "down", "router", "shared_down", "shared_up", "up"]

    def plain(x_, w_):
        return ref.experts(x_, w_, p, c)

    with jax.default_matmul_precision("highest"):
        want, (_, top) = plain(jnp.asarray(x), w)
        grads = jax.grad(lambda x_, w_: jnp.sum(jnp.square(plain(x_, w_)[0])),
                         argnums=(0, 1))(jnp.asarray(x), w)
    _close(got[0], want, 2e-5)
    top = np.asarray(top)
    assert int(got[1][0]) == int(((top >= held[0])
                                  & (top < sum(held))).sum())
    _close(got[2], grads[0], 5e-5)
    for n, g in zip(trained, got[3:]):
        _close(g, grads[1][n], 5e-5)


def test_relu2_takes_no_gate_and_an_unknown_activation_is_refused():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        with pytest.raises(ValueError, match="takes no gate_attr"):
            fluid.layers.moe_ffn(x, 8, 32, 2, activation="relu2",
                                 gate_attr=fluid.ParamAttr(name="g"))
        with pytest.raises(ValueError, match="none of"):
            fluid.layers.moe_ffn(x, 8, 32, 2, activation="gelu")


@pytest.mark.parametrize("which", ["forward", "gradients"])
@pytest.mark.parametrize("width", [256, 192], ids=["lane_tiles", "whole_192"])
def test_relu_sq_kernels_interpreted_match_the_composition(width, which,
                                                           monkeypatch):
    """The six kernels of an un-gated layer (`grouped._mlp_sq`: the
    `relu_sq` / `relu_sq_grad` epilogues and relu(a)^2 formed again in
    front of d down), interpreted, against three plain `ragged_dot`s; also
    at an expert width that is NO multiple of a lane tile (192 = 64 x 3, as
    the cell's 1856 = 64 x 29), worked as one whole tile; a width that is
    no whole half lane tiles (200, 1000), or within one lane tile (64),
    stays `lax.ragged_dot`'s."""
    from paddle_tpu.parallel import grouped

    monkeypatch.setattr(grouped, "ROW_TILES", (32, 16, 8))
    monkeypatch.setattr(grouped, "_BLOCK_ROWS", 8)
    rs = np.random.default_rng(5)
    N, K, E = 64, 128, 4
    counts = jnp.asarray([13, 0, 30, 9], jnp.int32)      # 52 of 64 rows
    xs = jnp.asarray(rs.normal(0, 1, (N, K)), jnp.float32)
    up = jnp.asarray(rs.normal(0, 0.1, (E, K, width)), jnp.float32)
    down = jnp.asarray(rs.normal(0, 0.1, (E, width, K)), jnp.float32)
    assert grouped.mlp_takes(N, K, width)
    assert not any(grouped.takes(N, K, w) for w in (200, 1000, 64, 2112))
    tiles = (grouped.tiles_for(N, K, width, xs.dtype),
             grouped.tiles_for(N, width, K, xs.dtype))
    assert tiles[0][1] == (K, width)

    def kernels(xs, up, down):
        return grouped._mlp_sq(xs, up, down, counts, None, tiles, True)[0]

    def plain(xs, up, down):
        a = jax.lax.ragged_dot(xs, up, counts)
        return jax.lax.ragged_dot(jnp.square(jnp.maximum(a, 0)), down,
                                  counts)

    with jax.default_matmul_precision("highest"):
        if which == "forward":
            ys, a = grouped._mlp_sq(xs, up, down, counts, None, tiles, True)
            _close(ys, plain(xs, up, down), 2e-5)
            _close(a[:52], jax.lax.ragged_dot(xs, up, counts)[:52], 2e-5)
            assert not np.asarray(ys[52:]).any()
        else:
            g = jnp.asarray(rs.normal(0, 1, (N, K)), jnp.float32)
            got = jax.vjp(kernels, xs, up, down)[1](g)
            want = jax.vjp(plain, xs, up, down)[1](g)
            for a, b in zip(got, want):
                _close(a, b, 5e-5)


# ----------------------------------------------------------- the share
CHIPS = 16


def _uncut():
    """An uncut tiny expert layer: 32 experts all held, seeded weights; u a
    normed state."""
    ref = _ref()
    cfg = _cfg(n_routed_experts=32, num_hidden_layers=2,
               hybrid_override_pattern="ME",
               deployment=dict(n_routed_experts=32, first_expert=0,
                               num_hidden_layers=52))
    rs = np.random.default_rng(11)
    w = {n: jnp.asarray(np.zeros(s) if "e_score" in n
                        else rs.normal(0, 0.3, s), jnp.float32)
         for n, s in ref.param_shapes(cfg).items() if n.startswith(P + "l1.")}
    u = rs.normal(0, 1, (T, 64)).astype(np.float32)
    return cfg, ref, w, u


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The guide's share test: each of the 16 chips computes the shared
    expert whole and its 2 of the 32 experts' part; the routed parts summed
    over the chips plus the shared expert ONCE are the uncut reference's E
    layer; every choice lands on exactly one chip."""
    cfg, ref, w, u = _uncut()
    p = P + "l1."
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.experts(jnp.asarray(u), w, p, cfg)
        shared = np.asarray(ref.shared_expert(jnp.asarray(u), w, p))
    routed, rows = np.zeros_like(shared), 0
    for chip in range(CHIPS):
        c, ws = ref.share_of(cfg, w, chip, CHIPS)
        assert c["n_routed_experts"] == 2
        assert c["deployment"]["first_expert"] == 2 * chip
        _, got, _ = _experts_program((2 * chip, 2), u, cfg, ws)
        with jax.default_matmul_precision("highest"):
            want, _ = ref.experts(jnp.asarray(u), ws, p, c)
        _close(got[0], want, 2e-5)
        routed += got[0] - shared
        rows += int(got[1][0])
    assert rows == 6 * T
    _close(routed + shared, whole, 2e-5)
    assert np.abs(routed).max() > 1e-2 * np.abs(np.asarray(whole)).max()


def test_the_vocabulary_share_is_a_slice():
    """Chip 11 of 16 is chip 3 of the 8 that share the vocabulary: its
    table is rows 96..127 of an uncut table of 256, its head those
    columns."""
    ref = _ref()
    cfg = _cfg(n_routed_experts=32, deployment=dict(
        n_routed_experts=32, first_expert=0, num_hidden_layers=52))
    rs = np.random.default_rng(12)
    w = {n: jnp.asarray(rs.normal(0, 0.1, s), jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    c, ws = ref.share_of(cfg, w, 11, 16, vocab_chips=8)
    assert c["vocab_size"] == 32 and c["n_routed_experts"] == 2
    assert c["deployment"]["first_expert"] == 22
    np.testing.assert_array_equal(np.asarray(ws[P + "embed"]),
                                  np.asarray(w[P + "embed"][96:128]))
    np.testing.assert_array_equal(np.asarray(ws[P + "head"]),
                                  np.asarray(w[P + "head"][:, 96:128]))
    np.testing.assert_array_equal(np.asarray(ws[P + "l1.up"]),
                                  np.asarray(w[P + "l1.up"][22:24]))
    assert ws[P + "l0.w_in"] is w[P + "l0.w_in"]      # the mixers whole


def test_the_reference_is_independent_of_the_system():
    path = os.path.join(REPO, "chipbench", "reference",
                        "nemotron_3_nano_30b_a3b.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert 'PRECISION = "highest"' in text
    # the scan is a loop over tokens with the two lines of the recurrence
    assert "def token(h, t):" in text and "jnp.exp(d_t * a)" in text
    assert "chunk" not in text.split("def ssm_scan")[1].split("def ")[0] \
        .replace("STATE_BLOCK", "")
