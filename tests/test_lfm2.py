"""`paddle_tpu.models.lfm2` at a small size with every published RATIO kept
(hidden 64, 8 query heads on 2 key/value heads of 8: 4 : 1; top-4 of 32
routed experts of which 8 held; a convolution of L = 3; the two layer
kinds; a dense layer, then sparse ones: published layers 0, 2, 3, 4, 5 =
conv + dense, attention, conv, conv, conv; 2 x 32 tokens) against the plain
float32 reference of `chipbench/reference/lfm2_8b_a1b.py`, on seeded
weights read out of the scope; what the model forced (a gated short
convolution, per-head QK norm, a tied head, `norm_eps` in the router's
renormalisation); and the tests that tie a chip's share to the model: the
parts all four shares of the experts give, with what every chip computes
alike (the operator, the dense MLP) counted once, add up to the uncut
reference's layer, and the vocabulary share is a slice.

Tolerance: float32 against float32 on the CPU; the two differ in the order
of float32 sums only: 1e-5 of the largest element, as tests/test_laguna.py
has it. The first AdamW step is judged on the gradients the system itself
produced, for the reason given in tests/test_xing4.py.
"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SMALL = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    num_experts=8, vocab_size=256, sequence_length=32,
    deployment=dict(num_experts=32, first_expert=8,
                    layers_held=[0, 2, 3, 4, 5]))
PEAK_RATE = 3e-4     # a recipe's (the file's `assumed.optimizer`)
SPEED = 0.01
T, E_ALL, P = 64, 32, "lfm2."
KINDS = ["conv", "full_attention", "conv", "conv", "conv"]
SPARSE = [1, 2, 3, 4]       # program layers with experts


def _file():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "lfm2_8b_a1b.json")) as f:
        return json.load(f)


def _cfg(**changes):
    """The configuration file at the small sizes, at a recipe's peak
    learning rate (the cell's 1e-6 makes a step smaller than half an ulp
    of a norm scale: nothing an update could be judged by)."""
    cfg = dict(_file(), **dict(SMALL, **changes))
    cfg["optimizer"] = dict(cfg["optimizer"], learning_rate=PEAK_RATE,
                            router_bias_update_speed=SPEED)
    return cfg


def _close(got, want, tol=1e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= floor + tol * max(
        np.max(np.abs(want)), 1e-30)


def _build(cfg, seed=5, untied=False):
    from chipbench.configs import lfm2_8b_a1b as builder

    if not untied:
        return builder, builder.build(fluid, cfg, seed)
    # the same program with a head of its own [V, C], drawn as the table is
    from paddle_tpu.models import lfm2
    real = fluid.layers.matmul

    def head(h, table, transpose_y=False, **kw):
        assert transpose_y and table.name == P + "embed"
        own = fluid.layers.create_parameter(
            shape=list(table.shape), dtype="float32", name=P + "head",
            default_initializer=fluid.initializer.Normal(0.0, lfm2.INIT_STD))
        return real(h, own, transpose_y=True)

    fluid.layers.matmul = head
    try:
        return builder, builder.build(fluid, cfg, seed)
    finally:
        fluid.layers.matmul = real


def _run_small(cfg, seed=5, untied=False):
    """The system's numbers on one seeded batch: weights as drawn but the
    routers' (std 0.5: scores far enough apart that float32 sums in another
    order do not flip a choice), the experts' biases (set non-zero: choosing
    by score + bias and weighing by the scores then differ) and the conv's
    taps (std 0.5: a convolution that matters), logits, loss, routing, the
    operator branches, every gradient, the weights after one step."""
    builder, built = _build(cfg, seed, untied)
    ref = builder.reference
    rs = np.random.default_rng(0)
    feed = {"tokens": rs.integers(0, 256, (2, 32)).astype(np.int32),
            "labels": rs.integers(0, 256, (2, 32)).astype(np.int32)}
    names = [p.name for p in built["prog"].global_block().all_parameters()]
    trained = [n for n in names if ref.trained(n)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        for n in names:
            if n.endswith("expert_bias"):
                scope.set_var(n, rs.normal(0, 0.2, E_ALL).astype(np.float32))
            elif n.endswith("router"):
                scope.set_var(n, rs.normal(0, 0.5, (64, E_ALL)).astype(
                    np.float32))
            elif n.endswith("conv_taps"):
                scope.set_var(n, rs.normal(0, 0.5, (3, 64)).astype(
                    np.float32))
            elif n == P + "head":
                scope.set_var(n, np.asarray(scope.find_var(P + "embed")))
        w0 = {n: np.asarray(scope.find_var(n)) for n in names}
        branches = [v for _, u, o in built["operators"] for v in (u, o)]
        convs = [v for i in sorted(built["short_convs"])
                 for v in built["short_convs"][i]]
        logits, *ops = exe.run(built["test_prog"], feed=feed,
                               fetch_list=[built["logits"]] + branches
                               + convs)
        ops, conv_ops = ops[:len(branches)], ops[len(branches):]
        routing = [v for r in built["routing"] for v in r]
        got = exe.run(built["prog"], feed=feed,
                      fetch_list=[built["loss"]] + routing
                      + [n + "@GRAD" for n in trained])
        w1 = {n: np.asarray(scope.find_var(n)) for n in names}
    n_r = len(routing)
    return dict(
        cfg=cfg, ref=ref, builder=builder, built=built, feed=feed,
        names=names, w0=w0, w1=w1, logits=logits, loss=got[0],
        operators=list(zip(ops[::2], ops[1::2])),
        conv_ops=dict(zip(sorted(built["short_convs"]),
                          zip(conv_ops[::2], conv_ops[1::2]))),
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


def test_parameters_are_the_reference_s(small):
    prog = small["built"]["prog"]
    got = {p.name: tuple(p.shape)
           for p in prog.global_block().all_parameters()}
    assert got == {k: tuple(v) for k, v in
                   small["ref"].param_shapes(small["cfg"]).items()}
    picks = small["builder"].sampled_params(small["cfg"])
    assert set(picks.values()) <= set(got)
    # ONE table: no parameter is a head
    assert not any("head" in n for n in got)
    from paddle_tpu.models import lfm2
    assert lfm2.layer_kinds(small["cfg"]) == KINDS \
        == small["ref"].layer_kinds(small["cfg"])
    assert small["builder"].first_hand_layers(small["cfg"]) == dict(
        conv_dense=0, attention=1, conv_sparse=2)


def test_the_file_s_parameter_count_is_the_program_s():
    """At the published widths (the program is only built, nothing runs):
    every trained parameter of the program, against `parameters` and the
    parts the file gives, and the issue's arithmetic."""
    from chipbench.configs import lfm2_8b_a1b as builder

    cfg = _file()
    prog = builder.build(fluid, cfg, 1)["prog"]
    sizes = {p.name: int(np.prod(p.shape))
             for p in prog.global_block().all_parameters()
             if builder.reference.trained(p.name)}
    assert sum(sizes.values()) == cfg["parameters"] == 507820160
    parts = cfg["parameters_by_part"]

    def of(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert sizes[P + "embed"] == parts["tied_table"] == 16384 * 2048
    assert of(P + "l0.") == parts["layer_0_conv_dense"] == 60827648
    assert of(P + "l1.") == parts["attention_sparse_layer"] == 98635904
    assert {of(f"{P}l{i}.") for i in (2, 3, 4)} \
        == {parts["conv_sparse_layer"]} == {104933376}
    assert of(P + "l2.conv_") == parts["conv_operator"] == 16783360
    assert of(P + "l1.w_") + 128 == parts["attention_operator"] == 10485888
    assert of(P + "l0.mlp_") == parts["dense_mlp"] == 3 * 2048 * 7168
    assert 3 * sizes[P + "l2.gate"] == parts["held_experts_a_layer"] \
        == 8 * parts["one_expert"]
    assert sizes[P + "l1.router"] == parts["router_a_layer"] == 2048 * 32
    # no width differs from the published config; the floors are kept
    for key, want in dict(hidden_size=2048, intermediate_size=7168,
                          moe_intermediate_size=1792, conv_L_cache=3,
                          num_attention_heads=32, num_key_value_heads=8,
                          num_experts_per_tok=4, rope_theta=1000000,
                          norm_eps=1e-5, routed_scaling_factor=1,
                          norm_topk_prob=True, use_expert_bias=True,
                          conv_bias=False).items():
        assert cfg[key] == want
    dep = cfg["deployment"]
    assert cfg["num_hidden_layers"] == 5 == len(dep["layers_held"])
    assert dep["num_experts"] == 32 == 4 * cfg["num_experts"]
    assert cfg["vocab_size"] * 4 == dep["vocab_size"] == 65536
    assert len(cfg["layer_types"]) == 24
    assert cfg["layer_types"].count("full_attention") == 6
    assert [cfg["layer_types"][l] for l in dep["layers_held"]] == KINDS
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_dense_layers", "num_experts",
         "vocab_size"])


def test_logits(small):
    _close(small["logits"], np.asarray(small["want"]["logits"]).reshape(
        T, -1))


def test_the_op_of_each_conv_layer_first_hand(small):
    """Every conv layer hands out its `short_conv` op's own input [T, 3C]
    and output [T, C]; the output is the reference's gated convolution of
    that input (what the chip comparison holds the op's precision by)."""
    assert sorted(small["conv_ops"]) == [i for i, k in enumerate(KINDS)
                                         if k == "conv"]
    for i, (x, y) in small["conv_ops"].items():
        assert x.shape == (T, 3 * 64) and y.shape == (T, 64)
        want = small["ref"].gated_conv(
            jnp.asarray(x).reshape(2, 32, -1),
            jnp.asarray(small["w0"][f"{P}l{i}.conv_taps"]))
        _close(y, np.asarray(want).reshape(T, -1))
        assert np.abs(y).max() > 0


def test_loss(small):
    _close(np.asarray(small["loss"]).reshape(()), small["want"]["loss"])


@pytest.mark.parametrize("layer", range(5))
def test_operator_branch_of_each_layer_first_hand(small, layer):
    """A conv layer's branch (in_proj, B * z, three taps with zeros before
    the row, C *, out_proj) and the attention layer's (per-head QK norm,
    rotary, 4 query heads a key/value head) against the reference on the
    SAME normed input: a wrong tap order, a wrong third, a row that sees
    the row before it, a norm over the whole projection fails here by
    itself."""
    u, branch = small["operators"][layer]
    w = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    ref, cfg = small["ref"], small["cfg"]
    want = ref.operator_branch(cfg, w, layer, jnp.asarray(u).reshape(
        2, 32, -1))
    _close(branch, np.asarray(want).reshape(T, -1))
    assert np.abs(branch).max() > 0
    assert small["built"]["operators"][layer][0] == KINDS[layer]
    if KINDS[layer] == "conv":
        # the taps the other way round are another function
        p = f"{P}l{layer}.conv_taps"
        other = ref.operator_branch(
            cfg, dict(w, **{p: w[p][::-1]}), layer,
            jnp.asarray(u).reshape(2, 32, -1))
        assert np.abs(np.asarray(other).reshape(T, -1) - branch).max() \
            > 1e-2 * np.abs(branch).max()
        # the two rows are separate sequences: as ONE row of 64 tokens
        # the second row's first two tokens differ
        joined = np.asarray(ref.operator_branch(
            cfg, w, layer, jnp.asarray(u).reshape(1, 64, -1))).reshape(T, -1)
        assert np.abs(joined[32:34] - branch[32:34]).max() \
            > 1e-3 * np.abs(branch).max()
        _close(joined[34:], branch[34:])


def test_qk_norm_is_per_head_with_one_scale(small):
    """The scales are [head_dim]: a norm over the whole projection (OLMoE's)
    would have [H * D]."""
    shapes = small["ref"].param_shapes(small["cfg"])
    assert shapes[P + "l1.q_layernorm"] == shapes[P + "l1.k_layernorm"] \
        == (8,)
    assert np.abs(small["grads"][P + "l1.q_layernorm"]).max() > 0


@pytest.mark.parametrize("layer", range(4))
def test_routing_is_by_score_plus_bias_weights_without_it(small, layer):
    """The chosen are the top-4 of sigmoid(u W_r) + b; the weights are the
    chosen scores over (their sum + 1e-6), without b."""
    ids, load, rows = small["routing"][layer]
    biased, top = small["want"]["routing"][layer]
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(top, 1))
    assert load.shape == (E_ALL,) and load.sum() == 4 * T
    np.testing.assert_array_equal(load, np.bincount(np.asarray(top).ravel(),
                                                    minlength=E_ALL))
    assert int(rows[0]) == load[8:16].sum()
    bias = small["w0"][f"{P}l{SPARSE[layer]}.expert_bias"]
    plain = np.argsort(-(np.asarray(biased) - bias), axis=1)[:, :4]
    assert (np.sort(plain, 1) != np.sort(np.asarray(top), 1)).any()


def test_the_renormalisation_adds_1e_6(small):
    """`norm_eps` reaches the op: every sparse layer's `moe_ffn` carries
    1e-6, and with scores this small it moves the weights (the op's
    default 1e-20 would not)."""
    from paddle_tpu.ops import lm_ops

    ops = [op for op in small["built"]["prog"].global_block().ops
           if op.type == "moe_ffn"]
    assert len(ops) == 4 and all(op.attrs["norm_eps"] == 1e-6 for op in ops)
    x = jnp.full((4, 8), -3.0, jnp.float32)
    router = jnp.full((8, 6), 0.5, jnp.float32)     # sigmoid(-12): 6e-6
    top = {}
    for eps in (1e-6, 1e-20):
        r = lm_ops.Routing(dict(top_k=2, score_func="sigmoid",
                                norm_topk=True, norm_eps=eps), 6)
        top[eps] = np.asarray(lm_ops._route(x, router, None, r)[0][0])
    s = 1.0 / (1.0 + np.exp(12.0))
    np.testing.assert_allclose(top[1e-6], s / (2 * s + 1e-6), rtol=1e-5)
    np.testing.assert_allclose(top[1e-20], 0.5, rtol=1e-5)


def test_norm_eps_by_default_appends_today_s_op():
    """`norm_eps=None` leaves no trace on the op (the accepted cells'
    programs are as they were); the op without the attribute divides by
    the sum + 1e-20 as it always has."""
    from paddle_tpu.ops import lm_ops

    def ops_of(**kw):
        prog = fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(
                prog, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[32], dtype="float32")
            fluid.layers.moe_ffn(x, 8, 16, 2, score_func="sigmoid",
                                 norm_topk=True, **kw)
        op, = (o for o in prog.global_block().ops if o.type == "moe_ffn")
        return (sorted(k for k in op.inputs if op.input(k)), dict(op.attrs))

    plain = ops_of()
    assert ops_of(norm_eps=None) == plain and "norm_eps" not in plain[1]
    assert ops_of(norm_eps=1e-6)[1] == dict(plain[1], norm_eps=1e-6)
    assert lm_ops.Routing(plain[1], 8).norm_eps == 1e-20


def test_every_gradient(small):
    assert set(small["grads"]) == set(small["want"]["grads"])
    for name, g in small["want"]["grads"].items():
        _close(small["grads"][name], g, floor=2e-10)
    for leaf in ("l2.gate", "l0.conv_taps", "l3.conv_in", "l1.k_layernorm"):
        assert np.abs(small["grads"][P + leaf]).max() > 0


def _sampled():
    from chipbench.configs import lfm2_8b_a1b as builder

    return sorted(builder.sampled_params(_cfg()))


@pytest.mark.parametrize("which", _sampled())
def test_sampled_gradient_and_first_update(small, which):
    name = small["builder"].sampled_params(small["cfg"])[which]
    _close(small["grads"][name], small["want"]["grads"][name], floor=2e-10)
    # w1 - w0 carries the rounding of w1: half an ulp of the largest weight
    _close(small["w1"][name] - small["w0"][name],
           small["want"]["delta"][name], 3e-4,
           floor=float(np.spacing(np.abs(small["w0"][name]).max())))


def test_the_tied_table_s_gradient_is_the_sum_of_its_two_readers(small):
    """A row only the lookup touches (an id among the tokens, its logit
    column weighed by every position alike), a row no token has (the
    head's term alone), and the whole: the reference's one gradient of one
    array. With the head's term cut (the reference's logits read a
    `stop_gradient` copy) the lookup's rows alone remain."""
    ref, cfg = small["ref"], small["cfg"]
    w = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    got = small["grads"][P + "embed"]
    _close(got, small["want"]["grads"][P + "embed"])
    tokens = small["feed"]["tokens"]

    # the lookup's term: the gradient with the head reading a constant copy
    def loss_cut(table):
        w_ = dict(w, **{P + "embed": table})
        x = table[jnp.asarray(tokens)]
        for i, kind in enumerate(ref.layer_kinds(cfg)):
            x, _ = ref.layer(x, w_, i, kind, cfg)
        logits = ref.rms_norm(x, w_[P + "embedding_norm"], cfg["norm_eps"]) \
            @ jax.lax.stop_gradient(table).T
        logp = jax.nn.log_softmax(logits, -1)
        lab = jnp.asarray(small["feed"]["labels"])
        return -jnp.mean(jnp.take_along_axis(logp, lab[..., None], -1))

    with jax.default_matmul_precision("highest"):
        of_lookup = np.asarray(jax.grad(loss_cut)(w[P + "embed"]))
    absent = np.setdiff1d(np.arange(256), tokens.ravel())
    present = np.unique(tokens.ravel())
    assert len(absent) and len(present)
    # rows no token has: the lookup gives nothing, the head everything
    assert np.abs(of_lookup[absent]).max() == 0
    assert np.abs(got[absent]).max() > 0
    # rows a token has: both terms
    assert np.abs(got[present] - of_lookup[present]).max() \
        > 1e-3 * np.abs(got).max()
    assert np.abs(of_lookup[present]).max() > 0


def test_untying_the_head_changes_the_update(small):
    """The tie is not a no-op: the same program with a head of its own
    [V, C] that starts as a copy of the table gives the same loss, another
    gradient for the table (the lookup's alone) and another first update."""
    untied = _run_small(_cfg(), untied=True)
    for n in small["w0"]:
        np.testing.assert_array_equal(untied["w0"][n], small["w0"][n])
    _close(untied["loss"], small["loss"])
    tied_g, lookup_g = small["grads"][P + "embed"], untied["grads"][P + "embed"]
    _close(lookup_g + untied["grads"][P + "head"], tied_g, 1e-4, floor=1e-9)
    assert np.abs(tied_g - lookup_g).max() > 0.1 * np.abs(tied_g).max()
    moved = small["w1"][P + "embed"] - small["w0"][P + "embed"]
    moved_untied = untied["w1"][P + "embed"] - untied["w0"][P + "embed"]
    assert np.abs(moved - moved_untied).max() > 0.5 * np.abs(moved).max()


def test_the_clip_and_the_decay_count_the_tied_table_once(small):
    """One `adam` op and one clipped gradient for the table; the global
    norm is the reference's over ONE gradient of the table."""
    ops = small["built"]["prog"].global_block().ops
    assert sum(1 for op in ops if op.type == "adam"
               and op.input("Param") == [P + "embed"]) == 1
    norm = float(np.sqrt(sum(np.sum(np.square(g.astype(np.float64)))
                             for g in small["grads"].values())))
    _, want = small["ref"].adamw_first_update(
        small["cfg"], small["w0"],
        {k: jnp.asarray(v) for k, v in small["want"]["grads"].items()})
    assert abs(norm - float(want)) <= 1e-5 * float(want)
    assert small["ref"].decays(P + "embed") and small["ref"].decays(
        P + "l0.conv_taps")


@pytest.mark.parametrize("layer", range(4))
def test_the_bias_is_not_trained_and_follows_the_load(small, layer):
    name = f"{P}l{SPARSE[layer]}.expert_bias"
    assert name not in small["grads"]
    load = small["routing"][layer][1].astype(np.float64)
    want = small["w0"][name] + np.float32(SPEED) * np.sign(
        load.mean() - load).astype(np.float32)
    assert np.any(load != load.mean())
    np.testing.assert_array_equal(small["w1"][name], want)
    chosen = small["want"]["routing"][layer][1]
    _close(small["ref"].balance_step(small["cfg"], jnp.asarray(
        small["w0"][name]), chosen, SPEED), want, 1e-7)


def test_the_decay_acts_at_the_recipe_s_rate(small):
    """What the cell's weakened update check cannot see (`distorts`): with
    the decay left out of the expected step a matrix's update, the taps'
    and the tied table's are off by more than the tolerance, a norm
    scale's is not."""
    o = small["cfg"]["optimizer"]
    no_decay, _ = small["ref"].adamw_first_update(
        dict(small["cfg"], optimizer=dict(o, weight_decay=0.0)),
        small["w0"], {k: jnp.asarray(v) for k, v in small["grads"].items()},
        epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    for name in (P + "l1.w_q", P + "l2.conv_taps", P + "embed"):
        got = small["w1"][name] - small["w0"][name]
        assert np.abs(got - np.asarray(no_decay[name])).max() \
            > 1e-3 * np.abs(got).max()
    for name in (P + "l1.operator_norm", P + "l1.q_layernorm"):
        assert not small["ref"].decays(name)
        _close(small["w1"][name] - small["w0"][name], no_decay[name], 3e-4,
               floor=float(np.spacing(1.0)))


@pytest.mark.parametrize("place", ["cpu", "tpu"])
def test_lowered_counts_name_the_conv_and_the_tie(small, place):
    from paddle_tpu.ops import lm_ops

    prog = small["built"]["prog"]
    got = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform=place))
    # the program leaves the tokens open: the rows are taken to be many
    want = {"moe_ffn_grouped": 4, "moe_ffn_held_experts": 4,
            "moe_ffn_row_bound": 4, "short_conv_gated": 4,
            "short_conv_grad_by_hand": 4, "tied_table_lookup": 1,
            "tied_table_head": 1}
    if place == "tpu":
        want.update(flash_attention=1, flash_attention_bwd=1,
                    flash_attention_head_groups=1,
                    flash_fwd_visited_blocks=1, flash_fwd_masked_blocks=1)
    assert got == want
    # the inference clone has no backward
    test = lm_ops.lowered_counts(small["built"]["test_prog"],
                                 types.SimpleNamespace(platform="cpu"))
    assert "short_conv_grad_by_hand" not in test
    assert test["short_conv_gated"] == 4


def test_lowered_counts_at_the_published_widths_under_the_policy():
    """The cell's program, built under bf16 AMP: the grouped kernels take
    K 2048 / F 1792 with their epilogues, the conv kernels [8192, 6144], the step reads kept bf16 copies
    of all twelve expert matrices, and the tied table's lookup gradient
    (16,384 x 2048 float32 = 134 MB from 8192 ids) is the row-tile
    kernel's: `row_sum.takes` decides (PR 38)."""
    from chipbench.configs import lfm2_8b_a1b as builder
    from paddle_tpu import amp
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import row_sum

    amp.enable("bfloat16")
    try:
        prog = builder.build(fluid, _file(), 1)["prog"]
        got = lm_ops.lowered_counts(prog,
                                    types.SimpleNamespace(platform="tpu"))
    finally:
        amp.disable()
    assert got == dict(
        moe_ffn_grouped=4, grouped_matmul_kernel=4, grouped_mlp_epilogues=4,
        flash_attention=1, flash_attention_bwd=1,
        flash_attention_head_groups=1, moe_ffn_held_experts=4,
        moe_ffn_row_bound=4, moe_ffn_kept_copies=4,
        lookup_table_grad_tiled=1, short_conv_gated=4,
        short_conv_grad_by_hand=4, short_conv_kernel=4,
        short_conv_grad_kernel=4, tied_table_lookup=1, tied_table_head=1,
        # the one attention layer's forward, 1024 x 1024 blocks over a row
        # of 8192: the mask on the diagonal's 8 of 36 (PR 41; 36 before)
        flash_fwd_visited_blocks=36, flash_fwd_masked_blocks=8)
    assert row_sum.takes(16384, 2048, 8192, "float32")
    assert lm_ops.window_blocks(prog) == (0, 0)


def test_the_program_names_its_scopes(small):
    prog = small["built"]["prog"]
    scopes = {str(op.attrs.get("op_namescope", "")).strip("/")
              for op in prog.global_block().ops}
    tops = {s.split("/")[0] for s in scopes}
    assert {"embed", "conv", "attn", "dense_mlp", "moe", "lm_head",
            "router_bias"} <= tops
    assert {"conv/norm", "conv/in_proj", "conv/short_conv",
            "conv/out_proj", "attn/norm"} <= scopes
    by_type = {}
    for op in prog.global_block().ops:
        by_type.setdefault(op.type, set()).add(
            str(op.attrs.get("op_namescope", "")).strip("/"))
    assert by_type["short_conv"] == {"conv/short_conv"}
    assert by_type["causal_attention"] == {"attn"}
    assert by_type["moe_ffn"] == {"moe"}
    assert by_type["lookup_table"] == {"embed"}
    assert by_type["matmul"] == {"lm_head"}


# ----------------------------------------------------------- the share
CHIPS = 4


def _uncut():
    """An uncut tiny model: 32 experts all held, the whole vocabulary of
    1024 rows, and seeded weights; u a normed state."""
    cfg = _cfg(num_experts=32, vocab_size=1024,
               deployment=dict(num_experts=32, first_expert=0,
                               layers_held=[0, 2, 3, 4, 5]))
    from chipbench.reference import lfm2_8b_a1b as ref

    rs = np.random.default_rng(11)
    big = ("router", "taps", ".gate", ".up", ".down")   # branches that matter
    w = {n: jnp.asarray(rs.normal(0, 0.3 if any(b in n for b in big)
                                  else 0.08, s), jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    x = jnp.asarray(rs.normal(0, 1, (2, 32, 64)), jnp.float32)
    return cfg, ref, w, x


def _program_part(build, weights, feeds, dtype="float32"):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        ins = [fluid.layers.data(name=n, shape=list(v.shape[1:]),
                                 dtype=str(v.dtype))
               for n, v in feeds.items()]
        out = build(*ins)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in prog.global_block().all_parameters():
            scope.set_var(p.name, np.asarray(weights[p.name]))
        got = exe.run(prog, feed=feeds, fetch_list=list(
            out if isinstance(out, (list, tuple)) else [out]))
    return np.asarray(got[0]) if len(got) == 1 else [
        np.asarray(g) for g in got]


@pytest.fixture(scope="module", params=[0, 1, 2],
                ids=["conv_dense", "attention_sparse", "conv_sparse"])
def layer_parts(request):
    """A whole decoder layer of each kind on x [T, C]: what each of the 4
    chips computes of it (the operator and, in layer 0, the dense MLP
    whole on every chip; the experts the held ones') and the uncut
    reference's layer."""
    from paddle_tpu.models import lfm2

    i = request.param
    cfg, ref, w, x = _uncut()
    kind = KINDS[i]
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.layer(x, w, i, kind, cfg)
    parts = []
    for chip in range(CHIPS):
        c, ws = ref.share_of(cfg, w, chip, CHIPS)

        def build(x_, c=c):
            y, routing, _ = lfm2.layer(x_, c, 32, i, kind)
            return [y] + ([routing[2]] if routing else [])

        got = _program_part(build, ws, {"x": np.asarray(x.reshape(T, 64))})
        got, held = (got[0], int(got[1][0])) if isinstance(got, list) \
            else (got, None)
        with jax.default_matmul_precision("highest"):
            want, _ = ref.layer(x, ws, i, kind, c)
        parts.append((got, np.asarray(want).reshape(T, 64), held))
    return i, np.asarray(x.reshape(T, 64)), \
        np.asarray(whole).reshape(T, 64), parts


@pytest.mark.parametrize("chip", range(CHIPS))
def test_a_share_of_a_layer_is_the_reference_s_share(layer_parts, chip):
    _, _, _, parts = layer_parts
    got, want, _ = parts[chip]
    _close(got, want)


def test_the_four_shares_add_up_to_the_uncut_layer(layer_parts):
    """x + operator + MLP: every chip computes x + the operator (and in
    layer 0 the dense MLP) alike: counted ONCE; the experts' parts are
    summed over the chips. A dense layer is the same on every chip."""
    i, x, whole, parts = layer_parts
    if i == 0:
        for got, _, held in parts:
            assert held is None
            _close(got, whole)
        return
    rows = [held for _, _, held in parts]
    assert sum(rows) == 4 * T and min(rows) > 0
    # what every chip computes alike: the layer with no expert held, from
    # the reference (the operator's branch on x)
    cfg, ref, w, x3 = _uncut()
    p, eps = f"{P}l{i}.", cfg["norm_eps"]
    with jax.default_matmul_precision("highest"):
        alike = x3 + ref.operator(ref.rms_norm(
            x3, w[p + "operator_norm"], eps), w, p, cfg, KINDS[i])
    alike = np.asarray(alike).reshape(T, 64)
    total = alike + sum(got - alike for got, _, _ in parts)
    _close(total, whole)
    assert np.abs(whole - alike).max() > 1e-2 * np.abs(whole).max()


def test_the_vocabulary_share_is_a_slice():
    """Chip 1's table is rows 256..511 of the uncut table; its logits are
    those columns of the uncut model's logits for the same state (the
    tied head reads the same slice), and its lookup of id j is the uncut
    model's of id 256 + j."""
    cfg, ref, w, x = _uncut()
    c, ws = ref.share_of(cfg, w, 1, CHIPS)
    assert c["vocab_size"] == 256 and c["num_experts"] == 8
    assert c["deployment"]["first_expert"] == 8
    np.testing.assert_array_equal(np.asarray(ws[P + "embed"]),
                                  np.asarray(w[P + "embed"][256:512]))
    from paddle_tpu.models import lfm2

    ids = np.random.default_rng(3).integers(0, 256, (2, 32)).astype(np.int32)

    def build(t, c=c):
        out = lfm2.lfm2(t, dict(c, num_hidden_layers=0))
        return [out["logits"]]

    got = _program_part(build, ws, {"tokens": ids})
    with jax.default_matmul_precision("highest"):
        emb = w[P + "embed"][jnp.asarray(ids) + 256]
        want = ref.rms_norm(emb, w[P + "embedding_norm"], cfg["norm_eps"]) \
            @ w[P + "embed"].T
    _close(got, np.asarray(want).reshape(T, -1)[:, 256:512])


def test_the_reference_is_independent_of_the_system():
    path = os.path.join(REPO, "chipbench", "reference", "lfm2_8b_a1b.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert 'PRECISION = "highest"' in text
