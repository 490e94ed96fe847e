"""`paddle_tpu.models.smallthinker` at a small size (hidden 64, 16 experts
of width 32 of which 4 held, top-3, 7 query heads on 1 key/value head of
16, a window of 8 in rows of 32, layers [full without positions, window,
window, window], 2 x 32 tokens) against the plain float32 reference of
`chipbench/reference/smallthinker_21b_a3b.py`, on seeded weights read out
of the scope; what the model forced of `moe_ffn` (a router that reads
another variable than the experts do, ReLU-gated experts, a bias on the
LOGITS of a softmax router); and the tests that tie a chip's share to the
model: the parts all four shares of a layer give add up to the uncut
reference's layer.

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
    hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=4, head_dim=16,
    num_attention_heads=7, num_key_value_heads=1, moe_num_primary_experts=4,
    moe_num_active_primary_experts=3, sliding_window_size=8, vocab_size=256,
    sequence_length=32,
    deployment=dict(moe_num_primary_experts=16, first_expert=4))
PEAK_RATE = 3e-4     # a recipe's (the file's `assumed.optimizer`)
SAMPLED = ("head", "embedding", "w_q_full", "w_k_full", "w_q_window",
           "w_k_window", "w_v", "w_o", "router", "router_window",
           "expert_gate", "expert_up", "expert_down", "norm_scale")
T, E_ALL, P = 64, 16, "smallthinker."
SPEEDS = [0.01, 0.02, 0.03, 0.04]     # of the stand-in bias, a layer


def _file():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        return json.load(f)


def _cfg(**changes):
    """The configuration file at the small sizes, at a recipe's peak
    learning rate (the cell's 1e-6 makes a step smaller than half an ulp
    of a norm scale: nothing an update could be judged by)."""
    cfg = dict(_file(), **dict(SMALL, **changes))
    cfg["optimizer"] = dict(cfg["optimizer"], learning_rate=PEAK_RATE,
                            router_bias_update_speed_by_layer=SPEEDS)
    return cfg


def _close(got, want, tol=1e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= floor + tol * max(
        np.max(np.abs(want)), 1e-30)


def _run_small(cfg, seed=5):
    """The system's numbers on one seeded batch: weights as drawn but the
    routers' (std 0.5: logits far enough apart that float32 sums in
    another order do not flip a choice) and their biases (set non-zero:
    choosing by logit + bias and weighing by the logits then differ),
    logits, loss, routing, the attention branches, every gradient, the
    weights after one step."""
    from chipbench.configs import smallthinker_21b_a3b as builder

    ref = builder.reference
    built = builder.build(fluid, cfg, seed)
    rs = np.random.default_rng(0)
    feed = {"tokens": rs.integers(0, 256, (2, 32)).astype(np.int32),
            "labels": rs.integers(0, 256, (2, 32)).astype(np.int32)}
    names = list(ref.param_shapes(cfg))
    trained = [n for n in names if ref.trained(n)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        for n in names:
            if n.endswith("router_bias"):
                scope.set_var(n, rs.normal(0, 0.3, E_ALL).astype(np.float32))
            elif n.endswith("router"):
                scope.set_var(n, rs.normal(0, 0.5, (64, E_ALL)).astype(
                    np.float32))
        w0 = {n: np.asarray(scope.find_var(n)) for n in names}
        branches = [v for pair in built["attention"] for v in pair]
        logits, *attn = exe.run(built["test_prog"], feed=feed,
                                fetch_list=[built["logits"]] + branches)
        routing = [v for r in built["routing"] for v in r]
        got = exe.run(built["prog"], feed=feed,
                      fetch_list=[built["loss"]] + routing
                      + [n + "@GRAD" for n in trained])
        w1 = {n: np.asarray(scope.find_var(n)) for n in names}
    n_r = len(routing)
    return dict(
        cfg=cfg, ref=ref, builder=builder, feed=feed, names=names, w0=w0,
        w1=w1, logits=logits, loss=got[0], attention=list(zip(attn[::2],
                                                              attn[1::2])),
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
    prog = small["builder"].build(fluid, small["cfg"], 5)["prog"]
    got = {p.name: tuple(p.shape)
           for p in prog.global_block().all_parameters()}
    assert got == {k: tuple(v) for k, v in
                   small["ref"].param_shapes(small["cfg"]).items()}
    picks = small["builder"].sampled_params(small["cfg"])
    assert set(picks) == set(SAMPLED) and set(picks.values()) <= set(got)


def test_the_file_s_parameter_count_is_the_program_s():
    """At the published widths (the program is only built, nothing runs):
    every trained parameter of the program, against `parameters` and the
    parts the file gives, and the issue's arithmetic."""
    from chipbench.configs import smallthinker_21b_a3b as builder

    cfg = _file()
    prog = builder.build(fluid, cfg, 1)["prog"]
    sizes = {p.name: int(np.prod(p.shape))
             for p in prog.global_block().all_parameters()
             if builder.reference.trained(p.name)}
    assert sum(sizes.values()) == cfg["parameters"] == 593615360
    parts = cfg["parameters_by_part"]

    def of(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert {of(f"{P}l{i}.") for i in range(4)} == {parts["layer"]} \
        == {99783680}
    assert of(P + "l0.w_") == parts["attention_a_layer"] == 5242880
    assert sizes[P + "l1.router"] == parts["router_a_layer"] == 163840
    assert sizes[P + "l2.gate"] == 16 * 2560 * 768
    assert 3 * sizes[P + "l2.gate"] == parts["held_experts_a_layer"] \
        == 16 * parts["one_expert"]
    assert sizes[P + "embed"] + sizes[P + "head"] \
        == parts["embedding_and_head"]
    # no width differs from the published config; the floors are kept
    for key, want in dict(hidden_size=2560, moe_ffn_hidden_size=768,
                          head_dim=128, moe_num_active_primary_experts=6,
                          sliding_window_size=4096, rope_theta=1500000,
                          rms_norm_eps=1e-6).items():
        assert cfg[key] == want
    dep = cfg["deployment"]
    assert cfg["num_hidden_layers"] == 4
    assert dep["moe_num_primary_experts"] == 64 == 4 * cfg[
        "moe_num_primary_experts"]
    assert cfg["vocab_size"] * 4 == dep["vocab_size"] == 151936
    assert cfg["num_attention_heads"] * 4 == dep["num_attention_heads"] == 28
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "moe_num_primary_experts",
         "num_attention_heads", "num_key_value_heads", "vocab_size"])


def test_logits(small):
    _close(small["logits"], np.asarray(small["want"]["logits"]).reshape(
        T, -1))


def test_loss(small):
    _close(np.asarray(small["loss"]).reshape(()), small["want"]["loss"])


@pytest.mark.parametrize("layer", [0, 1, 2, 3])
def test_attention_branch_of_each_layer_first_hand(small, layer):
    """The full layer's branch (no rotary) and a window layer's (rotary, a
    band of 8) against the reference on the SAME normed input: a band off
    by one, a rotary where none belongs, a wrong head group fails here by
    itself."""
    u, branch = small["attention"][layer]
    w = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    want = small["ref"].attention_branch(
        small["cfg"], w, layer, jnp.asarray(u).reshape(2, 32, -1))
    _close(branch, np.asarray(want).reshape(T, -1))
    assert np.abs(branch).max() > 0
    # the layouts decide: the other kind of layer is another function
    flipped = dict(small["cfg"], rope_layout=[1, 0, 0, 0],
                   sliding_window_layout=[1, 0, 0, 0])
    other = small["ref"].attention_branch(
        flipped, w, layer, jnp.asarray(u).reshape(2, 32, -1))
    assert np.abs(np.asarray(other).reshape(T, -1) - branch).max() \
        > 1e-2 * np.abs(branch).max()


@pytest.mark.parametrize("layer", [0, 1, 2, 3])
def test_routing_is_by_the_layer_s_input_logits_plus_bias(small, layer):
    """The chosen are the top-3 of r + b, r from the layer's INPUT (for
    layer 0 the embedding rows themselves), and not from the normed state
    after attention, which the experts read."""
    ids, load, rows = small["routing"][layer]
    biased, top = small["want"]["routing"][layer]
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(top, 1))
    assert load.shape == (E_ALL,) and load.sum() == 3 * T
    np.testing.assert_array_equal(load, np.bincount(np.asarray(top).ravel(),
                                                    minlength=E_ALL))
    assert int(rows[0]) == load[4:8].sum()
    w0, p = small["w0"], f"{P}l{layer}."
    bias = w0[p + "router_bias"]
    plain = np.argsort(-(np.asarray(biased) - bias), axis=1)[:, :3]
    assert (np.sort(plain, 1) != np.sort(np.asarray(top), 1)).any()
    if layer == 0:
        r = w0[P + "embed"][small["feed"]["tokens"].ravel()].astype(
            np.float64) @ w0[p + "router"].astype(np.float64)
        np.testing.assert_array_equal(
            np.sort(np.argsort(-(r + bias), axis=1)[:, :3], 1),
            np.sort(ids, 1))


def test_routing_on_the_normed_state_would_differ(small):
    """The swap this model is about: a reference whose router reads what
    the experts read (the normed state after attention) chooses other
    experts for many tokens, in layer 0 already, and the system's choice
    is not that one."""
    ref, cfg = small["ref"], small["cfg"]
    w = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    x = w[P + "embed"][jnp.asarray(small["feed"]["tokens"])]
    p, eps = P + "l0.", cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        after = x + ref.attention(
            ref.rms_norm(x, w[p + "attn_norm"], eps), w, p, cfg, 0)
        u = ref.rms_norm(after, w[p + "ffn_norm"], eps).reshape(T, -1)
        _, _, swapped, _ = ref.route(u, w, p, cfg)
    ids = np.sort(small["routing"][0][0], 1)
    differ = (np.sort(np.asarray(swapped), 1) != ids).any(axis=1)
    assert differ.mean() > 0.2


@pytest.mark.parametrize("which", SAMPLED)
def test_sampled_gradient_and_first_update(small, which):
    name = small["builder"].sampled_params(small["cfg"])[which]
    _close(small["grads"][name], small["want"]["grads"][name])
    # w1 - w0 carries the rounding of w1: half an ulp of the largest weight
    # (the routers here are drawn at std 0.5)
    _close(small["w1"][name] - small["w0"][name],
           small["want"]["delta"][name], 3e-4,
           floor=float(np.spacing(np.abs(small["w0"][name]).max())))


def test_every_gradient(small):
    assert set(small["grads"]) == set(small["want"]["grads"])
    for name, g in small["want"]["grads"].items():
        _close(small["grads"][name], g, floor=2e-10)
    assert any(np.abs(small["grads"][n]).max() > 0
               for n in small["grads"] if n.endswith(".gate"))


def test_the_router_s_gradient_travels_through_router_input(small):
    """Cut the path from the router's logits back into x (the reference
    with `stop_gradient` on the router's input): the routers' own
    gradients stay (they need no path into x), the embedding's changes:
    so the embedding's rows' gradient, which the system matches, holds the
    term that reaches them through `RouterInput`."""
    ref, cfg = small["ref"], small["cfg"]
    w = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    real = ref.route

    def cut(x_in, w_, p, cfg_):
        return real(jax.lax.stop_gradient(x_in), w_, p, cfg_)

    ref.route = cut
    try:
        _, _, grads = ref.loss_and_grads(
            cfg, w, jnp.asarray(small["feed"]["tokens"]),
            jnp.asarray(small["feed"]["labels"]))
    finally:
        ref.route = real
    emb, got = np.asarray(grads[P + "embed"]), small["grads"][P + "embed"]
    assert np.abs(emb - got).max() > 1e-3 * np.abs(got).max()
    _close(got, small["want"]["grads"][P + "embed"])
    assert np.abs(small["grads"][P + "l0.router"]).max() > 0


@pytest.mark.parametrize("layer", [0, 1, 2, 3])
def test_the_bias_is_not_trained_and_follows_the_load(small, layer):
    name = f"{P}l{layer}.router_bias"
    assert name not in small["grads"]
    load = small["routing"][layer][1].astype(np.float64)
    speed = SPEEDS[layer]
    want = small["w0"][name] + np.float32(speed) * np.sign(
        load.mean() - load).astype(np.float32)
    assert np.any(load != load.mean())
    np.testing.assert_array_equal(small["w1"][name], want)
    chosen = small["want"]["routing"][layer][1]
    _close(small["ref"].balance_step(small["cfg"], jnp.asarray(
        small["w0"][name]), chosen, speed), want, 1e-7)


def test_a_speed_of_zero_appends_no_rule():
    from chipbench.configs import smallthinker_21b_a3b as builder

    cfg = _cfg()
    cfg["optimizer"]["router_bias_update_speed_by_layer"] = [0.0] * 4
    prog = builder.build(fluid, cfg, 5)["prog"]
    assert not any(op.attrs.get("op_namescope", "").endswith("router_bias")
                   or "router_bias" in str(op.attrs.get("op_namescope"))
                   for op in prog.global_block().ops)
    with_rule = builder.build(fluid, _cfg(), 5)["prog"]
    assert len(with_rule.global_block().ops) > len(prog.global_block().ops)


def test_the_decay_acts_at_the_recipe_s_rate(small):
    """What the cell's weakened update check cannot see (`distorts`): with
    the decay left out of the expected step a matrix's update is off by
    more than the tolerance, a norm scale's is not."""
    o = small["cfg"]["optimizer"]
    name = P + "l1.w_q"
    no_decay, _ = small["ref"].adamw_first_update(
        dict(small["cfg"], optimizer=dict(o, weight_decay=0.0)),
        small["w0"], {k: jnp.asarray(v) for k, v in small["grads"].items()},
        epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    got = small["w1"][name] - small["w0"][name]
    assert np.abs(got - np.asarray(no_decay[name])).max() \
        > 1e-3 * np.abs(got).max()
    assert not small["ref"].decays(P + "l1.attn_norm")


@pytest.mark.parametrize("place", ["cpu", "tpu"])
def test_lowered_counts_name_the_early_router_and_relu(small, place):
    from paddle_tpu.ops import lm_ops

    prog = small["builder"].build(fluid, small["cfg"], 5)["prog"]
    got = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform=place))
    # the program leaves the tokens open: the rows are taken to be many
    want = {"moe_ffn_grouped": 4, "moe_ffn_held_experts": 4,
            "moe_ffn_row_bound": 4, "moe_ffn_router_input": 4,
            "moe_ffn_relu": 4}
    if place == "tpu":
        # a row of the small size is one block a layer, the diagonal's
        want.update(flash_attention=4, flash_attention_bwd=4,
                    flash_attention_window=3, flash_attention_head_groups=4,
                    flash_fwd_visited_blocks=4, flash_fwd_masked_blocks=4)
    assert got == want


def test_lowered_counts_at_the_published_widths_under_the_policy():
    """The cell's program, built under bf16 AMP: the Pallas kernels take
    K 2560 / F 768 with their epilogues, the step reads kept bf16
    copies of all twelve expert matrices, the embedding's 389 MB
    gradient is the row-tile kernel's (PR 38) and so are the four layers'
    bounded sums (PR 40)."""
    from chipbench.configs import smallthinker_21b_a3b as builder
    from paddle_tpu import amp
    from paddle_tpu.ops import lm_ops

    amp.enable("bfloat16")
    try:
        prog = builder.build(fluid, _file(), 1)["prog"]
        got = lm_ops.lowered_counts(prog,
                                    types.SimpleNamespace(platform="tpu"))
    finally:
        amp.disable()
    assert got == dict(
        moe_ffn_grouped=4, grouped_matmul_kernel=4, grouped_mlp_epilogues=4,
        flash_attention=4, flash_attention_bwd=4, flash_attention_window=3,
        flash_attention_head_groups=4, moe_ffn_held_experts=4,
        moe_ffn_row_bound=4, moe_ffn_kept_copies=4, moe_ffn_router_input=4,
        moe_ffn_relu=4, lookup_table_grad_tiled=1, moe_ffn_rows_by_token=4,
        # the forward's blocks of 1024 x 1024 a head (PR 41): the full
        # layer visits 36 and masks the diagonal's 8, each of the three
        # bands of 4096 visits 30 and masks 8 + the far edge's 4
        flash_fwd_visited_blocks=36 + 3 * 30,
        flash_fwd_masked_blocks=8 + 3 * 12)
    visited, whole = lm_ops.window_blocks(prog)
    assert 0.6 * whole < visited < whole     # a band of 4096 in rows of 8192


def test_the_program_names_its_scopes(small):
    prog = small["builder"].build(fluid, small["cfg"], 5)["prog"]
    scopes = {str(op.attrs.get("op_namescope", "")).strip("/").split("/")[0]
              for op in prog.global_block().ops}
    assert {"embed", "attn_full", "attn_window", "moe", "lm_head",
            "router_bias"} <= scopes
    ops = [op for op in prog.global_block().ops if op.type == "moe_ffn"]
    assert len(ops) == 4
    for op in ops:
        assert op.input("RouterInput") and op.input("RouterInput") \
            != op.input("X")
        assert op.attrs["activation"] == "relu"


# ----------------------------------------------------------- the share
CHIPS = 4


def _uncut():
    """An uncut tiny model: 16 experts, 28 query heads on 4 key/value
    heads, and seeded weights; u the normed state, x_in the layer's
    input."""
    cfg = _cfg(moe_num_primary_experts=16, num_attention_heads=28,
               num_key_value_heads=4,
               deployment=dict(moe_num_primary_experts=16, first_expert=0))
    from chipbench.reference import smallthinker_21b_a3b as ref

    rs = np.random.default_rng(11)
    w = {n: jnp.asarray(rs.normal(0, 0.3 if "router" in n else 0.08, s),
                        jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    u, x_in = (jnp.asarray(rs.normal(0, 1, (2, 32, 64)), jnp.float32)
               for _ in range(2))
    return cfg, ref, w, u, x_in


def _program_part(build, weights, feeds):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        ins = [fluid.layers.data(name=n, shape=[64], dtype="float32")
               for n in feeds]
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


@pytest.fixture(scope="module")
def expert_parts():
    from paddle_tpu.models import smallthinker

    cfg, ref, w, u, x_in = _uncut()
    flat, flat_in = u.reshape(T, 64), x_in.reshape(T, 64)
    p = P + "l1."
    with jax.default_matmul_precision("highest"):
        part_all, _ = ref.experts(flat, flat_in, w, p, cfg)
    parts, rows = [], []
    for chip in range(CHIPS):
        c, ws = ref.share_of(cfg, w, chip, CHIPS)

        def build(x, x0, c=c):
            y, routing = smallthinker.experts(x, x0, c, p)
            return [y, routing[2]]

        got, held = _program_part(build, ws, {"u": np.asarray(flat),
                                              "x_in": np.asarray(flat_in)})
        with jax.default_matmul_precision("highest"):
            want, _ = ref.experts(flat, flat_in, ws, p, c)
        parts.append((got, np.asarray(want)))
        rows.append(int(held[0]))
    assert sum(rows) == 3 * T and min(rows) > 0
    return np.asarray(part_all), parts


@pytest.mark.parametrize("chip", range(CHIPS))
def test_an_expert_share_is_the_reference_s_share(expert_parts, chip):
    got, want = expert_parts[1][chip]
    _close(got, want)
    assert np.abs(want).max() > 0


def test_expert_shares_add_up_to_the_uncut_layer(expert_parts):
    """Over the 4 chips the held experts' parts are the uncut reference's
    expert branch: nothing is computed alike on every chip (no shared
    expert)."""
    part_all, parts = expert_parts
    _close(sum(got for got, _ in parts), part_all)


@pytest.fixture(scope="module", params=[0, 1],
                ids=["full_no_rotary", "window_rotary"])
def head_parts(request):
    """Layer 0 (full, no positions) and layer 1 (window of 8, rotary),
    each divided over 4 chips that hold 7 query heads and the one
    key/value head they read."""
    from paddle_tpu.models import smallthinker

    layer = request.param
    cfg, ref, w, u, _ = _uncut()
    p = f"{P}l{layer}."
    with jax.default_matmul_precision("highest"):
        whole = ref.attention(u, w, p, cfg, layer)
    parts = []
    for chip in range(CHIPS):
        c, ws = ref.share_of(cfg, w, chip, CHIPS)
        got = _program_part(
            lambda x, c=c: smallthinker.attention(
                x, c, 32, p, bool(c["sliding_window_layout"][layer]),
                bool(c["rope_layout"][layer])), ws,
            {"u": np.asarray(u.reshape(T, 64))})
        with jax.default_matmul_precision("highest"):
            want = ref.attention(u, ws, p, c, layer)
        parts.append((got, np.asarray(want).reshape(T, 64)))
    return np.asarray(whole).reshape(T, 64), parts


@pytest.mark.parametrize("chip", range(CHIPS))
def test_a_head_share_is_the_reference_s_share(head_parts, chip):
    got, want = head_parts[1][chip]
    _close(got, want)


def test_head_shares_add_up_to_the_uncut_layer(head_parts):
    """W_o's rows go with the heads: the chips' branches are partial sums
    of the uncut layer's."""
    whole, parts = head_parts
    _close(sum(got for got, _ in parts), whole)


def test_vocabulary_share_is_a_slice():
    cfg, ref, w, _, _ = _uncut()
    c, ws = ref.share_of(cfg, w, 1, 4)
    assert c["vocab_size"] == 64 and ws[P + "embed"].shape == (64, 64)
    np.testing.assert_array_equal(ws[P + "head"], w[P + "head"][:, 64:128])
    np.testing.assert_array_equal(ws[P + "embed"], w[P + "embed"][64:128])
    assert {n: tuple(v.shape) for n, v in ws.items()} == {
        n: tuple(s) for n, s in ref.param_shapes(c).items()}
    assert c["deployment"]["first_expert"] == 4
    assert c["moe_num_primary_experts"] == 4
    assert c["num_attention_heads"] == 7 and c["num_key_value_heads"] == 1


# ------------------------------------------------------------- the op
def _moe_args(rs, T_=48, H=32, E=8, F=16):
    x, x_r = (jnp.asarray(rs.normal(0, 1, (T_, H)), jnp.float32)
              for _ in range(2))
    router = jnp.asarray(rs.normal(0, 0.5, (H, E)), jnp.float32)
    bias = jnp.asarray(rs.normal(0, 0.5, (E,)), jnp.float32)
    gate, up = (jnp.asarray(rs.normal(0, 0.2, (E, H, F)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rs.normal(0, 0.2, (E, F, H)), jnp.float32)
    return x, x_r, router, bias, gate, up, down


def _moe_written_out(x, x_r, router, bias, gate, up, down, k, act):
    r = x_r @ router
    _, top = jax.lax.top_k(r + bias, k)
    w = jax.nn.softmax(jnp.take_along_axis(r, top, axis=1), axis=1)
    dense = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(top, r.shape[1]))
    hid = act(jnp.einsum("th,ehf->etf", x, gate)) * jnp.einsum(
        "th,ehf->etf", x, up)
    return jnp.einsum("etf,efh,te->th", hid, down, dense)


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_the_op_with_a_router_input_against_the_layer_written_out(
        activation):
    """`moe_ffn` and `moe_ffn_grad` with `RouterInput`, a bias on the
    softmax router's LOGITS and either gate: the output and the gradient
    of every input, X's holding the experts' term alone and
    RouterInput's the router's alone."""
    from paddle_tpu.ops import lm_ops

    rs = np.random.default_rng(3)
    x, x_r, router, bias, gate, up, down = _moe_args(rs)
    cot = jnp.asarray(rs.normal(0, 1, x.shape), jnp.float32)
    act = jax.nn.silu if activation == "silu" else jax.nn.relu
    attrs = {"top_k": 3, "score_func": "softmax", "norm_topk": True,
             "routed_scale": 1.0}
    if activation != "silu":
        attrs["activation"] = activation
    ins = {"X": [x], "RouterInput": [x_r], "Router": [router],
           "Bias": [bias], "Gate": [gate], "Up": [up], "Down": [down]}
    outs = lm_ops.moe_ffn_op(None, ins, attrs)
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(
            lambda x, x_r, router, gate, up, down: _moe_written_out(
                x, x_r, router, bias, gate, up, down, 3, act),
            x, x_r, router, gate, up, down)
        want_grads = vjp(cot)
    _close(outs["Out"][0], want, 2e-5)
    grads = lm_ops.moe_ffn_grad_op(None, dict(
        ins, **{"Out@GRAD": [cot]},
        **{s: outs[s] for s in ("GateOut", "UpOut", "DownOut")}), attrs)
    for slot, g in zip(("X", "RouterInput", "Router", "Gate", "Up", "Down"),
                       want_grads):
        _close(grads[slot + "@GRAD"][0], g, 1e-4)
    assert np.abs(grads["RouterInput@GRAD"][0]).max() > 0


def test_a_softmax_router_s_bias_joins_the_logits():
    """Scores near 1 / E: a bias of the size of the logits' spread added
    to the SCORES would choose by the bias alone; added to the logits it
    shifts the order as a prior does."""
    from paddle_tpu.ops import lm_ops

    rs = np.random.default_rng(8)
    x, _, router, bias, gate, up, down = _moe_args(rs)
    ins = {"X": [x], "Router": [router], "Bias": [bias], "Gate": [gate],
           "Up": [up], "Down": [down]}
    ids = np.asarray(lm_ops.moe_ffn_op(None, ins, {"top_k": 3})["ExpertIds"][0])
    r = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    by_logits = np.argsort(-(r + np.asarray(bias)), axis=1)[:, :3]
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(by_logits, 1))
    by_bias_alone = np.argsort(-np.asarray(bias))[:3]
    assert (np.sort(ids, 1) != np.sort(by_bias_alone)).any()
    # a sigmoid router's bias stays on the scores (the accepted cells')
    ids = np.asarray(lm_ops.moe_ffn_op(
        None, ins, {"top_k": 3, "score_func": "sigmoid"})["ExpertIds"][0])
    by_scores = np.argsort(-(1 / (1 + np.exp(-r)) + np.asarray(bias)),
                           axis=1)[:, :3]
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(by_scores, 1))


def test_the_layer_appends_today_s_op_by_default():
    """`router_input=None` (or the input itself) and `activation="silu"`
    leave no trace on the op: no `RouterInput` slot, no `activation`
    attribute."""
    def ops_of(**kw):
        prog = fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(
                prog, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[32], dtype="float32")
            fluid.layers.moe_ffn(x, 8, 16, 2, **{
                k: (x if v == "x" else v) for k, v in kw.items()})
        op, = (o for o in prog.global_block().ops if o.type == "moe_ffn")
        return (sorted(k for k in op.inputs if op.input(k)), dict(op.attrs))

    plain = ops_of()
    assert ops_of(router_input=None, activation="silu") == plain
    assert ops_of(router_input="x") == plain
    assert "RouterInput" not in plain[0] and "activation" not in plain[1]
    with pytest.raises(ValueError):
        ops_of(activation="gelu")
