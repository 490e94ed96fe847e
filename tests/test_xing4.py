"""`paddle_tpu.models.xing4` at a small size (hidden 64, 8 experts of which
2 held, 4 heads of which 2 held, 4 residual streams, 1 dense + 2 expert
layers + the multi-token-prediction module, 2 x 32 tokens) against the
plain float32 reference of `chipbench/reference/xing4_0_29b_a4b.py`, on
seeded weights read out of the scope; the ops the model forced
(`moe_ffn`'s share and `noaux_tc` routing, `causal_attention` with values
narrower than keys and its own scale, YaRN's rotary frequencies, the
residual mixers); and the test that
ties a chip's share to the model: the parts all shares of a layer give,
with what every chip computes alike counted once, add up to the uncut
reference's layer.

Tolerance: float32 against float32 on the CPU; the two differ in the
order of float32 sums only (grouped products against the dense masked
einsum, the op-by-op backward against one jax.grad): 1e-5 of the largest
element, as tests/test_olmoe.py has it; 2e-4 for the mixers' parameters,
whose gradients (1e-6 in size here) pass back through 20 Sinkhorn rounds
of divisions (measured 4e-5). The first AdamW step is judged on
the gradients the system itself produced, for the reason given there.
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
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=2,
    num_key_value_heads=2, n_routed_experts=2, num_experts_per_tok=2,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, vocab_size=256, sequence_length=32,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    deployment=dict(n_routed_experts=8, first_expert=4))
PEAK_RATE = 3e-4     # the recipe's (the file's `assumed.optimizer`)
SAMPLED = ("head", "embedding", "w_qa", "w_kvb", "w_o", "router",
           "expert_gate", "expert_up", "expert_down", "shared_gate",
           "shared_up", "shared_down", "phi_res", "alpha", "mtp_proj",
           "norm_scale")


def _cfg(**changes):
    """The configuration file at the small sizes, at the recipe's peak
    learning rate (the cell's 1e-6 makes a step smaller than half an ulp
    of a norm scale: nothing an update could be judged by)."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "xing4_0_29b_a4b.json")) as f:
        cfg = dict(json.load(f), **dict(SMALL, **changes))
    cfg["optimizer"] = dict(cfg["optimizer"], learning_rate=PEAK_RATE)
    return cfg


def _close(got, want, tol=1e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= floor + tol * max(
        np.max(np.abs(want)), 1e-30)


def _grad_tol(name):
    return 2e-4 if "mhc_" in name else 1e-5


def _run_small(cfg, seed=5):
    """The system's numbers on one seeded batch: weights as drawn but the
    router's bias (set non-zero: choosing by score + bias and weighing by
    score then differ) and the mixers' alphas (0.5: the per-token part of
    the mixing matters), logits of both heads, losses, routing, every
    gradient, the weights after one step."""
    from chipbench.configs import xing4_0_29b_a4b as builder

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
                scope.set_var(n, rs.normal(0, 0.03, 8).astype(np.float32))
            elif n.endswith("alpha"):
                scope.set_var(n, np.full(3, 0.5, np.float32))
        w0 = {n: np.asarray(scope.find_var(n)) for n in names}
        logits, mtp = exe.run(
            built["test_prog"], feed=feed,
            fetch_list=[built["logits"], built["mtp_logits"]])
        routing = [v for r in built["routing"] for v in r]
        got = exe.run(built["prog"], feed=feed,
                      fetch_list=[built["loss"], built["ce"],
                                  built["ce_mtp"]] + routing
                      + [n + "@GRAD" for n in trained])
        w1 = {n: np.asarray(scope.find_var(n)) for n in names}
    n_r = len(routing)
    return dict(
        cfg=cfg, ref=ref, builder=builder, feed=feed, names=names, w0=w0,
        w1=w1, logits=logits, mtp=mtp, loss=got[0], ce=got[1],
        ce_mtp=got[2], routing=[got[3 + 3 * i:6 + 3 * i]
                                for i in range(n_r // 3)],
        grads=dict(zip(trained, got[3 + n_r:])))


@pytest.fixture(scope="module")
def small():
    s = _run_small(_cfg())
    ref, cfg, feed = s["ref"], s["cfg"], s["feed"]
    loss, rest, grads = ref.loss_and_grads(
        cfg, {k: jnp.asarray(v) for k, v in s["w0"].items()},
        jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]))
    s["want"] = dict(loss=loss, ce=rest[0], ce_mtp=rest[1], logits=rest[2],
                     mtp=rest[3], routing=rest[4], grads=grads)
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


@pytest.mark.parametrize("what", ["logits", "mtp"])
def test_logits_of_both_heads(small, what):
    _close(small[what], np.asarray(small["want"][what]).reshape(64, -1))


@pytest.mark.parametrize("what", ["loss", "ce", "ce_mtp"])
def test_both_cross_entropies_and_their_sum(small, what):
    _close(np.asarray(small[what]).reshape(()), small["want"][what])
    if what == "loss":
        want = small["want"]
        _close(want["loss"], want["ce"] + 0.3 * want["ce_mtp"], 1e-6)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_routing_is_by_score_plus_bias(small, layer):
    """Every expert layer (the module's is the last) chose the
    reference's top-k of score + bias; its counts are over all 8 experts
    and sum to top_k x tokens; the rows its products took are the counts
    of the 2 held experts."""
    ids, load, rows = small["routing"][layer]
    biased, top = small["want"]["routing"][layer]
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(top, 1))
    assert load.shape == (8,) and load.sum() == 2 * 64
    np.testing.assert_array_equal(load, np.bincount(np.asarray(top).ravel(),
                                                    minlength=8))
    assert int(rows[0]) == load[4:6].sum()
    # the bias decides for some token: the top-k of the scores alone is
    # another set
    p = f"xing.l{layer + 1}." if layer < 2 else "xing.mtp."
    scores = np.asarray(biased) - small["w0"][p + "router_bias"]
    plain = np.argsort(-scores, axis=1)[:, :2]
    assert (np.sort(plain, 1) != np.sort(np.asarray(top), 1)).any()


@pytest.mark.parametrize("which", SAMPLED)
def test_sampled_gradient_and_first_update(small, which):
    name = small["builder"].sampled_params(small["cfg"])[which]
    _close(small["grads"][name], small["want"]["grads"][name],
           _grad_tol(name))
    _close(small["w1"][name] - small["w0"][name],
           small["want"]["delta"][name], 3e-4)


def test_every_gradient(small):
    assert set(small["grads"]) == set(small["want"]["grads"])
    # 2e-10: float32 noise of the mixers' smallest gradients (5e-9 here;
    # the matrices' are 1e-2)
    for name, g in small["want"]["grads"].items():
        _close(small["grads"][name], g, _grad_tol(name), floor=2e-10)
    # held experts saw rows somewhere, so their gradients are not all zero
    assert any(np.abs(small["grads"][n]).max() > 0
               for n in small["grads"] if n.endswith(".gate"))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_bias_is_not_trained_and_follows_the_load(small, layer):
    """`noaux_tc`: no gradient reaches the bias; after the step it has
    risen by the speed for every expert under the mean of that step's
    choices and fallen by it for every one over it, in float32 under AMP
    or not."""
    name = (f"xing.l{layer + 1}." if layer < 2 else "xing.mtp.") \
        + "router_bias"
    assert name not in small["grads"]
    load = small["routing"][layer][1].astype(np.float64)
    speed = small["cfg"]["optimizer"]["router_bias_update_speed"]
    want = small["w0"][name] + np.float32(speed) * np.sign(
        load.mean() - load).astype(np.float32)
    assert np.any(load != load.mean())
    np.testing.assert_array_equal(small["w1"][name], want)


def test_the_inference_program_leaves_the_bias_alone(small):
    built = small["builder"].build(fluid, small["cfg"], 5)
    wrote = lambda prog: {n for op in prog.global_block().ops
                          for n in op.output_arg_names()
                          if n.endswith("router_bias")}
    assert wrote(built["test_prog"]) == set()
    assert wrote(built["prog"]) == {
        "xing.l1.router_bias", "xing.l2.router_bias", "xing.mtp.router_bias"}


def test_each_expert_layer_routes_on_its_own(small):
    """What the cell's roofline and utilization readers are given: the
    rows the held experts took differ from layer to layer, so one layer's
    count says nothing of another's."""
    rows = [int(r[0]) for _, _, r in small["routing"]]
    assert len(rows) == 3 and len(set(rows)) > 1
    assert set(small["builder"].build(fluid, small["cfg"], 5)) >= {
        "routing", "logits", "mtp_logits", "test_prog"}


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_down_product_wrote_the_held_rows_and_no_others(small, layer):
    """`DownOut` is in expert order, the held experts' rows first: the
    rows that are not all zero are exactly `RowsHeld` (what
    `compare_lm_share` holds the grouped kernels to on the chip)."""
    built = small["builder"].build(fluid, small["cfg"], 5)
    op = [o for o in built["test_prog"].global_block().ops
          if o.type == "moe_ffn"][layer]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        down, held, ids = exe.run(
            built["test_prog"], feed=small["feed"],
            fetch_list=[op.output(s)[0]
                        for s in ("DownOut", "RowsHeld", "ExpertIds")])
    written = np.any(np.asarray(down) != 0, axis=1)
    assert down.shape == (2 * 64, 64)
    assert written.sum() == int(held[0]) == ((ids >= 4) & (ids < 6)).sum()
    assert written[:int(held[0])].all()


@pytest.mark.parametrize("place", ["cpu", "tpu"])
def test_lowered_counts_name_the_share_and_the_kernels_alone(small, place):
    """Three expert blocks lowered with a share; on a TPU place the flash
    kernels of the 4 blocks. No counter that only restates an op count."""
    import types
    from paddle_tpu.ops import lm_ops

    prog = small["builder"].build(fluid, small["cfg"], 5)["prog"]
    got = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform=place))
    # the program leaves the tokens open: the rows are taken to be many
    want = {"moe_ffn_grouped": 3, "moe_ffn_held_experts": 3,
            "moe_ffn_row_bound": 3}
    if place == "tpu":
        want.update(flash_attention=4, flash_attention_bwd=4,
                    flash_fwd_visited_blocks=4, flash_fwd_masked_blocks=4)
    assert got == want


# ----------------------------------------------------------- the share
def _uncut():
    """An uncut tiny layer: 8 experts, 4 heads, and seeded weights."""
    cfg = _cfg(n_routed_experts=8, num_attention_heads=4,
               num_key_value_heads=4,
               deployment=dict(n_routed_experts=8, first_expert=0))
    from chipbench.reference import xing4_0_29b_a4b as ref

    rs = np.random.default_rng(11)
    w = {n: jnp.asarray(rs.normal(0, 0.05 if "bias" in n else 0.08, s),
                        jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    u = jnp.asarray(rs.normal(0, 1, (2, 32, 64)), jnp.float32)
    return cfg, ref, w, u


def _program_part(build, weights, feed):
    """Run a small program of the model's own layer functions with the
    given weights; returns its output."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        u = fluid.layers.data(name="u", shape=[64], dtype="float32")
        out = build(u)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in prog.global_block().all_parameters():
            scope.set_var(p.name, np.asarray(weights[p.name]))
        got = exe.run(prog, feed={"u": feed}, fetch_list=list(
            out if isinstance(out, (list, tuple)) else [out]))
    return np.asarray(got[0]) if len(got) == 1 else [
        np.asarray(g) for g in got]


EXPERT_SHARES = [(0, 2), (2, 2), (4, 2), (6, 2)]
HEAD_SHARES = [(0, 2), (2, 2)]


def _held_rows_and_all(y, routing):
    return [y, routing[2]]


@pytest.fixture(scope="module", params=[False, True],
                ids=["as_drawn", "a_router_that_overflows_the_row_bound"])
def expert_parts(request):
    """The second time every token chooses expert 0 (its bias raised by
    10), and the expert layer's row bound is 64 of the 128 choice rows (a
    row tile of 16 instead of 512, which covers any bound at this size):
    the first share receives more than 64 and takes the overflow branch,
    the other three stay within the bound."""
    from unittest import mock
    from paddle_tpu.models import xing4
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import grouped

    cfg, ref, w, u = _uncut()
    tiles = grouped.ROW_TILES
    if request.param:
        tiles = (16,)
        w = dict(w, **{"xing.l1.router_bias":
                       w["xing.l1.router_bias"].at[0].add(10.0)})
    flat = u.reshape(64, 64)
    with jax.default_matmul_precision("highest"):
        part_all, shared, _ = ref.experts(flat, w, "xing.l1.", cfg)
    parts, rows = [], []
    with mock.patch.object(grouped, "ROW_TILES", tiles):
        bound = lm_ops.row_bound(128, 2, 8)
        for first, n in EXPERT_SHARES:
            c, ws = ref.share_of(cfg, w, first, n, 0, 4)
            got, held = _program_part(
                lambda x, c=c: _held_rows_and_all(
                    *xing4.experts(x, c, "xing.l1.")), ws, np.asarray(flat))
            with jax.default_matmul_precision("highest"):
                want, _, _ = ref.experts(flat, ws, "xing.l1.", c)
            parts.append((got, np.asarray(want)))
            rows.append(int(held[0]))
    assert sum(rows) == 128
    assert bound == (64 if request.param else 128)
    assert (rows[0] > 64) == request.param and 0 < max(rows[1:]) <= 64
    return np.asarray(part_all), np.asarray(shared), parts


@pytest.mark.parametrize("share", range(len(EXPERT_SHARES)))
def test_an_expert_share_is_the_reference_s_share(expert_parts, share):
    """The program's expert layer told to hold experts [first, +2) of 8:
    the reference's held part for that share, plus the shared expert."""
    _, shared, parts = expert_parts
    got, want = parts[share]
    _close(got, want + shared)
    assert np.abs(want).max() > 0


def test_expert_shares_add_up_to_the_uncut_layer(expert_parts):
    """Over the 4 shares, the held experts' parts plus the shared expert
    counted ONCE are the uncut reference's expert branch."""
    part_all, shared, parts = expert_parts
    total = sum(got - shared for got, _ in parts) + shared
    _close(total, part_all + shared)


@pytest.fixture(scope="module")
def head_parts():
    from paddle_tpu.models import xing4

    cfg, ref, w, u = _uncut()
    with jax.default_matmul_precision("highest"):
        whole = ref.attention(u, w, "xing.l1.", cfg)
    parts = []
    for first, n in HEAD_SHARES:
        c, ws = ref.share_of(cfg, w, 0, 8, first, n)
        got = _program_part(
            lambda x, c=c: xing4.latent_attention(x, c, 32, "xing.l1."), ws,
            np.asarray(u.reshape(64, 64)))
        with jax.default_matmul_precision("highest"):
            want = ref.attention(u, ws, "xing.l1.", c)
        parts.append((got, np.asarray(want).reshape(64, 64)))
    return np.asarray(whole).reshape(64, 64), parts


@pytest.mark.parametrize("share", range(len(HEAD_SHARES)))
def test_a_head_share_is_the_reference_s_share(head_parts, share):
    got, want = head_parts[1][share]
    _close(got, want)


def test_head_shares_add_up_to_the_uncut_layer(head_parts):
    whole, parts = head_parts
    _close(sum(got for got, _ in parts), whole)


def test_vocabulary_share_is_a_slice():
    cfg, ref, w, _ = _uncut()
    c, ws = ref.share_of(cfg, w, 0, 8, 0, 4, first_row=64, n_rows=32)
    assert c["vocab_size"] == 32 and ws["xing.embed"].shape == (32, 64)
    np.testing.assert_array_equal(ws["xing.head"], w["xing.head"][:, 64:96])
    assert {n: tuple(v.shape) for n, v in ws.items()} == {
        n: tuple(s) for n, s in ref.param_shapes(c).items()}


# ------------------------------------------------------------- the ops
def _one_op(op_type, inputs, outputs, attrs, feeds):
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        block = prog.global_block()
        for name, v in feeds.items():
            block.create_var(name=name, shape=v.shape, dtype=str(v.dtype))
        outs = {slot: [block.create_var(name=slot.lower(), dtype="float32")]
                for slot in outputs}
        block.append_op(op_type, {s: [n] for s, n in inputs.items()}, outs,
                        attrs)
    exe = fluid.Executor(fluid.CPUPlace())
    return exe.run(prog, feed=feeds, fetch_list=[slot.lower()
                                                 for slot in outputs])


@pytest.mark.parametrize("seed,scale,alpha,rows_within", [
    (0, 1.0, 0.01, 1e-4), (1, 1.0, 0.01, 1e-4), (2, 1.0, 0.01, 1e-4),
    (3, 4.0, 1.0, 0.1), (4, 12.0, 1.0, 0.2)],
    ids=["as_drawn_0", "as_drawn_1", "as_drawn_2", "harsh", "harsher"])
def test_sinkhorn_makes_doubly_stochastic_matrices(seed, scale, alpha,
                                                   rows_within):
    """After 20 rounds the mixing matrices' rows and columns sum to 1
    within 1e-4 for mixers as the model draws them (alpha 0.01, biases of
    std 1). A harsher matrix (entries e^-30 to e^30 after the clip) is
    not converged by 20 rounds: its columns, normalised last, still sum
    to 1, its rows nearly. HPost lies in (0, 2)."""
    rs = np.random.default_rng(seed)
    n, C, T = 4, 16, 24
    feeds = {"x": rs.normal(0, 1, (n, T, C)).astype(np.float32),
             "phi_pre": rs.normal(0, .3, (n * C, n)).astype(np.float32),
             "phi_post": rs.normal(0, .3, (n * C, n)).astype(np.float32),
             "phi_res": rs.normal(0, .3, (n * C, n * n)).astype(np.float32),
             "alpha": np.full(3, alpha, np.float32),
             "b_pre": rs.normal(0, 1, n).astype(np.float32),
             "b_post": rs.normal(0, 1, n).astype(np.float32),
             "b_res": rs.normal(0, scale, n * n).astype(np.float32)}
    u, h_post, h_res = _one_op(
        "mhc_mix", dict(X="x", PhiPre="phi_pre", PhiPost="phi_post",
                        PhiRes="phi_res", Alpha="alpha", BPre="b_pre",
                        BPost="b_post", BRes="b_res"),
        ("U", "HPost", "HRes"),
        dict(epsilon=1e-6, sinkhorn_iters=20, clamp_min=-30.0,
             clamp_max=30.0), feeds)
    assert h_res.shape == (T, n, n) and (h_res >= 0).all()
    assert np.abs(h_res.sum(axis=2) - 1).max() < rows_within
    assert np.abs(h_res.sum(axis=1) - 1).max() < 1e-4
    assert u.shape == (T, C) and (0 < h_post).all() and (h_post < 2).all()
    from chipbench.reference import xing4_0_29b_a4b as ref

    cfg = dict(hc_mult=n, hc_eps=1e-6, hc_sinkhorn_iters=20,
               mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
    with jax.default_matmul_precision("highest"):
        pre, post, res = ref.mixers(
            jnp.swapaxes(jnp.asarray(feeds["x"]), 0, 1),
            {"m." + k: jnp.asarray(v) for k, v in feeds.items()}, "m.", cfg)
    _close(h_res, res, 2e-5)
    _close(h_post, post)
    _close(u, jnp.einsum("tn,ntc->tc", pre, feeds["x"]))


def test_bf16_state_meets_float32_mixers_exactly():
    """Under AMP the state is bfloat16 and the mixers' matrices float32:
    the projections are the float32 product of the bfloat16 values, not a
    product of rounded matrices (`_exact_dot`), in the forward and in the
    matrices' gradient."""
    from paddle_tpu.ops import lm_ops

    rs = np.random.default_rng(3)
    a = jnp.asarray(rs.normal(0, 1, (48, 256)), jnp.bfloat16)
    b = jnp.asarray(rs.normal(0, 1, (256, 24)), jnp.float32)
    g = jnp.asarray(rs.normal(0, 1, (48, 24)), jnp.float32)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    got = lm_ops._exact_dot(a, b, (((1,), (0,)), ((), ())))
    assert got.dtype == jnp.float32
    _close(got, want, 2e-6)
    rounded = np.asarray(a, np.float64) @ np.asarray(
        b.astype(jnp.bfloat16), np.float64)
    assert np.abs(rounded - want).max() > 1e-3 * np.abs(want).max()
    _close(lm_ops._exact_dot(a, g, (((0,), (0,)), ((), ()))),
           np.asarray(a, np.float64).T @ np.asarray(g, np.float64), 2e-6)


@pytest.mark.parametrize("on_kernel", [False, True],
                         ids=["plain", "flash_interpreted"])
def test_attention_with_values_narrower_than_keys(monkeypatch, on_kernel):
    """`causal_attention` at Q, K [1, 48, 2, 24], V [1, 48, 2, 16] and a
    scale that is not 1 / sqrt(D): output and all three gradients against
    a plain softmax, through the plain composition and through the flash
    kernels (interpreted)."""
    from paddle_tpu.ops import lm_ops

    monkeypatch.setattr(lm_ops, "on_tpu", lambda: on_kernel)
    rs = np.random.default_rng(4)
    q, k = (jnp.asarray(rs.normal(0, 1, (1, 48, 2, 24)), jnp.float32)
            for _ in range(2))
    v, cot = (jnp.asarray(rs.normal(0, 1, (1, 48, 2, 16)), jnp.float32)
              for _ in range(2))
    scale = 0.37

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.arange(48)[:, None] >= jnp.arange(48)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    want, vjp = jax.vjp(plain, q, k, v)
    outs = lm_ops.causal_attention_op(
        None, {"Q": [q], "K": [k], "V": [v]}, {"scale": scale})
    o, lse = outs["Out"][0], outs["Lse"][0]
    assert o.shape == (1, 48, 2, 16) and lse.shape == (1, 2, 48)
    _close(o, want, 2e-5)
    grads = lm_ops.causal_attention_grad_op(
        None, {"Q": [q], "K": [k], "V": [v], "Out": [o], "Lse": [lse],
               "Out@GRAD": [cot]}, {"scale": scale})
    for slot, g in zip(("Q@GRAD", "K@GRAD", "V@GRAD"), vjp(cot)):
        _close(grads[slot][0], g, 1e-4)


def test_yarn_frequencies_are_the_reference_s():
    from chipbench.reference import xing4_0_29b_a4b as ref
    from paddle_tpu.ops import lm_ops

    cfg = _cfg(qk_rope_head_dim=64, rope_scaling=dict(
        beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=4096, type="yarn"))
    got = lm_ops.rotary_frequencies(64, 10000, 64.0, 32, 1, 4096)
    np.testing.assert_allclose(got, ref.yarn_frequencies(cfg), rtol=1e-6)
    plain = lm_ops.rotary_frequencies(64, 10000)
    # fast pairs keep their frequency, slow ones have it divided by 64
    np.testing.assert_allclose(got[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(got[-4:], plain[-4:] / 64, rtol=1e-6)
    assert ((got <= plain * (1 + 1e-6)) & (got >= plain / 64 * (1 - 1e-6))
            ).all()
    # m = 0.1 ln 64 + 1, squared into the softmax scale
    from paddle_tpu.models import xing4
    full = dict(cfg, qk_nope_head_dim=128)
    assert xing4.softmax_scale(full) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert ref.softmax_scale(full) == xing4.softmax_scale(full)
