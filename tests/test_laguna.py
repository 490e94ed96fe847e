"""`paddle_tpu.models.laguna` at a small size (hidden 64, 16 experts of
which 4 held, query heads 6 / 8 by layer type of which all or an eighth
held, one or two key/value heads, a window of 8 in rows of 32, layers
[full + dense, window, window, full], 2 x 32 tokens) against the plain
float32 reference of `chipbench/reference/laguna_xs_2.py`, on seeded
weights read out of the scope; the ops the model forced (`causal_attention`
with a window and grouped-query heads, `rotary_embedding` with a partial
rotation and YaRN's attention factor, two rotary tables in one model, the
per-head gate); and the tests that tie a chip's share to the model: the
parts all shares of a layer give, with what every chip computes alike
counted once, add up to the uncut reference's layer.

Tolerance: float32 against float32 on the CPU; the two differ in the order
of float32 sums only: 1e-5 of the largest element, as tests/test_xing4.py
has it. The first AdamW step is judged on the gradients the system itself
produced, for the reason given there.
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

FULL, WINDOW = "full_attention", "sliding_attention"
SMALL = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_hidden_layers=4, head_dim=16,
    num_attention_heads=3, num_attention_heads_per_layer=[3, 4, 4, 3],
    num_key_value_heads=1, num_experts=4, num_experts_per_tok=2,
    sliding_window=8, vocab_size=256, sequence_length=32,
    layer_types=[FULL, WINDOW, WINDOW, FULL, WINDOW],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    deployment=dict(num_experts=16, first_expert=8))
PEAK_RATE = 3e-4     # a recipe's (the file's `assumed.optimizer`)
SAMPLED = ("head", "embedding", "w_q_full", "w_k_full", "w_q_window",
           "w_k_window", "w_v", "w_g", "w_o", "router", "expert_gate",
           "expert_up", "expert_down", "shared_gate", "shared_up",
           "shared_down", "norm_scale")
T = 64


def _file():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "laguna_xs_2.json")) as f:
        return json.load(f)


def _cfg(**changes):
    """The configuration file at the small sizes, at a recipe's peak
    learning rate (the cell's 1e-6 makes a step smaller than half an ulp
    of a norm scale: nothing an update could be judged by)."""
    cfg = dict(_file(), **dict(SMALL, **changes))
    cfg["optimizer"] = dict(cfg["optimizer"], learning_rate=PEAK_RATE)
    return cfg


def _close(got, want, tol=1e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= floor + tol * max(
        np.max(np.abs(want)), 1e-30)


def _run_small(cfg, seed=5):
    """The system's numbers on one seeded batch: weights as drawn but the
    router's bias (set non-zero: choosing by score + bias and weighing by
    score then differ), logits, loss, routing, the attention branches,
    every gradient, the weights after one step."""
    from chipbench.configs import laguna_xs_2 as builder

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
                scope.set_var(n, rs.normal(0, 0.03, 16).astype(np.float32))
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
    parts the file gives."""
    from chipbench.configs import laguna_xs_2 as builder

    cfg = _file()
    prog = builder.build(fluid, cfg, 1)["prog"]
    sizes = {p.name: int(np.prod(p.shape))
             for p in prog.global_block().all_parameters()
             if builder.reference.trained(p.name)}
    assert sum(sizes.values()) == cfg["parameters"] == 540637184
    parts = cfg["parameters_by_part"]

    def of(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert of("laguna.l0.") == parts["layer_0_full_attention_dense_mlp"]
    assert of("laguna.l1.") == of("laguna.l2.") == of("laguna.l3.") \
        == parts["window_expert_layer"]
    assert of("laguna.l4.") == parts["full_expert_layer"]
    assert of("laguna.l0.w_") == parts["attention_full_layer"]
    assert of("laguna.l1.w_") == parts["attention_window_layer"]
    assert sizes["laguna.embed"] + sizes["laguna.head"] \
        == parts["embedding_and_head"]
    # no width differs from the published config; the floors are kept
    for key, want in dict(hidden_size=2048, intermediate_size=8192,
                          moe_intermediate_size=512, head_dim=128,
                          shared_expert_intermediate_size=512,
                          num_experts_per_tok=8, sliding_window=512).items():
        assert cfg[key] == want
    assert cfg["num_hidden_layers"] == 5 and cfg["num_experts"] == 32
    assert cfg["vocab_size"] * 8 == cfg["deployment"]["vocab_size"]
    assert cfg["layer_types"][:5] == [FULL, WINDOW, WINDOW, WINDOW, FULL]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4


def test_logits(small):
    _close(small["logits"], np.asarray(small["want"]["logits"]).reshape(
        T, -1))


def test_loss(small):
    _close(np.asarray(small["loss"]).reshape(()), small["want"]["loss"])


@pytest.mark.parametrize("layer", [0, 1, 2, 3])
def test_attention_branch_of_each_layer_first_hand(small, layer):
    """A full and a window layer's branch against the reference on the
    SAME normed input (what `compare_lm_window_share` does on the chip):
    a band off by one, a wrong key/value head, a rotary on the wrong half
    or a missing gate fails here by itself."""
    u, branch = small["attention"][layer]
    want = small["ref"].attention_branch(
        small["cfg"], {k: jnp.asarray(v) for k, v in small["w0"].items()},
        layer, jnp.asarray(u).reshape(2, 32, -1))
    _close(branch, np.asarray(want).reshape(T, -1))
    assert np.abs(branch).max() > 0


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_routing_is_by_score_plus_bias(small, layer):
    ids, load, rows = small["routing"][layer]
    biased, top = small["want"]["routing"][layer]
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(top, 1))
    assert load.shape == (16,) and load.sum() == 2 * T
    np.testing.assert_array_equal(load, np.bincount(np.asarray(top).ravel(),
                                                    minlength=16))
    assert int(rows[0]) == load[8:12].sum()
    scores = np.asarray(biased) - small["w0"][
        f"laguna.l{layer + 1}.router_bias"]
    plain = np.argsort(-scores, axis=1)[:, :2]
    assert (np.sort(plain, 1) != np.sort(np.asarray(top), 1)).any()


@pytest.mark.parametrize("which", SAMPLED)
def test_sampled_gradient_and_first_update(small, which):
    name = small["builder"].sampled_params(small["cfg"])[which]
    _close(small["grads"][name], small["want"]["grads"][name])
    _close(small["w1"][name] - small["w0"][name],
           small["want"]["delta"][name], 3e-4)


def test_every_gradient(small):
    assert set(small["grads"]) == set(small["want"]["grads"])
    for name, g in small["want"]["grads"].items():
        _close(small["grads"][name], g, floor=2e-10)
    assert any(np.abs(small["grads"][n]).max() > 0
               for n in small["grads"] if n.endswith(".gate"))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_bias_is_not_trained_and_follows_the_load(small, layer):
    name = f"laguna.l{layer + 1}.router_bias"
    assert name not in small["grads"]
    load = small["routing"][layer][1].astype(np.float64)
    speed = small["cfg"]["optimizer"]["router_bias_update_speed"]
    want = small["w0"][name] + np.float32(speed) * np.sign(
        load.mean() - load).astype(np.float32)
    assert np.any(load != load.mean())
    np.testing.assert_array_equal(small["w1"][name], want)


def test_the_decay_acts_at_the_recipe_s_rate(small):
    """What the cell's weakened update check cannot see (`distorts`): with
    the decay left out of the expected step a matrix's update is off by
    more than the tolerance, a norm scale's is not."""
    o = small["cfg"]["optimizer"]
    name = "laguna.l1.w_q"
    no_decay, _ = small["ref"].adamw_first_update(
        dict(small["cfg"], optimizer=dict(o, weight_decay=0.0)),
        small["w0"], {k: jnp.asarray(v) for k, v in small["grads"].items()},
        epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    got = small["w1"][name] - small["w0"][name]
    assert np.abs(got - np.asarray(no_decay[name])).max() \
        > 1e-3 * np.abs(got).max()
    assert not small["ref"].decays("laguna.l1.attn_norm")


@pytest.mark.parametrize("place", ["cpu", "tpu"])
def test_lowered_counts_name_windows_and_head_groups(small, place):
    import types
    from paddle_tpu.ops import lm_ops

    prog = small["builder"].build(fluid, small["cfg"], 5)["prog"]
    got = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform=place))
    # the program leaves the tokens open: the rows are taken to be many
    want = {"moe_ffn_grouped": 3, "moe_ffn_held_experts": 3,
            "moe_ffn_row_bound": 3}
    if place == "tpu":
        want.update(flash_attention=4, flash_attention_bwd=4,
                    flash_attention_window=2, flash_attention_head_groups=4,
                    flash_fwd_visited_blocks=4, flash_fwd_masked_blocks=4)
    assert got == want


def test_window_blocks_are_counted_from_the_shapes():
    """At the published shapes: three window layers of 8 heads at 8192
    with a band of 512, against the same grids over the triangle."""
    from chipbench.configs import laguna_xs_2 as builder
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import flash

    prog = builder.build(fluid, _file(), 1)["prog"]
    visited, whole = lm_ops.window_blocks(prog)
    side = lm_ops.flash_blocks(512)["block_q"]
    assert lm_ops.flash_blocks(512, backward=True)["block_q"] == side
    n = 3 * 8 * 3      # layers x heads x (forward, dK/dV, dQ)
    assert visited == n * flash.blocks_visited(8192, 8192, side, side, 512)
    assert whole == n * flash.blocks_visited(8192, 8192, side, side)
    assert side == 512 and 0 < visited < 0.25 * whole
    # the forward's blocks a head, and those that pay for the mask (PR 41):
    # two full layers at 1024 x 1024 (36, the diagonal's 8), three bands of
    # 512 at 512 x 512 (31, every one crossed by an edge)
    import types
    got = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform="tpu"))
    assert got["flash_fwd_visited_blocks"] == 2 * 36 + 3 * 31
    assert got["flash_fwd_masked_blocks"] == 2 * 8 + 3 * 31
    assert "flash_fwd_masked_blocks" not in lm_ops.lowered_counts(
        prog, types.SimpleNamespace(platform="cpu"))
    assert lm_ops.flash_blocks(None) == lm_ops.FLASH_FWD_BLOCKS
    assert lm_ops.flash_blocks(0, True) == lm_ops.FLASH_BWD_BLOCKS
    # a program with no window layer has nothing to count
    cfg = _cfg(layer_types=[FULL] * 5)
    assert lm_ops.window_blocks(builder.build(fluid, cfg, 1)["prog"]) \
        == (0, 0)


# ----------------------------------------------------------- the share
CHIPS = 2


def _uncut():
    """An uncut tiny model: 16 experts, 6 / 8 query heads by layer type on
    2 key/value heads, and seeded weights."""
    cfg = _cfg(num_experts=16, num_attention_heads=6,
               num_attention_heads_per_layer=[6, 8, 8, 6],
               num_key_value_heads=2,
               deployment=dict(num_experts=16, first_expert=0))
    from chipbench.reference import laguna_xs_2 as ref

    rs = np.random.default_rng(11)
    w = {n: jnp.asarray(rs.normal(0, 0.05 if "bias" in n else 0.08, s),
                        jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    u = jnp.asarray(rs.normal(0, 1, (2, 32, 64)), jnp.float32)
    return cfg, ref, w, u


def _program_part(build, weights, feed):
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


def _held_rows_and_all(y, routing):
    return [y, routing[2]]


@pytest.fixture(scope="module", params=[False, True],
                ids=["as_drawn", "a_router_that_overflows_the_row_bound"])
def expert_parts(request):
    """The second time every token chooses expert 0 (its bias raised by
    10), and the expert layer's row bound is 64 of the 128 choice rows (a
    row tile of 16 instead of 512, which covers any bound at this size):
    the first chip receives more than 64 and takes the overflow branch,
    the other three stay within the bound."""
    from unittest import mock
    from paddle_tpu.models import laguna
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import grouped

    cfg, ref, w, u = _uncut()
    tiles = grouped.ROW_TILES
    if request.param:
        tiles = (16,)
        w = dict(w, **{"laguna.l1.router_bias":
                       w["laguna.l1.router_bias"].at[0].add(10.0)})
    flat = u.reshape(T, 64)
    with jax.default_matmul_precision("highest"):
        part_all, shared, _ = ref.experts(flat, w, "laguna.l1.", cfg)
    parts, rows = [], []
    with mock.patch.object(grouped, "ROW_TILES", tiles):
        bound = lm_ops.row_bound(2 * T, 4, 16)
        for chip in range(4):
            c, ws = ref.share_of(cfg, w, chip, 4)
            got, held = _program_part(
                lambda x, c=c: _held_rows_and_all(
                    *laguna.experts(x, c, "laguna.l1.")), ws,
                np.asarray(flat))
            with jax.default_matmul_precision("highest"):
                want, _, _ = ref.experts(flat, ws, "laguna.l1.", c)
            parts.append((got, np.asarray(want)))
            rows.append(int(held[0]))
    assert sum(rows) == 2 * T
    assert bound == (64 if request.param else 2 * T)
    assert (rows[0] > 64) == request.param and 0 < max(rows[1:]) <= 64
    return np.asarray(part_all), np.asarray(shared), parts


@pytest.mark.parametrize("chip", range(4))
def test_an_expert_share_is_the_reference_s_share(expert_parts, chip):
    _, shared, parts = expert_parts
    got, want = parts[chip]
    _close(got, want + shared)
    assert np.abs(want).max() > 0


def test_expert_shares_add_up_to_the_uncut_layer(expert_parts):
    """Over the 4 chips, the held experts' parts plus the shared expert
    counted ONCE are the uncut reference's expert branch."""
    part_all, shared, parts = expert_parts
    total = sum(got - shared for got, _ in parts) + shared
    _close(total, part_all + shared)


@pytest.fixture(scope="module", params=[0, 1], ids=["full", "window"])
def head_parts(request):
    """Layer 0 (full: 6 heads, half of each rotated, YaRN) and layer 1
    (window: 8 heads, whole heads rotated, a band of 8), each divided over
    2 chips that hold 3 or 4 query heads and one key/value head."""
    from paddle_tpu.models import laguna

    layer = request.param
    cfg, ref, w, u = _uncut()
    p, kind = f"laguna.l{layer}.", cfg["layer_types"][layer]
    with jax.default_matmul_precision("highest"):
        whole = ref.attention(u, w, p, cfg, kind, ref.heads_of(cfg, layer))
    parts = []
    for chip in range(CHIPS):
        c, ws = ref.share_of(cfg, w, chip, CHIPS)
        heads = ref.heads_of(c, layer)
        got = _program_part(
            lambda x, c=c, heads=heads: laguna.attention(
                x, c, 32, p, kind, heads), ws,
            np.asarray(u.reshape(T, 64)))
        with jax.default_matmul_precision("highest"):
            want = ref.attention(u, ws, p, c, kind, heads)
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
    cfg, ref, w, _ = _uncut()
    c, ws = ref.share_of(cfg, w, 1, 2)
    assert c["vocab_size"] == 128 and ws["laguna.embed"].shape == (128, 64)
    np.testing.assert_array_equal(ws["laguna.head"],
                                  w["laguna.head"][:, 128:])
    assert {n: tuple(v.shape) for n, v in ws.items()} == {
        n: tuple(s) for n, s in ref.param_shapes(c).items()}
    assert c["deployment"]["first_expert"] == 8 and c["num_experts"] == 8


# ------------------------------------------------------------- the ops
def _rotary_written_out(x, theta, rotary_dim, factor=1.0, freq=None):
    """Rotary by the definition, a pair at a time: numbers i and i + R/2
    of the first R of a head turn by position x frequency_i."""
    x = np.asarray(x, np.float64)
    B, S, H, D = x.shape
    R = rotary_dim
    if freq is None:
        freq = theta ** (-np.arange(0, R, 2) / R)
    out = x.copy()
    for s in range(S):
        for i in range(R // 2):
            c, sn = np.cos(s * freq[i]) * factor, np.sin(s * freq[i]) * factor
            a, b = x[:, s, :, i], x[:, s, :, i + R // 2]
            out[:, s, :, i] = a * c - b * sn
            out[:, s, :, i + R // 2] = b * c + a * sn
    return out


@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_the_two_rotary_tables_against_one_written_out(kind):
    """Full layers: the first half of each head, YaRN's frequencies, cos
    and sin x 1.4159, the other half untouched. Window layers: the whole
    head, plain theta 10000. The op (through the layer and the model's
    `rotary_of`) and the reference's table against the definition."""
    from chipbench.reference import laguna_xs_2 as ref
    from paddle_tpu.models import laguna
    from paddle_tpu.ops import lm_ops

    cfg = _cfg()
    rs = np.random.default_rng(2)
    x = rs.normal(0, 1, (2, 12, 3, 16)).astype(np.float32)
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        xv = fluid.layers.data(name="x", shape=[12, 3, 16], dtype="float32")
        y = fluid.layers.rotary_embedding(xv, **laguna.rotary_of(cfg, kind))
    got, = fluid.Executor(fluid.CPUPlace()).run(prog, feed={"x": x},
                                                fetch_list=[y])
    rp = cfg["rope_parameters"][kind]
    if kind == FULL:
        freq = np.asarray(lm_ops.rotary_frequencies(8, 500000, 64.0, 64, 1,
                                                    16))
        plain = 500000.0 ** (-np.arange(0, 8, 2) / 8)
        # fast pairs keep their frequency, the slowest has it divided by 64
        assert freq[0] == pytest.approx(plain[0])
        assert freq[-1] == pytest.approx(plain[-1] / 64, rel=1e-5)
        want = _rotary_written_out(x, None, 8, rp["attention_factor"], freq)
        np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
        assert prog.global_block().ops[-1].attrs["rotary_dim"] == 8
    else:
        want = _rotary_written_out(x, 10000.0, 16)
        assert "rotary_dim" not in prog.global_block().ops[-1].attrs
    _close(got, want, 2e-6)
    cos, sin = ref.rotary_table(cfg, kind, 12)
    _close(ref.rope(jnp.asarray(x), cos, sin), want, 2e-6)


@pytest.mark.parametrize("on_kernel", [False, True],
                         ids=["plain", "flash_interpreted"])
@pytest.mark.parametrize("window,kv_heads", [(0, 1), (8, 1), (8, 2),
                                             (100, 1)])
def test_attention_op_with_a_window_and_head_groups(monkeypatch, on_kernel,
                                                    window, kv_heads):
    """`causal_attention` at Q [1, 48, 4, 16], K, V [1, 48, kv, 16] with
    the attr `window`: output and all three gradients against a softmax
    with the mask written out, through the plain composition and through
    the flash kernels (interpreted)."""
    from paddle_tpu.ops import lm_ops

    monkeypatch.setattr(lm_ops, "on_tpu", lambda: on_kernel)
    rs = np.random.default_rng(4)
    q, cot = (jnp.asarray(rs.normal(0, 1, (1, 48, 4, 16)), jnp.float32)
              for _ in range(2))
    k, v = (jnp.asarray(rs.normal(0, 1, (1, 48, kv_heads, 16)), jnp.float32)
            for _ in range(2))

    def plain(q, k, v):
        k, v = (jnp.repeat(t, 4 // kv_heads, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
        back = jnp.arange(48)[:, None] - jnp.arange(48)[None, :]
        mask = (back >= 0) & ((back < window) if window else True)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    want, vjp = jax.vjp(plain, q, k, v)
    attrs = {"window": window} if window else {}
    outs = lm_ops.causal_attention_op(
        None, {"Q": [q], "K": [k], "V": [v]}, attrs)
    o, lse = outs["Out"][0], outs["Lse"][0]
    assert o.shape == (1, 48, 4, 16) and lse.shape == (1, 4, 48)
    _close(o, want, 2e-5)
    grads = lm_ops.causal_attention_grad_op(
        None, {"Q": [q], "K": [k], "V": [v], "Out": [o], "Lse": [lse],
               "Out@GRAD": [cot]}, attrs)
    for slot, g in zip(("Q@GRAD", "K@GRAD", "V@GRAD"), vjp(cot)):
        assert grads[slot][0].shape == g.shape
        _close(grads[slot][0], g, 1e-4)
