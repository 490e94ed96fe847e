"""`paddle_tpu.models.keye_vl` at a small size with every published RATIO
kept (hidden 64; 8 query heads on 2 key/value heads of 16; an indexer of 4
heads of 8 on one key head, topk 16; top-8 of 32 routed experts of which 8
held; four layers; 2 x 48 tokens) against the plain float32 reference of
`chipbench/reference/keye_vl_2_0_30b_a3b.py`, whose selection is
`lax.top_k` a row, on seeded weights read out of the scope; the three new
ops and their hand-written gradients alone against `jax.grad` of the plain
form; the masked flash kernels interpreted; and the tests that tie a
chip's share to the model.

Tolerance: float32 against float32 on the CPU; the two differ in the order
of float32 sums only: 1e-5 of the largest element, as tests/test_lfm2.py
has it (the indexer's gradients sum exponentials over a row: 1e-4). The
first AdamW step is judged on the gradients the system itself produced,
for the reason given in tests/test_xing4.py.
"""

import functools
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

PEAK_RATE = 3e-4     # a recipe's (the file's `assumed.optimizer`)
S, T, E_ALL, P = 48, 96, 32, "keyevl."


def _file():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "keye_vl_2_0_30b_a3b.json")) as f:
        return json.load(f)


def _cfg(**changes):
    cfg = _file()
    small = dict(
        hidden_size=64, moe_intermediate_size=32, num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, num_experts=8,
        num_local_experts=8, vocab_size=256, sequence_length=S,
        sa_config=dict(cfg["sa_config"], indexer_num_heads=4,
                       indexer_head_dim=8, topk=16),
        deployment=dict(cfg["deployment"], num_experts=E_ALL,
                        first_expert=8))
    cfg = dict(cfg, **dict(small, **changes))
    cfg["optimizer"] = dict(cfg["optimizer"], learning_rate=PEAK_RATE)
    return cfg


def _close(got, want, tol=1e-5, floor=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= floor + tol * max(
        np.max(np.abs(want)), 1e-30)


def _run_small(cfg, seed=5):
    """The system's numbers on one seeded batch: weights as drawn but the
    routers' and the indexers' (std 0.5: logits and scores far enough apart
    that float32 sums in another order flip no choice, and an indexer that
    matters)."""
    from chipbench.configs import keye_vl_2_0_30b_a3b as builder

    built = builder.build(fluid, cfg, seed)
    ref = builder.reference
    rs = np.random.default_rng(0)
    feed = {"tokens": rs.integers(0, 256, (2, S)).astype(np.int32),
            "labels": rs.integers(0, 256, (2, S)).astype(np.int32)}
    params = built["prog"].global_block().all_parameters()
    names = [p.name for p in params]
    shapes = {p.name: tuple(p.shape) for p in params}
    trained = [n for n in names if ref.trained(n)]
    masks = [own[2][6] for own in built["attention"]]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        for n in names:
            if n.endswith(("router", "w_qi", "w_ki", "w_w")):
                scope.set_var(n, rs.normal(0, 0.5, shapes[n]).astype(
                    np.float32))
        w0 = {n: np.asarray(scope.find_var(n)) for n in names}
        routing = [v for r in built["routing"] for v in r]
        got = exe.run(built["prog"], feed=feed, fetch_list=(
            [built["loss"], built["ce"], built["indexer_loss"],
             built["logits"]] + built["indexer_losses"] + masks + routing
            + [n + "@GRAD" for n in trained]))
        w1 = {n: np.asarray(scope.find_var(n)) for n in names}
    n_l = cfg["num_hidden_layers"]
    rest = got[4 + 2 * n_l:]
    return dict(
        cfg=cfg, ref=ref, builder=builder, built=built, feed=feed,
        names=names, w0=w0, w1=w1, loss=got[0], ce=got[1],
        indexer_loss=got[2], logits=got[3], indexer_losses=got[4:4 + n_l],
        masks=got[4 + n_l:4 + 2 * n_l],
        routing=[rest[3 * i:3 * i + 3] for i in range(n_l)],
        grads=dict(zip(trained, rest[3 * n_l:])))


@pytest.fixture(scope="module")
def small():
    s = _run_small(_cfg())
    ref, cfg, feed = s["ref"], s["cfg"], s["feed"]
    wj = {k: jnp.asarray(v) for k, v in s["w0"].items()}
    t, l = jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"])
    loss, rest, grads = ref.loss_and_grads(cfg, wj, t, l)
    s["want"] = dict(loss=loss, logits=rest[0], routing=rest[1], ce=rest[2],
                     indexer_losses=rest[3], masks=rest[4], grads=grads)
    # each part of the loss alone: which parameters it reaches
    s["want"]["grads_ce"] = ref.loss_and_grads(cfg, wj, t, l,
                                               parts=(1.0, 0.0))[2]
    s["want"]["grads_indexer"] = ref.loss_and_grads(cfg, wj, t, l,
                                                    parts=(0.0, 1.0))[2]
    o = cfg["optimizer"]
    s["want"]["delta"], _ = ref.adamw_first_update(
        cfg, s["w0"], {k: jnp.asarray(v) for k, v in s["grads"].items()},
        epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    return s


# ------------------------------------------------- program against reference
def test_parameters_are_the_reference_s(small):
    got = {p.name: tuple(p.shape)
           for p in small["built"]["prog"].global_block().all_parameters()}
    assert got == {k: tuple(v) for k, v in
                   small["ref"].param_shapes(small["cfg"]).items()}
    assert set(small["builder"].sampled_params(small["cfg"]).values()) \
        <= set(got)


def test_the_file_s_parameter_count_is_the_program_s():
    """At the published widths (the program is only built, nothing runs):
    every trained parameter of the program, against `parameters` and the
    parts the file gives, and the issue's arithmetic."""
    from chipbench.configs import keye_vl_2_0_30b_a3b as builder

    cfg = _file()
    prog = builder.build(fluid, cfg, 1)["prog"]
    sizes = {p.name: int(np.prod(p.shape))
             for p in prog.global_block().all_parameters()
             if builder.reference.trained(p.name)}
    parts = cfg["parameters_by_part"]
    assert sum(sizes.values()) == cfg["parameters"] == 465391104 \
        == 4 * parts["layer"] + parts["table"] + parts["head"] \
        + parts["final_norm"]

    def of(prefix, leaves=None):
        return sum(v for k, v in sizes.items() if k.startswith(prefix)
                   and (leaves is None or k.rsplit(".", 1)[1] in leaves))

    assert sizes[P + "embed"] == sizes[P + "head"] == 18992 * 2048
    assert {of(f"{P}l{i}.") for i in range(4)} == {parts["layer"]} \
        == {96899456}
    assert of(P + "l0.", ("w_q", "w_k", "w_v", "w_o", "q_norm", "k_norm")) \
        == parts["attention"] == 2 * 2048 * 4096 + 2 * 2048 * 512 + 256
    assert of(P + "l0.", builder.reference.INDEXER) == parts["indexer"] \
        == 2048 * 1024 + 2048 * 64 + 128 + 2048 * 16
    assert sizes[P + "l0.gate"] * 3 == parts["held_experts_a_layer"] \
        == 16 * parts["one_expert"] == 16 * 3 * 2048 * 768
    assert sizes[P + "l0.router"] == parts["router_a_layer"] == 2048 * 128
    # no width differs from the published config; the floors are kept
    for key, want in dict(
            hidden_size=2048, head_dim=128, num_attention_heads=32,
            num_key_value_heads=4, moe_intermediate_size=768,
            num_experts_per_tok=8, rope_theta=10000000, rms_norm_eps=1e-6,
            norm_topk_prob=True, intermediate_size=6144).items():
        assert cfg[key] == want, key
    assert cfg["sa_config"] == dict(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
        kv_chunk_size=512, q_chunk_size=512, topk=2048)
    assert cfg["num_hidden_layers"] == 4 and cfg["num_experts"] == 16 \
        and cfg["vocab_size"] * 8 == cfg["deployment"]["vocab_size"]
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "num_local_experts", "vocab_size"]


def test_both_losses_and_the_logits(small):
    want = small["want"]
    _close(small["loss"], [want["loss"]])
    _close(small["ce"], [want["ce"]])
    _close(small["indexer_loss"], [sum(want["indexer_losses"])])
    for got, ref in zip(small["indexer_losses"], want["indexer_losses"]):
        _close(got, [ref])
    _close(small["logits"].reshape(2, S, -1), want["logits"], tol=2e-5)
    assert float(small["indexer_loss"][0]) > 0.1    # an indexer that matters


def test_the_selection_is_the_reference_s_exactly(small):
    for got, ref in zip(small["masks"], small["want"]["masks"]):
        assert got.dtype == np.int8
        assert np.array_equal(got != 0, np.asarray(ref))
        # min(t + 1, topk) keys a query, none above the diagonal
        assert np.array_equal(got.sum(axis=2)[0],
                              np.minimum(np.arange(S) + 1, 16))
        assert not np.triu(got[0], 1).any()


def test_routing_is_the_reference_s(small):
    for (ids, load, rows), (_, top) in zip(small["routing"],
                                           small["want"]["routing"]):
        assert np.array_equal(np.sort(ids, axis=1),
                              np.sort(np.asarray(top), axis=1))
        assert load.sum() == 8 * T
        assert rows[0] == ((ids >= 8) & (ids < 16)).sum()


KINDS = ["embedding", "head", "w_q", "w_k", "w_v", "w_o", "q_scale",
         "k_scale", "w_qi", "w_ki", "ki_norm", "ki_norm_bias", "w_w",
         "w_qi_last", "w_ki_last", "w_w_last", "router", "router_last",
         "expert_gate", "expert_up", "expert_down", "norm_scale"]


@pytest.mark.parametrize("which", KINDS)
def test_sampled_gradient_and_first_update(small, which):
    name = small["builder"].sampled_params(small["cfg"])[which]
    tol = 1e-4 if small["ref"].of_the_indexer(name) else 1e-5
    _close(small["grads"][name], small["want"]["grads"][name], tol=tol,
           floor=1e-9)
    _close(small["w1"][name] - small["w0"][name],
           small["want"]["delta"][name], tol=2e-3, floor=1e-9)


def test_every_parameter_s_gradient(small):
    assert set(small["grads"]) == set(small["want"]["grads"])
    assert set(KINDS) == set(small["builder"].sampled_params(small["cfg"]))
    for name, got in small["grads"].items():
        _close(got, small["want"]["grads"][name], tol=1e-4, floor=1e-9)


def test_the_two_losses_reach_disjoint_parameter_sets(small):
    """EXACT ZEROS: in the reference `jax.grad` of the cross-entropy alone
    is zero on every parameter of an indexer, and of the indexers' losses
    alone zero on everything else; the system's gradient of the sum is the
    one or the other; and a program that minimises one part declares no
    gradient for the other's parameters at all."""
    ref, want, cfg = small["ref"], small["want"], small["cfg"]
    for name in small["grads"]:
        mine, other = ("grads_indexer", "grads_ce") \
            if ref.of_the_indexer(name) else ("grads_ce", "grads_indexer")
        assert not np.asarray(want[other][name]).any(), name
        assert np.asarray(want[mine][name]).any(), name
        _close(small["grads"][name], want[mine][name], tol=1e-4, floor=1e-9)
    both = small["built"]["reached"]
    ce = small["builder"].build(fluid, cfg, 5, loss_of="ce")["reached"]
    ix = small["builder"].build(fluid, cfg, 5, loss_of="indexer")["reached"]
    assert ce and not any(map(ref.of_the_indexer, ce))
    assert len(ix) == 5 * 4 and all(map(ref.of_the_indexer, ix))
    assert sorted(ce + ix) == both


def test_the_target_and_the_indexer_s_input_are_detached(small):
    """Planted in the reference: with the target differentiated, or the
    indexer reading u with its gradient, the attention's parameters get
    another gradient (so the two stop-gradients are what the tests above
    hold, not an accident of the sizes)."""
    ref, cfg = small["ref"], small["cfg"]
    wj = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    t, l = (jnp.asarray(small["feed"][k]) for k in ("tokens", "labels"))
    stated = small["want"]["grads"][P + "l0.w_q"]
    for plant in (dict(detach_target=False), dict(detach_indexer=False)):
        got = ref.loss_and_grads(cfg, wj, t, l, **plant)[2][P + "l0.w_q"]
        assert np.abs(np.asarray(got - stated)).max() \
            > 1e-3 * np.abs(np.asarray(stated)).max(), plant


def test_the_decay_spares_the_norms_and_the_indexer_s_layer_norm(small):
    from paddle_tpu.models import keye_vl

    ref = small["ref"]
    for n in small["names"]:
        assert keye_vl.decays(n) == ref.decays(n)
    spared = {n.rsplit(".", 1)[1] for n in small["names"]
              if not ref.decays(n)}
    assert spared == {"attn_norm", "ffn_norm", "final_norm", "q_norm",
                      "k_norm", "ki_norm", "ki_norm_bias"}
    o, n = small["cfg"]["optimizer"], P + "l0.w_qi"
    moved = small["w1"][n] - small["w0"][n]
    undecayed = moved + o["learning_rate"] * o["weight_decay"] * small["w0"][n]
    assert np.abs(moved - undecayed).max() > 0.01 * np.abs(moved).max()


def test_a_given_selection_replaces_the_reference_s_own(small):
    """`selections`: the reference attends on the masks it is handed; with
    the system's own it gives the same numbers, with another choice other
    ones."""
    ref, cfg = small["ref"], small["cfg"]
    wj = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    t, l = (jnp.asarray(small["feed"][k]) for k in ("tokens", "labels"))
    given = [jnp.asarray(m) != 0 for m in small["masks"]]
    loss, _, _ = ref.loss_and_grads(cfg, wj, t, l, given)
    _close([loss], [small["want"]["loss"]], tol=1e-6)
    causal = [jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (2, S, S))
              ] * 4
    other, _, _ = ref.loss_and_grads(cfg, wj, t, l, causal)
    assert abs(float(other) - float(loss)) > 1e-3


def test_mrope_with_equal_ids_is_plain_rotary():
    """`mrope_section` [16, 24, 24] shares a head's 64 frequencies among
    three position ids; a text token's three are its position."""
    from chipbench.reference import keye_vl_2_0_30b_a3b as ref

    cfg = _file()
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 12, 3, 128)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(12), (3, 2, 12))
    sections = cfg["rope_scaling"]["mrope_section"]
    assert sum(sections) == cfg["head_dim"] // 2
    plain = ref.rope(x, cfg["rope_theta"])
    assert np.array_equal(np.asarray(ref.mrope(x, cfg["rope_theta"], pos,
                                               sections)), np.asarray(plain))
    moved = ref.mrope(x, cfg["rope_theta"], pos.at[1].add(5), sections)
    assert not np.allclose(np.asarray(moved), np.asarray(plain))
    # and the system's rotary op is that rotary
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        v = fluid.layers.data(name="x", shape=[12, 3, 128], dtype="float32")
        y = fluid.layers.rotary_embedding(v, theta=cfg["rope_theta"])
    got, = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed={"x": np.asarray(x)}, fetch_list=[y])
    _close(got, plain, tol=1e-6)


# ----------------------------------------------------- the ops on their own
def _indexer_draw(rng, B, n, hi=3, di=8):
    return (jnp.asarray(rng.normal(size=(B, n, hi, di)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, n, 1, di)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, n, hi)), jnp.float32))


def _plain_scores(q_i, k_i, w):
    s = jnp.einsum("bqhd,bkd->bqhk", q_i, k_i[:, :, 0, :])
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)


def _top_k_rows(I, topk):
    """[B, S, S] bool by `lax.top_k` a row: the plain selection."""
    B, n, _ = I.shape
    causal = jnp.tril(jnp.ones((n, n), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, I, -jnp.inf), min(topk, n))
    picked = jnp.zeros((B, n, n), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(n)[None, :, None],
        idx].set(True)
    return picked & causal


def _op(kind, ins, outs, attrs, feeds, fetch):
    """One op alone through a program, float32, on the CPU place."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        block = prog.global_block()
        for n, v in feeds.items():
            block.create_var(name=n, shape=v.shape, dtype=str(v.dtype))
        for n in outs.values():
            block.create_var(name=n, dtype="float32")
        block.append_op(kind, {k: [v] for k, v in ins.items()},
                        {k: [v] for k, v in outs.items()}, attrs)
    return fluid.Executor(fluid.CPUPlace()).run(
        prog, feed={k: np.asarray(v) for k, v in feeds.items()},
        fetch_list=fetch)


# rows shorter than, equal to and longer than topk; lengths that are and
# are not multiples of the lowering's block of queries (patched to 16)
LENGTHS = [(12, 16), (16, 16), (40, 16), (48, 16), (37, 5)]
LENGTHS_OF_THE_OPS = [(12, 16), (16, 16), (40, 16), (37, 5)]


@pytest.fixture
def blocks_of_16(monkeypatch):
    from paddle_tpu.parallel import sparse_index

    monkeypatch.setattr(sparse_index, "BLOCK", 16)
    # the functions' default argument was bound at import
    for name in ("select", "head_mean", "loss_and_grads"):
        fn = getattr(sparse_index, name)
        monkeypatch.setattr(fn, "__defaults__", tuple(
            16 if d == 256 else d for d in fn.__defaults__))


@pytest.mark.parametrize("n,topk", LENGTHS)
def test_indexer_select_is_top_k_a_row(n, topk, blocks_of_16):
    rng = np.random.default_rng(n)
    q_i, k_i, w = _indexer_draw(rng, 2, n)
    mask, tau = _op("indexer_select", dict(QI="qi", KI="ki", W="w"),
                    dict(Mask="mask", Threshold="tau"), dict(topk=topk),
                    dict(qi=q_i, ki=k_i, w=w), ["mask", "tau"])
    I = _plain_scores(q_i, k_i, w)
    want = _top_k_rows(I, topk)
    assert mask.dtype == np.int8 and mask.shape == (2, n, n)
    assert np.array_equal(mask != 0, np.asarray(want))
    # the threshold is the least chosen score (the op's own sum of the
    # same products, in another order than the plain form's)
    assert tau.dtype == np.float32 and tau.shape == (2, n)
    np.testing.assert_allclose(tau, np.min(np.where(want, I, np.inf), axis=2),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,topk", [(24, 8), (40, 16)])
def test_of_equal_scores_the_lower_position_wins(n, topk, blocks_of_16):
    """Planted ties: keys in pairs of identical k_I score alike for every
    query; and a whole row of equal scores (w = 0). `lax.top_k` and the
    bisection both give the tie to the lower s."""
    from paddle_tpu.parallel import sparse_index

    rng = np.random.default_rng(7)
    q_i, k_i, w = _indexer_draw(rng, 1, n)
    k_i = jnp.repeat(k_i[:, ::2], 2, axis=1)        # s and s + 1 alike
    w = w.at[0, n - 1].set(0.0)                     # the last row: all 0
    I = _plain_scores(q_i, k_i, w)
    assert np.array_equal(np.asarray(I[0, :, 0::2]), np.asarray(I[0, :, 1::2]))
    mask, tau = sparse_index.select(q_i[0], k_i[0, :, 0], w[0], topk)
    want = _top_k_rows(I, topk)[0]
    assert float(tau[n - 1]) == 0.0
    assert np.array_equal(np.asarray(mask) != 0, np.asarray(want))
    # the row of equal scores keeps its FIRST topk keys
    assert np.array_equal(np.flatnonzero(np.asarray(mask)[n - 1]),
                          np.arange(topk))
    # -0.0 and +0.0 are one score
    signed = jnp.asarray([[0.0, -0.0, 1.0, -0.0, 0.0, -1.0]], jnp.float32)
    got, tau = sparse_index.select_rows(jnp.broadcast_to(signed, (1, 6)), 5,
                                        3)
    assert np.array_equal(np.flatnonzero(np.asarray(got)[0]), [0, 1, 2])
    assert float(tau[0]) == 0.0
    # a negative threshold comes back through the folded sign
    got, tau = sparse_index.select_rows(-1.0 - jnp.arange(6.0)[None], 5, 3)
    assert np.array_equal(np.flatnonzero(np.asarray(got)[0]), [0, 1, 2])
    assert float(tau[0]) == -3.0


def _attention_draw(rng, B, H, Hkv, n, D):
    return (jnp.asarray(rng.normal(size=(B, n, H, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, n, Hkv, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, n, Hkv, D)), jnp.float32))


def _plain_attention(q, k, v, chosen):
    """[B, S, H, D] layout, the softmax over the chosen keys alone; also
    the probabilities [B, H, S, S]."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    pr = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", pr, v), pr


@pytest.mark.parametrize("n,topk", LENGTHS_OF_THE_OPS)
def test_sparse_attention_and_its_gradient(n, topk, blocks_of_16):
    """The op and its hand-written grad op against `jax.grad` of the plain
    form, under the selection the indexer op makes."""
    from paddle_tpu.parallel import sparse_index

    rng = np.random.default_rng(100 + n)
    q, k, v = _attention_draw(rng, 2, 4, 2, n, 8)
    q_i, k_i, w = _indexer_draw(rng, 2, n)
    mask = jnp.stack([sparse_index.select(q_i[b], k_i[b, :, 0], w[b],
                                          topk)[0] for b in range(2)])
    cot = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        vs = [fluid.layers.data(name=nm, shape=list(t.shape[1:]),
                                dtype=str(t.dtype))
              for nm, t in (("q", q), ("k", k), ("v", v), ("m", mask),
                            ("c", cot))]
        for var in vs[:3]:
            var.stop_gradient = False
        o, lse = fluid.layers.sparse_attention(*vs[:4])
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(o, vs[4]))
        grads = fluid.backward.calc_gradient(loss, vs[:3])
    assert "sparse_attention_grad" in [op.type
                                       for op in prog.global_block().ops]
    got = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed=dict(q=np.asarray(q), k=np.asarray(k), v=np.asarray(v),
                        m=np.asarray(mask), c=np.asarray(cot)),
        fetch_list=[o, lse] + grads)
    chosen = mask != 0
    want, pr = _plain_attention(q, k, v, chosen)
    _close(got[0], want)
    want_grads = jax.grad(lambda *a: jnp.sum(
        _plain_attention(*a, chosen)[0] * cot), argnums=(0, 1, 2))(q, k, v)
    for g, wg in zip(got[2:], want_grads):
        _close(g, wg, tol=2e-5)
    # the probabilities sum to 1 over the chosen keys and are 0 elsewhere
    assert np.allclose(np.asarray(pr.sum(-1)), 1.0, atol=1e-6)
    assert not np.asarray(jnp.where(chosen[:, None], 0.0, pr)).any()


def test_a_group_of_query_heads_reads_its_key_value_head():
    """8 query heads on 2 key/value heads: query head h reads key/value
    head h // 4 under a selection (moving a key of head 1 moves heads 4..7
    and no other)."""
    from paddle_tpu.ops import lm_ops

    rng = np.random.default_rng(2)
    q, k, v = (jnp.swapaxes(t, 1, 2)
               for t in _attention_draw(rng, 1, 8, 2, 24, 8))
    mask = jnp.asarray(np.tril(rng.random((1, 24, 24)) < 0.5)
                       | np.eye(24, dtype=bool), jnp.int8)
    base, _ = lm_ops._plain_sparse_attention(q, k, v, mask)
    moved, _ = lm_ops._plain_sparse_attention(
        q, k.at[:, 1, 0].add(1.0), v, mask)
    changed = np.abs(np.asarray(moved - base)).max(axis=(0, 2, 3)) > 1e-6
    assert changed.tolist() == [False] * 4 + [True] * 4


def _plain_indexer_loss(q, k, q_i, k_i, w, chosen):
    """mean_t KL(stop_gradient(mean_h A) || softmax over the chosen keys of
    I), written out whole."""
    group = q.shape[2] // k.shape[2]
    kk = jnp.repeat(k, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(q.shape[-1])
    p = jax.lax.stop_gradient(jnp.mean(jax.nn.softmax(
        jnp.where(chosen[:, None], s, -jnp.inf), -1), axis=1))
    log_q = jax.nn.log_softmax(
        jnp.where(chosen, _plain_scores(q_i, k_i, w), -jnp.inf), -1)
    weigh = chosen & (p > 0)
    return jnp.sum(jnp.where(weigh, p * (
        jnp.log(jnp.where(weigh, p, 1.0)) - jnp.where(weigh, log_q, 0.0)),
        0.0)) / (q.shape[0] * q.shape[1])


@pytest.mark.parametrize("n,topk", LENGTHS_OF_THE_OPS)
def test_indexer_loss_and_its_gradient(n, topk, blocks_of_16):
    """The op's loss, and the gradients its grad op hands to q_I, k_I and
    w, against `jax.grad` of the plain form; through the layer, whose
    detached copies keep every gradient away from q and k."""
    from paddle_tpu.parallel import sparse_index

    rng = np.random.default_rng(200 + n)
    q, k, v = _attention_draw(rng, 2, 4, 2, n, 8)
    q_i, k_i, w = _indexer_draw(rng, 2, n)
    mask = jnp.stack([sparse_index.select(q_i[b], k_i[b, :, 0], w[b],
                                          topk)[0] for b in range(2)])
    feeds = dict(q=q, k=k, v=v, qi=q_i, ki=k_i, w=w, m=mask)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        vs = {nm: fluid.layers.data(name=nm, shape=list(t.shape[1:]),
                                    dtype=str(t.dtype))
              for nm, t in feeds.items()}
        for nm in ("q", "k", "qi", "ki", "w"):
            vs[nm].stop_gradient = False
        _, lse = fluid.layers.sparse_attention(vs["q"], vs["k"], vs["v"],
                                               vs["m"])
        loss = fluid.layers.indexer_loss(vs["q"], vs["k"], lse, vs["qi"],
                                         vs["ki"], vs["w"], vs["m"])
        three = fluid.layers.scale(loss, scale=3.0)
        grads = fluid.backward.calc_gradient(
            three, [vs[nm] for nm in ("qi", "ki", "w", "q", "k")])
    # the loss trains the indexer's inputs and nothing of the attention's
    assert grads[3] is None and grads[4] is None
    got = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed={nm: np.asarray(t) for nm, t in feeds.items()},
        fetch_list=[loss] + grads[:3])
    chosen = mask != 0
    want, want_grads = jax.value_and_grad(
        lambda a, b, c: _plain_indexer_loss(q, k, a, b, c, chosen),
        argnums=(0, 1, 2))(q_i, k_i, w)
    _close(got[0], [want])
    for g, wg in zip(got[1:], want_grads):
        _close(g, 3.0 * wg, tol=1e-4, floor=1e-9)


def _pallas_call_names(fn, *args):
    """The names of the pallas_call equations of `fn`'s jaxpr, in order."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    # a function of its own a call: `make_jaxpr` keeps a function's trace
    walk(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)
    return names


@pytest.mark.parametrize("which", ["forward", "backward", "two_kernels",
                                   "one_against_two"])
@pytest.mark.parametrize("n,block,dtype,holes", [
    (64, 32, "float32", False), (40, 16, "float32", False),
    (24, 1024, "float32", False), (64, 32, "bfloat16", False),
    (64, 32, "float32", True)],
    ids=["two_blocks", "padded", "one_block", "bf16", "empty_rows"])
def test_masked_flash_kernels_interpreted(n, block, dtype, holes, which,
                                          monkeypatch):
    """The kernels of `parallel/flash.py` under a mask (interpreted)
    against the plain composition: 8 query heads on 2 key/value heads
    under a selection, rows that are and are not whole blocks. The
    backward as the ONE kernel a call of these sizes gets (`backward`), as
    the dK/dV and the dQ kernel a call past `fused_backward_fits` gets
    (`two_kernels`: the share patched to nothing), and the one against the
    two on the same draw. `empty_rows`: in the first row of tokens the
    queries of the first block (and the first of the second) see no key at
    all (output 0, logsumexp -inf, dQ 0, nothing added to dK or dV, no
    NaN) and none of the second block sees a key of the first key block, a
    visited block; the oracle gives the empty queries their own key and a
    zero cotangent."""
    import ml_dtypes

    from paddle_tpu.ops.lm_ops import _plain_sparse_attention
    from paddle_tpu.parallel import flash, sparse_index

    np_dtype = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    rng = np.random.default_rng(n)
    q, k, v = (jnp.swapaxes(t, 1, 2).astype(np_dtype)
               for t in _attention_draw(rng, 2, 8, 2, n, 16))
    q_i, k_i, w = _indexer_draw(rng, 2, n)
    mask = jnp.stack([sparse_index.select(q_i[b], k_i[b, :, 0], w[b], 12)[0]
                      for b in range(2)])
    do = jnp.asarray(rng.normal(size=q.shape)).astype(np_dtype)
    want_mask, want_do = mask, do
    empty = np.zeros(mask.shape[:2], bool)
    if holes:
        mask = mask.at[0, :2 * block, :block].set(0)
        empty = ~np.asarray(mask).any(axis=2)        # [B, S]: no key at all
        assert empty[0, :block].all() and not empty[0, block:].all()
        assert not empty[1].any()
        want_mask = mask | (jnp.eye(n, dtype=mask.dtype) * empty[:, :, None])
        want_do = do * ~empty[:, None, :, None]
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    want_o, want_lse = _plain_sparse_attention(*f32, want_mask)
    o, lse = flash.flash_attention_fwd(q, k, v, causal=True, mask=mask,
                                       block_q=block, block_k=block)
    tol = dict(atol=1e-4, rtol=1e-3) if dtype == "float32" \
        else dict(atol=0.1, rtol=0.05)
    if which == "forward":
        assert o.dtype == q.dtype and lse.dtype == jnp.float32
        if holes:
            rows = np.broadcast_to(empty[:, None], lse.shape)
            assert not np.asarray(o)[rows].any()
            assert np.isneginf(np.asarray(lse)[rows]).all()
            o, lse, want_o, want_lse = (np.asarray(t)[~rows] for t in (
                o, lse, want_o, want_lse))
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(want_o), **tol)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   **tol)
        return

    def backward(*operands):
        return flash.flash_attention_bwd(*operands, causal=True, mask=mask,
                                         block_q=block, block_k=block)

    operands = (q, k, v, o, lse, do)
    if which != "backward":
        one = backward(*operands)
        assert _pallas_call_names(backward, *operands) \
            == [flash.SPARSE_BWD_KERNEL]
        monkeypatch.setattr(flash, "_FUSED_ACCUMULATORS_SHARE", 0.0)
        assert _pallas_call_names(backward, *operands) \
            == list(flash.SPARSE_KERNELS[1:])
    got = backward(*operands)
    if which == "one_against_two":
        # the same products on the same operands; dQ's alone is given to
        # the MXU transposed
        same = dict(atol=1e-6, rtol=1e-6) if dtype == "float32" \
            else dict(atol=0.02, rtol=0.02)
        for a, b in zip(one, got):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), **same)
        return
    _, vjp = jax.vjp(
        lambda *a: _plain_sparse_attention(*a, want_mask)[0], *f32)
    want = vjp(want_do.astype(jnp.float32))
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == q.dtype
        assert not np.isnan(np.asarray(a, np.float32)).any()
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   **tol)
    if holes:
        assert not np.asarray(got[0], np.float32)[
            np.broadcast_to(empty[:, None], lse.shape)].any()


INDEX_LOSS_CASES = {
    # name: (tokens, query heads, key/value heads, (block_q, block_k),
    #        dtype, what q is multiplied by)
    "two_blocks": (64, 8, 2, (32, 32), "float32", 1.0),
    "wide_keys": (64, 8, 2, (16, 32), "float32", 1.0),
    "wide_queries": (64, 8, 2, (32, 16), "float32", 1.0),
    "one_block": (32, 8, 2, (32, 32), "float32", 1.0),
    "heads_32_on_4": (64, 32, 4, (32, 32), "float32", 1.0),
    "bf16": (64, 8, 2, (32, 32), "bfloat16", 1.0),
    # scores hundreds apart: exp(s - lse) is exactly 0 on chosen pairs, in
    # all eight heads at once on some
    "p_underflows": (64, 8, 2, (32, 32), "float32", 400.0),
}


@functools.lru_cache(maxsize=None)
def _index_loss_case(name):
    """(the kernels' (loss, d q_I, d k_I, d w), `sparse_index
    .loss_and_grads`'s, the share of chosen pairs with p == 0) of a case,
    made once: topk 12 of 32 or 64 tokens, so the first 12 queries of a
    row have fewer keys than topk."""
    import ml_dtypes

    from paddle_tpu.ops.lm_ops import _plain_sparse_attention
    from paddle_tpu.parallel import index_loss, sparse_index

    n, H, Hkv, blocks, dtype, q_scale = INDEX_LOSS_CASES[name]
    np_dtype = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    rng = np.random.default_rng(7 + n + H)
    q, k, _ = (t[0].astype(np_dtype)
               for t in _attention_draw(rng, 1, H, Hkv, n, 16))
    q = (q * q_scale).astype(np_dtype)
    q_i, k_i, w = (t[0].astype(np_dtype)
                   for t in _indexer_draw(rng, 1, n, hi=4))
    k_i = k_i[:, 0]
    mask = sparse_index.select(q_i, k_i, w, 12, block=16)[0]
    q_h, k_h = (jnp.swapaxes(t, 0, 1) for t in (q, k))
    scale = 16 ** -0.5
    lse = _plain_sparse_attention(
        *(t[None].astype(jnp.float32) for t in (q_h, k_h, k_h)),
        mask[None], scale)[1][0]
    want = sparse_index.loss_and_grads(q_h, k_h, lse, q_i, k_i, w, mask,
                                       scale, block=16)
    got = index_loss.loss_and_grads(q_h, k_h, lse, q_i, k_i, w, mask,
                                    scale, blocks=blocks)
    p = sparse_index.head_mean(q_h, k_h, lse, mask, scale, block=16)
    zero = float(jnp.sum((p == 0) & (mask != 0)) / jnp.sum(mask != 0))
    return got, want, zero


@pytest.mark.parametrize("which", ["loss", "d_q", "d_k", "d_w"])
@pytest.mark.parametrize("name", list(INDEX_LOSS_CASES))
def test_index_loss_kernels_interpreted(name, which):
    """The two kernels of `parallel/index_loss.py` (interpreted) against
    `sparse_index.loss_and_grads`, their oracle: the loss and its gradient
    with respect to q_I, k_I and w; blocks that are and are not square,
    rows whose first queries have fewer than `topk` keys, 32 query heads on
    4, bf16 operands (the gradient products' left operand is rounded to
    them, as the chip's default precision rounds the plain lowering's),
    chosen pairs whose p underflows to 0."""
    got, want, zero = _index_loss_case(name)
    i = ["loss", "d_q", "d_k", "d_w"].index(which)
    assert got[i].shape == want[i].shape and got[i].dtype == jnp.float32
    assert (zero > 0.02) == (name == "p_underflows"), zero
    bf16 = INDEX_LOSS_CASES[name][4] == "bfloat16"
    top = float(np.abs(np.asarray(want[i])).max())
    np.testing.assert_allclose(
        np.asarray(got[i]), np.asarray(want[i]), rtol=1e-4,
        atol=top * (1e-2 if bf16 and which in ("d_q", "d_k") else 2e-5))


def test_indexer_loss_takes_the_kernels_on_a_tpu_place_alone(monkeypatch):
    """The op hands a row to `index_loss.loss_and_grads` only where the
    step is traced for a TPU place AND `index_loss.takes` the shapes: with
    both steered true the op's four outputs are the plain lowering's."""
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import index_loss, sparse_index

    rng = np.random.default_rng(11)
    q, k, v = _attention_draw(rng, 2, 8, 2, 32, 16)
    q_i, k_i, w = _indexer_draw(rng, 2, 32, hi=4)
    mask = jnp.stack([sparse_index.select(q_i[b], k_i[b, :, 0], w[b],
                                          12, block=16)[0] for b in range(2)])
    _, lse = lm_ops._plain_sparse_attention(
        *(jnp.swapaxes(t, 1, 2) for t in (q, k, v)), mask)
    ins = {"Q": [q], "K": [k], "Lse": [lse], "QI": [q_i], "KI": [k_i],
           "W": [w], "Mask": [mask]}
    plain = lm_ops.indexer_loss_op(None, ins, {})
    calls = []
    real = index_loss.loss_and_grads
    monkeypatch.setattr(
        index_loss, "loss_and_grads",
        lambda *a, **kw: calls.append(a[0].shape) or real(
            *a, blocks=(16, 16), **kw))
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)
    assert lm_ops.indexer_loss_op(None, ins, {})["Loss"][0] \
        == plain["Loss"][0] and calls == []       # shapes not taken
    monkeypatch.setattr(index_loss, "takes", lambda *a, **kw: True)
    kernels = lm_ops.indexer_loss_op(None, ins, {})
    assert calls == [(8, 32, 16)]                 # a row, heads first
    for slot in ("Loss", "QIGrad", "KIGrad", "WGrad"):
        a, b = kernels[slot][0], plain[slot][0]
        assert a.shape == b.shape and a.dtype == b.dtype
        _close(a, b, tol=1e-4, floor=1e-9)
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: False)
    lm_ops.indexer_loss_op(None, ins, {})
    assert len(calls) == 1                        # never off a TPU place


def _select_case_scores(name, rng, n, S):
    I = rng.normal(size=(n, S))
    if name == "keys_in_identical_pairs":
        I = np.repeat(I[:, ::2], 2, axis=1)
    elif name == "a_row_of_equal_scores":
        I[n - 3] = 0.25
    elif name == "signed_zeros_are_one_score":
        I = np.where(rng.random((n, S)) < 0.5, 0.0, -0.0) \
            * np.where(rng.random((n, S)) < 0.9, 1.0, np.nan)
        I = np.where(np.isnan(I), rng.normal(size=(n, S)), I)
    elif name == "a_negative_threshold":
        I = -1.0 - np.abs(I)
    elif name == "ties_across_a_key_tile":
        # row t's keys 120 .. 135 alike and above every other key but 40:
        # with topk 48 a row keeps the first 8 of the 16, up to key 127
        I = -1.0 - np.abs(I)
        I[:, :40] = 5.0 + np.abs(I[:, :40])
        I[:, 120:136] = 2.0
    elif name == "one_row_needs_the_running_count":
        I[n - 40, 7:90] = 0.5 * np.float32(I[n - 40]).max()
    elif name in ("ties_everywhere", "ties_by_the_mxu_s_sum",
                  "ties_by_a_sum_down_the_sublanes"):
        # on a grid of halves: every row has keys at its threshold
        I = np.round(I * 2) / 2
    return jnp.asarray(I, jnp.float32)


INDEX_SELECT_CASES = {
    # name: (queries, first query, keys, topk, (chunk, key tile), lane sum)
    "random": (256, 0, 256, 64, (32, 128), "lanes"),
    # the chunk of queries 32 .. 63 straddles t = topk
    "topk_not_a_multiple_of_the_chunk": (256, 0, 256, 40, (32, 128),
                                         "lanes"),
    "topk_of_one": (128, 0, 128, 1, (32, 128), "lanes"),
    "topk_past_the_row": (128, 0, 128, 500, (32, 128), "lanes"),
    # a scan's step: queries 128 .. 191 of a row of 256
    "a_block_of_a_scan": (64, 128, 256, 40, (32, 128), "lanes"),
    # the plain form's padded queries past the row see every key
    "queries_past_the_row": (64, 224, 256, 40, (32, 128), "lanes"),
    "wide_key_tiles": (256, 0, 512, 100, (64, 256), "lanes"),
    "the_mxu_s_sum": (256, 0, 256, 40, (64, 128), "mxu"),
    "a_sum_down_the_sublanes": (256, 0, 256, 40, (128, 128), "sublanes"),
    "keys_in_identical_pairs": (256, 0, 256, 40, (32, 128), "lanes"),
    "a_row_of_equal_scores": (256, 0, 256, 40, (32, 128), "lanes"),
    "signed_zeros_are_one_score": (256, 0, 256, 40, (32, 128), "lanes"),
    "a_negative_threshold": (256, 0, 256, 40, (32, 128), "lanes"),
    "ties_across_a_key_tile": (256, 0, 256, 48, (32, 128), "lanes"),
    "one_row_needs_the_running_count": (256, 0, 256, 40, (32, 128),
                                        "lanes"),
    "ties_everywhere": (256, 0, 512, 100, (64, 256), "lanes"),
    "ties_by_the_mxu_s_sum": (256, 0, 256, 40, (32, 128), "mxu"),
    "ties_by_a_sum_down_the_sublanes": (128, 128, 256, 40, (128, 128),
                                        "sublanes"),
}


@functools.lru_cache(maxsize=None)
def _index_select_case(name):
    """(the scores, the kernel's (mask, threshold), the plain
    `sparse_index.select_rows`'s) of a case, made once."""
    from paddle_tpu.parallel import index_select, sparse_index

    n, first, S, topk, blocks, lane_sum = INDEX_SELECT_CASES[name]
    I = _select_case_scores(name, np.random.default_rng(len(name)), n, S)
    chosen, tau = sparse_index.select_rows(I, first, topk)
    return I, index_select.select_rows(I, first, topk, blocks, lane_sum), \
        (np.asarray(chosen).astype(np.int8), np.asarray(tau))


@pytest.mark.parametrize("which", ["mask", "threshold"])
@pytest.mark.parametrize("name", list(INDEX_SELECT_CASES))
def test_index_select_kernel_interpreted(name, which):
    """The kernel of `parallel/index_select.py` (interpreted) against the
    plain `sparse_index.select_rows`, its oracle: mask and threshold EQUAL,
    every bit. Chunks whose queries all have fewer than `topk` keys, the
    chunk that straddles t = topk, a block in the middle of a row and one
    past its end, the three ways a count's lane-wise partial sums are
    added up, and planted ties: keys in identical pairs, a whole row of
    equal scores (which keeps its FIRST topk keys), +0.0 and -0.0 one
    score, a negative threshold, more ties than a row may keep lying
    across a key tile's boundary, a chunk where one row needs the running
    count and its neighbours do not."""
    n, first, S, topk, _, _ = INDEX_SELECT_CASES[name]
    I, got, want = _index_select_case(name)
    i = ["mask", "threshold"].index(which)
    assert got[i].shape == want[i].shape and got[i].dtype == want[i].dtype
    bits = np.int8 if which == "mask" else np.int32
    assert np.array_equal(np.asarray(got[i]).view(bits), want[i].view(bits))
    if which == "threshold":
        return
    mask, t = np.asarray(got[0]), first + np.arange(n)
    assert np.array_equal(mask.sum(axis=1),
                          np.minimum(np.minimum(t + 1, S), topk))
    assert not mask[np.arange(S)[None, :] > t[:, None]].any()
    if name == "a_row_of_equal_scores":
        assert np.array_equal(np.flatnonzero(mask[n - 3]), np.arange(topk))
        assert float(got[1][n - 3]) == 0.25
    if name == "ties_across_a_key_tile":
        assert np.array_equal(np.flatnonzero(mask[n - 1]),
                              np.r_[0:40, 120:128])
        assert float(got[1][n - 1]) == 2.0
    if name == "signed_zeros_are_one_score":
        zero = np.asarray(got[1]) == 0
        assert zero.sum() > n // 2 and not np.signbit(
            np.asarray(got[1])[zero]).any()
    if name == "a_negative_threshold":
        assert (np.asarray(got[1]) < -1).all()
    if name == "one_row_needs_the_running_count":
        # the rows around row n - 40 have a key of their own at the
        # threshold and no other
        at = np.asarray(I) == np.asarray(got[1])[:, None]
        at &= np.arange(S)[None, :] <= t[:, None]
        assert at[n - 40].sum() > 40 and (np.delete(at, n - 40, 0)
                                          .sum(axis=1) == 1).all()


def test_select_rows_takes_the_kernel_on_a_tpu_place_alone(monkeypatch):
    """`sparse_index.select_rows` hands a block to `index_select
    .select_rows` only where the step is traced for a TPU place AND
    `index_select.takes` the shapes: with the place steered true `select`
    (the score product, the scan over blocks of queries) gives the plain
    lowering's mask and threshold, bit for bit."""
    from paddle_tpu.parallel import index_select, sparse_index

    rng = np.random.default_rng(13)
    rows, S = index_select.BLOCKS               # one chunk, one key tile
    q_i, k_i, w = (t[0] for t in _indexer_draw(rng, 1, S, hi=4))
    k_i = k_i[:, 0]
    plain = sparse_index.select(q_i, k_i, w, 100, block=rows)
    calls = []
    real = index_select.select_rows
    monkeypatch.setattr(
        index_select, "select_rows",
        lambda I, first, topk: calls.append(I.shape) or real(
            I, first, topk, interpret=True))
    assert index_select.takes(rows, S, 100)
    assert not index_select.takes(48, 48, 16)      # the model tests' row
    sparse_index.select(q_i, k_i, w, 100, block=rows)
    assert calls == []                             # never off a TPU place
    monkeypatch.setattr(index_select, "pallas_interpret", lambda: False)
    kernel = sparse_index.select(q_i, k_i, w, 100, block=rows)
    assert calls == [(rows, S)]                # a scan's step, traced once
    for a, b in zip(kernel, plain):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    sparse_index.select(q_i, k_i, w, 100, block=rows - 32)
    assert len(calls) == 1                         # shapes not taken


@pytest.mark.parametrize("plant", ["no_relu", "w_one", "scores_bf16",
                                   "whole_triangle"])
def test_every_plant_of_the_study_bites_on_the_kernel_path(plant,
                                                           monkeypatch):
    """The study one precision down (`chipbench
    .lower_precision_lm_sparse_attn_share`) plants `no_relu`, `w_one` and
    `scores_bf16` by replacing `sparse_index.scores`, and `whole_triangle`
    by replacing `sparse_index.select_rows`, from outside: the kernel
    TAKES the scores `select` formed and is reached through `select_rows`,
    so mask and threshold move under every one of them (a kernel that
    formed the product itself, or that `select` called by another name,
    would make the plant a no-op)."""
    from chipbench import lower_precision_lm_sparse_attn_share as study
    from paddle_tpu.parallel import index_select, sparse_index

    rng = np.random.default_rng(17)
    q_i, k_i, w = (t[0] for t in _indexer_draw(rng, 1, 256, hi=4))
    k_i = k_i[:, 0]
    real = index_select.select_rows
    monkeypatch.setattr(
        index_select, "select_rows",
        lambda I, first, topk: real(I, first, topk, (32, 128),
                                    interpret=True))
    monkeypatch.setattr(index_select, "pallas_interpret", lambda: False)
    monkeypatch.setattr(index_select, "takes", lambda *a: True)
    stated = sparse_index.select(q_i, k_i, w, 40, block=64)
    with study._planted(plant):
        planted = sparse_index.select(q_i, k_i, w, 40, block=64)
    assert np.asarray(stated[0]).sum() == 40 * 41 // 2 + 216 * 40
    # (rounding the scores to bf16 moves a few keys a row across the
    # threshold; the others move a pair in ten or more)
    moved = (np.asarray(planted[0]) != np.asarray(stated[0])).mean()
    assert moved > (1e-4 if plant == "scores_bf16" else 0.05)
    assert not np.array_equal(np.asarray(planted[1])[40:],
                              np.asarray(stated[1])[40:])


def test_the_saved_logsumexp_stays_float32_under_amp():
    """`sparse_attention` is on AMP's white list (bf16 operands into the
    kernels) and its grad op reads `Lse` as the forward left it."""
    from paddle_tpu import amp

    lse = jnp.ones((1, 2, 4), jnp.float32)
    q = jnp.ones((1, 4, 2, 8), jnp.float32)
    amp.enable("bfloat16")
    try:
        ins = amp.apply_policy("sparse_attention_grad", {
            "Q": [q], "Lse": [lse], "Mask": [jnp.ones((1, 4, 4), jnp.int8)]})
        assert ins["Q"][0].dtype == jnp.bfloat16
        assert ins["Lse"][0].dtype == jnp.float32
        assert ins["Mask"][0].dtype == jnp.int8
        neutral = amp.apply_policy("indexer_loss", {"Lse": [lse], "Q": [q]})
        assert neutral["Q"][0].dtype == jnp.float32
    finally:
        amp.disable()


def test_shapes_are_checked_as_the_ops_are_appended():
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        L = fluid.layers
        q_i = L.data(name="qi", shape=[8, 3, 4], dtype="float32")
        k_i = L.data(name="ki", shape=[8, 1, 4], dtype="float32")
        w = L.data(name="w", shape=[8, 3], dtype="float32")
        q = L.data(name="q", shape=[8, 4, 16], dtype="float32")
        kv = L.data(name="kv", shape=[8, 2, 16], dtype="float32")
        mask, threshold = L.indexer_select(q_i, k_i, w, 4)
        assert tuple(mask.shape) == (-1, 8, 8) and mask.dtype == "int8"
        assert tuple(threshold.shape) == (-1, 8)
        o, lse = L.sparse_attention(q, kv, kv, mask)
        assert tuple(o.shape) == (-1, 8, 4, 16)
        assert tuple(lse.shape) == (-1, 4, 8)
        assert tuple(L.indexer_loss(q, kv, lse, q_i, k_i, w,
                                    mask).shape) == (1,)
        two_heads = L.data(name="k2", shape=[8, 2, 4], dtype="float32")
        with pytest.raises(Exception, match="one key head"):
            L.indexer_select(q_i, two_heads, w, 4)
        with pytest.raises(Exception, match="topk"):
            L.indexer_select(q_i, k_i, w, 0)
        three = L.data(name="k3", shape=[8, 3, 16], dtype="float32")
        with pytest.raises(Exception, match="multiple"):
            L.sparse_attention(q, three, three, mask)


def test_lowered_counts_name_the_new_lowerings(small):
    from paddle_tpu.ops.lm_ops import lowered_counts

    class Cpu:
        platform = "cpu"

    class Tpu:
        platform = "tpu"

    prog = small["built"]["prog"]
    cpu, tpu = lowered_counts(prog, Cpu), lowered_counts(prog, Tpu)
    for counts in (cpu, tpu):
        assert counts["indexer_select_bisection"] == 4
        assert counts["indexer_loss_with_grads"] == 4
        assert counts["sparse_attention_plain"] == 4
        # 4 layers, the rows the program leaves open counted as one:
        # sum_t min(t + 1, 16) of 48 against 48 x 49 / 2
        assert counts["sparse_attention_selected_pairs"] \
            == 4 * (16 * 17 // 2 + 32 * 16)
        assert counts["sparse_attention_causal_pairs"] == 4 * 48 * 49 // 2
    assert "sparse_attention_kernel" not in cpu
    assert tpu["sparse_attention_kernel"] == 4 \
        == tpu["sparse_attention_grad_kernel"]
    # 48 tokens: the backward's accumulators fit, ONE kernel a grad op
    assert "sparse_attention_grad_fused" not in cpu
    assert tpu["sparse_attention_grad_fused"] == 4
    assert "flash_attention" not in tpu      # no causal flash kernel left
    # 48 tokens are no whole block of the loss's kernels: the plain scan
    assert "indexer_loss_kernel" not in cpu
    assert "indexer_loss_kernel" not in tpu
    # nor a whole chunk of the selection's: XLA's passes over the block
    assert "indexer_select_kernel" not in cpu
    assert "indexer_select_kernel" not in tpu
    # the cell's own: 14,681,088 of 33,558,528 a layer at one row of 8192
    from chipbench import costs_sparse_attn_share as costs
    assert costs.selected_pairs(8192, 2048) == 14681088
    assert costs.causal_pairs(8192) == 33558528


@pytest.mark.parametrize("policy", ["bfloat16", None])
def test_the_loss_s_kernels_are_counted_at_the_cell_s_shapes(policy):
    """Four `indexer_loss` ops at the `keye_vl_2_0_30b_a3b` cell's shapes
    (one row of 8192 tokens, 32 heads on 4 of 128, an indexer of 16 heads
    of 64): `indexer_loss_kernel` 4 on a TPU place under the bf16 policy,
    none for a float32 program, never on the CPU."""
    from paddle_tpu import amp
    from paddle_tpu.ops.lm_ops import lowered_counts

    class Cpu:
        platform = "cpu"

    class Tpu:
        platform = "tpu"

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        L = fluid.layers
        S = 8192
        q = L.data(name="q", shape=[S, 32, 128], dtype="float32")
        kv = L.data(name="kv", shape=[S, 4, 128], dtype="float32")
        q_i = L.data(name="qi", shape=[S, 16, 64], dtype="float32")
        k_i = L.data(name="ki", shape=[S, 1, 64], dtype="float32")
        w = L.data(name="w", shape=[S, 16], dtype="float32")
        mask = L.data(name="m", shape=[S, S], dtype="int8")
        lse = L.data(name="lse", shape=[32, S], dtype="float32")
        for _ in range(4):
            L.indexer_loss(q, kv, lse, q_i, k_i, w, mask)
    if policy:
        amp.enable(policy)
    try:
        cpu, tpu = lowered_counts(prog, Cpu), lowered_counts(prog, Tpu)
    finally:
        amp.disable()
    assert cpu["indexer_loss_with_grads"] == 4 \
        == tpu["indexer_loss_with_grads"]
    assert "indexer_loss_kernel" not in cpu
    assert tpu.get("indexer_loss_kernel") == (4 if policy else None)


@pytest.mark.parametrize("S,topk,kernel", [(8192, 2048, 4), (8192, 100, 4),
                                           (4000, 2048, None),
                                           (65536, 2048, None)])
def test_the_selection_s_kernel_is_counted_at_the_cell_s_shapes(S, topk,
                                                                kernel):
    """Four `indexer_select` ops at the `keye_vl_2_0_30b_a3b` cell's shapes
    (one row of 8192 tokens, an indexer of 16 heads of 64, topk 2048):
    `indexer_select_kernel` 4 on a TPU place beside
    `indexer_select_bisection` 4 (it is still a bisection), whatever the
    dtype or topk; none for a row that is no whole key tile or whose
    chunk's scores, keys and mask pass the kernel's share of VMEM; never on
    the CPU. The counter asks `index_select.takes` of the block of queries
    `sparse_index.select` hands `select_rows`, as the dispatch does."""
    from paddle_tpu.ops.lm_ops import lowered_counts
    from paddle_tpu.parallel import index_select, sparse_index

    class Cpu:
        platform = "cpu"

    class Tpu:
        platform = "tpu"

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        L = fluid.layers
        q_i = L.data(name="qi", shape=[S, 16, 64], dtype="float32")
        k_i = L.data(name="ki", shape=[S, 1, 64], dtype="float32")
        w = L.data(name="w", shape=[S, 16], dtype="float32")
        for _ in range(4):
            L.indexer_select(q_i, k_i, w, topk)
    cpu, tpu = lowered_counts(prog, Cpu), lowered_counts(prog, Tpu)
    assert cpu["indexer_select_bisection"] == 4 \
        == tpu["indexer_select_bisection"]
    assert "indexer_select_kernel" not in cpu
    assert tpu.get("indexer_select_kernel") == kernel
    assert index_select.takes(min(sparse_index.BLOCK, S), S, topk) \
        == bool(kernel)


@pytest.mark.parametrize("S,fused", [(8192, 4), (16384, None)])
def test_the_one_kernel_backward_is_counted_where_it_fits(S, fused):
    """Four `sparse_attention_grad` ops at the `keye_vl_2_0_30b_a3b` cell's
    heads (32 on 4 of 128): at the cell's row of 8192 dQ, dK and dV of a
    head are 12 MiB of float32, a quarter of the kernels' VMEM limit holds
    them and each grad op is ONE kernel (`sparse_attention_grad_fused` 4 of
    `sparse_attention_grad_kernel` 4 on a TPU place); at a row of 16384
    they are 24 MiB and the dK/dV and dQ kernels stay. Never on the CPU.
    The counter asks `flash.fused_backward_fits`, as the dispatch does."""
    from paddle_tpu.ops.lm_ops import lowered_counts
    from paddle_tpu.parallel import flash

    class Cpu:
        platform = "cpu"

    class Tpu:
        platform = "tpu"

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        L = fluid.layers
        q = L.data(name="q", shape=[S, 32, 128], dtype="float32")
        kv = L.data(name="kv", shape=[S, 4, 128], dtype="float32")
        mask = L.data(name="m", shape=[S, S], dtype="int8")
        q.stop_gradient = False
        outs = [L.sparse_attention(q, kv, kv, mask)[0] for _ in range(4)]
        fluid.backward.calc_gradient(L.mean(L.sums(outs)), [q])
    cpu, tpu = lowered_counts(prog, Cpu), lowered_counts(prog, Tpu)
    assert tpu["sparse_attention_grad_kernel"] == 4
    assert tpu.get("sparse_attention_grad_fused") == fused
    assert "sparse_attention_grad_fused" not in cpu
    assert flash.fused_backward_fits(S, S, 128, 128) == bool(fused)


def test_op_costs_weigh_the_new_ops(small):
    from paddle_tpu.trace.costs import op_costs

    rows = {r["op"]: r["flops_est"]
            for r in op_costs(small["built"]["prog"], batch_size=2)}
    pairs = 2 * 48 * 49 / 2
    assert rows["indexer_select"] == pairs * 2 * 4 * 8
    assert rows["indexer_loss"] == pairs * (6 * 4 * 8 + 2 * 8 * 16)
    assert rows["sparse_attention"] > 0 and rows["sparse_attention_grad"] \
        == 2 * rows["sparse_attention"]


# ----------------------------------------------------------- the share
CHIPS = 8


def _uncut():
    """An uncut tiny model: 32 experts all held, the whole vocabulary of
    1024 rows, and seeded weights; x a state."""
    cfg = _cfg(num_experts=32, num_local_experts=32, vocab_size=1024,
               deployment=dict(num_experts=32, first_expert=0))
    from chipbench.reference import keye_vl_2_0_30b_a3b as ref

    rs = np.random.default_rng(11)
    big = ("router", ".gate", ".up", ".down", "w_qi", "w_ki", "w_w")

    def draw(n, s):
        if n.endswith("expert_bias"):
            return np.zeros(s)
        return rs.normal(0, 0.3 if any(b in n for b in big) else 0.08, s)

    w = {n: jnp.asarray(draw(n, s), jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    x = jnp.asarray(rs.normal(0, 1, (2, S, 64)), jnp.float32)
    return cfg, ref, w, x


def _program_part(build, weights, feeds):
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
        got = exe.run(prog, feed=feeds, fetch_list=list(out))
    return [np.asarray(g) for g in got]


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["even", "past_the_row_bound"])
def test_the_shares_add_up_to_the_uncut_layer(overflow, monkeypatch):
    """x + attention behind its indexer: every chip computes them alike:
    counted ONCE; the held experts' parts are summed over the 8 chips. Also
    where one chip's experts receive more rows than the layer's row bound
    (short row tiles give the tiny layer a bound at all)."""
    from paddle_tpu.models import keye_vl
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import grouped

    monkeypatch.setattr(grouped, "ROW_TILES", (8,))
    cfg, ref, w, x = _uncut()
    i = 1
    if overflow:
        # the bias of the choice (zero in the model) as the test's lever:
        # every token's first four choices fall on chip 0's experts
        w = dict(w, **{f"{P}l{i}.expert_bias": jnp.asarray(
            np.where(np.arange(32) < 4, 50.0, 0.0), jnp.float32)})
    with jax.default_matmul_precision("highest"):
        whole, loss_whole, _, _ = ref.layer(x, w, i, cfg)
    whole = np.asarray(whole).reshape(T, 64)
    flat = np.asarray(x).reshape(T, 64)
    experts_sum, bound_passed = np.zeros_like(whole), []
    for chip in range(CHIPS):
        c, ws = ref.share_of(cfg, w, chip, CHIPS)

        def build(x_, c=c):
            y, routing, loss, (_, branch, _) = keye_vl.layer(x_, c, S, i)
            return [y, routing[2], branch, loss]

        got, held, branch, loss = _program_part(build, ws, {"x": flat})
        with jax.default_matmul_precision("highest"):
            want, _, _, _ = ref.layer(x, ws, i, c)
        _close(got, np.asarray(want).reshape(T, 64), tol=2e-5)
        _close(loss, [loss_whole], tol=1e-4, floor=1e-8)
        # the chip's part beyond what every chip computes alike
        experts_sum += got - flat - branch
        bound_passed.append(int(held[0]) > lm_ops.row_bound(8 * T, 4, 32))
    _close(flat + branch + experts_sum, whole, tol=5e-5)
    assert bound_passed[0] == overflow and not any(bound_passed[1:])


def test_the_vocabulary_s_shares_cut_the_table_and_the_head():
    cfg, ref, w, _ = _uncut()
    c, ws = ref.share_of(cfg, w, 5, CHIPS, vocab_chips=CHIPS)
    assert c["vocab_size"] == 128 and c["num_experts"] == 4 \
        and c["deployment"]["first_expert"] == 20
    assert np.array_equal(np.asarray(ws[P + "embed"]),
                          np.asarray(w[P + "embed"])[640:768])
    assert np.array_equal(np.asarray(ws[P + "head"]),
                          np.asarray(w[P + "head"])[:, 640:768])
    assert np.array_equal(np.asarray(ws[P + "l2.gate"]),
                          np.asarray(w[P + "l2.gate"])[20:24])
    for leaf in ("w_q", "w_qi", "w_ki", "w_w", "router", "ki_norm"):
        assert ws[P + "l0." + leaf] is w[P + "l0." + leaf]


def test_the_model_is_registered():
    from paddle_tpu import models

    assert "keye_vl" in models.__all__ and models.keye_vl.P == P
    for name in ("indexer_select", "sparse_attention", "indexer_loss",
                 "detached"):
        assert name in fluid.layers.nn.__all__
