"""`paddle_tpu.models.qwen3_next` at a small size with every published
RATIO kept (hidden 64; 2 key heads serving 4 value heads of 16; a
convolution of 4 taps; 8 query heads on 1 key/value head of 16 with a
quarter of it rotated; top-10 of 32 routed experts of which 8 held, a
shared expert; one period: delta, delta, delta, full; 2 x 32 tokens in
chunks of 8) against the plain float32 reference of
`chipbench/reference/qwen3_next_80b_a3b.py`, whose delta rule runs TOKEN BY
TOKEN, on seeded weights read out of the scope; the chunked op and its
hand-written gradient alone against that recurrence and `jax.grad` of it;
what the model forced (the convolution's silu variant, flash at heads of
256 with groups of 8); and the tests that tie a chip's share to the model.

Tolerance: float32 against float32 on the CPU; the two differ in the order
of float32 sums only: 1e-5 of the largest element, as tests/test_lfm2.py
has it (the two per-head scalars' gradients sum 64 tokens of exponentials:
1e-4). The first AdamW step is judged on the gradients the system itself
produced, for the reason given in tests/test_xing4.py.
"""

import json
import os
import sys
import types
from unittest import mock

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
    shared_expert_intermediate_size=32, num_attention_heads=8,
    num_key_value_heads=1, head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, num_experts=8, vocab_size=256,
    sequence_length=32, deployment=dict(num_experts=32, first_expert=8))
PEAK_RATE = 3e-4     # a recipe's (the file's `assumed.optimizer`)
T, E_ALL, P = 64, 32, "qwen3next."
KINDS = ["linear_attention"] * 3 + ["full_attention"]


def _file():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
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


def _run_small(cfg, seed=5):
    """The system's numbers on one seeded batch: weights as drawn but the
    routers' (std 0.5: logits far enough apart that float32 sums in another
    order do not flip a choice) and the convolution's taps (std 0.5: a
    convolution that matters)."""
    from chipbench.configs import qwen3_next_80b_a3b as builder

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
        for n in names:
            if n.endswith(("router", "conv_taps")):
                scope.set_var(n, rs.normal(0, 0.5, shapes[n]).astype(
                    np.float32))
        w0 = {n: np.asarray(scope.find_var(n)) for n in names}
        branches = [v for _, u, o in built["operators"] for v in (u, o)]
        own = [v for i in sorted(built["delta_ops"])
               for v in built["delta_ops"][i]]
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
        names=names, w0=w0, w1=w1, logits=logits, loss=got[0],
        operators=list(zip(ops[::2], ops[1::2])),
        delta_ops={i: own_got[5 * j:5 * j + 5]
                   for j, i in enumerate(sorted(built["delta_ops"]))},
        routing=[got[1 + 3 * i:4 + 3 * i] for i in range(n_r // 3)],
        grads=dict(zip(trained, got[1 + n_r:])))


@pytest.fixture(scope="module", autouse=True)
def chunks_of_8():
    """The lowering's chunk is its own constant (128, swept on the v5e): at
    rows of 32 tokens the tests shorten it, so a row is several chunks."""
    from paddle_tpu.parallel import delta_rule

    with mock.patch.object(delta_rule, "CHUNK", 8):
        yield


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
    assert set(small["builder"].sampled_params(small["cfg"]).values()) \
        <= set(got)
    from paddle_tpu.models import qwen3_next
    assert qwen3_next.layer_kinds(small["cfg"]) == KINDS \
        == small["ref"].layer_kinds(small["cfg"])


def test_the_file_s_parameter_count_is_the_program_s():
    """At the published widths (the program is only built, nothing runs):
    every trained parameter of the program, against `parameters` and the
    parts the file gives, and the issue's arithmetic."""
    from chipbench.configs import qwen3_next_80b_a3b as builder

    cfg = _file()
    prog = builder.build(fluid, cfg, 1)["prog"]
    sizes = {p.name: int(np.prod(p.shape))
             for p in prog.global_block().all_parameters()
             if builder.reference.trained(p.name)}
    parts = cfg["parameters_by_part"]
    held, one = cfg["num_experts"], 3 * 2048 * 512
    assert sum(sizes.values()) == cfg["parameters"] \
        == 3 * parts["delta_layer"] + parts["full_attention_layer"] \
        + parts["table"] + parts["head"] + parts["final_norm"]

    def of(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert sizes[P + "embed"] == sizes[P + "head"] == 18992 * 2048
    assert {of(f"{P}l{i}.") for i in (0, 1, 2)} == {parts["delta_layer"]}
    assert of(P + "l3.") == parts["full_attention_layer"]
    delta = sum(sizes[P + "l0." + n] for n in (
        "w_qkvz", "w_ba", "conv_taps", "A_log", "dt_bias", "gated_norm",
        "w_o"))
    assert delta == parts["delta_operator"] == 33718464 \
        == 2048 * 12288 + 2048 * 64 + 8192 * 4 + 64 + 128 + 4096 * 2048
    full = sum(sizes[P + "l3." + n] for n in (
        "w_qg", "w_k", "w_v", "w_o", "q_norm", "k_norm"))
    assert full == parts["full_attention_operator"] == 27263488
    assert sizes[P + "l0.gate"] * 3 == parts["held_experts_a_layer"] \
        == held * one == held * parts["one_expert"]
    assert parts["feed_forward_a_layer"] == held * one + one + 2048 \
        + 2048 * 512
    # with the 32 held experts the issue asked for: its 625,667,136
    assert cfg["parameters"] + 4 * (32 - held) * one == 625667136
    # no width differs from the published config; the floors are kept
    for key, want in dict(
            hidden_size=2048, head_dim=256, num_attention_heads=16,
            num_key_value_heads=2, linear_num_key_heads=16,
            linear_num_value_heads=32, linear_key_head_dim=128,
            linear_value_head_dim=128, linear_conv_kernel_dim=4,
            moe_intermediate_size=512, shared_expert_intermediate_size=512,
            num_experts_per_tok=10, partial_rotary_factor=0.25,
            rope_theta=10000000, rms_norm_eps=1e-6, norm_topk_prob=True,
            full_attention_interval=4, intermediate_size=5120).items():
        assert cfg[key] == want
    dep = cfg["deployment"]
    assert cfg["num_hidden_layers"] == 4 == len(dep["layers_held"]) \
        == cfg["full_attention_interval"]
    assert dep["num_experts"] == 512 \
        == dep["chips_sharing_a_layer"] * cfg["num_experts"]
    assert held >= 8 and dep["first_expert"] == dep["chip"] * held > 0
    assert cfg["vocab_size"] * 8 == dep["vocab_size"] == 151936
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size"])
    assert cfg["source"] == ("https://huggingface.co/Qwen/Qwen3-Next-80B-"
                             "A3B-Instruct/blob/main/config.json")


def test_logits(small):
    _close(small["logits"], np.asarray(small["want"]["logits"]).reshape(
        T, -1))


def test_loss(small):
    _close(small["loss"], [float(small["want"]["loss"])])


@pytest.mark.parametrize("layer", range(4))
def test_operator_branch_of_each_layer_first_hand(small, layer):
    u, got = small["operators"][layer]
    w = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    want = small["ref"].operator_branch(
        small["cfg"], w, layer, jnp.asarray(u).reshape(2, 32, 64))
    _close(got, np.asarray(want).reshape(T, 64))


@pytest.mark.parametrize("layer", range(3))
def test_the_ops_of_each_delta_layer_first_hand(small, layer):
    """The convolution's op against four shifted slices and the delta
    rule's op (output and final state) against the token-by-token
    recurrence, each on the op's own input."""
    ref, cfg = small["ref"], small["cfg"]
    x, mixed, ba, o, last = small["delta_ops"][layer]
    p = f"{P}l{layer}."
    w = {k: jnp.asarray(v) for k, v in small["w0"].items()}
    with jax.default_matmul_precision("highest"):
        conv = ref.silu_conv(jnp.asarray(x).reshape(2, 32, -1),
                             w[p + "conv_taps"])
        o_ref, last_ref = ref.delta_rule(*ref.delta_inputs(
            jnp.asarray(mixed).reshape(2, 32, -1),
            jnp.asarray(ba).reshape(2, 32, -1), w, p, cfg))
    _close(mixed, np.asarray(conv).reshape(T, -1))
    _close(o, np.asarray(o_ref).reshape(T, -1))
    _close(last, last_ref)
    assert last.shape == (2, 4, 16, 16)


@pytest.mark.parametrize("layer", range(4))
def test_routing_is_the_ten_largest_renormalised(small, layer):
    ids, load, rows = small["routing"][layer]
    chosen_by, top = small["want"]["routing"][layer]
    assert ids.shape == (T, 10)
    np.testing.assert_array_equal(np.sort(ids, axis=1),
                                  np.sort(np.asarray(top), axis=1))
    np.testing.assert_array_equal(
        load, np.bincount(np.asarray(top).ravel(), minlength=E_ALL))
    assert int(rows[0]) == int(load[8:16].sum()) > 0


def test_the_zero_bias_is_not_trained_and_changes_no_choice(small):
    for i in range(4):
        n = f"{P}l{i}.expert_bias"
        assert not small["w0"][n].any()
        np.testing.assert_array_equal(small["w0"][n], small["w1"][n])
        assert n not in small["grads"]


def test_every_gradient(small):
    for name, g in small["grads"].items():
        tol = 1e-4 if name.endswith(("A_log", "dt_bias")) else 1e-5
        _close(g, small["want"]["grads"][name], tol=tol, floor=1e-9)


def _sampled():
    from chipbench.configs import qwen3_next_80b_a3b as builder
    return sorted(builder.sampled_params(_cfg()))


@pytest.mark.parametrize("which", _sampled())
def test_sampled_gradient_and_first_update(small, which):
    name = small["builder"].sampled_params(small["cfg"])[which]
    tol = 1e-4 if name.endswith(("A_log", "dt_bias")) else 1e-5
    _close(small["grads"][name], small["want"]["grads"][name], tol=tol,
           floor=1e-9)
    _close(small["w1"][name] - small["w0"][name],
           small["want"]["delta"][name], tol=2e-3, floor=1e-9)


def test_the_decay_spares_the_norms_and_the_two_gate_scalars(small):
    from paddle_tpu.models import qwen3_next

    ref = small["ref"]
    for n in small["names"]:
        assert qwen3_next.decays(n) == ref.decays(n)
    spared = {n.rsplit(".", 1)[1] for n in small["names"]
              if not ref.decays(n)}
    assert spared == {"operator_norm", "ffn_norm", "final_norm", "q_norm",
                      "k_norm", "gated_norm", "A_log", "dt_bias"}
    # at the recipe's rate the decay is a visible part of the step
    o, n = small["cfg"]["optimizer"], P + "l0.conv_taps"
    moved = small["w1"][n] - small["w0"][n]
    undecayed = moved + o["learning_rate"] * o["weight_decay"] * small["w0"][n]
    assert np.abs(moved - undecayed).max() > 0.01 * np.abs(moved).max()


def test_the_gate_scalars_are_drawn_as_the_family_draws_them(small):
    a = np.exp(small["w0"][P + "l0.A_log"])
    assert a.shape == (4,) and (a > 0).all() and (a < 16).all()
    dt = np.log1p(np.exp(small["w0"][P + "l1.dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert len({float(v) for v in small["w0"][P + "l0.A_log"]}) == 4


def test_the_startup_program_under_the_policy_leaves_float32_masters():
    """The cells run their startup program with bf16 AMP on: every
    parameter, the two gate scalars with their initialisers' own ops among
    them, is a float32 master (a bf16 dt_bias fails the K-step scan's
    carry)."""
    from chipbench.configs import qwen3_next_80b_a3b as builder
    from paddle_tpu import amp

    amp.enable("bfloat16")
    try:
        built = builder.build(fluid, _cfg(), 3)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(built["startup"])
            dtypes = {p.name: str(np.asarray(scope.find_var(p.name)).dtype)
                      for p in built["prog"].global_block().all_parameters()}
    finally:
        amp.disable()
    assert set(dtypes.values()) == {"float32"}, dtypes


@pytest.mark.parametrize("place", ["cpu", "tpu"])
def test_lowered_counts_name_the_delta_rule_and_the_conv(small, place):
    from paddle_tpu.ops import lm_ops

    prog = small["built"]["prog"]
    got = lm_ops.lowered_counts(prog, types.SimpleNamespace(platform=place))
    want = {"moe_ffn_grouped": 4, "moe_ffn_held_experts": 4,
            "moe_ffn_row_bound": 4, "short_conv_silu": 3,
            "short_conv_silu_grad_by_hand": 3, "delta_rule_chunked": 3,
            "delta_rule_grad_by_hand": 3, "gated_norm_one_op": 3,
            "gated_norm_grad_by_hand": 3}
    if place == "tpu":
        # 128 channels are a lane tile: the variant's kernels take them
        # (heads of 16 in chunks of 8 are none: no `delta_rule_kernel`, no
        # `gated_norm_kernel`)
        want.update(flash_attention=1, flash_attention_bwd=1,
                    flash_attention_head_groups=1,
                    flash_fwd_visited_blocks=1, flash_fwd_masked_blocks=1,
                    short_conv_silu_kernel=3, short_conv_silu_grad_kernel=3)
    assert got == want
    test = lm_ops.lowered_counts(small["built"]["test_prog"],
                                 types.SimpleNamespace(platform="cpu"))
    assert "delta_rule_grad_by_hand" not in test
    assert test["delta_rule_chunked"] == 3


def test_lowered_counts_at_the_published_widths_under_the_policy():
    """The cell's program, built under bf16 AMP: flash at heads of 256, the
    grouped kernels at K 2048 / F 512, the convolution's silu variant by
    its two kernels ([8192, 8192] in blocks of 256 tokens) and the delta
    rule's in-chunk work by its two kernels (heads of 128 in chunks of
    128: the counters are read under the lowering's real chunk), the gated
    norm as one op a layer by its two kernels ([8192, 32 heads of 128])."""
    from chipbench.configs import qwen3_next_80b_a3b as builder
    from paddle_tpu import amp
    from paddle_tpu.ops import lm_ops

    from paddle_tpu.parallel import delta_rule

    amp.enable("bfloat16")
    try:
        with mock.patch.object(delta_rule, "CHUNK", 128):
            prog = builder.build(fluid, _file(), 1)["prog"]
        got = lm_ops.lowered_counts(prog,
                                    types.SimpleNamespace(platform="tpu"))
        on_cpu = lm_ops.lowered_counts(
            prog, types.SimpleNamespace(platform="cpu"))
    finally:
        amp.disable()
    assert "delta_rule_kernel" not in on_cpu
    assert on_cpu["delta_rule_chunked"] == 3
    assert "gated_norm_kernel" not in on_cpu
    assert on_cpu["gated_norm_one_op"] == 3
    assert got == dict(
        moe_ffn_grouped=4, grouped_matmul_kernel=4, grouped_mlp_epilogues=4,
        flash_attention=1, flash_attention_bwd=1,
        flash_attention_head_groups=1, flash_attention_head_256=1,
        moe_ffn_held_experts=4, moe_ffn_row_bound=4, moe_ffn_kept_copies=4,
        lookup_table_grad_tiled=1, moe_ffn_rows_by_token=4,
        short_conv_silu=3, short_conv_silu_grad_by_hand=3,
        short_conv_silu_kernel=3, short_conv_silu_grad_kernel=3,
        delta_rule_chunked=3, delta_rule_grad_by_hand=3,
        delta_rule_kernel=3, delta_rule_grad_kernel=3,
        gated_norm_one_op=3, gated_norm_grad_by_hand=3,
        gated_norm_kernel=3, gated_norm_grad_kernel=3,
        flash_fwd_visited_blocks=36, flash_fwd_masked_blocks=8)


def test_the_program_names_its_scopes(small):
    prog = small["built"]["prog"]
    by_type = {}
    for op in prog.global_block().ops:
        by_type.setdefault(op.type, set()).add(
            str(op.attrs.get("op_namescope", "")).strip("/"))
    scopes = set().union(*by_type.values())
    assert {"embed", "delta", "attn", "moe", "lm_head"} \
        <= {s.split("/")[0] for s in scopes}
    assert {"delta/norm", "delta/in_proj", "delta/short_conv",
            "delta/delta_rule", "delta/gated_norm", "delta/out_proj",
            "attn/norm", "attn/qk_norm", "attn/rotary", "attn/gate",
            "moe/shared"} <= scopes
    assert by_type["short_conv"] == {"delta/short_conv"}
    assert by_type["gated_delta_rule"] == {"delta/delta_rule"}
    assert by_type["gated_delta_rule_grad"] == {"delta/delta_rule"}
    # the gated norm is ONE op a layer (and one grad op), between two
    # reshapes: no `rms_norm`, `swish` or `elementwise_mul` of its own
    assert by_type["gated_rms_norm"] == {"delta/gated_norm"}
    assert by_type["gated_rms_norm_grad"] == {"delta/gated_norm"}
    under = [op.type for op in prog.global_block().ops
             if str(op.attrs.get("op_namescope", "")).strip("/")
             == "delta/gated_norm"]
    assert sorted(set(under) - {"reshape", "reshape_grad", "reshape2",
                                "reshape2_grad"}) \
        == ["gated_rms_norm", "gated_rms_norm_grad"]
    assert under.count("gated_rms_norm") == 3
    assert "delta/gated_norm" not in by_type["rms_norm"] \
        | by_type.get("swish", set())
    assert by_type["causal_attention"] == {"attn"}
    assert by_type["moe_ffn"] == {"moe"}
    assert by_type["rotary_embedding"] == {"attn/rotary"}


# ------------------------------------------- the chunked op and its gradient
def _delta_case(S, rows, hk, hv, d, a_scale, dtype, seed):
    rng = np.random.default_rng(seed)
    n = rows * S
    qkv = jnp.asarray(rng.normal(size=(n, 2 * hk * d + hv * d)), dtype)
    ba = jnp.asarray(rng.normal(size=(n, 2 * hv)), dtype)
    a_log = jnp.asarray(np.log(rng.uniform(0.5, 16, hv) * a_scale),
                        jnp.float32)
    dt_bias = jnp.asarray(rng.normal(size=hv), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(n, hv * d)), dtype)
    return qkv, ba, a_log, dt_bias, cot


def _recurrence(qkv, ba, a_log, dt_bias, rows, S, hk, hv, d):
    from chipbench.reference import qwen3_next_80b_a3b as ref

    cfg = dict(linear_num_key_heads=hk, linear_num_value_heads=hv,
               linear_key_head_dim=d, linear_value_head_dim=d)
    w = {"A_log": a_log, "dt_bias": dt_bias}
    o, last = ref.delta_rule(*ref.delta_inputs(
        qkv.astype(jnp.float32).reshape(rows, S, -1),
        ba.astype(jnp.float32).reshape(rows, S, -1), w, "", cfg))
    return o.reshape(rows * S, -1), last


@pytest.mark.parametrize("S,chunk,heads,a_scale,dtype", [
    (32, 8, (2, 4), 1.0, "float32"),       # whole chunks
    (29, 8, (2, 4), 1.0, "float32"),       # a padded last chunk
    (64, 64, (1, 2), 0.2, "float32"),      # one chunk a row
    (64, 32, (4, 8), 1.0, "float32"),      # four head groups
    (40, 8, (2, 4), 1e-3, "float32"),      # g near 0: no decay
    (64, 64, (2, 4), 1.0, "float32"),      # a chunk's sum of g near -100
    (64, 16, (2, 4), 1.0, "bfloat16"),
], ids=["whole_chunks", "padded_chunk", "one_chunk", "head_groups",
        "g_near_zero", "chunk_sum_near_minus_100", "bf16"])
def test_the_chunked_delta_rule_and_its_gradient_by_hand(S, chunk, heads,
                                                         a_scale, dtype):
    """`parallel/delta_rule.py` against the token-by-token recurrence and
    `jax.grad` of it: the output, the final state and all four gradients
    (d qkv, d [b | a], d A_log, d dt_bias)."""
    from chipbench.reference import qwen3_next_80b_a3b as ref
    from paddle_tpu.parallel import delta_rule as dr

    hk, hv = heads
    rows, d = 2, 16
    qkv, ba, a_log, dt_bias, cot = _delta_case(
        S, rows, hk, hv, d, a_scale, jnp.dtype(dtype), 3)
    shape = dict(seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=chunk, eps=1e-6)

    def want_fn(*a):
        return _recurrence(*a, rows, S, hk, hv, d)

    with jax.default_matmul_precision("highest"):
        out, starts, last = jax.jit(
            lambda *a: dr.delta_rule_fwd(*a, **shape))(qkv, ba, a_log,
                                                       dt_bias)
        got = jax.jit(lambda *a: dr.delta_rule_bwd(*a, **shape))(
            qkv, ba, a_log, dt_bias, starts, cot)
        o_ref, last_ref = want_fn(qkv, ba, a_log, dt_bias)
        want = jax.grad(lambda *a: jnp.sum(
            want_fn(*a)[0] * cot.astype(jnp.float32)),
            argnums=(0, 1, 2, 3))(qkv, ba, a_log, dt_bias)
        g = np.asarray(ref.gates(ba.astype(jnp.float32), a_log,
                                 dt_bias)[0]).reshape(rows, S, hv)
    low = g[:, :min(chunk, S)].sum(axis=1).min()
    if a_scale == 1e-3:
        assert low > -0.5                       # no decay to speak of
    if chunk == 64 and a_scale == 1.0:
        assert low < -80                        # exp(low) underflows float32
    assert starts.shape == dr.states_shape(rows, S, hk, hv, d, d, chunk)
    assert starts.dtype == last.dtype == jnp.float32
    assert out.dtype == got[0].dtype == qkv.dtype
    assert not np.asarray(starts[:, 0]).any()       # a row starts from zero
    tight = dtype == "float32"
    tol = 2e-5 if tight else 0.03
    _close(np.asarray(out, np.float32), o_ref, tol=tol)
    _close(last, last_ref, tol=tol)
    for a, b, t in zip(got, want, (tol, tol, 20 * tol if tight else 0.05,
                                   20 * tol if tight else 0.05)):
        _close(np.asarray(a, np.float32), np.asarray(b, np.float32), tol=t,
               floor=1e-7)


def test_value_heads_2j_and_2j_plus_1_read_key_head_j():
    """Changing key head 1's q and k moves the outputs of value heads 2 and
    3 and of no other; changing value head 2's v moves that head alone."""
    from paddle_tpu.parallel import delta_rule as dr

    hk, hv, d, S = 2, 4, 16, 16
    qkv, ba, a_log, dt_bias, _ = _delta_case(S, 1, hk, hv, d, 1.0,
                                             jnp.float32, 4)
    shape = dict(seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=8, eps=1e-6)

    def out(x):
        return np.asarray(dr.delta_rule_fwd(x, ba, a_log, dt_bias,
                                            **shape)[0]).reshape(S, hv, d)

    base = out(qkv)
    for cols, moved in (((d, 2 * d), {2, 3}),                  # q of key 1
                        ((hk * d + d, hk * d + 2 * d), {2, 3}),  # k of key 1
                        ((2 * hk * d + 2 * d, 2 * hk * d + 3 * d), {2})):
        changed = out(qkv.at[:, cols[0]:cols[1]].multiply(-0.5))
        differs = {h for h in range(hv)
                   if np.abs(changed[:, h] - base[:, h]).max() > 1e-6}
        assert differs == moved


def test_the_op_s_gradient_is_the_hand_written_one(small):
    """The program's backward holds `gated_delta_rule_grad` ops that read
    the forward's chunk-start states, and no generic vjp of the op."""
    ops = small["built"]["prog"].global_block().ops
    grads = [op for op in ops if op.type == "gated_delta_rule_grad"]
    fwd = [op for op in ops if op.type == "gated_delta_rule"]
    assert len(grads) == len(fwd) == 3
    assert {g.input("States")[0] for g in grads} \
        == {f.output("States")[0] for f in fwd}
    import inspect

    from paddle_tpu.parallel import delta_rule as dr
    src = inspect.getsource(dr._one_group_bwd)
    # the one vjp is of `_parts`, which holds no scan
    assert src.count("jax.vjp") == 1 and "_parts(" in src
    assert "scan" not in inspect.getsource(dr._parts)
    assert "scan" not in inspect.getsource(dr._prepared)


# ---------------------------------------------------------- the kernel path
def _both_paths(qkv, ba, a_log, dt_bias, cot, shape):
    """((out, last state, the q, k and v thirds of d qkv, d [b | a], d
    A_log, d dt_bias) of the kernel path, interpreted, the same of the
    plain form)."""
    from paddle_tpu.parallel import delta_rule as dr

    cuts = [shape["hk"] * shape["dk"], 2 * shape["hk"] * shape["dk"]]

    def run(fwd, bwd):
        out, starts, last = jax.jit(lambda *a: fwd(*a, **shape))(
            qkv, ba, a_log, dt_bias)
        d_qkv, *rest = jax.jit(lambda *a: bwd(*a, **shape))(
            qkv, ba, a_log, dt_bias, starts, cot)
        return (out, last, *jnp.split(d_qkv, cuts, axis=-1), *rest), starts

    return (run(dr.kernels_fwd, dr.kernels_bwd),
            run(dr.delta_rule_fwd, dr.delta_rule_bwd))


def _rms_apart(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.sqrt(np.mean((a - b) ** 2))
                 / (np.sqrt(np.mean(b ** 2)) + 1e-30))


@pytest.mark.parametrize("heads", [(1, 1), (1, 2)], ids=["1to1", "1to2"])
@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_path_against_the_plain_form(dtype, rows, heads):
    """`kernels_fwd` / `kernels_bwd` (the two Pallas kernels of
    `parallel/delta_parts.py`, interpreted) against the plain chunked form
    on the same inputs: the output, the final state and the gradients to
    q, k, v, [b | a], A_log and dt_bias. Float32 differs by the order of
    sums and the triangular inverse's three bf16 passes written out (the
    CPU runs the plain form's `Precision.HIGH` exactly); bf16 by roundings
    of cotangents the plain vjp leaves float32."""
    from paddle_tpu.parallel import delta_rule as dr

    hk, hv = heads
    S, d, chunk = 256, 128, 128
    qkv, ba, a_log, dt_bias, cot = _delta_case(
        S, rows, hk, hv, d, 0.05, jnp.dtype(dtype), 5)
    shape = dict(seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=chunk, eps=1e-6)
    assert dr.takes(rows, S, hk, hv, d, d, chunk, dtype)
    (got, starts), (want, plain_starts) = _both_paths(
        qkv, ba, a_log, dt_bias, cot, shape)
    assert starts.shape == dr.states_shape(rows, S, hk, hv, d, d, chunk,
                                           kernels=True)
    assert starts.dtype == got[1].dtype == jnp.float32
    assert got[0].dtype == got[2].dtype == qkv.dtype
    # (one key head: the plain form works it as one group)
    np.testing.assert_allclose(
        np.asarray(starts), np.asarray(plain_starts)[0],
        atol=2e-5 if dtype == "float32" else 2e-3)
    tol = 5e-5 if dtype == "float32" else 0.02
    for name, a, b in zip(("out", "last", "d_q", "d_k", "d_v", "d_ba",
                           "d_a_log", "d_dt_bias"), got, want):
        assert a.shape == b.shape, name
        assert _rms_apart(a, b) < tol, (name, _rms_apart(a, b))


@pytest.mark.parametrize("knob,value,S,heads", [
    ("CHUNKS_A_STEP", 4, 512, (1, 2)), ("CHUNKS_A_STEP", 1, 256, (2, 4)),
    ("KEEPS_INVERSE", False, 256, (1, 2))],
    ids=["four_chunks_a_step", "one_chunk_a_step_two_key_heads",
         "the_inverse_formed_again"])
def test_the_kernel_path_s_knobs_change_no_result(knob, value, S, heads):
    """What the sweep turns (`tools/delta_rule_sweep.py`): the chunks a grid
    step works side by side, the backward forming the triangular inverse
    again instead of reading it."""
    from paddle_tpu.parallel import delta_parts
    from paddle_tpu.parallel import delta_rule as dr

    hk, hv = heads
    d, chunk = 128, 128
    qkv, ba, a_log, dt_bias, cot = _delta_case(S, 1, hk, hv, d, 0.05,
                                               jnp.float32, 9)
    shape = dict(seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=chunk, eps=1e-6)
    home = dr if hasattr(dr, knob) else delta_parts
    with mock.patch.object(home, knob, value):
        (got, starts), (want, _) = _both_paths(qkv, ba, a_log, dt_bias, cot,
                                               shape)
        assert starts.shape == dr.states_shape(1, S, hk, hv, d, d, chunk,
                                               kernels=True)
    for name, a, b in zip(("out", "last", "d_q", "d_k", "d_v", "d_ba",
                           "d_a_log", "d_dt_bias"), got, want):
        assert _rms_apart(a, b) < 5e-5, (name, _rms_apart(a, b))


@pytest.mark.parametrize("refused", ["head_of_16", "chunk_of_8",
                                     "ragged_row", "float16"])
def test_a_shape_the_kernels_refuse_runs_the_plain_form(monkeypatch,
                                                        refused):
    """On a TPU place the op hands itself to the kernels only where `takes`
    holds; every other shape gives the plain form's results bit for bit
    and its `States` layout."""
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import delta_rule as dr

    S, d, chunk, dtype = {"head_of_16": (32, 16, 8, "float32"),
                          "chunk_of_8": (32, 128, 8, "float32"),
                          "ragged_row": (200, 128, 128, "float32"),
                          "float16": (128, 128, 128, "float16")}[refused]
    hk, hv = 1, 2
    assert not dr.takes(1, S, hk, hv, d, d, chunk, dtype)
    qkv, ba, a_log, dt_bias, _ = _delta_case(S, 1, hk, hv, d, 0.05,
                                             jnp.dtype(dtype), 6)
    attrs = dict(seq_len=S, num_k_heads=hk, num_v_heads=hv, head_k_dim=d,
                 head_v_dim=d, chunk=chunk)
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(dr, "kernels_fwd", None)    # a call would raise
    res = lm_ops.gated_delta_rule_op(None, {
        "QKV": [qkv], "BA": [ba], "ALog": [a_log], "DtBias": [dt_bias]},
        attrs)
    want = dr.delta_rule_fwd(qkv, ba, a_log, dt_bias, seq_len=S, hk=hk,
                             hv=hv, dk=d, dv=d, chunk=chunk, eps=1e-6)
    for got, ref in zip((res["Out"][0], res["States"][0],
                         res["FinalState"][0]), want):
        assert got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32))


def test_the_op_takes_the_kernels_on_a_tpu_place_and_not_elsewhere(
        monkeypatch):
    """Forward op and grad op decide alike (`States` is laid out by the
    path that wrote it): the kernel path under a TPU place at a shape
    `takes` holds for, the plain form on the CPU."""
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import delta_rule as dr

    S, d, hk, hv = 128, 128, 1, 2
    qkv, ba, a_log, dt_bias, cot = _delta_case(S, 1, hk, hv, d, 0.05,
                                               jnp.float32, 7)
    ins = {"QKV": [qkv], "BA": [ba], "ALog": [a_log], "DtBias": [dt_bias]}
    attrs = dict(seq_len=S, num_k_heads=hk, num_v_heads=hv, head_k_dim=d,
                 head_v_dim=d, chunk=128)
    called = []
    for name in ("kernels_fwd", "kernels_bwd", "delta_rule_fwd",
                 "delta_rule_bwd"):
        real = getattr(dr, name)

        def spy(*a, real=real, name=name, **kw):
            called.append(name)
            return real(*a, **kw)

        monkeypatch.setattr(dr, name, spy)
    for tpu, want in ((True, ["kernels_fwd", "kernels_bwd"]),
                      (False, ["delta_rule_fwd", "delta_rule_bwd"])):
        del called[:]
        monkeypatch.setattr(lm_ops, "on_tpu", lambda tpu=tpu: tpu)
        res = lm_ops.gated_delta_rule_op(None, ins, attrs)
        lm_ops.gated_delta_rule_grad_op(
            None, dict(ins, States=res["States"], **{"Out@GRAD": [cot]}),
            attrs)
        assert called == want


def _planted(name):
    """The study's own plant (`chipbench.lower_precision_lm_delta_share`),
    or the probe's patch of the inverse's precision
    (`compare_lm_delta_share.delta_ops_in_float32`)."""
    from chipbench import lower_precision_lm_delta_share as study
    from paddle_tpu.parallel import delta_rule as dr

    if name == "inverse_highest":
        return mock.patch.object(dr, "INVERSE_PRECISION",
                                 jax.lax.Precision.HIGHEST)
    return study._planted(name)


@pytest.mark.parametrize("plant,moves_by", [
    ("state_bf16", 1e-5), ("g_bf16", 1e-5), ("no_decay", 1e-2),
    ("beta_one", 1e-2), ("no_qk_norm", 1e-2), ("inverse_highest", 1e-9)])
def test_every_plant_of_the_study_bites_on_the_kernel_path(plant, moves_by):
    """The study one precision down and the comparison's float32 probe
    replace `_states`, `_states_transposed`, `gates`, `l2_normalized` and
    `INVERSE_PRECISION` on `parallel/delta_rule.py` from outside: the
    kernel path looks each up as it is traced, so the output and the
    gradient move under every one of them (a kernel that carried the
    state, or formed the gates, itself would make the plant a no-op and
    the study a study of nothing)."""
    S, d, hk, hv = 256, 128, 1, 2
    qkv, ba, a_log, dt_bias, cot = _delta_case(S, 1, hk, hv, d, 0.05,
                                               jnp.float32, 8)
    shape = dict(seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=128, eps=1e-6)
    (stated, _), _ = _both_paths(qkv, ba, a_log, dt_bias, cot, shape)
    with _planted(plant):
        (planted, _), _ = _both_paths(qkv, ba, a_log, dt_bias, cot, shape)
    # (without the norm a chunk's inverse overflows: that moved too)
    assert not _rms_apart(planted[0], stated[0]) <= moves_by     # out
    assert not _rms_apart(planted[3], stated[3]) <= moves_by     # d k


# ------------------------------------------------- the convolution's variant
@pytest.mark.parametrize("S,L,dtype", [(32, 4, "float32"), (7, 4, "float32"),
                                       (16, 2, "float32"),
                                       (32, 4, "bfloat16")])
def test_the_silu_convolution_against_shifted_slices(S, L, dtype):
    from chipbench.reference import qwen3_next_80b_a3b as ref
    from paddle_tpu.ops import lm_ops

    rng = np.random.default_rng(6)
    C, rows = 24, 3
    x = jnp.asarray(rng.normal(size=(rows * S, C)), jnp.dtype(dtype))
    w = jnp.asarray(rng.normal(0, 0.5, (L, C)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(rows * S, C)), jnp.dtype(dtype))

    def want_fn(x, w):
        return ref.silu_conv(x.astype(jnp.float32).reshape(rows, S, C),
                             w).reshape(rows * S, C)

    got = lm_ops.silu_conv(x, w, S)
    d_x, d_w = lm_ops.silu_conv_grad(x, w, cot, S)
    want_dx, want_dw = jax.grad(lambda x, w: jnp.sum(
        want_fn(x, w) * cot.astype(jnp.float32)), argnums=(0, 1))(x, w)
    tol = 1e-5 if dtype == "float32" else 0.02
    assert got.dtype == d_x.dtype == x.dtype and d_w.dtype == jnp.float32
    _close(np.asarray(got, np.float32), want_fn(x, w), tol=tol)
    _close(np.asarray(d_x, np.float32), np.asarray(want_dx, np.float32),
           tol=tol)
    _close(d_w, want_dw, tol=tol)
    # rows are separate sequences: a row's first token sees no other row
    lone = lm_ops.silu_conv(x[S:2 * S], w, S)
    _close(np.asarray(lone, np.float32), np.asarray(got[S:2 * S],
                                                    np.float32), tol=1e-6)


@pytest.mark.parametrize("S,rows,C,L,dtype", [
    (64, 2, 256, 4, "float32"), (48, 1, 256, 2, "float32"),
    (16, 4, 128, 4, "float32"), (64, 2, 256, 4, "bfloat16")],
    ids=["one_block_a_row", "three_blocks_a_row", "rows_of_one_block",
         "bf16"])
def test_the_silu_convolution_s_kernels_against_the_plain_form(S, rows, C, L,
                                                               dtype):
    """`parallel/short_conv.py`'s variant kernels (interpreted), forward
    and backward, against `lm_ops.silu_conv` / `silu_conv_grad`: blocks
    that carry rows from and to their neighbours, rows that do not."""
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import short_conv as kernels

    rng = np.random.default_rng(1)
    x, g = (jnp.asarray(rng.normal(size=(rows * S, C)), jnp.dtype(dtype))
            for _ in range(2))
    w = jnp.asarray(rng.normal(0, 0.5, (L, C)), jnp.float32)
    assert kernels.silu_takes(rows * S, C, S, L, x.dtype)
    assert not kernels.silu_takes(rows * S, C + 8, S, L, x.dtype)
    tol = 1e-6 if dtype == "float32" else 1e-2     # one bf16 rounding
    _close(np.asarray(kernels.silu_conv_fwd(x, w, S), np.float32),
           np.asarray(lm_ops.silu_conv(x, w, S), np.float32), tol=tol)
    got, want = kernels.silu_conv_bwd(x, w, g, S), \
        lm_ops.silu_conv_grad(x, w, g, S)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        _close(np.asarray(a, np.float32), np.asarray(b, np.float32), tol=tol)


def test_the_layer_takes_the_variant_and_lfm2_s_form_is_unchanged():
    """`layers.short_conv(gating="silu")` appends the op with the attr and
    taps [L, C]; without it the op is LFM2's, attribute for attribute."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[12], dtype="float32")
        y = fluid.layers.short_conv(x, 8, kernel_size=4, gating="silu")
        z = fluid.layers.short_conv(x, 8, kernel_size=3)
    silu, gated = [op for op in prog.global_block().ops
                   if op.type == "short_conv"]
    assert silu.attrs["gating"] == "silu" and tuple(y.shape) == (-1, 12)
    assert "gating" not in gated.attrs and tuple(z.shape) == (-1, 4)
    assert set(k for k in gated.attrs if not k.startswith("op_")) \
        == {"seq_len"}
    shapes = {p.name: tuple(p.shape)
              for p in prog.global_block().all_parameters()}
    assert sorted(shapes.values()) == [(3, 4), (4, 12)]
    with pytest.raises(ValueError):
        fluid.layers.short_conv(x, 8, gating="gelu")


# ------------------------------------------- flash at heads of 256, groups of 8
@pytest.mark.parametrize("which", ["forward", "dkv", "dq"])
@pytest.mark.parametrize("S,dtype", [(128, "float32"), (100, "float32"),
                                     (128, "bfloat16")],
                         ids=["two_blocks", "padded", "bf16"])
def test_flash_at_heads_of_256_sixteen_on_two(S, dtype, which):
    """The cell's head shape at short rows: 16 query heads on 2 key/value
    heads (groups of 8) of D = 256, two lane tiles, over the whole
    triangle: the forward kernel and both backward kernels (interpreted)
    against the plain composition."""
    import ml_dtypes

    from paddle_tpu.ops.lm_ops import _plain_causal_attention
    from paddle_tpu.parallel.flash import flash_attention

    np_dtype = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    rng = np.random.default_rng(9)
    q, cot = (jnp.asarray(rng.normal(size=(1, 16, S, 256)).astype(np_dtype))
              for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(1, 2, S, 256)).astype(np_dtype))
            for _ in range(2))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64)

    def plain(q, k, v):
        f = (t.astype(jnp.float32) for t in (q, k, v))
        return _plain_causal_attention(*f)[0].astype(q.dtype)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(
            f(*a).astype(jnp.float32) * cot.astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    if which == "forward":
        got, want = [flash(q, k, v)], [plain(q, k, v)]
    else:
        got, want = grads(flash), grads(plain)
        pick = slice(1, 3) if which == "dkv" else slice(0, 1)
        got, want = got[pick], want[pick]
    tol = dict(atol=1e-4, rtol=1e-3) if dtype == "float32" \
        else dict(atol=0.3, rtol=0.05)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


# ----------------------------------------------------------- the share
CHIPS = 4


def _uncut():
    """An uncut tiny model: 32 experts all held, the whole vocabulary of
    1024 rows, and seeded weights; x a state."""
    cfg = _cfg(num_experts=32, vocab_size=1024,
               deployment=dict(num_experts=32, first_expert=0))
    from chipbench.reference import qwen3_next_80b_a3b as ref

    rs = np.random.default_rng(11)
    big = ("router", "taps", ".gate", ".up", ".down", "shared")

    def draw(n, s):
        if n.endswith("expert_bias"):
            return np.zeros(s)
        if n.endswith("A_log"):
            return np.log(rs.uniform(0.5, 8, s))
        return rs.normal(0, 0.3 if any(b in n for b in big) else 0.08, s)

    w = {n: jnp.asarray(draw(n, s), jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    x = jnp.asarray(rs.normal(0, 1, (2, 32, 64)), jnp.float32)
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


def _layer_parts(i, overflow=False):
    """A whole decoder layer on x [T, C]: what each of the 4 chips computes
    of it (the operator and the shared expert whole on every chip; the
    experts the held ones') and the uncut reference's layer. `overflow`:
    a choice that sends eight of every token's ten to chip 0's experts, so
    its rows pass the layer's row bound."""
    from paddle_tpu.models import qwen3_next

    cfg, ref, w, x = _uncut()
    if overflow:
        # the bias of the choice (zero in the model) as the test's lever:
        # every token's first eight choices fall on chip 0's experts
        w = dict(w, **{f"{P}l{i}.expert_bias": jnp.asarray(
            np.where(np.arange(32) < 8, 50.0, 0.0), jnp.float32)})
    kind = KINDS[i]
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.layer(x, w, i, kind, cfg)
    parts = []
    for chip in range(CHIPS):
        c, ws = ref.share_of(cfg, w, chip, CHIPS)

        def build(x_, c=c):
            y, routing, _ = qwen3_next.layer(x_, c, 32, i, kind)
            return [y, routing[2]]

        got, held = _program_part(build, ws,
                                  {"x": np.asarray(x.reshape(T, 64))})
        with jax.default_matmul_precision("highest"):
            want, _ = ref.layer(x, ws, i, kind, c)
        parts.append((got, np.asarray(want).reshape(T, 64), int(held[0])))
    return cfg, ref, w, x, np.asarray(whole).reshape(T, 64), parts


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["even", "past_the_row_bound"])
@pytest.mark.parametrize("i", [0, 3], ids=["delta_layer", "attention_layer"])
def test_the_shares_add_up_to_the_uncut_layer(i, overflow, monkeypatch):
    """x + operator + shared expert: every chip computes them alike:
    counted ONCE; the held experts' parts are summed over the chips. Also
    where one chip's experts receive more rows than the layer's row bound
    (short row tiles give the tiny layer a bound at all)."""
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import grouped

    if overflow:
        monkeypatch.setattr(grouped, "ROW_TILES", (8,))
    cfg, ref, w, x, whole, parts = _layer_parts(i, overflow)
    for got, want, _ in parts:
        _close(got, want)
    rows = [held for _, _, held in parts]
    assert sum(rows) == 10 * T
    if overflow:
        bound = lm_ops.row_bound(10 * T, 8, 32)
        assert bound < 10 * T and max(rows) > bound
    else:
        assert min(rows) > 0
    # what every chip computes alike: the layer with no routed expert
    p, eps = f"{P}l{i}.", cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        mid = x + ref.operator(ref.rms_norm(x, w[p + "operator_norm"], eps),
                               w, p, cfg, KINDS[i])
        u = ref.rms_norm(mid, w[p + "ffn_norm"], eps).reshape(T, 64)
        alike = mid.reshape(T, 64) + ref.shared_expert(u, w, p)
    alike = np.asarray(alike)
    total = alike + sum(got - alike for got, _, _ in parts)
    _close(total, whole)
    assert np.abs(whole - alike).max() > 1e-2 * np.abs(whole).max()


def test_the_vocabulary_share_is_a_slice():
    """Chip 1 of the 4 that share the vocabulary: its table is rows
    256..511 of the uncut table, its head those columns of the uncut
    head."""
    cfg, ref, w, _ = _uncut()
    c, ws = ref.share_of(cfg, w, 1, CHIPS, vocab_chips=CHIPS)
    assert c["vocab_size"] == 256 and c["num_experts"] == 8
    assert c["deployment"]["first_expert"] == 8
    np.testing.assert_array_equal(np.asarray(ws[P + "embed"]),
                                  np.asarray(w[P + "embed"][256:512]))
    np.testing.assert_array_equal(np.asarray(ws[P + "head"]),
                                  np.asarray(w[P + "head"][:, 256:512]))
    from paddle_tpu.models import qwen3_next

    ids = np.random.default_rng(3).integers(0, 256, (2, 32)).astype(np.int32)
    got, = _program_part(
        lambda t: [qwen3_next.qwen3_next(
            t, dict(c, num_hidden_layers=0))["logits"]], ws, {"tokens": ids})
    with jax.default_matmul_precision("highest"):
        emb = w[P + "embed"][jnp.asarray(ids) + 256]
        want = ref.rms_norm(emb, w[P + "final_norm"],
                            cfg["rms_norm_eps"]) @ w[P + "head"]
    _close(got, np.asarray(want).reshape(T, -1)[:, 256:512])


def test_the_reference_is_independent_of_the_system():
    path = os.path.join(REPO, "chipbench", "reference",
                        "qwen3_next_80b_a3b.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert 'PRECISION = "highest"' in text
    # the recurrence is a scan over tokens with the three lines of the rule
    assert "def token(state, x):" in text and "jnp.exp(g_t)" in text
