"""`chipbench/costs_lm.py` against counts taken from the plain
reference's own shapes: every `dot_general` of the reference's forward
pass at a small size is walked in its jaxpr and its multiply-adds x 2
summed by the part of the model it belongs to. The reference computes the
expert layer dense over all E experts and attention over the whole
square; the costs count the k experts a token meets and half the square,
so those two parts agree after the stated factor, the rest exactly."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import costs, costs_lm
from chipbench.reference import olmoe_1b_7b as ref

HERE = os.path.dirname(__file__)
SMALL = dict(hidden_size=64, num_attention_heads=4, num_experts=8,
             num_experts_per_tok=2, intermediate_size=32, vocab_size=256,
             num_hidden_layers=2)
S, ROWS = 16, 2


def _cfg(**over):
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "olmoe_1b_7b.json")) as f:
        return dict(json.load(f), **over)


def _dot_flops(jaxpr, out):
    """2 x multiply-adds of every dot_general, by (lhs shape, rhs shape)."""
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dot_flops(sub, out)
        if eqn.primitive.name != "dot_general":
            continue
        a, b = (v.aval.shape for v in eqn.invars)
        (ca, _), _ = eqn.params["dimension_numbers"]
        contract = int(np.prod([a[i] for i in ca]))
        n = 2 * int(np.prod(eqn.outvars[0].aval.shape)) * contract
        out[(a, b)] = out.get((a, b), 0) + n
    return out


def test_forward_flops_match_the_reference_s_products():
    cfg = _cfg(**SMALL)
    w = {n: jnp.zeros(s, jnp.float32)
         for n, s in ref.param_shapes(cfg).items()}
    tokens = jnp.zeros((ROWS, S), jnp.int32)
    dots = _dot_flops(jax.make_jaxpr(
        lambda w_: ref.forward(cfg, w_, tokens)[0])(w).jaxpr, {})
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    E, F, k = (cfg["num_experts"], cfg["intermediate_size"],
               cfg["num_experts_per_tok"])
    T, L = ROWS * S, cfg["num_hidden_layers"]
    by = {"projections": 0, "attention": 0, "router": 0, "experts": 0,
          "head": 0}
    for (a, b), n in dots.items():
        if b == (H, V):
            by["head"] += n
        elif b == (H, E):
            by["router"] += n
        elif b == (H, H):
            by["projections"] += n
        elif len(b) == 3 and b[0] == E or a[-1] == E * F or (
                len(a) == 3 and a[1:] == (E, F)):
            by["experts"] += n
        else:
            by["attention"] += n
    assert sum(by.values()) == sum(dots.values())
    want = {p: v * T for p, v in
            costs_lm.forward_flops_per_token(cfg, S).items()}
    assert by["head"] == want["head"]
    assert by["router"] == want["router"]
    assert by["projections"] == want["projections"]
    # dense over E experts in the reference, the k a token meets in the costs
    assert by["experts"] * k == want["experts"] * E
    # the whole square in the reference, the lower triangle in the costs
    assert by["attention"] == 2 * want["attention"]
    assert costs_lm.train_flops_per_token(cfg, S) * T == 3 * sum(
        want.values())


def test_published_widths_give_the_issue_s_arithmetic():
    cfg = _cfg()
    parts = costs_lm.forward_flops_per_token(cfg, 4096)
    assert parts["experts"] == 8 * 3 * 2 * 2048 * 1024          # 100.7 M
    assert parts["head"] == 2 * 2048 * 50304                     # 206.0 M
    layer = sum(v for p, v in parts.items() if p != "head")
    assert round(layer / 1e6) == 151
    assert round(costs_lm.train_flops_per_token(cfg, 4096) / 1e9, 2) == 1.07


def test_least_times_and_which_bound_binds():
    cfg, peaks = _cfg(), costs.peaks_for("TPU v5 lite")
    rows = 8192 * cfg["num_experts_per_tok"]
    f = costs_lm.expert_product_flops(rows, 2048, 1024)
    b = costs_lm.expert_product_bytes(rows, 2048, 1024, 64)
    assert f == 2 * rows * 2048 * 1024
    assert b == 2 * (rows * 2048 + 64 * 2048 * 1024 + rows * 1024)
    # operations bind a grouped product at these shapes, bytes would not
    assert f / peaks["bf16_flops_per_s"] > b / peaks["hbm_bytes_per_s"]
    assert costs_lm.expert_layer_least_seconds(
        cfg, 8192, True, peaks) == 9 * f / peaks["bf16_flops_per_s"]
    assert costs_lm.expert_layer_least_seconds(cfg, 8192, False, peaks) \
        * 3 == costs_lm.expert_layer_least_seconds(cfg, 8192, True, peaks)
    # attention: 6 products over half the square in training, 2 in inference
    one = 2 * 4096 * 4096 * 128 // 2
    assert costs_lm.causal_attention_flops(2, 16, 4096, 128, True) \
        == 2 * 16 * 6 * one
    assert costs_lm.causal_attention_flops(2, 16, 4096, 128, False) \
        == 2 * 16 * 2 * one
    t = costs_lm.attention_least_seconds(cfg, 2, 4096, True, peaks)
    assert abs(t - 2 * 16 * 6 * one / 197e12) < 1e-12
    assert costs_lm.matmul_bytes(3, 5, 7) == 2 * (15 + 35 + 21)
    assert costs_lm.least_seconds(1e12, 819e9 * 2, peaks) == 2.0
