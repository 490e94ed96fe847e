"""`paddle_tpu.models.olmoe` at a small size (2 layers, hidden 64, 4
heads, 8 experts top-2, vocabulary 256, 2 x 32 tokens) against the plain
float32 reference of `chipbench/reference/olmoe_1b_7b.py`, on seeded
weights read out of the scope: logits of the inference program, the
training loss, EVERY parameter's gradient and first AdamW update.

Tolerance: float32 against float32 on the CPU; the two differ in the
order of float32 sums only (grouped products against the dense masked
einsum, the op-by-op backward against one jax.grad), measured 2e-7 to
9e-7 of the largest element: 1e-5.

The first AdamW step is -lr * (g / (|g| + eps') + wd * W0) on the clipped
gradient, where the system's adam op (the reference framework's
formulation: eps beside sqrt(v) BEFORE the bias correction) has eps' =
eps / sqrt(1 - beta2) and PyTorch has eps; the reference is evaluated at
eps', as the configuration's `assumed` states. g / (|g| + eps') turns a
1e-9 difference in a 1e-8 gradient (they exist at this size) into 0.6%
of a step, so the optimizer path (clip, adam, decay) is judged on the
gradients the system itself produced, which the test before it holds to
the reference's. W1 - W0 is read back from float32 weights, so half an
ulp of a norm scale of 1.0 (6e-8) is 1.5e-4 of a 4e-4 step: 3e-4 of the
largest step. A missing decay term (8e-3 of a step) fails it, and so does
a missing clip (the global norm is 1.9 here; it moves the steps of the
smallest gradients, and the test checks that it would be seen).
"""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             num_experts=8, num_experts_per_tok=2, intermediate_size=32,
             vocab_size=256, sequence_length=32, num_hidden_layers=2)


@pytest.fixture(scope="module")
def small():
    """The system's and the reference's numbers on one seeded batch."""
    from chipbench.configs import olmoe_1b_7b as builder
    from chipbench.reference import olmoe_1b_7b as ref

    with open(os.path.join(REPO, "chipbench", "configs",
                           "olmoe_1b_7b.json")) as f:
        cfg = dict(json.load(f), **SMALL)
    built = builder.build(fluid, cfg, 5)
    rs = np.random.default_rng(0)
    feed = {"tokens": rs.integers(0, 256, (2, 32)).astype(np.int32),
            "labels": rs.integers(0, 256, (2, 32)).astype(np.int32)}
    names = list(ref.param_shapes(cfg))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        w0 = {n: np.asarray(scope.find_var(n)) for n in names}
        logits, = exe.run(built["test_prog"], feed=feed,
                          fetch_list=[built["logits"]])
        ids, load = built["routing"][0]
        got = exe.run(built["prog"], feed=feed,
                      fetch_list=[built["loss"], built["ce"], ids, load]
                      + [n + "@GRAD" for n in names])
        w1 = {n: np.asarray(scope.find_var(n)) for n in names}
    loss, (ce, ref_logits, routing, _), grads = ref.loss_and_grads(
        cfg, {k: jnp.asarray(v) for k, v in w0.items()},
        jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]))
    o = cfg["optimizer"]
    sys_grads = dict(zip(names, (jnp.asarray(g) for g in got[4:])))

    def update_of(cfg_):
        return ref.adamw_first_update(
            cfg_, w0, sys_grads,
            epsilon=o["epsilon"] / np.sqrt(1 - o["beta2"]))

    delta, norm = update_of(cfg)
    return dict(cfg=cfg, names=names, built=built, w0=w0, w1=w1,
                update_of=update_of,
                logits=np.asarray(logits), loss=float(got[0].ravel()[0]),
                ce=float(got[1].ravel()[0]), ids=np.asarray(got[2]),
                load=np.asarray(got[3]),
                grads=dict(zip(names, got[4:])),
                ref=dict(loss=float(loss), ce=float(ce), norm=float(norm),
                         logits=np.asarray(ref_logits), grads=grads,
                         delta=delta, top_e=np.asarray(routing[0][1])),
                shapes=ref.param_shapes(cfg))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


def test_parameters_are_the_reference_s(small):
    prog = small["built"]["prog"]
    params = {p.name: tuple(p.shape)
              for p in prog.global_block().all_parameters()}
    assert params == small["shapes"]
    assert len(params) == 3 + 12 * 2


def test_logits_and_loss(small):
    assert rel(small["logits"].reshape(small["ref"]["logits"].shape),
               small["ref"]["logits"]) <= 1e-5
    assert abs(small["loss"] - small["ref"]["loss"]) <= 1e-5 * small["loss"]
    assert abs(small["ce"] - small["ref"]["ce"]) <= 1e-5 * small["ce"]
    # both router terms are in the loss the program minimises
    assert small["loss"] - small["ce"] > 0.01


def test_routing(small):
    np.testing.assert_array_equal(np.sort(small["ids"], 1),
                                  np.sort(small["ref"]["top_e"], 1))
    assert small["load"].sum() == 2 * 32 * 2


def test_every_gradient(small):
    worst = {n: rel(small["grads"][n], small["ref"]["grads"][n])
             for n in small["names"]}
    assert max(worst.values()) <= 1e-5, worst


def test_every_first_adamw_update(small):
    assert small["ref"]["norm"] > 1.0          # the clip acts
    worst = {n: rel(small["w1"][n] - small["w0"][n],
                    small["ref"]["delta"][n]) for n in small["names"]}
    assert max(worst.values()) <= 3e-4, worst
    # the check can tell: the reference without the decay, or without the
    # clip, is further from the system than the tolerance
    for key, off in (("weight_decay", 0.0), ("clip_global_norm", 1e9)):
        cfg = dict(small["cfg"], optimizer=dict(small["cfg"]["optimizer"],
                                                **{key: off}))
        other, _ = small["update_of"](cfg)
        assert max(rel(small["w1"][n] - small["w0"][n], other[n])
                   for n in small["names"]) > 1e-3, key


def test_lowered_counts_by_place():
    """The training program holds one `causal_attention` and its grad op
    a layer: on a TPU place both count as lowered through the flash
    kernels, on any other place neither does; the inference clone has no
    backward to count. At the published widths the embedding's 412 MB
    gradient counts as written by the row-tile kernel (PR 38), on a TPU
    place only; SMALL's table is far under its threshold."""
    from types import SimpleNamespace

    from chipbench.configs import olmoe_1b_7b as builder
    from paddle_tpu.ops import lm_ops

    with open(os.path.join(REPO, "chipbench", "configs",
                           "olmoe_1b_7b.json")) as f:
        cfg = dict(json.load(f), **SMALL)
    built = builder.build(fluid, cfg, 11)
    tpu, cpu = SimpleNamespace(platform="tpu"), SimpleNamespace(
        platform="cpu")
    layers = cfg["num_hidden_layers"]
    # SMALL's rows fit one block: the forward visits it a layer, masked
    blocks = {"flash_fwd_visited_blocks": layers,
              "flash_fwd_masked_blocks": layers}
    assert lm_ops.lowered_counts(built["prog"], tpu) == {
        "moe_ffn_grouped": layers, "flash_attention": layers,
        "flash_attention_bwd": layers, **blocks}
    assert lm_ops.lowered_counts(built["prog"], cpu) == {
        "moe_ffn_grouped": layers}
    assert lm_ops.lowered_counts(built["test_prog"], tpu) == {
        "moe_ffn_grouped": layers, "flash_attention": layers, **blocks}
    # SMALL's widths are no multiples of 128: the products stay
    # `lax.ragged_dot` there; at the published widths the Pallas grouped
    # kernels take them, on a TPU place only
    with open(os.path.join(REPO, "chipbench", "configs",
                           "olmoe_1b_7b.json")) as f:
        full = builder.build(fluid, json.load(f), 11)
    assert lm_ops.lowered_counts(full["prog"], tpu) == {
        "moe_ffn_grouped": 1, "grouped_matmul_kernel": 1,
        "grouped_mlp_epilogues": 1, "flash_attention": 1,
        "flash_attention_bwd": 1, "lookup_table_grad_tiled": 1,
        # rows of 4096 at 1024 x 1024: the mask on the diagonal's 4 of 10
        "flash_fwd_visited_blocks": 10, "flash_fwd_masked_blocks": 4}
    assert lm_ops.lowered_counts(full["prog"], cpu) == {
        "moe_ffn_grouped": 1}


def test_scan_of_k_steps_runs_and_counts_its_lowerings():
    """`Executor.run(iters=K)` on stacked token feeds, under bf16 AMP: the
    losses are finite and fall on a repeated batch, every token is routed
    in the last step, and the step span and the registry carry
    `moe_ffn_grouped` (one per layer) and, off a TPU place, neither
    `flash_attention` nor `flash_attention_bwd`."""
    from chipbench.configs import olmoe_1b_7b as builder
    from paddle_tpu import amp, flags, trace

    with open(os.path.join(REPO, "chipbench", "configs",
                           "olmoe_1b_7b.json")) as f:
        cfg = dict(json.load(f), **SMALL)
    built = builder.build(fluid, cfg, 11)
    rs = np.random.default_rng(1)
    row = rs.integers(0, 256, (1, 2, 32)).astype(np.int32)
    feed = {"tokens": np.repeat(row, 3, 0),
            "labels": np.repeat(np.roll(row, -1, 2), 3, 0)}
    ids, load = built["routing"][1]
    from paddle_tpu import monitor

    with flags.flag_guard(trace=True, monitor=True), amp.auto_cast(), \
            fluid.scope_guard(fluid.Scope()):
        trace.reset()
        before = monitor.registry().counter("moe_ffn_grouped",
                                            cache="executor").value
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        losses, counts = exe.run(built["prog"], feed=feed,
                                 fetch_list=[built["loss"], load], iters=3)
        spans = trace.snapshot()[0]
        counted = monitor.registry().counter(
            "moe_ffn_grouped", cache="executor").value - before
    losses = np.asarray(losses).reshape(-1)
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert np.asarray(counts).reshape(-1, 8)[-1].sum() == 2 * 32 * 2
    steps = [s for s in spans
             if s["name"] == "executor.step" and s["attrs"].get("iters")]
    assert steps and steps[-1]["attrs"]["moe_ffn_grouped"] == 2
    assert "flash_attention" not in steps[-1]["attrs"]
    assert "flash_attention_bwd" not in steps[-1]["attrs"]
    assert counted == 2            # once, for the one program prepared
