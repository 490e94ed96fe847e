"""Study: what `compare_lm_sparse_attn_share` reads when the SYSTEM computes
the `keye_vl_2_0_30b_a3b` configuration with one thing planted, one
precision below what it states or one term of the layer left out or
changed, and whether that comes out not `correct`. Not part of any cell;
its readings are the second of the two each limit of
`compare_lm_sparse_attn_share` is set from (PERF.md section 6, PR 49).

    python -m chipbench.lower_precision_lm_sparse_attn_share --seeds 11

A variant plants one fault in the system itself, from outside (the
lowering holds no switch for it); `stated` changes nothing and must come
out `correct`:

    whole_triangle       the selection left out: every causal key chosen
    topk_1024            the indexer keeps 1024 keys a query, not 2048
    no_relu              I = sum_j w_j (q_I_j . k_I), the ReLU left out
    w_one                w = 1: the heads' scores summed unweighted
    previous_selection   a layer attends on the selection of the layer
                         before it (the first on its own)
    triangle_softmax     the softmax normalised over the whole triangle
                         and then masked (the chosen keys' weights do not
                         sum to 1)
    target_not_detached  the indexer's loss hands gradients to q and k
    indexer_reads_u      the indexer reads u with its gradient (no
                         stop_gradient in front of its projections)
    scores_bf16          I rounded to bfloat16 before the threshold
    no_qk_norm           the per-head RMSNorm of q and k left out (the
                         scales still multiply)
    masters              AdamW's state and the master weights in bfloat16

One JSON line a variant, and
`chiprun_out/lower_precision_lm_sparse_attn_share.jsonl`.
"""

import argparse
import contextlib
import json
import os
import types
from unittest import mock

import numpy as np

from chipbench import compare_lm_sparse_attn_share as compare
from chipbench import harness

CELL = "keye_vl_2_0_30b_a3b_train_packed8k"
# those behind the forward pass first: they share `stated`'s reference side
VARIANTS = ("stated", "masters", "target_not_detached", "indexer_reads_u",
            "scores_bf16", "whole_triangle", "topk_1024", "no_relu", "w_one",
            "previous_selection", "triangle_softmax", "no_qk_norm")


def _bf16(x):
    """x rounded to bfloat16's 8 bits and left float32 (`reduce_precision`:
    a convert there and back inside one fusion is elided on the chip)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _patched(*patches):
    stack = contextlib.ExitStack()
    for target, name, value in patches:
        stack.enter_context(mock.patch.object(target, name, value))
    return stack


def _planted(name):
    """The context in which the system is built and run for `name`: the
    lowering's own functions wrapped from outside, module attribute by
    module attribute."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.core import registry
    from paddle_tpu.layers import nn
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import flash, sparse_index

    if name == "whole_triangle":
        def select_rows(I, first, topk):
            n, S = I.shape
            causal = jnp.arange(S)[None, :] <= (first + jnp.arange(n))[:, None]
            return causal, jnp.min(jnp.where(causal, I, jnp.inf), axis=1)

        return _patched((sparse_index, "select_rows", select_rows))
    if name in ("no_relu", "w_one", "scores_bf16"):
        real = sparse_index.scores

        def scores(q_i, k_i, w):
            if name == "w_one":
                return real(q_i, k_i, jnp.ones_like(w))
            if name == "scores_bf16":
                return _bf16(real(q_i, k_i, w))
            s = jnp.einsum("qhd,kd->qhk", q_i, k_i,
                           preferred_element_type=jnp.float32)
            return jnp.sum(s * w.astype(jnp.float32)[:, :, None], axis=1)

        return _patched((sparse_index, "scores", scores))
    if name == "triangle_softmax":
        # o over the chosen keys times exp(lse_S - lse_T): each chosen
        # key's weight is its share of the whole TRIANGLE's softmax
        def reweighed(o, lse, lse_t):
            return (o.astype(jnp.float32) * jnp.exp(lse - lse_t)[
                ..., None]).astype(o.dtype), lse

        real_kernel, real_plain = (flash.flash_attention_fwd,
                                   lm_ops._plain_sparse_attention)

        def kernel(q, k, v, mask=None, **kw):
            return reweighed(*real_kernel(q, k, v, mask=mask, **kw),
                             real_kernel(q, k, v, **kw)[1])

        def plain(q, k, v, mask, scale=None):
            return reweighed(
                *real_plain(q, k, v, mask, scale),
                lm_ops._plain_causal_attention(q, k, v, scale)[1])

        return _patched((flash, "flash_attention_fwd", kernel),
                        (lm_ops, "_plain_sparse_attention", plain))
    if name == "indexer_reads_u":
        # the model calls `fluid.layers.detached`; the loss's layer calls
        # `nn.detached` by its module's own name and keeps it
        return _patched((fluid.layers, "detached", lambda x: x))
    if name == "target_not_detached":
        # the layer hands the op q and k themselves, and the op's grad op
        # declares a gradient for both (zeros: what the check holds is
        # which parameters the loss REACHES)
        op_def = registry._registry["indexer_loss"]
        grad_def = registry._registry["indexer_loss_grad"]
        real_maker, real_grad = op_def.grad_maker, grad_def.fn

        def maker(op, gout, gin):
            desc, = real_maker(op, gout, gin)
            for s in ("Q", "K"):
                desc["inputs"][s] = op.input(s)
                desc["outputs"][s + "@GRAD"] = gin.get(s, [""])
            return [desc]

        def grad(ctx, ins, attrs):
            outs = real_grad(ctx, ins, attrs)
            for s in ("Q", "K"):
                outs[s + "@GRAD"] = [jnp.zeros_like(ins[s][0])]
            return outs

        return _patched((nn, "detached", lambda x: x),
                        (op_def, "grad_maker", maker), (grad_def, "fn", grad))
    return contextlib.nullcontext()


def _mutating(builder, mutate):
    """The builder with `mutate(prog)` applied to the programs it builds."""
    def build(*args, **kw):
        built = builder.build(*args, **kw)
        for prog in (built["prog"], built["test_prog"]):
            mutate(prog)
            prog._mutation = getattr(prog, "_mutation", 0) + 1
        return built

    return types.SimpleNamespace(
        build=build, sampled_params=builder.sampled_params,
        first_hand_layers=builder.first_hand_layers,
        reference=builder.reference, P=builder.P)


def _previous_selection(prog):
    """Every consumer of a layer's mask reads the mask of the layer before
    it (the first layer its own)."""
    ops = prog.global_block().ops
    masks = [op.output("Mask")[0] for op in ops
             if op.type == "indexer_select"]
    before = dict(zip(masks[1:], masks[:-1]))
    for op in ops:
        if op.type != "indexer_select" and "Mask" in op.inputs:
            op.inputs["Mask"] = [before.get(n, n) for n in op.input("Mask")]


def _no_qk_norm(prog):
    """The per-head norms of q and k become Y = X * Scale."""
    for op in prog.global_block().ops:
        scope = str(op.attrs.get("op_namescope", "")).strip("/")
        if op.type == "rms_norm" and scope.endswith("attn/qk_norm"):
            op.type = "elementwise_mul"
            op.inputs = {"X": op.input("X"), "Y": op.input("Scale")}
            op.outputs = {"Out": op.output("Y")}
            op.attrs["axis"] = -1


def system_of(name, fluid, cfg, builder, place, seed, tok, lab):
    """`compare.system_side` of the system with `name` planted."""
    from paddle_tpu import amp

    if name == "topk_1024":
        cfg = dict(cfg, sa_config=dict(cfg["sa_config"],
                                       topk=cfg["sa_config"]["topk"] // 2))
    if name == "previous_selection":
        builder = _mutating(builder, _previous_selection)
    if name == "no_qk_norm":
        builder = _mutating(builder, _no_qk_norm)
    if cfg.get("amp"):
        amp.enable(cfg["amp"], custom_white_list=(
            ("adam",) if name == "masters" else None))
    try:
        with _planted(name):
            return compare.system_side(fluid, cfg, builder, place, seed,
                                       tok, lab)
    finally:
        amp.disable()


def _same_forward(got, other):
    """Whether two systems chose, and wrote into the first-hand layers, the
    same numbers: the reference side of one is then the other's."""
    return other is not None and all(
        np.array_equal(a, b) for key in ("masks", "ids")
        for a, b in zip(got[key], other[key])) \
        and all(np.array_equal(v, other["own"][i][k])
                for i, own in got["own"].items() for k, v in own.items())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=list(VARIANTS))
    ap.add_argument("--override", help="JSON, as harness.run_cell takes it "
                    "(a tiny size on the CPU)")
    args = ap.parse_args(argv)
    import paddle_tpu as fluid

    _, _, cfg, traffic, builder, kind = harness.Files().cell(CELL)
    if args.override:
        override = json.loads(args.override)
        cfg = dict(cfg, **override.get("config", {}))
        traffic = dict(traffic, **override.get("traffic", {}))
    place = fluid.TPUPlace(0)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/lower_precision_lm_sparse_attn_share.jsonl",
              "a") as log:
        for seed in args.seeds:
            tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1,
                                          int(cfg["reference"]["rows"]))
            w0 = before = ref = None
            for name in args.variants:
                got = system_of(name, fluid, cfg, builder, place, seed,
                                tok, lab)
                w0 = w0 or got["w0"]
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                # downstream of a choice the reference follows THAT
                # system's choice: it is formed again a variant, unless
                # the variant before chose and wrote the same (a plant
                # behind the forward pass). Outside the plant: the
                # comparison holds what the system wrote
                if not _same_forward(got, before):
                    ref = compare.reference_side(cfg, builder, got, tok,
                                                 lab)
                before = {"masks": got["masks"], "own": got["own"],
                          "ids": got["ids"]}
                report = compare.judge(cfg, builder, got, ref)
                line = json.dumps({"seed": seed, "variant": name,
                                   "ok": report["ok"],
                                   "failed": report["failed"],
                                   "compared": report["compared"],
                                   "report": report})
                print(json.dumps({"seed": seed, "variant": name,
                                  "ok": report["ok"],
                                  "failed": report["failed"],
                                  "compared": report["compared"]}),
                      flush=True)
                log.write(line + "\n")
                log.flush()
                del got


if __name__ == "__main__":
    main()
