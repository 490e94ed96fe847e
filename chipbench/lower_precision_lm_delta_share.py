"""Study: what `compare_lm_delta_share` reads when the SYSTEM computes the
`qwen3_next_80b_a3b` configuration one precision below what it states, or
with one term of the layer left out, and whether that comes out not
`correct`. Not part of any cell; its readings are the second of the two
each limit of `compare_lm_delta_share` is set from (PERF.md section 6, PR
43). The machinery is `lower_precision_lm`'s and
`lower_precision_lm_short_conv_share`'s (`BF16_INSIDE`, the kernel
wrapper, AMP's lists and `FLOAT32_SLOTS`), imported.

    python -m chipbench.lower_precision_lm_delta_share --seeds 11 12

The configuration states bf16 AMP with float32 master weights, router,
norm statistics, g, beta, the decays and the CARRIED STATE of the delta
rule, loss and optimizer. A variant lowers or removes one of those in the
system itself; `stated` changes nothing and must come out `correct`:

    state_bf16       the carried state (and its cotangent) in bfloat16
    g_bf16           g and beta rounded to bfloat16 (the decays exp(G_i -
                     G_j) are then formed from the rounded g)
    no_decay         g = 0: the state never decays
    beta_one         beta = 1: every token overwrites its key's slot
    no_qk_norm       q and k not L2-normalised (q still times dk^-1/2)
    taps_reversed    the convolution's taps in the opposite order
    no_output_gate   attention's sigmoid(gate) left out (the gate reads 1)
    router_bf16      the router's product, softmax and top-k in bfloat16
    masters          AdamW's state and the master weights in bfloat16

One JSON line a variant, and
`chiprun_out/lower_precision_lm_delta_share.jsonl`.
"""

import argparse
import contextlib
import json
import os
import types
from unittest import mock

import numpy as np

from chipbench import compare_lm_delta_share as compare
from chipbench import harness
from chipbench.lower_precision_lm import BF16_INSIDE
from chipbench.lower_precision_lm_short_conv_share import _wrap_kernels

CELL = "qwen3_next_80b_a3b_train_packed8k"
VARIANTS = {
    # name: (ops moved to AMP's white list, op types whose FLOAT32_SLOTS
    # are dropped, kernels whose float32 parts run in bf16)
    "stated": ((), (), ()),
    "state_bf16": ((), (), ()),
    "g_bf16": ((), (), ()),
    "no_decay": ((), (), ()),
    "beta_one": ((), (), ()),
    "no_qk_norm": ((), (), ()),
    "taps_reversed": ((), (), ()),
    "no_output_gate": ((), (), ()),
    "router_bf16": ((), ("moe_ffn",), ("moe_ffn",)),
    "masters": (("adam",), (), ()),
}


def _delta_rule_with(**changes):
    """`parallel/delta_rule.py` with module attributes replaced."""
    from paddle_tpu.parallel import delta_rule

    stack = contextlib.ExitStack()
    for name, value in changes.items():
        stack.enter_context(mock.patch.object(delta_rule, name, value))
    return stack


def _bf16(x):
    """x rounded to bfloat16's 8 bits and left float32 (`reduce_precision`:
    a convert there and back inside one fusion is elided on the chip)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _planted(name):
    """The context in which the system is built and run for `name`: the
    lowering's own functions wrapped from outside, module attribute by
    module attribute (`parallel/delta_rule.py` holds no switch for it)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import delta_rule
    from paddle_tpu.parallel import short_conv as kernels

    if name == "state_bf16":
        # both scans carry their state in the dtype they are handed it in
        def carried_in_bf16(real):
            return lambda *a: real(*a[:-1], a[-1].astype(jnp.bfloat16))

        return _delta_rule_with(
            _states=carried_in_bf16(delta_rule._states),
            _states_transposed=carried_in_bf16(
                delta_rule._states_transposed))
    if name in ("g_bf16", "no_decay", "beta_one"):
        real = delta_rule.gates

        def gates(ba, a_log, dt_bias):
            g, beta = real(ba, a_log, dt_bias)
            if name == "g_bf16":
                return _bf16(g), _bf16(beta)
            # zero times the real value: the inputs keep a (zero) gradient
            return (g * 0.0, beta) if name == "no_decay" \
                else (g, beta * 0.0 + 1.0)

        return _delta_rule_with(gates=gates)
    if name == "no_qk_norm":
        return _delta_rule_with(l2_normalized=lambda x, eps, scale=1.0: (
            x.astype(jnp.float32) * scale).astype(x.dtype))
    if name == "taps_reversed":
        # the plain form AND the kernels a TPU place takes
        stack = contextlib.ExitStack()
        for mod, fn in ((lm_ops, "silu_conv"), (lm_ops, "silu_conv_grad"),
                        (kernels, "silu_conv_fwd"),
                        (kernels, "silu_conv_bwd")):
            real = getattr(mod, fn)

            def reversed_taps(x, w, *rest, real=real,
                              grad=fn.endswith(("grad", "bwd"))):
                res = real(x, w[::-1], *rest)
                return (res[0], res[1][::-1]) if grad else res

            stack.enter_context(mock.patch.object(mod, fn, reversed_taps))
        return stack
    return contextlib.nullcontext()


def _without_the_output_gate(builder):
    """The builder with attention's `sigmoid(gate)` replaced by 1 in the
    programs it builds (the op under `attn/gate` becomes 0 x + 1)."""
    def build(*args, **kw):
        built = builder.build(*args, **kw)
        for prog in (built["prog"], built["test_prog"]):
            for op in prog.global_block().ops:
                scope = str(op.attrs.get("op_namescope", "")).strip("/")
                if op.type == "sigmoid" and scope == "attn/gate":
                    op.type = "scale"
                    op.attrs.update(scale=0.0, bias=1.0)
            prog._mutation = getattr(prog, "_mutation", 0) + 1
        return built

    return types.SimpleNamespace(
        build=build, sampled_params=builder.sampled_params,
        first_hand_layers=builder.first_hand_layers,
        reference=builder.reference)


def run_variant(name, fluid, cfg, builder, place, seed, tok, lab):
    import jax.numpy as jnp
    from paddle_tpu import amp

    white, no_slots, inside = VARIANTS[name]
    if name == "no_output_gate":
        builder = _without_the_output_gate(builder)
    slots = amp.FLOAT32_SLOTS
    amp.enable(cfg["amp"], custom_white_list=white)
    amp.FLOAT32_SLOTS = {k: v for k, v in slots.items()
                         if k not in no_slots}
    BF16_INSIDE.update({t: jnp.bfloat16 for t in inside})
    try:
        with _planted(name):
            return compare.system_side(fluid, cfg, builder, place, seed,
                                       tok, lab)
    finally:
        BF16_INSIDE.clear()
        amp.FLOAT32_SLOTS = slots
        amp.disable()


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
    _wrap_kernels()
    place = fluid.TPUPlace(0)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/lower_precision_lm_delta_share.jsonl",
              "a") as log:
        for seed in args.seeds:
            tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1,
                                          int(cfg["reference"]["rows"]))
            ref = w0 = None
            for name in args.variants:
                got = run_variant(name, fluid, cfg, builder, place, seed,
                                  tok, lab)
                inputs, op_inputs = compare.own_inputs(got)
                w0 = w0 or got["w0"]
                if ref is None or not all(
                        np.array_equal(a, b)
                        for a, b in zip(got["ids"], ref["sent"])):
                    # the reference goes where THAT system's experts went
                    # (PR 56): its pass is made again a variant, unless
                    # the variant before sent every token the same way
                    ref = compare.reference_side(
                        cfg, builder, w0, tok, lab, inputs, op_inputs,
                        sent=got["ids"])
                else:
                    # the first-hand checks hold the branch and the ops,
                    # not their inputs: a variant's are set against the
                    # reference's on THAT system's inputs
                    ref = dict(
                        ref, operators=compare.reference_branches(
                            cfg, builder, w0, tok, inputs),
                        delta_ops=compare.reference_delta_ops(
                            cfg, builder, w0, tok, op_inputs))
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                report = compare.judge(cfg, builder, got, ref)
                line = json.dumps({"seed": seed, "variant": name,
                                   "ok": report["ok"],
                                   "failed": report["failed"],
                                   "report": report})
                print(line, flush=True)
                log.write(line + "\n")
                log.flush()
                del got


if __name__ == "__main__":
    main()
