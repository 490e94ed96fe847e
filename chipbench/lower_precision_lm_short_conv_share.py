"""Study: what `compare_lm_short_conv_share` reads when the SYSTEM computes
the `lfm2_8b_a1b` configuration one precision below what it states, and
whether that comes out not `correct`. Not part of any cell; its readings are
the second of the two each limit of `compare_lm_short_conv_share` is set
from (PERF.md section 6, PR 39). The machinery is `lower_precision_lm`'s
(its `BF16_INSIDE`, AMP's lists and `FLOAT32_SLOTS`); the kernel wrapper
here wraps `short_conv` and its hand-written grad among its ops.

    python -m chipbench.lower_precision_lm_short_conv_share --seeds 11 12

The configuration states bf16 AMP with float32 master weights, router
(matmul, sigmoid, top-k), norm statistics, the conv's gates and taps, loss
and optimizer. A variant turns one of those to bf16 in the system itself:
`conv` (the op's gates B * z and C * c and its tap sums in bf16, the taps
cast down: the plain form runs, the kernels are float32 inside by
construction), `norms`, `router` (the op's float32 parts in bf16 and its
`Router` and `Bias` slots cast down), `masters`, `all`; `stated` changes
nothing and must come out `correct`. One is no precision but a planted
fault: `taps_reversed`, the system's convolution with its taps in the
opposite order, which the first-hand conv check must fail by itself. One
JSON line a variant, and
`chiprun_out/lower_precision_lm_short_conv_share.jsonl`.
"""

import argparse
import contextlib
import json
import os
from unittest import mock

import numpy as np

from chipbench import compare_lm_short_conv_share as compare
from chipbench import harness
from chipbench.lower_precision_lm import BF16_INSIDE

CELL = "lfm2_8b_a1b_train_packed8k"
CONV_OPS = ("short_conv", "short_conv_grad")
VARIANTS = {
    # name: (ops moved to AMP's white list (None: every black-list op),
    # op types whose FLOAT32_SLOTS are dropped, kernels whose float32
    # parts run in bf16)
    "stated": ((), (), ()),
    "conv": ((), ("short_conv",), CONV_OPS),
    "norms": ((), (), ("rms_norm",)),
    "router": ((), ("moe_ffn",), ("moe_ffn",)),
    "masters": (("adam",), (), ()),
    "all": (None, ("moe_ffn", "short_conv"),
            ("moe_ffn", "rms_norm") + CONV_OPS),
    "taps_reversed": ((), (), ()),
}


def _wrap_kernels():
    """As `lower_precision_lm._wrap_kernels`, for this configuration's
    ops: each reads `lm_ops.F32` while it traces."""
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    from paddle_tpu.ops import lm_ops

    for op_type in ("moe_ffn", "rms_norm") + CONV_OPS:
        op_def = registry.get_op_def(op_type)

        def kernel(ctx, ins, attrs, fn=op_def.fn, op_type=op_type):
            lm_ops.F32 = BF16_INSIDE.get(op_type, jnp.float32)
            try:
                return fn(ctx, ins, attrs)
            finally:
                lm_ops.F32 = jnp.float32

        op_def.fn = kernel


def _taps_the_other_way_round():
    """The system's two conv lowerings handed the taps reversed: the
    planted fault."""
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import short_conv as kernels

    stack = contextlib.ExitStack()
    for mod, name in ((lm_ops, "short_conv"), (lm_ops, "short_conv_grad"),
                      (kernels, "short_conv_fwd"),
                      (kernels, "short_conv_bwd")):
        real = getattr(mod, name)

        def reversed_taps(x, w, *rest, real=real, grad=name.endswith(
                ("grad", "bwd"))):
            res = real(x, w[::-1], *rest)
            return (res[0], res[1][::-1]) if grad else res

        stack.enter_context(mock.patch.object(mod, name, reversed_taps))
    return stack


def run_variant(name, fluid, cfg, builder, place, seed, tok, lab):
    import jax.numpy as jnp
    from paddle_tpu import amp

    white, no_slots, inside = VARIANTS[name]
    planted = _taps_the_other_way_round() if name == "taps_reversed" \
        else contextlib.nullcontext()
    slots = amp.FLOAT32_SLOTS
    amp.enable(cfg["amp"], custom_white_list=amp.BLACK_LIST
               if white is None else white)
    amp.FLOAT32_SLOTS = {k: v for k, v in slots.items()
                         if k not in no_slots}
    BF16_INSIDE.update({t: jnp.bfloat16 for t in inside})
    try:
        with planted:
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
    with open("chiprun_out/lower_precision_lm_short_conv_share.jsonl",
              "a") as log:
        for seed in args.seeds:
            tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1,
                                          int(cfg["reference"]["rows"]))
            ref = w0 = None
            for name in args.variants:
                got = run_variant(name, fluid, cfg, builder, place, seed,
                                  tok, lab)
                inputs, conv_inputs = compare.own_inputs(got)
                if ref is None:
                    w0 = got["w0"]
                    ref = compare.reference_side(cfg, builder, w0, tok, lab,
                                                 inputs, conv_inputs)
                else:
                    # the first-hand check holds the branch, not its
                    # input: a variant's branches are set against the
                    # reference's on THAT system's inputs
                    ref = dict(
                        ref, operators=compare.reference_branches(
                            cfg, builder, w0, tok, inputs),
                        conv_neighbours=compare.reference_conv_neighbours(
                            cfg, builder, w0, tok, inputs),
                        conv_ops=compare.reference_conv_ops(
                            cfg, builder, w0, tok, conv_inputs))
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                report = compare.judge(cfg, builder, got, ref, tokens=tok)
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
