"""Study: what `compare_lm_ssd_share` reads when the SYSTEM computes the
`nemotron_3_nano_30b_a3b` configuration one precision below what it
states, and whether that comes out not `correct`. Not part of any cell;
its readings are the second of the two each limit of `compare_lm_ssd_share`
is set from (PERF.md section 6, PR 54). The machinery is
`lower_precision_lm`'s and `lower_precision_lm_short_conv_share`'s
(`BF16_INSIDE`, the kernel wrapper, AMP's lists and `FLOAT32_SLOTS`),
imported.

    python -m chipbench.lower_precision_lm_ssd_share --seeds 11 12

The configuration states bf16 AMP with float32 master weights, router, norm
statistics, the scan's steps, decays and CARRIED STATE (whose products take
float32 operands in three bf16 passes), loss and optimizer. A variant
lowers one of those in the system itself (`parallel/ssd.py` holds no
switch: its functions are wrapped from outside); `stated` changes nothing
and must come out `correct`:

    state_bf16       the scan's carried state (and its cotangent) rounded
                     to bfloat16 at every chunk
    decays_bf16      delta, a = delta A and every decay exp(.) rounded to
                     bfloat16
    state_one_pass   the products that read the state or its cotangent at
                     ONE bf16 pass where the configuration says three
    router_bf16      the router's product, sigmoid and top-k in bfloat16
    masters          AdamW's state and the master weights in bfloat16

One JSON line a variant, and
`chiprun_out/lower_precision_lm_ssd_share.jsonl`.
"""

import argparse
import contextlib
import json
import os
from unittest import mock

import numpy as np

from chipbench import compare_lm_ssd_share as compare
from chipbench import harness
from chipbench.lower_precision_lm import BF16_INSIDE
from chipbench.lower_precision_lm_delta_share import _bf16
from chipbench.lower_precision_lm_short_conv_share import _wrap_kernels

CELL = "nemotron_3_nano_30b_a3b_train_packed4k"
VARIANTS = {
    # name: (ops moved to AMP's white list, op types whose FLOAT32_SLOTS
    # are dropped, kernels whose float32 parts run in bf16)
    "stated": ((), (), ()),
    "state_bf16": ((), (), ()),
    "decays_bf16": ((), (), ()),
    "state_one_pass": ((), (), ()),
    "router_bf16": ((), ("moe_ffn",), ("moe_ffn",)),
    "masters": (("adam",), (), ()),
}


def _planted(name):
    """The context in which the system is built and run for `name`: the
    lowering's own functions wrapped from outside, module attribute by
    module attribute."""
    import jax
    from paddle_tpu.parallel import ssd

    stack = contextlib.ExitStack()
    if name == "state_bf16":
        real = ssd.carried
        stack.enter_context(mock.patch.object(
            ssd, "carried", lambda g, h, s: _bf16(real(g, _bf16(h), s))))
    elif name == "decays_bf16":
        steps, decay = ssd.steps, ssd.decay
        stack.enter_context(mock.patch.object(
            ssd, "steps", lambda *a: tuple(_bf16(v) for v in steps(*a))))
        stack.enter_context(mock.patch.object(
            ssd, "decay", lambda x: _bf16(decay(_bf16(x)))))
    elif name == "state_one_pass":
        stack.enter_context(mock.patch.object(
            ssd, "STATE_PRECISION", jax.lax.Precision.DEFAULT))
    return stack


def run_variant(name, fluid, cfg, builder, place, seed, tok, lab):
    import jax.numpy as jnp
    from paddle_tpu import amp

    white, no_slots, inside = VARIANTS[name]
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
    with open("chiprun_out/lower_precision_lm_ssd_share.jsonl", "a") as log:
        for seed in args.seeds:
            tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1,
                                          int(cfg["reference"]["rows"]))
            ref = w0 = None
            for name in args.variants:
                got = run_variant(name, fluid, cfg, builder, place, seed,
                                  tok, lab)
                inputs = {k: u for k, (u, _) in got["operators"].items()}
                op_inputs = {k: v[:3] for k, v in got["mamba_ops"].items()}
                if ref is None:
                    w0 = got["w0"]
                    ref = compare.reference_side(cfg, builder, w0, tok, lab,
                                                 inputs, op_inputs)
                else:
                    # the first-hand checks hold the branch and the ops,
                    # not their inputs: a variant's are set against the
                    # reference's on THAT system's inputs
                    branches, chosen = compare.reference_branches(
                        cfg, builder, w0, tok, inputs)
                    ref = dict(
                        ref, operators=branches, experts_chosen=chosen,
                        mamba_ops=compare.reference_mamba_ops(
                            cfg, builder, w0, tok, op_inputs))
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                report = compare.judge(cfg, builder, got, ref)
                line = json.dumps({"seed": seed, "variant": name,
                                   "ok": report["ok"],
                                   "failed": report["failed"],
                                   "compared": report["compared"]})
                print(line, flush=True)
                log.write(line + "\n")
                log.flush()
                del got


if __name__ == "__main__":
    main()
