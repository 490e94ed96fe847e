"""Study: what `compare_lm_share` reads when the SYSTEM computes the
`xing4_0_29b_a4b` configuration one precision below what it states, and
whether that comes out not `correct`. Not part of any cell; its readings
are the second of the two each limit of `compare_lm_share` is set from
(PERF.md section 6). The machinery is `lower_precision_lm`'s (its
`BF16_INSIDE` and kernel wrapper, AMP's lists and `FLOAT32_SLOTS`).

    python -m chipbench.lower_precision_lm_share --seeds 11 12

The configuration states bf16 AMP with float32 master weights, residual
mixers (projections, sigmoids, Sinkhorn), router (matmul, sigmoid, top-k),
norm statistics, loss and optimizer. A variant turns one of those to bf16
in the system itself; `stated` changes nothing and must come out
`correct`; `all` is bf16 everywhere. One JSON line a variant, and
`chiprun_out/lower_precision_lm_share.jsonl`.
"""

import argparse
import json
import os

import numpy as np

from chipbench import compare_lm_share, harness
from chipbench.lower_precision_lm import BF16_INSIDE

CELL = "xing4_0_29b_a4b_train_packed4k"
MIXERS = ("mhc_mix", "mhc_update")
VARIANTS = {
    # name: (ops moved to AMP's white list (None: every black-list op),
    # op types whose FLOAT32_SLOTS are dropped, kernels whose float32
    # parts run in bf16)
    "stated": ((), (), ()),
    "mixers": ((), MIXERS, MIXERS),
    "router": ((), ("moe_ffn",), ("moe_ffn",)),
    "norms": ((), (), ("rms_norm",)),
    "masters": (("adam",), (), ()),
    "all": (None, MIXERS + ("moe_ffn",), MIXERS + ("moe_ffn", "rms_norm")),
}


def _wrap_kernels():
    """As `lower_precision_lm._wrap_kernels`, for this configuration's
    ops: each reads `lm_ops.F32` while it traces."""
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    from paddle_tpu.ops import lm_ops

    for op_type in MIXERS + ("moe_ffn", "rms_norm"):
        op_def = registry.get_op_def(op_type)

        def kernel(ctx, ins, attrs, fn=op_def.fn, op_type=op_type):
            lm_ops.F32 = BF16_INSIDE.get(op_type, jnp.float32)
            try:
                return fn(ctx, ins, attrs)
            finally:
                lm_ops.F32 = jnp.float32

        op_def.fn = kernel


def run_variant(name, fluid, cfg, builder, place, seed, tok, lab):
    import jax.numpy as jnp
    from paddle_tpu import amp

    white, no_slots, inside = VARIANTS[name]
    slots = amp.FLOAT32_SLOTS
    amp.enable(cfg["amp"], custom_white_list=amp.BLACK_LIST
               if white is None else white)
    amp.FLOAT32_SLOTS = {k: v for k, v in slots.items()
                         if k not in no_slots}
    BF16_INSIDE.update({t: jnp.bfloat16 for t in inside})
    try:
        return compare_lm_share.system_side(fluid, cfg, builder, place,
                                            seed, tok, lab)
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
    with open("chiprun_out/lower_precision_lm_share.jsonl", "a") as log:
        for seed in args.seeds:
            tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1,
                                          int(cfg["reference"]["rows"]))
            ref = w0 = None
            for name in args.variants:
                got = run_variant(name, fluid, cfg, builder, place, seed,
                                  tok, lab)
                if ref is None:
                    w0 = got["w0"]
                    ref = compare_lm_share.reference_side(cfg, builder, w0,
                                                          tok, lab)
                    w0 = {n: w0[n]
                          for n in builder.sampled_params(cfg).values()}
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                got["w0"] = w0
                report = compare_lm_share.judge(cfg, builder, got, ref)
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
