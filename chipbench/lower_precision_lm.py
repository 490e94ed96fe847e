"""Study: what `compare_lm` reads when the SYSTEM computes a language-model
configuration one precision below what it states, and whether that comes
out not `correct`. Not part of any cell; its readings are the second of
the two each limit of `compare_lm` is set from (PERF.md section 6).

    python -m chipbench.lower_precision_lm --seeds 11 12 [--variants all]

The configuration states bf16 AMP with float32 master weights, router
(matmul, softmax, top-k, both router losses), norm statistics, loss and
optimizer. A variant turns one of those float32 parts to bf16 in the
system itself, by the means the system has: AMP's lists (a black-list op
named in `custom_white_list` takes bf16 inputs and so computes and
writes bf16), AMP's `FLOAT32_SLOTS`, and the dtype `ops/lm_ops.py`'s
kernels compute their float32 parts in (`lm_ops.F32`, set around one op
type's kernel). `stated` changes nothing and must come out `correct`;
`all` is bf16 everywhere. The flash kernel's inner softmax stays float32
in every variant: it is inside the Pallas kernel.

The reference runs once a seed; every variant of the seed starts from the
same float32 weights. One JSON line a variant, and
`chiprun_out/lower_precision_lm.jsonl`.
"""

import argparse
import json
import os

import numpy as np

from chipbench import compare_lm, harness

CELL = "olmoe_1b_7b_train_packed4k"
BF16_INSIDE = {}      # op type -> dtype of its kernel's float32 parts
VARIANTS = {
    # name: (ops moved to AMP's white list, FLOAT32_SLOTS kept, kernels
    # whose float32 parts run in bf16)
    "stated": ((), True, ()),
    "router": ((), False, ("moe_ffn",)),
    "norms": ((), True, ("rms_norm",)),
    "loss": (("softmax_with_cross_entropy", "mean"), True, ()),
    "masters": (("adam",), True, ()),
    "all": (None, False, ("moe_ffn", "rms_norm")),
}


def _wrap_kernels():
    """The two kernels read `lm_ops.F32` while they trace: set it around
    each call, from BF16_INSIDE. Installed before any `<type>_grad` is
    derived, so the generic vjp differentiates the wrapped kernel."""
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    from paddle_tpu.ops import lm_ops

    for op_type in ("moe_ffn", "rms_norm"):
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

    white, keep_slots, inside = VARIANTS[name]
    slots = amp.FLOAT32_SLOTS
    amp.enable(cfg["amp"], custom_white_list=amp.BLACK_LIST
               if white is None else white)
    amp.FLOAT32_SLOTS = slots if keep_slots else {}
    BF16_INSIDE.update({t: jnp.bfloat16 for t in inside})
    try:
        return compare_lm.system_side(fluid, cfg, builder, place, seed,
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
    with open("chiprun_out/lower_precision_lm.jsonl", "a") as log:
        for seed in args.seeds:
            tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1,
                                          int(cfg["reference"]["rows"]))
            ref = w0 = None
            for name in args.variants:
                got = run_variant(name, fluid, cfg, builder, place, seed,
                                  tok, lab)
                if ref is None:
                    w0 = got["w0"]
                    ref = compare_lm.reference_side(cfg, builder, w0, tok,
                                                    lab)
                    w0 = {n: w0[n]
                          for n in builder.sampled_params(cfg).values()}
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                got["w0"] = w0
                report = compare_lm.judge(cfg, builder, got, ref)
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
