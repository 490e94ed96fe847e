"""Study: what `compare_lm_window_share` reads when the SYSTEM computes the
`laguna_xs_2` configuration one precision below what it states, and
whether that comes out not `correct`. Not part of any cell; its readings
are the second of the two each limit of `compare_lm_window_share` is set
from (PERF.md section 6, PR 32). The machinery is `lower_precision_lm`'s
(its `BF16_INSIDE`, AMP's lists and `FLOAT32_SLOTS`) and
`lower_precision_lm_share`'s kernel wrapper (it wraps `moe_ffn` and
`rms_norm` among its ops).

    python -m chipbench.lower_precision_lm_window_share --seeds 11 12

The configuration states bf16 AMP with float32 master weights, router
(matmul, sigmoid, top-k), norm statistics, loss and optimizer. A variant
turns one of those to bf16 in the system itself; `stated` changes nothing
and must come out `correct`; `all` is bf16 everywhere;
`band_off_by_one` is no precision but a planted fault, the system's window
one position too long, which the first-hand attention check must fail by
itself. One JSON line a variant, and
`chiprun_out/lower_precision_lm_window_share.jsonl`.
"""

import argparse
import json
import os

import numpy as np

from chipbench import compare_lm_window_share as compare
from chipbench import harness
from chipbench.lower_precision_lm import BF16_INSIDE
from chipbench.lower_precision_lm_share import _wrap_kernels

CELL = "laguna_xs_2_train_packed8k"
VARIANTS = {
    # name: (ops moved to AMP's white list (None: every black-list op),
    # op types whose FLOAT32_SLOTS are dropped, kernels whose float32
    # parts run in bf16)
    "stated": ((), (), ()),
    "router": ((), ("moe_ffn",), ("moe_ffn",)),
    "norms": ((), (), ("rms_norm",)),
    "masters": (("adam",), (), ()),
    "all": (None, ("moe_ffn",), ("moe_ffn", "rms_norm")),
    # not a precision: the SYSTEM's band one position too long (the attr
    # `window` of its window layers 513, the reference's 512), planted to
    # show that the first-hand attention check fails it on the chip
    "band_off_by_one": ((), (), ()),
}


def run_variant(name, fluid, cfg, builder, place, seed, tok, lab):
    import jax.numpy as jnp
    from paddle_tpu import amp

    white, no_slots, inside = VARIANTS[name]
    if name == "band_off_by_one":
        cfg = dict(cfg, sliding_window=cfg["sliding_window"] + 1)
    slots = amp.FLOAT32_SLOTS
    amp.enable(cfg["amp"], custom_white_list=amp.BLACK_LIST
               if white is None else white)
    amp.FLOAT32_SLOTS = {k: v for k, v in slots.items()
                         if k not in no_slots}
    BF16_INSIDE.update({t: jnp.bfloat16 for t in inside})
    try:
        return compare.system_side(fluid, cfg, builder, place, seed, tok,
                                   lab)
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
    with open("chiprun_out/lower_precision_lm_window_share.jsonl",
              "a") as log:
        for seed in args.seeds:
            tok, lab, _ = kind.token_rows(cfg, traffic, seed + 1,
                                          int(cfg["reference"]["rows"]))
            ref = w0 = None
            for name in args.variants:
                got = run_variant(name, fluid, cfg, builder, place, seed,
                                  tok, lab)
                inputs = [u for u, _ in got["attention"]]
                if ref is None:
                    w0 = got["w0"]
                    ref = compare.reference_side(cfg, builder, w0, tok, lab,
                                                 inputs)
                else:
                    # the first-hand check holds the branch, not its
                    # input: a variant's branches are set against the
                    # reference's on THAT system's inputs (a bf16 norm
                    # changes them)
                    ref = dict(
                        ref, attention=compare.reference_branches(
                            cfg, builder, w0, tok, inputs),
                        attention_band=compare.reference_band_neighbours(
                            cfg, builder, w0, tok, inputs))
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
