"""Study: what `compare_lm_early_route_share` reads when the SYSTEM
computes the `smallthinker_21b_a3b` configuration one precision below what
it states, and whether that comes out not `correct`. Not part of any cell;
its readings are the second of the two each limit of
`compare_lm_early_route_share` is set from (PERF.md section 6, PR 36). The
machinery is `lower_precision_lm`'s (its `BF16_INSIDE`, AMP's lists and
`FLOAT32_SLOTS`) and `lower_precision_lm_share`'s kernel wrapper (it wraps
`moe_ffn` and `rms_norm` among its ops).

    python -m chipbench.lower_precision_lm_early_route_share --seeds 11 12

The configuration states bf16 AMP with float32 master weights, router
(its input's rows as they arrive, matmul, softmax, top-k), norm
statistics, loss and optimizer. A variant turns one of those to bf16 in
the system itself: `router` (the op's float32 parts in bf16 and its
`RouterInput`, `Router` and `Bias` slots cast down: layer 0 then scores
bf16 embedding rows), `norms`, `masters`, `all`; `stated` changes nothing
and must come out `correct`. Two are no precision but planted faults:
`router_after_attention`, the SYSTEM's router reading the normed state
after attention (what the experts read: `moe_ffn` built without
`router_input`), which the layer-0 check must fail by itself; and
`band_off_by_one`, the system's window one position too long. One JSON
line a variant, and `chiprun_out/lower_precision_lm_early_route_share.jsonl`.
"""

import argparse
import json
import os
from unittest import mock

import numpy as np

from chipbench import compare_lm_early_route_share as compare
from chipbench import harness
from chipbench.lower_precision_lm import BF16_INSIDE
from chipbench.lower_precision_lm_share import _wrap_kernels

CELL = "smallthinker_21b_a3b_train_packed8k"
VARIANTS = {
    # name: (ops moved to AMP's white list (None: every black-list op),
    # op types whose FLOAT32_SLOTS are dropped, kernels whose float32
    # parts run in bf16)
    "stated": ((), (), ()),
    "router": ((), ("moe_ffn",), ("moe_ffn",)),
    "norms": ((), (), ("rms_norm",)),
    "masters": (("adam",), (), ()),
    "all": (None, ("moe_ffn",), ("moe_ffn", "rms_norm")),
    "router_after_attention": ((), (), ()),
    "band_off_by_one": ((), (), ()),
}


def _router_reads_what_the_experts_read():
    """`layers.moe_ffn` deaf to `router_input`: the planted fault."""
    import paddle_tpu as fluid

    real = fluid.layers.moe_ffn
    return mock.patch.object(
        fluid.layers, "moe_ffn",
        lambda *a, router_input=None, **kw: real(*a, **kw))


def run_variant(name, fluid, cfg, builder, place, seed, tok, lab):
    import contextlib

    import jax.numpy as jnp
    from paddle_tpu import amp

    white, no_slots, inside = VARIANTS[name]
    planted = contextlib.nullcontext()
    if name == "band_off_by_one":
        cfg = dict(cfg, sliding_window_size=cfg["sliding_window_size"] + 1)
    elif name == "router_after_attention":
        planted = _router_reads_what_the_experts_read()
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
    with open("chiprun_out/lower_precision_lm_early_route_share.jsonl",
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
                    ref = compare.reference_side(
                        cfg, builder, w0, tok, lab, inputs, got["ids"],
                        got["ids_eval"])
                else:
                    # the first-hand check holds the branch, not its
                    # input: a variant's branches are set against the
                    # reference's on THAT system's inputs
                    ref = dict(
                        ref, attention=compare.reference_branches(
                            cfg, builder, w0, tok, inputs),
                        attention_band=compare.reference_band_neighbours(
                            cfg, builder, w0, tok, inputs))
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                # the logits are held against the reference sent where
                # THIS variant's inference program went (PR 56)
                ref = dict(ref, logits_sent=compare.reference_logits_sent(
                    cfg, builder, w0, tok, got["ids_eval"]))
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
