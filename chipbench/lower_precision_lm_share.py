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

    python -m chipbench.lower_precision_lm_share --seeds 11 12 13 \
        --reference-faults

reads instead FAULTS OF THE MIXERS' BACKWARD (`REFERENCE_FAULTS`), planted
in the reference put in the program's place: the system only draws the
seed's weights, the reference's gradients with the fault are held against
its own without, on the cell's row (PR 48: what the pooled `phi_res` /
`alpha` limits of `compare_lm_share` are held down by; a variant of the
system costs a compile of the step, 5.7 minutes, a fault here 1).
"""

import argparse
import json
import os
from unittest import mock

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


# WHAT EACH FAULT DOES TO `reference.mixers` (x -> HPre, HPost, HRes). The
# values are the sound ones; one term of the BACKWARD is left out or turned,
# each a term the program's hand-written `_mhc_mix_bwd` / `_mhc_update_bwd`
# spells out: the coefficients' path back to the state (`through` and the
# rms's share of d x), d HRes with its two stream axes exchanged, d HPre,
# d HPost.
REFERENCE_FAULTS = ("coefficient_path_dropped", "res_grad_transposed",
                    "pre_grad_dropped", "post_grad_dropped")


def faulty_mixers(sound, fault):
    import jax
    import jax.numpy as jnp

    keep = jax.lax.stop_gradient

    def mixers(x, w, m, cfg):
        if fault == "coefficient_path_dropped":
            return sound(keep(x), w, m, cfg)
        pre, post, res = sound(x, w, m, cfg)
        if fault == "pre_grad_dropped":
            return keep(pre), post, res
        if fault == "post_grad_dropped":
            return pre, keep(post), res
        assert fault == "res_grad_transposed", fault
        turned = jnp.swapaxes(res, -1, -2)
        return pre, post, keep(res - turned) + turned

    return mixers


def drawn_weights(fluid, cfg, builder, place, seed):
    """{name: float32 array} as the system's startup program draws them
    from the seed; its scope is gone when this returns."""
    import gc

    from paddle_tpu import amp

    amp.enable(cfg["amp"], custom_white_list=())
    try:
        built = builder.build(fluid, cfg, seed, for_compare=True)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(place).run(built["startup"])
            w0 = {p.name: np.asarray(scope.find_var(p.name), np.float32)
                  for p in built["prog"].global_block().all_parameters()}
    finally:
        amp.disable()
    del scope, built
    gc.collect()
    return w0


def reference_fault_reports(cfg, builder, w0, tok, lab, faults):
    """[(fault, report)]: the numbers `compare_lm_share.gradients_held`
    reads (the sampled parameters' cosine and norm ratio, the expert
    matrices whole; the mixers' pooled), the reference WITH the fault in
    the program's place against the reference without."""
    import jax.numpy as jnp

    ref, picks = builder.reference, builder.sampled_params(cfg)
    pooled = tuple(compare_lm_share.POOLED.values())
    wj = {k: jnp.asarray(v) for k, v in w0.items()}
    t, l = jnp.asarray(tok), jnp.asarray(lab)

    def gradients(fault):
        mixers = ref.mixers if fault is None else faulty_mixers(ref.mixers,
                                                                fault)
        with mock.patch.object(ref, "mixers", mixers):
            loss, _, grads = ref.loss_and_grads(cfg, wj, t, l)
        gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
        return float(loss), gnorm, {
            n: np.asarray(g) for n, g in grads.items()
            if n.endswith(pooled) or n in picks.values()}

    loss, gnorm, sound = gradients(None)
    found = []
    for fault in faults:
        loss_f, gnorm_f, got = gradients(fault)
        assert abs(loss_f - loss) <= 1e-6 * abs(loss), (fault, loss_f, loss)
        report = {
            "config": cfg["name"], "planted_in": "reference",
            "global_grad_norm_err": abs(gnorm_f - gnorm) / gnorm,
            "by_param": {key: dict(zip(
                ("grad_cos", "grad_norm_ratio"),
                compare_lm_share._cos_ratio(got[n], sound[n])))
                for key, n in picks.items()},
            **{kind + "_pooled": v
               for kind, v in compare_lm_share.pooled_gradients(
                   {"clipped_pooled": {n: g for n, g in got.items()
                                       if n.endswith(pooled)},
                    "scale": 1.0}, sound).items()}}
        found.append((fault, report))
    return found


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
    ap.add_argument("--reference-faults", nargs="*", default=None,
                    choices=REFERENCE_FAULTS, metavar="FAULT",
                    help="read these faults of the mixers' backward, "
                    "planted in the reference (none named: all)")
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
            if args.reference_faults is not None:
                for name, report in reference_fault_reports(
                        cfg, builder, drawn_weights(fluid, cfg, builder,
                                                    place, seed), tok, lab,
                        args.reference_faults or REFERENCE_FAULTS):
                    held = compare_lm_share.gradients_held(report)
                    line = json.dumps({
                        "seed": seed, "variant": name, "ok": held,
                        "failed": [] if held else ["gradients"],
                        "report": report})
                    print(line, flush=True)
                    log.write(line + "\n")
                    log.flush()
                continue
            for name in args.variants:
                got = run_variant(name, fluid, cfg, builder, place, seed,
                                  tok, lab)
                w0 = w0 or got["w0"]
                assert all(np.array_equal(got["w0"][n], w0[n]) for n in w0)
                if ref is None or not all(
                        np.array_equal(a, b)
                        for a, b in zip(got["ids"], ref["sent"])):
                    # the reference goes where THAT system's experts went
                    # (PR 56): formed again a variant, unless the variant
                    # before sent every token the same way
                    ref = compare_lm_share.reference_side(
                        cfg, builder, w0, tok, lab, sent=got["ids"])
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
