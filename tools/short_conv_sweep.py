"""`short_conv` alone, on the chip: the gated short convolution of the
`lfm2_8b_a1b` cell's shape (X [8192, 6144] bf16, 3 float32 taps), forward
and backward, as XLA lowers the plain form (`lm_ops.short_conv`,
`short_conv_grad`) and as the Pallas kernels of `parallel/short_conv.py`
run it; each beside its least time (`chipbench/costs_short_conv_share`:
the op's least bytes over the chip's bandwidth) and checked against the
plain form. 8 runs a dispatch (a dispatch costs the host as long as a
small kernel takes). PERF.md (PR 39) holds what this printed.

    chiprun -- python tools/short_conv_sweep.py
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RUNS = 8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3901)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--channels", type=int, default=2048)
    ap.add_argument("--taps", type=int, default=3)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import costs, costs_short_conv_share
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import short_conv as kernels

    S, C, L = args.tokens, args.channels, args.taps
    rs = np.random.default_rng(args.seed)
    x = jnp.asarray(rs.standard_normal((S, 3 * C)), jnp.bfloat16)
    w = jnp.asarray(rs.standard_normal((L, C)) * 0.5, jnp.float32)
    g = jnp.asarray(rs.standard_normal((S, C)), jnp.bfloat16)
    kind = jax.devices()[0].device_kind
    # off the chip (a rehearsal of the wiring) the v5e's peaks: no time
    # printed there is a device time
    peaks = costs.peaks_for(kind if jax.devices()[0].platform == "tpu"
                            else "TPU v5 lite")
    cfg = dict(rows_per_step=1, sequence_length=S, hidden_size=C,
               conv_L_cache=L)
    least = {
        "forward": costs_short_conv_share.short_conv_least_seconds(
            cfg, False, peaks)}
    least["backward"] = costs_short_conv_share.short_conv_least_seconds(
        cfg, True, peaks) - least["forward"]
    forms = {
        ("forward", "plain"): lambda x, w, g: lm_ops.short_conv(x, w, S),
        ("forward", "kernel"): lambda x, w, g: kernels.short_conv_fwd(
            x, w, S),
        ("backward", "plain"): lambda x, w, g: lm_ops.short_conv_grad(
            x, w, g, S),
        ("backward", "kernel"): lambda x, w, g: kernels.short_conv_bwd(
            x, w, g, S)}
    results = {}
    for (what, form), fn in forms.items():

        def many(x, w, g, fn=fn):
            # RUNS runs a dispatch in a loop that carries X and the
            # results; each run writes one number of its result into X
            # (in place), so no run, and no part of one, is hoisted out of
            # the loop, merged with another or cut down to that number
            def body(_, carry):
                x_c, _ = carry
                out = fn(x_c, w, g)
                tip = jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]
                return x_c.at[0, :1].set(tip.astype(x_c.dtype)), out

            return jax.lax.fori_loop(0, RUNS, body, (x, fn(x, w, g)))[1]

        run = jax.jit(many)
        jax.block_until_ready(run(x, w, g))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = run(x, w, g)
        jax.block_until_ready(out)
        # the loop's RUNS and the run that seeds its carry
        ms = (time.perf_counter() - t0) / (args.calls * (RUNS + 1)) * 1e3
        results[(what, form)] = jax.jit(fn)(x, w, g)
        print(json.dumps({
            "what": what, "form": form, "ms_a_run": ms,
            "least_ms": least[what] * 1e3,
            "roofline_share_pct": 100 * least[what] * 1e3 / ms}),
              flush=True)
    for what in ("forward", "backward"):
        a = jax.tree_util.tree_leaves(results[(what, "plain")])
        b = jax.tree_util.tree_leaves(results[(what, "kernel")])
        err = [float(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32))
                     .max() / jnp.abs(u.astype(jnp.float32)).max())
               for u, v in zip(a, b)]
        print(json.dumps({"what": what, "kernel_against_plain_max_rel": err}),
              flush=True)


if __name__ == "__main__":
    main()
