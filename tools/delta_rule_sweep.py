"""`gated_delta_rule` and the silu convolution alone, on the chip, at the
`qwen3_next_80b_a3b` cell's shape (QKV [8192, 8192] bf16, 16 key heads
serving 32 value heads of 128, chunks of 64; X [8192, 8192] bf16, 4 float32
taps): forward and backward of the plain chunked form as
`parallel/delta_rule.py` runs it, and with its knobs turned (the heads in 1,
2 or 4 groups; the triangular inverse's products at HIGHEST or HIGH; chunks
of 128), and THE KERNEL PATH (`kernels_fwd` / `kernels_bwd`: the in-chunk
work in the two Pallas kernels of `parallel/delta_parts.py`; whole, with
its knobs turned: the chunks a grid step works side by side, chunks of
256, the backward forming the triangular inverse again; the two kernels
alone; what the path leaves to XLA piece by piece), each beside the
least time of the WORK (`chipbench/costs_delta_share`: bytes and the
chunked form's operations at the stated chunk of 64, whatever the variant).
4 runs a dispatch. PERF.md (PR 43, PR 44) holds what this printed.

    chiprun -- python tools/delta_rule_sweep.py
    python tools/delta_rule_sweep.py --tiny     # the wiring, on the CPU
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RUNS = 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=4301)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--variants", nargs="+", default=None)
    ap.add_argument("--chunks-a-step", nargs="+", type=int,
                    default=[1, 2, 4, 8])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from chipbench import costs, costs_delta_share
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import delta_parts
    from paddle_tpu.parallel import delta_rule as dr

    # (tiny: heads of 128 and chunks of 64, so that twice the chunk is a
    # shape the kernels take, interpreted)
    S, hk, hv, d, chunk, L = (256, 2, 4, 128, 64, 4) if args.tiny else \
        (8192, 16, 32, 128, 64, 4)
    conv = 2 * hk * d + hv * d
    rs = np.random.default_rng(args.seed)
    qkv = jnp.asarray(rs.standard_normal((S, conv)), jnp.bfloat16)
    ba = jnp.asarray(rs.standard_normal((S, 2 * hv)), jnp.bfloat16)
    a_log = jnp.asarray(np.log(rs.uniform(1e-3, 16, hv)), jnp.float32)
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(0.1), hv))
    dt_bias = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    d_out = jnp.asarray(rs.standard_normal((S, hv * d)), jnp.bfloat16)
    taps = jnp.asarray(rs.standard_normal((L, conv)) * 0.5, jnp.float32)
    kind = jax.devices()[0].device_kind
    peaks = costs.peaks_for(kind if jax.devices()[0].platform == "tpu"
                            else "TPU v5 lite")
    cfg = dict(rows_per_step=1, sequence_length=S, delta_chunk=chunk,
               linear_num_key_heads=hk, linear_num_value_heads=hv,
               linear_key_head_dim=d, linear_value_head_dim=d,
               linear_conv_kernel_dim=L, num_hidden_layers=1,
               full_attention_interval=2)
    c = costs_delta_share
    least = {"delta_forward": c.delta_rule_least_seconds(cfg, False, peaks)}
    least["delta_backward"] = c.delta_rule_least_seconds(cfg, True, peaks) \
        - least["delta_forward"]
    least["conv_forward"] = c.short_conv_least_seconds_of(cfg, False, peaks)
    least["conv_backward"] = c.short_conv_least_seconds_of(
        cfg, True, peaks) - least["conv_forward"]

    def shape(chunk_=chunk):
        return dict(seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=chunk_,
                    eps=1e-6)

    # name: (module knobs, chunk[, the kernel path])
    variants = {
        "as_run": ({}, chunk),
        "inverse_high": ({"INVERSE_PRECISION": lax.Precision.HIGH}, chunk),
        "inverse_default": ({"INVERSE_PRECISION": None}, chunk),
        "groups_2": ({"HEAD_GROUPS": 2}, chunk),
        "groups_1": ({"HEAD_GROUPS": 1}, chunk),
        "chunk_128": ({}, 2 * chunk),
        "chunk_128_high": ({"INVERSE_PRECISION": lax.Precision.HIGH},
                           2 * chunk),
        "chunk_128_high_groups_8": (
            {"INVERSE_PRECISION": lax.Precision.HIGH, "HEAD_GROUPS": 8},
            2 * chunk),
        "chunk_128_high_groups_16": (
            {"INVERSE_PRECISION": lax.Precision.HIGH, "HEAD_GROUPS": 16},
            2 * chunk),
        "chunk_128_groups_8": ({"HEAD_GROUPS": 8}, 2 * chunk),
        "high_groups_8": (
            {"INVERSE_PRECISION": lax.Precision.HIGH, "HEAD_GROUPS": 8},
            chunk),
        "chunk_256_high_groups_8": (
            {"INVERSE_PRECISION": lax.Precision.HIGH, "HEAD_GROUPS": 8},
            4 * chunk),
        "kernels": ({}, 2 * chunk, True),
        "kernels_form_inverse_again": ({"KEEPS_INVERSE": False}, 2 * chunk,
                                       True),
        "kernels_1_chunk_a_step": ({"CHUNKS_A_STEP": 1}, 2 * chunk, True),
        "kernels_2_chunks_a_step": ({"CHUNKS_A_STEP": 2}, 2 * chunk, True),
        "kernels_8_chunks_a_step": ({"CHUNKS_A_STEP": 8}, 2 * chunk, True),
        "kernels_chunk_256": ({}, 4 * chunk, True),
        "kernels_inverse_highest": (
            {"INVERSE_PRECISION": lax.Precision.HIGHEST}, 2 * chunk, True),
    }
    on_kernels = jax.devices()[0].platform == "tpu" or args.tiny

    def timed(fn, *xs):
        def many(first, *rest):
            def body(_, carry):
                x_c, _ = carry
                out = fn(x_c, *rest)
                tip = jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]
                return x_c.at[0, :1].set(tip.astype(x_c.dtype)), out

            return lax.fori_loop(0, RUNS, body, (first, fn(first, *rest)))[1]

        run = jax.jit(many)
        jax.block_until_ready(run(*xs))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = run(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (args.calls * (RUNS + 1)) * 1e3

    base = None
    for name, (knobs, chunk_, *kernels) in variants.items():
        if args.variants and name not in args.variants:
            continue
        if kernels and not on_kernels:
            continue
        path = (dr.kernels_fwd, dr.kernels_bwd) if kernels else \
            (dr.delta_rule_fwd, dr.delta_rule_bwd)
        # a knob is `parallel/delta_rule.py`'s, or the kernels' module's
        home = {k: dr if hasattr(dr, k) else delta_parts for k in knobs}
        was = {k: getattr(home[k], k) for k in knobs}
        for k, v in knobs.items():
            setattr(home[k], k, v)
        try:
            sh = shape(chunk_)
            fwd = lambda q, *r: path[0](q, *r, **sh)  # noqa: E731
            out, starts, last = jax.jit(fwd)(qkv, ba, a_log, dt_bias)
            bwd = lambda q, *r: path[1](q, *r, **sh)  # noqa: E731
            ms_f = timed(fwd, qkv, ba, a_log, dt_bias)
            ms_b = timed(bwd, qkv, ba, a_log, dt_bias, starts, d_out)
        finally:
            for k, v in was.items():
                setattr(home[k], k, v)
        line = {"op": "gated_delta_rule", "variant": name,
                "forward_ms": ms_f, "backward_ms": ms_b,
                "least_forward_ms": least["delta_forward"] * 1e3,
                "least_backward_ms": least["delta_backward"] * 1e3,
                "roofline_share_pct": 100 * 1e3 * (
                    least["delta_forward"] + least["delta_backward"])
                / (ms_f + ms_b)}
        o32 = np.asarray(out, np.float32)
        if base is None:
            base = o32
        else:
            line["out_against_as_run_rms"] = float(
                np.sqrt(np.mean((o32 - base) ** 2)) / np.sqrt(
                    np.mean(base ** 2)))
        print(json.dumps(line), flush=True)
    if not args.variants or "pieces" in args.variants:
        # the forward's pieces, the heads in groups as the op works them
        groups = dr._groups(hk)
        part = dr._group_shape(shape(), groups)

        def parts(q, *r):
            return lax.map(lambda a: dr._parts(*dr._prepared(*a, **part)),
                           dr._grouped_inputs(q, *r, groups, **shape()))

        made = jax.jit(parts)(qkv, ba, a_log, dt_bias)

        def states(n_mat, b_mat, g_end):
            return lax.map(lambda a: dr._states(
                *a, jnp.zeros(a[1].shape[1:], jnp.float32)),
                (n_mat, b_mat, g_end))

        def vjp_of_parts(q, *r):
            out, vjp = jax.vjp(parts, q, *r)
            return vjp(out)

        print(json.dumps({
            "op": "gated_delta_rule", "variant": "pieces",
            "prepared_and_parts_forward_ms": timed(parts, qkv, ba, a_log,
                                                   dt_bias),
            "states_scan_forward_ms": timed(states, *made[2:]),
            "parts_forward_and_transpose_ms": timed(
                vjp_of_parts, qkv, ba, a_log, dt_bias)}), flush=True)
    if on_kernels and (not args.variants
                       or "kernel_pieces" in args.variants):
        # the two kernels alone, all heads at once, chunks of 128, by the
        # chunks a grid step works side by side
        C = 2 * chunk
        dims = dict(rows=1, seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=C,
                    eps=1e-6)
        g, beta = dr.gates(ba, a_log, dt_bias)

        def kernel_fwd(outputs):
            return lambda x, *r: tuple(delta_parts.delta_parts_fwd(
                x, *r, **dims, outputs=outputs).values())

        whole = delta_parts.OUTPUTS[:5]
        made = jax.jit(lambda *a: delta_parts.delta_parts_fwd(
            *a, **dims, outputs=whole + ("t",)))(qkv, g, beta)
        starts, left, t = made["b_mat"], made["b_mat"] * 0.5, made["t"]
        again = ("r_mat", "n_mat", "g_end")
        was = delta_parts.CHUNKS_A_STEP
        for a_step in args.chunks_a_step if not args.tiny else (1, 2):
            delta_parts.CHUNKS_A_STEP = a_step
            print(json.dumps({
                "op": "gated_delta_rule", "variant": "kernel_pieces",
                "chunks_a_step": a_step,
                "delta_parts_fwd_ms": timed(kernel_fwd(whole), qkv, g, beta),
                "delta_parts_fwd_in_the_backward_ms": timed(
                    kernel_fwd(again), qkv, g, beta, d_out),
                "delta_parts_fwd_in_the_backward_keeping_t_ms": timed(
                    kernel_fwd(again + ("t",)), qkv, g, beta, d_out),
                "delta_parts_bwd_ms": timed(
                    lambda x, *r: delta_parts.delta_parts_bwd(
                        x, *r, d_out, starts, left, **dims), qkv, g, beta),
                "delta_parts_bwd_reading_t_ms": timed(
                    lambda x, *r: delta_parts.delta_parts_bwd(
                        x, *r, d_out, starts, left, t, **dims), qkv, g,
                    beta),
            }), flush=True)
        delta_parts.CHUNKS_A_STEP = was
    if on_kernels and (not args.variants
                       or "kernel_path_pieces" in args.variants):
        # what the kernel path leaves to XLA, piece by piece, all heads at
        # once at chunks of 128
        C = 2 * chunk
        dims = dict(rows=1, seq_len=S, hk=hk, hv=hv, dk=d, dv=d, chunk=C,
                    eps=1e-6)
        low = qkv.dtype
        g, beta = jax.jit(dr.gates)(ba, a_log, dt_bias)
        made = jax.jit(lambda *a: delta_parts.delta_parts_fwd(
            *a, d_out, **dims, outputs=delta_parts.OUTPUTS))(qkv, g, beta)
        zeros = jnp.zeros(made["b_mat"].shape[1:], jnp.float32)
        starts, _ = jax.jit(dr._states)(made["n_mat"], made["b_mat"],
                                        made["g_end"], zeros)

        def outputs(q_p, starts, o0):
            return dr._tokens_first(dr._dot(q_p, starts, low) + o0, 1,
                                    S).astype(low)

        def gates_transposed(ba_, a_, d_, d_g, d_beta):
            return jax.vjp(dr.gates, ba_, a_, d_)[1]((d_g, d_beta))

        third = hk * d
        print(json.dumps({
            "op": "gated_delta_rule", "variant": "kernel_path_pieces",
            "gates_ms": timed(dr.gates, ba, a_log, dt_bias),
            "states_scan_ms": timed(
                lambda n, b, e: dr._states(n, b, e, zeros), made["n_mat"],
                made["b_mat"], made["g_end"]),
            "output_product_and_tokens_first_ms": timed(
                outputs, made["q_p"], starts, made["o0"]),
            "states_transposed_scan_ms": timed(
                lambda n, e, r: dr._states_transposed(n, e, r, zeros),
                made["n_mat"], made["g_end"], made["r_mat"]),
            "gates_vjp_ms": timed(gates_transposed, ba, a_log, dt_bias, g,
                                  beta),
            "concatenate_d_qkv_ms": timed(
                lambda a, b, c: jnp.concatenate([a, b, c], axis=-1),
                qkv[:, :third], qkv[:, third:2 * third],
                qkv[:, 2 * third:]),
        }), flush=True)
    if not args.variants or "conv" in args.variants:
        ms_f = timed(lambda x, w: lm_ops.silu_conv(x, w, S), qkv, taps)
        ms_b = timed(lambda x, w, g: lm_ops.silu_conv_grad(x, w, g, S),
                     qkv, taps, qkv)
        from paddle_tpu.parallel import short_conv as kernels

        if jax.devices()[0].platform == "tpu" or args.tiny:
            kf = timed(lambda x, w: kernels.silu_conv_fwd(x, w, S), qkv,
                       taps)
            kb = timed(lambda x, w, g: kernels.silu_conv_bwd(x, w, g, S),
                       qkv, taps, qkv)
            print(json.dumps({
                "op": "short_conv(silu)", "variant": "kernel",
                "forward_ms": kf, "backward_ms": kb,
                "roofline_share_pct": 100 * 1e3 * (
                    least["conv_forward"] + least["conv_backward"])
                / (kf + kb)}), flush=True)
        print(json.dumps({
            "op": "short_conv(silu)", "variant": "plain",
            "forward_ms": ms_f, "backward_ms": ms_b,
            "least_forward_ms": least["conv_forward"] * 1e3,
            "least_backward_ms": least["conv_backward"] * 1e3,
            "roofline_share_pct": 100 * 1e3 * (
                least["conv_forward"] + least["conv_backward"])
            / (ms_f + ms_b)}), flush=True)


if __name__ == "__main__":
    main()
