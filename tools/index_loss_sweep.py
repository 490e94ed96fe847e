"""`indexer_loss` alone, on the chip, at the `keye_vl_2_0_30b_a3b` cell's
shape (one row of 8192 tokens; the main attention's q 32 heads on k 4 heads
of 128, bf16, and its float32 logsumexp; the indexer's q_I 16 heads of 64 on
one k_I, w; the exact top-2048 mask the bisection makes of them): the loss
WITH its three gradients, as the op runs it, through the plain lowering
(`sparse_index.loss_and_grads`, a scan over blocks of 256 queries) and
through the two Pallas kernels of `parallel/index_loss.py` over block
sizes, then each kernel alone, each beside the least time of the indexer's
work a layer (`chipbench/costs_sparse_attn_share`: every causal pair,
forward and two gradients) and held to the plain lowering's numbers.
8 runs a dispatch. PERF.md (PR 50) holds what this printed.

    chiprun -- python tools/index_loss_sweep.py
    python tools/index_loss_sweep.py --tiny     # the wiring, on the CPU
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RUNS = 8
BLOCKS = [(256, 256), (256, 512), (512, 256), (512, 512), (256, 1024),
          (1024, 256), (512, 1024), (1024, 512)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=5001)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--blocks", nargs="+", default=None,
                    help="pairs as 512x512; default: the whole table")
    ap.add_argument("--no-plain", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from chipbench import costs, costs_sparse_attn_share
    from paddle_tpu.ops.lm_ops import _plain_sparse_attention
    from paddle_tpu.parallel import index_loss, sparse_index

    S, H, Hkv, D, Hi, Di, topk = (256, 8, 2, 128, 4, 64, 64) if args.tiny \
        else (8192, 32, 4, 128, 16, 64, 2048)
    blocks = [(128, 128), (128, 256), (256, 128)] if args.tiny else BLOCKS
    if args.blocks:
        blocks = [tuple(int(x) for x in b.split("x")) for b in args.blocks]
    rs = np.random.default_rng(args.seed)
    low = jnp.bfloat16

    def draw(*shape, std=1.0):
        return jnp.asarray(rs.standard_normal(shape) * std, low)

    q, k = draw(S, H, D), draw(S, Hkv, D)
    q_i, k_i = draw(S, Hi, Di), draw(S, Di)
    w = draw(S, Hi, std=(Hi * Di) ** -0.5)
    scale = D ** -0.5
    mask, _ = jax.jit(lambda *a: sparse_index.select(*a, topk))(q_i, k_i, w)
    q_h, k_h = jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1)
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        from paddle_tpu.parallel import flash

        lse = jax.jit(lambda q, k, m: flash.flash_attention_fwd(
            q[None], k[None], k[None], causal=True, scale=scale,
            mask=m[None], block_q=1024, block_k=1024)[1][0])(q_h, k_h, mask)
    else:
        lse = _plain_sparse_attention(q_h[None], k_h[None], k_h[None],
                                      mask[None], scale)[1][0]
    peaks = costs.peaks_for(jax.devices()[0].device_kind if on_tpu
                            else "TPU v5 lite")
    cfg = dict(rows_per_step=1, sequence_length=S, num_hidden_layers=1,
               sa_config=dict(indexer_num_heads=Hi, indexer_head_dim=Di,
                              topk=topk))
    least_ms = 1e3 * costs_sparse_attn_share.indexer_least_seconds_of(
        cfg, True, peaks)

    def timed(fn, *xs):
        def many(first, *rest):
            def body(_, carry):
                x_c, _ = carry
                out = fn(x_c, *rest)
                tip = jax.tree_util.tree_leaves(out)[0].reshape(-1)[0]
                return x_c.at[(0,) * x_c.ndim].set(
                    tip.astype(x_c.dtype) * 0 + x_c[(0,) * x_c.ndim]), out

            return lax.fori_loop(0, RUNS, body, (first, fn(first, *rest)))[1]

        run = jax.jit(many)
        jax.block_until_ready(run(*xs))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = run(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (args.calls * (RUNS + 1)) * 1e3

    def rms(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.sqrt(np.mean((a - b) ** 2))
                     / max(np.sqrt(np.mean(b ** 2)), 1e-30))

    def line(**kv):
        kv["least_ms"] = least_ms
        kv["indexer_roofline_share_of_this_alone_pct"] = \
            100 * least_ms / kv["ms"]
        print(json.dumps(kv), flush=True)

    def plain(q_h, k_h, lse, q_i, k_i, w, mask):
        return sparse_index.loss_and_grads(q_h, k_h, lse, q_i, k_i, w, mask,
                                           scale)

    want = jax.jit(plain)(q_h, k_h, lse, q_i, k_i, w, mask)
    if not args.no_plain:
        line(variant="plain", ms=timed(plain, q_h, k_h, lse, q_i, k_i, w,
                                       mask))
    for pair in blocks:
        if not index_loss.takes(S, H, Hkv, D, Hi, Di, low, pair):
            continue

        def kernels(q, k, lse, q_i, k_i, w, mask, pair=pair):
            return index_loss.loss_and_grads(q, k, lse, q_i, k_i, w, mask,
                                             scale, blocks=pair)

        try:
            got = jax.jit(kernels)(q_h, k_h, lse, q_i, k_i, w, mask)
        except Exception as e:      # Mosaic refuses the pair (VMEM)
            print(json.dumps({"variant": "kernels", "blocks": "%dx%d" % pair,
                              "refused": str(e)[-300:]}), flush=True)
            continue
        qi_h, w32 = jnp.swapaxes(q_i, 0, 1), w.astype(jnp.float32)
        lse_t = lse.T
        p, log_z, p_sum, _ = jax.jit(
            lambda *a: index_loss.target(*a, scale, pair))(
            q_h, k_h, lse_t, qi_h, k_i, w32, mask)
        line(variant="kernels", blocks="%dx%d" % pair,
             ms=timed(kernels, q_h, k_h, lse, q_i, k_i, w, mask),
             target_ms=timed(
                 lambda *a: index_loss.target(*a, scale, pair), q_h, k_h,
                 lse_t, qi_h, k_i, w32, mask),
             grads_ms=timed(
                 lambda *a: index_loss.grads(*a, pair), qi_h, k_i, w32,
                 mask, p, log_z, p_sum),
             loss=float(got[0]), plain_loss=float(want[0]),
             d_q_rms=rms(got[1], want[1]), d_k_rms=rms(got[2], want[2]),
             d_w_rms=rms(got[3], want[3]))


if __name__ == "__main__":
    main()
