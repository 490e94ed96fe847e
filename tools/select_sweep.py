"""`indexer_select`'s selection alone, on the chip, at the
`keye_vl_2_0_30b_a3b` cell's shape (one row of 8192 tokens; the scores [8192,
8192] float32 of a seed's q_I 16 heads of 64 on one k_I, w; topk 2048): the
exact top-k threshold and the mask through the plain lowering
(`sparse_index.select_rows`, a scan over blocks of 256 queries), the plain
lowering's parts alone (the fold into ordered keys, the 32 counting passes,
the running count for the ties, the mask's write), and through the Pallas
kernel of `parallel/index_select.py` over its blocks (queries a chunk, keys
a loop step) and the three ways it adds up a count's lane-wise partial sums,
called once a row on the scores whole and once a block of 256 queries from
a scan; then `sparse_index.select` whole, the score product included, with
each lowering. Each beside the least time the selection's HBM traffic takes
(the scores read once, the mask written once) and held BIT-EQUAL, mask and
threshold, to the plain lowering. 8 runs a dispatch. PERF.md (PR 58) holds
what this printed.

    chiprun -- python tools/select_sweep.py
    python tools/select_sweep.py --tiny     # the wiring, on the CPU
"""

import argparse
import contextlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RUNS = 8
# (queries a chunk, keys a loop step)
BLOCKS = [(64, 512), (32, 512), (128, 512), (256, 512), (64, 256),
          (64, 1024), (64, 2048), (128, 1024), (128, 256)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=5001)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--blocks", nargs="+", default=None,
                    help="pairs as 64x512; default: the whole table")
    ap.add_argument("--no-plain", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from chipbench import costs
    from paddle_tpu.parallel import index_select, sparse_index

    S, Hi, Di, topk, block = (256, 4, 64, 40, 64) if args.tiny \
        else (8192, 16, 64, 2048, sparse_index.BLOCK)
    blocks = [(32, 128), (64, 256), (128, 128)] if args.tiny else BLOCKS
    if args.blocks:
        blocks = [tuple(int(x) for x in b.split("x")) for b in args.blocks]
    rs = np.random.default_rng(args.seed)

    def draw(*shape, std=1.0):
        return jnp.asarray(rs.standard_normal(shape) * std, jnp.bfloat16)

    q_i, k_i = draw(S, Hi, Di), draw(S, Di)
    w = draw(S, Hi, std=(Hi * Di) ** -0.5)
    on_tpu = jax.devices()[0].platform == "tpu"
    peaks = costs.peaks_for(jax.devices()[0].device_kind if on_tpu
                            else "TPU v5 lite")
    # the scores read once, the mask written once
    least_ms = 1e3 * (4 + 1) * S * S / peaks["hbm_bytes_per_s"]
    steps = S // block

    def blocked(x):
        return x.reshape((steps, block) + x.shape[1:])

    def by_blocks(fn, *xs):
        """`fn(i, block of each x)` over the blocks of queries, a scan."""
        _, out = lax.scan(lambda _, b: (None, fn(b[0], *b[1:])), None,
                          (jnp.arange(steps),) + tuple(blocked(x)
                                                       for x in xs))
        return jax.tree_util.tree_map(
            lambda o: o.reshape((S,) + o.shape[2:]), out)

    def score_rows(q_i, k_i, w):
        return by_blocks(lambda _, q, w_b: sparse_index.scores(q, k_i, w_b),
                         q_i, w)

    I = jax.jit(score_rows)(q_i, k_i, w)

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

    def line(**kv):
        kv["least_ms"] = least_ms
        print(json.dumps(kv), flush=True)

    @contextlib.contextmanager
    def plain_lowering():
        """`sparse_index.select_rows` traced as a place without Mosaic
        lowers it, here on the chip."""
        taken, index_select.takes = index_select.takes, lambda *a: False
        try:
            yield
        finally:
            index_select.takes = taken

    def plain(I):
        mask, tau = by_blocks(
            lambda i, I_b: sparse_index.select_rows(I_b, i * block, topk), I)
        return mask.astype(jnp.int8), tau

    with plain_lowering():
        want_mask, want_tau = (np.asarray(x) for x in jax.jit(plain)(I))
    chosen = want_mask.sum(axis=1)
    assert np.array_equal(chosen, np.minimum(np.arange(S) + 1, topk))

    def same(mask, tau):
        return dict(
            mask_equal=bool(np.array_equal(np.asarray(mask), want_mask)),
            threshold_equal=bool(np.array_equal(
                np.asarray(tau).view(np.int32), want_tau.view(np.int32))))

    def causal_keys(i, I_b):
        t = i * block + jnp.arange(block)
        return t, jnp.where(jnp.arange(S)[None, :] <= t[:, None],
                            sparse_index._keys(I_b), jnp.uint32(0))

    def fold(i, I_b):
        return jnp.max(causal_keys(i, I_b)[1], axis=1)

    def passes(i, I_b):
        t, keys = causal_keys(i, I_b)
        return sparse_index._kth_largest(keys, jnp.minimum(t + 1, topk))

    def running_count(_, I_b, tau_b):
        at = I_b == tau_b[:, None]
        return jnp.sum(jnp.cumsum(at, axis=1, dtype=jnp.int32) <= 1, axis=1)

    def write(i, I_b, tau_b):
        return (causal_keys(i, I_b)[1]
                >= sparse_index._keys(tau_b)[:, None]).astype(jnp.int8)

    tau = jnp.asarray(want_tau)
    if not args.no_plain:
        with plain_lowering():
            line(variant="plain", ms=timed(plain, I))
        line(variant="plain: the fold alone",
             ms=timed(lambda I: by_blocks(fold, I), I))
        line(variant="plain: the fold and the 32 passes",
             ms=timed(lambda I: by_blocks(passes, I), I))
        line(variant="plain: the running count alone",
             ms=timed(lambda I, tau: by_blocks(running_count, I, tau), I,
                      tau))
        line(variant="plain: the fold and the mask's write",
             ms=timed(lambda I, tau: by_blocks(write, I, tau), I, tau))
    for pair in blocks:
        if not index_select.takes(block, S, topk, pair):
            continue
        for lane_sum in index_select.LANE_SUMS:
            if lane_sum == "sublanes" and pair[0] % 128:
                continue
            name = dict(blocks="%dx%d" % pair, lane_sum=lane_sum)

            def a_row(I, pair=pair, lane_sum=lane_sum):
                return index_select.select_rows(I, 0, topk, pair, lane_sum)

            def a_block(I, pair=pair, lane_sum=lane_sum):
                return by_blocks(
                    lambda i, I_b: index_select.select_rows(
                        I_b, i * block, topk, pair, lane_sum), I)

            for variant, fn in (("kernel, a row", a_row),
                                ("kernel, a block", a_block)):
                if variant.endswith("block") and lane_sum != "lanes":
                    continue
                try:
                    got = jax.jit(fn)(I)
                except Exception as e:      # Mosaic refuses the form
                    print(json.dumps(dict(name, variant=variant,
                                          refused=str(e)[-300:])),
                          flush=True)
                    continue
                line(variant=variant, ms=timed(fn, I), **name, **same(*got))
    # the op's lowering whole, the score product in front: the plain one
    # with the kernel's dispatch steered off, then as the place has it
    # (held to the plain one's own scores: another program may sum them in
    # another order than `score_rows` above)
    def select(*a):
        return sparse_index.select(*a, topk, block)

    with plain_lowering():
        want_mask, want_tau = (np.asarray(x)
                               for x in jax.jit(select)(q_i, k_i, w))
        if not args.no_plain:
            line(variant="scores alone", ms=timed(score_rows, q_i, k_i, w))
            line(variant="sparse_index.select, plain",
                 ms=timed(select, q_i, k_i, w))
    line(variant="sparse_index.select, as this place lowers it",
         ms=timed(select, q_i, k_i, w), **same(*jax.jit(select)(q_i, k_i, w)))


if __name__ == "__main__":
    main()
