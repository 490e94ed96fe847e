"""The combine of a share-holding expert layer, alone, on the chip: Out_t =
sum over token t's k choices of w[t, j] * (its row of the down product),
from a table of the first B sorted rows (`lm_ops._combine`, PR 33), in the
forms tried, at the two share-holding cells' shapes, beside the full-size
combine and the dispatch gathers at B and at N rows. PERF.md (PR 33) holds
what this printed.

    chiprun -- python tools/combine_sweep.py
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (cell, tokens, top_k, hidden, held, experts)
SHAPES = [("laguna_xs_2", 8192, 8, 2048, 32, 256),
          ("xing4_0_29b_a4b", 4096, 4, 3584, 8, 64)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3301)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import lm_ops

    F32, bf = jnp.float32, jnp.bfloat16
    rows_out = []
    for cell, T, k, H, held, E in SHAPES:
        N = T * k
        B = lm_ops.row_bound(N, held, E)
        rs = np.random.default_rng(args.seed)
        chosen = np.stack([rs.permutation(E)[:k] for _ in range(T)])
        key = np.where(chosen.reshape(-1) < held, chosen.reshape(-1), held)
        order = np.argsort(key, kind="stable").astype(np.int32)
        inv = np.argsort(order).astype(np.int32)
        R = int((key < held).sum())
        w = np.where((chosen < held), rs.random((T, k)), 0.0)
        ops = dict(
            x=jnp.asarray(rs.standard_normal((T, H)), bf),
            ys=jnp.asarray(rs.standard_normal((B, H)) * (
                np.arange(B)[:, None] < R), bf),
            ys_n=jnp.asarray(rs.standard_normal((N, H)) * (
                np.arange(N)[:, None] < R), bf),
            w=jnp.asarray(w, F32), order=jnp.asarray(order),
            inv=jnp.asarray(inv))

        def rows_or_zero(table, idx):
            return jnp.take(table, idx, axis=0, mode="fill", fill_value=0)

        def combine(ys, w, inv):
            y = rows_or_zero(ys, inv).reshape(T, k, -1)
            return jnp.einsum("tkh,tk->th", y.astype(F32), w).astype(bf)

        def combine_by_choice(ys, w, inv):
            inv = inv.reshape(T, k)
            return sum(rows_or_zero(ys, inv[:, j]).astype(F32)
                       * w[:, j, None] for j in range(k)).astype(bf)

        def combine_zero_row(ys, w, inv):
            table = jnp.concatenate([ys, jnp.zeros((1, H), bf)])
            y = table[jnp.minimum(inv, B)].reshape(T, k, -1)
            return jnp.einsum("tkh,tk->th", y.astype(F32), w).astype(bf)

        def combine_scatter(ys, w, order):
            tok = order[:B] // k
            rows = ys.astype(F32) * w.reshape(-1)[order[:B]][:, None]
            return jnp.zeros((T, H), F32).at[tok].add(rows).astype(bf)

        def d_x_sum(g, inv):
            return rows_or_zero(g, inv).reshape(T, k, -1).sum(
                axis=1).astype(bf)

        def d_x_by_choice(g, inv):
            inv = inv.reshape(T, k)
            return sum(rows_or_zero(g, inv[:, j]).astype(F32)
                       for j in range(k)).astype(bf)

        def scalars_gather(d_w, inv):
            return jnp.take(d_w, inv, mode="fill", fill_value=0)

        def scalars_scatter(d_w, order):
            return jnp.zeros((N,), F32).at[order[:B]].set(
                d_w, unique_indices=True)

        def scalars_by_lane(d_w, inv):
            rows = jnp.take(d_w.reshape(B // 128, 128), inv // 128, axis=0,
                            mode="fill", fill_value=0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (N, 128), 1)
            return jnp.sum(jnp.where(lane == (inv % 128)[:, None], rows,
                                     0.0), axis=1)

        d_w = jnp.asarray(rs.standard_normal(B), F32)
        o = ops
        table, full = (o["ys"], o["w"], o["inv"]), (o["ys_n"], o["w"],
                                                       o["inv"])
        cases = [
            ("combine, B-row table, one gather", combine, table),
            ("combine, B-row table, a gather a choice (the op's)",
             combine_by_choice, table),
            ("combine, B-row table + a zero row, inv clipped",
             combine_zero_row, table),
            ("combine, scatter-add of the B rows", combine_scatter,
             (o["ys"], o["w"], o["order"])),
            ("combine, N-row table (full size)", combine, full),
            ("d x, B-row table, one gather", d_x_sum, (o["ys"], o["inv"])),
            ("d x, B-row table, a gather a choice (the op's)",
             d_x_by_choice, (o["ys"], o["inv"])),
            ("d x, N-row table (full size)", d_x_sum,
             (o["ys_n"], o["inv"])),
            ("weights' gradient to token order, a gather of N scalars",
             scalars_gather, (d_w, o["inv"])),
            ("weights' gradient to token order, a scatter of B scalars "
             "(the op's)", scalars_scatter, (d_w, o["order"])),
            ("weights' gradient to token order, rows of 128 and a lane",
             scalars_by_lane, (d_w, o["inv"])),
            ("dispatch gather, B rows", lambda x, order: x[order[:B] // k],
             (o["x"], o["order"])),
            ("dispatch gather, N rows", lambda x, order: x[order // k],
             (o["x"], o["order"])),
        ]
        want = None
        for name, fn, operands in cases:
            f = jax.jit(fn)
            got = jax.block_until_ready(f(*operands))
            err = None
            if name.startswith("weights") or (
                    name.startswith("combine") and "N-row" not in name):
                got = np.asarray(got, np.float32)
                if name.endswith("one gather") or "N scalars" in name:
                    want = got      # the first form of each quantity
                err = float(np.max(np.abs(got - want)))
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = f(*operands)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.calls * 1e3
            rows_out.append({"cell": cell, "N": N, "B": B, "R": R, "H": H,
                             "case": name, "ms": round(ms, 4),
                             "max_abs_diff_to_first": err})
            print(json.dumps(rows_out[-1]), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out", "pr33"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "pr33",
                           "combine_sweep.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows_out}, f,
                  indent=1)


if __name__ == "__main__":
    main()
