"""The bounded sums of a share-holding expert layer, alone, on the chip:
Out_t = sum over token t's k choices of w[t, j] * (its row of the down
product), from a table of the first B sorted rows (`lm_ops._combine`, PR
33), and the dispatch's backward, the same sum without weights, in the
forms tried, at the four share-holding cells' shapes: the gathers (one of
all T * k choices, one a choice: the op's until PR 40), XLA's scatter-add
of the B rows, and the B rows summed by token through the row-tile kernel
(`parallel/row_sum.py`; PR 40: float32 rows in and out as ISSUE 40 drew it,
the result written as bf16, bf16 rows widened and weighed in the kernel,
and the op's form, those sorted by the kernel's tile of tokens and not by
the token). Each sum twice: `alone` (the result is the output)
and `consumed` (a residual `h + result` reads it at once, as the step's
next op does: a result alone may sit in `S(1)`). Beside them the full-size
combine, the forms of the weights' gradient and the dispatch gathers at B
and at N rows. REPS runs a dispatch (a dispatch costs the host as long as a
small kernel takes: PR 31), the index arrays rolled from run to run so that
no run is the last one's. PERF.md (PR 33, PR 40) holds what this printed;
it set `row_sum.takes_choices`.

    chiprun -- python tools/combine_sweep.py
    JAX_PLATFORMS=cpu python tools/combine_sweep.py --tiny   # rehearsal
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REPS = 8
# (cell, tokens, top_k, hidden, held, experts)
SHAPES = [("smallthinker_21b_a3b", 8192, 6, 2560, 16, 64),
          ("laguna_xs_2", 8192, 8, 2048, 32, 256),
          ("lfm2_8b_a1b", 8192, 4, 2048, 8, 32),
          ("xing4_0_29b_a4b", 4096, 4, 3584, 8, 64)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=4001)
    ap.add_argument("--sums-only", action="store_true",
                    help="the bounded sums alone, not PR 33's other cases")
    ap.add_argument("--tiny", action="store_true",
                    help="a small shape, kernels interpreted: a rehearsal")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import row_sum

    F32, bf = jnp.float32, jnp.bfloat16
    on_chip = jax.devices()[0].platform == "tpu"
    shapes = [("tiny", 256, 4, 256, 2, 8)] if args.tiny else SHAPES
    rows_out = []
    for cell, T, k, H, held, E in shapes:
        N = T * k
        B = lm_ops.row_bound(N, held, E)
        tiles = (64, 16) if args.tiny else row_sum.tiles_for(H)
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

        def one_gather(ys, w, inv):
            y = rows_or_zero(ys, inv).reshape(T, k, -1).astype(F32)
            if w is None:
                return y.sum(axis=1).astype(bf)
            return jnp.einsum("tkh,tk->th", y, w).astype(bf)

        def by_choice(ys, w, inv):
            return lm_ops._sum_of_choices(ys, inv, k, w).astype(bf)

        def zero_row(ys, w, inv):
            table = jnp.concatenate([ys, jnp.zeros((1, H), bf)])
            y = table[jnp.minimum(inv, B)].reshape(T, k, -1)
            return jnp.einsum("tkh,tk->th", y.astype(F32), w).astype(bf)

        def slot_weights(w, order):
            return None if w is None else w.reshape(-1)[order[:B]]

        def scatter(ys, w, order):
            token = jnp.where(jnp.arange(B) < R, order[:B] // k, T)
            rows = ys.astype(F32)
            if w is not None:
                rows = rows * slot_weights(w, order)[:, None]
            return jnp.zeros((T, H), F32).at[token].add(
                rows, mode="drop").astype(bf)

        def by_token(order):
            token = jnp.where(jnp.arange(B) < R, order[:B] // k, T)
            slots, token = row_sum.sort_by_id(token, T)
            return row_sum.with_tail(slots, tiles[1]), token

        def kernel_f32(out_dtype):
            def form(ys, w, order):
                slots, token = by_token(order)
                rows = ys[slots].astype(F32)
                if w is not None:
                    rows = rows * slot_weights(w, order)[slots][:, None]
                return row_sum.sum_sorted_rows(
                    token, rows, T, tiles, interpret=not on_chip,
                    out_dtype=out_dtype).astype(bf)
            return form

        def kernel_by_token(ys, w, order):
            slots, token = by_token(order)
            w = slot_weights(w, order)
            return row_sum.sum_sorted_rows(
                token, ys[slots], T, tiles, interpret=not on_chip,
                weights=None if w is None else w[slots], out_dtype=bf)

        def by_tile(order):
            # the op's own: a counting sort by the kernel's tile of tokens
            # and one scatter of B scalars that pack slot and choice
            return lm_ops._by_token(order[:B], R, T, k, H)

        def kernel_op(ys, w, order):
            return lm_ops._sum_by_token(
                ys, by_tile(order), T, None if w is None else w.reshape(-1))

        def gather_alone(which):
            def form(ys, w, order):
                slots = (by_token if which == "token" else by_tile)(order)[0]
                return ys[slots][:T]
            return form

        def without_weights(fn):
            return lambda ys, idx: fn(ys, None, idx)

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
        sums = [("one gather", one_gather, "inv"),
                ("a gather a choice (the op's until PR 40)", by_choice,
                 "inv"),
                ("scatter-add of the B rows", scatter, "order"),
                ("kernel, float32 rows, float32 out", kernel_f32(F32),
                 "order"),
                ("kernel, float32 rows, bf16 out", kernel_f32(bf), "order"),
                ("kernel, bf16 rows weighed inside, bf16 out, sorted by "
                 "token", kernel_by_token, "order"),
                ("kernel, bf16 rows weighed inside, bf16 out, sorted by "
                 "tile (the op's)", kernel_op, "order")]
        cases = [("combine, B-row table, " + name, fn, (o["ys"], o["w"],
                                                         o[idx]), True)
                 for name, fn, idx in sums]
        cases += [("d x, B-row table, " + name, without_weights(fn),
                   (o["ys"], o[idx]), True) for name, fn, idx in sums]
        # the sort and the B-row gather in front of the kernel, alone (the
        # first T rows returned: a result of the other cases' size)
        cases += [("the kernel's gather, " + name, without_weights(
            gather_alone(which)), (o["ys"], o["order"]), False)
            for name, which in (("sorted by token: scattered rows", "token"),
                                ("sorted by tile: runs of rows", "tile"))]
        if not args.sums_only:
            cases += [
                ("combine, B-row table + a zero row, inv clipped", zero_row,
                 (o["ys"], o["w"], o["inv"]), False),
                ("combine, N-row table (full size)", one_gather,
                 (o["ys_n"], o["w"], o["inv"]), False),
                ("d x, N-row table (full size)", without_weights(one_gather),
                 (o["ys_n"], o["inv"]), False),
                ("weights' gradient to token order, a gather of N scalars",
                 scalars_gather, (d_w, o["inv"]), False),
                ("weights' gradient to token order, a scatter of B scalars "
                 "(the op's)", scalars_scatter, (d_w, o["order"]), False),
                ("weights' gradient to token order, rows of 128 and a lane",
                 scalars_by_lane, (d_w, o["inv"]), False),
                ("dispatch gather, B rows",
                 lambda x, order: x[order[:B] // k], (o["x"], o["order"]),
                 False),
                ("dispatch gather, N rows", lambda x, order: x[order // k],
                 (o["x"], o["order"]), False)]

        def timed(fn, operands, consumed):
            """ms a run: REPS runs a dispatch, the index arrays rolled."""
            def runs(carry, *operands):
                def again(i, carry):
                    got = fn(*(jnp.roll(a, i) if a.dtype == jnp.int32 else a
                               for a in operands))
                    return carry + got if consumed else got
                return lax.fori_loop(0, REPS, again, carry)

            jf = jax.jit(runs, donate_argnums=(0,))
            shape = jax.eval_shape(fn, *operands)
            carry = jax.block_until_ready(
                jf(jnp.zeros(shape.shape, shape.dtype), *operands))
            t0 = time.perf_counter()
            for _ in range(args.calls):
                carry = jf(carry, *operands)
            jax.block_until_ready(carry)
            return round((time.perf_counter() - t0)
                         / (args.calls * REPS) * 1e3, 4)

        want = {}
        for name, fn, operands, is_sum in cases:
            got = np.asarray(jax.jit(fn)(*operands), np.float32)
            # against the first form of each quantity
            quantity = name.split(",")[0]
            err = None
            if is_sum or name.startswith("weights"):
                err = float(np.max(np.abs(got - want.setdefault(quantity,
                                                                got))))
            row = {"cell": cell, "T": T, "k": k, "N": N, "B": B, "R": R,
                   "H": H, "case": name,
                   "ms_alone": timed(fn, operands, False),
                   "max_abs_diff_to_first": err}
            if is_sum:
                row["ms_consumed"] = timed(fn, operands, True)
            rows_out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out", "pr40"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "pr40",
                           "combine_sweep.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows_out}, f,
                  indent=1)


if __name__ == "__main__":
    main()
