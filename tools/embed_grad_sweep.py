"""The embedding's gradient, alone, on the chip: XLA's lowering of
`zeros([V, H]).at[ids].add(rows)` against the row-tile kernel
(`paddle_tpu/parallel/row_sum.py`, PR 38) at the four token cells' shapes
and at two tables between Laguna's 103 MB and Xing's 235 MB, Zipf(1.1) ids
laid over the vocabulary by a seeded permutation (as
`chipbench/kinds/train_tokens.py` draws them), the kernel's tile rows R and
chunk rows C swept. Each form twice: `alone` (the table is the result) and
`consumed` (the table is a temporary that an update `w - 1e-3 * table`
reads at once, as the step's `adam` does: only there may XLA assign the
scatter's result to `S(1)`). REPS runs a dispatch (a dispatch costs the
host as long as a small kernel takes: PR 31), the ids rolled from run to
run. PERF.md (PR 38) holds what this printed; it set
`row_sum.MIN_TABLE_BYTES` and `row_sum.tiles_for`.

    chiprun -- python tools/embed_grad_sweep.py
    JAX_PLATFORMS=cpu python tools/embed_grad_sweep.py --tiny   # rehearsal
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REPS = 8
# (name, V, H, T)
SHAPES = [("smallthinker_21b_a3b", 37984, 2560, 8192),
          ("olmoe_1b_7b", 50304, 2048, 8192),
          ("xing4_0_29b_a4b", 16384, 3584, 4096),
          ("laguna_xs_2", 12544, 2048, 8192),
          ("151 MB", 18432, 2048, 8192),
          ("201 MB", 24576, 2048, 8192)]
TILES = [(256, 64), (512, 64), (128, 64), (256, 32), (256, 128), (512, 128),
         (1024, 128)]


def zipf_ids(rs, V, T, exponent=1.1):
    """T ids: rank r has weight r^-exponent, the ranks laid over the V ids
    by a permutation."""
    weight = np.arange(1, V + 1, dtype=np.float64) ** -exponent
    return rs.permutation(V)[rs.choice(V, size=T, p=weight / weight.sum())]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3801)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, kernels interpreted: a rehearsal")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.parallel import row_sum

    shapes, tiles = SHAPES, TILES
    if args.tiny:
        shapes, tiles = [("tiny", 1000, 256, 512)], [(64, 16), (128, 8)]
    on_chip = jax.devices()[0].platform == "tpu"
    rows_out = []
    for name, V, H, T in shapes:
        rs = np.random.default_rng(args.seed)
        ids = jnp.asarray(zipf_ids(rs, V, T), jnp.int32)
        g = jnp.asarray(rs.standard_normal((T, H)), jnp.float32)
        counts = np.bincount(np.asarray(ids), minlength=V)

        def xla(i, r):
            return jnp.zeros((V, H), jnp.float32).at[i].add(r)

        forms = [("xla scatter", xla)] + [
            ("kernel R=%d C=%d" % rc, lambda i, r, rc=rc:
             row_sum.sum_rows_by_id(i, r, V, rc, interpret=not on_chip))
            for rc in tiles]
        want = None
        for form, fn in forms:
            def alone(table, i, r):
                def again(_, carry):
                    i, _ = carry
                    return jnp.roll(i, 1), fn(i, r)
                return lax.fori_loop(0, REPS, again, (i, table))[1]

            def consumed(w, i, r):
                def again(_, carry):
                    i, w = carry
                    return jnp.roll(i, 1), w - 1e-3 * fn(i, r)
                return lax.fori_loop(0, REPS, again, (i, w))[1]

            got = np.asarray(jax.jit(fn)(ids, g))
            if want is None:
                want = got
            err = float(np.max(np.abs(got - want)))
            ms = {}
            for how, runs in (("alone", alone), ("consumed", consumed)):
                jf = jax.jit(runs, donate_argnums=(0,))
                table = jax.block_until_ready(
                    jf(jnp.zeros((V, H), jnp.float32), ids, g))
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    table = jf(table, ids, g)
                jax.block_until_ready(table)
                ms[how] = round((time.perf_counter() - t0)
                                / (args.calls * REPS) * 1e3, 4)
                del table
            rows_out.append({
                "shape": name, "V": V, "H": H, "T": T,
                "table_MB": round(V * H * 4 / 1e6, 1),
                "distinct_ids": int((counts > 0).sum()),
                "most_rows_of_one_id": int(counts.max()),
                "form": form, "ms_alone": ms["alone"],
                "ms_consumed": ms["consumed"],
                "max_abs_diff_to_xla": err})
            print(json.dumps(rows_out[-1]), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out", "pr38"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "pr38",
                           "embed_grad_sweep.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows_out}, f,
                  indent=1)


if __name__ == "__main__":
    main()
