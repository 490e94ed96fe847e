"""The grouped products of a layer that holds 8 of its 64 experts, alone,
on the chip: the Pallas kernels of paddle_tpu/parallel/grouped.py at the
`xing4_0_29b_a4b` cell's shapes ([16384, 3584] x [8, 3584, 1024] and
[16384, 1024] x [8, 1024, 3584] bf16, the groups ending after about 2048
of the 16384 rows), over the K / M tile limit of `tiles_for` (a row of 4
KiB splits K = 3584 in two, 8 KiB keeps it whole) and the row tile,
against `lax.ragged_dot` in the same process. PERF.md (PR 30) holds what
this printed.

    chiprun -- python tools/grouped_share_sweep.py
    python tools/grouped_share_sweep.py --compile-only   # a described v5e

`--epilogues` (PR 31) times instead, at these shapes and group sizes, the
kernels of `grouped_mlp` that carry element-wise work with it, without it,
and without it followed by the XLA pass it replaces
(`grouped_sweep.epilogue_rows`), into chiprun_out/pr31/.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3001)
    ap.add_argument("--epilogues", action="store_true")
    args = ap.parse_args()
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from paddle_tpu.parallel import grouped

    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", False)
        grouped.pallas_interpret = lambda: False
    N, E, bf = 16384, 8, jnp.bfloat16
    rs = np.random.default_rng(args.seed)
    # 4096 tokens x 4 choices over 64 experts, 8 of them held
    chosen = rs.integers(0, 64, N)
    counts = np.bincount(chosen[chosen < E], minlength=E).astype(np.int32)
    if args.epilogues:
        from grouped_sweep import epilogue_rows     # beside this file

        rows = epilogue_rows(N, E, 3584, 1024, counts.tolist(), args.calls,
                             rows_past=True)
        os.makedirs(os.path.join(REPO, "chiprun_out", "pr31"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "pr31",
                               "share_epilogues.json"), "w") as f:
            json.dump(rows, f, indent=1)
        return
    results = []
    for shape_key, (K, M) in (("gate_up", (3584, 1024)),
                              ("down", (1024, 3584))):
        for wide_bytes in (4096, 8192):
            for tm in (512, 256, 128):
                wide = wide_bytes // 2
                tk = grouped._largest_tile(K, wide)
                tn = grouped._largest_tile(M, wide)
                tiles = (tm, (tk, tn), (tn, tk), (tk, tn))

                def fn(lhs, rhs, cnt, g, tiles=tiles):
                    out, vjp = jax.vjp(
                        lambda a, b: grouped.grouped_matmul(
                            a, b, cnt, None, tiles, True), lhs, rhs)
                    return (out,) + vjp(g)

                def ragged(lhs, rhs, cnt, g):
                    out, vjp = jax.vjp(
                        lambda a, b: lax.ragged_dot(
                            a, b, cnt, preferred_element_type=bf), lhs, rhs)
                    return (out,) + vjp(g)

                for name, f in (("kernels", fn), ("ragged_dot", ragged)):
                    if name == "ragged_dot" and (tm, wide_bytes) != (
                            512, 4096):
                        continue
                    row = {"shape": shape_key, "what": name,
                           "tile_row_bytes": wide_bytes, "tm": tm,
                           "tk_tn": [tk, tn], "rows_held": int(counts.sum())}
                    if args.compile_only:
                        def sds(s, dt=bf):
                            return jax.ShapeDtypeStruct(s, dt,
                                                        sharding=sharding)
                        try:
                            c = jax.jit(f).lower(
                                sds((N, K)), sds((E, K, M)),
                                sds((E,), jnp.int32), sds((N, M))).compile()
                            row["temp_mb"] = \
                                c.memory_analysis().temp_size_in_bytes / 1e6
                        except Exception as e:     # what Mosaic refuses
                            row["refused"] = str(e)[:300]
                    else:
                        lhs = jnp.asarray(rs.normal(0, 1, (N, K)), bf)
                        rhs = jnp.asarray(rs.normal(0, .02, (E, K, M)), bf)
                        g = jnp.asarray(rs.normal(0, 1, (N, M)), bf)
                        cnt = jnp.asarray(counts)
                        jf = jax.jit(f)
                        jax.block_until_ready(jf(lhs, rhs, cnt, g))
                        t = time.perf_counter()
                        for _ in range(args.calls):
                            o = jf(lhs, rhs, cnt, g)
                        jax.block_until_ready(o)
                        row["ms_fwd_and_both_grads"] = (
                            time.perf_counter() - t) / args.calls * 1e3
                    print(json.dumps(row), flush=True)
                    results.append(row)
    os.makedirs(os.path.join(REPO, "chiprun_out", "pr30"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "pr30",
                           "grouped_share_sweep.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
