"""The expert layer's grouped products alone, on the chip: the program's
Pallas kernels (paddle_tpu/parallel/grouped.py) over row, K and M tiles,
against `lax.ragged_dot` (XLA's `ragged-dot-none`) in the same process, at
the `olmoe_1b_7b` cell's shapes, with a seeded step's real
`TokensPerExpert` and with even groups. PERF.md (PR 29) holds the table
this printed and `grouped.tiles_for` the rule read off it.

    chiprun -- sh -c 'python tools/grouped_sweep.py counts --seed 2901 &&
                      python tools/grouped_sweep.py sweep'

`counts` runs the cell's forward once (its own process: the model's 2.5
GB leave the chip with it) and writes chiprun_out/pr29/counts.json;
`sweep` times every variant (a jit is traced at its first call, so the
module's `_BLOCK_ROWS` is set per variant; host clock around `--calls`
back-to-back calls ending in block_until_ready) and writes
chiprun_out/pr29/<--out>. `--compile-only` compiles every variant for a described v5e instead (no
chip, no time): what Mosaic refuses shows here first.

`epilogues` (PR 31) times the four kernels of `grouped_mlp` that carry
element-wise work (SiLU * up behind the up product, its backward behind
the d h product, the second d xs product added onto the first, and h
formed in front of d down) with it, without it, and without it followed
by the XLA pass it replaces, and writes chiprun_out/pr31/epilogues.json:

    chiprun -- sh -c 'python tools/grouped_sweep.py counts --seed 2901 &&
                      python tools/grouped_sweep.py epilogues'
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "chiprun_out", "pr29")
REPS = 8        # runs of a kernel inside one dispatch: `epilogue_rows`


def real_counts(seed):
    import numpy as np
    import paddle_tpu as fluid
    from chipbench.configs import olmoe_1b_7b as builder
    from chipbench.kinds.train_tokens import token_rows
    from paddle_tpu import amp

    with open(os.path.join(REPO, "chipbench", "configs",
                           "olmoe_1b_7b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "chipbench", "traffic",
                           "train_tokens_packed4k.json")) as f:
        traffic = json.load(f)
    tok, lab, _ = token_rows(cfg, traffic, seed, int(cfg["rows_per_step"]))
    amp.enable(cfg["amp"])
    built = builder.build(fluid, cfg, seed)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(built["startup"])
    load, = exe.run(built["test_prog"], feed={"tokens": tok, "labels": lab},
                    fetch_list=[built["routing"][0][1]])
    load = np.asarray(load).reshape(-1)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "counts.json"), "w") as f:
        json.dump({"seed": seed, "counts": [int(c) for c in load]}, f)
    print("counts", load.tolist(), "max/mean", load.max() / load.mean())


def variants(args):
    """(kind, shape key, (tm, tk, tn, rows of a block of a tile that a
    boundary crosses) or None for ragged_dot)."""
    out = []
    for shape in ("gate_up", "down"):
        K, M = (2048, 1024) if shape == "gate_up" else (1024, 2048)
        for kind in ("fwd", "dlhs", "drhs"):
            out.append((kind, shape, None))
            # the contraction and the output width of THIS kernel
            k, m = (M, K) if kind == "dlhs" else (K, M)
            if kind == "drhs":
                pairs = {(min(k, 1024), min(m, 1024)), (512, 512),
                         (k, m), (min(k, 1024), m), (k, min(m, 1024)),
                         (512, min(m, 1024)), (min(k, 1024), 512)}
            else:
                pairs = {(k, m), (k, m // 2), (k, 512), (k, 256),
                         (min(k, 1024), min(m, 1024)), (512, 512),
                         (512, m)}
            if args.whole:
                pairs = {(k, m)}
            for tm in args.tm:
                for tk, tn in sorted(pairs):
                    for rows in args.block_rows:
                        if rows <= tm:
                            out.append((kind, shape, (tm, tk, tn, rows)))
    return out


def build(kind, shape, tiles, counts, jax, jnp, sds=None):
    """The jitted call and its operands (shapes when `sds`)."""
    from jax import lax
    from paddle_tpu.parallel import grouped

    N, E = 65536, 64
    K, M = (2048, 1024) if shape == "gate_up" else (1024, 2048)
    bf = jnp.bfloat16

    def ragged(a, b, c):
        return lax.ragged_dot(a, b, group_sizes=c, preferred_element_type=bf)

    if tiles is None:
        f = ragged
    else:
        tm, tk, tn, block_rows = tiles
        grouped._BLOCK_ROWS = block_rows      # read when `f` is traced

        def f(a, b, c):
            return grouped.grouped_matmul(
                a, b, c, None, (tm, (tk, tn), (tk, tn), (tk, tn)))

    if kind == "fwd":
        fn = f
    else:
        def fn(a, b, c, g):
            da, db = jax.vjp(lambda x, y: f(x, y, c), a, b)[1](g)
            return da if kind == "dlhs" else db

    shapes = [((N, K), bf), ((E, K, M), bf), ((E,), jnp.int32)]
    if kind != "fwd":
        shapes.append(((N, M), bf))
    if sds:
        return jax.jit(fn), [sds(s, d) for s, d in shapes]
    key = jax.random.PRNGKey(0)
    ops = [jax.random.normal(jax.random.fold_in(key, i), s, d)
           if d == bf else jnp.asarray(counts, jnp.int32)
           for i, (s, d) in enumerate(shapes)]
    return jax.jit(fn), ops


def sweep(args):
    import jax
    import jax.numpy as jnp

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        from paddle_tpu.parallel import grouped

        grouped.pallas_interpret = lambda: False
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def sds(s, d):
            return jax.ShapeDtypeStruct(s, d, sharding=chip)

        for kind, shape, tiles in variants(args):
            fn, ops = build(kind, shape, tiles, None, jax, jnp, sds)
            try:
                mem = fn.lower(*ops).compile().memory_analysis()
                print(kind, shape, tiles, "ok temp", mem.temp_size_in_bytes)
            except Exception as e:
                print(kind, shape, tiles, "REFUSED", str(e)[:300])
        return

    dev = jax.devices()[0]
    assert dev.platform == "tpu", dev
    with open(args.counts) as f:
        real = json.load(f)["counts"]
    groups = {"real": real, "even": [65536 // 64] * 64}
    rows = []
    for kind, shape, tiles in variants(args):
        row = {"kind": kind, "shape": shape, "tiles": tiles}
        for name, counts in groups.items():
            try:
                fn, ops = build(kind, shape, tiles, counts, jax, jnp)
                jax.block_until_ready(fn(*ops))
                jax.block_until_ready(fn(*ops))
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    o = fn(*ops)
                jax.block_until_ready(o)
                row[name] = (time.perf_counter() - t0) / args.calls * 1e3
            except Exception as e:
                row[name] = None
                row["error"] = str(e)[:200]
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, args.out), "w") as f:
        json.dump({"device": dev.device_kind, "calls": args.calls,
                   "counts": real, "rows": rows}, f, indent=1)


def epilogue_rows(N, E, H, F, counts, calls, rows_past=False):
    """One row a kernel of `grouped_mlp` that carries element-wise work:
    ms a call of the kernel `plain`, `with` the work in it, and of
    `plain_and_pass`, the plain kernel followed by the XLA pass the work
    replaces; [N, H] rows, E experts of width F, bf16, the tiles
    `tiles_for` chooses."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.parallel import grouped
    from paddle_tpu.parallel.grouped import _gmm, _tgmm

    bf = jnp.bfloat16
    (tm, up_fwd, up_dlhs, _), (_, _, down_dlhs, down_drhs) = (
        grouped.tiles_for(N, H, F, bf), grouped.tiles_for(N, F, H, bf))
    key = jax.random.PRNGKey(0)

    def draw(i, shape, scale=1.0):
        return (jax.random.normal(jax.random.fold_in(key, i), shape)
                * scale).astype(bf)

    o = dict(xs=draw(0, (N, H)), d_ys=draw(1, (N, H)), a=draw(2, (N, F)),
             b=draw(3, (N, F)), d_b=draw(4, (N, F)),
             up=draw(5, (E, H, F), H ** -.5),
             down=draw(6, (E, F, H), F ** -.5),
             counts=jnp.asarray(counts, jnp.int32))

    def meta(o, empty_groups=False):
        return grouped.visits(o["counts"], N, tm, empty_groups)

    def silu_mul(a, b):
        return jax.nn.silu(a) * b

    def up(o, *epilogue):
        return _gmm(o["xs"], o["up"], *meta(o), (tm,) + up_fwd, False,
                    *epilogue)

    def d_h(o, *epilogue):
        return _gmm(o["d_ys"], o["down"], *meta(o), (tm,) + down_dlhs, True,
                    *epilogue)

    def d_xs(o, *epilogue):
        return _gmm(o["d_b"], o["up"], *meta(o), (tm,) + up_dlhs, True,
                    *epilogue)

    def d_down(o, lhs):
        return _tgmm(lhs, o["d_ys"], *meta(o, True), (tm,) + down_drhs, bf,
                     mask_lhs=True)

    # the kernel plain, with the work in it, plain and then the pass that
    # work replaces; `ex` is the first d xs product, each call's result
    # given to the next
    variants = {
        "up_forward": (
            lambda o, ex: up(o),
            lambda o, ex: up(o, "silu_mul", (o["a"],)),
            lambda o, ex: silu_mul(o["a"], up(o))),
        "d_h": (
            lambda o, ex: d_h(o),
            lambda o, ex: d_h(o, "silu_mul_grad", (o["a"], o["b"])),
            lambda o, ex: jax.vjp(silu_mul, o["a"], o["b"])[1](d_h(o))),
        "second_d_xs": (
            lambda o, ex: d_xs(o),
            lambda o, ex: d_xs(o, "add", (ex,)),
            lambda o, ex: ex + d_xs(o)),
        "d_down": (
            lambda o, ex: d_down(o, o["a"]),
            lambda o, ex: d_down(o, (o["a"], o["b"])),
            lambda o, ex: d_down(o, silu_mul(o["a"], o["b"]))),
    }
    rows = []
    for name, fns in variants.items():
        row = {"kernel": name, "rows": N, "rows_held": int(sum(counts))}
        if rows_past:
            row["rows_past"] = True
        gives = name == "second_d_xs"
        for what, fn in zip(("plain", "with", "plain_and_pass"), fns):
            def runs(ex, o, fn=fn):
                # REPS runs a dispatch (one costs the host ~0.2 ms, as
                # long as a kernel at the share's shapes takes), the group
                # sizes rotated from run to run so that none is the one
                # before it
                def again(_, carry):
                    c, out = carry
                    return jnp.roll(c, 1), fn(dict(o, counts=c),
                                              out if gives else ex)

                return lax.fori_loop(0, REPS - 1, again, (
                    jnp.roll(o["counts"], 1), fn(o, ex)))[1]

            jf = jax.jit(runs, donate_argnums=(0,) if gives else ())
            ex = jnp.zeros((N, H), bf)
            out = jax.block_until_ready(jf(ex, o))
            t0 = time.perf_counter()
            for _ in range(calls):
                out = jf(out if gives else ex, o)
            jax.block_until_ready(out)
            row[what + "_ms"] = (
                time.perf_counter() - t0) / (calls * REPS) * 1e3
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def epilogues(args):
    import jax

    dev = jax.devices()[0]
    assert dev.platform == "tpu", dev
    with open(args.counts) as f:
        real = json.load(f)["counts"]
    out = {"device": dev.device_kind, "calls": args.calls, "counts": real}
    for name, counts in (("real", real), ("even", [65536 // 64] * 64)):
        out[name] = epilogue_rows(65536, 64, 2048, 1024, counts, args.calls)
    os.makedirs(os.path.join(REPO, "chiprun_out", "pr31"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "pr31", "epilogues.json"),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("phase", choices=["counts", "sweep", "epilogues"])
    p.add_argument("--seed", type=int, default=2901)
    p.add_argument("--calls", type=int, default=20)
    p.add_argument("--tm", type=int, nargs="+", default=[128, 256, 512, 1024])
    p.add_argument("--block-rows", type=int, nargs="+", default=[128],
                   help="rows of a block of a boundary tile (= tm: the "
                        "whole tile, masked; > tm: skipped)")
    p.add_argument("--out", default="sweep.json")
    p.add_argument("--whole", action="store_true",
                   help="only whole-K, whole-M tiles")
    p.add_argument("--counts", default=os.path.join(OUT, "counts.json"),
                   help="the group sizes `counts` wrote (the chip tool "
                        "does not copy chiprun_out/ to the next call)")
    p.add_argument("--compile-only", action="store_true")
    a = p.parse_args()
    {"counts": lambda: real_counts(a.seed), "sweep": lambda: sweep(a),
     "epilogues": lambda: epilogues(a)}[a.phase]()
