"""The MASKED flash backward alone, on the chip, at the `keye_vl_2_0_30b_a3b`
cell's shape ([1, 32 on 4, 8192, 128] bf16 under an exact top-2048 mask, as
`tools/index_loss_sweep.py` draws one): the two masked kernels a caller got
until PR 53 (`flash._flash_bwd`: dK/dV with four products a visited pair,
dQ with three, each forming the score block, its exponential, dO V^T and
the widened mask again) against ONE kernel that visits each score block
once and feeds all three gradients from it (five products), in the forms
the reading could not decide between:

    kq_tn       scores held [bk, bq] as the dK/dV kernel holds them (the
                caller's transposed mask, lse | delta as lane-major rows):
                dV and dK plain products, dQ[i] += dst^T k given to Mosaic
                whole (a product that contracts the first axis of both)
    kq_xpose    the same, dst transposed in bf16 first, then a plain product
    kq_kt       the same, dQ accumulated TRANSPOSED, [D, Sq] += k^T dst (a
                plain product of 128 rows; k^T an operand formed outside)
    qk_tn       scores held [bq, bk] as the dQ kernel holds them (the mask
                as it is, no transposed copy; lse | delta as columns): dQ a
                plain product, dV += p^T dO and dK += ds^T q given whole
    qk_xpose    the same, p and ds transposed in bf16 first
    qk_rows     qk_tn with lse | delta as the lane-major rows [2, bq] the
                dK/dV kernel reads, relaid into columns inside the kernel
                (a [BH, Sq, 2] float32 operand lies in HBM as 128 lanes a
                row: 134 MB a layer written and read for 2 MB of numbers)
    kq_maskt    kq_tn reading the mask as it is, its block transposed
                inside the kernel (widened to float32 first; `_i32`,
                `_bf16`: to those)
    <form>_<bq>x<bk>   other blocks than 1024 x 1024

All with dQ [Sq, D] float32 of the query head and dK, dV [Sk, D] float32 of
the key/value head resident in VMEM (grid (key/value head, head of the
group, key block, query block)). The variants are this file's own copies
of the visit (the package keeps one kernel and no switch); `now` is what
`flash.flash_attention_bwd` dispatches to. Each is held to the two kernels'
gradients. RUNS runs a dispatch; the timed call is the whole backward as
the grad op runs it (delta, the stack of lse | delta and, where a form
wants one, the mask's transposed copy among it). PERF.md (PR 53) holds
what this printed.

    chiprun -- python tools/flash_backward_sweep.py
    JAX_PLATFORMS=cpu python tools/flash_backward_sweep.py --tiny
    JAX_PLATFORMS=cpu python tools/flash_backward_sweep.py --compile-only
"""

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RUNS = 8
# variant: (orientation of the score block, how the transposed products
# are given, (block_q, block_k))
VARIANTS = {
    "kq_tn": ("kq", "tn", (1024, 1024)),
    "kq_xpose": ("kq", "xpose", (1024, 1024)),
    "kq_kt": ("kq", "kt", (1024, 1024)),
    "qk_tn": ("qk", "tn", (1024, 1024)),
    "qk_xpose": ("qk", "xpose", (1024, 1024)),
    "qk_rows": ("qk", "rows", (1024, 1024)),
    "kq_maskt": ("kq", "maskt", (1024, 1024)),
    "kq_maskt_i32": ("kq", "maskt_i32", (1024, 1024)),
    "kq_maskt_bf16": ("kq", "maskt_bf16", (1024, 1024)),
    "kq_tn_512x1024": ("kq", "tn", (512, 1024)),
    "kq_tn_1024x512": ("kq", "tn", (1024, 512)),
    "kq_tn_512x512": ("kq", "tn", (512, 512)),
    "qk_tn_512x1024": ("qk", "tn", (512, 1024)),
    "qk_tn_1024x512": ("qk", "tn", (1024, 512)),
}
# products a visited pair and head
PRODUCTS = {"parent_dq": 3, "parent_dkv": 4, "parent": 7}


def _kernel(q_ref, k_ref, v_ref, do_ref, ld_ref, mask_ref, *rest, orient,
            form, scale, block_q, block_k, nq, nk, group):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from paddle_tpu.parallel import flash

    if form == "kt":
        kt_ref, *rest = rest
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    g, ki, qi = (pl.program_id(a) for a in (1, 2, 3))
    first = (ki == 0) & (qi == 0)
    last = (ki == nk - 1) & (qi == nq - 1)

    @pl.when(first)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(first & (g == 0))
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _accumulate(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        low = q.dtype
        rows_q = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        rows_k = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        if orient == "kq":
            st = flash._dot(k, q, flash._NT) * scale           # [bk, bq]
            if form.startswith("maskt"):
                wide = {"maskt": jnp.float32, "maskt_i32": jnp.int32,
                        "maskt_bf16": jnp.bfloat16}[form]
                pt = jnp.where(mask_ref[0].astype(wide).T != 0,
                               jnp.exp(st - ld_ref[0, 0:1, :]), 0.0)
            else:
                pt = flash._weights(st, ld_ref[0, 0:1, :], True, 0, 0, 1,
                                    True, None, None, [mask_ref])
            dv_scr[rows_k, :] += flash._dot(pt.astype(low), do, flash._NN)
            dpt = flash._dot(v, do, flash._NT)
            dst = (pt * (dpt - ld_ref[0, 1:2, :])).astype(low)
            dk_scr[rows_k, :] += flash._dot(dst, q, flash._NN)
            if form == "tn" or form.startswith("maskt"):
                dq_scr[rows_q, :] += flash._dot(dst, k, flash._TN)
            elif form == "xpose":
                dq_scr[rows_q, :] += flash._dot(dst.T, k, flash._NN)
            else:
                dq_scr[:, rows_q] += flash._dot(kt_ref[0], dst, flash._NN)
            return
        s = flash._dot(q, k, flash._NT) * scale                # [bq, bk]
        if form == "rows":
            # [1, bq] -> every sublane the same -> [bq, _LANES], every
            # lane of a row the same -> across the block's keys
            lse, delta = (flash._across(jnp.broadcast_to(
                ld_ref[0, r:r + 1, :], (flash._LANES, block_q)).T, block_k)
                for r in (0, 1))
        else:
            lse, delta = ld_ref[0, :, 0:1], ld_ref[0, :, 1:2]
        p = flash._weights(s, lse, True, 0, 0, 0, True, None, None,
                           [mask_ref])
        dp = flash._dot(do, v, flash._NT)
        ds = (p * (dp - delta)).astype(low)
        p = p.astype(low)
        dq_scr[rows_q, :] += flash._dot(ds, k, flash._NN)
        if form in ("tn", "rows"):
            dv_scr[rows_k, :] += flash._dot(p, do, flash._TN)
            dk_scr[rows_k, :] += flash._dot(ds, q, flash._TN)
        else:
            dv_scr[rows_k, :] += flash._dot(p.T, do, flash._NN)
            dk_scr[rows_k, :] += flash._dot(ds.T, q, flash._NN)

    flash._for_chosen(_accumulate, qi, ki, block_q, block_k)

    @pl.when(last)
    def _finish_dq():
        dq = dq_scr[...].T if form == "kt" else dq_scr[...]
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    @pl.when(last & (g == group - 1))
    def _finish_dkv():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def fused(variant, q, k, v, do, lse, delta, mask, scale, interpret):
    """`flash._flash_bwd`'s arguments (folded [BH, S, D], S whole blocks,
    one batch entry) -> dq, dk, dv through ONE kernel of the variant."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.parallel import flash

    orient, form, (block_q, block_k) = VARIANTS[variant]
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    nq, nk = Sq // block_q, Sk // block_k
    group = BH // k.shape[0]
    q_of, _ = flash._band(block_q, block_k, nq, nk, True, None)

    def head(b, g, j, i):
        return b * group + g

    ld = jnp.stack([lse, delta], axis=1)                   # [BH, 2, Sq]
    ld_spec = pl.BlockSpec(
        (1, 2, block_q), lambda b, g, j, i: (head(b, g, j, i), 0,
                                             q_of(j, i)))
    mask_spec = pl.BlockSpec((1, block_q, block_k),
                             lambda b, g, j, i: (0, q_of(j, i), j))
    if orient == "kq" and not form.startswith("maskt"):
        mask = jnp.swapaxes(mask, 1, 2)
        mask_spec = pl.BlockSpec((1, block_k, block_q),
                                 lambda b, g, j, i: (0, j, q_of(j, i)))
    if orient == "qk" and form != "rows":
        ld = jnp.swapaxes(ld, 1, 2)
        ld_spec = pl.BlockSpec(
            (1, block_q, 2), lambda b, g, j, i: (head(b, g, j, i),
                                                 q_of(j, i), 0))
    of_q = lambda b, g, j, i: (head(b, g, j, i), q_of(j, i), 0)  # noqa: E731
    of_k = lambda b, g, j, i: (b, j, 0)                          # noqa: E731
    in_specs = [pl.BlockSpec((1, block_q, D), of_q),
                pl.BlockSpec((1, block_k, D), of_k),
                pl.BlockSpec((1, block_k, Dv), of_k),
                pl.BlockSpec((1, block_q, Dv), of_q), ld_spec, mask_spec]
    operands = [q, k, v, do, ld, mask]
    dq_acc = (Sq, D)
    if form == "kt":
        in_specs.append(pl.BlockSpec((1, D, block_k),
                                     lambda b, g, j, i: (b, 0, j)))
        operands.append(jnp.swapaxes(k, 1, 2))
        dq_acc = (D, Sq)
    return pl.pallas_call(
        functools.partial(_kernel, orient=orient, form=form, scale=scale,
                          block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                          group=group),
        grid=(k.shape[0], group, nk, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, Sq, D),
                         lambda b, g, j, i: (head(b, g, j, i), 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, g, j, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, Dv), lambda b, g, j, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM(dq_acc, jnp.float32),
                        pltpu.VMEM((Sk, D), jnp.float32),
                        pltpu.VMEM((Sk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=flash._BWD_VMEM_LIMIT),
        interpret=interpret,
        name="sparse_flash_bwd_" + variant,
    )(*operands)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=5301)
    ap.add_argument("--tiny", action="store_true",
                    help="a row of 256, blocks of 64 and 128, interpreted: "
                         "the wiring, and no time")
    ap.add_argument("--compile-only", action="store_true",
                    help="compile each variant for a described v5e")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from chipbench import costs
    from paddle_tpu.parallel import flash, sparse_index

    S, H, Hkv, D, Hi, Di, topk = (256, 4, 2, 128, 4, 64, 64) if args.tiny \
        else (8192, 32, 4, 128, 16, 64, 2048)
    if args.tiny:
        for name, (orient, form, blocks) in list(VARIANTS.items()):
            VARIANTS[name] = (orient, form, tuple(b // 8 for b in blocks))
    low = jnp.bfloat16
    on_chip = jax.devices()[0].platform == "tpu"
    interpret = not (on_chip or args.compile_only)
    scale = D ** -0.5
    peak = costs.peaks_for("TPU v5 lite")["bf16_flops_per_s"]

    def parent(q, k, v, do, lse, delta, mask, blocks=None):
        bq, bk = blocks or VARIANTS["kq_tn"][2]
        return flash._flash_bwd(q, k, v, do, lse, delta, scale, True, bq, bk,
                                None, None, mask)

    forms = {
        "parent": parent,
        "parent_dq": lambda *a: parent(*a)[:1],
        "parent_dkv": lambda *a: parent(*a)[1:],
        "now": lambda *a: flash._fused_bwd(
            *a[:-1], scale, *VARIANTS["kq_tn"][2], a[-1])}
    for var in VARIANTS:
        forms[var] = functools.partial(
            lambda *a, var: fused(var, *a, scale, interpret), var=var)
    if args.only:
        forms = {n: f for n, f in forms.items()
                 if n in args.only or n == "parent"}

    def whole(fn):
        """The backward as the grad op runs it: delta formed outside."""
        def run(q, k, v, o, lse, do, mask):
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            -1)
            return fn(q, k, v, do, lse, delta, mask)
        return run

    shapes = ((H, S, D), (Hkv, S, D), (Hkv, S, D), (H, S, D))
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        flash.pallas_interpret = lambda: False

        def sds(shape, dt=low):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=chip)

        operands = [sds(s) for s in shapes[:3]] + [
            sds(shapes[0]), sds((H, S), "float32"), sds(shapes[0]),
            sds((1, S, S), "int8")]
        for name, fn in forms.items():
            try:
                compiled = jax.jit(whole(fn)).lower(*operands).compile()
                print(json.dumps({
                    "variant": name, "compiles": True, "temp_bytes":
                    compiled.memory_analysis().temp_size_in_bytes}),
                    flush=True)
            except Exception as e:            # what Mosaic refuses
                print(json.dumps({"variant": name,
                                  "refused": str(e)[-600:]}), flush=True)
        return

    rs = np.random.default_rng(args.seed)

    def draw(*shape, std=1.0):
        return jnp.asarray(rs.standard_normal(shape) * std, low)

    q, k, v, do = (draw(*s) for s in shapes)
    q_i, k_i = draw(S, Hi, Di), draw(S, Di)
    w = draw(S, Hi, std=(Hi * Di) ** -0.5)
    mask = jax.jit(lambda *a: sparse_index.select(*a, topk)[0])(
        q_i, k_i, w)[None]
    if on_chip:
        o, lse = jax.jit(lambda q, k, v, m: flash.flash_attention_fwd(
            q[None], k[None], v[None], causal=True, scale=scale, mask=m,
            block_q=1024, block_k=1024))(q, k, v, mask)
    else:
        from paddle_tpu.ops.lm_ops import _plain_sparse_attention

        o, lse = _plain_sparse_attention(q[None], k[None], v[None], mask,
                                         scale)
    operands = (q, k, v, o[0], lse[0], do, mask)
    bq, bk = VARIANTS["kq_tn"][2]
    pairs = H * flash.blocks_visited(S, S, bq, bk) * bq * bk

    def timed(fn):
        def many(*xs):
            def body(_, carry):
                xs, _ = carry
                out = fn(*xs)
                # each run writes one number of its result into EVERY
                # operand, so nothing (the mask's transposed copy, delta,
                # the stack of lse | delta) is formed once for all runs
                tip = jax.tree_util.tree_leaves(out)[0].reshape(-1)[0] * 0
                at = lambda x: (0,) * x.ndim                     # noqa: E731
                return tuple(x.at[at(x)].set(
                    x[at(x)] + tip.astype(x.dtype)) for x in xs), out

            return lax.fori_loop(0, RUNS, body, (xs, fn(*xs)))[1]

        run = jax.jit(many)
        jax.block_until_ready(run(*operands))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = run(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (args.calls * (RUNS + 1)) * 1e3

    def rms(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.sqrt(np.mean((a - b) ** 2))
                     / max(np.sqrt(np.mean(b ** 2)), 1e-30))

    want = jax.jit(whole(forms["parent"]))(*operands)
    rows = []
    for name, fn in forms.items():
        row = {"variant": name, "products": PRODUCTS.get(name, 5)}
        try:
            got = jax.jit(whole(fn))(*operands)
            if name not in PRODUCTS:
                row["rms_against_parent"] = [rms(a, b)
                                             for a, b in zip(got, want)]
                assert max(row["rms_against_parent"]) < 2e-2, row
            if not args.tiny:
                row["ms"] = timed(whole(fn))
                row["ps_a_visited_pair"] = row["ms"] * 1e9 / pairs
                # D = Dv: a product of one pair and head is 2 D operations
                row["mxu_peak_share_pct"] = 100 * row["products"] * 2 * D \
                    * pairs / peak / (row["ms"] * 1e-3)
        except AssertionError:
            raise
        except Exception as e:                # what Mosaic refuses
            row["refused"] = str(e)[-400:]
        print(json.dumps(row), flush=True)
        rows.append(row)
    if on_chip:
        out = os.path.join(REPO, "chiprun_out", "pr53")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "flash_backward_sweep.json"), "w") as f:
            json.dump({"device": str(jax.devices()[0].device_kind),
                       "runs_a_dispatch": RUNS, "visited_pairs": pairs,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
