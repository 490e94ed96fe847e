"""The flash FORWARD kernel alone, on the chip, at the five token cells'
shapes: the kernel as it stood before PR 41 (the mask and the -inf guards on
every visited block, the running max and normaliser as 1-D scratch) against
PR 41's three steps added in turn, beside the dQ kernel's time at the same
shape (one product more, the same exp) and the kernel paddle_tpu/parallel/
flash.py holds now.

    parent      mask + guards on every visited block, m / l 1-D [bq]
                (a band's forward went through `_for_block` already)
    edges       + step 1: the mask where the diagonal or the band's far edge
                crosses a block (`flash._for_block`), guards everywhere
    unguarded   + step 2: no -inf guard on an unmasked block
    lanes       + step 3, m / l as [bq, 128], every lane of a row the same
    column      + step 3, m / l as [bq, 1]
    transposed  + step 3, scores held [bk, bq] as dK/dV holds them, m / l
                lane-major rows [1, bq], the output accumulated transposed
    lanes_masked, lanes_guarded
                step 3 (lanes) without steps 1 and 2, and without step 2
    lanes_one_body
                step 3 (lanes) as ONE body: the mask on every visited
                block, the guards on m alone, none on p
    now         flash.flash_attention_fwd
    dq          flash.flash_attention_bwd's dQ kernel alone

The variants are this file's own copy of the forward's softmax step (the
package keeps one kernel and no switch); grids, BlockSpecs and index maps
are `flash`'s. RUNS runs a dispatch (a dispatch costs the host as long as a
small kernel takes). PERF.md (PR 41) holds what this printed.

    chiprun -- python tools/flash_forward_sweep.py
    JAX_PLATFORMS=cpu python tools/flash_forward_sweep.py --tiny   # wiring
    JAX_PLATFORMS=cpu python tools/flash_forward_sweep.py --compile-only
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
LANES = 128
# variant: (step 1: the mask on edge blocks alone, step 2: no guard on an
# unmasked block, the layout of m and l)
VARIANTS = {
    "parent": (False, False, "flat"),
    "edges": (True, False, "flat"),
    "unguarded": (True, True, "flat"),
    "lanes": (True, True, "lanes"),
    "column": (True, True, "column"),
    "transposed": (True, True, "transposed"),
    # step 3 without steps 1 and 2, and without step 2
    "lanes_masked": (False, False, "lanes"),
    "lanes_guarded": (True, False, "lanes"),
    # ONE body, no code beside the parent's: the mask on every visited
    # block, the guards on m alone; `p = where(isneginf(s), 0, p)` dropped
    # (redundant once m_safe is finite: exp(-inf) = 0)
    "lanes_one_body": (False, False, "lanes"),
}

# (name, rows, query heads, key/value heads, S, D, Dv, window): what one
# chip of each cell hands `causal_attention` a layer
SHAPES = (
    ("lfm2_32on8_d64", 1, 32, 8, 8192, 64, 64, None),
    ("smallthinker_full_7on1", 1, 7, 1, 8192, 128, 128, None),
    ("smallthinker_band4096_7on1", 1, 7, 1, 8192, 128, 128, 4096),
    ("laguna_full_6on1", 1, 6, 1, 8192, 128, 128, None),
    ("laguna_band512_8on1", 1, 8, 1, 8192, 128, 128, 512),
    ("olmoe_16on16_x2", 2, 16, 16, 4096, 128, 128, None),
    ("xing_4_d192_dv128", 1, 4, 4, 4096, 192, 128, None),
)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            variant, scale, block_q, block_k, nk, window, n_keys):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from paddle_tpu.parallel import flash

    edges, unguarded, layout = VARIANTS[variant]
    flat = layout == "flat"
    qi = pl.program_id(1)
    step = ki = pl.program_id(2)
    if window is not None:
        ki = flash._first_key_block(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def keep_of(shape, q_axis):
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        q_axis)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        1 - q_axis)
        keep = q_pos >= k_pos
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
        return keep

    def wide(x, n):
        """A row statistic in its layout -> [rows, n]."""
        if layout == "flat":
            return x[:, None]
        if layout != "lanes":
            return x
        # [rows, LANES], every lane of a row the same
        if n % LANES == 0:
            return jnp.tile(x, (1, n // LANES))
        return x[:, :n] if n < LANES else jnp.broadcast_to(
            x[:, :1], (x.shape[0], n))

    def _accumulate_rows(masked):
        """Scores [bq, bk]; m / l 1-D [bq] (the parent's), [bq, LANES] or
        [bq, 1] (what a row reduction gives)."""
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = flash._dot(q, k, flash._NT) * scale
        if masked:
            s = jnp.where(keep_of(s.shape, 0), s, -jnp.inf)
        guard = masked or not unguarded
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=not flat))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new) if guard \
            else m_new
        p = jnp.exp(s - wide(m_safe, s.shape[1]))
        # the parent kept this guard off a band's unmasked blocks only
        if (masked or (not unguarded and window is None)) \
                and variant != "lanes_one_body":
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.exp(m_prev - m_safe)
        if guard:
            corr = jnp.where(jnp.isneginf(m_prev), 0.0, corr)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1,
                                                 keepdims=not flat)
        acc_scr[...] = acc_scr[...] * wide(corr, acc_scr.shape[1]) \
            + flash._dot(p.astype(v.dtype), v, flash._NN)
        m_scr[...] = m_new

    def _accumulate_transposed(masked):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        st = flash._dot(k, q, flash._NT) * scale           # [bk, bq]
        if masked:
            st = jnp.where(keep_of(st.shape, 1), st, -jnp.inf)
        m_prev = m_scr[...]                                # [1, bq]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new) if masked \
            else m_new
        pt = jnp.exp(st - m_safe)
        if masked:
            pt = jnp.where(jnp.isneginf(st), 0.0, pt)
        corr = jnp.exp(m_prev - m_safe)
        if masked:
            corr = jnp.where(jnp.isneginf(m_prev), 0.0, corr)
        l_scr[...] = corr * l_scr[...] + jnp.sum(pt, axis=0, keepdims=True)
        # [Dv, bq] = v.T @ pt
        acc_scr[...] = acc_scr[...] * corr + flash._dot(
            v, pt.astype(v.dtype), (((0,), (0,)), ((), ())))
        m_scr[...] = m_new

    accumulate = _accumulate_transposed if layout == "transposed" \
        else _accumulate_rows
    inside = True if window is None else ki < n_keys
    if variant == "lanes_one_body":
        visited, _ = flash._crossed(qi, ki, block_q, block_k, None, window)
        pl.when(inside & visited)(lambda: accumulate(True))
    elif edges or window is not None:
        # the parent's band went through `_for_block` already
        flash._for_block(accumulate, qi, ki, block_q, block_k, True, None,
                         window, inside)
    else:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(
            lambda: accumulate(True))

    @pl.when(step == nk - 1)
    def _finish():
        m, l = m_scr[...], jnp.maximum(l_scr[...], 1e-30)
        lse = jnp.where(jnp.isneginf(m), -jnp.inf, m + jnp.log(l))
        if layout == "transposed":
            o_ref[0] = (acc_scr[...] / l).T.astype(o_ref.dtype)
            lse_ref[0] = jnp.broadcast_to(lse, (8, lse.shape[1]))
            return
        o_ref[0] = (acc_scr[...] / wide(l, acc_scr.shape[1])).astype(
            o_ref.dtype)
        if layout == "lanes":
            lse_ref[0] = lse.T[:8]
        else:
            lse = lse if flat else lse[:, 0]
            lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def forward(variant, q, k, v, window, block_q, block_k, interpret):
    """[B, H, S, D] (S a multiple of the blocks) -> (out, lse [B, H, S]):
    `flash._flash_fwd` with this file's kernel and its scratch."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.parallel import flash

    B, H, S, D = q.shape
    Dv = v.shape[-1]
    qf, kf, vf = (flash._folded(x) for x in (q, k, v))
    nq = n_keys = nk = S // block_q
    kv = flash._of_head(H // k.shape[1])
    _, k_of = flash._band(block_q, block_k, nq, nk, window is not None,
                          window)
    if window is not None:
        nk = flash._band_extents(block_q, block_k, nq, nk, window)[0]
    layout = VARIANTS[variant][2]
    stat = {"flat": (block_q,), "lanes": (block_q, LANES),
            "column": (block_q, 1), "transposed": (1, block_q)}[layout]
    acc = (Dv, block_q) if layout == "transposed" else (block_q, Dv)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, variant=variant,
                          scale=1.0 / float(D) ** 0.5, block_q=block_q,
                          block_k=block_k, nk=nk, window=window,
                          n_keys=n_keys),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j: (kv(b), k_of(i, j), 0)),
            pl.BlockSpec((1, block_k, Dv),
                         lambda b, i, j: (kv(b), k_of(i, j), 0))],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 8, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(stat, jnp.float32),
                        pltpu.VMEM(stat, jnp.float32),
                        pltpu.VMEM(acc, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd_" + variant,
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dv), lse[:, 0, :].reshape(B, H, S)


def pairs(B, H, S, window):
    """Score pairs the layer needs: the triangle's, or the band's."""
    if window is None or window >= S:
        return B * H * S * S / 2
    return B * H * (S * window - window * window / 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=4101)
    ap.add_argument("--tiny", action="store_true",
                    help="rows of 256, blocks of 64 and 32, interpreted: "
                         "the wiring, and no time")
    ap.add_argument("--compile-only", action="store_true",
                    help="compile each variant for a described v5e")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import flash

    bf = jnp.bfloat16
    rs = np.random.default_rng(args.seed)
    on_chip = jax.devices()[0].platform == "tpu"
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        flash.pallas_interpret = lambda: False
    rows = []
    for name, B, H, Hkv, S, D, Dv, window in SHAPES:
        if args.only and name not in args.only:
            continue
        blocks = lm_ops.flash_blocks(window)
        bq, bk = blocks["block_q"], blocks["block_k"]
        if args.tiny:
            S, H, Hkv = 256, max(H // Hkv, 1) * 2, 2
            bq = bk = 64 if window is None or window > 512 else 32
            window = window and (128 if window > 512 else 32)
        shapes = ((B, H, S, D), (B, Hkv, S, D), (B, Hkv, S, Dv),
                  (B, H, S, Dv))
        row = {"shape": name, "B": B, "heads": [H, Hkv], "S": S,
               "D": [D, Dv], "window": window, "blocks": [bq, bk],
               "visited": flash.blocks_visited(S, S, bq, bk, window),
               "pairs": pairs(B, H, S, window), "ms": {}, "ps_a_pair": {}}
        interpret = not (on_chip or args.compile_only)
        forms = {var: functools.partial(
            lambda q, k, v, do, o, lse, var: forward(
                var, q, k, v, window, bq, bk, interpret), var=var)
            for var in VARIANTS}
        forms["now"] = lambda q, k, v, do, o, lse: flash.flash_attention_fwd(
            q, k, v, causal=True, window=window, block_q=bq, block_k=bk)
        # XLA drops the dK/dV call, whose results nothing reads
        forms["dq"] = lambda q, k, v, do, o, lse: flash.flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, window=window,
            **lm_ops.flash_blocks(window, backward=True))[0]
        if args.tiny:
            forms["dq"] = lambda q, k, v, do, o, lse: \
                flash.flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                                          window=window, block_q=bq,
                                          block_k=bk)[0]
        if args.compile_only:
            sds = [jax.ShapeDtypeStruct(s, bf, sharding=chip) for s in shapes]
            sds += [sds[3], jax.ShapeDtypeStruct(shapes[0][:3], jnp.float32,
                                                 sharding=chip)]
            for var, fn in forms.items():
                try:
                    jax.jit(fn).lower(*sds).compile()
                    row["ms"][var] = "compiles"
                except Exception as e:        # what Mosaic refuses
                    row["ms"][var] = "refused: " + str(e)[-400:]
            print(json.dumps(row), flush=True)
            continue
        q, k, v, do = (jnp.asarray(rs.standard_normal(s), bf) for s in shapes)
        want = jax.jit(forms["parent"])(q, k, v, do, None, None)
        operands = (q, k, v, do) + tuple(want)
        for var, fn in forms.items():

            def many(q, *rest, fn=fn):
                # RUNS runs a dispatch; each writes one number of its
                # result into q, so none is hoisted, merged or cut down
                def body(_, carry):
                    q_c, _ = carry
                    got = fn(q_c, *rest)
                    tip = jax.tree_util.tree_leaves(got)[0].reshape(-1)[:1]
                    return q_c.at[0, 0, 0, :1].set(tip.astype(q_c.dtype)), got

                return lax.fori_loop(0, RUNS, body, (q, fn(q, *rest)))[1]

            try:
                if var not in ("parent", "dq"):
                    # against the parent's Out (bf16) and Lse (float32)
                    got = jax.jit(fn)(*operands)
                    row.setdefault("max_abs_against_parent", {})[var] = [
                        float(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32)).max())
                        for a, b in zip(got, want)]
                    assert not any(bool(jnp.isnan(a.astype(jnp.float32))
                                        .any()) for a in got), var
                if args.tiny:
                    jax.block_until_ready(jax.jit(fn)(*operands))
                    continue
                run = jax.jit(many)
                jax.block_until_ready(run(*operands))
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    res = run(*operands)
                jax.block_until_ready(res)
                # the loop's RUNS and the run that seeds its carry
                row["ms"][var] = (time.perf_counter() - t0) / (
                    args.calls * (RUNS + 1)) * 1e3
                row["ps_a_pair"][var] = row["ms"][var] * 1e9 / row["pairs"]
            except Exception as e:            # what Mosaic refuses
                row["ms"][var] = "refused: " + str(e)[-300:]
        print(json.dumps(row), flush=True)
        rows.append(row)
    if on_chip:
        out = os.path.join(REPO, "chiprun_out", "pr41")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "flash_forward_sweep.json"), "w") as f:
            json.dump({"device": str(jax.devices()[0].device_kind),
                       "runs_a_dispatch": RUNS, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
