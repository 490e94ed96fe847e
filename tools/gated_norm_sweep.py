"""`gated_rms_norm` and its grad alone, on the chip, at the
`qwen3_next_80b_a3b` cell's shape (one row of 8192 tokens; X and the gate
[8192, 32 heads of 128], a float32 scale [128]): forward and backward of

    three_ops   what the program held before the op: `rms_norm`, `swish`,
                `elementwise_mul` with their generic vjps (`jax.vjp`, which
                forms the forward again as the registry's grad ops do)
    plain       the op's plain form (`lm_ops.gated_rms_norm` and its
                hand-written grad), XLA's fusions
    kernels     the two Pallas kernels of `parallel/gated_norm.py`, at each
                of `--blocks` tokens a block (the default marked)

ms a layer, bf16 and float32 operands, each beside the least time of the
bytes (read X, Z, write Y; read X, Z, d Y, write d X, d Z at the HBM's
peak), and the kernels' results against the plain form's: float32 operands
to round-off, bf16 operands in bf16 ulps of the plain form's (the largest
distance and the share of numbers that differ at all). X and d Y arrive [T,
H D] and are split into heads inside the timed function, as the model's
`reshape` ops have it; the kernels' X arrives as the delta rule's output
product leaves it in the step, [T / block, H, block, D], and is turned
token-major inside the timed function, which XLA cancels against the
kernels' own transposition (without that a relayout copy is timed with
them). Times are the device's own: 8 runs a profiler trace
(`tools/ssd_scan_sweep.py: device_ops`). PERF.md (PR 57) holds what this
printed.

    chiprun -- python tools/gated_norm_sweep.py
    python tools/gated_norm_sweep.py --tiny     # the wiring, CPU
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.dirname(os.path.abspath(__file__))]

EPS = 1e-6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=5701)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--blocks", nargs="+", type=int,
                    default=[32, 64, 128, 256, 512])
    ap.add_argument("--dtypes", nargs="+", default=["bfloat16", "float32"])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import costs
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import gated_norm
    from ssd_scan_sweep import device_ops

    T, H, D = (64, 2, 128) if args.tiny else (8192, 32, 128)
    rs = np.random.default_rng(args.seed)
    on_tpu = jax.devices()[0].platform == "tpu"
    peak = costs.peaks_for(jax.devices()[0].device_kind if on_tpu
                           else "TPU v5 lite")["hbm_bytes_per_s"]
    f32 = [jnp.asarray(rs.standard_normal((T, H * D)) * s, jnp.float32)
           for s in (3.0, 2.0, 1.0)]
    w = jnp.asarray(1.0 + 0.5 * rs.standard_normal(D), jnp.float32)

    def heads(a):
        return a.reshape(T, H, D)

    def three_ops(x, z, w):
        y = lm_ops.rms_norm_op(None, {"X": [heads(x)], "Scale": [w]},
                               {"epsilon": EPS})["Y"][0].reshape(T, H * D)
        return y * (z * jax.nn.sigmoid(z))

    def form(fwd, bwd, heads=heads):
        return (lambda x, z, w: fwd(heads(x), z, w, EPS).reshape(T, H * D),
                lambda x, z, w, g: _flat(bwd(heads(x), z, w,
                                             g.reshape(T, H, D), EPS)))

    def _flat(grads):
        return grads[0].reshape(T, H * D), grads[1], grads[2]

    def head_major(x, b):       # as the delta rule's output product lies
        return jnp.moveaxis(x.reshape(T // b, b, H, D), 2, 1)

    forms = {
        "plain": form(lm_ops.gated_rms_norm, lm_ops.gated_rms_norm_grad),
        "three_ops": (three_ops, lambda x, z, w, g: jax.vjp(
            three_ops, x, z, w)[1](g))}
    for b in args.blocks:
        forms["kernels_%d" % b] = form(
            lambda *a, b=b: gated_norm.gated_norm_fwd(*a, block=b),
            lambda *a, b=b: gated_norm.gated_norm_bwd(*a, block=b),
            heads=lambda o: jnp.moveaxis(o, 2, 1).reshape(T, H, D))

    def ulps(got, want):
        """(the largest distance in bf16 ulps of `want`, the share of
        numbers that differ)."""
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
        apart = np.abs(got - want)
        return float(np.max(apart / ulp)), float(np.mean(apart > 0))

    def rel(got, want):
        got, want = (np.asarray(a, np.float64) for a in (got, want))
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    for dtype in args.dtypes:
        x, z, g = (a.astype(dtype) for a in f32)
        size = T * H * D * jnp.dtype(dtype).itemsize
        least = {"forward": 1e3 * 3 * size / peak,
                 "backward": 1e3 * 5 * size / peak}
        default = "kernels_%d" % gated_norm._block(
            T, H * D, jnp.dtype(dtype).itemsize)
        for name, (fwd, bwd) in forms.items():
            found = {"form": name, "operands": dtype,
                     "default_block": name == default}
            xs = x if not name.startswith("kernels") else head_major(
                x, int(name.split("_")[1]))
            try:
                y = jax.jit(fwd)(xs, z, w)
                grads = jax.jit(bwd)(xs, z, w, g)
                found["forward_ms"] = device_ops(jax, fwd, (xs, z, w))[0]
                found["backward_ms"] = device_ops(jax, bwd, (xs, z, w, g))[0]
            except Exception as e:      # Mosaic refuses (VMEM)
                found["refused"] = str(e)[-300:]
                print(json.dumps(found), flush=True)
                continue
            if found["forward_ms"] is not None:
                found["both_ms"] = found["forward_ms"] + found["backward_ms"]
                found["share_of_hbm_peak_pct"] = \
                    100 * sum(least.values()) / found["both_ms"]
            found.update(least_forward_ms=least["forward"],
                         least_backward_ms=least["backward"])
            if name == "plain":
                want = (y,) + tuple(grads)
            else:
                parts = zip(("y", "d_x", "d_z"), (y,) + tuple(grads), want)
                if dtype == "float32":
                    found.update({k + "_rel": rel(a, b)
                                  for k, a, b in parts})
                else:
                    for k, a, b in parts:
                        found[k + "_ulps"], found[k + "_differ"] = ulps(a, b)
                found["d_w_rel"] = rel(grads[2], want[3])
            print(json.dumps(found), flush=True)


if __name__ == "__main__":
    main()
