"""`ssd_scan` and its grad alone, on the chip, at the
`nemotron_3_nano_30b_a3b` cell's shape (one row of 4096 tokens; x [4096, 64
heads of 64], B, C [4096, 8 groups of 128] bf16; dt [4096, 64], A_log,
dt_bias, D float32; chunks of 128): forward and backward of the plain
chunked form (`ssd.ssd_fwd` / `ssd_bwd`) and of THE KERNEL PATH
(`ssd.kernels_fwd` / `kernels_bwd`: the in-chunk work in the Pallas kernels
of `parallel/ssd_parts.py`), each kernel alone and the two scans over the
chunks the path leaves to XLA, ms a layer, each beside the least time of
the WORK (`chipbench/costs_ssd_share`), and every output of the kernel path
against the plain form's: bf16 operands as the step runs them, float32
operands under full matmul precision as the comparison's probe does
(`compare_lm_ssd_share.scan_in_float32`). `--plants`: both paths under each
plant of `chipbench/lower_precision_lm_ssd_share` (`state_bf16`,
`decays_bf16`, `state_one_pass`), how far y and d x move from the stated
path's. Times are the device's own: 8 runs a profiler trace. `--profile`:
each form's passes operation by operation. PERF.md (PR 55) holds what this
printed.

    chiprun -- python tools/ssd_scan_sweep.py --plants
    python tools/ssd_scan_sweep.py --tiny --plants     # the wiring, CPU
"""

import argparse
import json
import os
import sys
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RUNS = 8
NAMES = ("x", "B", "C", "dt", "A_log", "dt_bias", "D")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=5501)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--plants", action="store_true")
    ap.add_argument("--no-pieces", action="store_true")
    ap.add_argument("--chunks-a-step", nargs="+", type=int, default=[],
                    help="the kernel path and its kernels alone with this "
                    "many chunks a grid step (`ssd_parts.CHUNKS_A_STEP`)")
    ap.add_argument("--profile", nargs="*", default=None,
                    metavar="FORM", help="trace these forms' forward and "
                    "backward (default: both) and print each one's device "
                    "operations by self time")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import costs, costs_ssd_share
    from chipbench.lower_precision_lm_ssd_share import _planted
    from paddle_tpu.parallel import ssd, ssd_parts

    T, H, P, G, N, Q = (256, 4, 64, 2, 128, 128) if args.tiny else \
        (4096, 64, 64, 8, 128, 128)
    shape = dict(seq_len=T, heads=H, head_dim=P, groups=G, state=N, chunk=Q)
    rs = np.random.default_rng(args.seed)

    def draw(*dims, dtype=jnp.float32, std=1.0):
        return jnp.asarray(rs.standard_normal(dims) * std, dtype)

    dt0 = np.exp(rs.uniform(np.log(1e-3), np.log(0.1), H))
    f32_ins = (draw(T, H * P), draw(T, G * N), draw(T, G * N), draw(T, H),
               jnp.asarray(np.log(rs.uniform(1, 16, H)), jnp.float32),
               jnp.asarray(dt0 + np.log(-np.expm1(-dt0)), jnp.float32),
               draw(H) + 1.0)
    f32_dy = draw(T, H * P)

    def operands(low):
        return tuple(a.astype(low) for a in f32_ins[:3]) + f32_ins[3:], \
            f32_dy.astype(low)

    on_tpu = jax.devices()[0].platform == "tpu"
    peaks = costs.peaks_for(jax.devices()[0].device_kind if on_tpu
                            else "TPU v5 lite")
    cfg = dict(rows_per_step=1, sequence_length=T, chunk_size=Q,
               mamba_num_heads=H, mamba_head_dim=P, n_groups=G,
               ssm_state_size=N, conv_kernel=4)
    least = {"forward": 1e3 * costs_ssd_share.scan_least_seconds(
        cfg, False, peaks)}
    least["backward"] = 1e3 * costs_ssd_share.scan_least_seconds(
        cfg, True, peaks) - least["forward"]

    def timed(fn, *xs):
        return device_ops(jax, fn, xs)[0]

    def rms(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.sqrt(np.mean((a - b) ** 2))
                     / max(np.sqrt(np.mean(b ** 2)), 1e-30))

    def line(**kv):
        print(json.dumps(kv), flush=True)

    def both(fwd, bwd, ins, dy):
        """(y, states, final state), the seven gradients."""
        out = jax.jit(lambda *a: fwd(*a, **shape))(*ins)
        return out, jax.jit(lambda *a: bwd(*a, **shape))(*ins, out[1], dy)

    def against(got, want):
        (y, starts, last), grads = got
        (y0, starts0, last0), grads0 = want
        found = {"y_rms": rms(y, y0), "states_rms": rms(starts, starts0),
                 "final_state_rms": rms(last, last0)}
        found.update({"d_%s_rms" % n: rms(g, g0)
                      for n, g, g0 in zip(NAMES, grads, grads0)})
        return found

    forms = {"plain": (ssd.ssd_fwd, ssd.ssd_bwd),
             "kernels": (ssd.kernels_fwd, ssd.kernels_bwd)}
    assert ssd.takes(1, dtype=jnp.bfloat16, **shape)
    # ---- the step's operands: times and outputs
    ins, dy = operands(jnp.bfloat16)
    found = {name: both(*fns, ins, dy) for name, fns in forms.items()}
    for name, (fwd, bwd) in forms.items():
        ms_f = timed(lambda *a, fwd=fwd: fwd(*a, **shape), *ins)
        ms_b = timed(lambda *a, bwd=bwd: bwd(*a, **shape), *ins,
                     found[name][0][1], dy)
        both_ms = None if ms_f is None else ms_f + ms_b
        line(form=name, operands="bfloat16", forward_ms=ms_f,
             backward_ms=ms_b, both_ms=both_ms,
             least_forward_ms=least["forward"],
             least_backward_ms=least["backward"],
             scan_roofline_share_of_this_alone_pct=both_ms
             and 100 * sum(least.values()) / both_ms,
             **(against(found[name], found["plain"])
                if name != "plain" else {}))
    # ---- float32 operands at full matmul precision: the probe's view
    ins32, dy32 = operands(jnp.float32)
    with jax.default_matmul_precision("highest"):
        found32 = {name: both(*fns, ins32, dy32)
                   for name, fns in forms.items()}
    line(form="kernels", operands="float32_highest",
         **against(found32["kernels"], found32["plain"]))
    for name in (forms if args.profile == [] else args.profile or ()):
        profile(jax, name, *forms[name], shape, ins, dy,
                found[name][0][1])
    x, b, c, dt, a_log, dt_bias, d = ins
    starts = found["kernels"][0][1]
    _, delta, a, g = jax.jit(lambda *a: ssd._by_head(
        *a, 1, T, H, G, Q))(dt, a_log, dt_bias)
    kernels = {
        ssd_parts.KERNELS[0]: (ssd_parts.chunk_states, (x, b, a, delta)),
        ssd_parts.KERNELS[0] + "_transposed": (ssd_parts.chunk_states,
                                               (dy, c, a)),
        ssd_parts.KERNELS[1]: (ssd_parts.chunk_outputs,
                               (x, b, c, delta, a, d, starts)),
        ssd_parts.KERNELS[2]: (ssd_parts.chunk_grads,
                               (x, dy, b, c, delta, a, d, starts, starts))}
    for n in args.chunks_a_step:
        with mock.patch.object(ssd_parts, "CHUNKS_A_STEP", n):
            fwd, bwd = forms["kernels"]
            found_n = {"chunks_a_step": n}
            try:
                found_n.update(
                    forward_ms=timed(lambda *a: fwd(*a, **shape), *ins),
                    backward_ms=timed(lambda *a: bwd(*a, **shape), *ins,
                                      starts, dy))
                found_n.update({k: timed(
                    lambda *a, fn=fn: fn(*a, **shape), *xs)
                    for k, (fn, xs) in kernels.items()})
            except Exception as e:      # Mosaic refuses (VMEM)
                found_n["refused"] = str(e)[-300:]
            line(**found_n)
    if not args.no_pieces:
        # ---- the kernel path piece by piece
        line(piece="by_head_and_steps", ms=timed(
            lambda *a: ssd._by_head(*a, 1, T, H, G, Q), dt, a_log, dt_bias))
        for k, (fn, xs) in kernels.items():
            line(piece=k, ms=timed(lambda *a, fn=fn: fn(*a, **shape), *xs))
        line(piece="scan_over_chunks", ms=timed(
            lambda own, g: ssd._over_chunks(g, own), starts, g))
        line(piece="scan_over_chunks_reverse", ms=timed(
            lambda own, g: ssd._over_chunks(g, own, True), starts, g))
    if args.plants:
        # ---- every plant of the study must reach both paths alike
        for plant in ("state_bf16", "decays_bf16", "state_one_pass"):
            moved = {}
            with _planted(plant):
                for name, fns in forms.items():
                    (y, _, last), grads = both(*fns, ins, dy)
                    (y0, _, last0), grads0 = found[name]
                    moved[name] = {"y": rms(y, y0),
                                   "final_state": rms(last, last0),
                                   "d_x": rms(grads[0], grads0[0])}
                with jax.default_matmul_precision("highest"):
                    for name, fns in forms.items():
                        (y, _, last), _ = both(*fns, ins32, dy32)
                        (y0, _, last0), _ = found32[name]
                        moved[name].update(
                            f32_y=rms(y, y0), f32_final_state=rms(last, last0))
            line(plant=plant, moved_rms=moved)


def device_ops(jax, fn, xs, top=0):
    """(ms a run, the `top` largest operations as [name @ scope, ms a run,
    events a run]) of the jitted `fn(*xs)`: the device's own clock, the self
    times of the operations of RUNS runs under one profiler trace (a loop on
    the device would let XLA lift out of it what does not change, the
    running sums over dt here; the host's clock adds a dispatch a run)."""
    import glob
    import tempfile

    from chipbench import xplane

    fn = jax.jit(fn)
    jax.block_until_ready(fn(*xs))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(RUNS):
                out = fn(*xs)
            jax.block_until_ready(out)
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        planes = xplane.load(files[0]) if files else []
    devs = xplane.device_planes(planes)
    if not devs:                    # the CPU: no device time to read
        return None, []
    events = devs[0].line(xplane.OPS_LINE).events
    rows = {}
    for e, self_ps in zip(events, xplane.self_times(events)):
        if xplane.op_code(e.name) in xplane.CONTAINERS:
            continue
        scope = str(e.stats.get("tf_op") or "").split(":")[0]
        row = rows.setdefault(xplane.stable_name(e) + " @ " + scope[-60:],
                              [0, 0])
        row[0] += self_ps
        row[1] += 1
    ranked = sorted(rows.items(), key=lambda kv: -kv[1][0])
    return (sum(v[0] for v in rows.values()) * 1e-9 / RUNS,
            [[k, round(ps * 1e-9 / RUNS, 4), n // RUNS]
             for k, (ps, n) in ranked[:top]])


def profile(jax, name, fwd, bwd, shape, ins, dy, starts):
    """One JSON line a pass: the device's operations by self time, the
    largest first."""
    passes = {"forward": (lambda *a: fwd(*a, **shape), ins),
              "backward": (lambda *a: bwd(*a, **shape), ins + (starts, dy))}
    for which, (fn, xs) in passes.items():
        ms, ops = device_ops(jax, fn, xs, top=14)
        print(json.dumps({"profile": name, "pass": which, "ms_a_run": ms,
                          "ops": ops}), flush=True)


if __name__ == "__main__":
    main()
