"""The comparison that decides `correct` for a language-model
configuration: the system under test against the configuration's plain
float32 reference, at the published widths, on the device the cell runs
on, outside the window, on seeded rows of the cell's own traffic.

Compared on one row of tokens (`cfg["reference"]["rows"]`):

* routing: the experts the system chose for every token (`ExpertIds`)
  against the reference's top-k;
* logits of the system's INFERENCE program against the reference's, per
  token, and the training loss: the whole, the cross-entropy, the router's
  z-loss (continuous in the router's logits) and its load-balance loss
  (which counts discrete choices), each apart;
* gradients, fetched as `<param>@GRAD` after the global-norm clip and
  divided by the clip's scale: the head, the router, one expert's three
  matrices, `Wq`, a norm scale and the embedding, by norm ratio and
  cosine (Adam's first step is lr * g / (|g| + eps): it hides a
  gradient's size), and the global gradient norm the clip computed;
* the first AdamW update of the same parameters, W1 - W0 read out of the
  scope, against -lr * (g / (|g| + eps') + decay * W0) on the clipped
  gradient the system's adam op was handed.

The configuration's builder names the parameters (`sampled_params`).

The limits and their two readings. The configuration states bf16 AMP:
every matrix product, the flash kernel and the grouped expert products
take operands rounded to 8 bits of mantissa and accumulate in float32;
master weights, the norms' statistics, the router with its softmax and
top-k, the loss and the optimizer are float32. Each limit lies between
the largest reading of the system as stated ("stated": my chip runs, PR
26: 28 seeds, 12 of them for what the first 16 did not report) and the reading
of the SYSTEM run one precision below ("lower": `python -m
chipbench.lower_precision_lm` on the chip, which turns one float32 part
to bf16 at a time and then all of them); PERF.md section 6 has the table.
What each limit is for, `limit: stated max | lower`:

* ROUTING_FLIP_MAX 7%: 4.9% of tokens | `all` 3.5-6.4%. Routing is
  discrete: the router is float32 but its input went through bf16
  products, so a token whose k-th and next probabilities are closer than
  that rounding picks the other. Where the system's set differs, every
  exchanged expert must lie within ROUTING_MARGIN 4% (relative
  probability) of the reference's k-th: 2.4% | 2.1-3.2%. One layer deep
  neither separates the precisions; they hold a wrong router (a wrong
  expert is tens of percent away). The training step and the inference
  program are two compiled programs and need not break a near-tie the
  same way: each is held to the reference apart.
* LOGITS_RMS_TOL 0.70%, the rms error over the tokens the inference
  program routed as the reference did, relative to the rms of their
  logits: 0.64% (0.58-0.64 over 12 seeds) | `norms` 0.71-0.76%, `all`
  0.79-0.82% (`router` 0.66-0.68%). This is what tells the forward's
  precision. LOGITS_TOL 1.2%, the largest error of a token relative to the
  row's largest |logit|: 0.78% | `all` 0.94-1.02%; the largest of 2e8
  values swings with the seed, so this one holds a token gone wrong (a
  flipped token's logits move by 8-10%), not the precision.
* LOSS_TOL 3e-4, the whole loss and the cross-entropy: 1.7e-4 (28 seeds,
  the next 8.8e-5) | `loss` 4.4e-4-2.7e-3, `all` 4.6e-3-6.9e-3.
* Z_LOSS_TOL 1e-3, mean logsumexp^2 of the router's logits, continuous:
  3.2e-4 (12 seeds) | `router` 1.5e-3-2.8e-3 (`norms` 2.4e-4-4.9e-4 is
  inside the stated range: the logits' rms holds that one).
* BALANCE_TOL 1%: 2.4e-3 | `router` 1.4e-3-3.2e-3. The load-balance loss
  counts discrete choices, so the near-ties above move it whatever the
  precision; the limit holds the formula (a missing term, the k-times
  variant), the z-loss holds the router's precision.
* GRAD_COS_MIN 0.999 and GRAD_NORM_TOL 2.5%, each sampled parameter:
  0.99968, 0.9929-1.0108 | `all` 0.99973, 0.990-1.008: a wrong gradient,
  not its precision (one expert's gradient moves 1% with the near-ties).
  The router's gradient comes from both router terms and the expert
  outputs, so a dropped term turns it.
* GLOBAL_NORM_TOL 1.2e-3, the norm the clip computed: 6.2e-4 | `all`
  1.8e-3. CLIP_SCALE_TOL 1e-4, the scale against clip / that norm:
  6e-8 | `all` 1.9e-3-2.7e-3.
* UPDATE_TOL 2e-3 of the parameter's largest step: 9.5e-4 | `masters` and
  `all` 1.0; a missing decay reads 0.011 (lr * 0.1 * |W| at the largest
  |W| of 0.09). The stated reading is not zero because the clipped
  gradient is fetched as the bf16 the clip's multiply wrote while XLA may
  keep it float32 inside the fused update: where |g| is near eps' the
  step g / (|g| + eps') moves by up to 2^-8 / 4 = 1e-3.
"""

import gc
import time

import numpy as np

from chipbench.harness import memory_peak

ROUTING_MARGIN = 0.04
ROUTING_FLIP_MAX = 0.07
LOGITS_TOL = 0.012
LOGITS_RMS_TOL = 0.0070
LOSS_TOL = 3e-4
Z_LOSS_TOL = 1e-3
BALANCE_TOL = 0.01
GRAD_NORM_TOL = 0.025
GLOBAL_NORM_TOL = 1.2e-3
GRAD_COS_MIN = 0.999
UPDATE_TOL = 2e-3
CLIP_SCALE_TOL = 1e-4


def _scalar(v):
    return float(np.asarray(v, np.float32).reshape(-1)[0])


def _rel(a, b):
    return abs(a - b) / abs(b)


def _cos_ratio(a, b):
    a, b = a.ravel().astype(np.float64), b.ravel().astype(np.float64)
    na, nb = np.linalg.norm(a), np.linalg.norm(b)
    if na == 0.0 or nb == 0.0:
        return None, None
    return float(a @ b / (na * nb)), float(na / nb)


def routing_report(ids_sys, probs_ref, top_ref, margin):
    """Tokens whose chosen set differs from the reference's, and whether
    every exchanged expert is a neighbour of the reference's threshold:
    its reference probability within `margin` (relative) of the k-th."""
    k = top_ref.shape[1]
    E = probs_ref.shape[1]
    chosen_sys = np.zeros(probs_ref.shape, bool)
    np.put_along_axis(chosen_sys, ids_sys, True, axis=1)
    chosen_ref = np.zeros(probs_ref.shape, bool)
    np.put_along_axis(chosen_ref, top_ref, True, axis=1)
    differs = chosen_sys ^ chosen_ref                       # [T, E]
    kth = np.sort(probs_ref, axis=1)[:, E - k][:, None]
    gap = np.abs(probs_ref - kth) / kth
    flipped = differs.any(axis=1)
    worst = float(np.where(differs, gap, 0.0).max()) if flipped.any() else 0.0
    return {"tokens": int(len(flipped)), "flipped": int(flipped.sum()),
            "flipped_share": float(flipped.mean()),
            "sets_of_k": bool((chosen_sys.sum(axis=1) == k).all()),
            "worst_gap": worst, "margin": margin,
            "ok": bool(worst <= margin
                       and (chosen_sys.sum(axis=1) == k).all())}, ~flipped


def _clip_vars(prog):
    """Names of the global norm and of the scale the clip multiplies by."""
    ops = [op for op in prog.global_block().ops
           if op.attrs.get("op_namescope") == "gradient_clip"]
    gnorm = next(op.output("Out")[0] for op in ops if op.type == "sqrt")
    scale = next(op.output("Out")[0] for op in ops
                 if op.type == "elementwise_div")
    return gnorm, scale


def system_side(fluid, cfg, builder, place, seed, tokens, labels):
    """What the system computes on the row, as numpy: the weights the
    startup program drew (`w0`, every parameter), the inference program's
    logits and routing, the training step's losses, routing, global norm,
    clip scale, clipped gradients and updated weights of the sampled
    parameters. Its scope is gone when this returns."""
    built = builder.build(fluid, cfg, seed, for_compare=True)
    picks = builder.sampled_params(cfg)
    gnorm_var, scale_var = _clip_vars(built["prog"])
    feed = {built["token_feed"]: tokens, built["label_feed"]: labels}
    ids_var = built["routing"][0][0]
    balance_var, z_var = built["aux"][0]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(built["startup"])
        w0 = {p.name: np.asarray(scope.find_var(p.name), np.float32)
              for p in built["prog"].global_block().all_parameters()}
        logits, ids_eval = exe.run(built["test_prog"], feed=feed,
                                   fetch_list=[built["logits"], ids_var])
        fetched = exe.run(
            built["prog"], feed=feed,
            fetch_list=[built["loss"], built["ce"], balance_var, z_var,
                        gnorm_var, scale_var, ids_var]
            + [n + "@GRAD_clipped" for n in picks.values()])
        w1 = {k: np.asarray(scope.find_var(n)).astype(np.float32)
              for k, n in picks.items()}
    got = dict(zip(("loss", "ce", "balance", "z", "gnorm", "scale"),
                   (_scalar(v) for v in fetched[:6])))
    got.update(w0=w0, w1=w1, logits=np.asarray(logits, np.float32),
               ids_eval=np.asarray(ids_eval), ids=np.asarray(fetched[6]),
               clipped={k: np.asarray(v).astype(np.float32)
                        for k, v in zip(picks, fetched[7:])})
    del scope, exe, fetched, built
    gc.collect()
    return got


def reference_side(cfg, builder, w0, tokens, labels):
    """The plain reference on the same weights and row, as numpy."""
    import jax.numpy as jnp

    ref, picks = builder.reference, builder.sampled_params(cfg)
    loss, (ce, logits, routing, aux), grads = ref.loss_and_grads(
        cfg, {k: jnp.asarray(v) for k, v in w0.items()},
        jnp.asarray(tokens), jnp.asarray(labels))
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
    probs, top = (np.asarray(v) for v in routing[0])
    return dict(loss=float(loss), ce=float(ce), balance=float(aux[0][0]),
                z=float(aux[0][1]), gnorm=gnorm, probs=probs, top=top,
                logits=np.asarray(logits).reshape(probs.shape[0], -1),
                grads={k: np.asarray(grads[n]) for k, n in picks.items()})


def judge(cfg, builder, got, ref):
    """The report: every number, the limits, which of them `failed`."""
    picks = builder.sampled_params(cfg)
    # the inference program and the training step are two compiled
    # programs: near-ties need not fall the same way in both
    route, _ = routing_report(got["ids"], ref["probs"], ref["top"],
                              ROUTING_MARGIN)
    route_eval, same = routing_report(got["ids_eval"], ref["probs"],
                                      ref["top"], ROUTING_MARGIN)
    diff = got["logits"] - ref["logits"]
    err = np.abs(diff).max(axis=1) / np.abs(ref["logits"]).max()
    # the busiest expert of the reference's routing stands for "one expert"
    expert = int(np.bincount(ref["top"].ravel()).argmax())
    o = cfg["optimizer"]
    eps = o["epsilon"] / np.sqrt(1.0 - o["beta2"])
    by_param = {}
    for key, name in picks.items():
        g_hat, g_ref = got["clipped"][key], ref["grads"][key]
        a, b = got["w0"][name], got["w1"][key]
        if key.startswith("expert_"):
            g_hat, g_ref, a, b = (v[expert] for v in (g_hat, g_ref, a, b))
        cos, ratio = _cos_ratio(g_hat / got["scale"], g_ref)
        decay = 0.0 if name.endswith("_norm") else o["weight_decay"]
        want = -o["learning_rate"] * (g_hat / (np.abs(g_hat) + eps)
                                      + decay * a)
        by_param[key] = {
            "grad_cos": cos, "grad_norm_ratio": ratio,
            "update_err": float(np.abs((b - a) - want).max()
                                / np.abs(want).max())}
    report = {
        "config": cfg["name"], "rows": int(cfg["reference"]["rows"]),
        "expert": expert, "reference": cfg["reference"]["file"],
        "routing": route, "routing_inference": route_eval,
        "logits_err_max": float(err[same].max()),
        "logits_err_rms": float(np.sqrt(np.mean(np.square(diff[same])))
                                / np.sqrt(np.mean(np.square(
                                    ref["logits"][same])))),
        "logits_err_flipped_max": float(err[~same].max())
        if (~same).any() else None,
        "train_loss": [got["loss"], ref["loss"]],
        "train_loss_err": _rel(got["loss"], ref["loss"]),
        "cross_entropy_err": _rel(got["ce"], ref["ce"]),
        "z_loss": [got["z"], ref["z"]],
        "z_loss_err": _rel(got["z"], ref["z"]),
        "balance_loss": [got["balance"], ref["balance"]],
        "balance_loss_err": _rel(got["balance"], ref["balance"]),
        "global_grad_norm": [got["gnorm"], ref["gnorm"]],
        "global_grad_norm_err": _rel(got["gnorm"], ref["gnorm"]),
        "clip_scale": got["scale"],
        "clip_scale_err": _rel(got["scale"], min(
            1.0, o["clip_global_norm"] / got["gnorm"])),
        "by_param": by_param,
        "limits": {"routing_margin": ROUTING_MARGIN,
                   "routing_flip_max": ROUTING_FLIP_MAX,
                   "logits": LOGITS_TOL, "logits_rms": LOGITS_RMS_TOL,
                   "loss": LOSS_TOL,
                   "z_loss": Z_LOSS_TOL, "balance_loss": BALANCE_TOL,
                   "grad_norm": GRAD_NORM_TOL,
                   "global_grad_norm": GLOBAL_NORM_TOL,
                   "grad_cos_min": GRAD_COS_MIN, "update": UPDATE_TOL,
                   "clip_scale": CLIP_SCALE_TOL},
    }
    worst = {k: [f(v[k] for v in by_param.values() if v[k] is not None)
                 for f in (min, max)]
             for k in ("grad_cos", "grad_norm_ratio", "update_err")}
    held = {
        "routing": all(r["ok"] and r["flipped_share"] <= ROUTING_FLIP_MAX
                       for r in (route, route_eval)),
        "logits": bool(np.isfinite(report["logits_err_max"])
                       and report["logits_err_max"] <= LOGITS_TOL
                       and report["logits_err_rms"] <= LOGITS_RMS_TOL),
        "loss": report["train_loss_err"] <= LOSS_TOL
        and report["cross_entropy_err"] <= LOSS_TOL,
        "z_loss": report["z_loss_err"] <= Z_LOSS_TOL,
        "balance_loss": report["balance_loss_err"] <= BALANCE_TOL,
        "global_grad_norm": report["global_grad_norm_err"]
        <= GLOBAL_NORM_TOL,
        "clip_scale": report["clip_scale_err"] <= CLIP_SCALE_TOL,
        "grad_cos": all(v["grad_cos"] is not None
                        for v in by_param.values())
        and worst["grad_cos"][0] >= GRAD_COS_MIN,
        "grad_norm": max(abs(r - 1.0) for r in worst["grad_norm_ratio"])
        <= GRAD_NORM_TOL,
        "update": worst["update_err"][1] <= UPDATE_TOL,
    }
    report["failed"] = sorted(k for k, v in held.items() if not v)
    report["ok"] = not report["failed"]
    return report


def against_reference(fluid, cfg, builder, place, seed, tokens, labels):
    """`tokens`, `labels`: int32 [rows, S] of the cell's traffic. Returns
    a report with `ok` and every number. The system's scope is freed
    before the reference runs, and the caller builds the timed program
    after this returns: `device_peak_bytes` says how high the comparison
    pushed the device's memory."""
    import jax

    t0 = time.perf_counter()
    got = system_side(fluid, cfg, builder, place, seed, tokens, labels)
    ref = reference_side(cfg, builder, got["w0"], tokens, labels)
    report = judge(cfg, builder, got, ref)
    report["device_peak_bytes"] = int(memory_peak(jax.local_devices()))
    report["seconds"] = time.perf_counter() - t0
    return report
