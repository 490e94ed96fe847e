"""The comparison that decides `correct` for a configuration: the system
under test against the configuration's plain float32 reference, at the
published widths, on the device the cell runs on, outside the window.

Three things are compared on one seeded batch (8 images by default):

* the first Momentum step's update of every parameter (W1 - W0 of the
  system's training program against -lr * grad of the reference's
  training-mode loss at W0), reported for a sample of layers and judged
  over all of them;
* the training loss of that step;
* the logits and loss of the system's INFERENCE program at W1 (moving
  statistics included) against the reference's inference pass at W1.

Tolerances and why. The system runs bf16 AMP: every convolution and matrix
product takes operands rounded to 8 bits of mantissa (relative step 2^-8)
and accumulates in float32; master weights, batch-norm statistics, softmax,
loss and the optimizer stay float32.

* Logits, relative to the largest |reference logit| of the batch: the
  roundings of ~53 weighted layers add up like a random walk, sqrt(53) *
  2^-9 = 1.4%; measured 0.5-1.0% on the v5e over 5 seeds (resnet50) and
  0.7-0.8% (se_resnext50) (my chip runs, PR 23). Bound 4%.
* Training loss, relative: measured 0.08-1.6% (resnet50), 0.07-0.24%
  (se_resnext50); bound 5%, three times the worst seed.
* Update SIZE: the median over all parameters of |W1 - W0| / |lr * grad|
  measured 0.994-1.017 (resnet50), 0.999-1.001 (se_resnext50); bound 5%.
  A wrong learning rate, a dropped or doubled gradient, loss scaling left
  in, or master weights kept in bf16 (an update of 1% of a weight is
  under bf16's step, so most of it would vanish) all break this by far
  more.
* Update DIRECTION of the head (the layer whose gradient does not pass
  through the network): cosine measured 0.9949-0.9954 and 0.9991-0.9992;
  bound 0.98.
* Update direction of the layers under the head is REPORTED and only held
  to be clearly positive (median cosine >= 0.05, where unrelated vectors
  of these sizes give under 0.01). At random initial weights a deep ReLU
  network with batch norm is in the regime of "shattered gradients"
  (Balduzzi et al., arXiv:1702.08591): the gradient keeps its size but
  turns under the smallest perturbation of the forward pass. float32
  against float32 the median cosine is 0.997; with bf16 operands it is
  0.19-0.22 for ResNet-50 and 0.58-0.59 for SE-ResNeXt-50 at the same
  sizes (8 images, on the v5e; XLA:CPU with bf16 emulated gave 0.19 and
  0.61), and no tolerance on it can tell AMP as stated from anything
  worse. The size, the head, the loss and the logits can.
"""

import time

import numpy as np

# relative to the largest |reference logit| of the batch
LOGITS_TOL = 0.04
# |loss - reference loss| / reference loss, training and inference
LOSS_TOL = 0.05
# median over all parameters of |system update| / |reference update|, -1
UPDATE_NORM_TOL = 0.05
# cosine of the head's update with the reference's
HEAD_COS_MIN = 0.98
# median cosine under the head: reported, held only to be clearly positive
DEEP_COS_MIN = 0.05


def _cos_ratio(a, b):
    a, b = a.ravel().astype(np.float64), b.ravel().astype(np.float64)
    na, nb = np.linalg.norm(a), np.linalg.norm(b)
    if na == 0.0 or nb == 0.0:
        return None, None
    return float(a @ b / (na * nb)), float(na / nb)


def sample_layers(layers):
    """The first conv, a mid-network 3x3 (or grouped) conv, one batch-norm
    scale, one squeeze-excitation (or any hidden) fc, and the head."""
    convs = [g[0] for k, g in layers if k == "conv"]
    bns = [g[0] for k, g in layers if k == "bn"]
    fcs = [g[0] for k, g in layers if k == "fc"]
    picks = {"first_conv": convs[0], "mid_conv": convs[len(convs) // 2],
             "bn_scale": bns[len(bns) // 2], "head": fcs[-1]}
    if len(fcs) > 1:
        picks["se_fc"] = fcs[len(fcs) // 2]
    return picks


def against_reference(fluid, cfg, builder, place, seed, batch=None,
                      parts=("update", "inference")):
    """Runs the comparison; returns a report with `ok` and every number.
    A serving cell runs no training step, so it asks for the inference
    part alone (at the seeded weights as the startup program left them)."""
    import jax.numpy as jnp

    from chipbench import programs
    from chipbench.reference import convnet

    t0 = time.perf_counter()
    batch = int(batch or cfg["reference"]["batch"])
    ref = builder.reference
    train_cfg = getattr(builder, "compare_train_cfg", lambda c: c)(cfg)
    built = builder.build(fluid, cfg, seed, for_compare=True)
    layers = builder.reference_order(
        programs.layers_in_program_order(built["test_prog"]), cfg)
    xs, ys = programs.seeded_images(cfg, seed, 1, batch)
    feed = {built["image_feed"]: programs.to_system(cfg, xs[0]),
            built["label_feed"]: ys[0]}
    logits_var = next(op.input("X")[0] for op in reversed(
        built["test_prog"].global_block().ops) if op.type == "softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(built["startup"])
        w0, names = programs.read_tape(scope, layers)
        w1, loss_sys = w0, None
        if "update" in parts:
            loss_sys, = exe.run(built["prog"], feed=feed,
                                fetch_list=[built["loss"]])
            loss_sys = float(np.asarray(loss_sys).reshape(-1)[0])
            w1, _ = programs.read_tape(scope, layers)
        logits_sys, eval_loss_sys = exe.run(
            built["test_prog"], feed=feed,
            fetch_list=[logits_var, built["loss"]])
    eval_loss_sys = float(np.asarray(eval_loss_sys).reshape(-1)[0])
    logits_sys = np.asarray(logits_sys, np.float32)

    x = jnp.asarray(xs[0], jnp.float32) * cfg["input_scale"]
    y = jnp.asarray(ys[0])
    loss_ref = grads = None
    if "update" in parts:
        loss_ref, grads = convnet.loss_and_grads(ref.network, train_cfg,
                                                 w0, x, y)
        loss_ref = float(loss_ref)
    logits_ref, eval_loss_ref, _ = convnet.run(
        ref.network, cfg, w1, x, y, train=False)
    eval_loss_ref = float(eval_loss_ref)
    logits_ref = np.asarray(logits_ref)

    lr = cfg["optimizer"]["learning_rate"]
    stats = {n for k, g in layers if k == "bn" for n in g[2:]}
    cos, ratio, by_name = [], [], {}
    for n, a, b, g in zip(names, w0, w1, grads or []):
        if n in stats:
            continue
        c, r = _cos_ratio(b - a, -lr * np.asarray(g))
        if c is None:
            continue
        cos.append(c)
        ratio.append(r)
        by_name[n] = (c, r)
    logits_err = float(np.max(np.abs(logits_sys - logits_ref))
                       / np.max(np.abs(logits_ref)))
    # a saturated inference loss (the system clips -log p) says nothing
    eval_loss_err = abs(eval_loss_sys - eval_loss_ref) / abs(eval_loss_ref)
    update = "update" in parts
    report = {
        "config": cfg["name"], "batch": batch, "parts": list(parts),
        "reference": cfg["reference"]["file"],
        "parameters_compared": len(cos),
        "train_loss": [loss_sys, loss_ref],
        "train_loss_err": abs(loss_sys - loss_ref) / abs(loss_ref)
        if update else None,
        "eval_loss": [eval_loss_sys, eval_loss_ref],
        "eval_loss_err": eval_loss_err,
        "logits_err": logits_err,
        "update_cos_median": float(np.median(cos)) if update else None,
        "update_cos_min": float(np.min(cos)) if update else None,
        "update_norm_ratio_median": float(np.median(ratio))
        if update else None,
        "sampled_updates": {k: by_name.get(n) for k, n in
                            sample_layers(layers).items()}
        if update else None,
        "head_update_cos": (by_name.get(sample_layers(layers)["head"])
                            or [None])[0] if update else None,
        "tolerances": {"logits": LOGITS_TOL, "loss": LOSS_TOL,
                       "update_norm": UPDATE_NORM_TOL,
                       "head_cos_min": HEAD_COS_MIN,
                       "deep_cos_min": DEEP_COS_MIN},
        "seconds": None,
    }
    report["ok"] = bool(
        np.isfinite(logits_err) and logits_err <= LOGITS_TOL
        and (eval_loss_err <= LOSS_TOL or eval_loss_ref > 30.0))
    if update:
        report["ok"] = bool(
            report["ok"] and report["train_loss_err"] <= LOSS_TOL
            and report["update_cos_median"] >= DEEP_COS_MIN
            and report["head_update_cos"] >= HEAD_COS_MIN
            and abs(report["update_norm_ratio_median"] - 1.0)
            <= UPDATE_NORM_TOL)
    report["seconds"] = time.perf_counter() - t0
    return report
