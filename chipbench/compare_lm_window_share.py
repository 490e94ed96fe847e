"""The comparison that decides `correct` for a language model whose layers
alternate between full and sliding-window attention with different head
counts, grouped-query heads and a per-head output gate, and that holds one
chip's SHARE of each layer (`laguna_xs_2`): the system under test against
the configuration's plain float32 reference (which is given the same
share), at the published widths, on the device the cell runs on, outside
the window, on the rows the cell's own window starts with. Two objects
are set against the reference. (1) THE EXECUTABLE THE WINDOW TIMES (the
K-step scan): its losses of steps 0 and 1, as the kind fetched them, against
the reference's loss on the first step's rows and its loss on the second
step's rows AFTER ITS OWN first step (every trained weight moved by the
first AdamW update behind the global clip, each router's bias by the rule),
same seed's weights, same rows (`timed_steps`); the reference also gives the
second loss had nothing been carried, which says what this check can see.
(2) A second build of the same program (as `compare_lm_share`, whose helpers
and `compare_lm`'s this file imports, not copies), in a scope of its own,
run for ONE step on the first step's rows with the gradients fetched, and
its inference clone: the same ops, lowerings and kernels as the timed
program, for what a scan does not fetch (gradients, updates, routing,
branches). Beyond its first two losses the timed scan is held by the cell's
own checks (every token routed, the products took the held rows, the first
router's bias carried through every step run).

What is particular to the configuration lives here and in its builder
(`sampled_params`, what is fetched): `compare_lm_share.judge` reads a
multi-token-prediction head, mixers and that configuration's keys.
Compared on one row of tokens:

* FIRST-HAND, before any routing can blur it: the attention branch of the
  first full layer (layer 0) and of the first window layer (layer 1), the
  system's output against the reference's ON THE SAME INPUT (the normed
  input the system itself fed the branch, fetched and handed to the
  reference): rms error relative to the rms, and the largest error
  relative to the largest value. A band off by one, a wrong key/value
  head, a rotary on the wrong half of a head, a missing gate or attention
  factor fails here by itself;
* that input itself, of both layers, against the reference's computed FROM
  THE TOKENS (layer 1's through the reference's own layer 0): its rms
  error, and the rms of its per-row scale error (the norm statistic:
  rounding averages out over a row's 2048 columns, a bf16 statistic does
  not);
* routing of EVERY expert layer, the training step and the inference
  program each apart, each layer judged on the tokens every layer before
  it routed alike (`compare_lm_share._routing_by_layer`);
* logits per token over the tokens routed as the reference routed them in
  every layer; the loss; the global gradient norm the clip computed and
  its scale;
* gradient cosine, norm ratio and first AdamW update of a sampled
  parameter of each kind (`sampled_params`: head, embedding, W_q and W_k
  of a full and of a window layer, W_v, W_g, W_o, the router, one held
  expert's three matrices (the busiest held expert of the first expert
  layer by the reference's routing), the shared expert's, a norm scale);
* what the grouped kernels wrote, in every expert layer of the inference
  program: the rows of the down product that are not all zero are exactly
  `RowsHeld`, and that is the number of choices on the held experts;
* each router's bias after the step: moved by the configuration's speed
  towards an even load of that step's own choices, exactly.

The limits, each from two readings (PERF.md section 6, PR 32, has the
table): the largest reading of the system as the configuration states it
over the builder's seeds ("stated": 26, the last 12 under the checks the
review round added), and the SYSTEM one precision below
(`python -m chipbench.lower_precision_lm_window_share` on the chip, two
seeds: the router, the norms' statistics, the master weights, then all in
bf16), or, for the attention branch, which the configuration states in
bf16, a WRONG BAND PLANTED IN THE PROGRAM (that study's `band_off_by_one`:
the system's window 513, on the chip, three seeds):

* ATTENTION_TOL 1.1% of the largest value, ATTENTION_RMS_TOL 1.5% of the
  rms: stated 0.72%, 0.62% (full), 0.58%, 0.43% (window) | no attention
  factor 40%, 52%; the whole head rotated 67%, 133%; no gate 120%, 101%
  (the float32 reference against itself, random inputs, on the CPU); the
  planted band 0.75-1.89%, 0.58-0.62%: on the cell's own traffic one key
  more among 512 moves the branch by about the bf16 noise (Zipf ids
  repeat, so a row's 512 values are far from independent), and the two
  limits alone fail it in two seeds of three. So, beside them: THE
  REFERENCE'S BAND MUST FIT BEST, the window branch's rms error against
  the reference at the stated window below that at either neighbour
  (stated 0.40-0.42% at 512 against 0.58-0.67% at 511 and 513; planted
  0.58-0.62% at 512 against 0.41% at 513, every seed);
* NORM_SCALE_TOL, the per-row scale of a first-hand layer's normed input
  against the reference's from the tokens: layer 0 1e-5 (stated 0.0, the
  same float32 norm of the same embedding | `norms` 1.3e-3, 1.5e-3);
  layer 1 4e-4 (stated 1.19e-4-1.23e-4 | `norms` 1.95e-3, 2.00e-3). The
  input's rms error is reported without a limit (layer 1: stated
  0.92-0.97% | `norms` 1.06%, 1.13%: layer 0's bf16 products, not
  separable);
* `timed_steps`, LOSS_TOL on each: the timed scan's loss of step 0 and of
  step 1 against the reference's: stated 4.2e-5, 5.7e-5 | had the scan
  carried nothing into step 1 4.3e-3-5.0e-3 (the reference's own second
  loss from unmoved weights, every seed);
* ROUTING_FLIP_MAX 14% of the tokens alike so far, in any layer: stated
  12.67% | `router` 15.4-18.1%, `all` 16.1-19.6% (`norms` 10.4-14.1%: held
  by the norm's limit): the lower reading is 1.22x the stated one, under
  the 3x that would make it a limit on the precision, so ROUTING IS NOT
  HELD AGAINST THE CONTROL: it holds a wrong router (tens of percent);
  each exchanged expert within `ROUTING_MARGIN` 3% of the k-th score
  (stated 0.94% | `all` 1.3%: not separated);
* GRAD_LIMITS: the router's cosine >= 0.95, norm within 10% (stated
  0.9859, 4.9% | `router` 0.595, 25%; `all` 0.63-0.67); the held expert's
  >= 0.99 (0.9979 | `router` 0.9818), every other cosine >= 0.998
  (0.99983 | `router` 0.99618; `all` 0.9962). The NORM RATIOS of those
  two kinds follow the routing's near-ties (one flipped token of a large
  gradient moves a single expert's norm) and do not separate the
  precisions; they hold a gradient of the wrong scale: the held expert's
  within 10% (25 seeds <= 0.63%, one 3.5% at cosine 0.999 |
  `router` 13%), every other within 3% (0.94%, W_g of a window layer |
  `router` 1.2%, `all` 1.15%). They stood at 4% and 1% until the review
  round's seeds read 3.5% and 0.94%: room for one more seed and no more,
  which is how `compare_lm_share` met its 13th (PERF.md section 7 row
  34);
* UPDATE_TOL 0.1 of a step: stated 0.0593 (a norm scale: half an ulp of
  1.0 is 6% of a 1e-6 step; matrices 0.006) | `masters`, `all` 243; NOT a
  missing decay (0.2% of a step here; tests/test_laguna.py holds it);
* CLIP_SCALE_TOL 1e-5 (7.5e-8 | `all` 9.8e-4, 2.2e-3), GLOBAL_NORM_TOL
  2.5e-3 (6.1e-4 | `all` 1.1e-2, 9.6e-3);
* LOSS_TOL 6e-4 on the one-step build's loss (1.3e-4 | `all` 8.9e-5,
  2.5e-3: one seed of two), LOGITS_TOL 2.5% and LOGITS_RMS_TOL 2% (stated
  1.52%, 1.24% | `all` 1.77%, 1.48%) do not separate the precisions five
  layers deep: they hold a wrong forward (a dropped branch moves the
  logits by tens of percent).
"""

import gc
import time

import numpy as np

from chipbench.compare_lm import _clip_vars, _cos_ratio, _rel, _scalar
from chipbench.compare_lm_share import (ROUTING_MARGIN, _logits_errors,
                                        _products, _routing_by_layer)
from chipbench.harness import memory_peak

ROUTING_FLIP_MAX = 0.14
LOGITS_TOL = 0.025
LOGITS_RMS_TOL = 0.02
LOSS_TOL = 6e-4
GLOBAL_NORM_TOL = 2.5e-3
CLIP_SCALE_TOL = 1e-5
UPDATE_TOL = 0.1
ATTENTION_RMS_TOL = 0.015
ATTENTION_TOL = 0.011
# by first-hand layer (`FIRST_HAND`): layer 0's input is the norm of the
# float32 embedding, layer 1's lies behind a bf16 layer
NORM_SCALE_TOL = {0: 1e-5, 1: 4e-4}
# gradient cosine at least, norm ratio within, by kind of parameter: what
# the discrete routing touches directly (the router, the one held expert
# sampled) moves with the near-ties
GRAD_LIMITS = {"router": (0.95, 0.10), "expert": (0.99, 0.10)}
GRAD_LIMITS_ELSE = (0.998, 0.03)
# the layers whose attention branch is compared first-hand: the first
# full and the first window layer; no routing lies before either
FIRST_HAND = (0, 1)
WINDOW = "sliding_attention"


def system_side(fluid, cfg, builder, place, seed, tokens, labels):
    """What the system computes on the row, as numpy: the weights the
    startup program drew (`w0`, every parameter), the inference program's
    logits, routing and the attention branches (input, output) of
    `FIRST_HAND`, the training step's loss, routing, global norm, clip
    scale, clipped gradients and updated weights of the sampled
    parameters. Its scope is gone when this returns."""
    built = builder.build(fluid, cfg, seed, for_compare=True)
    picks = builder.sampled_params(cfg)
    gnorm_var, scale_var = _clip_vars(built["prog"])
    feed = {built["token_feed"]: tokens, built["label_feed"]: labels}
    ids_vars = [r[0] for r in built["routing"]]
    branches = [v for i in FIRST_HAND for v in built["attention"][i]]
    products = _products(built["test_prog"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(built["startup"])
        w0 = {p.name: np.asarray(scope.find_var(p.name), np.float32)
              for p in built["prog"].global_block().all_parameters()}
        evaled = exe.run(built["test_prog"], feed=feed,
                         fetch_list=[built["logits"]] + branches + ids_vars
                         + [n for pair in products for n in pair])
        n_ids = 1 + len(branches) + len(ids_vars)
        rows_written = [
            (int(np.any(np.asarray(down) != 0, axis=1).sum()),
             int(np.asarray(held).reshape(-1)[0]))
            for down, held in zip(evaled[n_ids::2], evaled[n_ids + 1::2])]
        evaled = evaled[:n_ids]
        fetched = exe.run(
            built["prog"], feed=feed,
            fetch_list=[built["loss"], gnorm_var, scale_var] + ids_vars
            + [n + "@GRAD_clipped" for n in picks.values()])
        w1 = {k: np.asarray(scope.find_var(n)).astype(np.float32)
              for k, n in picks.items()}
        biases = [(w0[op.input("Bias")[0]],
                   np.asarray(scope.find_var(op.input("Bias")[0])))
                  for op in built["prog"].global_block().ops
                  if op.type == "moe_ffn"]
    n_layers, n_b = len(ids_vars), len(branches)
    got = dict(zip(("loss", "gnorm", "scale"),
                   (_scalar(v) for v in fetched[:3])))
    got.update(
        w0=w0, w1=w1, logits=np.asarray(evaled[0], np.float32),
        attention=[(np.asarray(u, np.float32), np.asarray(o, np.float32))
                   for u, o in zip(evaled[1:1 + n_b:2],
                                   evaled[2:1 + n_b:2])],
        ids_eval=[np.asarray(v) for v in evaled[1 + n_b:]],
        rows_written=rows_written, biases=biases,
        ids=[np.asarray(v) for v in fetched[3:3 + n_layers]],
        clipped={k: np.asarray(v).astype(np.float32)
                 for k, v in zip(picks, fetched[3 + n_layers:])})
    del scope, exe, fetched, evaled, built
    gc.collect()
    return got


def reference_branches(cfg, builder, w0, tokens, attention_inputs,
                       layers=FIRST_HAND):
    """The reference's attention branches of `layers` on the normed
    inputs the system itself fed its own, [T, C] each."""
    import jax.numpy as jnp

    # a layer's own attention weights alone: the rest of the 541 M stay
    # on the host
    return [np.asarray(builder.reference.attention_branch(
        cfg, {k: jnp.asarray(v) for k, v in w0.items()
              if k.startswith("laguna.l%d.w_" % i)}, i,
        jnp.asarray(u).reshape(tokens.shape + (-1,)))).reshape(
            tokens.size, -1) for i, u in zip(layers, attention_inputs)]


def reference_band_neighbours(cfg, builder, w0, tokens, attention_inputs):
    """{window: the reference's attention branch of the first-hand WINDOW
    layer, on the system's own input, had its band been one position
    shorter or longer than the configuration's}: the system's branch must
    lie nearer the reference's at the stated window than at either
    neighbour. (On the cell's traffic one key more among 512 moves the
    branch by 0.4% of its rms, about the bf16 noise, so the limit on the
    error alone sees a band off by one in its largest element only.)"""
    W = int(cfg["sliding_window"])
    i = next(i for i in FIRST_HAND if cfg["layer_types"][i] == WINDOW)
    u = attention_inputs[FIRST_HAND.index(i)]
    return {w: reference_branches(dict(cfg, sliding_window=w), builder, w0,
                                  tokens, [u], layers=(i,))[0]
            for w in (W - 1, W + 1)}


def reference_inputs(cfg, builder, w0, tokens):
    """The normed inputs of `FIRST_HAND`'s attention branches as the
    reference computes them FROM THE TOKENS, [T, C] each: the embedding
    through every layer before, all of it the reference's own."""
    import jax
    import jax.numpy as jnp

    ref, eps = builder.reference, cfg["rms_norm_eps"]
    last = max(FIRST_HAND)
    # the layers before the last first-hand one, and its norm, alone on
    # the device
    before = ("laguna.embed", "laguna.l%d.attn_norm" % last) + tuple(
        "laguna.l%d." % i for i in range(last))
    w = {k: jnp.asarray(v) for k, v in w0.items() if k.startswith(before)}

    def inputs(w_, t):
        x, found = w_["laguna.embed"][t], {}
        for i in range(last + 1):
            if i in FIRST_HAND:
                found[i] = ref.rms_norm(
                    x, w_["laguna.l%d.attn_norm" % i], eps)
            if i < last:
                x, _ = ref.layer(x, w_, i, cfg)
        return [found[i] for i in FIRST_HAND]

    with jax.default_matmul_precision(ref.PRECISION):
        return [np.asarray(u).reshape(tokens.size, -1)
                for u in jax.jit(inputs)(w, jnp.asarray(tokens))]


def reference_second_step(cfg, builder, wj, grads, routing, tokens, labels):
    """The reference's loss on the rows of step 1 after ITS OWN first
    step (the first AdamW update of every trained weight behind the
    global clip, each router's bias moved by the rule on the reference's
    own choices), and beside it the loss on the same rows had the first
    step left the state as it was: (loss, loss with nothing carried)."""
    import jax
    import jax.numpy as jnp

    ref, o = builder.reference, cfg["optimizer"]
    delta, _ = ref.adamw_first_update(
        cfg, wj, grads, epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    w1 = dict(wj)
    for name in list(delta):
        w1[name] = wj[name] + delta.pop(name)
    biases = sorted((k for k in wj if k.endswith("router_bias")),
                    key=lambda k: int(k.split(".")[1][1:]))
    for name, (_, chosen) in zip(biases, routing):
        load = jnp.bincount(chosen.ravel(), length=wj[name].shape[0])
        w1[name] = wj[name] + o["router_bias_update_speed"] * jnp.sign(
            jnp.mean(load.astype(jnp.float32)) - load)
    with jax.default_matmul_precision(ref.PRECISION):
        loss = jax.jit(lambda w_, t, l: ref.loss_fn(cfg, w_, t, l)[0])
        t, l = jnp.asarray(tokens), jnp.asarray(labels)
        return float(loss(w1, t, l)), float(loss(wj, t, l))


def reference_side(cfg, builder, w0, tokens, labels, attention_inputs):
    """The plain reference on the same weights and rows, as numpy;
    `attention_inputs`: as `reference_branches` takes them. `tokens` may
    hold the rows of a second step behind those of the first
    (`cfg["reference"]["rows"]`): `reference_second_step`."""
    import jax.numpy as jnp

    ref, picks = builder.reference, builder.sampled_params(cfg)
    rows = int(cfg["reference"]["rows"])
    first, then = (tokens[:rows], labels[:rows]), (tokens[rows:2 * rows],
                                                    labels[rows:2 * rows])
    wj = {k: jnp.asarray(v) for k, v in w0.items()}
    loss, (logits, routing), grads = ref.loss_and_grads(
        cfg, wj, jnp.asarray(first[0]), jnp.asarray(first[1]))
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
    T = first[0].size
    side = dict(
        loss=float(loss), gnorm=gnorm,
        routing=[(np.asarray(b), np.asarray(t)) for b, t in routing],
        logits=np.asarray(logits).reshape(T, -1),
        grads={k: np.asarray(grads[n]) for k, n in picks.items()})
    del logits
    if len(then[0]):
        side["second_step"] = reference_second_step(
            cfg, builder, wj, grads, routing, *then)
    del grads, wj
    side["attention"] = reference_branches(cfg, builder, w0, first[0],
                                           attention_inputs)
    side["attention_band"] = reference_band_neighbours(
        cfg, builder, w0, first[0], attention_inputs)
    side["attention_inputs"] = reference_inputs(cfg, builder, w0, first[0])
    return side


def _branch_errors(got, ref):
    diff = got.astype(np.float64) - ref
    return (float(np.abs(diff).max() / np.abs(ref).max()),
            float(np.sqrt(np.mean(diff ** 2)) / np.sqrt(np.mean(ref ** 2))))


def judge(cfg, builder, got, ref, timed=None):
    """The report: every number, the limits, which of them `failed`.
    `timed`: {"losses": the losses of steps 0 and 1 as the TIMED
    executable (the K-step scan the window times) fetched them}, where
    `ref` holds a second step."""
    picks = builder.sampled_params(cfg)
    route, _ = _routing_by_layer(got["ids"], ref["routing"])
    route_eval, same = _routing_by_layer(got["ids_eval"], ref["routing"])
    main_max, main_rms = _logits_errors(got["logits"], ref["logits"], same)
    first, held_n = cfg["deployment"]["first_expert"], cfg["num_experts"]
    n_all = cfg["deployment"]["num_experts"]
    counts = np.bincount(ref["routing"][0][1].ravel(), minlength=n_all)
    expert = int(counts[first:first + held_n].argmax())
    rows = [[written, held, int(((ids >= first)
                                 & (ids < first + held_n)).sum())]
            for (written, held), ids in zip(got["rows_written"],
                                            got["ids_eval"])]
    o = cfg["optimizer"]
    eps = o["epsilon"] / np.sqrt(1.0 - o["beta2"])
    bias_moved = []
    for (before, after), ids in zip(got["biases"], got["ids"]):
        load = np.bincount(ids.ravel(), minlength=n_all).astype(np.float64)
        want = before + np.float32(o["router_bias_update_speed"]) \
            * np.sign(load.mean() - load).astype(np.float32)
        bias_moved.append(bool(np.array_equal(after, want)))
    by_param = {}
    for key, name in picks.items():
        g_hat, g_ref = got["clipped"][key], ref["grads"][key]
        a, b = got["w0"][name], got["w1"][key]
        if key.startswith("expert_"):
            g_hat, g_ref, a, b = (v[expert] for v in (g_hat, g_ref, a, b))
        cos, ratio = _cos_ratio(g_hat / got["scale"], g_ref)
        decay = o["weight_decay"] if builder.reference.decays(name) else 0.0
        want = -o["learning_rate"] * (g_hat / (np.abs(g_hat) + eps)
                                      + decay * a)
        kind = "expert" if key.startswith("expert_") else key
        cos_min, ratio_tol = GRAD_LIMITS.get(kind, GRAD_LIMITS_ELSE)
        by_param[key] = {
            "grad_cos": cos, "grad_norm_ratio": ratio,
            "grad_ok": bool(cos is not None and cos >= cos_min
                            and abs(ratio - 1.0) <= ratio_tol),
            "update_err": float(np.abs((b - a) - want).max()
                                / np.abs(want).max())}
    # [largest error over the largest value, rms error over the rms] of
    # each first-hand attention branch
    attention = {
        cfg["layer_types"][i]: _branch_errors(o_sys, o_ref)
        for i, (_, o_sys), o_ref in zip(FIRST_HAND, got["attention"],
                                        ref["attention"])}
    # the window layer's branch against the reference's at the stated
    # window and at its two neighbours: {window: rms error over the rms}
    W = int(cfg["sliding_window"])
    o_win = got["attention"][[cfg["layer_types"][i]
                              for i in FIRST_HAND].index(WINDOW)][1]
    band = {w: _branch_errors(o_win, o_ref)[1]
            for w, o_ref in ref["attention_band"].items()}
    band[W] = attention[WINDOW][1]
    # each first-hand branch's normed input against the reference's FROM
    # THE TOKENS: [rms error over the rms, rms of the per-row scale - 1
    # (the norm statistic: rounding averages out over a row's columns)]
    inputs = {}
    for i, (y, _), y_ref in zip(FIRST_HAND, got["attention"],
                                ref["attention_inputs"]):
        row_scale = np.sum(y * y_ref, axis=1) / np.sum(y_ref * y_ref, axis=1)
        inputs[cfg["layer_types"][i]] = (
            _branch_errors(y, y_ref)[1],
            float(np.sqrt(np.mean(np.square(row_scale - 1.0)))))
    # the executable the window times, its steps 0 and 1 against the
    # reference's first step and its second after its own update
    steps = {}
    if timed is not None and "second_step" in ref:
        after, unmoved = ref["second_step"]
        steps = {"loss_timed_reference": [
                     [float(timed["losses"][0]), ref["loss"]],
                     [float(timed["losses"][1]), after]],
                 "second_loss_had_nothing_carried": unmoved}
        steps["err"] = [_rel(a, b) for a, b in steps["loss_timed_reference"]]
        steps["err_had_nothing_carried"] = _rel(unmoved, after)
    report = {
        "attention_branch_err_max_rms": attention,
        "window_branch_err_rms_by_reference_window": {
            str(w): band[w] for w in sorted(band)},
        "attention_input_err_rms_rowscale": inputs,
        "timed_steps": steps,
        "product_rows_written_held_chosen": rows,
        "router_bias_moved_by_the_rule": bias_moved,
        "config": cfg["name"], "rows": int(cfg["reference"]["rows"]),
        "expert": first + expert, "reference": cfg["reference"]["file"],
        "routing": route, "routing_inference": route_eval,
        "tokens_routed_alike_everywhere": float(same.mean()),
        "logits_err_max": main_max, "logits_err_rms": main_rms,
        "train_loss": [got["loss"], ref["loss"]],
        "train_loss_err": _rel(got["loss"], ref["loss"]),
        "global_grad_norm": [got["gnorm"], ref["gnorm"]],
        "global_grad_norm_err": _rel(got["gnorm"], ref["gnorm"]),
        "clip_scale": got["scale"],
        "clip_scale_err": _rel(got["scale"], min(
            1.0, o["clip_global_norm"] / got["gnorm"])),
        "by_param": by_param,
        "limits": {"routing_margin": ROUTING_MARGIN,
                   "routing_flip_max": ROUTING_FLIP_MAX,
                   "logits": LOGITS_TOL, "logits_rms": LOGITS_RMS_TOL,
                   "loss": LOSS_TOL, "grad_by_kind": GRAD_LIMITS,
                   "grad_else": GRAD_LIMITS_ELSE,
                   "global_grad_norm": GLOBAL_NORM_TOL,
                   "update": UPDATE_TOL, "clip_scale": CLIP_SCALE_TOL,
                   "attention": ATTENTION_TOL,
                   "attention_rms": ATTENTION_RMS_TOL,
                   "norm_scale": NORM_SCALE_TOL},
    }
    worst = {k: [f(v[k] for v in by_param.values() if v[k] is not None)
                 for f in (min, max)]
             for k in ("grad_cos", "grad_norm_ratio", "update_err")}
    report["worst"] = worst
    held = {
        "attention": len(attention) == len(FIRST_HAND) and all(
            np.isfinite(mx) and mx <= ATTENTION_TOL
            and rms <= ATTENTION_RMS_TOL for mx, rms in attention.values())
        and band[W] < min(band[W - 1], band[W + 1]),
        "norms": len(inputs) == len(FIRST_HAND) and all(
            np.isfinite(scale) and scale <= NORM_SCALE_TOL[i]
            for i, (_, scale) in zip(FIRST_HAND, inputs.values())),
        "routing": all(
            r["ok"] and r["flipped_share"] <= ROUTING_FLIP_MAX
            for r in route + route_eval),
        "logits": bool(np.isfinite(main_max) and main_max <= LOGITS_TOL
                       and main_rms <= LOGITS_RMS_TOL),
        "loss": report["train_loss_err"] <= LOSS_TOL,
        "global_grad_norm": report["global_grad_norm_err"]
        <= GLOBAL_NORM_TOL,
        "clip_scale": report["clip_scale_err"] <= CLIP_SCALE_TOL,
        "gradients": all(v["grad_ok"] for v in by_param.values()),
        "update": worst["update_err"][1] <= UPDATE_TOL,
        "product_rows": len(rows) == len(got["ids_eval"])
        and all(w == h == c for w, h, c in rows),
        "router_bias": len(bias_moved) == len(got["ids"])
        and all(bias_moved),
    }
    if timed is not None:
        held["timed_steps"] = len(steps.get("err", ())) == 2 and all(
            np.isfinite(e) and e <= LOSS_TOL for e in steps["err"])
    report["failed"] = sorted(k for k, v in held.items() if not v)
    report["ok"] = not report["failed"]
    return report


def against_reference(fluid, cfg, builder, place, seed, tokens, labels,
                      timed=None):
    """`tokens`, `labels`: int32 [2 x rows, S], the rows of the cell's own
    steps 0 and 1 (`rows` = `cfg["reference"]["rows"]` a step); `timed`:
    as `judge` takes it. Returns a report with `ok` and every number. The
    caller has freed the timed program's scope; the system's scope here is
    freed before the reference runs: `device_peak_bytes` is the process's
    high-water mark after the comparison."""
    import jax

    t0 = time.perf_counter()
    rows = int(cfg["reference"]["rows"])
    got = system_side(fluid, cfg, builder, place, seed, tokens[:rows],
                      labels[:rows])
    ref = reference_side(cfg, builder, got["w0"], tokens, labels,
                         [u for u, _ in got["attention"]])
    report = judge(cfg, builder, got, ref, timed)
    report["device_peak_bytes"] = int(memory_peak(jax.local_devices()))
    report["seconds"] = time.perf_counter() - t0
    return report
