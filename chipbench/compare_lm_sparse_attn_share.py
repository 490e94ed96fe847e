"""The comparison that decides `correct` for a language model whose every
layer is grouped-query attention OVER THE KEYS A LEARNED INDEXER PICKS for
each query, the indexer trained beside the model by a loss of its own, and
softmax-routed held experts, that holds one chip's SHARE of the experts
and of the vocabulary (`keye_vl_2_0_30b_a3b`): the system under test
against the configuration's plain float32 reference (which is given the
same share, selects by `lax.top_k` a row and masks its softmax
explicitly), at the published widths, on the device the cell runs on,
outside the window, on the rows the cell's own window starts with. The
helpers of the other share cells' comparisons are imported, not copied. Two
objects are set against the reference: (1) THE EXECUTABLE THE WINDOW
TIMES, its losses of steps 0 and 1 against the reference's first step and
its second after its own AdamW update, and its losses of step 0 and of the
LAST step of its first chunk against the second build's own, run one step
at a time through that chunk (what tells a carried state from one left as
it was: PR 48); (2) a second build of the same program run step by step
with the gradients fetched, and its inference clone.

A CHOICE IS DISCRETE: bf16 operands flip near-ties of the indexer's
scores, and a query that attends to another key is another function. So
the SELECTION is compared as a selection (the share of (t, s) choices the
system and the reference agree on, and how far from the row's threshold,
in spreads of the row's scores, the farthest disagreement lies), and
EVERYTHING DOWNSTREAM OF A CHOICE IS COMPARED ON THE SYSTEM'S OWN CHOICE:
the reference takes the system's masks (`selections`) and, SINCE PR 56, THE
EXPERTS THE SYSTEM'S TRAINING STEP SENT EACH TOKEN TO (`routing`): it
weighs them by its own scores and returns its own free top-k beside, which
`routing` judges the system's choices against, as choices; the losses, the
norm and every gradient are then read over every token on a reference that
went where the system went (a router's gradient is a sum over the few
percent of a row its held experts see: a handful of tokens on the other
side of a bf16 near-tie moved the first router's norm by 2 - 7% and
refused the accepted program on three seeds of seven: PERF.md section 6,
PR 56).

Compared on one row of 8192 tokens, of the first and of the last layer
first-hand (`first_hand_layers`):

* THE INDEXER'S SCORES AS THE STEP FORMED THEM, by the one thing it
  writes of them: `indexer_select`'s `Threshold`, each query's least
  chosen score as the bisection found it, against the reference's least
  chosen score on the op's own inputs (q_I, k_I, w as the system's
  projections wrote them). A [S, S] float32 matrix is never written; a
  fresh call of the lowering's score function from here would hold
  nothing the step ran (review, PR 49);
* THE SELECTION: the system's mask against the reference's own choice from
  the layer's normed input (its whole float32 indexer): agreement, the
  farthest disagreement from the threshold, every row's count exact
  (min(t + 1, topk)) and nothing above the diagonal;
* THE SPARSE ATTENTION BRANCH, the head-mean probabilities p and L_I, on
  the system's own choice and the layer's own normed input;
* routing of the four layers (the eight chosen of 128), logits over the
  tokens routed alike everywhere, both losses, the global gradient norm
  and the clip's scale;
* gradient cosine, norm ratio and first AdamW update of a sampled
  parameter of each kind (`sampled_params`: W_qI, W_kI, the LayerNorm and
  W_w among them), and THE TWO PARAMETER SETS ARE DISJOINT: a build that
  minimises the cross-entropy alone reaches no parameter of an indexer,
  one that minimises the indexers' losses alone reaches nothing else
  (exact: no gradient variable exists);
* every layer's `DownOut`: its non-zero rows are `RowsHeld` = the choices
  on the held experts.

The limits, each from two readings: the largest the system gave as the
configuration states it over the builder's seeds on the chip ("stated"),
and the SYSTEM with one thing planted (`python -m
chipbench.lower_precision_lm_sparse_attn_share`, on the chip). The
readings stand beside each constant; PERF.md section 6, PR 49.
"""

import gc
import time

import numpy as np

from chipbench import held
from chipbench.compare_lm import _clip_vars, _cos_ratio, _rel, _scalar
from chipbench.compare_lm_early_route_share import routing_report
from chipbench.compare_lm_share import _logits_errors as _errors_over
from chipbench.compare_lm_share import _products, routing_by_layer
from chipbench.compare_lm_window_share import _branch_errors

# READINGS, every one from a run on the chip at the cell's size (my chip
# runs, PR 49): "stated" = the worst the system gave as the configuration
# states it, over the ten recorded runs of the cell's first session
# (`chiprun_out/pr49/`: three trees; the four of the final tree, the table
# at std 4, read inside the others' ranges), the study's seed and the
# review round's seven runs on fresh seeds | what the study's TWELVE
# variants read at seed 11 on the chip, calls 65 and 66
# (`lower_precision_lm_sparse_attn_share`): the plants that read against
# the number, the least of them first. A limit stands between the two,
# with room on both sides; one that no plant reads against says so: it
# holds a mechanism, not a precision. PERF.md section 6, PR 49.
# The scores as the step formed them: `Threshold` against the reference's
# least chosen score on the op's own inputs, rms over the row's queries of
# the reference's rms (the same bf16 operands, their products accumulated
# in float32 in another order): stated 6.3e-8 | `scores_bf16` 1.70e-3,
# `topk_1024` 0.64, `no_relu` 0.66, `whole_triangle` 1.8, `w_one` 45
THRESHOLD_RMS_TOL = 1e-5
# the share of the causal (t, s) pairs on which system and reference make
# the same choice (stated 0.99821 | `no_relu` 0.849, `topk_1024` 0.797,
# `w_one` 0.603, `whole_triangle` 0.437), and the farthest disagreement
# from the reference's threshold in spreads (std) of the row's causal
# scores (stated 0.051 | `no_relu` 2.9, `topk_1024` 6.2, `whole_triangle`
# 7.2, `w_one` 7.3): bf16 projections flip the near-ties, nothing else.
# (`scores_bf16` reads 0.99822 and 0.034 here, as `stated` does: bf16
# operands already make the flips a rounded score makes)
SELECTION_AGREE_MIN = 0.995
SELECTION_MARGIN = 0.15
# the attention branch on the system's own choice and input (max, rms):
# stated 0.0055, 0.0049 | `no_qk_norm` 0.090, 0.135, `triangle_softmax`
# 0.39, 0.49, `previous_selection` 0.48, 0.54
ATTENTION_TOL = 0.015
ATTENTION_RMS_TOL = 0.012
# the head-mean probabilities p over the chosen pairs (rms of the
# reference's rms: stated 9.6e-4 | `previous_selection` 0.019,
# `no_qk_norm` 0.057) and the indexer's loss (relative, a layer's and the
# model's: stated 1.3e-3 | `no_qk_norm` 0.11, `previous_selection` 0.62,
# `no_relu` 1.4, `w_one` 296; `triangle_softmax` 2.2e-3 is not told here)
PROBS_RMS_TOL = 0.004
INDEXER_LOSS_TOL = 0.01
# top-8 of 128 on bf16 streams flips 2-10% of a layer's tokens between
# near-tied experts: stated 0.098, every exchanged expert within 0.029
# spreads of the eighth logit | `triangle_softmax` 0.27, 0.37,
# `previous_selection` 0.31, 0.42 (`no_qk_norm` 0.14, 0.090: not told here)
ROUTING_FLIP_MAX = 0.25
ROUTING_MARGIN = 0.1
# over the tokens every layer routed alike (80-88% of the row): stated
# 0.0091 max, 0.0065 rms | `no_qk_norm` 0.033, 0.018 (the max alone),
# `triangle_softmax` 0.10, 0.034, `previous_selection` 0.14, 0.041
LOGITS_TOL = 0.03
LOGITS_RMS_TOL = 0.02
# the step's loss and the cross-entropy alone: stated 1.1e-4 |
# `triangle_softmax` 1.2e-3, `no_qk_norm` 3.5e-3, `previous_selection`
# 0.014, `no_relu` 0.042, `w_one` 17
LOSS_TOL = 1e-3
# the timed scan against the second build of the same program: step 0
# stated 4.2e-6, its LAST step 5.1e-6 | no plant of the study reads here
# (it runs no window); a scan that carried nothing reads 0.0052 - 0.068 at
# its last step in the same ten runs (each run's own second reading: the
# inference program's loss of that step's rows at the weights as drawn)
TIMED_TWIN_TOL = 5e-5
TIMED_TWIN_LAST_TOL = 1e-4
# stated 7.4e-4 | `no_qk_norm` 4.1e-3, `previous_selection` 0.078,
# `no_relu` 0.61 (`indexer_reads_u` 2.4e-3: not told here). The clip's
# scale is held to the system's OWN norm (stated 7.2e-8): no plant reads
# against it
GLOBAL_NORM_TOL = 3e-3
CLIP_SCALE_TOL = 1e-5
# the first AdamW update on the system's own gradient, EVERY sampled
# parameter, the table among them: the updated weight against the exact
# sum element by element, LESS ONE FLOAT32 ULP OF THE ELEMENT, as a share
# of the largest step (the table is drawn at std 4, `assumed.init`: a step
# of 1e-6 is under an ulp of its elements of 8 and more, so what a share
# of the step can hold of it is only what rounding does not explain; the
# first session's plain share read 0.405 there, against 1.0 for a table
# that did not move): stated 9.4e-4 (half an ulp of an element of 0.016 -
# 0.031, the matrices'; the table 3.1e-4, a norm scale 0) | `masters` 0.88
# (a norm scale of 1.0), 240 (every matrix at std 0.02), 2.2e4 (the table)
UPDATE_TOL = 0.03
# (least cosine, largest |norm ratio - 1|). The first router: stated
# 0.99957 / 0.0102 | `indexer_reads_u` 0.98706 / 0.070 (`no_qk_norm`,
# `previous_selection` by the ratio alone: 0.032, 0.031). The LAST layer's
# router and the sampled expert read the tokens' flips upstream: stated
# 0.99814 / 0.0163 and 0.99681 / 0.0345 over the final tree's runs (0.102
# the router's ratio on the first tree, whose routers had collapsed) |
# `triangle_softmax` 0.99051, `previous_selection` 0.98926 the router's
# cosine, `previous_selection` 0.97441 the expert's (`triangle_softmax`
# 0.99314: not told here); NO plant moves either RATIO past a sound run's
# (0.016, 0.031): the two ratio limits hold a scale (a missing 1 / top_k,
# a gradient counted twice), not a precision, and stand 3 and 3.5 times
# above the sound runs' worst; the last router's cosine limit (1 - cos
# 8e-3) stands 4.2 times over its sound worst (1.9e-3) and 1.2 times
# under the plants'. The indexers': stated 0.99978 / 0.0074 |
# `no_qk_norm` 0.99752 / 0.026 (the cosine alone),
# `previous_selection` 0.90137 / 0.78, `no_relu` 0.70352 / 4.3, `w_one`
# 0.0035 / 220. The others: stated 0.99996 / 0.0041 | `triangle_softmax`
# 0.99525 / 0.053 (W_q), `no_qk_norm` 0.98711 / 0.15, `indexer_reads_u`
# 0.61216 / 0.66 (a norm scale)
# PR 56, ON THE REFERENCE SENT WHERE THE SYSTEM'S EXPERTS WENT. The first
# router's (0.999, 0.015) STANDS AS IT STOOD: against the plain reference
# its ratio read 0.0714 and 0.0196 on PR 53's two refused seeds (1999000444,
# 1853000777; 1 - cos 2.5e-3, 6.6e-4), against the routed one 0.0045 and
# 0.0050 (1 - cos 2.4e-5), two more seeds 0.0003 and 0.0031: the limit has
# 3 times of room over them, and the plant `indexer_reads_u` reads 0.2725
# (1 - cos 0.0397) on the routed reference (seed 1906508178; 0.070 and
# 0.0129 against the plain one at seed 11): 18 and 40 times over. The
# last router's and the expert's, which no plant held from above (ratios
# 0.05 and 0.12: "a scale, not a precision"; the router's cosine 1.2 times
# under its plants), are set again from the routed rows: the last router 1 -
# cos <= 1.2e-5, ratio <= 0.0037; the expert 1 - cos <= 2.0e-5, ratio <=
# 0.0040 (three seeds) | `triangle_softmax` the expert's 1 - cos 0.0069,
# `previous_selection` 0.026, the router's 0.0095 / 0.0107 (plain rows, PR
# 49). Were (0.992, 0.05) and (0.99, 0.12): now some six times over the
# worst of three seeds on the ratios, and the cosines ten times under the
# plants that read 1.2 times over them or passed
GRAD_LIMITS = {"router": (0.999, 0.015), "router_last": (0.999, 0.025),
               "expert": (0.999, 0.03)}
GRAD_LIMITS_INDEXER = (0.999, 0.03)
GRAD_LIMITS_ELSE = (0.9995, 0.012)
INDEXER_KEYS = ("w_qi", "w_ki", "ki_norm", "w_w")


def _logits_errors(got, ref, same):
    if not same.any():
        return float("inf"), float("inf")
    return _errors_over(got, ref, same)


def _f32(v):
    return np.asarray(v, np.float32)


def system_side(fluid, cfg, builder, place, seed, tokens, labels,
                then=None):
    """What the system computes on the row, as numpy: the weights the
    startup program drew (`w0`), the inference program's routing, the
    first-hand layers' own values (the branch's normed input and output,
    q, k, q_I, k_I, w, the mask, the selection's thresholds, the
    logsumexp, L_I) and the products' rows; the training step's three
    losses, logits, masks of every layer, routing, global norm, clip
    scale, clipped gradients and updated weights of the sampled
    parameters; with `then` = (tokens, labels) of the steps behind the
    first, each step's loss behind the one before (`losses_next`) and,
    before any step, the inference program's loss of the first and of the
    last of those rows at the weights as drawn. Its scope is gone when
    this returns."""
    built = builder.build(fluid, cfg, seed, for_compare=True)
    picks = builder.sampled_params(cfg)
    at = builder.first_hand_layers(cfg)
    gnorm_var, scale_var = _clip_vars(built["prog"])
    feed = {built["token_feed"]: tokens, built["label_feed"]: labels}
    ids_vars = [r[0] for r in built["routing"]]
    masks = [own[2][6] for own in built["attention"]]
    own = []
    for i in at:
        u, branch, (q, k, _, q_i, k_i, w, mask, lse, _, threshold) = \
            built["attention"][i]
        own += [u, branch, q, k, q_i, k_i, w, mask, lse, threshold,
                built["indexer_losses"][i]]
    products = _products(built["test_prog"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(built["startup"])
        w0 = {p.name: _f32(scope.find_var(p.name))
              for p in built["prog"].global_block().all_parameters()}
        evaled = exe.run(built["test_prog"], feed=feed, fetch_list=(
            ids_vars + own + [n for pair in products for n in pair]))
        n_ids, n_own = len(ids_vars), len(own)
        rows_written = [
            (int(np.any(np.asarray(down) != 0, axis=1).sum()),
             int(np.asarray(held).reshape(-1)[0]))
            for down, held in zip(evaled[n_ids + n_own::2],
                                  evaled[n_ids + n_own + 1::2])]
        evaled = [np.asarray(v) for v in evaled[:n_ids + n_own]]
        rows = len(tokens)
        behind = [] if then is None else [
            (then[0][i:i + rows], then[1][i:i + rows])
            for i in range(0, len(then[0]), rows)]
        unmoved = [None, None]
        if behind:
            unmoved = [_scalar(exe.run(
                built["test_prog"], fetch_list=[built["loss"]],
                feed={built["token_feed"]: t, built["label_feed"]: l})[0])
                for t, l in ((tokens, labels), behind[-1])]
        step_fetches = [built["loss"], built["ce"], built["indexer_loss"],
                        gnorm_var, scale_var] + ids_vars
        fetched = exe.run(built["prog"], feed=feed, fetch_list=(
            step_fetches + [built["logits"]] + masks
            + [n + "@GRAD_clipped" for n in picks.values()]))
        w1 = {k: _f32(scope.find_var(n)) for k, n in picks.items()}
        losses_next = [_scalar(exe.run(
            built["prog"], fetch_list=step_fetches,
            feed={built["token_feed"]: t, built["label_feed"]: l})[0])
            for t, l in behind]
    n_l = len(ids_vars)
    got = dict(zip(("loss", "ce", "indexer_loss", "gnorm", "scale"),
                   (_scalar(v) for v in fetched[:5])))
    names = ("u", "branch", "q", "k", "q_i", "k_i", "w", "mask", "lse",
             "threshold", "indexer_loss")
    got.update(
        losses_next=losses_next, loss_unmoved_first=unmoved[0],
        loss_unmoved_last=unmoved[1], w0=w0, w1=w1,
        ids_eval=evaled[:n_ids],
        own={i: dict(zip(names, evaled[n_ids + len(names) * j:
                                       n_ids + len(names) * (j + 1)]))
             for j, i in enumerate(at)},
        rows_written=rows_written,
        ids=[np.asarray(v) for v in fetched[5:5 + n_l]],
        logits=_f32(fetched[5 + n_l]),
        masks=[np.asarray(v) for v in fetched[6 + n_l:6 + 2 * n_l]],
        clipped={k: _f32(v) for k, v in zip(picks, fetched[6 + 2 * n_l:])})
    del scope, exe, fetched, evaled
    # which parameters each part of the loss reaches: two more builds,
    # nothing run (`append_backward` declares a gradient for what it
    # reaches and for nothing else)
    got["reached"] = {part: builder.build(fluid, cfg, seed,
                                          loss_of=part)["reached"]
                      for part in ("ce", "indexer")}
    got["reached"]["both"] = built["reached"]
    del built
    gc.collect()
    return got


def _selection_report(jnp, I, chosen_ref, mask, topk):
    """The system's mask [S, S] against the reference's scores and choice:
    (share of the causal pairs chosen alike, the farthest disagreement from
    the row's threshold in spreads of the row's causal scores, whether
    every row of the mask holds min(t + 1, topk) keys and none above the
    diagonal)."""
    S = I.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    sys_c = jnp.asarray(mask) != 0
    differs = (sys_c ^ chosen_ref) & causal
    n = jnp.sum(causal, axis=1)
    mean = jnp.sum(jnp.where(causal, I, 0.0), axis=1) / n
    std = jnp.sqrt(jnp.sum(jnp.where(causal, jnp.square(I - mean[:, None]),
                                     0.0), axis=1) / n)
    # the threshold: the least chosen score of the reference's row
    tau = jnp.min(jnp.where(chosen_ref, I, jnp.inf), axis=1)
    gap = jnp.abs(I - tau[:, None]) / jnp.maximum(std, 1e-30)[:, None]
    counts = jnp.sum(sys_c, axis=1)
    exact = jnp.all(counts == jnp.minimum(jnp.arange(S) + 1, topk)) \
        & ~jnp.any(sys_c & ~causal)
    return (float(1.0 - jnp.sum(differs) / jnp.sum(causal)),
            float(jnp.max(jnp.where(differs, gap, 0.0))), bool(exact))


def first_hand(cfg, builder, w0, own):
    """The first-hand layers, system against reference, a layer at a time
    on the device: {layer: {threshold_rms, selection (agree, margin,
    exact), branch (max, rms), probs_rms, indexer_loss [system,
    reference]}}."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import sparse_index

    ref, report = builder.reference, {}
    topk, D = cfg["sa_config"]["topk"], cfg["head_dim"]
    S = cfg["sequence_length"]

    def threshold_of(q_i, k_i, w_i):
        chosen = ref.selection(q_i, k_i, w_i, topk)
        return jnp.min(jnp.where(chosen, ref.indexer_scores(q_i, k_i, w_i),
                                 jnp.inf), axis=-1)

    for i, got in own.items():
        u = jnp.asarray(got["u"], jnp.float32).reshape(1, S, -1)
        q_i, k_i, w = (jnp.asarray(got[k])[0] for k in ("q_i", "k_i", "w"))
        mask = jnp.asarray(got["mask"])
        # the scores AS THE STEP FORMED THEM, by the one thing it writes of
        # them: each query's least chosen score (`Threshold`, the
        # bisection's own) against the reference's least chosen score on
        # the op's own inputs
        with jax.default_matmul_precision(ref.PRECISION):
            tau = np.asarray(jax.jit(threshold_of)(*(
                t.astype(jnp.float32)[None] for t in (q_i, k_i[:, 0], w))))
        threshold_rms = float(
            np.sqrt(np.mean((_f32(got["threshold"]) - tau) ** 2))
            / np.sqrt(np.mean(tau ** 2)))
        # the selection: the reference's whole indexer from the layer's
        # normed input
        I, chosen = ref.indexer_of(cfg, w0, i, u)
        selection = _selection_report(jnp, I[0], chosen[0], mask[0], topk)
        del I, chosen
        # the branch, p and L_I on the system's own choice
        branch, loss, _ = ref.attention_branch(cfg, w0, i, u, mask != 0)
        p_ref = ref.probabilities_of(cfg, w0, i, u, mask != 0)[0]
        q, k = (jnp.swapaxes(jnp.asarray(got[n])[0], 0, 1)
                for n in ("q", "k"))
        p_sys = jax.jit(lambda *a: sparse_index.head_mean(*a, D ** -0.5))(
            q, k, jnp.asarray(got["lse"])[0], mask[0])
        picked = np.asarray(mask[0]) != 0
        p_diff = np.asarray(p_sys - p_ref)[picked]
        report[i] = {
            "threshold_rms": threshold_rms, "selection": selection,
            "branch": _branch_errors(_f32(got["branch"]),
                                     np.asarray(branch).reshape(S, -1)),
            "probs_rms": float(np.sqrt(np.mean(p_diff ** 2)) / np.sqrt(
                np.mean(np.asarray(p_ref)[picked] ** 2))),
            "indexer_loss": [_scalar(got["indexer_loss"]), float(loss)]}
        del p_sys, p_ref, branch
    return report


def reference_second_step(cfg, builder, wj, grads, tokens, labels):
    """The reference's loss on the rows of step 1 after ITS OWN first step
    (the first AdamW update of every trained weight behind the global
    clip, the indexers' among them, on its own choice), and the loss on
    the same rows had the first step left the state as it was."""
    import jax
    import jax.numpy as jnp

    ref, o = builder.reference, cfg["optimizer"]
    delta, _ = ref.adamw_first_update(
        cfg, wj, grads, epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    w1 = dict(wj)
    for name in list(delta):
        w1[name] = wj[name] + delta.pop(name)
    with jax.default_matmul_precision(ref.PRECISION):
        loss = jax.jit(lambda w_, t, l: ref.loss_fn(cfg, w_, t, l)[0])
        t, l = jnp.asarray(tokens), jnp.asarray(labels)
        return float(loss(w1, t, l)), float(loss(wj, t, l))


def reference_pass(cfg, builder, got, tokens, labels, routed=True):
    """The reference's pass over the rows ON THE SYSTEM'S OWN CHOICES (the
    training step's masks and, with `routed`, the experts it sent each
    token to: `loss_and_grads(routing=)`; the reference's own free top-k
    beside, `routing`), as numpy; `tokens` may hold the rows of a second
    step behind those of the first."""
    import jax.numpy as jnp

    ref, picks = builder.reference, builder.sampled_params(cfg)
    rows = int(cfg["reference"]["rows"])
    first, then = (tokens[:rows], labels[:rows]), (tokens[rows:2 * rows],
                                                    labels[rows:2 * rows])
    wj = {k: jnp.asarray(v) for k, v in got["w0"].items()}
    t0, l0 = jnp.asarray(first[0]), jnp.asarray(first[1])
    loss, (logits, routing, ce, losses, _), grads = ref.loss_and_grads(
        cfg, wj, t0, l0, [jnp.asarray(m) != 0 for m in got["masks"]],
        routing=[jnp.asarray(v) for v in got["ids"]] if routed else None)
    T = first[0].size
    side = dict(
        loss=float(loss), ce=float(ce), routed=bool(routed),
        indexer_loss=float(sum(losses)),
        gnorm=float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))),
        routing=[(np.asarray(b), np.asarray(t)) for b, t in routing],
        logits=np.asarray(logits).reshape(T, -1),
        grads={k: np.asarray(grads[n]) for k, n in picks.items()})
    del logits
    if len(then[0]):
        side["second_step"] = reference_second_step(cfg, builder, wj, grads,
                                                    *then)
    return side


def reference_side(cfg, builder, got, tokens, labels, routed=True):
    """`reference_pass` and the first-hand layers (`first_hand`)."""
    import jax.numpy as jnp

    side = reference_pass(cfg, builder, got, tokens, labels, routed)
    gc.collect()
    side["first_hand"] = first_hand(cfg, builder, {
        k: jnp.asarray(v) for k, v in got["w0"].items()
        if k.startswith(tuple(f"{builder.P}l{i}." for i in got["own"]))},
        got["own"])
    return side


def reference_of(cfg, builder, got, tokens, labels, routed=True,
                 whole=True):
    """The reference for the system side `got`: on its masks, and sent
    where its training step's experts went (`routed`) or routed by itself;
    `whole`: with the first-hand layers, else the pass alone (one signature
    in the three share comparisons: `chipbench.census` reads every seed
    both ways)."""
    side = reference_side if whole else reference_pass
    return side(cfg, builder, got, tokens, labels, routed)


def _routing_by_layer(ids, routing_ref, sent=None):
    return routing_by_layer(routing_report, ROUTING_MARGIN, ids,
                            routing_ref, sent)


def _grad_limits(key):
    if key.startswith("expert_"):
        return GRAD_LIMITS["expert"]
    if key.startswith(INDEXER_KEYS):
        return GRAD_LIMITS_INDEXER
    return GRAD_LIMITS.get(key, GRAD_LIMITS_ELSE)


def _timed_steps(got, ref, timed):
    after, unmoved = ref["second_step"]
    steps = {"loss_timed_reference": [
                 [float(timed["losses"][0]), ref["loss"]],
                 [float(timed["losses"][1]), after]],
             "second_loss_had_nothing_carried": unmoved}
    steps["err"] = [_rel(a, b) for a, b in steps["loss_timed_reference"]]
    own = [got["loss"]] + list(got["losses_next"])
    scan = [float(v) for v in timed.get("chunk_losses",
                                        timed["losses"])][:len(own)]
    steps.update(loss_second_build=own, loss_timed=scan,
                 err_second_build=[_rel(t, o) for t, o in zip(scan, own)])
    if len(own) > 1 and got.get("loss_unmoved_last") is not None:
        steps["err_second_build_last"] = steps["err_second_build"][-1]
        steps["loss_unmoved_first_last"] = [got["loss_unmoved_first"],
                                            got["loss_unmoved_last"]]
        # what a scan that never carried its state would read at its last
        # step: the weights as drawn, on that step's rows
        steps["err_last_had_nothing_carried"] = _rel(
            got["loss_unmoved_last"], own[-1])
    return steps


def judge(cfg, builder, got, ref, timed=None):
    """The report: every number, the limits, which of them `failed`."""
    picks = builder.sampled_params(cfg)
    # the reference went where the TRAINING step went (`routed`): its
    # logits compare on every token, the inference program's choices where
    # it went there too
    sent = got["ids"] if ref.get("routed") else None
    route, same = _routing_by_layer(got["ids"], ref["routing"], sent)
    route_eval, _ = _routing_by_layer(got["ids_eval"], ref["routing"], sent)
    main_max, main_rms = _logits_errors(got["logits"], ref["logits"], same)
    first = cfg["deployment"]["first_expert"]
    held_n, n_all = cfg["num_experts"], cfg["deployment"]["num_experts"]
    counts = np.bincount(ref["routing"][-1][1].ravel(), minlength=n_all)
    expert = int(counts[first:first + held_n].argmax())
    rows = [[written, held, int(((ids >= first)
                                 & (ids < first + held_n)).sum())]
            for (written, held), ids in zip(got["rows_written"],
                                            got["ids_eval"])]
    o = cfg["optimizer"]
    eps = o["epsilon"] / np.sqrt(1.0 - o["beta2"])
    by_param = {}
    for key, name in picks.items():
        g_hat, g_ref = got["clipped"][key], ref["grads"][key]
        a, b = got["w0"][name], got["w1"][key]
        if key.startswith("expert_"):
            g_hat, g_ref, a, b = (v[expert] for v in (g_hat, g_ref, a, b))
        cos, ratio = _cos_ratio(g_hat / got["scale"], g_ref)
        decay = o["weight_decay"] if builder.reference.decays(name) else 0.0
        g64, a64 = g_hat.astype(np.float64), a.astype(np.float64)
        want = -o["learning_rate"] * (g64 / (np.abs(g64) + eps)
                                      + decay * a64)
        # the updated weight against the exact sum, element by element,
        # less ONE FLOAT32 ULP OF THE ELEMENT (the op's two subtractions
        # round half an ulp each), as a share of the largest step
        ulp = np.spacing(np.maximum(np.abs(a), np.abs(b)))
        by_param[key] = {
            "grad_cos": cos, "grad_norm_ratio": ratio,
            "update_err": float(max(
                (np.abs(b - (a64 + want)) - ulp).max(), 0.0)
                / max(np.abs(want).max(), 1e-30))}
    indexer = builder.reference.of_the_indexer
    reached = got["reached"]
    disjoint = {
        "ce_reaches_no_indexer": not any(map(indexer, reached["ce"])),
        "indexer_reaches_nothing_else": bool(reached["indexer"]) and all(
            map(indexer, reached["indexer"])),
        "together_they_reach_all": sorted(
            reached["ce"] + reached["indexer"]) == reached["both"]}
    report = {
        "first_hand": {str(i): v for i, v in ref["first_hand"].items()},
        "gradient_sets": disjoint,
        "timed_steps": _timed_steps(got, ref, timed)
        if timed is not None and "second_step" in ref else {},
        "product_rows_written_held_chosen": rows,
        "config": cfg["name"], "rows": int(cfg["reference"]["rows"]),
        "expert": first + expert, "reference": cfg["reference"]["file"],
        "reference_routed_as_the_system": bool(ref.get("routed")),
        # each layer's routing judged on the tokens sent, so far, where the
        # reference went (`_routing_by_layer(sent=)`)
        "routing_judged_where_sent": True,
        "routing": route, "routing_inference": route_eval,
        "tokens_routed_alike_everywhere": float(same.mean()),
        "logits_err_max": main_max, "logits_err_rms": main_rms,
        "train_loss": [got["loss"], ref["loss"]],
        "train_loss_err": _rel(got["loss"], ref["loss"]),
        "cross_entropy": [got["ce"], ref["ce"]],
        "cross_entropy_err": _rel(got["ce"], ref["ce"]),
        "indexer_loss": [got["indexer_loss"], ref["indexer_loss"]],
        "indexer_loss_err": _rel(got["indexer_loss"], ref["indexer_loss"]),
        "global_grad_norm": [got["gnorm"], ref["gnorm"]],
        "global_grad_norm_err": _rel(got["gnorm"], ref["gnorm"]),
        "clip_scale": got["scale"],
        "clip_scale_err": _rel(got["scale"], min(
            1.0, o["clip_global_norm"] / got["gnorm"])),
        "by_param": by_param,
    }
    report["failed"] = verdict(report, timed is not None)
    report["ok"] = not report["failed"]
    # every number `verdict` read beside its limit, the failing ones
    # first: the harness prints these last, on standard error and in the
    # result's line
    report["compared"] = held.compared(numbers_held(report))
    return report


def numbers_held(report, timed=False):
    """{the number's name: (the reading of a `judge` report, its limit)} of
    EVERY number `verdict` reads (the timed scan's wherever the report holds
    them, whatever `timed` says: `verdict` picks the checks), each entry
    reading `reading <= limit` (an agreement or a cosine as 1 - it, a norm
    ratio as |ratio - 1|, an exact check as a count against 0). `verdict`
    holds the readings THROUGH this table, the run prints it last
    (`compared`, the failing ones first) and `chipbench.limits_study` lays
    the part set again (`SET_AGAIN`) over the rows on record, so a limit
    and what it reads are spelt once (`chipbench/held.py`)."""
    hand = report["first_hand"].values()
    routing = report["routing"] + report["routing_inference"]
    steps = report.get("timed_steps") or {}
    by_param = report["by_param"]

    def worst(key, pick, of=max):
        return of(pick(v[key]) for v in hand)

    found = {
        "THRESHOLD_RMS_TOL": (worst("threshold_rms", float),
                              THRESHOLD_RMS_TOL),
        "SELECTION_AGREE_MIN": (1.0 - worst("selection", lambda s: s[0], min),
                                1.0 - SELECTION_AGREE_MIN),
        "SELECTION_MARGIN": (worst("selection", lambda s: s[1]),
                             SELECTION_MARGIN),
        "SELECTION rows not of min(t + 1, topk) causal keys": (
            sum(not v["selection"][2] for v in hand), 0),
        "ATTENTION_TOL": (worst("branch", lambda b: b[0]), ATTENTION_TOL),
        "ATTENTION_RMS_TOL": (worst("branch", lambda b: b[1]),
                              ATTENTION_RMS_TOL),
        "PROBS_RMS_TOL": (worst("probs_rms", float), PROBS_RMS_TOL),
        "INDEXER_LOSS_TOL": (max(
            [_rel(*v["indexer_loss"]) for v in hand]
            + [report["indexer_loss_err"]]), INDEXER_LOSS_TOL),
        "ROUTING_FLIP_MAX": (max(r["flipped_share"] for r in routing),
                             ROUTING_FLIP_MAX),
        "ROUTING_MARGIN": (max(r["worst_gap_in_spreads"] for r in routing),
                           ROUTING_MARGIN),
        "ROUTING layers judged on no token": (
            sum(not r["tokens"] for r in routing), 0),
        "LOGITS_TOL": (report["logits_err_max"], LOGITS_TOL),
        "LOGITS_RMS_TOL": (report["logits_err_rms"], LOGITS_RMS_TOL),
        "LOSS_TOL": (max(report["train_loss_err"],
                         report["cross_entropy_err"]), LOSS_TOL),
        "GLOBAL_NORM_TOL": (report["global_grad_norm_err"], GLOBAL_NORM_TOL),
        "CLIP_SCALE_TOL": (report["clip_scale_err"], CLIP_SCALE_TOL),
        "UPDATE_TOL": (max(v["update_err"] for v in by_param.values()),
                       UPDATE_TOL),
        "GRADIENT_SETS not disjoint or not whole": (
            sum(not v for v in report["gradient_sets"].values()), 0),
        **held.product_rows(report),
    }
    found.update(held.gradients(by_param, _grad_limits))
    if steps:
        found["TIMED LOSS_TOL"] = (max(steps["err"]), LOSS_TOL)
        found["TIMED_TWIN_TOL"] = (steps["err_second_build"][0],
                                   TIMED_TWIN_TOL)
        found["TIMED_TWIN_LAST_TOL"] = (steps.get("err_second_build_last"),
                                        TIMED_TWIN_LAST_TOL)
    return found


# which numbers each check holds, by the prefix of their names
CHECKS = {"indexer_scores": ("THRESHOLD_RMS_TOL",),
          "selection": ("SELECTION",), "sparse_attention": ("ATTENTION_",),
          "head_mean_probabilities": ("PROBS_RMS_TOL",),
          "indexer_loss": ("INDEXER_LOSS_TOL",), "routing": ("ROUTING",),
          "logits": ("LOGITS",), "loss": ("LOSS_TOL",),
          "global_grad_norm": ("GLOBAL_NORM_TOL",),
          "clip_scale": ("CLIP_SCALE_TOL",), "gradients": ("GRAD[",),
          "gradient_sets_disjoint": ("GRADIENT_SETS",),
          "update": ("UPDATE_TOL",), "product_rows": ("product_rows",)}
TIMED_CHECKS = {"timed_steps": ("TIMED LOSS_TOL",),
                "timed_steps_second_build": ("TIMED_TWIN",)}
# the limits set again from rows on record (PR 56: what the routed
# reference let tighten, and what its census left under M), which
# `limits_study table` and its test hold to M
SET_AGAIN = tuple(f"GRAD[{k}] {what}" for k in (
    "router", "router_last", "expert_gate", "expert_up", "expert_down")
    for what in ("1 - cos", "ratio"))
# the numbers that read otherwise once the reference is routed as the
# system routed: a row read against the plain reference says nothing of
# their limits (`limits_study`)
FOLLOWS_ROUTING = ("ROUTING", "LOGITS", "LOSS_TOL", "GLOBAL_NORM_TOL",
                   "GRAD[", "INDEXER_LOSS_TOL", "TIMED LOSS_TOL")


def numbers_set_again(report):
    return held.set_again(numbers_held(report), SET_AGAIN)


def verdict(report, timed=False, without=()):
    """Which checks the numbers of a `judge` report fail, by name: the
    report's own numbers against THIS module's limits; `without`: name
    prefixes of numbers a record does not hold."""
    return held.failed_checks(
        numbers_held(report),
        dict(CHECKS, **(TIMED_CHECKS if timed else {})), without)


def against_reference(fluid, cfg, builder, place, seed, tokens, labels,
                      timed=None):
    """`tokens`, `labels`: int32 [2 x rows, S], the rows of the cell's own
    steps 0 and 1; `timed`: {"losses", "chunk_losses", "chunk_rows"} of the
    timed scan. Returns a report with `ok` and every number. The caller
    has freed the timed program's scope; the system's scope here is freed
    before the reference runs."""
    import jax

    from chipbench.harness import memory_peak

    t0 = time.perf_counter()
    rows = int(cfg["reference"]["rows"])
    t_all, l_all = (timed or {}).get("chunk_rows", (tokens, labels))
    got = system_side(fluid, cfg, builder, place, seed, tokens[:rows],
                      labels[:rows], then=(t_all[rows:], l_all[rows:]))
    gc.collect()
    ref = reference_of(cfg, builder, got, tokens, labels)
    report = judge(cfg, builder, got, ref, timed)
    report["device_peak_bytes"] = int(memory_peak(jax.local_devices()))
    report["seconds"] = time.perf_counter() - t0
    return report
