"""The comparison that decides `correct` for a language model whose layers
are ONE branch each: MAMBA-2 MIXERS (a 4-tap convolution with a bias, then
a selective state-space scan whose state is carried along the row, a gated
grouped norm behind it), one grouped-query attention layer without
positions, and sigmoid-routed UN-GATED relu^2 experts beside a shared one,
that holds one chip's SHARE of the experts and of the vocabulary
(`nemotron_3_nano_30b_a3b`): the system under test against the
configuration's plain float32 reference (which is given the same share and
computes the scan TOKEN BY TOKEN), at the published widths, on the device
the cell runs on, outside the window, on the rows the cell's own window
starts with. In `compare_lm_delta_share`'s mould, whose helpers (and
`compare_lm`'s, `compare_lm_share`'s, `compare_lm_window_share`'s,
`compare_lm_early_route_share`'s) this file imports, not copies. Two
objects are set against the reference: (1) THE EXECUTABLE THE WINDOW TIMES,
its losses of steps 0 and 1 against the reference's first step and its
second after its own AdamW update; (2) a second build of the same program
run for one step with the gradients fetched, and its inference clone.

Compared on one row of 4096 tokens:

* THE OPS ALONE, first-hand, of the first and of the last mixer:
  `ssd_scan`'s output y [T, 64 x 64] AND the state behind the row's last
  token [64, 64, 128] against the reference's token-by-token recurrence on
  the op's own inputs ([x | B | C] as the system's convolution wrote them,
  dt as its projection did, the layer's A_log, dt_bias and D), AND THE
  SAME OP ONCE MORE IN FLOAT32 at full matmul precision on those inputs
  (`scan_in_float32`: what the op itself holds below float32. Through the
  step's bf16 operands the state reads 0.16% from the recurrence, and a
  state carried in bf16 only twice that: no limit with room tells them
  apart; in float32 the stated op stands 1e-6 away and a lowered state,
  decay or pass count 1e-3; its output is held WITHOUT THE SKIP TERM D x,
  which is nine tenths of y and exact: what the state carries is what a
  lowered pass count moves);
  `short_conv` (gating "silu", with its bias) against the reference's four
  shifted sums on the op's own input;
* FIRST-HAND BRANCHES: the mixer of layer 0 and of layer 7, the attention
  branch of layer 5 (32 query heads on 2 key/value heads of 128, no
  positions) and the expert branch of layer 1 (the held share and the
  shared expert; over the tokens whose six experts the system chose as the
  reference does on the same input), the system's output against the
  reference's ON THE SAME normed input;
* the first mixer's input itself against the reference's from the tokens:
  the rms of its per-row scale error (the norm statistic);
* routing of the four expert layers (the six chosen of 128), each judged on
  the tokens every layer before it routed alike: the share flipped, and
  every exchanged expert within `routing_margin` spreads of the
  reference's sixth score;
* logits per token over the tokens routed alike everywhere; the loss; the
  global gradient norm and the clip's scale;
* gradient cosine, norm ratio and first AdamW update of a sampled parameter
  of each kind (`sampled_params`), the update less one ulp of the element;
* every expert layer's `DownOut`: its non-zero rows are `RowsHeld` = the
  choices on the held experts.

THE LIMITS (`LIMITS`): one table, each limit with its reason and its two
readings: the largest the system gave as the configuration states it over
the builder's seeds on the chip ("stated"), with AT LEAST 3 x of room over
at least 8 fresh seeds (PRs 45-47 and 52 were refused over limits that
sound runs crossed), and the SYSTEM with one thing lowered (`python -m
chipbench.lower_precision_lm_ssd_share`, on the chip: the scan's carried
state in bf16, its decays in bf16, the router in bf16, the state's products
at one bf16 pass where the configuration says three): the least a plant
gave. Both tables: PERF.md section 6, PR 54.
"""

import gc
import time

import numpy as np

from chipbench.compare_lm import _clip_vars, _cos_ratio, _rel, _scalar
from chipbench.compare_lm_early_route_share import routing_report
from chipbench.compare_lm_share import _logits_errors as _errors_over
from chipbench.compare_lm_share import _products
from chipbench.compare_lm_window_share import _branch_errors

P = "nemotronh."
FIRST_HAND = ("mamba_first", "mamba_last", "attention", "experts")
MIXERS = ("mamba_first", "mamba_last")

# name: (limit, what it holds). THE READINGS BEHIND EACH, `stated` | the
# least a plant of the study gave that this limit is there for ("-": no
# plant reads against it: coarse, it holds a mechanism), stand in PERF.md
# section 6, PR 54, both tables whole; every limit has at least 3 x of
# room over the largest `stated` reading of the builder's seeds on the chip
# (my chip runs, PR 54: the cell's runs and the study's `stated` rows).
LIMITS = {
    "scan_op_rms": (0.002, "ssd_scan's output, rms over the reference's"),
    "scan_state_rms": (0.006, "the state behind the row's last token"),
    "scan_f32_op_rms": (2.75e-4, "the op alone in float32: its own precision"),
    "scan_f32_state_rms": (4e-4, "the same probe's last state"),
    "conv_op_rms": (0.006, "silu(conv4 + b): one bf16 rounding"),
    "mamba_max": (0.025, "a mixer branch, largest element"),
    "mamba_rms": (0.018, "a mixer branch, rms"),
    "attention_max": (0.015, "the attention branch, largest element"),
    "attention_rms": (0.012, "the attention branch, rms"),
    "experts_rms": (0.012, "the expert branch over tokens routed alike"),
    "norm_first_rowscale": (1e-6, "the norm of the float32 embedding"),
    "routing_flip_max": (0.35, "share of tokens with a choice exchanged"),
    "routing_margin": (1.0, "an exchanged expert's distance, in spreads"),
    "logits_max": (0.3, "logits a token, largest element"),
    "logits_rms": (0.05, "logits, rms"),
    "loss": (4.5e-4, "the loss, relative"),
    "timed_loss": (9e-4, "the timed scan's losses of steps 0 and 1"),
    "timed_twin": (2e-4, "the timed scan's step 0 and the second build's"),
    "global_grad_norm": (6.5e-4, "the global gradient norm, relative"),
    "clip_scale": (1e-5, "the clip's scale against 1 / norm"),
    "grad_cos_gap": (0.05, "1 - cosine of a sampled gradient"),
    "grad_norm_ratio": (0.12, "|norm ratio - 1| of a sampled gradient"),
    "update": (0.004, "the first AdamW update less an ulp of the element"),
}
# parameters whose gradient sums exponentials of the steps over the row or
# passes the discrete choice: their two gradient limits looser by this
# factor (the last router's cosine read 0.951 on one seed of seven)
LOOSE = ("A_log", "dt_bias", "router")
LOOSE_FACTOR = 3.0


def _logits_errors(got, ref, same):
    if not same.any():
        return float("inf"), float("inf")
    return _errors_over(got, ref, same)


def system_side(fluid, cfg, builder, place, seed, tokens, labels):
    """What the system computes on the row, as numpy: the weights the
    startup program drew (`w0`, every parameter), the inference program's
    logits, routing, the branches (input, output) of `FIRST_HAND` and the
    two mixers' own ops (the convolution's input and output, dt, the scan's
    output and last state), the training step's loss, routing, global norm,
    clip scale, clipped gradients and updated weights of the sampled
    parameters. Its scope is gone when this returns."""
    built = builder.build(fluid, cfg, seed, for_compare=True)
    picks = builder.sampled_params(cfg)
    at = builder.first_hand_layers(cfg)
    gnorm_var, scale_var = _clip_vars(built["prog"])
    feed = {built["token_feed"]: tokens, built["label_feed"]: labels}
    ids_vars = [r[0] for r in built["routing"]]
    branches = [v for k in FIRST_HAND for v in built["operators"][at[k]][1:]]
    own = [v for k in MIXERS for v in built["mamba_ops"][at[k]]]
    products = _products(built["test_prog"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(built["startup"])
        w0 = {p.name: np.asarray(scope.find_var(p.name), np.float32)
              for p in built["prog"].global_block().all_parameters()}
        evaled = exe.run(
            built["test_prog"], feed=feed,
            fetch_list=[built["logits"]] + branches + ids_vars + own
            + [n for pair in products for n in pair])
        n_ids = 1 + len(branches) + len(ids_vars)
        own_got = [np.asarray(v, np.float32)
                   for v in evaled[n_ids:n_ids + len(own)]]
        rest = evaled[n_ids + len(own):]
        rows_written = [
            (int(np.any(np.asarray(down) != 0, axis=1).sum()),
             int(np.asarray(held).reshape(-1)[0]))
            for down, held in zip(rest[::2], rest[1::2])]
        evaled = evaled[:n_ids]
        fetched = exe.run(
            built["prog"], feed=feed,
            fetch_list=[built["loss"], gnorm_var, scale_var] + ids_vars
            + [n + "@GRAD_clipped" for n in picks.values()])
        w1 = {k: np.asarray(scope.find_var(n)).astype(np.float32)
              for k, n in picks.items()}
    n_layers, n_b = len(ids_vars), len(branches)
    got = dict(zip(("loss", "gnorm", "scale"),
                   (_scalar(v) for v in fetched[:3])))
    mamba_ops = {k: tuple(own_got[5 * i:5 * i + 5])
                 for i, k in enumerate(MIXERS)}
    got.update(
        mamba_ops_float32=scan_in_float32(built["test_prog"], w0, at,
                                          mamba_ops),
        w0=w0, w1=w1, logits=np.asarray(evaled[0], np.float32),
        expert_layers=list(built["expert_layers"]),
        operators={k: (np.asarray(u, np.float32), np.asarray(o, np.float32))
                   for k, u, o in zip(FIRST_HAND, evaled[1:1 + n_b:2],
                                      evaled[2:1 + n_b:2])},
        # (conv in, conv out, dt, y, last state) a mixer
        mamba_ops=mamba_ops,
        ids_eval=[np.asarray(v) for v in evaled[1 + n_b:]],
        rows_written=rows_written,
        ids=[np.asarray(v) for v in fetched[3:3 + n_layers]],
        clipped={k: np.asarray(v).astype(np.float32)
                 for k, v in zip(picks, fetched[3 + n_layers:])})
    del scope, exe, fetched, evaled, built
    gc.collect()
    return got


def _skip_term(mixed, d, head_dim):
    """D x [T, H P] of the convolution's output [T, H P + 2 G N], D [H]."""
    inner = len(d) * head_dim
    return (mixed[:, :inner].reshape(len(mixed), len(d), head_dim)
            * d[:, None]).reshape(len(mixed), inner)


def scan_in_float32(prog, w0, at, mamba_ops):
    """{what: (output less the skip term D x [T, H P], last state) of the
    SYSTEM's `ssd_scan` op run alone on that mixer's own [x | B | C] and
    dt, as float32 arrays under full matmul precision}: the op's
    registered kernel function under
    the program's own attrs, every in-chunk product exact, so that only
    what the lowering itself holds below float32 is left (the state's
    products at the three bf16 passes it states). A program of this check's
    own and NOT the timed step, as `compare_lm_delta_share`'s probe is."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.lm_ops import ssd_scan_op

    ops = {op.input("ALog")[0]: op for op in prog.global_block().ops
           if op.type == "ssd_scan"}
    found = {}

    def alone(op):
        inner = int(op.attrs["num_heads"]) * int(op.attrs["head_dim"])
        width = int(op.attrs["num_groups"]) * int(op.attrs["state_size"])

        def run(mixed, dt, a_log, dt_bias, d):
            res = ssd_scan_op(None, {
                "X": [mixed[:, :inner]], "B": [mixed[:, inner:inner + width]],
                "C": [mixed[:, inner + width:]], "Dt": [dt], "ALog": [a_log],
                "DtBias": [dt_bias], "D": [d]}, op.attrs)
            return (res["Out"][0] - _skip_term(
                mixed, d, int(op.attrs["head_dim"])), res["FinalState"][0])

        return jax.jit(run)

    with jax.default_matmul_precision("highest"):
        for k, (_, mixed, dt, _, _) in mamba_ops.items():
            p = f"{P}l{at[k]}."
            out, last = alone(ops[p + "A_log"])(
                *(jnp.asarray(x, jnp.float32) for x in (
                    mixed, dt, w0[p + "A_log"], w0[p + "dt_bias"],
                    w0[p + "D"])))
            found[k] = (np.asarray(out), np.asarray(last))
    return found


def _layer_weights(w0, i):
    import jax.numpy as jnp

    return {k: jnp.asarray(v) for k, v in w0.items()
            if k.startswith(f"{P}l{i}.")}


def reference_branches(cfg, builder, w0, tokens, inputs):
    """{what: the reference's branch of that first-hand layer on the normed
    input the system itself fed its own, [T, C]}, and the experts the
    reference chooses on the expert layer's input [T, k]."""
    import jax
    import jax.numpy as jnp

    ref = builder.reference
    at = builder.first_hand_layers(cfg)
    found = {k: np.asarray(ref.layer_branch(
        cfg, _layer_weights(w0, at[k]), at[k],
        jnp.asarray(inputs[k]).reshape(tokens.shape + (-1,)))).reshape(
            tokens.size, -1) for k in FIRST_HAND}
    p = f"{P}l{at['experts']}."
    with jax.default_matmul_precision(ref.PRECISION):
        chosen = jax.jit(lambda w, u: ref.route(u, w, p, cfg)[2])(
            _layer_weights(w0, at["experts"]), jnp.asarray(inputs["experts"]))
    return found, np.asarray(chosen)


def reference_mamba_ops(cfg, builder, w0, tokens, op_inputs):
    """{what: (the reference's silu(conv4 + b) of the input the system's
    `short_conv` read, the token-by-token recurrence's output [T, H P] and
    last state [rows, H, P, N] on the inputs the system's `ssd_scan`
    read)}."""
    import jax
    import jax.numpy as jnp

    ref = builder.reference
    at = builder.first_hand_layers(cfg)
    found = {}

    def ops(w, p, x, mixed, dt):
        y, last = ref.ssm_scan(*ref.scan_inputs(mixed, dt, w, p, cfg))
        return (ref.silu_conv(x, w[p + "conv_taps"], w[p + "conv_bias"]), y,
                last)

    with jax.default_matmul_precision(ref.PRECISION):
        for k, arrays in op_inputs.items():
            p = f"{P}l{at[k]}."
            conv, y, last = jax.jit(lambda w, *a, p=p: ops(w, p, *a))(
                _layer_weights(w0, at[k]), *(
                    jnp.asarray(a).reshape(tokens.shape + (-1,))
                    for a in arrays))
            found[k] = (np.asarray(conv).reshape(tokens.size, -1),
                        np.asarray(y).reshape(tokens.size, -1),
                        np.asarray(last))
    return found


def reference_second_step(cfg, builder, wj, grads, tokens, labels):
    """The reference's loss on the rows of step 1 after ITS OWN first step
    (the first AdamW update of every trained weight behind the global
    clip), and the loss on the same rows had the first step left the state
    as it was: (loss, loss with nothing carried)."""
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


def reference_side(cfg, builder, w0, tokens, labels, inputs, op_inputs):
    """The plain reference on the same weights and rows, as numpy;
    `tokens` may hold the rows of a second step behind those of the first
    (`cfg["reference"]["rows"]`): `reference_second_step`. `inputs`,
    `op_inputs`: what the system fed its own branches and ops."""
    import jax.numpy as jnp

    ref, picks = builder.reference, builder.sampled_params(cfg)
    rows = int(cfg["reference"]["rows"])
    first, then = (tokens[:rows], labels[:rows]), (tokens[rows:2 * rows],
                                                    labels[rows:2 * rows])
    wj = {k: jnp.asarray(v) for k, v in w0.items()}
    t0, l0 = jnp.asarray(first[0]), jnp.asarray(first[1])
    loss, (logits, routing), grads = ref.loss_and_grads(cfg, wj, t0, l0)
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
    T = first[0].size
    side = dict(
        loss=float(loss), gnorm=gnorm,
        routing=[(np.asarray(b), np.asarray(t)) for b, t in routing],
        logits=np.asarray(logits).reshape(T, -1),
        grads={k: np.asarray(grads[n]) for k, n in picks.items()})
    del logits
    if len(then[0]):
        side["second_step"] = reference_second_step(cfg, builder, wj, grads,
                                                    *then)
    del grads
    at = builder.first_hand_layers(cfg)["mamba_first"]
    side["first_input"] = np.asarray(ref.rms_norm(
        wj[P + "embed"][t0], wj[f"{P}l{at}.norm"],
        cfg["layer_norm_epsilon"])).reshape(T, -1)
    del wj
    side["operators"], side["experts_chosen"] = reference_branches(
        cfg, builder, w0, first[0], inputs)
    side["mamba_ops"] = reference_mamba_ops(cfg, builder, w0, first[0],
                                            op_inputs)
    return side


def _routing_by_layer(ids, routing_ref, margin):
    """Each expert layer's report over the tokens that all earlier layers
    routed as the reference did, and the tokens every layer routed
    alike."""
    alike = np.ones(ids[0].shape[0], bool)
    reports = []
    for ids_l, (chosen_by, top) in zip(ids, routing_ref):
        rep, same = routing_report(ids_l[alike], chosen_by[alike],
                                   top[alike], margin)
        rep["tokens_alike_before"] = int(alike.sum())
        reports.append(rep)
        alike[alike] = same
    return reports, alike


def _limit(name, key=None):
    limit = LIMITS[name][0]
    if key is not None and name.startswith("grad_") \
            and any(s in key for s in LOOSE):
        limit *= LOOSE_FACTOR
    return limit


def judge(cfg, builder, got, ref, timed=None):
    """The report: every number, `compared` {limit: [reading, limit]} and
    which of them `failed`. `timed`: {"losses": the losses of steps 0 and 1
    as the TIMED executable fetched them}, where `ref` holds a second
    step."""
    picks = builder.sampled_params(cfg)
    at = builder.first_hand_layers(cfg)
    margin = LIMITS["routing_margin"][0]
    route, _ = _routing_by_layer(got["ids"], ref["routing"], margin)
    route_eval, same = _routing_by_layer(got["ids_eval"], ref["routing"],
                                         margin)
    main_max, main_rms = _logits_errors(got["logits"], ref["logits"], same)
    first = cfg["deployment"]["first_expert"]
    held_n = cfg["n_routed_experts"]
    n_all = cfg["deployment"]["n_routed_experts"]
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
        want = -o["learning_rate"] * (g_hat / (np.abs(g_hat) + eps)
                                      + decay * a)
        # less ONE ULP OF THE ELEMENT (as `compare_lm_sparse_attn_share`
        # has it since PR 49): at the cell's rate a step of 1e-6 is 2 ulps
        # of a dt_bias of -5 and 8 of a scale of 1, so the float32 sum
        # w + step itself stands up to half an ulp, a quarter of the step,
        # from the exact one (the first run read 0.44 on `dt_bias`)
        miss = np.maximum(np.abs((b - a) - want) - np.spacing(np.abs(a)), 0)
        by_param[key] = {
            "grad_cos": cos, "grad_norm_ratio": ratio,
            "update_err": float(miss.max()
                                / max(np.abs(want).max(), 1e-30))}
    operators = {k: _branch_errors(got["operators"][k][1],
                                   ref["operators"][k])
                 for k in FIRST_HAND if k != "experts"}
    # the expert branch over the tokens whose six the system chose as the
    # reference does ON THE SAME INPUT
    ids_first = got["ids_eval"][got["expert_layers"].index(at["experts"])]
    alike = (np.sort(ids_first, 1) == np.sort(ref["experts_chosen"], 1)) \
        .all(axis=1)
    operators["experts"] = _branch_errors(
        got["operators"]["experts"][1][alike],
        ref["operators"]["experts"][alike]) if alike.any() \
        else (float("inf"), float("inf"))
    conv_ops, scan_ops, scan_states, exact_ops, exact_states = ({}, {}, {},
                                                                  {}, {})
    for k in MIXERS:
        _, mixed, _, y, last = got["mamba_ops"][k]
        conv_ref, y_ref, last_ref = ref["mamba_ops"][k]
        conv_ops[k] = _branch_errors(mixed, conv_ref)
        scan_ops[k] = _branch_errors(y, y_ref)
        scan_states[k] = _branch_errors(last, last_ref)
        # what the state carries: y less D x of the op's own input
        p = f"{P}l{at[k]}."
        carried = y_ref - _skip_term(
            mixed.astype(np.float64), got["w0"][p + "D"].astype(np.float64),
            int(cfg["mamba_head_dim"]))
        exact_ops[k] = _branch_errors(got["mamba_ops_float32"][k][0], carried)
        exact_states[k] = _branch_errors(got["mamba_ops_float32"][k][1],
                                         last_ref)
    u, u_ref = got["operators"]["mamba_first"][0], ref["first_input"]
    row_scale = np.sum(u * u_ref, axis=1) / np.sum(u_ref * u_ref, axis=1)
    steps = {}
    if timed is not None and "second_step" in ref:
        after, unmoved = ref["second_step"]
        steps = {"loss_timed_reference": [
                     [float(timed["losses"][0]), ref["loss"]],
                     [float(timed["losses"][1]), after]],
                 "second_loss_had_nothing_carried": unmoved}
        steps["err"] = [_rel(a, b) for a, b in steps["loss_timed_reference"]]
        steps["err_had_nothing_carried"] = _rel(unmoved, after)
        steps["err_second_build"] = _rel(float(timed["losses"][0]),
                                         got["loss"])
    report = {
        "branch_err_max_rms": operators,
        "scan_op_err_max_rms": scan_ops,
        "scan_final_state_err_max_rms": scan_states,
        "scan_in_float32_op_err_max_rms": exact_ops,
        "scan_in_float32_final_state_err_max_rms": exact_states,
        "conv_op_err_max_rms": conv_ops,
        "first_input_err_rms_rowscale": [
            _branch_errors(u, u_ref)[1],
            float(np.sqrt(np.mean(np.square(row_scale - 1.0))))],
        "experts_branch_tokens_alike": float(alike.mean()),
        "timed_steps": steps,
        "product_rows_written_held_chosen": rows,
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
        "limits": {k: v[0] for k, v in LIMITS.items()},
    }
    report["compared"] = {name: [reading, limit] for name, (reading, limit)
                          in readings(report, timed is not None).items()}
    report["failed"] = verdict(report, timed is not None)
    report["ok"] = not report["failed"]
    return report


def readings(report, timed=False):
    """{the limit's name (a sampled parameter's: `name:parameter`): (the
    reading of a `judge` report it holds, the limit)}: `verdict` holds each
    reading to its limit THROUGH this table, so a limit and what it reads
    are spelt once."""
    branches = report["branch_err_max_rms"]
    routing = report["routing"] + report["routing_inference"]

    def worst(errors, part):
        return max(v[part] for v in errors.values())

    found = {
        "scan_op_rms": worst(report["scan_op_err_max_rms"], 1),
        "scan_state_rms": worst(report["scan_final_state_err_max_rms"], 1),
        "scan_f32_op_rms": worst(report["scan_in_float32_op_err_max_rms"], 1),
        "scan_f32_state_rms": worst(
            report["scan_in_float32_final_state_err_max_rms"], 1),
        "conv_op_rms": worst(report["conv_op_err_max_rms"], 1),
        "mamba_max": max(branches[k][0] for k in MIXERS),
        "mamba_rms": max(branches[k][1] for k in MIXERS),
        "attention_max": branches["attention"][0],
        "attention_rms": branches["attention"][1],
        "experts_rms": branches["experts"][1],
        "norm_first_rowscale": report["first_input_err_rms_rowscale"][1],
        "routing_flip_max": max(r["flipped_share"] for r in routing),
        "routing_margin": max(r["worst_gap_in_spreads"] for r in routing),
        "logits_max": report["logits_err_max"],
        "logits_rms": report["logits_err_rms"],
        "loss": report["train_loss_err"],
        "global_grad_norm": report["global_grad_norm_err"],
        "clip_scale": report["clip_scale_err"],
    }
    found = {k: (v, _limit(k)) for k, v in found.items()}
    for key, v in report["by_param"].items():
        cos, ratio = v["grad_cos"], v["grad_norm_ratio"]
        found["grad_cos_gap:" + key] = (
            None if cos is None else 1.0 - cos, _limit("grad_cos_gap", key))
        found["grad_norm_ratio:" + key] = (
            None if ratio is None else abs(ratio - 1.0),
            _limit("grad_norm_ratio", key))
        found["update:" + key] = (v["update_err"], _limit("update", key))
    if timed:
        steps = report["timed_steps"]
        errs = steps.get("err") or [None]
        found["timed_loss"] = (
            None if None in errs or len(errs) != 2 else max(errs),
            _limit("timed_loss"))
        found["timed_twin"] = (steps.get("err_second_build"),
                               _limit("timed_twin"))
    return found


def verdict(report, timed=False):
    """Which limits the numbers of a `judge` report fail, by name: the
    report's own numbers against THIS module's limits (a study's saved
    reports can be judged again after a limit was set from them)."""
    failed = {name.split(":")[0] for name, (reading, limit)
              in readings(report, timed).items()
              if reading is None or not np.isfinite(reading)
              or reading > limit}
    rows = report["product_rows_written_held_chosen"]
    if not (len(rows) == len(report["routing_inference"])
            and all(w == h == c for w, h, c in rows)):
        failed.add("product_rows")
    if not all(r["tokens"] for r in report["routing"]
               + report["routing_inference"]):
        failed.add("routing_tokens")
    return sorted(failed)


def against_reference(fluid, cfg, builder, place, seed, tokens, labels,
                      timed=None):
    """`tokens`, `labels`: int32 [2 x rows, S], the rows of the cell's own
    steps 0 and 1; `timed`: as `judge` takes it. Returns a report with
    `ok` and every number. The caller has freed the timed program's scope;
    the system's scope here is freed before the reference runs."""
    import jax

    from chipbench.harness import memory_peak

    t0 = time.perf_counter()
    rows = int(cfg["reference"]["rows"])
    got = system_side(fluid, cfg, builder, place, seed, tokens[:rows],
                      labels[:rows])
    gc.collect()
    inputs = {k: u for k, (u, _) in got["operators"].items()}
    op_inputs = {k: (x, mixed, dt)
                 for k, (x, mixed, dt, _, _) in got["mamba_ops"].items()}
    ref = reference_side(cfg, builder, got["w0"], tokens, labels, inputs,
                         op_inputs)
    report = judge(cfg, builder, got, ref, timed)
    report["device_peak_bytes"] = int(memory_peak(jax.local_devices()))
    report["seconds"] = time.perf_counter() - t0
    return report
