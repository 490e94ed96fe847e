"""nemotron_3_nano_30b_a3b: how the configuration is handed to the system
under test."""

import os

from chipbench import harness
from chipbench.reference import \
    nemotron_3_nano_30b_a3b as reference  # noqa: F401

# a program from before the model existed cannot run the cell: say so as
# the harness reads the cell's files, before it takes the device
if not os.path.exists(os.path.join(harness.repo_root(), "paddle_tpu",
                                   "models", "nemotron_h.py")):
    raise harness.Refused(
        "this checkout has no paddle_tpu/models/nemotron_h.py: it cannot "
        "run the nemotron_3_nano_30b_a3b configuration")

P = reference.P


def build(fluid, cfg, seed, for_compare=False):
    """int32 token and label rows in, `paddle_tpu.models.nemotron_h`, the
    cross-entropy, AdamW with global-norm clipping, the rule that moves the
    bias of the choice (`assumed.e_score_correction_bias`; a speed of 0
    appends none); plus the inference clone taken before the optimizer is
    appended."""
    from paddle_tpu.models import nemotron_h as model

    S, opt = cfg["sequence_length"], cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = model.nemotron_h(tokens, cfg)
        loss = model.nemotron_h_loss(out, labels)
        test_prog = prog.clone(for_test=True)
        model.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(loss)
        if opt["router_bias_update_speed"]:
            model.balance_routers(prog, opt["router_bias_update_speed"])
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                logits=out["logits"], routing=out["routing"],
                expert_layers=out["expert_layers"],
                operators=out["operators"], mamba_ops=out["mamba_ops"],
                token_feed="tokens", label_feed="labels")


def first_hand_layers(cfg):
    """{what: program layer} whose branch the comparison holds first-hand:
    the first mixer (its input is the norm of the float32 embedding), the
    last mixer (behind every other kind), the attention layer, the first
    expert layer."""
    kinds = reference.layer_kinds(cfg)
    mixers = [i for i, k in enumerate(kinds) if k == reference.MAMBA]
    return {"mamba_first": mixers[0], "mamba_last": mixers[-1],
            "attention": kinds.index(reference.ATTENTION),
            "experts": kinds.index(reference.EXPERTS)}


def sampled_params(cfg):
    """What `compare_lm_ssd_share` compares of the gradient and the first
    update: a parameter of each kind. Of the first mixer W_in, the taps, the
    convolution's bias, A_log, dt_bias, D, the gated norm's scale and
    W_out; of the last mixer the taps, A_log, dt_bias and D again; W_q,
    W_k, W_v, W_o of the attention layer; the first expert layer's router
    and the last's; the shared expert's two matrices; of the stacked
    expert matrices the comparison picks one held expert; a block norm's
    scale; the table and the head."""
    at = first_hand_layers(cfg)
    first, last, attn, moe = (f"{P}l{at[k]}." for k in (
        "mamba_first", "mamba_last", "attention", "experts"))
    moe_last = "{}l{}.".format(P, max(
        i for i, k in enumerate(reference.layer_kinds(cfg))
        if k == reference.EXPERTS))
    return {"embedding": P + "embed", "head": P + "head",
            "w_in": first + "w_in", "conv_taps": first + "conv_taps",
            "conv_bias": first + "conv_bias", "A_log": first + "A_log",
            "dt_bias": first + "dt_bias", "D": first + "D",
            "gated_norm_scale": first + "gated_norm",
            "w_out": first + "w_out",
            "conv_taps_last": last + "conv_taps",
            "A_log_last": last + "A_log", "dt_bias_last": last + "dt_bias",
            "D_last": last + "D",
            "w_q": attn + "w_q", "w_k": attn + "w_k", "w_v": attn + "w_v",
            "w_o": attn + "w_o",
            "router": moe + "router", "router_last": moe_last + "router",
            "shared_up": moe + "shared_up",
            "shared_down": moe_last + "shared_down",
            "expert_up": moe_last + "up", "expert_down": moe_last + "down",
            "norm_scale": first + "norm"}
