"""qwen3_next_80b_a3b: how the configuration is handed to the system under
test."""

import os

from chipbench import harness
from chipbench.reference import qwen3_next_80b_a3b as reference  # noqa: F401

# a program from before the model existed cannot run the cell: say so as
# the harness reads the cell's files, before it takes the device
if not os.path.exists(os.path.join(harness.repo_root(), "paddle_tpu",
                                   "models", "qwen3_next.py")):
    raise harness.Refused(
        "this checkout has no paddle_tpu/models/qwen3_next.py: it cannot "
        "run the qwen3_next_80b_a3b configuration")

P = reference.P


def build(fluid, cfg, seed, for_compare=False):
    """int32 token and label rows in, `paddle_tpu.models.qwen3_next`, the
    cross-entropy, AdamW with global-norm clipping (no rule moves the zero
    bias of the choice: `assumed.expert_bias`); plus the inference clone
    taken before the optimizer is appended."""
    from paddle_tpu.models import qwen3_next as model

    S, opt = cfg["sequence_length"], cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = model.qwen3_next(tokens, cfg)
        loss = model.qwen3_next_loss(out, labels)
        test_prog = prog.clone(for_test=True)
        model.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(loss)
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                logits=out["logits"], routing=out["routing"],
                operators=out["operators"], delta_ops=out["delta_ops"],
                token_feed="tokens", label_feed="labels")


def first_hand_layers(cfg):
    """{what: program layer} whose operator branch the comparison holds
    first-hand: the first delta layer (its input is the norm of the float32
    embedding), the last delta layer of the period (behind two expert
    layers), the full-attention layer."""
    kinds = reference.layer_kinds(cfg)
    deltas = [i for i, k in enumerate(kinds) if k == reference.DELTA]
    return {"delta_first": deltas[0], "delta_last": deltas[-1],
            "attention": kinds.index(reference.FULL)}


def sampled_params(cfg):
    """What `compare_lm_delta_share` compares of the gradient and the
    first update: a parameter of each kind. Of the first delta layer W_qkvz,
    W_ba, the taps, A_log, dt_bias, the gated norm's scale and W_o; of the
    last delta layer W_ba, the taps, A_log and dt_bias again; W_qg, W_k,
    W_v, W_o and both per-head QK scales of the attention layer; a router;
    the shared expert's gate w_s and one of its matrices; of the stacked
    expert matrices the comparison picks one held expert; an operator
    norm's scale; the table and the head."""
    at = first_hand_layers(cfg)
    first, last, attn = (f"{P}l{at[k]}." for k in
                         ("delta_first", "delta_last", "attention"))
    return {"embedding": P + "embed", "head": P + "head",
            "w_qkvz": first + "w_qkvz", "w_ba": first + "w_ba",
            "conv_taps": first + "conv_taps", "A_log": first + "A_log",
            "dt_bias": first + "dt_bias",
            "gated_norm_scale": first + "gated_norm",
            "delta_w_o": first + "w_o",
            "w_ba_last": last + "w_ba", "conv_taps_last": last + "conv_taps",
            "A_log_last": last + "A_log", "dt_bias_last": last + "dt_bias",
            "w_qg": attn + "w_qg", "w_k": attn + "w_k", "w_v": attn + "w_v",
            "w_o": attn + "w_o", "q_scale": attn + "q_norm",
            "k_scale": attn + "k_norm",
            "router": first + "router", "router_attn": attn + "router",
            "shared_w": first + "shared_w", "shared_up": last + "shared_up",
            "expert_gate": last + "gate", "expert_up": last + "up",
            "expert_down": last + "down",
            "norm_scale": first + "operator_norm"}
