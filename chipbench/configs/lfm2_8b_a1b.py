"""lfm2_8b_a1b: how the configuration is handed to the system under test."""

import os

from chipbench import harness
from chipbench.reference import lfm2_8b_a1b as reference  # noqa: F401

# a program from before the model existed cannot run the cell: say so as
# the harness reads the cell's files, before it takes the device
if not os.path.exists(os.path.join(harness.repo_root(), "paddle_tpu",
                                   "models", "lfm2.py")):
    raise harness.Refused("this checkout has no paddle_tpu/models/lfm2.py: "
                          "it cannot run the lfm2_8b_a1b configuration")


def build(fluid, cfg, seed, for_compare=False):
    """int32 token and label rows in, `paddle_tpu.models.lfm2`, the
    cross-entropy, AdamW with global-norm clipping, the rule that moves the
    experts' bias (`assumed.expert_bias_rule`; a speed of 0 appends none);
    plus the inference clone taken before the optimizer is appended."""
    from paddle_tpu.models import lfm2

    S, opt = cfg["sequence_length"], cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = lfm2.lfm2(tokens, cfg)
        loss = lfm2.lfm2_loss(out, labels)
        test_prog = prog.clone(for_test=True)
        lfm2.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(loss)
        if opt["router_bias_update_speed"]:
            lfm2.balance_routers(prog, opt["router_bias_update_speed"])
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                logits=out["logits"], routing=out["routing"],
                operators=out["operators"],
                short_convs=out.get("short_convs", {}), token_feed="tokens",
                label_feed="labels")


def first_hand_layers(cfg):
    """{what: program layer} whose operator branch the comparison holds
    first-hand: the conv of the dense layer, the conv of the first sparse
    conv layer, the first attention layer."""
    kinds = reference.layer_kinds(cfg)
    dense = cfg["num_dense_layers"]
    return {
        "conv_dense": next(i for i, k in enumerate(kinds)
                           if k == reference.CONV and i < dense),
        "attention": kinds.index(reference.ATTENTION),
        "conv_sparse": next(i for i, k in enumerate(kinds)
                            if k == reference.CONV and i >= dense)}


def sampled_params(cfg):
    """What `compare_lm_short_conv_share` compares of the gradient and the
    first update: a parameter of each kind. W_in, W_out and the taps of the
    dense and of a sparse conv layer; W_q, W_k, W_v, W_o and both per-head
    QK scales of the attention layer; a dense MLP matrix; the router of the
    attention layer and of a conv layer; of the stacked expert matrices the
    comparison picks one held expert; an operator norm's scale; the TIED
    TABLE, whose gradient is the sum of the lookup's and the head's."""
    at = first_hand_layers(cfg)
    dense, sparse, attn = (f"lfm2.l{at[k]}." for k in
                           ("conv_dense", "conv_sparse", "attention"))
    return {"embedding": "lfm2.embed",
            "conv_in_dense": dense + "conv_in",
            "conv_taps_dense": dense + "conv_taps",
            "conv_out_dense": dense + "conv_out",
            "conv_in_sparse": sparse + "conv_in",
            "conv_taps_sparse": sparse + "conv_taps",
            "conv_out_sparse": sparse + "conv_out",
            "w_q": attn + "w_q", "w_k": attn + "w_k", "w_v": attn + "w_v",
            "w_o": attn + "w_o", "q_scale": attn + "q_layernorm",
            "k_scale": attn + "k_layernorm", "mlp_up": dense + "mlp_up",
            "router": attn + "router", "router_conv": sparse + "router",
            "expert_gate": sparse + "gate", "expert_up": sparse + "up",
            "expert_down": sparse + "down",
            "norm_scale": dense + "operator_norm"}
