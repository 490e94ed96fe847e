"""smallthinker_21b_a3b: how the configuration is handed to the system
under test."""

import os

from chipbench import harness
from chipbench.reference import smallthinker_21b_a3b as reference  # noqa: F401

# a program from before the model existed cannot run the cell: say so as
# the harness reads the cell's files, before it takes the device
if not os.path.exists(os.path.join(harness.repo_root(), "paddle_tpu",
                                   "models", "smallthinker.py")):
    raise harness.Refused("this checkout has no paddle_tpu/models/"
                          "smallthinker.py: it cannot run the "
                          "smallthinker_21b_a3b configuration")


def build(fluid, cfg, seed, for_compare=False):
    """int32 token and label rows in, `paddle_tpu.models.smallthinker`, the
    cross-entropy, AdamW with global-norm clipping, the stand-in update of
    the routers' biases (`assumed.router_balance`, a speed a layer; speeds
    all 0 append none: the bias then stays at zero and the choice is the
    model's own);
    plus the inference clone taken before the optimizer is appended."""
    from paddle_tpu.models import smallthinker

    S, opt = cfg["sequence_length"], cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = smallthinker.smallthinker(tokens, cfg)
        loss = smallthinker.smallthinker_loss(out, labels)
        test_prog = prog.clone(for_test=True)
        smallthinker.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(loss)
        speeds = opt["router_bias_update_speed_by_layer"]
        if any(speeds):
            smallthinker.balance_routers(
                prog, list(speeds[:cfg["num_hidden_layers"]]))
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                logits=out["logits"], routing=out["routing"],
                attention=out["attention"], token_feed="tokens",
                label_feed="labels")


def sampled_params(cfg):
    """What `compare_lm_early_route_share` compares of the gradient and the
    first update: a parameter of each kind. W_q and W_k of a full (no
    rotary) and of a window layer, W_v, W_o; the router of layer 0 (it
    reads embedding rows: its gradient and the embedding's hold the term
    that travels through `RouterInput`) and of a later layer; of the
    stacked expert matrices the comparison picks one held expert."""
    n = cfg["num_hidden_layers"]
    full = "smallthinker.l%d." % cfg["sliding_window_layout"][:n].index(0)
    win = "smallthinker.l%d." % cfg["sliding_window_layout"][:n].index(1)
    p = "smallthinker.l0."
    return {"head": "smallthinker.head", "embedding": "smallthinker.embed",
            "w_q_full": full + "w_q", "w_k_full": full + "w_k",
            "w_q_window": win + "w_q", "w_k_window": win + "w_k",
            "w_v": win + "w_v", "w_o": win + "w_o",
            "router": p + "router", "router_window": win + "router",
            "expert_gate": p + "gate", "expert_up": p + "up",
            "expert_down": p + "down", "norm_scale": p + "attn_norm"}
