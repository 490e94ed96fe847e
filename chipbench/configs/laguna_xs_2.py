"""laguna_xs_2: how the configuration is handed to the system under
test."""

from chipbench.reference import laguna_xs_2 as reference  # noqa: F401


def build(fluid, cfg, seed, for_compare=False):
    """int32 token and label rows in, `paddle_tpu.models.laguna`, the
    cross-entropy, AdamW with global-norm clipping, the `noaux_tc`-style
    update of the routers' biases; plus the inference clone taken before
    the optimizer is appended."""
    from paddle_tpu.models import laguna

    S, opt = cfg["sequence_length"], cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = laguna.laguna(tokens, cfg)
        loss = laguna.laguna_loss(out, labels)
        test_prog = prog.clone(for_test=True)
        laguna.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(loss)
        laguna.balance_routers(prog, opt["router_bias_update_speed"])
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                logits=out["logits"], routing=out["routing"],
                attention=out["attention"], token_feed="tokens",
                label_feed="labels")


def sampled_params(cfg):
    """What `compare_lm_window_share` compares of the gradient and the
    first update: a parameter of each kind. W_q and W_k of a full and of a
    window layer, the gate, W_o; of the stacked expert matrices the
    comparison picks one held expert."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    sparse = cfg["mlp_layer_types"].index("sparse")
    full = "laguna.l%d." % kinds.index("full_attention")
    win = "laguna.l%d." % kinds.index("sliding_attention")
    p = "laguna.l%d." % sparse                  # the first expert layer
    return {"head": "laguna.head", "embedding": "laguna.embed",
            "w_q_full": full + "w_q", "w_k_full": full + "w_k",
            "w_q_window": win + "w_q", "w_k_window": win + "w_k",
            "w_v": win + "w_v", "w_g": win + "w_g", "w_o": win + "w_o",
            "router": p + "router", "expert_gate": p + "gate",
            "expert_up": p + "up", "expert_down": p + "down",
            "shared_gate": p + "shared_gate", "shared_up": p + "shared_up",
            "shared_down": p + "shared_down",
            "norm_scale": p + "attn_norm"}
