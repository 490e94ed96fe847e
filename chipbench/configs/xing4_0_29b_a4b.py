"""xing4_0_29b_a4b: how the configuration is handed to the system under
test."""

from chipbench.reference import xing4_0_29b_a4b as reference  # noqa: F401


def build(fluid, cfg, seed, for_compare=False):
    """int32 token and label rows in, `paddle_tpu.models.xing4` (the
    labels are also the tokens the multi-token-prediction module embeds),
    both cross-entropies, AdamW with global-norm clipping, the `noaux_tc`
    update of the routers' biases; plus the inference clone taken before
    the optimizer is appended."""
    from paddle_tpu.models import xing4

    S, opt = cfg["sequence_length"], cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = xing4.xing4(tokens, labels, cfg)
        loss, ce, ce_mtp = xing4.xing4_loss(
            out, labels, mtp_coef=cfg["loss"]["mtp_loss_coef"])
        test_prog = prog.clone(for_test=True)
        xing4.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(loss)
        xing4.balance_routers(prog, opt["router_bias_update_speed"])
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                ce=ce, ce_mtp=ce_mtp, logits=out["logits"],
                mtp_logits=out.get("mtp_logits"), routing=out["routing"],
                token_feed="tokens", label_feed="labels")


def sampled_params(cfg):
    """What `compare_lm_share` compares of the gradient and the first
    update: a parameter of each kind. The head and the embedding are each
    read twice (the module's second use); of the stacked expert matrices
    the comparison picks one held expert."""
    p = "xing.l%d." % cfg["first_k_dense_replace"]    # the first expert layer
    m = p + "ffn_mhc_"
    return {"head": "xing.head", "embedding": "xing.embed",
            "w_qa": p + "w_qa", "w_kvb": p + "w_kvb", "w_o": p + "w_o",
            "router": p + "router", "expert_gate": p + "gate",
            "expert_up": p + "up", "expert_down": p + "down",
            "shared_gate": p + "shared_gate", "shared_up": p + "shared_up",
            "shared_down": p + "shared_down", "phi_res": m + "phi_res",
            "alpha": m + "alpha", "mtp_proj": "xing.mtp.proj",
            "norm_scale": p + "attn_norm"}
