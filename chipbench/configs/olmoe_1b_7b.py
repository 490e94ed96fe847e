"""olmoe_1b_7b: how the configuration is handed to the system under test."""

from chipbench.reference import olmoe_1b_7b as reference  # noqa: F401


def build(fluid, cfg, seed, for_compare=False):
    """int32 token and label rows in, `paddle_tpu.models.olmoe`, the loss
    with both router terms, AdamW with global-norm clipping; plus the
    inference clone taken before the optimizer is appended."""
    from paddle_tpu.models import olmoe

    S, opt, loss_cfg = (cfg["sequence_length"], cfg["optimizer"],
                        cfg["loss"])
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = olmoe.olmoe(tokens, cfg)
        loss, ce = olmoe.olmoe_loss(out, labels,
                                    aux_coef=loss_cfg["aux_loss_coef"],
                                    z_coef=loss_cfg["z_loss_coef"])
        test_prog = prog.clone(for_test=True)
        olmoe.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(loss)
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                ce=ce, logits=out["logits"], routing=out["routing"],
                aux=out["aux"],
                token_feed="tokens", label_feed="labels")


def sampled_params(cfg):
    """What `compare_lm` compares of the gradient and the first update: the
    head, the router, one expert's three matrices (the comparison picks the
    expert), Wq, a norm scale and the embedding."""
    p = "olmoe.l0."
    return {"head": "olmoe.head", "router": p + "router",
            "expert_gate": p + "gate", "expert_up": p + "up",
            "expert_down": p + "down", "wq": p + "wq",
            "norm_scale": p + "attn_norm", "embedding": "olmoe.embed"}
