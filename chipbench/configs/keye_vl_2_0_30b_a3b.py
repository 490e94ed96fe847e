"""keye_vl_2_0_30b_a3b: how the configuration is handed to the system under
test."""

import os

from chipbench import harness
from chipbench.reference import keye_vl_2_0_30b_a3b as reference  # noqa: F401

# a program from before the model existed cannot run the cell: say so as
# the harness reads the cell's files, before it takes the device
if not os.path.exists(os.path.join(harness.repo_root(), "paddle_tpu",
                                   "models", "keye_vl.py")):
    raise harness.Refused(
        "this checkout has no paddle_tpu/models/keye_vl.py: it cannot "
        "run the keye_vl_2_0_30b_a3b configuration")

P = reference.P


def build(fluid, cfg, seed, for_compare=False, loss_of="both"):
    """int32 token and label rows in, `paddle_tpu.models.keye_vl`, the
    cross-entropy plus the indexers' losses, AdamW with global-norm
    clipping (no rule moves the zero bias of the choice:
    `assumed.expert_bias`); plus the inference clone taken before the
    optimizer is appended. `loss_of` "ce" / "indexer": the program
    minimises that part alone (what shows which parameters each part
    reaches)."""
    from paddle_tpu.models import keye_vl as model

    S, opt = cfg["sequence_length"], cfg["optimizer"]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        tokens = fluid.layers.data(name="tokens", shape=[S], dtype="int32")
        labels = fluid.layers.data(name="labels", shape=[S], dtype="int32")
        out = model.keye_vl(tokens, cfg)
        loss, ce, indexers = model.keye_vl_loss(out, labels)
        test_prog = prog.clone(for_test=True)
        _, reached = model.optimizer(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_global_norm"]).minimize(
                {"both": loss, "ce": ce, "indexer": indexers}[loss_of])
        prog.random_seed = startup.random_seed = int(seed) % (2 ** 31 - 1) + 1
    return dict(prog=prog, startup=startup, test_prog=test_prog, loss=loss,
                ce=ce, indexer_loss=indexers,
                indexer_losses=out["indexer_losses"],
                logits=out["logits"], routing=out["routing"],
                attention=out["attention"],
                reached=sorted(p.name for p, _ in reached),
                token_feed="tokens", label_feed="labels")


def first_hand_layers(cfg):
    """The layers whose attention branch, indexer and selection the
    comparison holds first-hand: the first (its input is the norm of the
    float32 embedding) and the last (behind the other layers' experts)."""
    return sorted({0, cfg["num_hidden_layers"] - 1})


def sampled_params(cfg):
    """What `compare_lm_sparse_attn_share` compares of the gradient and
    the first update: a parameter of each kind. Of the first layer W_q,
    W_k, W_v, W_o, both per-head QK scales, the indexer's W_qI, W_kI, its
    LayerNorm's scale and bias and W_w, a router, the attention norm's
    scale; of the last layer the indexer's three matrices again; of the
    stacked expert matrices the comparison picks one held expert; the
    table and the head."""
    first, last = (f"{P}l{i}." for i in (0, cfg["num_hidden_layers"] - 1))
    return {"embedding": P + "embed", "head": P + "head",
            "w_q": first + "w_q", "w_k": first + "w_k", "w_v": first + "w_v",
            "w_o": first + "w_o", "q_scale": first + "q_norm",
            "k_scale": first + "k_norm",
            "w_qi": first + "w_qi", "w_ki": first + "w_ki",
            "ki_norm": first + "ki_norm",
            "ki_norm_bias": first + "ki_norm_bias", "w_w": first + "w_w",
            "w_qi_last": last + "w_qi", "w_ki_last": last + "w_ki",
            "w_w_last": last + "w_w",
            "router": first + "router", "router_last": last + "router",
            "expert_gate": last + "gate", "expert_up": last + "up",
            "expert_down": last + "down",
            "norm_scale": first + "attn_norm"}
