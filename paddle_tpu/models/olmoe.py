"""OLMoE: a decoder language model whose every layer is pre-norm attention
with QK-norm and rotary positions, then a layer of sparse SwiGLU experts.

Muennighoff et al., "OLMoE: Open Mixture-of-Experts Language Models",
arXiv:2409.02060, as `transformers`' OlmoeDecoderLayer computes it
(config keys are those of the model's published config.json):

    n1 = RMS(x)
    h  = x + Wo . Attn(RoPE(split(RMS_q(Wq n1))), RoPE(split(RMS_k(Wk n1))),
                      split(Wv n1))
    y  = h + MoE(RMS(h))
    MoE(u)_t = sum_{e in top-k(p_t)} p_te . Wd_e(silu(Wg_e u_t) * (Wu_e u_t))
    p_t = softmax(Wr u_t)           (float32; not renormalised over the top-k)
    logits = Whead . RMS(y_last)
    loss = CE(logits, next token) + c_aux . L_balance + c_z . L_z

RMSNorm has a learned scale and no bias, no projection has a bias, the
q and k norms span the whole projection (before the head split), attention
is causal over the whole row. `fluid.name_scope`s put every op's lowering
under `attn/`, `moe/`, `lm_head/` or `embed/` in a device trace.
"""

import paddle_tpu as fluid
from paddle_tpu.core.framework import op_scope

# the paper's coefficients for the load-balancing and router z losses
AUX_LOSS_COEF = 0.01
Z_LOSS_COEF = 0.001
INIT_STD = 0.02


def _weight(name):
    return fluid.ParamAttr(
        name=name, initializer=fluid.initializer.Normal(0.0, INIT_STD))


def _linear(x, size, name):
    return fluid.layers.fc(x, size, param_attr=_weight(name), bias_attr=False)


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg["rms_norm_eps"],
                                 param_attr=fluid.ParamAttr(name=name))


def attention(x, cfg, seq_len, prefix):
    """x [T, hidden] -> the attention branch [T, hidden] (before the
    residual add)."""
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    n1 = _norm(x, cfg, prefix + "attn_norm")

    def heads_of(t):
        return fluid.layers.reshape(t, [-1, seq_len, heads, hidden // heads])

    def roped(name):
        t = _norm(_linear(n1, hidden, prefix + "w" + name), cfg,
                  prefix + name + "_norm")
        return fluid.layers.rotary_embedding(heads_of(t),
                                             theta=cfg["rope_theta"])

    o = fluid.layers.causal_attention(
        roped("q"), roped("k"), heads_of(_linear(n1, hidden, prefix + "wv")))
    return _linear(fluid.layers.reshape(o, [-1, hidden]), hidden,
                   prefix + "wo")


def experts(h, cfg, prefix):
    """h [T, hidden] -> (the expert branch [T, hidden], load-balance loss,
    z-loss, expert ids [T, k], tokens per expert [E])."""
    return fluid.layers.moe_ffn(
        _norm(h, cfg, prefix + "ffn_norm"), cfg["num_experts"],
        cfg["intermediate_size"], cfg["num_experts_per_tok"],
        router_attr=_weight(prefix + "router"),
        gate_attr=_weight(prefix + "gate"), up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down"))


def olmoe(tokens, cfg):
    """tokens [B, S] int32 -> dict(logits [B*S, vocab], aux [(balance,
    z)] per layer, routing [(expert ids, tokens per expert)] per layer)."""
    seq_len, hidden = int(tokens.shape[-1]), cfg["hidden_size"]
    with fluid.name_scope("embed"):
        x = fluid.layers.embedding(
            fluid.layers.reshape(tokens, [-1, 1]),
            [cfg["vocab_size"], hidden], param_attr=_weight("olmoe.embed"))
    aux, routing = [], []
    for i in range(cfg["num_hidden_layers"]):
        prefix = f"olmoe.l{i}."
        with fluid.name_scope("attn"):
            x = fluid.layers.elementwise_add(
                x, attention(x, cfg, seq_len, prefix))
        with fluid.name_scope("moe"):
            y, balance, z, ids, load = experts(x, cfg, prefix)
            x = fluid.layers.elementwise_add(x, y)
        aux.append((balance, z))
        routing.append((ids, load))
    with fluid.name_scope("lm_head"):
        logits = _linear(_norm(x, cfg, "olmoe.final_norm"),
                         cfg["vocab_size"], "olmoe.head")
    return dict(logits=logits, aux=aux, routing=routing)


def olmoe_loss(out, labels, aux_coef=AUX_LOSS_COEF, z_coef=Z_LOSS_COEF):
    """Mean next-token cross-entropy plus the two router losses summed over
    the layers; labels [B, S] int32. Returns (loss [1], cross-entropy)."""
    with fluid.name_scope("lm_head"):
        ce = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            out["logits"], fluid.layers.reshape(labels, [-1, 1])))
    # `sum`, not elementwise_add: that one is on AMP's white list and would
    # round the float32 loss to bf16
    # (`op_scope`: the ops' device-trace scope alone, no name changes)
    with op_scope("loss"):
        terms = [fluid.layers.reshape(ce, [1])]
        for balance, z in out["aux"]:
            terms += [fluid.layers.scale(balance, scale=aux_coef),
                      fluid.layers.scale(z, scale=z_coef)]
        return fluid.layers.sums(terms), ce


def decays(name):
    """AdamW's decay acts on the matrices, not on the norm scales."""
    return not name.endswith("_norm")


def optimizer(learning_rate=4e-4, weight_decay=0.1, clip_norm=1.0):
    """The paper's recipe: AdamW beta 0.9 / 0.95, eps 1e-8, decoupled decay
    0.1 (none on norms), gradients clipped to global norm 1.0. Call after
    the program is built (the clip is attached to its parameters)."""
    fluid.clip.set_gradient_clip(
        fluid.clip.GradientClipByGlobalNorm(clip_norm))
    return fluid.optimizer.Adam(
        learning_rate=learning_rate, beta1=0.9, beta2=0.95, epsilon=1e-8,
        weight_decay=weight_decay,
        apply_decay_param_fun=decays)
