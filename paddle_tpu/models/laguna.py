"""Laguna: a decoder language model whose layers alternate between full
causal attention and a sliding window, with different head counts for the
two, grouped-query heads, a per-head sigmoid gate on the attention output,
and sigmoid-routed small experts beside a shared one; built as the share
ONE chip holds of a layer that several chips divide.

Config keys are those of the model's published config.json
(https://huggingface.co/poolside/Laguna-XS.2), with the counts of heads
(`num_attention_heads_per_layer`, `num_key_value_heads`), routed experts
(`num_experts`) and vocabulary rows those HELD here; `deployment` says
what the layer has in all (`num_experts` the router's width,
`first_expert` the first one held). Layer l's kind is `layer_types[l]`
("full_attention" | "sliding_attention") and `mlp_layer_types[l]` ("dense"
| "sparse"); the lists may be longer than `num_hidden_layers` (the
published 40 entries: the first `num_hidden_layers` are built). x [T, C]:

    u = RMSNorm(x);  q = u W_q [T, H_l, D],  k = u W_k,  v = u W_v [T, Hkv, D]
    full layers: the first `partial_rotary_factor` x D numbers of each
      head of q and k rotated, YaRN's frequencies, cos and sin x
      `attention_factor`; window layers: the whole head, plain theta
    o = softmax(q k^T / sqrt(D) + mask) v, query head h on key/value head
      h // (H_l / Hkv); mask j <= i, window layers also i - j <
      `sliding_window`
    g = sigmoid(u W_g) [T, H_l];  x <- x + concat_h(g_h o_h) W_o
    u = RMSNorm(x);  dense: x <- x + SwiGLU(u; `intermediate_size`)
    sparse: s = sigmoid(u W_r) over ALL experts (float32); the
      `num_experts_per_tok` largest of s + b chosen (b persistable, not
      trained: `balance_routers` moves it after each step); w = s of the
      chosen / their sum x `moe_routed_scaling_factor`;
      x <- x + sum_e w_e SwiGLU_e(u; `moe_intermediate_size`) (the held
      experts' part) + SwiGLU_shared(u; `shared_expert_intermediate_size`)
    final RMSNorm, untied head, cross-entropy on the next token.

W_q, W_g's columns and W_o's rows are the held heads'; W_k, W_v the held
key/value heads': the attention branch is their part of the sum over all
heads. `fluid.name_scope`s put every op's lowering under `embed/`,
`attn_full/` or `attn_window/`, `mlp/`, `moe/`, `lm_head/`, and
`balance_routers`' under `router_bias/`.
"""

import paddle_tpu as fluid
from paddle_tpu.models.xing4 import balance_routers  # noqa: F401

INIT_STD = 0.02
FULL, WINDOW = "full_attention", "sliding_attention"


def _weight(name):
    return fluid.ParamAttr(
        name=name, initializer=fluid.initializer.Normal(0.0, INIT_STD))


def _linear(x, size, name):
    return fluid.layers.fc(x, size, param_attr=_weight(name), bias_attr=False)


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg["rms_norm_eps"],
                                 param_attr=fluid.ParamAttr(name=name))


def rotary_of(cfg, kind):
    """`layers.rotary_embedding`'s keyword arguments for a layer of
    `kind`: its entry of `rope_parameters`."""
    rp = cfg["rope_parameters"][kind]
    args = dict(theta=rp["rope_theta"], rotary_dim=int(
        cfg["head_dim"] * rp.get("partial_rotary_factor", 1.0)))
    if rp.get("rope_type") == "yarn":
        args["scaling"] = dict(rp, original_max_position_embeddings=(
            rp.get("original_max_position_embeddings")
            or cfg["rope_parameters"]["original_max_position_embeddings"]))
    return args


def attention(u, cfg, seq_len, prefix, kind, heads):
    """u [T, C] (normed) -> the held heads' part of the branch [T, C]."""
    L = fluid.layers
    kv_heads, D = cfg["num_key_value_heads"], cfg["head_dim"]
    rotary = rotary_of(cfg, kind)

    def heads_of(t, n):
        return L.rotary_embedding(L.reshape(t, [-1, seq_len, n, D]),
                                  **rotary)

    q = heads_of(_linear(u, heads * D, prefix + "w_q"), heads)
    k = heads_of(_linear(u, kv_heads * D, prefix + "w_k"), kv_heads)
    v = L.reshape(_linear(u, kv_heads * D, prefix + "w_v"),
                  [-1, seq_len, kv_heads, D])
    o = L.causal_attention(
        q, k, v, window=cfg["sliding_window"] if kind == WINDOW else None)
    o = L.reshape(o, [-1, heads, D])
    if cfg.get("gating"):
        gate = L.sigmoid(_linear(u, heads, prefix + "w_g"))
        o = L.elementwise_mul(o, gate, axis=0)
    return _linear(L.reshape(o, [-1, heads * D]), cfg["hidden_size"],
                   prefix + "w_o")


def swiglu(u, width, prefix):
    L = fluid.layers
    gate = L.swish(_linear(u, width, prefix + "gate"))
    return _linear(L.elementwise_mul(gate, _linear(u, width, prefix + "up")),
                   u.shape[-1], prefix + "down")


def experts(u, cfg, prefix):
    """u [T, C] (normed) -> (the held experts' part plus the shared
    expert [T, C], (expert ids, tokens per expert, rows held))."""
    dep = cfg["deployment"]
    y, _, _, ids, load, rows = fluid.layers.moe_ffn(
        u, dep["num_experts"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], router_attr=_weight(prefix + "router"),
        gate_attr=_weight(prefix + "gate"), up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down"), score_func="sigmoid",
        norm_topk=True, routed_scale=cfg["moe_routed_scaling_factor"],
        bias_attr=fluid.ParamAttr(name=prefix + "router_bias"),
        held=(dep["first_expert"], cfg["num_experts"]))
    shared = swiglu(u, cfg["shared_expert_intermediate_size"],
                    prefix + "shared_")
    return fluid.layers.elementwise_add(y, shared), (ids, load, rows)


def layer(x, cfg, seq_len, i):
    """Decoder layer i on x [T, C] -> (x', routing or None, (the attention
    branch's normed input, its output))."""
    L = fluid.layers
    prefix = f"laguna.l{i}."
    kind = cfg["layer_types"][i]
    with fluid.name_scope("attn_full" if kind == FULL else "attn_window"):
        u = _norm(x, cfg, prefix + "attn_norm")
        branch = attention(u, cfg, seq_len, prefix, kind,
                           cfg["num_attention_heads_per_layer"][i])
        x = L.elementwise_add(x, branch)
    if cfg["mlp_layer_types"][i] == "dense":
        with fluid.name_scope("mlp"):
            return L.elementwise_add(x, swiglu(
                _norm(x, cfg, prefix + "ffn_norm"), cfg["intermediate_size"],
                prefix + "mlp_")), None, (u, branch)
    with fluid.name_scope("moe"):
        y, routing = experts(_norm(x, cfg, prefix + "ffn_norm"), cfg, prefix)
        return L.elementwise_add(x, y), routing, (u, branch)


def laguna(tokens, cfg):
    """tokens [B, S] int32 -> dict(logits [B*S, vocab], routing [(expert
    ids [T, k], tokens per expert [E], rows held [1])] for each sparse
    layer, attention [(the attention branch's normed input, its output),
    both [T, C]] for each layer)."""
    L = fluid.layers
    seq_len = int(tokens.shape[-1])
    with fluid.name_scope("embed"):
        x = L.embedding(L.reshape(tokens, [-1, 1]),
                        [cfg["vocab_size"], cfg["hidden_size"]],
                        param_attr=_weight("laguna.embed"))
    routing, branches = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, r, branch = layer(x, cfg, seq_len, i)
        branches.append(branch)
        if r:
            routing.append(r)
    with fluid.name_scope("lm_head"):
        logits = _linear(_norm(x, cfg, "laguna.final_norm"),
                         cfg["vocab_size"], "laguna.head")
    return dict(logits=logits, routing=routing, attention=branches)


def laguna_loss(out, labels):
    """Mean cross-entropy of the next token; labels [B, S] int32."""
    L = fluid.layers
    with fluid.name_scope("lm_head"):
        return L.reshape(L.mean(L.softmax_with_cross_entropy(
            out["logits"], L.reshape(labels, [-1, 1]))), [1])


def decays(name):
    """AdamW's decay acts on the matrices, not on the norm scales."""
    return not name.endswith("norm")


def optimizer(learning_rate=3e-4, weight_decay=0.1, clip_norm=1.0):
    """AdamW beta 0.9 / 0.95, eps 1e-8, decoupled decay 0.1 on the
    matrices, gradients clipped to global norm 1.0 (`assumed` in the
    configuration file). Call after the program is built (the clip is
    attached to its parameters)."""
    fluid.clip.set_gradient_clip(
        fluid.clip.GradientClipByGlobalNorm(clip_norm))
    return fluid.optimizer.Adam(
        learning_rate=learning_rate, beta1=0.9, beta2=0.95, epsilon=1e-8,
        weight_decay=weight_decay, apply_decay_param_fun=decays)
