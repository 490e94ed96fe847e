"""SmallThinker: a decoder language model every layer of which is sparse,
whose router reads the layer's INPUT (before the attention norm and before
attention: "router placed before attention"), whose experts are gated by
ReLU, and whose layers alternate between full causal attention WITHOUT
positions and a sliding window with rotary positions; built as the share
ONE chip holds of a layer that several chips divide.

Config keys are those of the model's published config.json
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct), with the
counts of query heads (`num_attention_heads`), key/value heads
(`num_key_value_heads`), experts (`moe_num_primary_experts`) and vocabulary
rows those HELD here; `deployment` says what the layer has in all
(`moe_num_primary_experts` the router's width, `first_expert` the first
one held). Layer l is a window layer where `sliding_window_layout[l]` is 1
and rotates q and k where `rope_layout[l]` is 1; the lists may be longer
than `num_hidden_layers` (the published 52 entries: the first
`num_hidden_layers` are built). x [T, C], layer l:

    r = x W_r  [T, E] float32: the router's logits, of the layer's input
    u = RMSNorm(x);  q = u W_q [T, H, D],  k = u W_k,  v = u W_v [T, Hkv, D]
    rope_layout[l]: rotary on the whole head of q and k (`rope_theta`)
    o = softmax(q k^T / sqrt(D) + mask) v, query head h on key/value head
      h // (H / Hkv); mask j <= i, a window layer also i - j <
      `sliding_window_size`;  x <- x + concat(o) W_o
    u' = RMSNorm(x)
    the `moe_num_active_primary_experts` largest of r + b chosen (b
      persistable, not trained, `balance_routers` moves it after each
      step; the configuration's `assumed.router_balance`); w = softmax of r
      over the chosen (`moe_primary_router_apply_softmax`, `norm_topk_prob`)
    x <- x + sum_e w_e (relu(u' G_e) * (u' U_e)) D_e  (the held experts' part)
    final RMSNorm, untied head, cross-entropy on the next token.

No dense layer, no shared expert, no gate on the attention output, no
bias, no QK norm. W_q's columns and W_o's rows are the held heads', W_k,
W_v the held key/value heads'. The router is ONE `moe_ffn` op's
(`router_input=`): its choice, sorts and dispatch order depend on nothing
attention computes. `fluid.name_scope`s put every op's lowering under
`embed/`, `attn_full/` or `attn_window/`, `moe/`, `lm_head/`, and
`balance_routers`' under `router_bias/`.
"""

import paddle_tpu as fluid
from paddle_tpu.models.laguna import decays, optimizer  # noqa: F401
from paddle_tpu.models.xing4 import balance_routers  # noqa: F401

INIT_STD = 0.02


def _weight(name):
    return fluid.ParamAttr(
        name=name, initializer=fluid.initializer.Normal(0.0, INIT_STD))


def _linear(x, size, name):
    return fluid.layers.fc(x, size, param_attr=_weight(name), bias_attr=False)


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg["rms_norm_eps"],
                                 param_attr=fluid.ParamAttr(name=name))


def attention(u, cfg, seq_len, prefix, window, rotary):
    """u [T, C] (normed) -> the held heads' part of the branch [T, C]."""
    L = fluid.layers
    heads, kv_heads, D = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])

    def heads_of(t, n):
        t = L.reshape(t, [-1, seq_len, n, D])
        return L.rotary_embedding(t, theta=cfg["rope_theta"]) if rotary else t

    q = heads_of(_linear(u, heads * D, prefix + "w_q"), heads)
    k = heads_of(_linear(u, kv_heads * D, prefix + "w_k"), kv_heads)
    v = L.reshape(_linear(u, kv_heads * D, prefix + "w_v"),
                  [-1, seq_len, kv_heads, D])
    o = L.causal_attention(
        q, k, v, window=cfg["sliding_window_size"] if window else None)
    return _linear(L.reshape(o, [-1, heads * D]), cfg["hidden_size"],
                   prefix + "w_o")


def experts(u, x_in, cfg, prefix):
    """u [T, C] (the normed state after attention), x_in [T, C] (the
    layer's input, which the router reads) -> (the held experts' part [T,
    C], (expert ids, tokens per expert, rows held))."""
    dep = cfg["deployment"]
    y, _, _, ids, load, rows = fluid.layers.moe_ffn(
        u, dep["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"],
        cfg["moe_num_active_primary_experts"],
        router_attr=_weight(prefix + "router"),
        gate_attr=_weight(prefix + "gate"), up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down"), score_func="softmax",
        norm_topk=bool(cfg["norm_topk_prob"]),
        bias_attr=fluid.ParamAttr(name=prefix + "router_bias"),
        held=(dep["first_expert"], cfg["moe_num_primary_experts"]),
        router_input=x_in, activation="relu")
    return y, (ids, load, rows)


def layer(x, cfg, seq_len, i):
    """Decoder layer i on x [T, C] -> (x', routing, (the attention
    branch's normed input, its output))."""
    L = fluid.layers
    prefix = f"smallthinker.l{i}."
    window = bool(cfg["sliding_window_layout"][i])
    x_in = x
    with fluid.name_scope("attn_window" if window else "attn_full"):
        u = _norm(x, cfg, prefix + "attn_norm")
        branch = attention(u, cfg, seq_len, prefix, window,
                           bool(cfg["rope_layout"][i]))
        x = L.elementwise_add(x, branch)
    with fluid.name_scope("moe"):
        y, routing = experts(_norm(x, cfg, prefix + "ffn_norm"), x_in, cfg,
                             prefix)
        return L.elementwise_add(x, y), routing, (u, branch)


def smallthinker(tokens, cfg):
    """tokens [B, S] int32 -> dict(logits [B*S, vocab], routing [(expert
    ids [T, k], tokens per expert [E], rows held [1])] for each layer,
    attention [(the attention branch's normed input, its output), both
    [T, C]] for each layer)."""
    L = fluid.layers
    seq_len = int(tokens.shape[-1])
    with fluid.name_scope("embed"):
        x = L.embedding(L.reshape(tokens, [-1, 1]),
                        [cfg["vocab_size"], cfg["hidden_size"]],
                        param_attr=_weight("smallthinker.embed"))
    routing, branches = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, r, branch = layer(x, cfg, seq_len, i)
        routing.append(r)
        branches.append(branch)
    with fluid.name_scope("lm_head"):
        logits = _linear(_norm(x, cfg, "smallthinker.final_norm"),
                         cfg["vocab_size"], "smallthinker.head")
    return dict(logits=logits, routing=routing, attention=branches)


def smallthinker_loss(out, labels):
    """Mean cross-entropy of the next token; labels [B, S] int32."""
    L = fluid.layers
    with fluid.name_scope("lm_head"):
        return L.reshape(L.mean(L.softmax_with_cross_entropy(
            out["logits"], L.reshape(labels, [-1, 1]))), [1])
