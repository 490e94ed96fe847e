"""LFM2 (Liquid AI's `lfm2_moe`): a decoder language model most of whose
layers mix tokens with a GATED SHORT CONVOLUTION and the rest with
grouped-query attention, whose first layers have a dense SwiGLU and the
others sigmoid-routed experts chosen with the model's own bias, and whose
head is the embedding table itself; built as the share ONE chip holds of a
model whose experts and vocabulary rows several chips divide.

Config keys are those of the model's published config.json
(https://huggingface.co/LiquidAI/LFM2-8B-A1B), with the counts of experts
(`num_experts`) and vocabulary rows (`vocab_size`) those HELD here;
`deployment` says what the layer has in all (`num_experts` the router's
width, `first_expert` the first one held) and which published layers the
program builds (`layers_held`; none given: the first
`num_hidden_layers`). Program layer i is published layer l =
`layers_held[i]`, of kind `layer_types[l]`; it has the dense MLP where i <
`num_dense_layers`. x [T, C], T = rows x S tokens:

    u = RMSNorm(x; operator_norm)
    conv:  [B | C | z] = u W_in  [T, 3C];  v = B * z
           c_t = sum_j w_j v_{t - (L - 1 - j)}, w [L, C] depth-wise, zero
           before a row's first token;  x <- x + (C * c) W_out
    full_attention:  q = u W_q [T, H, D], k = u W_k, v = u W_v [T, Hkv, D]
           q, k <- RMSNorm over each head's D numbers (one scale [D] the
           heads share: q_layernorm, k_layernorm), then rotary on the whole
           head; o = softmax(q k^T / sqrt(D) + causal) v, query head h on
           key/value head h // (H / Hkv);  x <- x + concat(o) W_o
    u' = RMSNorm(x; ffn_norm)
    dense:   x <- x + (silu(u' W_gate) * (u' W_up)) W_down
    sparse:  s = sigmoid(u' W_r) over ALL experts; the
           `num_experts_per_tok` largest of s + b chosen (b = expert_bias,
           persistable, no gradient, part of the model: `balance_routers`
           moves it after each step); w = the chosen s over (their sum +
           1e-6), times `routed_scaling_factor`;  x <- x + the held
           experts' part of sum_e w_e (silu(u' G_e) * (u' U_e)) D_e
    logits = RMSNorm(x; embedding_norm) E^T, E [V, C] THE EMBEDDING TABLE:
    one parameter read by `lookup_table` and by the head's `matmul`
    (`transpose_y`), whose gradient is the sum of the two readers'.

The operators and the dense MLP are whole (a chip runs them on its own
rows); the experts are the held ones', the table the held rows'.
`fluid.name_scope`s put every op's lowering under `embed/`, `conv/` (with
`norm`, `in_proj`, `short_conv`, `out_proj` below it), `attn/`,
`dense_mlp/`, `moe/`, `lm_head/`, and `balance_routers`' under
`router_bias/`.
"""

import paddle_tpu as fluid
from paddle_tpu.models.laguna import decays, optimizer  # noqa: F401
from paddle_tpu.models.xing4 import (INIT_STD, _linear,  # noqa: F401
                                     _weight, balance_routers, swiglu)

# what the renormalisation of the chosen scores adds to their sum
NORM_TOPK_EPS = 1e-6
CONV, ATTENTION = "conv", "full_attention"
P = "lfm2."


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg["norm_eps"],
                                 param_attr=fluid.ParamAttr(name=name))


def layer_kinds(cfg):
    """The operator kind of each layer the program builds."""
    n = cfg["num_hidden_layers"]
    held = (cfg.get("deployment") or {}).get("layers_held") or range(n)
    return [cfg["layer_types"][l] for l in list(held)[:n]]


def head_dim(cfg):
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def short_conv(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> (the conv operator's branch [T, C], the op's own
    input [T, 3C] and output [T, C])."""
    C = cfg["hidden_size"]
    with fluid.name_scope("in_proj"):
        bcz = _linear(u, 3 * C, prefix + "conv_in")
    with fluid.name_scope("short_conv"):
        y = fluid.layers.short_conv(
            bcz, seq_len, kernel_size=cfg["conv_L_cache"],
            param_attr=_weight(prefix + "conv_taps"))
    with fluid.name_scope("out_proj"):
        return _linear(y, C, prefix + "conv_out"), (bcz, y)


def attention(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> (the attention operator's branch [T, C], None:
    it has no op of its own to hold first-hand)."""
    L = fluid.layers
    heads, kv_heads, D = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], head_dim(cfg))

    def heads_of(t, n, norm):
        t = _norm(L.reshape(t, [-1, seq_len, n, D]), cfg, prefix + norm)
        return L.rotary_embedding(t, theta=cfg["rope_theta"])

    q = heads_of(_linear(u, heads * D, prefix + "w_q"), heads, "q_layernorm")
    k = heads_of(_linear(u, kv_heads * D, prefix + "w_k"), kv_heads,
                 "k_layernorm")
    v = L.reshape(_linear(u, kv_heads * D, prefix + "w_v"),
                  [-1, seq_len, kv_heads, D])
    o = L.causal_attention(q, k, v)
    return _linear(L.reshape(o, [-1, heads * D]), cfg["hidden_size"],
                   prefix + "w_o"), None


def experts(u, cfg, prefix):
    """u [T, C] (normed) -> (the held experts' part [T, C], (expert ids,
    tokens per expert, rows held))."""
    dep = cfg["deployment"]
    y, _, _, ids, load, rows = fluid.layers.moe_ffn(
        u, dep["num_experts"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], router_attr=_weight(prefix + "router"),
        gate_attr=_weight(prefix + "gate"), up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down"), score_func="sigmoid",
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=cfg["routed_scaling_factor"],
        bias_attr=fluid.ParamAttr(name=prefix + "expert_bias")
        if cfg["use_expert_bias"] else None,
        held=(dep["first_expert"], cfg["num_experts"]),
        norm_eps=NORM_TOPK_EPS)
    return y, (ids, load, rows)


def layer(x, cfg, seq_len, i, kind):
    """Program layer i on x [T, C] -> (x', routing or None, (the operator
    branch's normed input, its output, and of a conv layer the
    `short_conv` op's own (input, output), else None))."""
    L = fluid.layers
    prefix = f"{P}l{i}."
    with fluid.name_scope("conv" if kind == CONV else "attn"):
        with fluid.name_scope("norm"):
            u = _norm(x, cfg, prefix + "operator_norm")
        branch, op = (short_conv if kind == CONV else attention)(
            u, cfg, seq_len, prefix)
        x = L.elementwise_add(x, branch)
    if i < cfg["num_dense_layers"]:
        with fluid.name_scope("dense_mlp"):
            y = swiglu(_norm(x, cfg, prefix + "ffn_norm"),
                       cfg["intermediate_size"], prefix + "mlp_")
            return L.elementwise_add(x, y), None, (u, branch, op)
    with fluid.name_scope("moe"):
        y, routing = experts(_norm(x, cfg, prefix + "ffn_norm"), cfg, prefix)
        return L.elementwise_add(x, y), routing, (u, branch, op)


def lfm2(tokens, cfg):
    """tokens [B, S] int32 -> dict(logits [B*S, vocab], routing [(expert
    ids [T, k], tokens per expert [E], rows held [1])] for each SPARSE
    layer, operators [(kind, the operator branch's normed input, its
    output), both [T, C]] for each layer, short_convs {layer: the
    `short_conv` op's (input [T, 3C], output [T, C])} for each conv
    layer)."""
    L = fluid.layers
    seq_len = int(tokens.shape[-1])
    with fluid.name_scope("embed"):
        x = L.embedding(L.reshape(tokens, [-1, 1]),
                        [cfg["vocab_size"], cfg["hidden_size"]],
                        param_attr=_weight(P + "embed"))
    table = fluid.default_main_program().global_block().var(P + "embed")
    routing, branches, convs = [], [], {}
    for i, kind in enumerate(layer_kinds(cfg)):
        x, r, (u, branch, op) = layer(x, cfg, seq_len, i, kind)
        if r is not None:
            routing.append(r)
        branches.append((kind, u, branch))
        if op is not None:
            convs[i] = op
    with fluid.name_scope("lm_head"):
        # the tied head: the table itself, transposed in the product
        logits = L.matmul(_norm(x, cfg, P + "embedding_norm"), table,
                          transpose_y=True)
    return dict(logits=logits, routing=routing, operators=branches,
                short_convs=convs)


def lfm2_loss(out, labels):
    """Mean cross-entropy of the next token; labels [B, S] int32."""
    L = fluid.layers
    with fluid.name_scope("lm_head"):
        return L.reshape(L.mean(L.softmax_with_cross_entropy(
            out["logits"], L.reshape(labels, [-1, 1]))), [1])
