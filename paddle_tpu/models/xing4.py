"""Xing4.0: a decoder language model with latent attention, a residual path
of n streams mixed by Sinkhorn-projected matrices, sigmoid-routed experts
beside a shared one, and a multi-token-prediction module; built as the
share ONE chip holds of a layer that several chips divide.

Config keys are those of the model's published config.json
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B), with the counts of
heads, routed experts and vocabulary rows those HELD here; `deployment`
says what the layer has in all (`n_routed_experts` the router's width,
`first_expert` the first one held). x is the residual state [n, T, C], n
= `hc_mult` (stream-major in the program); u a sublayer's input [T, C].

Residual path, around each sublayer F (attention; MLP or experts), after
Xie et al., mHC: Manifold-Constrained Hyper-Connections, arXiv:2512.24880
(`layers.mhc_expand`, `mhc_mix`, `mhc_update`):

    x~ = RMSNorm(vec(x));  HPre = sigmoid(a_pre x~ Phi_pre + b_pre)
    HPost = 2 sigmoid(a_post x~ Phi_post + b_post)
    HRes = Sinkhorn_20(exp(clip(a_res mat(x~ Phi_res) + b_res)))
    u = sum_i HPre_i x_i;   x' = HRes x + HPost (x) F(RMSNorm(u))

The embedding is copied into the n streams; they are summed before the
final norm. Latent attention (DeepSeek-V2/V3's, arXiv:2405.04434):

    c_q = RMSNorm(u W_qa);  [q_nope, q_rope]_h = c_q W_qb
    [c_kv, k_rope] = u W_kva;  [k_nope, v]_h = RMSNorm(c_kv) W_kvb
    q = [q_nope, RoPE(q_rope)], k = [k_nope, RoPE(k_rope)] (one k_rope
    for every head), out = concat_h(softmax(q k^T s + causal) v) W_o

with YaRN's frequencies and s = (qk_nope + qk_rope)^-1/2 m^2, m = 0.1
mscale_all_dim ln(factor) + 1. W_qa, W_kva and the two norms are whole;
W_qb, W_kvb, W_o are the held heads', and the branch's output is their
part of the sum over all heads. Experts (`layers.moe_ffn`): scores
sigmoid(u W_r) over ALL experts, the top-k of score + bias chosen (the
bias persistable, not trained: `balance_routers` moves it after each step
towards an even load), weights the chosen scores over their sum
times `routed_scaling_factor`; the held experts' part plus the shared
expert, a SwiGLU of `moe_intermediate_size` x `n_shared_experts`. The
first `first_k_dense_replace` layers have a dense SwiGLU of
`intermediate_size` instead. Multi-token prediction (DeepSeek-V3,
arXiv:2412.19437): h = the summed streams after the last layer;
h'_i = [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))] M, one expert layer of its
own on n copies of h', summed, a final norm of its own, the SHARED head,
predicting t_{i+2}; loss = CE + lambda CE_mtp.

`fluid.name_scope`s put every op's lowering under `embed/`, `attn/`,
`mhc/`, `mlp/`, `moe/`, `lm_head/`, the module's under `mtp/`, and
`balance_routers`' under `router_bias/`.
"""

import math

import paddle_tpu as fluid
from paddle_tpu.core.framework import op_scope
from paddle_tpu.layer_helper import LayerHelper

INIT_STD = 0.02
MTP_LOSS_COEF = 0.3


def _weight(name):
    return fluid.ParamAttr(
        name=name, initializer=fluid.initializer.Normal(0.0, INIT_STD))


def _linear(x, size, name):
    return fluid.layers.fc(x, size, param_attr=_weight(name), bias_attr=False)


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg["rms_norm_eps"],
                                 param_attr=fluid.ParamAttr(name=name))


def _param(name):
    return fluid.default_main_program().global_block().var(name)


def softmax_scale(cfg):
    """(qk_nope + qk_rope)^-1/2 m^2, m YaRN's attention factor over all
    dimensions (DeepSeek-V3's modelling code)."""
    rs = cfg.get("rope_scaling") or {}
    m = 1.0
    if rs.get("factor", 1.0) > 1.0 and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def latent_attention(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> the held heads' part of the branch [T, C]."""
    L = fluid.layers
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kv_rank = cfg["kv_lora_rank"]

    def rotary(t, n_heads):
        return L.rotary_embedding(
            L.reshape(t, [-1, seq_len, n_heads, rope]),
            theta=cfg["rope_theta"], scaling=cfg.get("rope_scaling"))

    c_q = _norm(_linear(u, cfg["q_lora_rank"], prefix + "w_qa"), cfg,
                prefix + "q_norm")
    q = L.reshape(_linear(c_q, heads * (nope + rope), prefix + "w_qb"),
                  [-1, seq_len, heads, nope + rope])
    q_nope, q_rope = L.split(q, [nope, rope], dim=3)
    c_kv, k_rope = L.split(_linear(u, kv_rank + rope, prefix + "w_kva"),
                           [kv_rank, rope], dim=1)
    kv = L.reshape(
        _linear(_norm(c_kv, cfg, prefix + "kv_norm"), heads * (nope + dv),
                prefix + "w_kvb"), [-1, seq_len, heads, nope + dv])
    k_nope, v = L.split(kv, [nope, dv], dim=3)
    k_rope = rotary(k_rope, 1)
    q = L.concat([q_nope, rotary(q_rope, heads)], axis=3)
    k = L.concat([k_nope, L.concat([k_rope] * heads, axis=2)], axis=3)
    o = L.causal_attention(q, k, v, scale=softmax_scale(cfg))
    return _linear(L.reshape(o, [-1, heads * dv]), cfg["hidden_size"],
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
        u, dep["n_routed_experts"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], router_attr=_weight(prefix + "router"),
        gate_attr=_weight(prefix + "gate"), up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down"),
        score_func=cfg["scoring_func"], norm_topk=cfg["norm_topk_prob"],
        routed_scale=cfg["routed_scaling_factor"],
        bias_attr=fluid.ParamAttr(name=prefix + "router_bias"),
        held=(dep["first_expert"], cfg["n_routed_experts"]))
    shared = swiglu(u, cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                    prefix + "shared_")
    return fluid.layers.elementwise_add(y, shared), (ids, load, rows)


def _around(x, cfg, prefix, scope, sublayer):
    """x' = HRes x + HPost (x) sublayer(RMSNorm(u)), u = HPre x."""
    with fluid.name_scope("mhc"):
        u, h_post, h_res = fluid.layers.mhc_mix(
            x, epsilon=cfg["hc_eps"], sinkhorn_iters=cfg["hc_sinkhorn_iters"],
            clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
            prefix=prefix + "mhc_")
    with fluid.name_scope(scope):
        y = sublayer(_norm(u, cfg, prefix + "norm"))
    with fluid.name_scope("mhc"):
        return fluid.layers.mhc_update(x, h_res, h_post, y)


def layer(x, cfg, seq_len, prefix, dense):
    """One decoder layer on the state x [n, T, C] -> (x', routing or
    None)."""
    x = _around(x, cfg, prefix + "attn_", "attn",
                lambda u: latent_attention(u, cfg, seq_len, prefix))
    if dense:
        return _around(x, cfg, prefix + "ffn_", "mlp", lambda u: swiglu(
            u, cfg["intermediate_size"], prefix + "mlp_")), None
    routing = []

    def ffn(u):
        y, r = experts(u, cfg, prefix)
        routing.append(r)
        return y

    return _around(x, cfg, prefix + "ffn_", "moe", ffn), routing[0]


def _embed(ids, cfg, table=None):
    """Rows of the embedding for ids [B, S]; `table`: the parameter of an
    earlier call (the module reads the one embedding a second time)."""
    flat = fluid.layers.reshape(ids, [-1, 1])
    size = [cfg["vocab_size"], cfg["hidden_size"]]
    if table is None:
        return fluid.layers.embedding(flat, size,
                                      param_attr=_weight("xing.embed"))
    helper = LayerHelper("embedding")
    out = helper.create_tmp_variable(table.dtype,
                                     shape=(flat.shape[0], size[1]))
    helper.append_op("lookup_table", {"Ids": [flat], "W": [table]},
                     {"Out": [out]}, {"padding_idx": -1})
    return out


def _head(h, cfg, norm_name, weight=None):
    """Logits [T, vocab] of the summed state h [T, C]: a final norm, then
    the head (`weight`: the parameter of an earlier call, shared)."""
    h = _norm(h, cfg, norm_name)
    if weight is None:
        return _linear(h, cfg["vocab_size"], "xing.head")
    helper = LayerHelper("fc")
    out = helper.create_tmp_variable(h.dtype, shape=(h.shape[0],
                                                     cfg["vocab_size"]))
    helper.append_op("mul", {"X": [h], "Y": [weight]}, {"Out": [out]},
                     {"x_num_col_dims": 1, "y_num_col_dims": 1})
    return out


def xing4(tokens, next_tokens, cfg):
    """tokens, next_tokens [B, S] int32 (a row and the same row one
    position on, which the module embeds) -> dict(logits, mtp_logits [B*S,
    vocab], routing [(expert ids [T, k], tokens per expert [E], rows held
    [1])] for each expert layer, the module's last)."""
    L = fluid.layers
    seq_len, n = int(tokens.shape[-1]), cfg["hc_mult"]
    with fluid.name_scope("embed"):
        x = L.mhc_expand(_embed(tokens, cfg), n)
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        x, r = layer(x, cfg, seq_len, f"xing.l{i}.",
                     dense=i < cfg["first_k_dense_replace"])
        if r:
            routing.append(r)
    with fluid.name_scope("lm_head"):
        h = L.reduce_sum(x, dim=0)
        logits = _head(h, cfg, "xing.final_norm")
    out = dict(logits=logits, routing=routing)
    if cfg.get("num_nextn_predict_layers"):
        with fluid.name_scope("mtp"):
            with fluid.name_scope("embed"):
                e = _embed(next_tokens, cfg, _param("xing.embed"))
                joined = L.concat([_norm(h, cfg, "xing.mtp.h_norm"),
                                   _norm(e, cfg, "xing.mtp.e_norm")], axis=1)
                entered = L.mhc_expand(_linear(joined, cfg["hidden_size"],
                                               "xing.mtp.proj"), n)
            x, r = layer(entered, cfg, seq_len, "xing.mtp.", dense=False)
            routing.append(r)
            with fluid.name_scope("lm_head"):
                out["mtp_logits"] = _head(
                    L.reduce_sum(x, dim=0), cfg, "xing.mtp.final_norm",
                    _param("xing.head"))
    return out


def xing4_loss(out, labels, mtp_coef=MTP_LOSS_COEF):
    """CE(logits, labels) + mtp_coef * CE_mtp; labels [B, S] int32 are the
    next tokens, the module's labels the ones after them: the labels one
    position on, a row's last position left out of CE_mtp (its label lies
    in the next row). Returns (loss [1], CE, CE_mtp or None)."""
    L = fluid.layers
    S = int(labels.shape[-1])
    with fluid.name_scope("lm_head"):
        ce = L.mean(L.softmax_with_cross_entropy(
            out["logits"], L.reshape(labels, [-1, 1])))
    if "mtp_logits" not in out:
        return L.reshape(ce, [1]), ce, None
    with fluid.name_scope("mtp"), fluid.name_scope("lm_head"):
        first, rest = L.split(labels, [1, S - 1], dim=1)
        after = L.concat([rest, first], axis=1)
        per_token = L.softmax_with_cross_entropy(
            out["mtp_logits"], L.reshape(after, [-1, 1]))
        kept, _ = L.split(L.reshape(per_token, [-1, S]), [S - 1, 1], dim=1)
        ce_mtp = L.mean(kept)
    # `sums`, not elementwise_add: that one is on AMP's white list and
    # would round the float32 loss to bf16 (`op_scope`: the ops'
    # device-trace scope alone, no name changes)
    with op_scope("loss"):
        return (L.sums([L.reshape(ce, [1]),
                        L.scale(L.reshape(ce_mtp, [1]), scale=mtp_coef)]),
                ce, ce_mtp)


def balance_routers(program, speed):
    """The `noaux_tc` rule, after a step's update: the bias of every
    router of `program` rises by `speed` for an expert that received
    fewer than the mean of the step's choices and falls by it for one
    that received more (DeepSeek-V3, arXiv:2412.19437, section 2.1.2:
    b_e += speed * sign(mean load - load_e)). The bias takes no gradient;
    this is all that moves it. `speed`: one number, or one a router in
    the program's order (routers whose logits spread differently). Call
    after `minimize`, and after the inference clone is taken. float32
    throughout: none of these ops is on AMP's white list
    (`elementwise_sub` is, and would round the counts)."""
    L = fluid.layers
    block = program.global_block()
    routers = [op for op in block.ops
               if op.type == "moe_ffn" and op.input("Bias")]
    speeds = list(speed) if isinstance(speed, (list, tuple)) \
        else [speed] * len(routers)
    if len(speeds) != len(routers):
        raise ValueError(f"{len(speeds)} speeds for {len(routers)} routers")
    with fluid.name_scope("router_bias"):
        for op, speed in zip(routers, speeds):
            bias = block.var(op.input("Bias")[0])
            load = L.cast(block.var(op.output("TokensPerExpert")[0]),
                          "float32")
            n = int(bias.shape[0])
            helper = LayerHelper("expand")
            total = helper.create_tmp_variable("float32", shape=(n,))
            helper.append_op("expand", {"X": [L.reduce_sum(load)]},
                             {"Out": [total]}, {"expand_times": [n]})
            # E load_e - sum of the loads: a whole number whatever the
            # shapes, so clipping it to [-1, 1] is its sign exactly
            over = L.sums([L.scale(load, scale=float(n)),
                           L.scale(total, scale=-1.0)])
            L.sums([bias, L.scale(L.clip(over, -1.0, 1.0),
                                  scale=-float(speed))], out=bias)


def decays(name):
    """AdamW's decay acts on the matrices: not on the norm scales, nor on
    the mixers' scalars and biases."""
    return not (name.endswith("norm") or name.endswith("alpha")
                or "mhc_b_" in name)


def optimizer(learning_rate=3e-4, weight_decay=0.1, clip_norm=1.0):
    """AdamW beta 0.9 / 0.95, eps 1e-8, decoupled decay 0.1 on the
    matrices, gradients clipped to global norm 1.0 (the recipe of the
    family's reports, `assumed` in the configuration file). Call after the
    program is built (the clip is attached to its parameters)."""
    fluid.clip.set_gradient_clip(
        fluid.clip.GradientClipByGlobalNorm(clip_norm))
    return fluid.optimizer.Adam(
        learning_rate=learning_rate, beta1=0.9, beta2=0.95, epsilon=1e-8,
        weight_decay=weight_decay, apply_decay_param_fun=decays)
