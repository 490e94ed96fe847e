"""Nemotron-H (NVIDIA's `nemotron_h`): a decoder language model whose
layers are ONE branch each, read from a pattern string: `M` a Mamba-2
mixer (a selective state-space scan behind a 4-tap convolution with a
bias, a gated grouped norm behind it), `*` grouped-query attention WITHOUT
positions, `E` sigmoid-routed UN-GATED experts relu(x U)^2 D beside one
shared expert of the same form; built as the share ONE chip holds of a
model whose experts and vocabulary rows several chips divide.

Config keys are those of the model's published config.json
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), with
the counts of experts (`n_routed_experts`) and vocabulary rows
(`vocab_size`) those HELD here; `deployment` says what the layer has in all
(`n_routed_experts` the router's width, `first_expert` the first one held,
`num_hidden_layers` the published depth). Every layer is x <- x +
f(RMSNorm(x; norm)), x [T, C], T = rows x S tokens, u = RMSNorm(x):

    M:  [z | xBC | dt] = u W_in   [T, H P + (H P + 2 G N) + H]
        xBC <- silu(conv_L(xBC) + b), depth-wise, causal, zero before a
        row's first token (`short_conv`, gating "silu", with a bias)
        [x | B | C] = xBC;  y = ssd_scan(x, B, C, dt; A_log, dt_bias, D):
        delta = softplus(dt + dt_bias), per head h_t = exp(-exp(A_log)
        delta_t) h_{t-1} + delta_t x_t B_t^T, y_t = h_t C_t + D x_t, h = 0
        at a row's first token, head h on group h // (H / G)'s B and C
        y <- RMSNorm over each group's H P / G numbers of y * silu(z)
        (the gate BEFORE the norm; scale `gated_norm` [H P]);  f = y W_out
    *:  q = u W_q [T, Hq, D], k = u W_k, v = u W_v [T, Hkv, D];  f =
        softmax(q k^T / sqrt(D) + causal) v W_o, query head h on key/value
        head h // (Hq / Hkv); no rotary, no position of any kind
    E:  s = sigmoid(u W_r) over ALL experts; the `num_experts_per_tok`
        largest of s + e_score_correction_bias chosen (the bias chooses,
        takes no gradient and weighs nothing: `balance_routers` moves it
        after each step); w = the chosen s over their sum, times
        `routed_scaling_factor`;  f = the held experts' part of sum_e w_e
        relu(u U_e)^2 D_e  +  relu(u U_s)^2 D_s
    logits = RMSNorm(x; final_norm) W_head (untied).

The mixers, attention and the shared expert are whole (a chip runs them on
its own rows); the experts are the held ones', the table and the head the
held rows'. `fluid.name_scope`s put every op's lowering under `embed/`,
`mamba/` (with `norm`, `in_proj`, `conv`, `scan`, `gated_norm`, `out_proj`
below it), `attn/` (`norm`), `moe/` (`norm`, `shared`), `lm_head/`, and
`balance_routers`' under `router_bias/`.
"""

import math

import paddle_tpu as fluid
from paddle_tpu.models.qwen3_next import (_InverseSoftplusOfLogUniform,
                                          _LogUniform)
from paddle_tpu.models.xing4 import INIT_STD, balance_routers  # noqa: F401

MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
KIND_OF = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}
P = "nemotronh."
# A_log = log U(1, 16): the Mamba-2 code's `A_init_range` (`assumed.init`)
A_RANGE = (1.0, 16.0)


def layer_kinds(cfg):
    """The kind of each layer the program builds, from the pattern string
    (`-`, the family's dense MLP layer, is in no published pattern this
    model file was written for and is refused)."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"pattern {pattern!r} names {len(pattern)} layers, "
                         f"num_hidden_layers is {cfg['num_hidden_layers']}")
    unknown = set(pattern) - set(KIND_OF)
    if unknown:
        raise ValueError(f"pattern {pattern!r}: no layer kind {unknown}")
    return [KIND_OF[c] for c in pattern]


def residual_std(cfg):
    """The std of a matrix that WRITES INTO THE RESIDUAL STREAM (W_out,
    W_o, the experts' down matrices) under `rescale_prenorm_residual`: the
    others' divided by the root of the PUBLISHED depth."""
    if not cfg.get("rescale_prenorm_residual"):
        return INIT_STD
    return INIT_STD / math.sqrt(cfg["deployment"]["num_hidden_layers"])


def _weight(name, std=INIT_STD):
    return fluid.ParamAttr(name=name,
                           initializer=fluid.initializer.Normal(0.0, std))


def _linear(x, size, name, std=INIT_STD):
    return fluid.layers.fc(x, size, param_attr=_weight(name, std),
                           bias_attr=False)


def _norm(x, cfg, name, group_size=None):
    return fluid.layers.rms_norm(x, epsilon=cfg["layer_norm_epsilon"],
                                 param_attr=fluid.ParamAttr(name=name),
                                 group_size=group_size)


def mamba_widths(cfg):
    """(d_inner = heads x head size, the convolution's channels d_inner +
    2 groups x state, the input projection's width)."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, conv, inner + conv + cfg["mamba_num_heads"]


def mamba(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> (the Mamba-2 branch [T, C], the ops' own (the
    convolution's input and output [T, channels], dt [T, H], the scan's
    output [T, H P] and last state))."""
    L = fluid.layers
    heads, groups, state = (cfg["mamba_num_heads"], cfg["n_groups"],
                            cfg["ssm_state_size"])
    inner, conv, width = mamba_widths(cfg)
    with fluid.name_scope("in_proj"):
        z, xbc, dt = L.split(_linear(u, width, prefix + "w_in"),
                             [inner, conv, heads], dim=1)
    with fluid.name_scope("conv"):
        mixed = L.short_conv(
            xbc, seq_len, kernel_size=cfg["conv_kernel"],
            param_attr=_weight(prefix + "conv_taps"), gating="silu",
            bias_attr=fluid.ParamAttr(name=prefix + "conv_bias")
            if cfg["use_conv_bias"] else None)
    with fluid.name_scope("scan"):
        x, b, c = L.split(mixed, [inner, groups * state, groups * state],
                          dim=1)
        y, last = L.ssd_scan(
            x, b, c, dt, seq_len, heads, cfg["mamba_head_dim"], groups,
            state,
            a_log_attr=fluid.ParamAttr(name=prefix + "A_log",
                                       initializer=_LogUniform(*A_RANGE)),
            dt_bias_attr=fluid.ParamAttr(
                name=prefix + "dt_bias",
                initializer=_InverseSoftplusOfLogUniform(
                    max(cfg["time_step_min"], cfg["time_step_floor"]),
                    cfg["time_step_max"])),
            d_attr=fluid.ParamAttr(name=prefix + "D"),
            chunk=cfg.get("chunk_size"))
    with fluid.name_scope("gated_norm"):
        gated = _norm(L.elementwise_mul(y, L.swish(z)), cfg,
                      prefix + "gated_norm", group_size=inner // groups)
    with fluid.name_scope("out_proj"):
        return _linear(gated, cfg["hidden_size"], prefix + "w_out",
                       residual_std(cfg)), (xbc, mixed, dt, y, last)


def attention(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> (the attention branch [T, C], None: it has no
    op of its own to hold first-hand)."""
    L = fluid.layers
    heads, kv_heads, D = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])

    def heads_of(name, n):
        return L.reshape(_linear(u, n * D, prefix + name),
                         [-1, seq_len, n, D])

    o = L.causal_attention(heads_of("w_q", heads), heads_of("w_k", kv_heads),
                           heads_of("w_v", kv_heads))
    return _linear(L.reshape(o, [-1, heads * D]), cfg["hidden_size"],
                   prefix + "w_o", residual_std(cfg)), None


def experts(u, cfg, prefix):
    """u [T, C] (normed) -> (the held experts' part plus the shared expert
    [T, C], (expert ids, tokens per expert, rows held))."""
    L = fluid.layers
    dep = cfg["deployment"]
    y, _, _, ids, load, rows = L.moe_ffn(
        u, dep["n_routed_experts"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], router_attr=_weight(prefix + "router"),
        up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down", residual_std(cfg)),
        score_func="sigmoid", norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=cfg["routed_scaling_factor"],
        bias_attr=fluid.ParamAttr(name=prefix + "e_score_correction_bias"),
        held=(dep["first_expert"], cfg["n_routed_experts"]),
        activation=cfg["mlp_hidden_act"])
    with fluid.name_scope("shared"):
        width = cfg["moe_shared_expert_intermediate_size"] \
            * cfg["n_shared_experts"]
        hid = L.square(L.relu(_linear(u, width, prefix + "shared_up")))
        shared = _linear(hid, cfg["hidden_size"], prefix + "shared_down",
                         residual_std(cfg))
    return L.elementwise_add(y, shared), (ids, load, rows)


_SCOPES = {MAMBA: "mamba", ATTENTION: "attn", EXPERTS: "moe"}


def layer(x, cfg, seq_len, i, kind):
    """Layer i on x [T, C] -> (x', the branch's normed input, its output,
    and what the branch hands over of its own: a mixer's ops' values, an
    expert layer's routing, None of attention)."""
    prefix = f"{P}l{i}."
    with fluid.name_scope(_SCOPES[kind]):
        with fluid.name_scope("norm"):
            u = _norm(x, cfg, prefix + "norm")
        if kind == EXPERTS:
            branch, own = experts(u, cfg, prefix)
        else:
            branch, own = (mamba if kind == MAMBA else attention)(
                u, cfg, seq_len, prefix)
        return fluid.layers.elementwise_add(x, branch), u, branch, own


def nemotron_h(tokens, cfg):
    """tokens [B, S] int32 -> dict(logits [B*S, vocab], routing [(expert
    ids [T, k], tokens per expert [E], rows held [1])] for each EXPERT
    layer in order, expert_layers [their indices], operators [(kind, the
    branch's normed input, its output), both [T, C]] for each layer,
    mamba_ops {layer: (the convolution's input and output, dt, the scan's
    output and last state)} for each mixer)."""
    L = fluid.layers
    seq_len = int(tokens.shape[-1])
    with fluid.name_scope("embed"):
        x = L.embedding(L.reshape(tokens, [-1, 1]),
                        [cfg["vocab_size"], cfg["hidden_size"]],
                        param_attr=_weight(P + "embed"))
    routing, expert_layers, branches, mamba_ops = [], [], [], {}
    for i, kind in enumerate(layer_kinds(cfg)):
        x, u, branch, own = layer(x, cfg, seq_len, i, kind)
        branches.append((kind, u, branch))
        if kind == EXPERTS:
            routing.append(own)
            expert_layers.append(i)
        elif kind == MAMBA:
            mamba_ops[i] = own
    with fluid.name_scope("lm_head"):
        logits = _linear(_norm(x, cfg, P + "final_norm"), cfg["vocab_size"],
                         P + "head")
    return dict(logits=logits, routing=routing, expert_layers=expert_layers,
                operators=branches, mamba_ops=mamba_ops)


def nemotron_h_loss(out, labels):
    """Mean cross-entropy of the next token; labels [B, S] int32."""
    L = fluid.layers
    with fluid.name_scope("lm_head"):
        return L.reshape(L.mean(L.softmax_with_cross_entropy(
            out["logits"], L.reshape(labels, [-1, 1]))), [1])


def decays(name):
    """AdamW's decay acts on the matrices and the convolution's taps: not
    on the norm scales, A_log, dt_bias, D and the convolution's bias."""
    return not name.endswith(("norm", "A_log", "dt_bias", ".D", "conv_bias"))


def optimizer(learning_rate=3e-4, weight_decay=0.1, clip_norm=1.0):
    """AdamW beta 0.9 / 0.95, eps 1e-8, decoupled decay where `decays`,
    gradients clipped to global norm 1.0 (`assumed` in the configuration
    file). Call after the program is built (the clip is attached to its
    parameters)."""
    fluid.clip.set_gradient_clip(
        fluid.clip.GradientClipByGlobalNorm(clip_norm))
    return fluid.optimizer.Adam(
        learning_rate=learning_rate, beta1=0.9, beta2=0.95, epsilon=1e-8,
        weight_decay=weight_decay, apply_decay_param_fun=decays)
