"""Qwen3-Next (Qwen's `qwen3_next`): a decoder language model three of
whose four layers mix tokens with a GATED DELTA RULE (Gated DeltaNet: a
matrix-valued state carried along the sequence, behind a 4-tap
convolution) and the fourth with output-gated grouped-query attention at
heads of 256, every layer's feed-forward softmax-routed experts beside one
shared expert behind a sigmoid gate; built as the share ONE chip holds of a
model whose experts and vocabulary rows several chips divide.

Config keys are those of the model's published config.json
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), with the counts
of experts (`num_experts`) and vocabulary rows (`vocab_size`) those HELD
here; `deployment` says what the layer has in all (`num_experts` the
router's width, `first_expert` the first one held). Layer i is a
full-attention layer where (i + 1) % `full_attention_interval` == 0, else
a delta layer. x [T, C], T = rows x S tokens, u = RMSNorm(x;
operator_norm):

    delta:  [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
            [q | k | v] <- silu(conv_L([q | k | v])), depth-wise, causal,
            zero before a row's first token (`short_conv`, gating "silu")
            o = gated_delta_rule([q | k | v], [b | a]; A_log, dt_bias): per
            head q, k L2-normalised (q times dk^-1/2), beta = sigmoid(b), g
            = -exp(A_log) softplus(a + dt_bias), and per value head
            S <- exp(g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
            o_t = S^T q_t, S = 0 at a row's first token
            y = RMSNorm_dv(o; gated_norm [dv]) * silu(z), float32 inside
            and rounded once (`gated_rms_norm`);  x <- x + y W_o
    full:   [q | gate] = u W_qg;  k = u W_k, v = u W_v [T, Hkv, D]
            q, k <- RMSNorm over each head's D numbers (q_norm, k_norm),
            then rotary on the first `partial_rotary_factor` x D numbers;
            attn = softmax(q k^T / sqrt(D) + causal) v, query head h on
            key/value head h // (H / Hkv);  x <- x + (attn *
            sigmoid(gate)) W_o
    u' = RMSNorm(x; ffn_norm);  p = softmax(u' W_r) over ALL experts, the
    `num_experts_per_tok` largest chosen and renormalised to sum 1
    x <- x + the held experts' part of sum_e p_e (silu(u' G_e) * (u'
         U_e)) D_e + sigmoid(u' w_s) * (silu(u' G_s) * (u' U_s)) D_s
    logits = RMSNorm(x; final_norm) W_head (untied).

The model has no bias of the choice and no rule that moves one:
`expert_bias` is a zero vector that nothing writes (the share path's
`moe_ffn` and the token kinds' timed loop name the router's bias; added to
the logits it changes no choice). The operators and the shared expert are
whole (a chip runs them on its own rows); the experts are the held ones',
the table and the head the held rows'. `fluid.name_scope`s put every op's
lowering under `embed/`, `delta/` (with `norm`, `in_proj`, `short_conv`,
`delta_rule`, `gated_norm`, `out_proj` below it), `attn/` (`norm`,
`qk_norm`, `rotary`, `gate`), `moe/` (`shared`), `lm_head/`.
"""

import math

import paddle_tpu as fluid
from paddle_tpu.initializer import Initializer
from paddle_tpu.models.xing4 import INIT_STD, _linear, _weight  # noqa: F401

DELTA, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6
P = "qwen3next."
# the family's initialisers of the two per-head scalars (`assumed.init`)
A_RANGE = (1e-3, 16.0)
DT_RANGE = (1e-3, 1e-1)


class _LogUniform(Initializer):
    """log of U(low, high): A_log."""

    def __init__(self, low, high):
        self._low, self._high = low, high

    def _uniform(self, var, block, low, high):
        block.append_op("uniform_random", {}, {"Out": [var]}, {
            "shape": list(var.shape), "min": float(low), "max": float(high),
            "seed": 0, "dtype": var.dtype})

    def __call__(self, var, block):
        self._uniform(var, block, self._low, self._high)
        return block.append_op("log", {"X": [var]}, {"Out": [var]}, {})


class _InverseSoftplusOfLogUniform(_LogUniform):
    """dt + log(1 - exp(-dt)), dt log-uniform in [low, high]: dt_bias, the
    number whose softplus is dt."""

    def __call__(self, var, block):
        def tmp():
            return block.create_var(
                name=fluid.unique_name.generate(var.name + ".init"),
                shape=var.shape, dtype=var.dtype)

        def op(kind, x, attrs=None):
            y = tmp()
            block.append_op(kind, {"X": [x]}, {"Out": [y]}, attrs or {})
            return y

        log_dt = tmp()
        self._uniform(log_dt, block, math.log(self._low),
                      math.log(self._high))
        dt = op("exp", log_dt)
        gap = op("log", op("scale", op("exp", op("scale", dt,
                                                 {"scale": -1.0})),
                           {"scale": -1.0, "bias": 1.0}))
        # `sum`, not `elementwise_add`: under AMP that one rounds to bf16
        return block.append_op("sum", {"X": [dt, gap]}, {"Out": [var]}, {})


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg["rms_norm_eps"],
                                 param_attr=fluid.ParamAttr(name=name))


def layer_kinds(cfg):
    """The operator kind of each layer the program builds."""
    every = cfg["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else DELTA
            for i in range(cfg["num_hidden_layers"])]


def delta(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> (the Gated DeltaNet branch [T, C], the ops' own
    (the convolution's input and output [T, channels], [b | a] [T, 2 Hv],
    the recurrence's output [T, Hv dv] and last state))."""
    L = fluid.layers
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    conv = 2 * hk * dk + hv * dv
    with fluid.name_scope("in_proj"):
        qkv, z = L.split(_linear(u, conv + hv * dv, prefix + "w_qkvz"),
                         [conv, hv * dv], dim=1)
        ba = _linear(u, 2 * hv, prefix + "w_ba")
    with fluid.name_scope("short_conv"):
        mixed = L.short_conv(
            qkv, seq_len, kernel_size=cfg["linear_conv_kernel_dim"],
            param_attr=_weight(prefix + "conv_taps"), gating="silu")
    with fluid.name_scope("delta_rule"):
        o, last = L.gated_delta_rule(
            mixed, ba, seq_len, hk, hv, dk, dv,
            a_log_attr=fluid.ParamAttr(name=prefix + "A_log",
                                       initializer=_LogUniform(*A_RANGE)),
            dt_bias_attr=fluid.ParamAttr(
                name=prefix + "dt_bias",
                initializer=_InverseSoftplusOfLogUniform(*DT_RANGE)),
            epsilon=L2_EPS)
    with fluid.name_scope("gated_norm"):
        y = L.reshape(L.gated_rms_norm(
            L.reshape(o, [-1, hv, dv]), z, epsilon=cfg["rms_norm_eps"],
            param_attr=fluid.ParamAttr(name=prefix + "gated_norm")),
            [-1, hv * dv])
    with fluid.name_scope("out_proj"):
        return _linear(y, cfg["hidden_size"], prefix + "w_o"), \
            (qkv, mixed, ba, o, last)


def attention(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> (the output-gated attention branch [T, C],
    None: it has no op of its own to hold first-hand)."""
    L = fluid.layers
    heads, kv_heads, D = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    rotary_dim = int(D * cfg["partial_rotary_factor"])

    def heads_of(t, n, norm):
        with fluid.name_scope("qk_norm"):
            t = _norm(L.reshape(t, [-1, seq_len, n, D]), cfg, prefix + norm)
        with fluid.name_scope("rotary"):
            return L.rotary_embedding(t, theta=cfg["rope_theta"],
                                      rotary_dim=rotary_dim)

    q, gate = L.split(_linear(u, 2 * heads * D, prefix + "w_qg"), 2, dim=1)
    q = heads_of(q, heads, "q_norm")
    k = heads_of(_linear(u, kv_heads * D, prefix + "w_k"), kv_heads,
                 "k_norm")
    v = L.reshape(_linear(u, kv_heads * D, prefix + "w_v"),
                  [-1, seq_len, kv_heads, D])
    o = L.reshape(L.causal_attention(q, k, v), [-1, heads * D])
    with fluid.name_scope("gate"):
        o = L.elementwise_mul(o, L.sigmoid(gate))
    return _linear(o, cfg["hidden_size"], prefix + "w_o"), None


def experts(u, cfg, prefix):
    """u [T, C] (normed) -> (the held experts' part plus the gated shared
    expert [T, C], (expert ids, tokens per expert, rows held))."""
    L = fluid.layers
    dep = cfg["deployment"]
    y, _, _, ids, load, rows = L.moe_ffn(
        u, dep["num_experts"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], router_attr=_weight(prefix + "router"),
        gate_attr=_weight(prefix + "gate"), up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down"), score_func="softmax",
        norm_topk=bool(cfg["norm_topk_prob"]),
        bias_attr=fluid.ParamAttr(name=prefix + "expert_bias"),
        held=(dep["first_expert"], cfg["num_experts"]))
    with fluid.name_scope("shared"):
        width = cfg["shared_expert_intermediate_size"]
        hid = L.elementwise_mul(
            L.swish(_linear(u, width, prefix + "shared_gate")),
            _linear(u, width, prefix + "shared_up"))
        shared = L.elementwise_mul(
            _linear(hid, cfg["hidden_size"], prefix + "shared_down"),
            L.sigmoid(_linear(u, 1, prefix + "shared_w")), axis=0)
    return L.elementwise_add(y, shared), (ids, load, rows)


def layer(x, cfg, seq_len, i, kind):
    """Layer i on x [T, C] -> (x', routing, (the operator branch's normed
    input, its output, and of a delta layer its ops' own values))."""
    L = fluid.layers
    prefix = f"{P}l{i}."
    with fluid.name_scope("delta" if kind == DELTA else "attn"):
        with fluid.name_scope("norm"):
            u = _norm(x, cfg, prefix + "operator_norm")
        branch, own = (delta if kind == DELTA else attention)(
            u, cfg, seq_len, prefix)
        x = L.elementwise_add(x, branch)
    with fluid.name_scope("moe"):
        y, routing = experts(_norm(x, cfg, prefix + "ffn_norm"), cfg, prefix)
        return L.elementwise_add(x, y), routing, (u, branch, own)


def qwen3_next(tokens, cfg):
    """tokens [B, S] int32 -> dict(logits [B*S, vocab], routing [(expert
    ids [T, k], tokens per expert [E], rows held [1])] for each layer,
    operators [(kind, the operator branch's normed input, its output),
    both [T, C]] for each layer, delta_ops {layer: (the convolution's
    input and output, [b | a], the recurrence's output and last state)}
    for each delta layer)."""
    L = fluid.layers
    seq_len = int(tokens.shape[-1])
    with fluid.name_scope("embed"):
        x = L.embedding(L.reshape(tokens, [-1, 1]),
                        [cfg["vocab_size"], cfg["hidden_size"]],
                        param_attr=_weight(P + "embed"))
    routing, branches, delta_ops = [], [], {}
    for i, kind in enumerate(layer_kinds(cfg)):
        x, r, (u, branch, own) = layer(x, cfg, seq_len, i, kind)
        routing.append(r)
        branches.append((kind, u, branch))
        if own is not None:
            delta_ops[i] = own
    with fluid.name_scope("lm_head"):
        logits = _linear(_norm(x, cfg, P + "final_norm"), cfg["vocab_size"],
                         P + "head")
    return dict(logits=logits, routing=routing, operators=branches,
                delta_ops=delta_ops)


def qwen3_next_loss(out, labels):
    """Mean cross-entropy of the next token; labels [B, S] int32."""
    L = fluid.layers
    with fluid.name_scope("lm_head"):
        return L.reshape(L.mean(L.softmax_with_cross_entropy(
            out["logits"], L.reshape(labels, [-1, 1]))), [1])


def decays(name):
    """AdamW's decay acts on the matrices and the convolution's taps: not
    on the norm scales, A_log and dt_bias."""
    return not name.endswith(("norm", "A_log", "dt_bias"))


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
