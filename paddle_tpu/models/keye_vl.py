"""The language model of Keye-VL-2.0 (Kwai-Keye's `KeyeVL2`): a decoder
whose every layer is grouped-query attention OVER THE KEYS A LEARNED
INDEXER PICKS FOR EACH QUERY (DeepSeek Sparse Attention's lightning
indexer in front of Qwen3-MoE's attention) and softmax-routed experts;
built as the share ONE chip holds of a model whose experts and vocabulary
rows several chips divide. The vision tower is not built: the catalogued
configuration carries no size of it, and the tokens are text.

Config keys are those of the model's published config.json
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B), with the counts of
experts (`num_experts`) and vocabulary rows (`vocab_size`) those HELD
here; `deployment` says what the layer has in all (`num_experts` the
router's width, `first_expert` the first one held); `sa_config` is the
indexer's. x [T, C], T = rows x S tokens, u = RMSNorm(x; attn_norm):

    q = u W_q [T, H, D], k = u W_k, v = u W_v [T, Hkv, D]; q, k <-
        RMSNorm over each head's D numbers (q_norm, k_norm), then rotary
        (`rotate_half`, all of D; `mrope_section` shares the frequencies
        among three position ids that are equal for a text token)
    indexer, on ub = stop_gradient(u):
        q_I = rotary(ub W_qI) [T, Hi, Di];  k_I = rotary(LayerNorm(ub
        W_kI)) [T, Di], one key head for all;  w = (ub W_w) Di^-1/2 Hi^-1/2
        I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t, float32
        S_t = the `topk` keys s <= t of largest I[t, s] (`indexer_select`)
    o[t, h] = softmax over S_t alone of q[t, h] . k[s, h // (H / Hkv)] /
        sqrt(D), times v (`sparse_attention`);  x <- x + o W_o
    L_I = mean_t KL(stop_gradient(mean_h A[t, h, .]) || softmax_{S_t} I[t,
        .]) (`indexer_loss`): it reaches W_qI, W_kI, the LayerNorm and W_w
        and nothing else, and nothing of the model's loss reaches them
    u' = RMSNorm(x; ffn_norm);  p = softmax(u' W_r) over ALL experts, the
    `num_experts_per_tok` largest chosen and renormalised to sum 1
    x <- x + the held experts' part of sum_e p_e (silu(u' G_e) * (u'
         U_e)) D_e
    logits = RMSNorm(x; final_norm) W_head (untied);
    loss = cross-entropy + sum over the layers of L_I.

The model has no bias of the choice and no rule that moves one:
`expert_bias` is a zero vector that nothing writes (as `qwen3_next`'s).
THE TABLE IS DRAWN AT STD 4 (`EMBED_STD`), every other matrix at 0.02:
with never-trained weights attention is a near-uniform mean of the values,
common to every query (the Zipf ids' most frequent tokens weigh most in
it), and at a table of std 0.02 that mean is the larger part of the stream
behind the first layer: every token of a row then looks alike, every router
sends all of them to the same eight experts (tokens per expert max / mean
15.9 of a possible 16, my chip run, PR 49), the held experts' share is a
lottery of the seed, and Adam's sign-like steps push every router further
along the common direction as a run goes on (at std 1 the last layers
still drifted past the row bound within 150 steps). A trained model's
tokens stay apart; the table's scale is the stand-in that keeps them so.
Attention and the indexer are whole (a chip runs them on its own rows);
the experts are the held ones', the table and the head the held rows'.
`fluid.name_scope`s put every op's lowering under `embed/`, `attn/` (with
`norm`, `qkv`, `qk_norm`, `rotary`, `indexer`, `select`,
`sparse_attention`, `indexer_loss`, `out_proj` below it), `moe/`,
`lm_head/`.
"""

import paddle_tpu as fluid
from paddle_tpu.models.xing4 import _linear, _weight

P = "keyevl."
LAYER_NORM_EPS = 1e-6
EMBED_STD = 4.0


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg["rms_norm_eps"],
                                 param_attr=fluid.ParamAttr(name=name))


def indexer(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> q_I [B, S, Hi, Di], k_I [B, S, 1, Di], w [B,
    S, Hi] of the lightning indexer, on a copy of u no gradient crosses."""
    L = fluid.layers
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    ub = L.detached(u)

    def roped(t, heads):
        return L.rotary_embedding(L.reshape(t, [-1, seq_len, heads, di]),
                                  theta=cfg["rope_theta"])

    q_i = roped(_linear(ub, hi * di, prefix + "w_qi"), hi)
    k_i = roped(L.layer_norm(
        _linear(ub, di, prefix + "w_ki"), begin_norm_axis=1,
        epsilon=LAYER_NORM_EPS,
        param_attr=fluid.ParamAttr(name=prefix + "ki_norm"),
        bias_attr=fluid.ParamAttr(name=prefix + "ki_norm_bias")), 1)
    w = L.reshape(L.scale(_linear(ub, hi, prefix + "w_w"),
                          scale=float(di) ** -0.5 * float(hi) ** -0.5),
                  [-1, seq_len, hi])
    return q_i, k_i, w


def attention(u, cfg, seq_len, prefix):
    """u [T, C] (normed) -> (the attention branch [T, C], the indexer's
    loss [1], the layer's own values for a first-hand comparison: q, k, v
    as the attention op reads them, q_I, k_I, w, the mask, the logsumexp,
    the attention's output before W_o)."""
    L = fluid.layers
    heads, kv_heads, D = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])

    def heads_of(t, n, norm=None):
        t = L.reshape(t, [-1, seq_len, n, D])
        if norm is None:
            return t
        with fluid.name_scope("qk_norm"):
            t = _norm(t, cfg, prefix + norm)
        with fluid.name_scope("rotary"):
            return L.rotary_embedding(t, theta=cfg["rope_theta"])

    with fluid.name_scope("qkv"):
        q, k, v = (_linear(u, n * D, prefix + name) for n, name in (
            (heads, "w_q"), (kv_heads, "w_k"), (kv_heads, "w_v")))
    q, k = heads_of(q, heads, "q_norm"), heads_of(k, kv_heads, "k_norm")
    v = heads_of(v, kv_heads)
    with fluid.name_scope("indexer"):
        q_i, k_i, w = indexer(u, cfg, seq_len, prefix)
    with fluid.name_scope("select"):
        mask, threshold = L.indexer_select(q_i, k_i, w,
                                           cfg["sa_config"]["topk"])
    with fluid.name_scope("sparse_attention"):
        o, lse = L.sparse_attention(q, k, v, mask)
    with fluid.name_scope("indexer_loss"):
        loss = L.indexer_loss(q, k, lse, q_i, k_i, w, mask)
    with fluid.name_scope("out_proj"):
        o = L.reshape(o, [-1, heads * D])
        return _linear(o, cfg["hidden_size"], prefix + "w_o"), loss, \
            (q, k, v, q_i, k_i, w, mask, lse, o, threshold)


def experts(u, cfg, prefix):
    """u [T, C] (normed) -> (the held experts' part [T, C], (expert ids,
    tokens per expert, rows held))."""
    dep = cfg["deployment"]
    y, _, _, ids, load, rows = fluid.layers.moe_ffn(
        u, dep["num_experts"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], router_attr=_weight(prefix + "router"),
        gate_attr=_weight(prefix + "gate"), up_attr=_weight(prefix + "up"),
        down_attr=_weight(prefix + "down"), score_func="softmax",
        norm_topk=bool(cfg["norm_topk_prob"]),
        bias_attr=fluid.ParamAttr(name=prefix + "expert_bias"),
        held=(dep["first_expert"], cfg["num_experts"]))
    return y, (ids, load, rows)


def layer(x, cfg, seq_len, i):
    """Layer i on x [T, C] -> (x', routing, the indexer's loss, (the
    attention branch's normed input, its output, its own values))."""
    L = fluid.layers
    prefix = f"{P}l{i}."
    with fluid.name_scope("attn"):
        with fluid.name_scope("norm"):
            u = _norm(x, cfg, prefix + "attn_norm")
        branch, loss, own = attention(u, cfg, seq_len, prefix)
        x = L.elementwise_add(x, branch)
    with fluid.name_scope("moe"):
        y, routing = experts(_norm(x, cfg, prefix + "ffn_norm"), cfg, prefix)
        return L.elementwise_add(x, y), routing, loss, (u, branch, own)


def keye_vl(tokens, cfg):
    """tokens [B, S] int32 -> dict(logits [B*S, vocab], routing [(expert
    ids [T, k], tokens per expert [E], rows held [1])] for each layer,
    indexer_losses [a [1] float32 for each layer], attention [(the
    branch's normed input [T, C], its output [T, C], (q, k, v, q_I, k_I,
    w, mask, lse, the attention's output [T, H D]))] for each layer)."""
    L = fluid.layers
    seq_len = int(tokens.shape[-1])
    with fluid.name_scope("embed"):
        x = L.embedding(L.reshape(tokens, [-1, 1]),
                        [cfg["vocab_size"], cfg["hidden_size"]],
                        param_attr=fluid.ParamAttr(
                            name=P + "embed",
                            initializer=fluid.initializer.Normal(
                                0.0, EMBED_STD)))
    routing, losses, branches = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        x, r, loss, own = layer(x, cfg, seq_len, i)
        routing.append(r)
        losses.append(loss)
        branches.append(own)
    with fluid.name_scope("lm_head"):
        logits = _linear(_norm(x, cfg, P + "final_norm"), cfg["vocab_size"],
                         P + "head")
    return dict(logits=logits, routing=routing, indexer_losses=losses,
                attention=branches)


def keye_vl_loss(out, labels):
    """(the step's loss [1] = the mean cross-entropy of the next token +
    the sum over the layers of the indexer's loss, the cross-entropy [1],
    the indexers' sum [1]); labels [B, S] int32. One backward pass, two
    disjoint sets of parameters."""
    L = fluid.layers
    with fluid.name_scope("lm_head"):
        ce = L.reshape(L.mean(L.softmax_with_cross_entropy(
            out["logits"], L.reshape(labels, [-1, 1]))), [1])
    # `sum`, not `elementwise_add`: under AMP that one rounds to bf16
    with fluid.name_scope("attn"), fluid.name_scope("indexer_loss"):
        indexers = L.sums(out["indexer_losses"])
        return L.sums([ce, indexers]), ce, indexers


def decays(name):
    """AdamW's decay acts on the matrices: not on the norm scales nor on
    the indexer's LayerNorm."""
    return not name.endswith(("norm", "norm_bias"))


def optimizer(learning_rate=3e-4, weight_decay=0.1, clip_norm=1.0):
    """AdamW beta 0.9 / 0.95, eps 1e-8, decoupled decay where `decays`,
    gradients clipped to global norm 1.0 (`assumed` in the configuration
    file), the indexer's parameters at the model's rate. Call after the
    program is built (the clip is attached to its parameters)."""
    fluid.clip.set_gradient_clip(
        fluid.clip.GradientClipByGlobalNorm(clip_norm))
    return fluid.optimizer.Adam(
        learning_rate=learning_rate, beta1=0.9, beta2=0.95, epsilon=1e-8,
        weight_decay=weight_decay, apply_decay_param_fun=decays)
