"""Xing4.0-29B-A4B, plainly: forward pass, both cross-entropies, gradients
and the first AdamW update in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; no kernels, no sorting, no
grouped products. Independent of `paddle_tpu`.

The layer equations are those of `paddle_tpu/models/xing4.py`'s docstring
(latent attention as DeepSeek-V2/V3, arXiv:2405.04434 / 2412.19437; the
residual path of `hc_mult` streams as Xie et al., mHC, arXiv:2512.24880;
the `noaux_tc` sigmoid router and the multi-token-prediction module as
DeepSeek-V3). Weights are a dict by name; `param_shapes` lists them.

THE SHARE. A layer may be divided over several chips: `cfg` then counts
the heads, routed experts and vocabulary rows HELD (`num_attention_heads`,
`n_routed_experts`, `vocab_size`) and `cfg["deployment"]` gives the
router's width (`n_routed_experts`) and the first expert held
(`first_expert`). The router scores and chooses over ALL experts; the
result is the held experts' part (what the others would add is left out)
plus the shared expert, which every chip computes alike. With a
deployment that holds everything this file is the uncut model, and
`share_of` cuts an uncut model's weights down to one chip's.

Departures, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
* the experts' matrices are stacked: gate / up [E', in, F], down [E', F, out];
* the expert layer is computed DENSE, every token through every held
  expert, masked by the router weights: the same function as routing,
  and it shares no sorting or grouping code with the system under test;
* each decoder block is wrapped in `jax.checkpoint`: the same numbers,
  and the float32 backward of 6 blocks at [4096, 4, 3584] fits the chip.
"""

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"


def _layer_names(cfg):
    """[(prefix, dense?)] of the decoder layers, the module's last."""
    names = [(f"xing.l{i}.", i < cfg["first_k_dense_replace"])
             for i in range(cfg["num_hidden_layers"])]
    if cfg.get("num_nextn_predict_layers"):
        names.append(("xing.mtp.", False))
    return names


def param_shapes(cfg):
    """{name: shape} of every weight the share holds."""
    C, V, n = cfg["hidden_size"], cfg["vocab_size"], cfg["hc_mult"]
    heads, E = cfg["num_attention_heads"], cfg["n_routed_experts"]
    F, Fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    Fs = F * cfg["n_shared_experts"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    shapes = {"xing.embed": (V, C)}
    for p, dense in _layer_names(cfg):
        for sub in ("attn_", "ffn_"):
            m = p + sub + "mhc_"
            shapes.update({
                m + "phi_pre": (n * C, n), m + "phi_post": (n * C, n),
                m + "phi_res": (n * C, n * n), m + "alpha": (3,),
                m + "b_pre": (n,), m + "b_post": (n,), m + "b_res": (n * n,),
                p + sub + "norm": (C,)})
        shapes.update({
            p + "w_qa": (C, qr), p + "q_norm": (qr,),
            p + "w_qb": (qr, heads * (nope + rope)),
            p + "w_kva": (C, kvr + rope), p + "kv_norm": (kvr,),
            p + "w_kvb": (kvr, heads * (nope + dv)),
            p + "w_o": (heads * dv, C)})
        if dense:
            shapes.update({p + "mlp_gate": (C, Fd), p + "mlp_up": (C, Fd),
                           p + "mlp_down": (Fd, C)})
        else:
            shapes.update({
                p + "router": (C, cfg["deployment"]["n_routed_experts"]),
                p + "router_bias": (cfg["deployment"]["n_routed_experts"],),
                p + "gate": (E, C, F), p + "up": (E, C, F),
                p + "down": (E, F, C), p + "shared_gate": (C, Fs),
                p + "shared_up": (C, Fs), p + "shared_down": (Fs, C)})
    shapes.update({"xing.final_norm": (C,), "xing.head": (C, V)})
    if cfg.get("num_nextn_predict_layers"):
        shapes.update({"xing.mtp.h_norm": (C,), "xing.mtp.e_norm": (C,),
                       "xing.mtp.proj": (2 * C, C),
                       "xing.mtp.final_norm": (C,)})
    return shapes


def trained(name):
    """The router's bias is state, not a trained parameter."""
    return not name.endswith("router_bias")


def share_of(cfg, w, first_expert, n_experts, first_head, n_heads,
             first_row=0, n_rows=None):
    """One chip's share of an uncut model: (cfg, weights) with the experts
    [first_expert, +n_experts), the heads [first_head, +n_heads) and the
    vocabulary rows [first_row, +n_rows) of `w`; everything else whole."""
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    n_rows = cfg["vocab_size"] if n_rows is None else n_rows
    H = cfg["num_attention_heads"]
    part = dict(cfg, num_attention_heads=n_heads, num_key_value_heads=n_heads,
                n_routed_experts=n_experts, vocab_size=n_rows,
                deployment=dict(cfg["deployment"], first_expert=first_expert))
    hs, es = slice(first_head, first_head + n_heads), slice(
        first_expert, first_expert + n_experts)
    out = {}
    for name, v in w.items():
        leaf = name.rsplit(".", 1)[1]
        if leaf in ("w_qb", "w_kvb"):
            width = nope + (rope if leaf == "w_qb" else dv)
            v = v.reshape(v.shape[0], H, width)[:, hs].reshape(
                v.shape[0], n_heads * width)
        elif leaf == "w_o":
            v = v.reshape(H, dv, -1)[hs].reshape(n_heads * dv, -1)
        elif leaf in ("gate", "up", "down"):
            v = v[es]
        elif name == "xing.embed":
            v = v[first_row:first_row + n_rows]
        elif name == "xing.head":
            v = v[:, first_row:first_row + n_rows]
        out[name] = v
    return part, out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_frequencies(cfg):
    """The rotary pairs' frequencies, YaRN's where `rope_scaling` says so
    (DeepSeek-V3's modelling code: `yarn_find_correction_range`,
    `yarn_linear_ramp_mask`)."""
    D, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    rs = cfg.get("rope_scaling")
    if not rs or rs["factor"] <= 1.0:
        return freq

    def pair_of(turns):
        return (D * math.log(rs["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / rs["factor"] * ramp + freq * (1.0 - ramp)


def rope(x, cfg):
    """x [B, S, heads, D]: rotate_half convention, position = index in S."""
    S, D = x.shape[1], x.shape[3]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * yarn_frequencies(cfg)
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def softmax_scale(cfg):
    rs = cfg.get("rope_scaling") or {}
    m = 1.0
    if rs.get("factor", 1.0) > 1.0 and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def attention(u, w, p, cfg):
    """u [B, S, C] (normed) -> the held heads' part of the branch."""
    B, S, _ = u.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rp, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    c_q = rms_norm(u @ w[p + "w_qa"], w[p + "q_norm"], eps)
    q = (c_q @ w[p + "w_qb"]).reshape(B, S, heads, nope + rp)
    kva = u @ w[p + "w_kva"]
    c_kv = rms_norm(kva[..., :cfg["kv_lora_rank"]], w[p + "kv_norm"], eps)
    k_rope = rope(kva[..., cfg["kv_lora_rank"]:][:, :, None, :], cfg)
    kv = (c_kv @ w[p + "w_kvb"]).reshape(B, S, heads, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, S, heads, rp))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale(cfg)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    pr = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, kv[..., nope:])
    return o.reshape(B, S, heads * dv) @ w[p + "w_o"]


def swiglu(u, w, p):
    return (jax.nn.silu(u @ w[p + "gate"]) * (u @ w[p + "up"])) @ w[p + "down"]


def route(u, w, p, cfg, chosen=None):
    """u [T, C] -> (scores [T, E], the scores the choice is made by
    (score + bias) [T, E], chosen experts [T, k], their weights [T, k]).
    With `chosen` [T, k] (a system's own choice: `loss_and_grads(routing=)`)
    the weights are those experts', by this function's own scores; the
    chosen experts returned stay the free top-k."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ w[p + "router"])
    biased = scores + jax.lax.stop_gradient(w[p + "router_bias"])
    _, top_e = jax.lax.top_k(biased, k)
    top_s = jnp.take_along_axis(scores, top_e if chosen is None else chosen,
                                axis=1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (top_s.sum(axis=1, keepdims=True) + 1e-20)
    return scores, biased, top_e, top_s * cfg["routed_scaling_factor"]


def experts(u, w, p, cfg, chosen=None):
    """u [T, C] (normed) -> (the held experts' part [T, C], the shared
    expert [T, C], (biased scores [T, E], chosen experts [T, k])): the
    part of the free top-k, or of `chosen` [T, k] where that is given (the
    pair returned is the free choice either way)."""
    E_all = cfg["deployment"]["n_routed_experts"]
    first, held = cfg["deployment"]["first_expert"], cfg["n_routed_experts"]
    _, biased, top_e, top_w = route(u, w, p, cfg, chosen)
    # DEPARTURE: dense over the held experts, masked by the router weights
    weight = jnp.einsum("tk,tke->te", top_w, jax.nn.one_hot(
        top_e if chosen is None else chosen, E_all, dtype=top_w.dtype))
    weight = weight[:, first:first + held]
    g = jnp.einsum("tc,ecf->tef", u, w[p + "gate"])
    a = jnp.einsum("tc,ecf->tef", u, w[p + "up"])
    hid = jax.nn.silu(g) * a * weight[:, :, None]
    return (jnp.einsum("tef,efc->tc", hid, w[p + "down"]),
            swiglu(u, w, p + "shared_"), (biased, top_e))


def sinkhorn(m, iters, eps):
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def mixers(x, w, m, cfg):
    """x [..., n, C] -> (HPre [..., n], HPost [..., n], HRes [..., n, n])."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    flat = x.reshape(x.shape[:-2] + (-1,))
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    a = w[m + "alpha"]
    pre = jax.nn.sigmoid(a[0] * (xt @ w[m + "phi_pre"]) + w[m + "b_pre"])
    post = 2.0 * jax.nn.sigmoid(a[1] * (xt @ w[m + "phi_post"])
                                + w[m + "b_post"])
    res = a[2] * (xt @ w[m + "phi_res"]) + w[m + "b_res"]
    res = jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                   cfg["mhc_h_res_clamp_max"])
    res = jnp.exp(res).reshape(res.shape[:-1] + (n, n))
    return pre, post, sinkhorn(res, cfg["hc_sinkhorn_iters"], eps)


def around(x, w, prefix, cfg, sublayer):
    """x' = HRes x + HPost (x) sublayer(RMSNorm(sum_i HPre_i x_i))."""
    pre, post, res = mixers(x, w, prefix + "mhc_", cfg)
    u = jnp.einsum("...n,...nc->...c", pre, x)
    y = sublayer(rms_norm(u, w[prefix + "norm"], cfg["rms_norm_eps"]))
    return (jnp.einsum("...ij,...jc->...ic", res, x)
            + post[..., None] * y[..., None, :])


def layer(x, w, p, cfg, dense, chosen=None):
    """x [B, S, n, C] -> (x', (biased scores, chosen) or None); `chosen`
    [T, k]: the experts the layer's tokens are sent to (`experts`)."""
    B, S = x.shape[:2]
    x = around(x, w, p + "attn_", cfg, lambda u: attention(u, w, p, cfg))
    if dense:
        return around(x, w, p + "ffn_", cfg,
                      lambda u: swiglu(u, w, p + "mlp_")), None
    routing = []

    def ffn(u):
        part, shared, r = experts(u.reshape(B * S, -1), w, p, cfg, chosen)
        routing.append(r)
        return (part + shared).reshape(B, S, -1)

    return around(x, w, p + "ffn_", cfg, ffn), routing[0]


def _streams(h, n):
    return jnp.broadcast_to(h[..., None, :], h.shape[:-1] + (n, h.shape[-1]))


def forward(cfg, w, tokens, next_tokens, given=None):
    """tokens, next_tokens [B, S] -> (logits [B, S, V], the module's
    logits or None, [(biased scores [T, E], chosen [T, k])] for each
    expert layer, the module's last). `given`: [chosen experts [T, k]] in
    that order, which the layers then send their tokens to; the list
    returned holds each layer's own free choice either way."""
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    x = _streams(w["xing.embed"][tokens], n)
    routing = []
    for p, dense in _layer_names(cfg):
        if p == "xing.mtp.":
            break
        chosen = None if given is None or dense else given[len(routing)]
        # DEPARTURE: a block's activations are computed again in the
        # backward (the same numbers; memory)
        x, r = jax.checkpoint(
            lambda x_, w_, c_, p=p, dense=dense: layer(
                x_, w_, p, cfg, dense, c_))(x, w, chosen)
        if r is not None:
            routing.append(r)
    h = x.sum(axis=-2)
    logits = rms_norm(h, w["xing.final_norm"], eps) @ w["xing.head"]
    if not cfg.get("num_nextn_predict_layers"):
        return logits, None, routing
    joined = jnp.concatenate(
        [rms_norm(h, w["xing.mtp.h_norm"], eps),
         rms_norm(w["xing.embed"][next_tokens], w["xing.mtp.e_norm"], eps)],
        -1)
    x, r = jax.checkpoint(
        lambda x_, w_, c_: layer(x_, w_, "xing.mtp.", cfg, False, c_))(
            _streams(joined @ w["xing.mtp.proj"], n), w,
            None if given is None else given[len(routing)])
    routing.append(r)
    mtp = rms_norm(x.sum(axis=-2), w["xing.mtp.final_norm"],
                   eps) @ w["xing.head"]
    return logits, mtp, routing


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss_fn(cfg, w, tokens, labels, given=None):
    """CE + mtp_coef * CE_mtp: `labels` the next tokens (which the module
    embeds), the module's labels the ones after, a row's last position
    left out. Returns (loss, (CE, CE_mtp, logits, module's logits,
    routing))."""
    logits, mtp, routing = forward(cfg, w, tokens, labels, given)
    ce = jnp.mean(_cross_entropy(logits, labels))
    if mtp is None:
        return ce, (ce, None, logits, None, routing)
    ce_mtp = jnp.mean(_cross_entropy(mtp[:, :-1], labels[:, 1:]))
    return (ce + cfg["loss"]["mtp_loss_coef"] * ce_mtp,
            (ce, ce_mtp, logits, mtp, routing))


def loss_and_grads(cfg, w, tokens, labels, routing=None):
    """`routing`: [the expert ids [T, k] a SYSTEM chose] for each expert
    layer in `forward`'s order. The reference then sends every token where
    the system sent it, weighs those experts by its own scores, and still
    returns its own free top-k beside: a near-tie that fell the other way
    is judged once, as a choice, and not again in every number behind it
    (PR 56). None: the plain reference."""
    # tokens and labels are arguments, not constants of the compiled
    # program: another seed's row then finds it in the compile cache
    with jax.default_matmul_precision(PRECISION):
        (loss, rest), grads = jax.jit(jax.value_and_grad(
            lambda w_, t, l, r: loss_fn(cfg, w_, t, l, r),
            has_aux=True))(w, tokens, labels, routing)
    return loss, rest, {k: g for k, g in grads.items() if trained(k)}


def decays(name):
    return not (name.endswith("norm") or name.endswith("alpha")
                or "mhc_b_" in name)


def adamw_first_update(cfg, w, grads, epsilon=None):
    """W1 - W0 of the first AdamW step after global-norm clipping, as
    PyTorch computes it: with zero moments the bias-corrected step is
    g / (|g| + eps); the decay is lr * wd * W0 beside it, on the matrices
    alone (`decays`). `epsilon` replaces the configuration's (a system
    that adds eps before the bias correction has, on this first step,
    eps / sqrt(1 - beta2) where PyTorch has eps)."""
    o = cfg["optimizer"]
    eps = o["epsilon"] if epsilon is None else epsilon
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in grads.values()))
    scale = jnp.minimum(1.0, o["clip_global_norm"] / (norm + 1e-6))
    delta = {}
    for name, g in grads.items():
        g = g * scale
        step = g / (jnp.abs(g) + eps)
        decay = o["weight_decay"] if decays(name) else 0.0
        delta[name] = -o["learning_rate"] * (step + decay * w[name])
    return delta, norm
