"""LFM2-8B-A1B, plainly: forward pass, cross-entropy, gradients and the first
AdamW update in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; no kernels, no cache, no
sorting, no grouped products, the convolution as shifted slices and the
mask written out. Written from the layer's equations as the configuration
file states them, independent of `paddle_tpu` (of `models/lfm2.py`,
`ops/lm_ops.py`, `parallel/`).

Program layer i is published layer `deployment.layers_held[i]`, of kind
`layer_types[that]`; dense where i < `num_dense_layers`. x [B, S, C]:

    u = RMSNorm(x; operator_norm)
    conv:  [B | C | z] = u W_in (thirds in that order);  v = B * z
           c_t = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t  (L = 3; w [L, C],
           depth-wise, no bias; ZERO before a row's first token)
           x <- x + (C * c) W_out
    full_attention:  q = u W_q [.., H, D], k = u W_k, v = u W_v [.., Hkv, D]
           q, k <- RMSNorm over each head's D numbers, one scale [D] shared
           by the heads, BEFORE the rotary; rotary on the whole head,
           `rotate_half`; o = softmax(q k^T / sqrt(D) + causal) v, query
           head h on key/value head h // (H / Hkv);  x <- x + concat(o) W_o
    u' = RMSNorm(x; ffn_norm)
    dense:   x <- x + (silu(u' W_gate) * (u' W_up)) W_down
    sparse:  s = sigmoid(u' W_r) [T, E_all]; chosen = the k largest of s + b
           (b the model's `expert_bias`: no gradient); w = s[chosen] /
           (sum s[chosen] + 1e-6) x routed_scaling_factor
           x <- x + sum over the chosen experts HELD of w_e (silu(u' G_e)
           * (u' U_e)) D_e
    logits = RMSNorm(x; embedding_norm) E^T, E the embedding table itself
    (ONE array: its gradient is one gradient); mean cross-entropy.

THE SHARE. `cfg` counts the experts and the vocabulary rows HELD;
`cfg["deployment"]` gives the router's width (`num_experts`) and the first
expert held (`first_expert`). The router scores and chooses over ALL
experts; the expert branch is the held experts' part. The operators and
the dense MLP are whole on every chip. With a deployment that holds
everything this file is the uncut model, and `share_of` cuts an uncut
model's weights down to one chip's.

Departures, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
  the conv's taps [L, C] where the published `Conv1d` has [C, 1, L];
* the convolution is L shifted slices of the row, where the published code
  is `Conv1d(groups=C, kernel L, padding L - 1)` cut to the row's length:
  the same numbers (tap j of the kernel meets the token L - 1 - j back);
* the experts' matrices are stacked, and the expert layer is computed
  DENSE, every token through every held expert, masked by the router
  weights: the same function as routing, and it shares no sorting or
  grouping code with the system under test;
* attention is computed a block of QUERY_BLOCK queries at a time, in a
  loop, and a block's scores are computed again in the backward
  (`jax.checkpoint`);
* each decoder layer is wrapped in `jax.checkpoint`: the same numbers, and
  the float32 backward at [8192, 2048] fits the chip.
"""

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 1024
NORM_TOPK_EPS = 1e-6
CONV, ATTENTION = "conv", "full_attention"
P = "lfm2."


def layer_kinds(cfg):
    n = cfg["num_hidden_layers"]
    held = (cfg.get("deployment") or {}).get("layers_held") or range(n)
    return [cfg["layer_types"][l] for l in list(held)[:n]]


def head_dim(cfg):
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def param_shapes(cfg):
    """{name: shape} of every weight the share holds."""
    C, V, D = cfg["hidden_size"], cfg["vocab_size"], head_dim(cfg)
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    E_all, L = cfg["deployment"]["num_experts"], cfg["conv_L_cache"]
    shapes = {P + "embed": (V, C)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"{P}l{i}."
        shapes[p + "operator_norm"] = (C,)
        if kind == CONV:
            shapes.update({p + "conv_in": (C, 3 * C),
                           p + "conv_taps": (L, C),
                           p + "conv_out": (C, C)})
        else:
            shapes.update({
                p + "w_q": (C, H * D), p + "q_layernorm": (D,),
                p + "w_k": (C, kv * D), p + "k_layernorm": (D,),
                p + "w_v": (C, kv * D), p + "w_o": (H * D, C)})
        shapes[p + "ffn_norm"] = (C,)
        if i < cfg["num_dense_layers"]:
            W = cfg["intermediate_size"]
            shapes.update({p + "mlp_gate": (C, W), p + "mlp_up": (C, W),
                           p + "mlp_down": (W, C)})
        else:
            shapes.update({
                p + "router": (C, E_all), p + "expert_bias": (E_all,),
                p + "gate": (E, C, F), p + "up": (E, C, F),
                p + "down": (E, F, C)})
    shapes[P + "embedding_norm"] = (C,)
    return shapes


def trained(name):
    """The experts' bias is the model's own state, not trained by the
    loss."""
    return not name.endswith("expert_bias")


def share_of(cfg, w, chip, chips, vocab=True):
    """Chip `chip` of `chips` that share each layer's experts and the
    vocabulary's rows of an uncut model: (cfg, weights) with its experts
    and (with `vocab`) its rows of the table; the operators, the dense MLP,
    the routers and the norms whole."""
    E, V = cfg["num_experts"], cfg["vocab_size"]
    e_n, v_n = E // chips, V // chips
    part = dict(cfg, num_experts=e_n, vocab_size=v_n if vocab else V,
                deployment=dict(cfg["deployment"], first_expert=chip * e_n))
    out = {}
    for name, v in w.items():
        leaf = name.rsplit(".", 1)[1]
        if leaf in ("gate", "up", "down"):
            v = v[chip * e_n:(chip + 1) * e_n]
        elif name == P + "embed" and vocab:
            v = v[chip * v_n:(chip + 1) * v_n]
        out[name] = v
    return part, out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [B, S, heads, D]: every head rotated whole, `rotate_half` layout,
    position = index in S."""
    S, D = x.shape[1], x.shape[3]
    freq = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def gated_conv(bcz, taps):
    """bcz [B, S, 3C] = [B | C | z], taps [L, C] -> C * conv_L(B * z)
    [B, S, C]: what lies between the conv operator's two projections."""
    C, L = taps.shape[1], taps.shape[0]
    b, c, z = bcz[..., :C], bcz[..., C:2 * C], bcz[..., 2 * C:]
    v = b * z
    S = v.shape[1]
    # DEPARTURE: L shifted slices (tap j meets the token L - 1 - j back;
    # before the row's first token: zero)
    conv = jnp.zeros_like(v)
    for j in range(L):
        back = L - 1 - j
        past = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :S]
        conv = conv + taps[j] * past
    return c * conv


def short_conv(u, w, p, cfg):
    """u [B, S, C] (normed) -> layer p's conv operator branch."""
    assert w[p + "conv_taps"].shape == (cfg["conv_L_cache"],
                                        cfg["hidden_size"])
    return gated_conv(u @ w[p + "conv_in"], w[p + "conv_taps"]) \
        @ w[p + "conv_out"]


def attention(u, w, p, cfg):
    """u [B, S, C] (normed) -> layer p's attention operator branch."""
    B, S, _ = u.shape
    D, H, kv = (head_dim(cfg), cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    eps = cfg["norm_eps"]
    q = (u @ w[p + "w_q"]).reshape(B, S, H, D)
    k = (u @ w[p + "w_k"]).reshape(B, S, kv, D)
    v = (u @ w[p + "w_v"]).reshape(B, S, kv, D)
    q = rope(rms_norm(q, w[p + "q_layernorm"], eps), cfg["rope_theta"])
    k = rope(rms_norm(k, w[p + "k_layernorm"], eps), cfg["rope_theta"])
    k, v = (jnp.repeat(t, H // kv, axis=2) for t in (k, v))
    # DEPARTURE: a block of queries at a time, in a loop (one block's scores
    # live at a time), each block's scores formed again in the backward
    # (the same numbers; 32 heads x 8192 x 8192 float32 scores and weights
    # are 17 GB kept whole)
    n = S // QUERY_BLOCK if S % QUERY_BLOCK == 0 else 1
    size = S // n

    def block(_, args):
        qb, q0 = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(D)
        keep = jnp.arange(S)[None, :] <= q0 + jnp.arange(size)[:, None]
        pr = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        return None, jnp.einsum("bhqk,bkhd->bqhd", pr, v)

    _, outs = jax.lax.scan(
        jax.checkpoint(block), None,
        (jnp.moveaxis(q.reshape(B, n, size, H, D), 1, 0),
         jnp.arange(n) * size))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H * D) @ w[p + "w_o"]


def operator(u, w, p, cfg, kind):
    return (short_conv if kind == CONV else attention)(u, w, p, cfg)


def dense_mlp(u, w, p):
    return (jax.nn.silu(u @ w[p + "mlp_gate"]) * (u @ w[p + "mlp_up"])) \
        @ w[p + "mlp_down"]


def route(u, w, p, cfg):
    """u [T, C] -> (scores s [T, E_all], what the choice is made by (s + b),
    chosen experts [T, k], their weights [T, k])."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ w[p + "router"])
    biased = s + jax.lax.stop_gradient(w[p + "expert_bias"]) \
        if cfg["use_expert_bias"] else s
    _, top_e = jax.lax.top_k(biased, k)
    top_s = jnp.take_along_axis(s, top_e, axis=1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=1, keepdims=True)
                         + NORM_TOPK_EPS)
    return s, biased, top_e, top_s * cfg["routed_scaling_factor"]


def experts(u, w, p, cfg):
    """u [T, C] (normed) -> (the held experts' part [T, C], (s + b [T,
    E_all], chosen experts [T, k]))."""
    E_all = cfg["deployment"]["num_experts"]
    first, held = cfg["deployment"]["first_expert"], cfg["num_experts"]
    _, biased, top_e, top_w = route(u, w, p, cfg)
    # DEPARTURE: dense over the held experts, masked by the router weights
    weight = jnp.einsum("tk,tke->te", top_w,
                        jax.nn.one_hot(top_e, E_all, dtype=top_w.dtype))
    weight = weight[:, first:first + held]

    def one(carry, e):
        gate, up, down, w_e = e
        hid = jax.nn.silu(u @ gate) * (u @ up) * w_e[:, None]
        return carry + hid @ down, None

    part, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w[p + "gate"], w[p + "up"], w[p + "down"], weight.T))
    return part, (biased, top_e)


def layer(x, w, i, kind, cfg):
    """x [B, S, C] -> (x', (s + b, chosen) or None)."""
    B, S, C = x.shape
    p, eps = f"{P}l{i}.", cfg["norm_eps"]
    x = x + operator(rms_norm(x, w[p + "operator_norm"], eps), w, p, cfg,
                     kind)
    u = rms_norm(x, w[p + "ffn_norm"], eps)
    if i < cfg["num_dense_layers"]:
        return x + dense_mlp(u, w, p), None
    part, r = experts(u.reshape(B * S, C), w, p, cfg)
    return x + part.reshape(B, S, C), r


def forward(cfg, w, tokens):
    """tokens [B, S] -> (logits [B, S, V], [(s + b [T, E_all], chosen [T,
    k])] for each sparse layer)."""
    x = w[P + "embed"][tokens]
    routing = []
    for i, kind in enumerate(layer_kinds(cfg)):
        # DEPARTURE: a layer's activations are computed again in the
        # backward (the same numbers; memory)
        x, r = jax.checkpoint(
            lambda x_, w_, i=i, kind=kind: layer(x_, w_, i, kind, cfg))(
                x, {k: v for k, v in w.items() if k.startswith(f"{P}l{i}.")})
        if r is not None:
            routing.append(r)
    # the tied head: the embedding table, transposed
    logits = rms_norm(x, w[P + "embedding_norm"],
                      cfg["norm_eps"]) @ w[P + "embed"].T
    return logits, routing


def loss_fn(cfg, w, tokens, labels):
    """Mean cross-entropy of the next token. Returns (loss, (logits,
    routing))."""
    logits, routing = forward(cfg, w, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(ce), (logits, routing)


def loss_and_grads(cfg, w, tokens, labels):
    # tokens and labels are arguments, not constants of the compiled
    # program: another seed's row then finds it in the compile cache
    with jax.default_matmul_precision(PRECISION):
        (loss, rest), grads = jax.jit(jax.value_and_grad(
            lambda w_, t, l: loss_fn(cfg, w_, t, l),
            has_aux=True))(w, tokens, labels)
    return loss, rest, {k: g for k, g in grads.items() if trained(k)}


def operator_branch(cfg, w, i, u):
    """Program layer i's operator branch (conv or attention) on a given
    normed input u [B, S, C]: what the comparison sets the system's own
    branch against, first-hand."""
    p, kind = f"{P}l{i}.", layer_kinds(cfg)[i]
    with jax.default_matmul_precision(PRECISION):
        return jax.jit(lambda w_, u_: operator(u_, w_, p, cfg, kind))(
            {k: v for k, v in w.items() if k.startswith(p)}, u)


def decays(name):
    return not name.endswith("norm")


def adamw_first_update(cfg, w, grads, epsilon=None):
    """W1 - W0 of the first AdamW step after global-norm clipping, as
    PyTorch computes it: with zero moments the bias-corrected step is
    g / (|g| + eps); the decay is lr * wd * W0 beside it, on everything
    but the norm scales (`decays`: the conv's taps and the tied table
    too). `epsilon` replaces the configuration's (a system that adds eps
    before the bias correction has, on this first step, eps / sqrt(1 -
    beta2) where PyTorch has eps)."""
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


def balance_step(cfg, bias, chosen, speed):
    """The rule that moves the experts' bias (`assumed.expert_bias_rule`):
    b_e += speed x sign(mean load - load_e) over one step's choices
    `chosen` [T, k]."""
    load = jnp.bincount(chosen.ravel(), length=bias.shape[0])
    return bias + speed * jnp.sign(
        jnp.mean(load.astype(jnp.float32)) - load)
