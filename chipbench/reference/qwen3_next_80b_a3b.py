"""Qwen3-Next-80B-A3B, plainly: forward pass, cross-entropy, gradients and
the first AdamW update in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; no kernels, no chunks, no
sorting, no grouped products: THE DELTA RULE TOKEN BY TOKEN, the
convolution as shifted slices, the mask written out. Written from the
layer's equations as the configuration file states them, independent of
`paddle_tpu` (of `models/qwen3_next.py`, `ops/lm_ops.py`, `parallel/`).

Program layer i is published layer i; layer i is a full-attention layer
where (i + 1) % `full_attention_interval` == 0, else a Gated DeltaNet
layer. x [B, S, C], u = RMSNorm(x; operator_norm):

    delta:  [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
            [q | k | v] <- silu(conv_L([q | k | v])), depth-wise, causal,
            taps [L, channels], no bias, ZERO before a row's first token
            per head: q <- q / sqrt(sum q^2 + 1e-6) dk^-1/2,
                      k <- k / sqrt(sum k^2 + 1e-6);
            key head j serves value heads (Hv / Hk) j ... (Hv / Hk) (j + 1) - 1
            beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
            per value head, S [dk, dv] = 0 at a row's first token:
                S <- exp(g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
                o_t = S^T q_t
            y = RMSNorm_dv(o) w * silu(z) per head;  x <- x + y W_o
    full:   [q | gate] = u W_qg [.., 2 H D];  k = u W_k, v = u W_v [.., Hkv, D]
            q, k <- RMSNorm over each head's D numbers (q_norm, k_norm [D]);
            rotary (`rotate_half`) on the first `partial_rotary_factor` D
            numbers of each head; causal softmax at D^-1/2, query head h on
            key/value head h // (H / Hkv);  y = attn * sigmoid(gate)
            x <- x + y W_o
    u' = RMSNorm(x; ffn_norm);  p = softmax(u' W_r) over ALL experts
    chosen = the k largest of p (+ b, `expert_bias`, zero: the model has
    none); w = p[chosen] / sum p[chosen]
    x <- x + sum over the chosen experts HELD of w_e (silu(u' G_e) * (u'
         U_e)) D_e + sigmoid(u' w_s) * (silu(u' G_s) * (u' U_s)) D_s
    logits = RMSNorm(x; final_norm) W_head; mean cross-entropy.

THE SHARE. `cfg` counts the experts and the vocabulary rows HELD;
`cfg["deployment"]` gives the router's width (`num_experts`) and the first
expert held (`first_expert`). The router scores and chooses over ALL
experts; the expert branch is the held experts' part plus the shared
expert. The operators are whole on every chip. With a deployment that
holds everything this file is the uncut model, and `share_of` cuts an
uncut model's weights down to one chip's.

Departures, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
  the conv's taps [L, channels] where the published `Conv1d` has
  [channels, 1, L]; the columns of W_qkvz are [q | k | v | z] and those of
  W_qg [q | gate], each part whole (the published projections interleave
  them by key head): with random weights the same model;
* the recurrence's backward keeps the state at every `STATE_BLOCK`-th
  token and forms a block's states again (`jax.checkpoint`): the same
  numbers, and 8192 states of 32 x 128 x 128 float32 (17 GB) need not be
  kept;
* the experts' matrices are stacked, and the expert layer is computed
  DENSE, every token through every held expert, masked by the router
  weights;
* attention is computed a block of QUERY_BLOCK queries at a time, in a
  loop, a block's scores computed again in the backward;
* each decoder layer is wrapped in `jax.checkpoint`.
"""

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 1024
STATE_BLOCK = 64
L2_EPS = 1e-6
DELTA, FULL = "linear_attention", "full_attention"
P = "qwen3next."


def layer_kinds(cfg):
    every = cfg["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else DELTA
            for i in range(cfg["num_hidden_layers"])]


def delta_dims(cfg):
    """(key heads, value heads, key head size, value head size, channels
    the convolution runs over)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hk, hv, dk, dv, 2 * hk * dk + hv * dv


def param_shapes(cfg):
    """{name: shape} of every weight the share holds."""
    C, V, D = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    Fs = cfg["shared_expert_intermediate_size"]
    E_all = cfg["deployment"]["num_experts"]
    hk, hv, dk, dv, conv = delta_dims(cfg)
    shapes = {P + "embed": (V, C)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"{P}l{i}."
        shapes[p + "operator_norm"] = (C,)
        if kind == DELTA:
            shapes.update({
                p + "w_qkvz": (C, conv + hv * dv), p + "w_ba": (C, 2 * hv),
                p + "conv_taps": (cfg["linear_conv_kernel_dim"], conv),
                p + "A_log": (hv,), p + "dt_bias": (hv,),
                p + "gated_norm": (dv,), p + "w_o": (hv * dv, C)})
        else:
            shapes.update({
                p + "w_qg": (C, 2 * H * D), p + "q_norm": (D,),
                p + "w_k": (C, kv * D), p + "k_norm": (D,),
                p + "w_v": (C, kv * D), p + "w_o": (H * D, C)})
        shapes.update({
            p + "ffn_norm": (C,), p + "router": (C, E_all),
            p + "expert_bias": (E_all,),
            p + "gate": (E, C, F), p + "up": (E, C, F), p + "down": (E, F, C),
            p + "shared_gate": (C, Fs), p + "shared_up": (C, Fs),
            p + "shared_down": (Fs, C), p + "shared_w": (C, 1)})
    shapes[P + "final_norm"] = (C,)
    shapes[P + "head"] = (C, V)
    return shapes


def trained(name):
    """The (zero) bias of the choice is no weight of the model."""
    return not name.endswith("expert_bias")


def share_of(cfg, w, chip, chips, vocab_chips=None):
    """Chip `chip` of `chips` that share each layer's experts of an uncut
    model (and, with `vocab_chips`, chip `chip % vocab_chips` of those that
    share the vocabulary's rows): (cfg, weights) with its experts and its
    rows of the table and columns of the head; the operators, the shared
    expert, the routers and the norms whole."""
    E, V = cfg["num_experts"], cfg["vocab_size"]
    e_n = E // chips
    v_n = V // vocab_chips if vocab_chips else V
    v0 = (chip % vocab_chips) * v_n if vocab_chips else 0
    part = dict(cfg, num_experts=e_n, vocab_size=v_n,
                deployment=dict(cfg["deployment"], first_expert=chip * e_n))
    out = {}
    for name, v in w.items():
        leaf = name.rsplit(".", 1)[1]
        if leaf in ("gate", "up", "down"):
            v = v[chip * e_n:(chip + 1) * e_n]
        elif name == P + "embed":
            v = v[v0:v0 + v_n]
        elif name == P + "head":
            v = v[:, v0:v0 + v_n]
        out[name] = v
    return part, out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta, rotary_dim):
    """x [B, S, heads, D]: the first `rotary_dim` numbers of every head
    rotated (`rotate_half` over those), the rest untouched; position =
    index in S."""
    S, R = x.shape[1], rotary_dim
    freq = 1.0 / (float(theta) ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :R // 2], x[..., R // 2:R]
    turned = x[..., :R] * jnp.cos(ang) \
        + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)
    return jnp.concatenate([turned, x[..., R:]], -1)


def silu_conv(x, taps):
    """x [B, S, channels], taps [L, channels] -> silu(conv_L(x)): tap j
    meets the token L - 1 - j back; before the row's first token: zero."""
    L, S = taps.shape[0], x.shape[1]
    # DEPARTURE: L shifted slices
    conv = jnp.zeros_like(x)
    for j in range(L):
        back = L - 1 - j
        conv = conv + taps[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
    return jax.nn.silu(conv)


def gates(ba, a_log, dt_bias):
    """[b | a] [.., 2 Hv] -> (g [.., Hv] <= 0, beta [.., Hv] in (0, 1))."""
    hv = a_log.shape[0]
    b, a = ba[..., :hv], ba[..., hv:]
    return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias), jax.nn.sigmoid(b)


def l2_normalized(x, scale=1.0):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS) \
        * scale


def delta_rule(q, k, v, g, beta):
    """THE RECURRENCE, token by token: q, k [B, S, Hv, dk] (normalised,
    each key head already repeated for the value heads it serves), v [B, S,
    Hv, dv], g, beta [B, S, Hv] -> (o [B, S, Hv, dv], the state behind the
    row's last token [B, Hv, dk, dv])."""
    B, S, hv, dk = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        miss = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + (b_t[..., None] * k_t)[..., :, None] \
            * miss[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    # DEPARTURE: the backward keeps one state a block of tokens
    size = STATE_BLOCK if S % STATE_BLOCK == 0 else S

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((S // size, size) + x.shape[:1]
                                             + x.shape[2:])
               for x in (q, k, v, g, beta))
    last, o = jax.lax.scan(
        block, jnp.zeros((B, hv, dk, v.shape[-1]), q.dtype), xs)
    return jnp.moveaxis(o.reshape((S,) + o.shape[2:]), 0, 1), last


def delta_inputs(qkv, ba, w, p, cfg):
    """The recurrence's inputs from the convolution's output [B, S,
    channels] and [b | a]: (q, k, v, g, beta) as `delta_rule` takes them."""
    hk, hv, dk, dv, _ = delta_dims(cfg)
    B, S, _ = qkv.shape
    q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
    q = l2_normalized(q.reshape(B, S, hk, dk), dk ** -0.5)
    k = l2_normalized(k.reshape(B, S, hk, dk))
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    g, beta = gates(ba, w[p + "A_log"], w[p + "dt_bias"])
    return q, k, v.reshape(B, S, hv, dv), g, beta


def delta_branch(u, w, p, cfg):
    """u [B, S, C] (normed) -> layer p's Gated DeltaNet branch."""
    hk, hv, dk, dv, conv = delta_dims(cfg)
    B, S, _ = u.shape
    qkvz = u @ w[p + "w_qkvz"]
    qkv = silu_conv(qkvz[..., :conv], w[p + "conv_taps"])
    o, _ = delta_rule(*delta_inputs(qkv, u @ w[p + "w_ba"], w, p, cfg))
    z = qkvz[..., conv:].reshape(B, S, hv, dv)
    y = rms_norm(o, w[p + "gated_norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return y.reshape(B, S, hv * dv) @ w[p + "w_o"]


def attention(u, w, p, cfg):
    """u [B, S, C] (normed) -> layer p's output-gated attention branch."""
    B, S, _ = u.shape
    D, H, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    eps, R = cfg["rms_norm_eps"], int(D * cfg["partial_rotary_factor"])
    qg = u @ w[p + "w_qg"]
    q, gate = qg[..., :H * D].reshape(B, S, H, D), qg[..., H * D:]
    k = (u @ w[p + "w_k"]).reshape(B, S, kv, D)
    v = (u @ w[p + "w_v"]).reshape(B, S, kv, D)
    q = rope(rms_norm(q, w[p + "q_norm"], eps), cfg["rope_theta"], R)
    k = rope(rms_norm(k, w[p + "k_norm"], eps), cfg["rope_theta"], R)
    k, v = (jnp.repeat(t, H // kv, axis=2) for t in (k, v))
    # DEPARTURE: a block of queries at a time, each block's scores formed
    # again in the backward
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
    attn = jnp.moveaxis(outs, 0, 1).reshape(B, S, H * D)
    return (attn * jax.nn.sigmoid(gate)) @ w[p + "w_o"]


def operator(u, w, p, cfg, kind):
    return (delta_branch if kind == DELTA else attention)(u, w, p, cfg)


def route(u, w, p, cfg, chosen=None):
    """u [T, C] -> (scores p [T, E_all], what the choice is made by,
    chosen experts [T, k], their weights [T, k]). With `chosen` [T, k] (a
    system's own choice: `loss_and_grads(routing=)`) the weights are those
    experts', by this function's own scores; the chosen experts returned
    stay the free top-k."""
    k = cfg["num_experts_per_tok"]
    logits = u @ w[p + "router"]
    s = jax.nn.softmax(logits, axis=-1)
    # the choice by logits + b is the choice by softmax(logits + b); b = 0
    chosen_by = logits + jax.lax.stop_gradient(w[p + "expert_bias"])
    _, top_e = jax.lax.top_k(chosen_by, k)
    top_s = jnp.take_along_axis(s, top_e if chosen is None else chosen,
                                axis=1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / jnp.sum(top_s, axis=1, keepdims=True)
    return s, chosen_by, top_e, top_s


def shared_expert(u, w, p):
    hid = jax.nn.silu(u @ w[p + "shared_gate"]) * (u @ w[p + "shared_up"])
    return jax.nn.sigmoid(u @ w[p + "shared_w"]) * (hid @ w[p + "shared_down"])


def experts(u, w, p, cfg, shared=True, chosen=None):
    """u [T, C] (normed) -> (the held experts' part plus (with `shared`)
    the shared expert [T, C], (what chose [T, E_all], chosen experts [T,
    k])): the part of the free top-k, or of `chosen` [T, k] where that is
    given (the pair returned is the free choice either way)."""
    E_all = cfg["deployment"]["num_experts"]
    first, held = cfg["deployment"]["first_expert"], cfg["num_experts"]
    _, chosen_by, top_e, top_w = route(u, w, p, cfg, chosen)
    # DEPARTURE: dense over the held experts, masked by the router weights
    weight = jnp.einsum("tk,tke->te", top_w, jax.nn.one_hot(
        top_e if chosen is None else chosen, E_all, dtype=top_w.dtype))
    weight = weight[:, first:first + held]

    def one(carry, e):
        gate, up, down, w_e = e
        hid = jax.nn.silu(u @ gate) * (u @ up) * w_e[:, None]
        return carry + hid @ down, None

    part, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w[p + "gate"], w[p + "up"], w[p + "down"], weight.T))
    if shared:
        part = part + shared_expert(u, w, p)
    return part, (chosen_by, top_e)


def layer(x, w, i, kind, cfg, chosen=None):
    """x [B, S, C] -> (x', (what chose, chosen)); `chosen` [T, k]: the
    experts the layer's tokens are sent to (`experts`)."""
    B, S, C = x.shape
    p, eps = f"{P}l{i}.", cfg["rms_norm_eps"]
    x = x + operator(rms_norm(x, w[p + "operator_norm"], eps), w, p, cfg,
                     kind)
    u = rms_norm(x, w[p + "ffn_norm"], eps)
    part, r = experts(u.reshape(B * S, C), w, p, cfg, chosen=chosen)
    return x + part.reshape(B, S, C), r


def forward(cfg, w, tokens, given=None):
    """tokens [B, S] -> (logits [B, S, V], [(what chose [T, E_all], chosen
    [T, k])] for each layer). `given`: [chosen experts [T, k]] a layer,
    which the layers then send their tokens to; the list returned holds
    each layer's own free choice either way."""
    x = w[P + "embed"][tokens]
    routing = []
    for i, kind in enumerate(layer_kinds(cfg)):
        # DEPARTURE: a layer's activations are computed again in the
        # backward (the same numbers; memory)
        x, r = jax.checkpoint(
            lambda x_, w_, c_, i=i, kind=kind: layer(x_, w_, i, kind, cfg,
                                                     c_))(
                x, {k: v for k, v in w.items() if k.startswith(f"{P}l{i}.")},
                None if given is None else given[i])
        routing.append(r)
    logits = rms_norm(x, w[P + "final_norm"],
                      cfg["rms_norm_eps"]) @ w[P + "head"]
    return logits, routing


def loss_fn(cfg, w, tokens, labels, given=None):
    """Mean cross-entropy of the next token. Returns (loss, (logits,
    routing))."""
    logits, routing = forward(cfg, w, tokens, given)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(ce), (logits, routing)


def loss_and_grads(cfg, w, tokens, labels, routing=None):
    """`routing`: [the expert ids [T, k] a SYSTEM chose] a layer. The
    reference then sends every token where the system sent it, weighs
    those experts by its own scores, and still returns its own free top-k
    beside: a near-tie that fell the other way is judged once, as a
    choice, and not again in every number behind it (PR 56). None: the
    plain reference."""
    # tokens and labels are arguments, not constants of the compiled
    # program: another seed's row then finds it in the compile cache
    with jax.default_matmul_precision(PRECISION):
        (loss, rest), grads = jax.jit(jax.value_and_grad(
            lambda w_, t, l, r: loss_fn(cfg, w_, t, l, r),
            has_aux=True))(w, tokens, labels, routing)
    return loss, rest, {k: g for k, g in grads.items() if trained(k)}


def operator_branch(cfg, w, i, u):
    """Program layer i's operator branch (delta or attention) on a given
    normed input u [B, S, C]: what the comparison sets the system's own
    branch against, first-hand."""
    p, kind = f"{P}l{i}.", layer_kinds(cfg)[i]
    with jax.default_matmul_precision(PRECISION):
        return jax.jit(lambda w_, u_: operator(u_, w_, p, cfg, kind))(
            {k: v for k, v in w.items() if k.startswith(p)}, u)


def decays(name):
    """AdamW's decay acts on the matrices and the taps, not on the norm
    scales, A_log and dt_bias."""
    return not name.endswith(("norm", "A_log", "dt_bias"))


def adamw_first_update(cfg, w, grads, epsilon=None):
    """W1 - W0 of the first AdamW step after global-norm clipping, as
    PyTorch computes it: with zero moments the bias-corrected step is
    g / (|g| + eps); the decay is lr * wd * W0 beside it, where `decays`.
    `epsilon` replaces the configuration's (a system that adds eps before
    the bias correction has, on this first step, eps / sqrt(1 - beta2)
    where PyTorch has eps)."""
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
