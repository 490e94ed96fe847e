"""NVIDIA-Nemotron-3-Nano-30B-A3B, plainly: forward pass, cross-entropy,
gradients and the first AdamW update in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; no kernels, no chunks, no
sorting, no grouped products: THE STATE-SPACE SCAN TOKEN BY TOKEN, the
convolution as four shifted sums, the mask written out, the experts a loop
over the held ones. Written from the layer's equations as the
configuration file states them, independent of `paddle_tpu` (of
`models/nemotron_h.py`, `ops/lm_ops.py`, `parallel/`).

Program layer i is published layer i; its kind is character i of
`hybrid_override_pattern` (M Mamba-2, * attention, E experts). Every layer
is x <- x + f(u), u = RMSNorm(x; norm, `layer_norm_epsilon`), x [B, S, C]:

    M:  [z | xBC | dt] = u W_in   [.., d + (d + 2 G N) + H], d = H P
        xBC <- silu(conv_L(xBC) + b): c_t = sum_j w_j xBC_{t - (L-1-j)} + b,
        depth-wise, taps [L, channels], ZERO before a row's first token
        x [.., H, P], B, C [.., G, N] = xBC; head h reads group h // (H/G)
        delta = softplus(dt + dt_bias) [.., H];  A = -exp(A_log) [H]
        per head, h [P, N] = 0 at a row's first token:
            h_t = exp(delta_t A) h_{t-1} + delta_t x_t B_t^T
            y_t = h_t C_t + D x_t
        y <- RMSNorm over each group's d / G numbers of (y * silu(z)),
        scale `gated_norm` [d] (the gate BEFORE the norm);  f = y W_out
    *:  q = u W_q [.., Hq, D];  k = u W_k, v = u W_v [.., Hkv, D];  causal
        softmax at D^-1/2, query head h on key/value head h // (Hq / Hkv);
        NO position of any kind;  f = attn W_o
    E:  s = sigmoid(u W_r) over ALL experts; chosen = the k largest of s +
        e_score_correction_bias (no group stage: `n_group` 1); w =
        s[chosen] / (sum s[chosen] + 1e-20) x `routed_scaling_factor`
        f = sum over the chosen experts HELD of w_e relu(u U_e)^2 D_e
            + relu(u U_s)^2 D_s
    logits = RMSNorm(x; final_norm) W_head; mean cross-entropy.

THE SHARE. `cfg` counts the experts and the vocabulary rows HELD;
`cfg["deployment"]` gives the router's width (`n_routed_experts`) and the
first expert held (`first_expert`). The router scores and chooses over ALL
experts; the expert branch is the held experts' part plus the shared
expert. The mixers and attention are whole on every chip. With a deployment
that holds everything this file is the uncut model, and `share_of` cuts an
uncut model's weights down to one chip's.

Departures, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
  the conv's taps [L, channels] where the published `Conv1d` has
  [channels, 1, L]; W_in's columns are [z | x | B | C | dt] as published;
* the scan's backward keeps the state at every `STATE_BLOCK`-th token and
  forms a block's states again (`jax.checkpoint`): the same numbers, and
  4096 states of 64 x 64 x 128 float32 (8.6 GB) need not be kept;
* the experts' matrices are stacked, and the expert layer is a loop over
  the held experts, every token through each, masked by the router
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
NORM_TOPK_EPS = 1e-20
MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
KIND_OF = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}
P = "nemotronh."


def layer_kinds(cfg):
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"], pattern
    return [KIND_OF[c] for c in pattern]


def mamba_dims(cfg):
    """(heads, head size, groups, state size, d_inner, channels the
    convolution runs over)."""
    H, Pd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return H, Pd, G, N, H * Pd, H * Pd + 2 * G * N


def param_shapes(cfg):
    """{name: shape} of every weight the share holds."""
    C, V, D = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    Hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, F = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    Fs = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
    E_all = cfg["deployment"]["n_routed_experts"]
    H, _, _, _, d, conv = mamba_dims(cfg)
    shapes = {P + "embed": (V, C)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"{P}l{i}."
        shapes[p + "norm"] = (C,)
        if kind == MAMBA:
            shapes.update({
                p + "w_in": (C, d + conv + H),
                p + "conv_taps": (cfg["conv_kernel"], conv),
                p + "conv_bias": (conv,), p + "A_log": (H,),
                p + "dt_bias": (H,), p + "D": (H,),
                p + "gated_norm": (d,), p + "w_out": (d, C)})
        elif kind == ATTENTION:
            shapes.update({
                p + "w_q": (C, Hq * D), p + "w_k": (C, kv * D),
                p + "w_v": (C, kv * D), p + "w_o": (Hq * D, C)})
        else:
            shapes.update({
                p + "router": (C, E_all),
                p + "e_score_correction_bias": (E_all,),
                p + "up": (E, C, F), p + "down": (E, F, C),
                p + "shared_up": (C, Fs), p + "shared_down": (Fs, C)})
    shapes[P + "final_norm"] = (C,)
    shapes[P + "head"] = (C, V)
    return shapes


def trained(name):
    """The bias of the choice takes no gradient: no weight of the model."""
    return not name.endswith("e_score_correction_bias")


def share_of(cfg, w, chip, chips, vocab_chips=None):
    """Chip `chip` of `chips` that share each layer's experts of an uncut
    model (and, with `vocab_chips`, chip `chip % vocab_chips` of those that
    share the vocabulary's rows): (cfg, weights) with its experts and its
    rows of the table and columns of the head; the mixers, attention, the
    shared expert, the routers and the norms whole."""
    E, V = cfg["n_routed_experts"], cfg["vocab_size"]
    e_n = E // chips
    v_n = V // vocab_chips if vocab_chips else V
    v0 = (chip % vocab_chips) * v_n if vocab_chips else 0
    part = dict(cfg, n_routed_experts=e_n, vocab_size=v_n,
                deployment=dict(cfg["deployment"], first_expert=chip * e_n))
    out = {}
    for name, v in w.items():
        leaf = name.rsplit(".", 1)[1]
        if leaf in ("up", "down"):
            v = v[chip * e_n:(chip + 1) * e_n]
        elif name == P + "embed":
            v = v[v0:v0 + v_n]
        elif name == P + "head":
            v = v[:, v0:v0 + v_n]
        out[name] = v
    return part, out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def grouped_rms_norm(x, scale, eps, groups):
    """The statistics over each of `groups` runs of the last axis, the
    scale one number a channel."""
    xg = x.reshape(x.shape[:-1] + (groups, -1))
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, -1, keepdims=True) + eps)
    return xg.reshape(x.shape) * scale


def silu_conv(x, taps, bias=None):
    """x [B, S, channels], taps [L, channels], bias [channels] -> silu(
    conv_L(x) + bias): tap j meets the token L - 1 - j back; before the
    row's first token: zero."""
    L, S = taps.shape[0], x.shape[1]
    # DEPARTURE: L shifted sums
    conv = jnp.zeros_like(x)
    for j in range(L):
        back = L - 1 - j
        conv = conv + taps[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
    return jax.nn.silu(conv if bias is None else conv + bias)


def ssm_scan(x, b, c, delta, a, d):
    """THE RECURRENCE, token by token: x [B, S, H, P], b, c [B, S, H, N]
    (each group already repeated for the heads that read it), delta [B, S,
    H] > 0, a [H] < 0, d [H] -> (y [B, S, H, P], the state behind the row's
    last token [B, H, P, N])."""
    B, S, H, Pd = x.shape

    def token(h, t):
        x_t, b_t, c_t, d_t = t
        h = jnp.exp(d_t * a)[..., None, None] * h \
            + (d_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t) + d[:, None] * x_t

    # DEPARTURE: the backward keeps one state a block of tokens
    size = STATE_BLOCK if S % STATE_BLOCK == 0 else S

    @jax.checkpoint
    def block(h, ts):
        return jax.lax.scan(token, h, ts)

    ts = tuple(jnp.moveaxis(t, 1, 0).reshape((S // size, size) + t.shape[:1]
                                             + t.shape[2:])
               for t in (x, b, c, delta))
    last, y = jax.lax.scan(block, jnp.zeros((B, H, Pd, b.shape[-1]), x.dtype),
                           ts)
    return jnp.moveaxis(y.reshape((S,) + y.shape[2:]), 0, 1), last


def scan_inputs(xbc, dt, w, p, cfg):
    """The recurrence's inputs from the convolution's output [B, S,
    channels] and dt [B, S, H]: (x, b, c, delta, a, d) as `ssm_scan` takes
    them."""
    H, Pd, G, N, d, _ = mamba_dims(cfg)
    B, S, _ = xbc.shape
    x, b, c = jnp.split(xbc, [d, d + G * N], axis=-1)
    b, c = (jnp.repeat(t.reshape(B, S, G, N), H // G, axis=2) for t in (b, c))
    delta = jax.nn.softplus(dt + w[p + "dt_bias"])
    return (x.reshape(B, S, H, Pd), b, c, delta, -jnp.exp(w[p + "A_log"]),
            w[p + "D"])


def mamba_branch(u, w, p, cfg):
    """u [B, S, C] (normed) -> layer p's Mamba-2 branch."""
    H, _, G, _, d, conv = mamba_dims(cfg)
    B, S, _ = u.shape
    zxbcdt = u @ w[p + "w_in"]
    z, xbc, dt = jnp.split(zxbcdt, [d, d + conv], axis=-1)
    xbc = silu_conv(xbc, w[p + "conv_taps"], w.get(p + "conv_bias"))
    y, _ = ssm_scan(*scan_inputs(xbc, dt, w, p, cfg))
    y = grouped_rms_norm(y.reshape(B, S, d) * jax.nn.silu(z),
                         w[p + "gated_norm"], cfg["layer_norm_epsilon"], G)
    return y @ w[p + "w_out"]


def attention(u, w, p, cfg):
    """u [B, S, C] (normed) -> layer p's attention branch: no positions."""
    B, S, _ = u.shape
    D, H, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    q = (u @ w[p + "w_q"]).reshape(B, S, H, D)
    k = (u @ w[p + "w_k"]).reshape(B, S, kv, D)
    v = (u @ w[p + "w_v"]).reshape(B, S, kv, D)
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
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H * D) @ w[p + "w_o"]


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def route(u, w, p, cfg):
    """u [T, C] -> (scores s [T, E_all], what the choice is made by,
    chosen experts [T, k], their weights [T, k])."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ w[p + "router"])
    chosen_by = s + jax.lax.stop_gradient(w[p + "e_score_correction_bias"])
    _, top_e = jax.lax.top_k(chosen_by, k)
    top_s = jnp.take_along_axis(s, top_e, axis=1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=1, keepdims=True)
                         + NORM_TOPK_EPS)
    return s, chosen_by, top_e, top_s * cfg["routed_scaling_factor"]


def shared_expert(u, w, p):
    return relu2(u @ w[p + "shared_up"]) @ w[p + "shared_down"]


def experts(u, w, p, cfg, shared=True):
    """u [T, C] (normed) -> (the held experts' part plus (with `shared`)
    the shared expert [T, C], (what chose [T, E_all], chosen experts [T,
    k]))."""
    E_all = cfg["deployment"]["n_routed_experts"]
    first, held = cfg["deployment"]["first_expert"], cfg["n_routed_experts"]
    _, chosen_by, top_e, top_w = route(u, w, p, cfg)
    # DEPARTURE: a loop over the held experts, masked by the router weights
    weight = jnp.einsum("tk,tke->te", top_w,
                        jax.nn.one_hot(top_e, E_all, dtype=top_w.dtype))
    weight = weight[:, first:first + held]

    def one(carry, e):
        up, down, w_e = e
        return carry + (relu2(u @ up) * w_e[:, None]) @ down, None

    part, _ = jax.lax.scan(one, jnp.zeros_like(u),
                           (w[p + "up"], w[p + "down"], weight.T))
    if shared:
        part = part + shared_expert(u, w, p)
    return part, (chosen_by, top_e)


def branch(u, w, p, cfg, kind):
    """Layer p's one branch on its normed input u [B, S, C] -> (f [B, S,
    C], an expert layer's (what chose, chosen) or None)."""
    if kind == EXPERTS:
        B, S, C = u.shape
        part, r = experts(u.reshape(B * S, C), w, p, cfg)
        return part.reshape(B, S, C), r
    return (mamba_branch if kind == MAMBA else attention)(u, w, p, cfg), None


def layer(x, w, i, kind, cfg):
    """x [B, S, C] -> (x', (what chose, chosen) of an expert layer)."""
    p = f"{P}l{i}."
    f, r = branch(rms_norm(x, w[p + "norm"], cfg["layer_norm_epsilon"]), w,
                  p, cfg, kind)
    return x + f, r


def forward(cfg, w, tokens):
    """tokens [B, S] -> (logits [B, S, V], [(what chose [T, E_all], chosen
    [T, k])] for each EXPERT layer in order)."""
    x = w[P + "embed"][tokens]
    routing = []
    for i, kind in enumerate(layer_kinds(cfg)):
        # DEPARTURE: a layer's activations are computed again in the
        # backward (the same numbers; memory)
        x, r = jax.checkpoint(
            lambda x_, w_, i=i, kind=kind: layer(x_, w_, i, kind, cfg))(
                x, {k: v for k, v in w.items() if k.startswith(f"{P}l{i}.")})
        if kind == EXPERTS:
            routing.append(r)
    logits = rms_norm(x, w[P + "final_norm"],
                      cfg["layer_norm_epsilon"]) @ w[P + "head"]
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


def layer_branch(cfg, w, i, u):
    """Program layer i's branch on a given normed input u [B, S, C]: what
    the comparison sets the system's own branch against, first-hand."""
    p, kind = f"{P}l{i}.", layer_kinds(cfg)[i]
    with jax.default_matmul_precision(PRECISION):
        return jax.jit(lambda w_, u_: branch(u_, w_, p, cfg, kind)[0])(
            {k: v for k, v in w.items() if k.startswith(p)}, u)


def decays(name):
    """AdamW's decay acts on the matrices and the taps, not on the norm
    scales, A_log, dt_bias, D and the convolution's bias."""
    return not name.endswith(("norm", "A_log", "dt_bias", ".D", "conv_bias"))


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
