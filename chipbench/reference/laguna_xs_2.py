"""Laguna-XS.2, plainly: forward pass, cross-entropy, gradients and the
first AdamW update in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; no kernels, no cache, no
sorting, no grouped products, the [S, S] mask written out. Independent of
`paddle_tpu` (of `models/laguna.py` and of `parallel/`).

The layer, as the configuration file states it (x [T, C]; layer l of kind
`layer_types[l]` with `num_attention_heads_per_layer[l]` query heads,
`num_key_value_heads` key/value heads of `head_dim`):

    u = RMSNorm(x);  q = u W_q,  k = u W_k,  v = u W_v  (no bias)
    full layers: the first partial_rotary_factor x head_dim numbers of
      each head of q and k rotated with YaRN's frequencies, cos and sin x
      attention_factor, the rest untouched; window layers: the whole head,
      plain theta
    scores q k^T / sqrt(head_dim); query head h reads key/value head
      h // (heads / kv heads); mask j <= i, and in window layers also
      i - j < sliding_window (the token itself counts)
    g = sigmoid(u W_g) [T, heads];  x <- x + concat_h(g_h o_h) W_o
    u = RMSNorm(x);  dense layers: x <- x + SwiGLU(u)
    sparse layers: s = sigmoid(u W_r) over all experts; the top-k of
      s + bias chosen; w = s of the chosen / their sum x
      moe_routed_scaling_factor;  x <- x + sum_e w_e SwiGLU_e(u) +
      SwiGLU_shared(u)
    final RMSNorm, untied head, cross-entropy on the next token.

THE SHARE. A layer may be divided over several chips: `cfg` then counts
the query heads of each layer, the key/value heads, the routed experts and
the vocabulary rows HELD, and `cfg["deployment"]` gives the router's width
(`num_experts`) and the first expert held (`first_expert`). The router
scores and chooses over ALL experts; the result is the held experts' part
(what the others would add is left out) plus the shared expert, which
every chip computes alike; the attention branch is the held heads' part of
the sum over heads (W_o's rows with them). With a deployment that holds
everything this file is the uncut model, and `share_of` cuts an uncut
model's weights down to one chip's.

Departures, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
* the experts' matrices are stacked: gate / up [E', in, F], down [E', F, out];
* the expert layer is computed DENSE, every token through every held
  expert, masked by the router weights: the same function as routing, and
  it shares no sorting or grouping code with the system under test;
* attention is computed a block of QUERY_BLOCK queries at a time against
  all keys, the block's rows of the [S, S] mask written out: the same
  numbers, and float32 scores of 8192 x 8192 x 8 heads never exist at once;
* each decoder layer is wrapped in `jax.checkpoint`: the same numbers, and
  the float32 backward of 5 layers at [8192, 2048] fits the chip.
"""

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 1024
FULL, WINDOW = "full_attention", "sliding_attention"


def heads_of(cfg, i):
    return cfg["num_attention_heads_per_layer"][i]


def param_shapes(cfg):
    """{name: shape} of every weight the share holds."""
    C, V, D = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv, E, F = (cfg["num_key_value_heads"], cfg["num_experts"],
                cfg["moe_intermediate_size"])
    shapes = {"laguna.embed": (V, C)}
    for i in range(cfg["num_hidden_layers"]):
        p, H = f"laguna.l{i}.", heads_of(cfg, i)
        shapes.update({
            p + "attn_norm": (C,), p + "w_q": (C, H * D),
            p + "w_k": (C, kv * D), p + "w_v": (C, kv * D),
            p + "w_o": (H * D, C), p + "ffn_norm": (C,)})
        if cfg.get("gating"):
            shapes[p + "w_g"] = (C, H)
        if cfg["mlp_layer_types"][i] == "dense":
            Fd = cfg["intermediate_size"]
            shapes.update({p + "mlp_gate": (C, Fd), p + "mlp_up": (C, Fd),
                           p + "mlp_down": (Fd, C)})
        else:
            Fs = cfg["shared_expert_intermediate_size"]
            shapes.update({
                p + "router": (C, cfg["deployment"]["num_experts"]),
                p + "router_bias": (cfg["deployment"]["num_experts"],),
                p + "gate": (E, C, F), p + "up": (E, C, F),
                p + "down": (E, F, C), p + "shared_gate": (C, Fs),
                p + "shared_up": (C, Fs), p + "shared_down": (Fs, C)})
    shapes.update({"laguna.final_norm": (C,), "laguna.head": (C, V)})
    return shapes


def trained(name):
    """The router's bias is state, not a trained parameter."""
    return not name.endswith("router_bias")


def share_of(cfg, w, chip, chips, vocab=True):
    """Chip `chip` of `chips` that share each layer of an uncut model:
    (cfg, weights) with its experts, its query heads of every layer, its
    key/value heads and (with `vocab`) its vocabulary rows; everything else
    whole. Heads are dealt so that a chip's query heads read the chip's
    key/value heads: key/value head j goes with query heads [j g, (j + 1)
    g) of a layer with g query heads a key/value head."""
    D, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    E, V = cfg["num_experts"], cfg["vocab_size"]
    per = [h // chips for h in cfg["num_attention_heads_per_layer"]]
    kv_n, e_n, v_n = kv // chips, E // chips, V // chips
    part = dict(cfg, num_attention_heads_per_layer=per,
                num_attention_heads=cfg["num_attention_heads"] // chips,
                num_key_value_heads=kv_n, num_experts=e_n,
                vocab_size=v_n if vocab else V,
                deployment=dict(cfg["deployment"],
                                first_expert=chip * e_n))
    es = slice(chip * e_n, (chip + 1) * e_n)
    out = {}
    for name, v in w.items():
        leaf = name.rsplit(".", 1)[1]
        layer = int(name.split(".")[1][1:]) if ".l" in name else None
        if leaf in ("w_q", "w_g", "w_o"):
            H = heads_of(cfg, layer)
            hs = slice(chip * (H // chips), (chip + 1) * (H // chips))
            if leaf == "w_q":
                v = v.reshape(v.shape[0], H, D)[:, hs].reshape(
                    v.shape[0], -1)
            elif leaf == "w_g":
                v = v[:, hs]
            else:
                v = v.reshape(H, D, -1)[hs].reshape(-1, v.shape[1])
        elif leaf in ("w_k", "w_v"):
            v = v.reshape(v.shape[0], kv, D)[
                :, chip * kv_n:(chip + 1) * kv_n].reshape(v.shape[0], -1)
        elif leaf in ("gate", "up", "down"):
            v = v[es]
        elif name == "laguna.embed" and vocab:
            v = v[chip * v_n:(chip + 1) * v_n]
        elif name == "laguna.head" and vocab:
            v = v[:, chip * v_n:(chip + 1) * v_n]
        out[name] = v
    return part, out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary_table(cfg, kind, S):
    """(cos, sin) [S, R] of a layer of `kind`, R the numbers of a head that
    are rotated; YaRN's frequencies and attention factor where the entry
    of `rope_parameters` says so (`transformers`'
    `_compute_yarn_parameters`: correction range over the R rotated
    numbers, truncated; linear ramp)."""
    rp = cfg["rope_parameters"][kind]
    R = int(cfg["head_dim"] * rp.get("partial_rotary_factor", 1.0))
    theta = float(rp["rope_theta"])
    freq = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    factor = 1.0
    if rp.get("rope_type") == "yarn":
        orig = rp.get("original_max_position_embeddings") or \
            cfg["rope_parameters"]["original_max_position_embeddings"]

        def pair_of(turns):
            return (R * math.log(orig / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(pair_of(rp["beta_fast"])), 0)
        high = min(math.ceil(pair_of(rp["beta_slow"])), R - 1)
        ramp = jnp.clip((jnp.arange(R // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        freq = freq / rp["factor"] * ramp + freq * (1.0 - ramp)
        factor = rp.get("attention_factor") or (
            0.1 * math.log(rp["factor"]) + 1.0)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rope(x, cos, sin):
    """x [B, S, heads, D]: the first R = cos.shape[1] numbers of each head
    rotated (`rotate_half` over those R), the rest as they are."""
    R = cos.shape[1]
    xr, rest = x[..., :R], x[..., R:]
    x1, x2 = xr[..., :R // 2], xr[..., R // 2:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    rot = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([rot, rest], -1)


def attention_scores_mask(S, q0, n, window):
    """Rows [q0, q0 + n) of the [S, S] mask: key j is seen by query i if
    j <= i and, with a window, i - j < window."""
    i = q0 + jnp.arange(n)[:, None]
    j = jnp.arange(S)[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (i - j < window)
    return keep


def attention(u, w, p, cfg, kind, heads):
    """u [B, S, C] (normed) -> the held heads' part of the branch."""
    B, S, _ = u.shape
    D, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    cos, sin = rotary_table(cfg, kind, S)
    q = rope((u @ w[p + "w_q"]).reshape(B, S, heads, D), cos, sin)
    k = rope((u @ w[p + "w_k"]).reshape(B, S, kv, D), cos, sin)
    v = (u @ w[p + "w_v"]).reshape(B, S, kv, D)
    group = heads // kv
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    window = cfg["sliding_window"] if kind == WINDOW else None
    outs = []
    # DEPARTURE: a block of queries at a time (the same numbers)
    for q0 in range(0, S, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(D)
        keep = attention_scores_mask(S, q0, qb.shape[1], window)
        pr = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", pr, v))
    o = jnp.concatenate(outs, axis=1)
    if cfg.get("gating"):
        o = o * jax.nn.sigmoid(u @ w[p + "w_g"])[..., None]
    return o.reshape(B, S, heads * D) @ w[p + "w_o"]


def swiglu(u, w, p):
    return (jax.nn.silu(u @ w[p + "gate"]) * (u @ w[p + "up"])) @ w[p + "down"]


def route(u, w, p, cfg):
    """u [T, C] -> (scores [T, E], the scores the choice is made by
    (score + bias) [T, E], chosen experts [T, k], their weights [T, k])."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ w[p + "router"])
    biased = scores + jax.lax.stop_gradient(w[p + "router_bias"])
    _, top_e = jax.lax.top_k(biased, k)
    top_s = jnp.take_along_axis(scores, top_e, axis=1)
    top_s = top_s / (top_s.sum(axis=1, keepdims=True) + 1e-20)
    return scores, biased, top_e, top_s * cfg["moe_routed_scaling_factor"]


def experts(u, w, p, cfg):
    """u [T, C] (normed) -> (the held experts' part [T, C], the shared
    expert [T, C], (biased scores [T, E], chosen experts [T, k]))."""
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
    return part, swiglu(u, w, p + "shared_"), (biased, top_e)


def layer(x, w, i, cfg):
    """x [B, S, C] -> (x', (biased scores, chosen) or None)."""
    B, S, _ = x.shape
    p, eps = f"laguna.l{i}.", cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w[p + "attn_norm"], eps), w, p, cfg,
                      cfg["layer_types"][i], heads_of(cfg, i))
    u = rms_norm(x, w[p + "ffn_norm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + swiglu(u, w, p + "mlp_"), None
    part, shared, r = experts(u.reshape(B * S, -1), w, p, cfg)
    return x + (part + shared).reshape(B, S, -1), r


def forward(cfg, w, tokens):
    """tokens [B, S] -> (logits [B, S, V], [(biased scores [T, E], chosen
    [T, k])] for each sparse layer)."""
    x = w["laguna.embed"][tokens]
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        # DEPARTURE: a layer's activations are computed again in the
        # backward (the same numbers; memory)
        x, r = jax.checkpoint(
            lambda x_, w_, i=i: layer(x_, w_, i, cfg))(x, w)
        if r is not None:
            routing.append(r)
    logits = rms_norm(x, w["laguna.final_norm"],
                      cfg["rms_norm_eps"]) @ w["laguna.head"]
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


def attention_branch(cfg, w, i, u):
    """Layer i's attention branch on a given normed input u [B, S, C]: what
    the comparison sets the system's own branch against, first-hand."""
    with jax.default_matmul_precision(PRECISION):
        return jax.jit(lambda w_, u_: attention(
            u_, w_, f"laguna.l{i}.", cfg, cfg["layer_types"][i],
            heads_of(cfg, i)))(
                {k: v for k, v in w.items()
                 if k.startswith(f"laguna.l{i}.w_")}, u)


def decays(name):
    return not name.endswith("norm")


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
