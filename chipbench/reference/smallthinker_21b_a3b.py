"""SmallThinker-21BA3B-Instruct, plainly: forward pass, cross-entropy,
gradients and the first AdamW update in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; no kernels, no cache, no
sorting, no grouped products, the mask written out. Written from the
layer's equations as the configuration file states them, independent of
`paddle_tpu` (of `models/smallthinker.py`, `ops/lm_ops.py`, `parallel/`).

The layer (x [T, C]; layer l; H query heads on Hkv key/value heads of D):

    r = x W_r                    [T, E]: THE ROUTER READS THE LAYER'S INPUT,
                                 before the attention norm and attention
    u = RMSNorm(x);  q = u W_q,  k = u W_k,  v = u W_v       (no bias)
    rope_layout[l] = 1: rotary on the whole head of q and k, theta
      `rope_theta`; 0: no positions at all
    scores q k^T / sqrt(D); query head h reads key/value head h // (H / Hkv);
      mask j <= i, and where sliding_window_layout[l] = 1 also i - j <
      sliding_window_size (the token itself counts)
    x <- x + concat_h(o_h) W_o
    u' = RMSNorm(x)
    chosen = the top-k of r + b (b a persistable bias, no gradient: the
      configuration's `assumed.router_balance`, the one departure from the
      model; zero leaves the model's own choice);  w = softmax of r over the
      chosen (softmax over all E renormalised over the chosen is the same)
    x <- x + sum_{e chosen} w_e (relu(u' G_e) * (u' U_e)) D_e
    final RMSNorm, untied head, cross-entropy on the next token.

THE SHARE. A layer may be divided over several chips: `cfg` then counts
the query heads, the key/value heads, the experts and the vocabulary rows
HELD, and `cfg["deployment"]` gives the router's width
(`moe_num_primary_experts`) and the first expert held (`first_expert`).
The router scores and chooses over ALL experts; the expert branch is the
held experts' part (what the others would add is left out), the attention
branch the held heads' part of the sum over heads (W_o's rows with them).
With a deployment that holds everything this file is the uncut model, and
`share_of` cuts an uncut model's weights down to one chip's.

Departures, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
* the experts' matrices are stacked: gate / up [E', in, F], down [E', F, out];
* the expert layer is computed DENSE, every token through every held
  expert, masked by the router weights: the same function as routing, and
  it shares no sorting or grouping code with the system under test;
* attention is computed a block of QUERY_BLOCK queries at a time against
  all keys, the block's rows of the mask written out: the same numbers,
  and float32 scores of 8192 x 8192 x 7 heads never exist at once;
* each decoder layer is wrapped in `jax.checkpoint`: the same numbers, and
  the float32 backward of 4 layers at [8192, 2560] fits the chip.
"""

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 1024
P = "smallthinker."


def param_shapes(cfg):
    """{name: shape} of every weight the share holds."""
    C, V, D = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, F = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    E_all = cfg["deployment"]["moe_num_primary_experts"]
    shapes = {P + "embed": (V, C)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{P}l{i}."
        shapes.update({
            p + "attn_norm": (C,), p + "w_q": (C, H * D),
            p + "w_k": (C, kv * D), p + "w_v": (C, kv * D),
            p + "w_o": (H * D, C), p + "ffn_norm": (C,),
            p + "router": (C, E_all), p + "router_bias": (E_all,),
            p + "gate": (E, C, F), p + "up": (E, C, F), p + "down": (E, F, C)})
    shapes.update({P + "final_norm": (C,), P + "head": (C, V)})
    return shapes


def trained(name):
    """The router's bias is state, not a trained parameter."""
    return not name.endswith("router_bias")


def share_of(cfg, w, chip, chips, vocab=True):
    """Chip `chip` of `chips` that share each layer of an uncut model:
    (cfg, weights) with its experts, its query heads, its key/value heads
    and (with `vocab`) its vocabulary rows; everything else whole. Key/value
    head j goes with the query heads [j g, (j + 1) g) that read it."""
    D = cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, V = cfg["moe_num_primary_experts"], cfg["vocab_size"]
    h_n, kv_n, e_n, v_n = H // chips, kv // chips, E // chips, V // chips
    part = dict(cfg, num_attention_heads=h_n, num_key_value_heads=kv_n,
                moe_num_primary_experts=e_n, vocab_size=v_n if vocab else V,
                deployment=dict(cfg["deployment"], first_expert=chip * e_n))
    hs = slice(chip * h_n, (chip + 1) * h_n)
    ks = slice(chip * kv_n, (chip + 1) * kv_n)
    out = {}
    for name, v in w.items():
        leaf = name.rsplit(".", 1)[1]
        if leaf == "w_q":
            v = v.reshape(v.shape[0], H, D)[:, hs].reshape(v.shape[0], -1)
        elif leaf == "w_o":
            v = v.reshape(H, D, -1)[hs].reshape(-1, v.shape[1])
        elif leaf in ("w_k", "w_v"):
            v = v.reshape(v.shape[0], kv, D)[:, ks].reshape(v.shape[0], -1)
        elif leaf in ("gate", "up", "down"):
            v = v[chip * e_n:(chip + 1) * e_n]
        elif name == P + "embed" and vocab:
            v = v[chip * v_n:(chip + 1) * v_n]
        elif name == P + "head" and vocab:
            v = v[:, chip * v_n:(chip + 1) * v_n]
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


def mask_rows(S, q0, n, window):
    """Rows [q0, q0 + n) of the [S, S] mask: key j is seen by query i if
    j <= i and, with a window, i - j < window."""
    i = q0 + jnp.arange(n)[:, None]
    j = jnp.arange(S)[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (i - j < window)
    return keep


def attention(u, w, p, cfg, i):
    """u [B, S, C] (normed) -> the held heads' part of layer i's branch."""
    B, S, _ = u.shape
    D, H, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    q = (u @ w[p + "w_q"]).reshape(B, S, H, D)
    k = (u @ w[p + "w_k"]).reshape(B, S, kv, D)
    v = (u @ w[p + "w_v"]).reshape(B, S, kv, D)
    if cfg["rope_layout"][i]:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(t, H // kv, axis=2) for t in (k, v))
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][i] else None
    outs = []
    # DEPARTURE: a block of queries at a time (the same numbers)
    for q0 in range(0, S, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(D)
        keep = mask_rows(S, q0, qb.shape[1], window)
        pr = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", pr, v))
    return jnp.concatenate(outs, axis=1).reshape(B, S, H * D) @ w[p + "w_o"]


def route(x_in, w, p, cfg, chosen=None):
    """x_in [T, C], the layer's INPUT -> (logits r [T, E], what the choice
    is made by (r + b) [T, E], chosen experts [T, k], their weights [T, k]:
    the softmax of r over the chosen). With `chosen` [T, k] (a system's
    own choice: `forward(given=)`) the weights are those experts', by this
    function's own logits; the chosen experts returned stay the free
    top-k."""
    k = cfg["moe_num_active_primary_experts"]
    r = x_in @ w[p + "router"]
    biased = r + jax.lax.stop_gradient(w[p + "router_bias"])
    _, top_e = jax.lax.top_k(biased, k)
    top_r = jnp.take_along_axis(r, top_e if chosen is None else chosen,
                                axis=1)
    return r, biased, top_e, jax.nn.softmax(top_r, axis=1)


def experts(u, x_in, w, p, cfg, chosen=None):
    """u [T, C] (the normed state after attention), x_in [T, C] (the
    layer's input) -> (the held experts' part [T, C], (r + b [T, E],
    chosen experts [T, k])): the part of the free top-k, or of `chosen`
    [T, k] where that is given (the pair returned is the free choice
    either way)."""
    E_all = cfg["deployment"]["moe_num_primary_experts"]
    first, held = (cfg["deployment"]["first_expert"],
                   cfg["moe_num_primary_experts"])
    # (callers that stand in for `route` take its four first arguments)
    _, biased, top_e, top_w = route(x_in, w, p, cfg) if chosen is None \
        else route(x_in, w, p, cfg, chosen)
    # DEPARTURE: dense over the held experts, masked by the router weights
    weight = jnp.einsum("tk,tke->te", top_w, jax.nn.one_hot(
        top_e if chosen is None else chosen, E_all, dtype=top_w.dtype))
    weight = weight[:, first:first + held]

    def one(carry, e):
        gate, up, down, w_e = e
        hid = jax.nn.relu(u @ gate) * (u @ up) * w_e[:, None]
        return carry + hid @ down, None

    part, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w[p + "gate"], w[p + "up"], w[p + "down"], weight.T))
    return part, (biased, top_e)


def layer(x, w, i, cfg, chosen=None):
    """x [B, S, C] -> (x', (r + b, chosen)); `chosen` [T, k]: the experts
    the layer's tokens are sent to (`experts`)."""
    B, S, C = x.shape
    p, eps = f"{P}l{i}.", cfg["rms_norm_eps"]
    x_in = x
    x = x + attention(rms_norm(x, w[p + "attn_norm"], eps), w, p, cfg, i)
    u = rms_norm(x, w[p + "ffn_norm"], eps)
    part, r = experts(u.reshape(B * S, C), x_in.reshape(B * S, C), w, p, cfg,
                      chosen)
    return x + part.reshape(B, S, C), r


def forward(cfg, w, tokens, given=None):
    """tokens [B, S] -> (logits [B, S, V], [(r + b [T, E], chosen [T, k])]
    for each layer). `given`: [chosen experts [T, k]] a layer, which the
    layers then send their tokens to; the list returned holds each layer's
    own free choice either way."""
    x = w[P + "embed"][tokens]
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        # DEPARTURE: a layer's activations are computed again in the
        # backward (the same numbers; memory)
        x, r = jax.checkpoint(
            lambda x_, w_, c_, i=i: layer(x_, w_, i, cfg, c_))(
                x, w, None if given is None else given[i])
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
    those experts by its own logits, and still returns its own free top-k
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


def attention_branch(cfg, w, i, u):
    """Layer i's attention branch on a given normed input u [B, S, C]: what
    the comparison sets the system's own branch against, first-hand."""
    p = f"{P}l{i}."
    with jax.default_matmul_precision(PRECISION):
        return jax.jit(lambda w_, u_: attention(u_, w_, p, cfg, i))(
            {k: v for k, v in w.items() if k.startswith(p + "w_")}, u)


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


def balance_step(cfg, bias, chosen, speed):
    """The stand-in for the training's load-balance loss
    (`assumed.router_balance`): b_e += speed x sign(mean load - load_e)
    over one step's choices `chosen` [T, k]."""
    load = jnp.bincount(chosen.ravel(), length=bias.shape[0])
    return bias + speed * jnp.sign(
        jnp.mean(load.astype(jnp.float32)) - load)
