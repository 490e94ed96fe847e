"""OLMoE-1B-7B, plainly: forward pass, loss with both router losses,
gradients and the first AdamW update in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`; no kernels, no sorting, no
grouped products. Independent of `paddle_tpu`.

Follows Muennighoff et al., arXiv:2409.02060, and the model's
`transformers` implementation (OlmoeDecoderLayer). Weights are a dict by
name; `param_shapes` lists them. Departures from the published
description, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
* the experts' matrices are stacked: gate / up [E, in, F], down [E, F, out];
* the expert layer is computed DENSE, every token through all experts,
  masked by the top-k router weights: the same function as routing, and
  it shares no sorting or grouping code with the system under test;
* the load-balancing loss is E * sum_e f_e * P_e with f_e the share of the
  T*k routing slots that chose e (the paper's formula); `transformers`'
  `load_balancing_loss_func` sums the k slots apart and is k times this.
"""

import jax
import jax.numpy as jnp

PRECISION = "highest"


def param_shapes(cfg):
    """{name: shape} of every weight, in the order the model applies them."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    E, F = cfg["num_experts"], cfg["intermediate_size"]
    shapes = {"olmoe.embed": (V, H)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"olmoe.l{i}."
        shapes.update({
            p + "attn_norm": (H,), p + "wq": (H, H), p + "q_norm": (H,),
            p + "wk": (H, H), p + "k_norm": (H,), p + "wv": (H, H),
            p + "wo": (H, H), p + "ffn_norm": (H,), p + "router": (H, E),
            p + "gate": (E, H, F), p + "up": (E, H, F), p + "down": (E, F, H),
        })
    shapes.update({"olmoe.final_norm": (H,), "olmoe.head": (H, V)})
    return shapes


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [B, S, heads, D]: rotate_half convention, position = index in S."""
    S, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(x, w, p, cfg):
    """x [B, S, H] -> the attention branch (before the residual add)."""
    B, S, H = x.shape
    heads = cfg["num_attention_heads"]
    D = H // heads
    eps = cfg["rms_norm_eps"]
    n1 = rms_norm(x, w[p + "attn_norm"], eps)
    # QK-norm over the whole projection, before the head split
    q = rms_norm(n1 @ w[p + "wq"], w[p + "q_norm"], eps)
    k = rms_norm(n1 @ w[p + "wk"], w[p + "k_norm"], eps)
    v = n1 @ w[p + "wv"]
    q, k = (rope(t.reshape(B, S, heads, D), cfg["rope_theta"])
            for t in (q, k))
    v = v.reshape(B, S, heads, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    pr = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(B, S, H)
    return o @ w[p + "wo"]


def experts(u, w, p, cfg):
    """u [T, H] -> (expert branch [T, H], balance loss, z loss, router
    probabilities [T, E], chosen experts [T, k])."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = u @ w[p + "router"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    top_p, top_e = jax.lax.top_k(probs, k)
    # DEPARTURE: dense over all experts, masked by the top-k weights
    # (norm_topk_prob false: the weights are NOT renormalised)
    chosen = jax.nn.one_hot(top_e, E, dtype=probs.dtype).sum(axis=1)  # [T, E]
    weight = probs * chosen
    g = jnp.einsum("th,ehf->tef", u, w[p + "gate"])
    a = jnp.einsum("th,ehf->tef", u, w[p + "up"])
    hid = jax.nn.silu(g) * a * weight[:, :, None]
    out = jnp.einsum("tef,efh->th", hid, w[p + "down"])
    # DEPARTURE: the paper's balance loss (see the module's docstring)
    share = jax.lax.stop_gradient(chosen.sum(axis=0)) / (u.shape[0] * k)
    balance = E * jnp.sum(share * probs.mean(axis=0))
    return out, balance, jnp.mean(lse * lse), probs, top_e


def forward(cfg, w, tokens):
    """tokens [B, S] -> (logits [B, S, V], [(balance, z)] per layer,
    [(router probabilities [T, E], chosen experts [T, k])] per layer)."""
    B, S = tokens.shape
    x = w["olmoe.embed"][tokens]
    aux, routing = [], []
    for i in range(cfg["num_hidden_layers"]):
        p = f"olmoe.l{i}."
        x = x + attention(x, w, p, cfg)
        u = rms_norm(x, w[p + "ffn_norm"], cfg["rms_norm_eps"])
        y, balance, z, probs, top_e = experts(u.reshape(B * S, -1), w, p, cfg)
        x = x + y.reshape(B, S, -1)
        aux.append((balance, z))
        routing.append((probs, top_e))
    x = rms_norm(x, w["olmoe.final_norm"], cfg["rms_norm_eps"])
    return x @ w["olmoe.head"], aux, routing


def loss_fn(cfg, w, tokens, labels):
    """Mean next-token cross-entropy + aux_coef * sum balance + z_coef *
    sum z. Returns (loss, (cross-entropy, logits, routing, aux))."""
    logits, aux, routing = forward(cfg, w, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    c = cfg["loss"]
    loss = ce + sum(c["aux_loss_coef"] * b + c["z_loss_coef"] * z
                    for b, z in aux)
    return loss, (ce, logits, routing, aux)


def loss_and_grads(cfg, w, tokens, labels):
    # tokens and labels are arguments, not constants of the compiled
    # program: another seed's row then finds it in the compile cache
    with jax.default_matmul_precision(PRECISION):
        (loss, rest), grads = jax.jit(jax.value_and_grad(
            lambda w_, t, l: loss_fn(cfg, w_, t, l),
            has_aux=True))(w, tokens, labels)
    return loss, rest, grads


def adamw_first_update(cfg, w, grads, epsilon=None):
    """W1 - W0 of the first AdamW step after global-norm clipping, as
    PyTorch computes it: with zero moments the bias-corrected step is
    g / (|g| + eps); the decay is lr * wd * W0 beside it, on the matrices
    and not on the norm scales. `epsilon` replaces the configuration's
    (a system that adds eps before the bias correction has, on this first
    step, eps / sqrt(1 - beta2) where PyTorch has eps)."""
    o = cfg["optimizer"]
    eps = o["epsilon"] if epsilon is None else epsilon
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in grads.values()))
    scale = jnp.minimum(1.0, o["clip_global_norm"] / (norm + 1e-6))
    delta = {}
    for name, g in grads.items():
        g = g * scale
        step = g / (jnp.abs(g) + eps)
        decay = 0.0 if name.endswith("_norm") else o["weight_decay"]
        delta[name] = -o["learning_rate"] * (step + decay * w[name])
    return delta, norm
