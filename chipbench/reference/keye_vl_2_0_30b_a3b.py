"""Keye-VL-2.0-30B-A3B's language model, plainly: forward pass, both
losses, gradients and the first AdamW update in `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")`; no kernels, no bisection,
no grouped products: the indexer's scores written out, THE SELECTION BY
`lax.top_k` A ROW, the softmax over the chosen keys by an explicit mask,
the indexer's loss with its two stop-gradients by `jax.grad`. Written from
the layer's equations as the configuration file states them, independent
of `paddle_tpu` (of `models/keye_vl.py`, `ops/lm_ops.py`, `parallel/`).

Every layer, x [B, S, C], u = RMSNorm(x; attn_norm):

    q = u W_q [.., H, D];  k = u W_k, v = u W_v [.., Hkv, D]
    q, k <- RMSNorm over each head's D numbers (q_norm, k_norm [D]); rotary
    (`rotate_half`) on all D numbers, theta `rope_theta` (`mrope` with
    three equal position ids is that rotary: `mrope` below shows it)
    indexer, ub = stop_gradient(u):
        q_I = rope(ub W_qI) [.., Hi, Di];  k_I = rope(LayerNorm(ub W_kI))
        [.., Di] (one key head for all);  w = (ub W_w) Di^-1/2 Hi^-1/2
        I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t
        S_t = the `topk` values of s <= t with the largest I[t, s] (all of
        them while t < topk; `lax.top_k` puts the lower index first among
        equals, so a tie goes to the lower s)
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // (H /
              Hkv)] / sqrt(D)) v[s, h // (H / Hkv)];  x <- x + o W_o
    p[t, s] = stop_gradient((1 / H) sum_h A[t, h, s]),  s in S_t
    L_I = mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(
          I[t, .])[s])
    u' = RMSNorm(x; ffn_norm);  p = softmax(u' W_r) over ALL experts;
    chosen = the k largest; w = p[chosen] / sum p[chosen]
    x <- x + sum over the chosen experts HELD of w_e (silu(u' G_e) * (u'
         U_e)) D_e
    logits = RMSNorm(x; final_norm) W_head;
    loss = mean cross-entropy + sum over the layers of L_I.

THE SHARE. `cfg` counts the experts and the vocabulary rows HELD;
`cfg["deployment"]` gives the router's width (`num_experts`) and the first
expert held (`first_expert`). The router scores and chooses over ALL
experts; the expert branch is the held experts' part. Attention and the
indexer are whole on every chip. With a deployment that holds everything
this file is the uncut model, and `share_of` cuts an uncut model's weights
down to one chip's.

A SELECTION MAY BE GIVEN (`selections`: a mask [B, S, S] a layer): then
the reference attends, and trains its indexer, on that choice and not on
its own, so that everything downstream of a choice can be compared on the
system's own (bf16 operands flip near-ties).

Departures, each marked DEPARTURE below:

* matrices are stored [in, out] (`x @ W`), `transformers` stores [out, in];
* the experts' matrices are stacked, and the expert layer is computed
  DENSE, every token through every held expert, masked by the router
  weights;
* the indexer's scores, the selection and attention are computed a block
  of QUERY_BLOCK queries at a time, in a loop, a block's scores computed
  again in the backward;
* each decoder layer is wrapped in `jax.checkpoint`.
"""

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 256
LAYER_NORM_EPS = 1e-6
P = "keyevl."
INDEXER = ("w_qi", "w_ki", "ki_norm", "ki_norm_bias", "w_w")


def param_shapes(cfg):
    """{name: shape} of every weight the share holds."""
    C, V, D = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    E_all = cfg["deployment"]["num_experts"]
    hi, di = (cfg["sa_config"][k] for k in ("indexer_num_heads",
                                            "indexer_head_dim"))
    shapes = {P + "embed": (V, C)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"{P}l{i}."
        shapes.update({
            p + "attn_norm": (C,), p + "w_q": (C, H * D),
            p + "w_k": (C, kv * D), p + "w_v": (C, kv * D),
            p + "q_norm": (D,), p + "k_norm": (D,), p + "w_o": (H * D, C),
            p + "w_qi": (C, hi * di), p + "w_ki": (C, di),
            p + "ki_norm": (di,), p + "ki_norm_bias": (di,),
            p + "w_w": (C, hi),
            p + "ffn_norm": (C,), p + "router": (C, E_all),
            p + "expert_bias": (E_all,),
            p + "gate": (E, C, F), p + "up": (E, C, F), p + "down": (E, F, C)})
    shapes[P + "final_norm"] = (C,)
    shapes[P + "head"] = (C, V)
    return shapes


def trained(name):
    """The (zero) bias of the choice is no weight of the model."""
    return not name.endswith("expert_bias")


def of_the_indexer(name):
    """The parameters the indexer's loss trains, and the model's does not."""
    return name.rsplit(".", 1)[-1] in INDEXER


def share_of(cfg, w, chip, chips, vocab_chips=None):
    """Chip `chip` of `chips` that share each layer's experts of an uncut
    model (and, with `vocab_chips`, chip `chip % vocab_chips` of those that
    share the vocabulary's rows): (cfg, weights) with its experts and its
    rows of the table and columns of the head; attention, the indexer,
    the routers and the norms whole."""
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


def layer_norm(x, scale, bias, eps=LAYER_NORM_EPS):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _turned(x, ang):
    """x [B, S, heads, D] rotated by the angles ang [B or 1, S, D / 2]
    (`rotate_half`)."""
    D = x.shape[-1]
    ang = jnp.concatenate([ang, ang], -1)[:, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _frequencies(D, theta):
    return 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))


def rope(x, theta):
    """x [B, S, heads, D]: every head's D numbers rotated; position =
    index in S."""
    S, D = x.shape[1], x.shape[3]
    return _turned(x, (jnp.arange(S, dtype=jnp.float32)[:, None]
                       * _frequencies(D, theta)[None, :])[None])


def mrope(x, theta, positions, sections):
    """The published multimodal rotary: `positions` [3, B, S] (temporal,
    height, width ids), `sections` (the config's `mrope_section`) share
    the D / 2 frequencies among the three, in order. A text token's three
    ids are its position, and then this is `rope`."""
    D = x.shape[3]
    ang = positions.astype(jnp.float32)[..., None] * _frequencies(D, theta)
    bounds = [0]
    for n in sections:
        bounds.append(bounds[-1] + n)
    assert bounds[-1] == D // 2, (sections, D)
    ang = jnp.concatenate([ang[i, :, :, bounds[i]:bounds[i + 1]]
                           for i in range(len(sections))], -1)
    return _turned(x, ang)


# ------------------------------------------------------------ the indexer
def indexer_inputs(u, w, p, cfg):
    """u [B, S, C] (normed, ALREADY detached by the caller) -> q_I [B, S,
    Hi, Di], k_I [B, S, Di], w [B, S, Hi] (the scale in it)."""
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    B, S, _ = u.shape
    q_i = rope((u @ w[p + "w_qi"]).reshape(B, S, hi, di), cfg["rope_theta"])
    k_i = rope(layer_norm(u @ w[p + "w_ki"], w[p + "ki_norm"],
                          w[p + "ki_norm_bias"])[:, :, None, :],
               cfg["rope_theta"])[:, :, 0, :]
    return q_i, k_i, (u @ w[p + "w_w"]) * (di ** -0.5 * hi ** -0.5)


def scores_of(q_i, k_i, w_i):
    """q_I [B, n, Hi, Di] (a block of queries), k_I [B, S, Di], w [B, n,
    Hi] -> I [B, n, S] over all keys."""
    s = jnp.einsum("bqhd,bkd->bqhk", q_i, k_i)
    return jnp.sum(jax.nn.relu(s) * w_i[..., None], axis=2)


def selection_of(I, first, topk):
    """I [B, n, S], the scores of queries first .. first + n - 1 -> [B, n,
    S] bool: the `topk` causal keys of largest score (all causal keys
    while there are no more than `topk`), by `lax.top_k` a row."""
    B, n, S = I.shape
    causal = jnp.arange(S)[None, :] <= (first + jnp.arange(n))[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal[None], I, -jnp.inf),
                           min(topk, S))
    picked = jnp.zeros((B, n, S), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(n)[None, :, None],
        idx].set(True)
    return picked & causal[None]


def _blocks(S):
    n = S // QUERY_BLOCK if S % QUERY_BLOCK == 0 else 1
    return n, S // n


def _split(x, n, size):
    """[B, S, ...] -> [n, B, size, ...]."""
    return jnp.moveaxis(x.reshape((x.shape[0], n, size) + x.shape[2:]), 1, 0)


def _joined(x):
    """[n, B, size, ...] -> [B, S, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def indexer_scores(q_i, k_i, w_i):
    """I [B, S, S] whole (entries above the diagonal are scored too: the
    caller knows which are causal)."""
    n, size = _blocks(q_i.shape[1])
    _, I = jax.lax.scan(
        lambda _, b: (None, scores_of(b[0], k_i, b[1])), None,
        (_split(q_i, n, size), _split(w_i, n, size)))
    return _joined(I)


def selection(q_i, k_i, w_i, topk):
    """[B, S, S] bool: the reference's own choice."""
    n, size = _blocks(q_i.shape[1])
    _, m = jax.lax.scan(
        lambda _, b: (None, selection_of(scores_of(b[0], k_i, b[1]),
                                         b[2], topk)), None,
        (_split(q_i, n, size), _split(w_i, n, size), jnp.arange(n) * size))
    return _joined(m)


# -------------------------------------------------------------- attention
def qkv(u, w, p, cfg):
    """u [B, S, C] -> q [B, S, H, D], k, v [B, S, Hkv, D] as the softmax
    reads them (normed, rotated)."""
    B, S, _ = u.shape
    D, H, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    eps = cfg["rms_norm_eps"]
    q = (u @ w[p + "w_q"]).reshape(B, S, H, D)
    k = (u @ w[p + "w_k"]).reshape(B, S, kv, D)
    v = (u @ w[p + "w_v"]).reshape(B, S, kv, D)
    return (rope(rms_norm(q, w[p + "q_norm"], eps), cfg["rope_theta"]),
            rope(rms_norm(k, w[p + "k_norm"], eps), cfg["rope_theta"]), v)


def _block_of_attention(q_b, k, v, chosen):
    """q_b [B, n, H, D], k, v [B, S, H, D] (key/value heads repeated),
    chosen [B, n, S] -> (o [B, n, H, D], the probabilities averaged over
    the heads [B, n, S]); the softmax over the chosen keys alone."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) / math.sqrt(q_b.shape[-1])
    pr = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", pr, v), jnp.mean(pr, axis=1)


def _block_of_loss(I, p, chosen):
    """sum over the block's queries of KL(p || softmax over the chosen
    keys of I)."""
    log_q = jax.nn.log_softmax(jnp.where(chosen, I, -jnp.inf), -1)
    weigh = chosen & (p > 0)
    return jnp.sum(jnp.where(
        weigh, p * (jnp.log(jnp.where(weigh, p, 1.0))
                    - jnp.where(weigh, log_q, 0.0)), 0.0))


def sparse_attention(q, k, v, q_i, k_i, w_i, topk, chosen=None,
                     detach_target=True):
    """(o [B, S, H, D], L_I, the choice [B, S, S] bool, p [B, S, S]): a
    block of queries at a time, on the reference's own choice or on
    `chosen` [B, S, S]."""
    B, S, H, _ = q.shape
    k, v = (jnp.repeat(t, H // k.shape[2], axis=2) for t in (k, v))
    n, size = _blocks(S)

    # DEPARTURE: a block of queries at a time, formed again in the backward
    def block(_, args):
        q_b, qi_b, wi_b, first, given = args
        I = scores_of(qi_b, k_i, wi_b)
        picked = selection_of(jax.lax.stop_gradient(I), first, topk) \
            if chosen is None else given
        o, p = _block_of_attention(q_b, k, v, picked)
        if detach_target:
            p = jax.lax.stop_gradient(p)
        return None, (o, _block_of_loss(I, p, picked), picked, p)

    given = _split(chosen.astype(bool), n, size) if chosen is not None \
        else jnp.zeros((n, B, size, 0), bool)
    _, (o, loss, picked, p) = jax.lax.scan(
        jax.checkpoint(block), None,
        (_split(q, n, size), _split(q_i, n, size), _split(w_i, n, size),
         jnp.arange(n) * size, given))
    return (_joined(o), jnp.sum(loss) / (B * S), _joined(picked),
            _joined(p))


def attention(u, w, p, cfg, chosen=None, detach_indexer=True,
              detach_target=True):
    """u [B, S, C] (normed) -> (layer p's attention branch [B, S, C], the
    indexer's loss, the choice [B, S, S] bool)."""
    B, S, _ = u.shape
    q, k, v = qkv(u, w, p, cfg)
    ub = jax.lax.stop_gradient(u) if detach_indexer else u
    q_i, k_i, w_i = indexer_inputs(ub, w, p, cfg)
    o, loss, picked, _ = sparse_attention(
        q, k, v, q_i, k_i, w_i, cfg["sa_config"]["topk"], chosen,
        detach_target)
    return o.reshape(B, S, -1) @ w[p + "w_o"], loss, picked


# ---------------------------------------------------------------- experts
def route(u, w, p, cfg, sent=None):
    """u [T, C] -> (scores p [T, E_all], what the choice is made by,
    chosen experts [T, k], their weights [T, k]). With `sent` [T, k] (a
    system's own choice: `loss_and_grads(routing=)`) the weights are those
    experts', by this function's own scores; the chosen experts returned
    stay the free top-k."""
    k = cfg["num_experts_per_tok"]
    logits = u @ w[p + "router"]
    s = jax.nn.softmax(logits, axis=-1)
    # the choice by logits + b is the choice by softmax(logits + b); b = 0
    chosen_by = logits + jax.lax.stop_gradient(w[p + "expert_bias"])
    _, top_e = jax.lax.top_k(chosen_by, k)
    top_s = jnp.take_along_axis(s, top_e if sent is None else sent, axis=1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / jnp.sum(top_s, axis=1, keepdims=True)
    return s, chosen_by, top_e, top_s


def experts(u, w, p, cfg, sent=None):
    """u [T, C] (normed) -> (the held experts' part [T, C], (what chose
    [T, E_all], chosen experts [T, k])): the part of the free top-k, or
    of `sent` [T, k] where that is given (the pair returned is the free
    choice either way)."""
    E_all = cfg["deployment"]["num_experts"]
    first, held = cfg["deployment"]["first_expert"], cfg["num_experts"]
    _, chosen_by, top_e, top_w = route(u, w, p, cfg, sent)
    # DEPARTURE: dense over the held experts, masked by the router weights
    weight = jnp.einsum("tk,tke->te", top_w, jax.nn.one_hot(
        top_e if sent is None else sent, E_all, dtype=top_w.dtype))
    weight = weight[:, first:first + held]

    def one(carry, e):
        gate, up, down, w_e = e
        hid = jax.nn.silu(u @ gate) * (u @ up) * w_e[:, None]
        return carry + hid @ down, None

    part, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w[p + "gate"], w[p + "up"], w[p + "down"], weight.T))
    return part, (chosen_by, top_e)


# ------------------------------------------------------------ whole model
def layer(x, w, i, cfg, chosen=None, sent=None, **detach):
    """x [B, S, C] -> (x', the indexer's loss, (what chose, chosen), the
    attention's choice); `sent` [T, k]: the experts the layer's tokens are
    sent to (`experts`)."""
    B, S, C = x.shape
    p, eps = f"{P}l{i}.", cfg["rms_norm_eps"]
    branch, loss, picked = attention(
        rms_norm(x, w[p + "attn_norm"], eps), w, p, cfg, chosen, **detach)
    x = x + branch
    u = rms_norm(x, w[p + "ffn_norm"], eps)
    part, r = experts(u.reshape(B * S, C), w, p, cfg, sent)
    return x + part.reshape(B, S, C), loss, r, picked


def forward(cfg, w, tokens, selections=None, sent=None, **detach):
    """tokens [B, S] -> (logits [B, S, V], [the indexer's loss] a layer,
    [(what chose [T, E_all], chosen [T, k])] a layer, [the attention's
    choice [B, S, S] bool] a layer). `sent`: [chosen experts [T, k]] a
    layer, which the layers then send their tokens to; the list returned
    holds each layer's own free choice either way."""
    x = w[P + "embed"][tokens]
    losses, routing, choices = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        given = None if selections is None else selections[i]
        sent_i = None if sent is None else sent[i]
        # DEPARTURE: a layer's activations are computed again in the
        # backward (the same numbers; memory)
        x, loss, r, picked = jax.checkpoint(
            lambda x_, w_, g_, s_, i=i: layer(x_, w_, i, cfg, g_, s_,
                                              **detach))(
                x, {k: v for k, v in w.items() if k.startswith(f"{P}l{i}.")},
                given, sent_i)
        losses.append(loss)
        routing.append(r)
        choices.append(picked)
    logits = rms_norm(x, w[P + "final_norm"],
                      cfg["rms_norm_eps"]) @ w[P + "head"]
    return logits, losses, routing, choices


def loss_fn(cfg, w, tokens, labels, selections=None, parts=(1.0, 1.0),
            routing=None, **detach):
    """parts[0] x the mean cross-entropy of the next token + parts[1] x
    the sum over the layers of the indexer's loss. Returns (loss, (logits,
    routing, cross-entropy, [the indexer's loss] a layer, choices))."""
    logits, losses, routing, choices = forward(cfg, w, tokens, selections,
                                               routing, **detach)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = jnp.mean(-jnp.take_along_axis(logp, labels[..., None],
                                       axis=-1)[..., 0])
    return parts[0] * ce + parts[1] * sum(losses), (
        logits, routing, ce, losses, choices)


def loss_and_grads(cfg, w, tokens, labels, selections=None,
                   parts=(1.0, 1.0), routing=None, **detach):
    """`selections`: [the keys [B, S, S] bool a SYSTEM's indexers chose] a
    layer; `routing`: [the expert ids [T, k] it chose] a layer. The
    reference then attends where the system attended and sends every
    token where the system sent it, weighs those experts by its own
    scores, and still returns its own free choices beside: a near-tie
    that fell the other way is judged once, as a choice, and not again in
    every number behind it (the experts': PR 56). None: the reference's
    own."""
    # tokens and labels are arguments, not constants of the compiled
    # program: another seed's row then finds it in the compile cache
    with jax.default_matmul_precision(PRECISION):
        (loss, rest), grads = jax.jit(jax.value_and_grad(
            lambda w_, t, l, s, r: loss_fn(cfg, w_, t, l, s, parts, r,
                                           **detach),
            has_aux=True))(w, tokens, labels, selections, routing)
    return loss, rest, {k: g for k, g in grads.items() if trained(k)}


def attention_branch(cfg, w, i, u, chosen=None):
    """Program layer i's attention branch, the indexer's loss and the
    choice on a given normed input u [B, S, C] (and, with `chosen`, on a
    given choice): what the comparison sets the system's own against,
    first-hand."""
    p = f"{P}l{i}."
    with jax.default_matmul_precision(PRECISION):
        return jax.jit(lambda w_, u_, c_: attention(u_, w_, p, cfg, c_))(
            {k: v for k, v in w.items() if k.startswith(p)}, u, chosen)


def probabilities_of(cfg, w, i, u, chosen):
    """p [B, S, S]: the probabilities of program layer i's attention over
    a given choice, averaged over the heads, on a given normed input u."""
    p = f"{P}l{i}."

    def probs(w_, u_, c_):
        q, k, v = qkv(u_, w_, p, cfg)
        q_i, k_i, w_i = indexer_inputs(u_, w_, p, cfg)
        return sparse_attention(q, k, v, q_i, k_i, w_i,
                                cfg["sa_config"]["topk"], c_)[3]

    with jax.default_matmul_precision(PRECISION):
        return jax.jit(probs)(
            {k: v for k, v in w.items() if k.startswith(p)}, u, chosen)


def indexer_of(cfg, w, i, u):
    """(I [B, S, S], the reference's choice [B, S, S] bool) of program
    layer i on a given normed input u."""
    p = f"{P}l{i}."

    def both(w_, u_):
        q_i, k_i, w_i = indexer_inputs(u_, w_, p, cfg)
        return (indexer_scores(q_i, k_i, w_i),
                selection(q_i, k_i, w_i, cfg["sa_config"]["topk"]))

    with jax.default_matmul_precision(PRECISION):
        return jax.jit(both)(
            {k: v for k, v in w.items() if k.startswith(p)}, u)


def decays(name):
    """AdamW's decay acts on the matrices: not on the norm scales nor on
    the indexer's LayerNorm."""
    return not name.endswith(("norm", "norm_bias"))


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
