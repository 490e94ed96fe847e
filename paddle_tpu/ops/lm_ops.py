"""Ops of a decoder language model with sparse experts: rms_norm,
rotary_embedding, causal_attention, moe_ffn.

No reference-framework counterpart (the reference predates them); the
equations are those of OLMoE (Muennighoff et al., arXiv:2409.02060) as the
`transformers` OlmoeDecoderLayer computes them. Gradients come from the
generic vjp of core/registry.py, but for `causal_attention`, whose
backward op takes the forward's output and logsumexp, and `moe_ffn`,
whose backward op takes the forward's three grouped products (the generic
vjp would run the Pallas kernels twice). `moe_ffn`'s token permutation has a
custom_vjp so that both directions are row gathers (the transpose of a
gather is a scatter-add, which a TPU serialises).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core import places
from ..core.registry import (register_grad_maker,
                             set_stop_gradient_outputs)
from .util import first, out, register_op

F32 = jnp.float32


@register_op("rms_norm")
def rms_norm_op(ctx, ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + eps) * Scale; statistics in
    float32 whatever X's dtype (as layer_norm), Y in X's dtype."""
    x, scale = first(ins, "X"), first(ins, "Scale")
    xf = x.astype(F32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(ms + attrs.get("epsilon", 1e-5))
    return out(Y=(y * scale.astype(F32)).astype(x.dtype))


@register_op("rotary_embedding")
def rotary_embedding_op(ctx, ins, attrs):
    """X [B, S, H, D] -> Out: each head's two halves rotated by the angle
    position * theta^(-2i/D) (`rotate_half`), position = index along S."""
    x = first(ins, "X")
    S, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (attrs.get("theta", 10000.0)
                      ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    xf = x.astype(F32)
    x1, x2 = xf[..., :D // 2], xf[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return out(Out=(xf * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype))


def on_tpu():
    """Whether the step being traced is compiled for a TPU place."""
    return places.trace_device().platform == "tpu"


def _plain_causal_attention(q, k, v):
    """softmax(Q K^T / sqrt(D) + mask) V on [B, H, S, D], float32 scores,
    and their logsumexp [B, H, S]: the lowering for places without
    Mosaic."""
    S, D = q.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=F32) / (D ** 0.5)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=F32).astype(q.dtype)
    return o, lse


# Blocks of the flash kernels for this op, from sweeps on the v5e at
# [2, 16, 4096, 128] bf16 causal. Forward (PERF.md, PR 26): 8.58 ms at the
# kernel's default (256, 256), 3.81 at (512, 512), 2.29 at (1024, 1024) (a
# quarter of the grid steps, each still inside VMEM at head sizes up to
# 128). Backward, the dK/dV and dQ kernels together (PERF.md, PR 27): 7.73
# ms at (256, 256), 4.12 at (512, 512), 3.75 at (1024, 1024) (dK/dV 2.11,
# dQ 1.80), 3.94 at (1024, 512), 4.02 at (512, 1024), 4.14-4.19 with a
# 2048 side; the lax.scan they replaced took 14.85. Shorter rows shrink
# the blocks (flash.normalize_blocks).
FLASH_FWD_BLOCKS = dict(block_q=1024, block_k=1024)
FLASH_BWD_BLOCKS = dict(block_q=1024, block_k=1024)


def _heads_first(ins, *slots):
    return (jnp.swapaxes(first(ins, s), 1, 2) for s in slots)


@register_op("causal_attention")
def causal_attention_op(ctx, ins, attrs):
    """Q, K, V [B, S, H, D] -> Out [B, S, H, D], causal over the whole row,
    scale 1/sqrt(D), and Lse [B, H, S] (the scores' logsumexp, kept for the
    backward). On a TPU place the Pallas flash kernel (parallel/flash.py),
    which never writes the [S, S] scores to HBM; elsewhere the plain
    composition."""
    q, k, v = _heads_first(ins, "Q", "K", "V")
    if on_tpu():
        from ..parallel.flash import flash_attention_fwd

        o, lse = flash_attention_fwd(q, k, v, causal=True,
                                     **FLASH_FWD_BLOCKS)
    else:
        o, lse = _plain_causal_attention(q, k, v)
    return out(Out=jnp.swapaxes(o, 1, 2), Lse=lse)


set_stop_gradient_outputs("causal_attention", ["Lse"])


@register_grad_maker("causal_attention")
def _causal_attention_grad_maker(op, gout, gin):
    """Hand-written, because the generic vjp would run the forward kernel
    a second time (XLA does not merge two Mosaic calls: 8.1 ms of a 151 ms
    step on the v5e, PERF.md PR 26): the backward takes Out and Lse as the
    forward left them."""
    return [dict(
        type="causal_attention_grad",
        inputs={"Q": op.input("Q"), "K": op.input("K"), "V": op.input("V"),
                "Out": op.output("Out"), "Lse": op.output("Lse"),
                "Out@GRAD": [x or "" for x in gout.get("Out", [])]},
        outputs={s + "@GRAD": gin.get(s, [""]) for s in ("Q", "K", "V")},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("causal_attention_grad")
def causal_attention_grad_op(ctx, ins, attrs):
    """On a TPU place the flash backward kernels (dK/dV and dQ) from the
    saved output and logsumexp; elsewhere the vjp of the plain
    composition."""
    q, k, v, o, do = _heads_first(ins, "Q", "K", "V", "Out", "Out@GRAD")
    if on_tpu():
        from ..parallel.flash import flash_attention_bwd

        grads = flash_attention_bwd(q, k, v, o, first(ins, "Lse"),
                                    do.astype(q.dtype), causal=True,
                                    **FLASH_BWD_BLOCKS)
    else:
        _, vjp = jax.vjp(lambda *a: _plain_causal_attention(*a)[0], q, k, v)
        grads = vjp(do.astype(q.dtype))
    dq, dk, dv = (jnp.swapaxes(g, 1, 2) for g in grads)
    return out(**{"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv})


# ----------------------------------------------------------------- moe_ffn
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """Rows of x [T, H] in expert order: slot s holds token order[s] // k."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], (inv, x.shape[0])


def _dispatch_bwd(k, res, g):
    inv, T = res
    return g[inv].reshape(T, k, -1).sum(axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, order, inv):
    """Expert-ordered rows back in token order (the inverse permutation)."""
    return y[inv]


def _unsort_fwd(y, order, inv):
    return y[inv], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def moe_ffn(x, router, gate, up, down, top_k):
    """The expert layer on tokens x [T, H]: the five outputs of
    `moe_ffn_op`."""
    return _moe_ffn(x, router, gate, up, down, top_k)[0]


def _moe_ffn(x, router, gate, up, down, top_k, products=None):
    """The op's five outputs and the three grouped products' results
    (gate, up, down, rows in expert order). Given those as `products` (the
    backward op hands over what the forward left) no product is computed
    again: the kernels then run for the gradients alone."""
    from ..parallel.grouped import grouped_dot

    T, E = x.shape[0], router.shape[1]
    # router, softmax and top-k in float32 at full precision whatever the
    # compute dtype: a bf16 logit moves the discrete choice
    logits = jnp.dot(x.astype(F32), router.astype(F32),
                     precision=lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    top_p, top_e = lax.top_k(probs, top_k)                  # [T, k]
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    counts = jnp.sum(flat_e[:, None] == jnp.arange(E)[None, :], axis=0,
                     dtype=jnp.int32)
    # grouped products over the rows each expert really received: Pallas
    # kernels on a TPU place, `lax.ragged_dot` elsewhere
    saved = products or (None, None, None)
    xs = _dispatch(x, order, inv, top_k)                    # [T*k, H]
    a = grouped_dot(xs, gate, counts, saved[0])
    b = grouped_dot(xs, up, counts, saved[1])
    ys = grouped_dot(jax.nn.silu(a) * b, down, counts, saved[2])
    y = _unsort(ys, order, inv).reshape(T, top_k, -1)
    o = jnp.einsum("tkh,tk->th", y.astype(F32), top_p)
    # load balance: E * sum_e (share of routing slots on e) * (mean prob of
    # e); the shares are counts and carry no gradient. z-loss: mean lse^2
    share = counts.astype(F32) / (T * top_k)
    aux = E * jnp.sum(share * jnp.mean(probs, axis=0))
    return ((o.astype(x.dtype), aux.reshape(1),
             jnp.mean(jnp.square(lse)).reshape(1), top_e.astype(jnp.int32),
             counts), (a, b, ys))


_MOE_INPUTS = ("X", "Router", "Gate", "Up", "Down")
_MOE_PRODUCTS = ("GateOut", "UpOut", "DownOut")


@register_op("moe_ffn")
def moe_ffn_op(ctx, ins, attrs):
    """X [T, H], Router [H, E], Gate / Up [E, H, F], Down [E, F, H] ->
    Out_t = sum over the top_k experts e of p_te * Down_e(silu(Gate_e x_t)
    * Up_e x_t), p = softmax(Router x) NOT renormalised over the chosen;
    AuxLoss [1], ZLoss [1], ExpertIds [T, top_k], TokensPerExpert [E].
    Tokens are sorted by expert and the three products are grouped over
    the rows routed (`parallel/grouped.py: grouped_dot`): no capacity, no
    dropped token, no padding to a per-expert size. GateOut, UpOut [T *
    top_k, F] and DownOut [T * top_k, H] are those products as computed,
    kept for the backward op."""
    (o, aux, z, ids, counts), products = _moe_ffn(
        *(first(ins, s) for s in _MOE_INPUTS), int(attrs.get("top_k", 1)))
    return out(Out=o, AuxLoss=aux, ZLoss=z, ExpertIds=ids,
               TokensPerExpert=counts, **dict(zip(_MOE_PRODUCTS, products)))


set_stop_gradient_outputs(
    "moe_ffn", ["ExpertIds", "TokensPerExpert", *_MOE_PRODUCTS])


@register_grad_maker("moe_ffn")
def _moe_ffn_grad_maker(op, gout, gin):
    """Hand-written for the reason `causal_attention`'s is: the generic
    vjp evaluates the forward again, XLA does not merge two Mosaic calls,
    and the three forward kernels would run twice a step. The backward op
    takes the products as the forward left them."""
    inputs = {s: op.input(s) for s in _MOE_INPUTS}
    inputs.update({s: op.output(s) for s in _MOE_PRODUCTS if op.output(s)})
    for s in ("Out", "AuxLoss", "ZLoss"):
        if any(gout.get(s) or []):
            inputs[s + "@GRAD"] = [x or "" for x in gout[s]]
    return [dict(
        type="moe_ffn_grad", inputs=inputs,
        outputs={s + "@GRAD": list(names) for s, names in gin.items()},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("moe_ffn_grad")
def moe_ffn_grad_op(ctx, ins, attrs):
    """The vjp of `moe_ffn`'s body at the forward's saved products (an op
    built without them computes them here)."""
    primals = [first(ins, s) for s in _MOE_INPUTS]
    products = tuple(first(ins, s) for s in _MOE_PRODUCTS)
    if any(p is None for p in products):
        products = None

    def fn(*args):
        return _moe_ffn(*args, int(attrs.get("top_k", 1)), products)[0][:3]

    outs, vjp = jax.vjp(fn, *primals)
    cots = []
    for o, slot in zip(outs, ("Out", "AuxLoss", "ZLoss")):
        g = first(ins, slot + "@GRAD")
        cots.append(jnp.zeros_like(o) if g is None
                    else g.astype(o.dtype).reshape(o.shape))
    return out(**{s + "@GRAD": g for s, g in zip(_MOE_INPUTS,
                                                  vjp(tuple(cots)))})


def _kernels_take(op, block):
    """Whether `grouped_dot` takes this `moe_ffn`'s products to the Pallas
    kernels, from the shapes the program states (rows it leaves open, a
    batch dimension of -1, are taken to fit)."""
    from ..parallel import grouped

    x, gate = (block.vars[op.input(s)[0]].shape for s in ("X", "Gate"))
    rows = x[0] * int(op.attrs.get("top_k", 1)) if x[0] > 0 else None
    return grouped.takes(rows, gate[1], gate[2])


# (op type, counter, whether the lowering exists on a TPU place only,
# which of those ops count: all when None)
_LOWERED = (("moe_ffn", "moe_ffn_grouped", False, None),
            ("moe_ffn", "grouped_matmul_kernel", True, _kernels_take),
            ("causal_attention", "flash_attention", True, None),
            ("causal_attention_grad", "flash_attention_bwd", True, None))


def lowered_counts(program, device):
    """{counter: n} for the step spans and the registry: `moe_ffn` ops of
    the program (each lowers through the grouped products; on a TPU place
    those whose shapes the Pallas grouped-matmul kernels take count as
    `grouped_matmul_kernel` too) and, on a TPU place, its
    `causal_attention` ops (each lowers through the flash kernel) and
    `causal_attention_grad` ops (each through the two backward kernels). A
    program without them reports none. Kept on the program until that is
    mutated, like `bn_pool.count`."""
    memo = getattr(program, "_lm_lowered", None)
    if memo is None or memo[0] != program._mutation:
        ops = [(op, b) for b in program.blocks for op in b.ops]
        memo = program._lm_lowered = (program._mutation, [
            sum(1 for op, b in ops
                if op.type == t and (which is None or which(op, b)))
            for t, _, _, which in _LOWERED])
    return {name: n for n, (_, name, tpu_only, _) in zip(memo[1], _LOWERED)
            if n and (device.platform == "tpu" or not tpu_only)}
