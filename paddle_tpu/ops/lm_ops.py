"""Ops of a decoder language model with sparse experts: rms_norm,
rotary_embedding, causal_attention, indexer_select, sparse_attention,
indexer_loss, short_conv, gated_rms_norm, gated_delta_rule, moe_ffn.

No reference-framework counterpart (the reference predates them); the
equations are those of OLMoE (Muennighoff et al., arXiv:2409.02060) as the
`transformers` OlmoeDecoderLayer computes them. Gradients come from the
generic vjp of core/registry.py, but for `causal_attention`, whose
backward op takes the forward's output and logsumexp, `short_conv` and
`gated_rms_norm`, whose backward ops read the forward's inputs alone, and
`moe_ffn`,
whose backward op takes the forward's three grouped products (the generic
vjp would run the Pallas kernels twice). `moe_ffn`'s token permutation has a
custom_vjp so that both directions are row gathers (the transpose of a
gather is a scatter-add, which a TPU serialises).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import amp
from ..core import places
from ..core.registry import (register_grad_maker,
                             set_stop_gradient_outputs)
from .util import first, out, register_op

F32 = jnp.float32


@register_op("rms_norm")
def rms_norm_op(ctx, ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + eps) * Scale; statistics in
    float32 whatever X's dtype (as layer_norm), Y in X's dtype. With the
    attr `group_size` g the mean is over each run of g numbers of the last
    axis (a grouped norm: Scale keeps the whole axis' length)."""
    x, scale = first(ins, "X"), first(ins, "Scale")
    xf = x.astype(F32)
    group = int(attrs.get("group_size", 0))
    if group:
        xf = xf.reshape(*x.shape[:-1], x.shape[-1] // group, group)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = (xf * lax.rsqrt(ms + attrs.get("epsilon", 1e-5))).reshape(x.shape)
    return out(Y=(y * scale.astype(F32)).astype(x.dtype))


def rotary_frequencies(D, theta, factor=1.0, beta_fast=32.0, beta_slow=1.0,
                       original_max_position=4096):
    """The D / 2 angular frequencies theta^(-2i/D), float32. With `factor`
    > 1 YaRN's (Peng et al., arXiv:2309.00071, as DeepSeek-V3's modelling
    code computes them): a pair that turns more than `beta_fast` times
    over the original context keeps its frequency, one that turns fewer
    than `beta_slow` times has it divided by `factor`, and a linear ramp
    over the pair index lies between."""
    i = jnp.arange(0, D, 2, dtype=jnp.float32)
    freq = 1.0 / (float(theta) ** (i / D))
    if factor <= 1.0:
        return freq

    def pair_of(turns):
        return (D * math.log(original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(float(theta))))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


@register_op("rotary_embedding")
def rotary_embedding_op(ctx, ins, attrs):
    """X [B, S, H, D] -> Out: each head's two halves rotated by the angle
    position * frequency_i (`rotate_half`), position = index along S;
    the frequencies are theta^(-2i/D), or YaRN's where `scaling_factor` >
    1 (`rotary_frequencies`). With `rotary_dim` R < D the first R numbers
    of each head are rotated (their two halves, frequencies theta^(-2i/R))
    and the other D - R pass untouched; `attention_factor` scales cos and
    sin (YaRN's, on the rotated part alone)."""
    x = first(ins, "X")
    S, D = x.shape[1], x.shape[3]
    R = int(attrs.get("rotary_dim", 0)) or D
    inv_freq = rotary_frequencies(
        R, attrs.get("theta", 10000.0), attrs.get("scaling_factor", 1.0),
        attrs.get("beta_fast", 32.0), attrs.get("beta_slow", 1.0),
        attrs.get("original_max_position", 4096)).astype(F32)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    factor = float(attrs.get("attention_factor", 1.0))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(F32)
    x1, x2 = xf[..., :R // 2], xf[..., R // 2:R]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    y = xf[..., :R] * cos + rot * sin
    if R < D:
        y = jnp.concatenate([y, xf[..., R:]], axis=-1)
    return out(Out=y.astype(x.dtype))


def on_tpu():
    """Whether the step being traced is compiled for a TPU place."""
    return places.trace_device().platform == "tpu"


def _plain_causal_attention(q, k, v, scale=None, window=None):
    """softmax(Q K^T * scale + mask) V on Q [B, H, S, D], K [B, Hkv, S, D]
    and V [B, Hkv, S, Dv] (`scale` None: 1 / sqrt(D); H / Hkv consecutive
    query heads read one key/value head; `window`: query i sees the keys
    0 <= i - j < window), float32 scores, and their logsumexp [B, H, S]:
    the lowering for places without Mosaic."""
    S, D = q.shape[2], q.shape[3]
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=F32)
    s = s / (D ** 0.5) if scale is None else s * scale
    back = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    mask = back >= 0 if window is None else (back >= 0) & (back < window)
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
# the blocks (flash.normalize_blocks). Both sweeps were of the whole
# triangle with one key/value head a query head; a layer with grouped-query
# heads and no window takes the same pair.
FLASH_FWD_BLOCKS = dict(block_q=1024, block_k=1024)
FLASH_BWD_BLOCKS = dict(block_q=1024, block_k=1024)
# A window layer's blocks follow its band, whose key blocks alone the
# kernels' grids run over: the largest power of two not above the window,
# between 128 and 1024. Swept on the v5e at [4, 8 on 1, 8192, 128] bf16, a
# band of 512 (tools/flash_window_sweep.py; PERF.md, PR 32; ms forward /
# backward): 3.09 / 3.85 at (512, 512), where a query block visits 2 key
# blocks and both carry a mask; 5.48 / 5.19 at (256, 256) (3 blocks, but a
# visit has ~1.4 us of fixed cost and a 256 x 256 step computes for 0.4);
# 11.3 / 10.3 at (128, 128); 3.19 / 6.12 at (1024, 1024) (four times the
# band's pairs); 3.78 / 4.77 at (256, 512), 5.90 / 4.63 at (512, 256).


def flash_blocks(window, backward=False):
    """The (block_q, block_k) keyword arguments of the flash kernels for a
    `causal_attention` with this window (0 or None: the whole triangle)."""
    if not window:
        return FLASH_BWD_BLOCKS if backward else FLASH_FWD_BLOCKS
    side = 128
    while side * 2 <= min(window, 1024):
        side *= 2
    return dict(block_q=side, block_k=side)


def _heads_first(ins, *slots):
    return (jnp.swapaxes(first(ins, s), 1, 2) for s in slots)


@register_op("causal_attention")
def causal_attention_op(ctx, ins, attrs):
    """Q [B, S, H, D], K [B, S, Hkv, D], V [B, S, Hkv, Dv] (Dv may differ
    from D: latent attention's keys carry a rotary part its values lack;
    H a multiple of Hkv: grouped-query heads, H / Hkv consecutive query
    heads read one key/value head) -> Out [B, S, H, Dv] and Lse [B, H, S]
    (the scores' logsumexp, kept for the backward). Causal over the whole
    row, or with the attr `window` W > 0 over a band: query i sees the
    keys j with 0 <= i - j < W, itself among them. The scores are scaled
    by the attr `scale`, 1/sqrt(D) where it is 0. On a TPU place the
    Pallas flash kernel (parallel/flash.py), which never writes the [S, S]
    scores to HBM, reads K and V in place for every head of a group and
    skips the blocks outside the band (blocks: `flash_blocks`); elsewhere
    the plain composition."""
    q, k, v = _heads_first(ins, "Q", "K", "V")
    scale = float(attrs.get("scale", 0.0)) or None
    window = int(attrs.get("window", 0)) or None
    if on_tpu():
        from ..parallel.flash import flash_attention_fwd

        o, lse = flash_attention_fwd(q, k, v, causal=True, scale=scale,
                                     window=window, **flash_blocks(window))
    else:
        o, lse = _plain_causal_attention(q, k, v, scale, window)
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
    scale = float(attrs.get("scale", 0.0)) or None
    window = int(attrs.get("window", 0)) or None
    if on_tpu():
        from ..parallel.flash import flash_attention_bwd

        grads = flash_attention_bwd(q, k, v, o, first(ins, "Lse"),
                                    do.astype(q.dtype), causal=True,
                                    scale=scale, window=window,
                                    **flash_blocks(window, backward=True))
    else:
        _, vjp = jax.vjp(
            lambda *a: _plain_causal_attention(*a, scale, window)[0],
            q, k, v)
        grads = vjp(do.astype(q.dtype))
    dq, dk, dv = (jnp.swapaxes(g, 1, 2) for g in grads)
    return out(**{"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv})


# ------------------------------------------- attention behind an indexer
# DeepSeek Sparse Attention's form (DeepSeek-V3.2-Exp's report), as
# Keye-VL-2.0's language model has it: `indexer_select` scores every causal
# (query, key) pair with a small multi-head ReLU product and keeps a
# query's `topk` best keys, exactly; `sparse_attention` is grouped-query
# attention whose softmax runs over those keys alone; `indexer_loss` trains
# the indexer towards the attention's own head-mean probabilities. The
# lowerings are `parallel/sparse_index.py` (plain, a block of queries at a
# time), `parallel/flash.py`'s kernels given the mask and, on a TPU place,
# `parallel/index_select.py`'s kernel for the selection's threshold and
# mask and `parallel/index_loss.py`'s two kernels for the loss.
def _rows_of(fn, *batched):
    """`fn` of one row of tokens over the leading (batch) axis, a row at a
    time: a row's temporaries are large."""
    return lax.map(lambda xs: fn(*xs), batched)


def _indexer_inputs(ins):
    """QI [B, S, Hi, Di], KI [B, S, 1, Di] (one key head for all), W [B,
    S, Hi] -> q_i, k_i [B, S, Di], w."""
    return first(ins, "QI"), first(ins, "KI")[:, :, 0, :], first(ins, "W")


@register_op("indexer_select")
def indexer_select_op(ctx, ins, attrs):
    """QI [B, S, Hi, Di], KI [B, S, 1, Di], W [B, S, Hi] -> Mask [B, S, S]
    int8 (query, key): 1 where the key is among the query's `topk` causal
    keys of largest I[t, s] = sum_j W[t, j] relu(QI[t, j] . KI[s]) (every
    causal key while t < topk; of equal scores the lower position), 0
    elsewhere and above the diagonal; and Threshold [B, S] float32, each
    query's least chosen score as the bisection found it (the one thing
    the op writes of the scores it forms: a step that fetches it not
    pays nothing for it). The scores are float32 from products on the
    operands' dtype; the selection is exact, by bisection on the scores'
    bits (`parallel/sparse_index.py`: no sort), a block of queries at a
    time; on a TPU place, for the shapes `index_select.takes`, a block's
    threshold and mask are ONE Pallas kernel (`parallel/index_select.py`:
    the keys resident in VMEM, only the causal key tiles counted), bit-equal
    to the plain form. Nothing is differentiated: the choice has no
    gradient."""
    from ..parallel import sparse_index

    topk = int(attrs["topk"])
    mask, threshold = _rows_of(
        lambda q, k, w: sparse_index.select(q, k, w, topk),
        *_indexer_inputs(ins))
    return out(Mask=mask, Threshold=threshold)


set_stop_gradient_outputs("indexer_select", ["Mask", "Threshold"])


def _plain_sparse_attention(q, k, v, mask, scale=None):
    """softmax over the chosen keys alone of Q K^T * scale, times V, on Q
    [B, H, S, D], K [B, Hkv, S, D], V [B, Hkv, S, Dv], mask [B, S, S]
    (nonzero: chosen), float32 scores, and their logsumexp [B, H, S]: the
    lowering for places without Mosaic."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=F32)
    s = s / (q.shape[3] ** 0.5) if scale is None else s * scale
    s = jnp.where((mask != 0)[:, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=F32).astype(q.dtype)
    return o, lse


@register_op("sparse_attention")
def sparse_attention_op(ctx, ins, attrs):
    """Q [B, S, H, D], K [B, S, Hkv, D], V [B, S, Hkv, Dv] (H a multiple
    of Hkv: H / Hkv consecutive query heads read one key/value head),
    Mask [B, S, S] int8 (`indexer_select`'s: the same keys for every head)
    -> Out [B, S, H, Dv] and Lse [B, H, S] float32: each query's softmax
    over ITS CHOSEN KEYS ALONE of Q K^T times the attr `scale` (1/sqrt(D)
    where 0). On a TPU place the Pallas kernels of `parallel/flash.py`
    with the mask's block as their predicate (no [S, S] scores in HBM);
    elsewhere the plain composition."""
    q, k, v = _heads_first(ins, "Q", "K", "V")
    scale = float(attrs.get("scale", 0.0)) or None
    if on_tpu():
        from ..parallel.flash import flash_attention_fwd

        o, lse = flash_attention_fwd(q, k, v, causal=True, scale=scale,
                                     mask=first(ins, "Mask"),
                                     **FLASH_FWD_BLOCKS)
    else:
        o, lse = _plain_sparse_attention(q, k, v, first(ins, "Mask"), scale)
    return out(Out=jnp.swapaxes(o, 1, 2), Lse=lse)


set_stop_gradient_outputs("sparse_attention", ["Lse"])


@register_grad_maker("sparse_attention")
def _sparse_attention_grad_maker(op, gout, gin):
    """Hand-written, as `causal_attention`'s: the backward takes Out and
    Lse as the forward left them, and the mask has no gradient."""
    return [dict(
        type="sparse_attention_grad",
        inputs={"Q": op.input("Q"), "K": op.input("K"), "V": op.input("V"),
                "Mask": op.input("Mask"), "Out": op.output("Out"),
                "Lse": op.output("Lse"),
                "Out@GRAD": [x or "" for x in gout.get("Out", [])]},
        outputs={s + "@GRAD": gin.get(s, [""]) for s in ("Q", "K", "V")},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("sparse_attention_grad")
def sparse_attention_grad_op(ctx, ins, attrs):
    """On a TPU place the masked backward of `parallel/flash.py` from the
    saved output and logsumexp (ONE kernel for all three gradients where
    `flash.fused_backward_fits`, else the dK/dV and the dQ kernel);
    elsewhere the vjp of the plain composition."""
    q, k, v, o, do = _heads_first(ins, "Q", "K", "V", "Out", "Out@GRAD")
    scale = float(attrs.get("scale", 0.0)) or None
    mask = first(ins, "Mask")
    if on_tpu():
        from ..parallel.flash import flash_attention_bwd

        grads = flash_attention_bwd(q, k, v, o, first(ins, "Lse"),
                                    do.astype(q.dtype), causal=True,
                                    scale=scale, mask=mask,
                                    **FLASH_BWD_BLOCKS)
    else:
        _, vjp = jax.vjp(
            lambda *a: _plain_sparse_attention(*a, mask, scale)[0], q, k, v)
        grads = vjp(do.astype(q.dtype))
    dq, dk, dv = (jnp.swapaxes(g, 1, 2) for g in grads)
    return out(**{"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv})


_INDEXER_TRAINED = ("QI", "KI", "W")


@register_op("indexer_loss")
def indexer_loss_op(ctx, ins, attrs):
    """The loss that trains the indexer beside the model (the report's
    sparse stage). Q [B, S, H, D], K [B, S, Hkv, D] and Lse [B, H, S], the
    `sparse_attention`'s inputs and logsumexp, all three CONSTANTS here
    (the target is detached); QI, KI, W and Mask as `indexer_select` took
    and gave them -> Loss [1] float32 = the mean over all B x S queries of
    KL(p_t || softmax over the chosen keys of I[t, .]), p_t the
    attention's probabilities over the chosen keys averaged over the H
    heads. The loss is a scalar, so its gradient with respect to QI, KI
    and W is formed in the same pass over the query blocks (QIGrad,
    KIGrad, WGrad, float32; the grad op scales them by the loss's
    cotangent), and the head-mean probabilities, a pass over the main
    attention's scores, are formed once a step. On a TPU place, for the
    shapes `index_loss.takes`, the two Pallas kernels of
    `parallel/index_loss.py`, a (query block, key block) tile at a time;
    elsewhere `sparse_index.loss_and_grads`, a scan over blocks of
    queries."""
    from ..parallel import index_loss, sparse_index

    q, k = _heads_first(ins, "Q", "K")
    scale = float(attrs.get("scale", 0.0)) or 1.0 / (q.shape[3] ** 0.5)
    q_i, k_i, w = _indexer_inputs(ins)
    kernels = on_tpu() and index_loss.takes(
        q.shape[2], q.shape[1], k.shape[1], q.shape[3], *q_i.shape[2:],
        q.dtype)
    lowering = (index_loss if kernels else sparse_index).loss_and_grads
    total, d_q, d_k, d_w = _rows_of(
        lambda *row: lowering(*row, scale),
        q, k, first(ins, "Lse"), q_i, k_i, w, first(ins, "Mask"))
    tokens = q.shape[0] * q.shape[2]
    return out(Loss=(jnp.sum(total) / tokens).reshape(1),
               QIGrad=d_q / tokens, KIGrad=d_k[:, :, None, :] / tokens,
               WGrad=d_w / tokens)


set_stop_gradient_outputs("indexer_loss", ["QIGrad", "KIGrad", "WGrad"])


@register_grad_maker("indexer_loss")
def _indexer_loss_grad_maker(op, gout, gin):
    """Hand-written: the forward left the gradients; Q, K, Lse and Mask
    get none (the layer hands the op detached copies of Q and K)."""
    inputs = {s: op.input(s) for s in _INDEXER_TRAINED}
    inputs.update({s + "Grad": op.output(s + "Grad")
                   for s in _INDEXER_TRAINED})
    inputs["Loss@GRAD"] = [x or "" for x in gout.get("Loss", [])]
    return [dict(
        type="indexer_loss_grad", inputs=inputs,
        outputs={s + "@GRAD": gin.get(s, [""]) for s in _INDEXER_TRAINED},
        attrs={})]


@register_op("indexer_loss_grad")
def indexer_loss_grad_op(ctx, ins, attrs):
    """d QI, d KI, d W = the saved gradients times the loss's cotangent,
    in the inputs' dtypes."""
    g = first(ins, "Loss@GRAD").astype(F32).reshape(())
    return out(**{s + "@GRAD": (first(ins, s + "Grad") * g).astype(
        first(ins, s).dtype) for s in _INDEXER_TRAINED})


# -------------------------------------------------------------- short_conv
# The parts of the op's lowering, each under a `jax.named_scope` inside the
# op's own, forward and backward (`conv/short_conv/short_conv/taps`,
# `conv/short_conv/short_conv_grad/filter_grad`) where XLA lowers the plain
# form (a Pallas kernel stands there under its own name instead): GATE the
# element-wise gates (C * c, d X's thirds), TAPS the L shifted
# multiply-adds along the token axis (in the backward: of d conv, towards
# the tokens after), FILTER_GRAD the reduction over the tokens of d conv
# times the shifted v, a tap.
GATE, TAPS, FILTER_GRAD = "gate", "taps", "filter_grad"
# the op's attr `gating`: absent, LFM2's C * conv(B * z) on [T, 3C]; SILU,
# silu(conv(x)) on [T, C] (Gated DeltaNet's, over its q, k and v channels)
SILU = "silu"


def _whole(results):
    """The op's results as arrays of their own: without this XLA splits
    the last gate off the op and forms it again inside each consumer (the
    output projection's product and that product's gradient each read the
    float32 convolution and X: 168 MB where Out is 34), and a device trace
    files that time under the consumers (v5e compile, PERF.md PR 39)."""
    return lax.optimization_barrier(results)


def _rows(x, seq_len):
    """X [T, 3C] -> [rows, S, 3C]: rows of `seq_len` tokens."""
    return x.reshape(-1, seq_len, x.shape[1])


def _shifted(a, L, past):
    """The L windows [rows, S, .] of a [rows, S, .] one tap apart: window j
    holds a_{t - (L - 1 - j)} (`past`) or a_{t + (L - 1 - j)} (not `past`:
    the transpose, which the backward applies to d c); what lies outside a
    row is zero: rows are separate sequences. Slices of ONE padded array
    in its own dtype, so that XLA reads it once where it fuses them."""
    S = a.shape[1]
    ap = jnp.pad(a, ((0, 0), (L - 1, 0) if past else (0, L - 1), (0, 0)))
    return [ap[:, (j if past else L - 1 - j):][:, :S] for j in range(L)]


def _gated(window, C):
    """v = B * z [rows, S, C] float32 of a window of X's rows."""
    return window[..., :C].astype(F32) * window[..., 2 * C:].astype(F32)


def short_conv(x, w, seq_len):
    """Out [T, C] = C * causal_depthwise_conv_L(B * z) of X [T, 3C] = [B | C
    | z] and the taps w [L, C] (w[L - 1] weighs the token itself): L
    shifted multiply-adds along the token axis of [rows, S, C], channels
    last as the input projection leaves them; the gates and the sum in
    float32, one rounding to X's dtype. The shifts are windows of X
    itself and v = B * z is formed a window at a time: one pass that reads
    X and writes Out (shifting a stored v costs a float32 [T, C] written
    and read again: v5e compile, PERF.md PR 39)."""
    L, C = w.shape[0], x.shape[1] // 3
    xr, wf = _rows(x, seq_len), w.astype(F32)
    windows = _shifted(xr, L, past=True)
    with jax.named_scope(TAPS):
        conv = sum(wf[j] * _gated(windows[j], C) for j in range(L))
    with jax.named_scope(GATE):
        out_ = xr[..., C:2 * C].astype(F32) * conv
        return _whole(out_.reshape(x.shape[0], C).astype(x.dtype))


def short_conv_grad(x, w, d_out, seq_len):
    """(d X [T, 3C] in X's dtype, d Filter [L, C] float32) from X, the taps
    and d Out alone: v = B * z and its convolution are formed again (three
    multiply-adds an element) and never kept, the three thirds of d X are
    written once, side by side, and d Filter is a float32 reduction over
    the tokens of [T, C] products, a tap."""
    L, C = w.shape[0], x.shape[1] // 3
    xr, wf = _rows(x, seq_len), w.astype(F32)
    g = d_out.reshape(xr.shape[0], seq_len, C)
    c = xr[..., C:2 * C]
    v = [_gated(win, C) for win in _shifted(xr, L, past=True)]
    with jax.named_scope(TAPS):
        d_c = g.astype(F32) * sum(wf[j] * v[j] for j in range(L))
        # d conv = d Out * C of the tokens after: windows of both factors
        d_v = sum(wf[j] * (gw.astype(F32) * cw.astype(F32))
                  for j, (gw, cw) in enumerate(zip(
                      _shifted(g, L, past=False), _shifted(c, L, past=False))))
    with jax.named_scope(GATE):
        d_x = jnp.concatenate([d_v * xr[..., 2 * C:].astype(F32), d_c,
                               d_v * xr[..., :C].astype(F32)], axis=-1)
    with jax.named_scope(FILTER_GRAD):
        d_conv = g.astype(F32) * c.astype(F32)
        d_w = jnp.stack([jnp.sum((v[j] * d_conv).astype(jnp.float32),
                                 axis=(0, 1)) for j in range(L)])
    return _whole((d_x.reshape(x.shape).astype(x.dtype), d_w))


def silu_conv(x, w, seq_len, bias=None):
    """Out [T, C] = silu(causal_depthwise_conv_L(X) + bias) of X [T, C],
    the taps w [L, C] (w[L - 1] weighs the token itself) and, where given,
    a bias [C] a channel, `short_conv`'s variant `gating="silu"`: the L
    shifted multiply-adds, the bias and the SiLU in float32, one rounding
    to X's dtype."""
    L = w.shape[0]
    xr, wf = _rows(x, seq_len), w.astype(F32)
    with jax.named_scope(TAPS):
        conv = sum(wf[j] * win.astype(F32)
                   for j, win in enumerate(_shifted(xr, L, past=True)))
        if bias is not None:
            conv = conv + bias.astype(F32)
    with jax.named_scope(GATE):
        return _whole(jax.nn.silu(conv).reshape(x.shape).astype(x.dtype))


def silu_conv_grad(x, w, d_out, seq_len, bias=None):
    """(d X [T, C] in X's dtype, d Filter [L, C] float32) of `silu_conv`
    from X, the taps and d Out alone: the convolution is formed again, d
    conv = d Out * silu'(conv), d X the taps' transpose over the tokens
    after, d Filter a float32 reduction over the tokens, a tap. With a
    `bias` [C], d Bias [C] float32 (d conv summed over the tokens) is the
    third result."""
    L = w.shape[0]
    xr, wf = _rows(x, seq_len), w.astype(F32)
    back = [win.astype(F32) for win in _shifted(xr, L, past=True)]
    with jax.named_scope(GATE):
        conv = sum(wf[j] * back[j] for j in range(L))
        if bias is not None:
            conv = conv + bias.astype(F32)
        s = jax.nn.sigmoid(conv)
        d_conv = d_out.reshape(xr.shape).astype(F32) \
            * (s * (1.0 + conv * (1.0 - s)))
    with jax.named_scope(TAPS):
        d_x = sum(wf[j] * win for j, win in enumerate(
            _shifted(d_conv, L, past=False)))
    with jax.named_scope(FILTER_GRAD):
        d_w = jnp.stack([jnp.sum(back[j] * d_conv, axis=(0, 1))
                         for j in range(L)])
        d_b = () if bias is None else (jnp.sum(d_conv, axis=(0, 1)),)
    return _whole((d_x.reshape(x.shape).astype(x.dtype), d_w, *d_b))


@register_op("short_conv")
def short_conv_op(ctx, ins, attrs):
    """The operator of a gated short-convolution layer (LFM2's, Liquid AI):
    X [T, 3C], the input projection's output as the product leaves it
    (channels last; the thirds B, C, z side by side), T = rows x `seq_len`
    tokens; Filter [L, C], depth-wise taps, Filter[L - 1] on the token
    itself -> Out [T, C] = C * c, c_t = sum_j Filter[j] (B * z)_{t - (L - 1
    - j)}, zero before a row's first token (rows of a batch are separate
    sequences). The gates and the L-tap sum in float32, one rounding to
    X's dtype on Out. On a TPU place the Pallas kernel of
    parallel/short_conv.py where it takes the shapes (one pass: X read
    once, Out written); elsewhere L shifted multiply-adds along the token
    axis: no transpose to [rows, C, S], no grouped convolution.

    With the attr `gating` = "silu" the op is the convolution of a Gated
    DeltaNet layer instead: X [T, C], Out [T, C] = silu(c), c_t = sum_j
    Filter[j] x_{t - (L - 1 - j)}, the sum and the SiLU in float32, one
    rounding; on a TPU place the variant's Pallas kernels where they take
    the shapes (`parallel/short_conv.py: silu_conv_fwd`), L shifted
    multiply-adds elsewhere (`silu_conv`). That variant alone takes a Bias
    [C] (a Mamba-2 mixer's convolution, float32 master read as it is):
    Out = silu(c + Bias); with it the op is the L shifted multiply-adds on
    every place (the kernels have no bias operand)."""
    x, w = first(ins, "X"), first(ins, "Filter")
    seq_len = int(attrs["seq_len"])
    bias = first(ins, "Bias")
    if bias is not None:
        return out(Out=silu_conv(x, w, seq_len, bias))
    if attrs.get("gating") == SILU:
        if _silu_kernels_take(x, w, seq_len):
            from ..parallel.short_conv import silu_conv_fwd

            return out(Out=silu_conv_fwd(x, w, seq_len))
        return out(Out=silu_conv(x, w, seq_len))
    if _conv_kernels_take(x, w, seq_len):
        from ..parallel.short_conv import short_conv_fwd

        return out(Out=short_conv_fwd(x, w, seq_len))
    return out(Out=short_conv(x, w, seq_len))


def _conv_kernels_take(x, w, seq_len):
    """Whether this trace hands `short_conv` to the Pallas kernels: a TPU
    place, shapes they take, and the op's inner precision the stated one
    (a study one precision down runs the plain form)."""
    from ..parallel import short_conv as kernels

    return on_tpu() and F32 == jnp.float32 and kernels.takes(
        x.shape[0], x.shape[1] // 3, seq_len, w.shape[0], x.dtype)


def _silu_kernels_take(x, w, seq_len):
    """`_conv_kernels_take` for the variant silu(conv(x))."""
    from ..parallel import short_conv as kernels

    return on_tpu() and F32 == jnp.float32 and kernels.silu_takes(
        x.shape[0], x.shape[1], seq_len, w.shape[0], x.dtype)


@register_grad_maker("short_conv")
def _short_conv_grad_maker(op, gout, gin):
    """Hand-written: the generic vjp of the shifted slices keeps a copy of
    v = B * z a tap for the backward; this one reads X, Filter and d Out."""
    slots = [s for s in ("X", "Filter", "Bias") if op.input(s)]
    return [dict(
        type="short_conv_grad",
        inputs={**{s: op.input(s) for s in slots},
                "Out@GRAD": [x or "" for x in gout.get("Out", [])]},
        outputs={s + "@GRAD": gin.get(s, [""]) for s in slots},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("short_conv_grad")
def short_conv_grad_op(ctx, ins, attrs):
    """d X and d Filter [L, C] (float32) of `short_conv` (and d Bias [C]
    of the "silu" variant given one): the backward kernel where the forward
    took its kernel, else the plain form's."""
    x, w = first(ins, "X"), first(ins, "Filter")
    seq_len = int(attrs["seq_len"])
    bias = first(ins, "Bias")
    if bias is not None:
        d_x, d_w, d_b = silu_conv_grad(x, w, first(ins, "Out@GRAD"),
                                       seq_len, bias)
        return out(**{"X@GRAD": d_x, "Filter@GRAD": d_w.astype(w.dtype),
                      "Bias@GRAD": d_b.astype(bias.dtype)})
    if attrs.get("gating") == SILU:
        if _silu_kernels_take(x, w, seq_len):
            from ..parallel.short_conv import silu_conv_bwd as grad
        else:
            grad = silu_conv_grad
    elif _conv_kernels_take(x, w, seq_len):
        from ..parallel.short_conv import short_conv_bwd as grad
    else:
        grad = short_conv_grad
    d_x, d_w = grad(x, w, first(ins, "Out@GRAD"), seq_len)
    return out(**{"X@GRAD": d_x, "Filter@GRAD": d_w.astype(w.dtype)})


# --------------------------------------------------------- gated_rms_norm
_GATED_NORM_INPUTS = ("X", "Gate", "Scale")


def _inverse_rms(xf, eps):
    return lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)


def gated_rms_norm(x, gate, w, eps):
    """Y = X r w silu(gate) in X's shape and dtype, r = rsqrt(mean(X^2, last
    axis) + eps): X [T, H, D], the gate [T, H D] or [T, H, D], w [D];
    everything float32, one rounding."""
    xf = x.astype(F32)
    z = gate.astype(F32).reshape(x.shape)
    return (xf * _inverse_rms(xf, eps) * w.astype(F32)
            * (z * jax.nn.sigmoid(z))).astype(x.dtype)


def gated_rms_norm_grad(x, gate, w, d_out, eps):
    """(d X, d gate in their shapes and dtypes, d w [D] float32) of
    `gated_rms_norm` from X, the gate, w and d Y alone: r is formed again.
    With n = x r w, s = silu(z), g = d y s w: d z = d y n silu'(z), d x = r
    g - x r^3 mean(g x), d w = the sum over tokens and heads of d y s x r."""
    xf, wf = x.astype(F32), w.astype(F32)
    z = gate.astype(F32).reshape(x.shape)
    dy = d_out.astype(F32).reshape(x.shape)
    r = _inverse_rms(xf, eps)
    xr, sig = xf * r, jax.nn.sigmoid(z)
    dys = dy * (z * sig)
    g = dys * wf
    d_z = dy * (xr * wf) * (sig * (1.0 + z * (1.0 - sig)))
    d_x = r * (g - xr * jnp.mean(g * xr, axis=-1, keepdims=True))
    d_w = jnp.sum((dys * xr).astype(jnp.float32).reshape(-1, x.shape[-1]),
                  axis=0)
    return (d_x.astype(x.dtype), d_z.reshape(gate.shape).astype(gate.dtype),
            d_w)


@register_op("gated_rms_norm")
def gated_rms_norm_op(ctx, ins, attrs):
    """The output norm of a Gated DeltaNet layer as ONE op: X [T, H, D] (the
    delta rule's output a head), Gate [T, H D] or [T, H, D] (z of the input
    projection), Scale [D] (a float32 master read as it is) -> Y = X /
    sqrt(mean(X^2, last axis) + `epsilon`) * Scale * silu(Gate) in X's shape
    and dtype; statistics, scale, SiLU and the products float32 whatever
    arrives, ONE rounding on Y (the three ops `rms_norm`, `swish`,
    `elementwise_mul` round twice more in between). On a TPU place the
    Pallas kernel of parallel/gated_norm.py where it takes the shapes (X
    and Gate read once, Y written); plain `jax.numpy` elsewhere."""
    x, gate, w = (first(ins, s) for s in _GATED_NORM_INPUTS)
    eps = float(attrs.get("epsilon", 1e-5))
    if _gated_norm_kernels_take(x, gate):
        from ..parallel.gated_norm import gated_norm_fwd

        return out(Y=gated_norm_fwd(x, gate, w, eps))
    return out(Y=gated_rms_norm(x, gate, w, eps))


def _gated_norm_kernels_take(x, gate):
    """Whether this trace hands `gated_rms_norm` to the Pallas kernels: a
    TPU place, shapes they take, X and the gate of one dtype, and the op's
    inner precision the stated one (a study one precision down runs the
    plain form)."""
    from ..parallel import gated_norm as kernels

    return on_tpu() and F32 == jnp.float32 and gate.dtype == x.dtype \
        and kernels.fits(x.shape, x.dtype)


@register_grad_maker("gated_rms_norm")
def _gated_rms_norm_grad_maker(op, gout, gin):
    """Hand-written: the generic vjp keeps the float32 normed X and SiLU for
    the backward; this one reads X, Gate, Scale and d Y."""
    return [dict(
        type="gated_rms_norm_grad",
        inputs={**{s: op.input(s) for s in _GATED_NORM_INPUTS},
                "Y@GRAD": [x or "" for x in gout.get("Y", [])]},
        outputs={s + "@GRAD": gin.get(s, [""]) for s in _GATED_NORM_INPUTS},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("gated_rms_norm_grad")
def gated_rms_norm_grad_op(ctx, ins, attrs):
    """d X, d Gate (in their dtypes) and d Scale [D] of `gated_rms_norm`:
    the backward kernel where the forward took its kernel, else the plain
    form's."""
    x, gate, w = (first(ins, s) for s in _GATED_NORM_INPUTS)
    eps = float(attrs.get("epsilon", 1e-5))
    if _gated_norm_kernels_take(x, gate):
        from ..parallel.gated_norm import gated_norm_bwd as grad
    else:
        grad = gated_rms_norm_grad
    d_x, d_z, d_w = grad(x, gate, w, first(ins, "Y@GRAD"), eps)
    return out(**{"X@GRAD": d_x, "Gate@GRAD": d_z,
                  "Scale@GRAD": d_w.astype(w.dtype)})


# ------------------------------------------------------- gated_delta_rule
def _delta_shape(attrs):
    """The keyword arguments of `parallel/delta_rule.py`'s two functions."""
    return dict(seq_len=int(attrs["seq_len"]), hk=int(attrs["num_k_heads"]),
                hv=int(attrs["num_v_heads"]), dk=int(attrs["head_k_dim"]),
                dv=int(attrs["head_v_dim"]), chunk=int(attrs["chunk"]),
                eps=float(attrs.get("epsilon", 1e-6)))


_DELTA_INPUTS = ("QKV", "BA", "ALog", "DtBias")


@register_op("gated_delta_rule")
def gated_delta_rule_op(ctx, ins, attrs):
    """The recurrence of a Gated DeltaNet layer (Yang et al.,
    arXiv:2412.06464; Qwen3-Next's linear-attention layers). QKV [T, 2 Hk
    dk + Hv dv] = [q | k | v], the convolution's output as it leaves it, T
    = rows x `seq_len` tokens; BA [T, 2 Hv] = [b | a]; ALog, DtBias [Hv]
    (float32 masters read as they are) -> Out [T, Hv dv]. Per head q and k
    are divided by sqrt(sum of squares + `epsilon`), q times dk^-1/2 as
    well; key head j serves the value heads (Hv / Hk) j ...; beta =
    sigmoid(b), g = -exp(ALog) softplus(a + DtBias), float32; per value
    head a state S [dk, dv], zero at a row's first token (rows are
    separate sequences): S <- exp(g_t) S; S <- S + beta_t k_t (v_t - S^T
    k_t)^T; o_t = S^T q_t. Worked in chunks of `chunk` tokens
    (`parallel/delta_rule.py`): the in-chunk quantities as batched
    products over all chunks (on a TPU place, for shapes they take, in the
    Pallas kernels of `parallel/delta_parts.py`), one [dk, dk] x [dk, dv]
    product a chunk and head in a scan, the carried state float32. States
    [head groups, chunks, rows x Hv / groups, dk, dv] float32 ([chunks,
    rows x Hv, dk, dv] from the kernel path, which works all heads at
    once), the state each chunk starts from, is kept for the backward op;
    FinalState [rows, Hv, dk, dv] is the state behind each
    row's last token."""
    from ..parallel import delta_rule

    qkv = first(ins, "QKV")
    fwd = delta_rule.kernels_fwd if _delta_kernels_take(qkv, attrs) \
        else delta_rule.delta_rule_fwd
    o, starts, last = fwd(
        *(first(ins, s) for s in _DELTA_INPUTS), **_delta_shape(attrs))
    return out(Out=o, States=starts, FinalState=last)


def _delta_kernels_take(qkv, attrs):
    """Whether this trace hands the op's in-chunk work to the Pallas kernels
    of `parallel/delta_parts.py`: a TPU place and shapes they take (the
    forward op and its grad op decide alike: `States` is laid out by the
    path that wrote it)."""
    return on_tpu() and _delta_shapes_taken(qkv.shape[0], qkv.dtype, attrs)


def _delta_shapes_taken(tokens, dtype, attrs):
    """`delta_rule.takes` of `tokens` tokens in `dtype` under the op's
    attrs."""
    from ..parallel import delta_rule

    shape = _delta_shape(attrs)
    seq_len = shape.pop("seq_len")
    shape.pop("eps")
    return tokens % seq_len == 0 and delta_rule.takes(
        tokens // seq_len, seq_len, dtype=dtype, **shape)


set_stop_gradient_outputs("gated_delta_rule", ["States", "FinalState"])


@register_grad_maker("gated_delta_rule")
def _gated_delta_rule_grad_maker(op, gout, gin):
    """Hand-written: the generic vjp of the chunk scan would keep every
    chunk's in-chunk quantities; this one takes the chunk-start states the
    forward left and forms the rest again."""
    inputs = {s: op.input(s) for s in _DELTA_INPUTS}
    inputs["States"] = op.output("States")
    inputs["Out@GRAD"] = [x or "" for x in gout.get("Out", [])]
    return [dict(
        type="gated_delta_rule_grad", inputs=inputs,
        outputs={s + "@GRAD": gin.get(s, [""]) for s in _DELTA_INPUTS},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("gated_delta_rule_grad")
def gated_delta_rule_grad_op(ctx, ins, attrs):
    """d QKV, d BA (in their dtypes), d ALog, d DtBias of
    `gated_delta_rule`: the reverse recurrence of the state's cotangent
    over the chunks, written out, from the saved chunk-start states."""
    from ..parallel import delta_rule

    args = [first(ins, s) for s in _DELTA_INPUTS]
    bwd = delta_rule.kernels_bwd if _delta_kernels_take(args[0], attrs) \
        else delta_rule.delta_rule_bwd
    grads = bwd(*args, first(ins, "States"), first(ins, "Out@GRAD"),
                **_delta_shape(attrs))
    return out(**{s + "@GRAD": g.astype(a.dtype)
                  for s, g, a in zip(_DELTA_INPUTS, grads, args)})


# --------------------------------------------------------------- ssd_scan
def _ssd_shape(attrs):
    """The keyword arguments of `parallel/ssd.py`'s two functions."""
    return dict(seq_len=int(attrs["seq_len"]), heads=int(attrs["num_heads"]),
                head_dim=int(attrs["head_dim"]),
                groups=int(attrs["num_groups"]),
                state=int(attrs["state_size"]), chunk=int(attrs["chunk"]))


_SSD_INPUTS = ("X", "B", "C", "Dt", "ALog", "DtBias", "D")


@register_op("ssd_scan")
def ssd_scan_op(ctx, ins, attrs):
    """The selective state-space scan of a Mamba-2 mixer (Dao & Gu,
    arXiv:2405.21060). X [T, H P], B, C [T, G N] (the convolution's output,
    split), Dt [T, H], T = rows x `seq_len` tokens; ALog, DtBias, D [H]
    (float32 masters read as they are) -> Out [T, H P]. delta =
    softplus(Dt + DtBias), A = -exp(ALog), float32; per head a state h [P,
    N], zero at a row's first token (rows are separate sequences): h_t =
    exp(delta_t A) h_{t-1} + delta_t x_t B_t^T; y_t = h_t C_t + D x_t, head
    h reading group h // (H / G)'s B and C. Worked in chunks of `chunk`
    tokens (`parallel/ssd.py`, the state-space dual form): the in-chunk
    products as batched products over all chunks (C B^T once a group; on a
    TPU place, for shapes they take, in the Pallas kernels of
    `parallel/ssd_parts.py`, which keep a chunk's decays in VMEM), a scan
    over the chunks that carries the state in float32. States [chunks,
    rows, G, H / G, P, N] float32, the state each chunk starts from, is
    kept for the backward op; FinalState [rows, H, P, N] is the state
    behind each row's last token. A row need not be whole chunks: its tail
    is padded with steps of zero."""
    from ..parallel import ssd

    args = [first(ins, s) for s in _SSD_INPUTS]
    fwd = ssd.kernels_fwd if _ssd_kernels_take(args[0], attrs) \
        else ssd.ssd_fwd
    o, starts, last = fwd(*args, **_ssd_shape(attrs))
    return out(Out=o, States=starts, FinalState=last)


def _ssd_kernels_take(x, attrs):
    """Whether this trace hands the op's in-chunk work to the Pallas kernels
    of `parallel/ssd_parts.py`: a TPU place and shapes they take (rows of
    whole chunks of 128, lane-tile widths, bf16 or float32); anything else
    lowers the plain form. Read from the call: no flag."""
    return on_tpu() and _ssd_shapes_taken(x.shape[0], x.dtype, attrs)


def _ssd_shapes_taken(tokens, dtype, attrs):
    """`ssd.takes` of `tokens` tokens in `dtype` under the op's attrs."""
    from ..parallel import ssd

    shape = _ssd_shape(attrs)
    seq_len = shape.pop("seq_len")
    return tokens % seq_len == 0 and ssd.takes(
        tokens // seq_len, seq_len, dtype=dtype, **shape)


set_stop_gradient_outputs("ssd_scan", ["States", "FinalState"])


@register_grad_maker("ssd_scan")
def _ssd_scan_grad_maker(op, gout, gin):
    """Hand-written: the generic vjp of the chunk scan would keep every
    chunk's [Q, Q] decays and products; this one takes the chunk-start
    states the forward left and forms the rest again."""
    inputs = {s: op.input(s) for s in _SSD_INPUTS}
    inputs["States"] = op.output("States")
    inputs["Out@GRAD"] = [x or "" for x in gout.get("Out", [])]
    return [dict(
        type="ssd_scan_grad", inputs=inputs,
        outputs={s + "@GRAD": gin.get(s, [""]) for s in _SSD_INPUTS},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("ssd_scan_grad")
def ssd_scan_grad_op(ctx, ins, attrs):
    """d X, d B, d C, d Dt (in their dtypes), d ALog, d DtBias, d D of
    `ssd_scan`: the reverse recurrence of the state's cotangent over the
    chunks, written out, from the saved chunk-start states."""
    from ..parallel import ssd

    args = [first(ins, s) for s in _SSD_INPUTS]
    bwd = ssd.kernels_bwd if _ssd_kernels_take(args[0], attrs) \
        else ssd.ssd_bwd
    grads = bwd(*args, first(ins, "States"), first(ins, "Out@GRAD"),
                **_ssd_shape(attrs))
    return out(**{s + "@GRAD": g.astype(a.dtype)
                  for s, g, a in zip(_SSD_INPUTS, grads, args)})


# ----------------------------------------------------------------- moe_ffn
# The parts of the layer's lowering outside its kernels, each under a
# `jax.named_scope` of its own inside the op's, forward and backward, so a
# device trace tells them apart (`moe/moe_ffn/dispatch`, `moe/moe_ffn_grad/
# combine`; chipbench/layer_metrics/expert_{route,move,cast}_share.py):
# ROUTE the router's products, scores, `top_k`, the chosen scores and both
# argsorts; DISPATCH the row gathers into expert order, their backward and
# the zeroing of `ys` / `d xs` past the groups (`parallel/grouped.py`);
# COMBINE the gather a choice, the weights, the sums, `DownOut`'s zero
# tail and the weights' gradient; `cast` is amp's (the float32 -> bf16
# casts of gate / up / down: `amp.CAST_SCOPE`).
ROUTE, DISPATCH, COMBINE = "route", "dispatch", "combine"
# JAX writes the first scope opened inside a function it differentiates as
# `jvp(<scope>)`, which chipbench/scopes.py drops with all it wraps: the
# backward op differentiates under this one, for JAX to wrap, and the
# parts' names come after it (as `grouped._SCOPE`).
_VJP = "vjp"


def _first_rows(a, rows):
    return a if a is None or a.shape[0] <= rows else a[:rows]


def _sum_of_choices(table, inv, k, weights=None):
    """[T, H] float32: the sum over token t's k choices j of (weights[t,
    j] x) the row of `table` that holds the choice, row inv[t * k + j] of
    the sorted rows. A table of all T * k rows: one gather and a sum over
    k (what a layer that holds every expert has always run). A table of
    the first B sorted rows only (`row_bound`): a choice whose row lies
    past it adds zero, and the gathers are taken a choice at a time, T
    rows each: [T * k, H] -> [T, k, H] is a relayout in HBM where k rows
    do not fill a tile, and XLA does not fuse it into the gather (v5e, ms
    forward / d x: 0.89 / 0.87 against 0.31 / 0.27 at [4096, 4, 3584]
    from 4,096 rows, 1.01 / 0.88 against 1.02 / 0.91 at [8192, 8, 2048]
    from 16,384; a scatter-add of the B rows 1.35 and 1.94;
    tools/combine_sweep.py, PERF.md PR 33)."""
    n = inv.shape[0]
    if table.shape[0] >= n:
        y = table[inv].reshape(n // k, k, -1)
        if weights is None:
            return y.sum(axis=1)
        return jnp.einsum("tkh,tk->th", y.astype(F32), weights)
    inv = inv.reshape(n // k, k)
    total = 0.0
    for j in range(k):
        rows = jnp.take(table, inv[:, j], axis=0, mode="fill",
                        fill_value=0).astype(F32)
        total = total + (rows if weights is None
                         else rows * weights[:, j, None])
    return total


def _by_token(order, rows_held, T, k, H):
    """The first B sorted slots by the TILE of tokens each belongs to, for
    `_sum_by_token`: slot s holds a row of token order[s] // k, and a slot
    at or past `rows_held` no held expert's row: it gets the token T, which
    the kernel skips. A counting sort over the few tiles, no `lax.sort`:
    a slot's place is its tile's start plus its rank among the tile's
    slots (a running count down the [B, tiles] membership), and ONE scatter
    of B scalars, each packing (slot, choice t * k + j), puts slots,
    choices and with them the tokens in that order; inside a tile the
    slots keep their own order, and the kernel asks for no more. (A sort
    that carries its payloads does the same on the chip 0.7 ms a step
    faster and costs every run 16-22 s of XLA's compile, 126 -> 142-149 s
    of set-up; a gather of B scalars costs 0.17 ms: PERF.md PR 40.) (The
    slots and the choices, each with the kernel's tail; the tokens.)"""
    from ..parallel import row_sum

    R, C = row_sum.tiles_for(H)
    B = order.shape[0]
    slot = jnp.arange(B, dtype=jnp.int32)
    tile = jnp.where(slot < rows_held, order // (k * R), T // R)
    member = tile[:, None] == jnp.arange(T // R + 1)[None, :]
    rank = jnp.cumsum(member.astype(jnp.int32), axis=0)
    start = jnp.cumsum(rank[-1]) - rank[-1]
    place = jnp.sum(jnp.where(member, rank - 1 + start[None, :], 0), axis=1)
    # 15 bits of slot, 16 of choice: `row_sum.takes_choices`
    packed = jnp.zeros((B,), jnp.int32).at[place].set(
        (slot << 16) | order, unique_indices=True)
    slots, choices = packed >> 16, packed & 0xFFFF
    token = jnp.where(slots < rows_held, choices // k, T)
    return row_sum.with_tail(slots, C), token, row_sum.with_tail(choices, C)


def _sum_by_token(table, by_token, T, weights=None):
    """`_sum_of_choices` on a bounded table as ONE sum of its B rows by
    token (`_by_token`; weights [T * k], a choice's): a B-row gather into
    the tiles' order and the row-tile kernel (`parallel/row_sum.py`),
    which writes each [R, H] tile of the [T, H] result once, in the
    table's dtype. The same float32 sums; a token's up to k rows are added
    in sorted-row order, not in choice order."""
    from ..parallel import row_sum

    slots, token, choices = by_token
    return row_sum.sum_sorted_rows(
        token, table[slots], T, row_sum.tiles_for(table.shape[1]),
        weights=None if weights is None else weights[choices],
        out_dtype=table.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k, by_token=None):
    """Rows of x [T, H] in expert order: slot s holds token order[s] // k.
    `order` may be the first B of the T * k sorted slots."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k, by_token):
    return x[order // k], (inv, by_token)


def _dispatch_bwd(k, res, g):
    inv, by_token = res
    d_x = _sum_of_choices(g, inv, k).astype(g.dtype) if by_token is None \
        else _sum_by_token(g, by_token, inv.shape[0] // k)
    return d_x, None, None, None


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


@jax.custom_vjp
def _combine(ys, top_p, order, inv, by_token=None):
    """Out_t = sum over token t's k choices of top_p[t, j] * (its row of
    ys), float32 sums, in ys's dtype. ys [B, H] holds the first B sorted
    rows, zero past the held experts' (a choice whose row lies past B adds
    nothing); order [B], inv [T * k]; `by_token`: `_by_token`'s where the
    B rows are summed by token, None where the choices are gathered."""
    return _combine_fwd(ys, top_p, order, inv, by_token)[0]


def _combine_fwd(ys, top_p, order, inv, by_token):
    if by_token is None:
        o = _sum_of_choices(ys, inv, top_p.shape[1], top_p).astype(ys.dtype)
    else:
        o = _sum_by_token(ys, by_token, top_p.shape[0], top_p.reshape(-1))
    return o, (ys, top_p, order, inv)


def _combine_bwd(res, g):
    """By hand, so that nothing of T * k rows is made but the weights'
    gradient [T * k]: d ys and the weights' gradient come from a B-row
    gather of d Out."""
    ys, top_p, order, inv = res
    g = g[order // top_p.shape[1]].astype(F32)              # [B, H]
    d_ys = g * top_p.reshape(-1)[order][:, None]
    d_w = jnp.sum(ys.astype(F32) * g, axis=1)
    # back in token order: a scatter of the B sorted slots (each written
    # once; 0.1 ms for 16,384 where a gather of all 65,536 took 0.47)
    d_w = jnp.zeros(inv.shape, F32).at[order].set(d_w, unique_indices=True)
    return (d_ys.astype(ys.dtype),
            d_w.reshape(top_p.shape).astype(top_p.dtype), None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


class Routing:
    """How `moe_ffn` scores, chooses and weighs, which experts the layer
    holds and what gates them; static, from the op's attributes. The
    defaults are OLMoE's: softmax scores, the k largest, their scores as
    weights, every expert held, SiLU on the gate."""

    def __init__(self, attrs, n_experts):
        self.top_k = int(attrs.get("top_k", 1))
        self.score_func = attrs.get("score_func", "softmax")
        self.norm_topk = bool(attrs.get("norm_topk", False))
        self.scale = float(attrs.get("routed_scale", 1.0))
        self.first = int(attrs.get("first_expert", 0))
        self.held = int(attrs.get("held_experts", 0)) or n_experts
        self.all_held = self.first == 0 and self.held == n_experts
        self.activation = attrs.get("activation", "silu")
        self.norm_eps = float(attrs.get("norm_eps", 1e-20))


# The even-load share of the rows, times this, bounds the rows a layer
# that holds a share of its experts moves around its products.
ROW_BOUND_FACTOR = 2


def row_bound(n_rows, held, n_experts):
    """The static row count B that a `moe_ffn` holding `held` of
    `n_experts` experts works on, of its n_rows = top_k x tokens sorted
    choice rows: ROW_BOUND_FACTOR times the share an even load would send
    to the held experts, rounded up to the grouped kernels' longest row
    tile (so `grouped.takes` accepts B wherever it accepts the tile),
    never above n_rows and n_rows itself where every expert is held. From
    the shapes alone. A step whose held experts received more rows
    (`RowsHeld` > B) runs the same body over all n_rows: nothing is
    dropped."""
    from ..parallel.grouped import ROW_TILES

    if held >= n_experts:
        return n_rows
    tile = ROW_TILES[0]
    even = ROW_BOUND_FACTOR * n_rows * held
    return min(n_rows, -(-even // (n_experts * tile)) * tile)


def moe_ffn(x, router, gate, up, down, top_k):
    """The expert layer on tokens x [T, H], every expert held, OLMoE's
    routing: `moe_ffn_op`'s outputs Out, AuxLoss, ZLoss, ExpertIds,
    TokensPerExpert."""
    return _moe_ffn(x, router, None, gate, up, down,
                    Routing({"top_k": top_k}, router.shape[1]))[0][:5]


@jax.named_scope(ROUTE)
def _route(x, router, bias, r):
    """The router's part of the layer on the rows x it reads (the op's
    `RouterInput`, `X` where it has none): (the chosen experts' weights top_p
    [T, k], AuxLoss, ZLoss), which carry gradients, and (ExpertIds,
    TokensPerExpert, the rows each held expert received, the sort by held
    expert `order` and its inverse `inv` [T * k]), which do not."""
    T, E, top_k = x.shape[0], router.shape[1], r.top_k
    # router, scores and top-k in float32 at full precision whatever the
    # compute dtype: a bf16 logit moves the discrete choice
    logits = jnp.dot(x.astype(F32), router.astype(F32),
                     precision=lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    if r.score_func == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jnp.exp(logits - lse[:, None])
    if bias is None:
        top_p, top_e = lax.top_k(probs, top_k)              # [T, k]
    else:
        # chosen by score + bias, weighed by the score alone; the bias is
        # not trained through the loss. Softmax scores lie near 1 / E, where
        # any usable step of a bias swamps them: there the bias joins the
        # LOGITS (the order of softmax(logits + b), a softmax prior)
        chosen_by = logits if r.score_func == "softmax" else probs
        _, top_e = lax.top_k(chosen_by + lax.stop_gradient(bias.astype(F32)),
                             top_k)
        # the chosen scores by comparison, not `take_along_axis`: a gather
        # of T * k scalars took a v5e 0.67 ms, and 0.76 again in the
        # backward op, of a Laguna layer's 7.6 outside its kernels
        # (PERF.md PR 33); one non-zero term a sum, so the same numbers
        top_p = jnp.sum(jnp.where(
            top_e[:, :, None] == jnp.arange(E)[None, None, :],
            probs[:, None, :], 0.0), axis=2)
    if r.norm_topk:
        top_p = top_p / (jnp.sum(top_p, axis=1, keepdims=True) + r.norm_eps)
    if r.scale != 1.0:
        top_p = top_p * r.scale
    flat_e = top_e.reshape(-1)
    if r.all_held:
        key = flat_e
    else:
        # rows of the held experts first, in expert order; the others'
        # rows after them, where no product visits them
        local = flat_e - r.first
        is_held = (local >= 0) & (local < r.held)
        key = jnp.where(is_held, local, r.held)
        top_p = jnp.where(is_held.reshape(T, top_k), top_p, 0.0)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    counts = jnp.sum(flat_e[:, None] == jnp.arange(E)[None, :], axis=0,
                     dtype=jnp.int32)
    held_counts = counts if r.all_held else jnp.sum(
        key[:, None] == jnp.arange(r.held)[None, :], axis=0,
        dtype=jnp.int32)
    # load balance: E * sum_e (share of routing slots on e) * (mean score
    # of e); the shares are counts and carry no gradient. z-loss: mean
    # lse^2
    share = counts.astype(F32) / (T * top_k)
    aux = E * jnp.sum(share * jnp.mean(probs, axis=0))
    return ((top_p, aux.reshape(1), jnp.mean(jnp.square(lse)).reshape(1)),
            (top_e.astype(jnp.int32), counts, held_counts, order, inv))


def _experts(x, top_p, gate, up, down, held_counts, order, inv, r, rows,
             products=None):
    """Out [T, H] and the three grouped products (gate, up, down) over the
    first `rows` of the T * k sorted choice rows: the dispatch, the MLP
    (Pallas kernels on a TPU place, `lax.ragged_dot` elsewhere) and the
    combine. `rows` is static; every row a held expert received must lie
    among them (`_held_rows_take`). Given `products` (the backward op
    hands over what the forward left) no product is computed again: the
    kernels then run for the gradients alone."""
    from ..parallel import row_sum
    from ..parallel.grouped import grouped_mlp

    T, top_k = top_p.shape
    order = _first_rows(order, rows)
    if products is not None:
        # DownOut keeps all the rows
        products = (*products[:2], _first_rows(products[2], rows))
    by_token = None
    with jax.named_scope(DISPATCH):
        if row_sum.on_tpu() and row_sum.takes_choices(
                T, top_k, x.shape[1], rows, x.dtype):
            # the sort that the combine and the dispatch's backward share
            by_token = _by_token(order, jnp.sum(held_counts), T, top_k,
                                 x.shape[1])
        xs = _dispatch(x, order, inv, top_k, by_token)      # [rows, H]
    ys, a, b = grouped_mlp(xs, gate, up, down, held_counts, products,
                           not r.all_held, r.activation)
    with jax.named_scope(COMBINE):
        if r.all_held:
            y = _unsort(ys, order, inv).reshape(T, top_k, -1)
            o = jnp.einsum("tkh,tk->th", y.astype(F32),
                           top_p).astype(x.dtype)
        else:
            o = _combine(ys, top_p, order, inv, by_token)
    return o, (a, b, ys)


def _held_rows_take(r, n_rows, n_experts, held_counts, body):
    """`body(B)` where the held experts' rows fit the layer's row bound B,
    `body(n_rows)` in a step where they do not, chosen on the device; with
    no bound below n_rows (every expert held, or so large a share)
    `body(n_rows)` alone and no `cond`. `body` gives results of the same
    shapes for either count."""
    bound = row_bound(n_rows, r.held, n_experts)
    if bound >= n_rows:
        return body(n_rows)
    return lax.cond(jnp.sum(held_counts) <= bound,
                    lambda: body(bound), lambda: body(n_rows))


def _moe_ffn(x, router, bias, gate, up, down, r, router_x=None):
    """The op's six outputs and the three grouped products' results
    (gate, up, down; rows in expert order) as the op leaves them: where
    the layer has a row bound B below its T * k rows (`row_bound`), gate
    and up of B rows and down of all T * k, zero past the held experts'
    rows. `router_x`: the rows the router reads where they are not x."""
    (top_p, aux, z), (top_e, counts, held_counts, order, inv) = _route(
        x if router_x is None else router_x, router, bias, r)
    n_rows, E = order.shape[0], router.shape[1]
    bound = row_bound(n_rows, r.held, E)

    def body(rows):
        o, (a, b, ys) = _experts(x, top_p, gate, up, down, held_counts,
                                 order, inv, r, rows)
        with jax.named_scope(COMBINE):
            if rows < n_rows:
                ys = jnp.concatenate(
                    [ys, jnp.zeros((n_rows - rows, ys.shape[1]), ys.dtype)])
            return o, (_first_rows(a, bound), _first_rows(b, bound), ys)

    o, products = _held_rows_take(r, n_rows, E, held_counts, body)
    return ((o, aux, z, top_e, counts, jnp.sum(held_counts).reshape(1)),
            products)


_MOE_INPUTS = ("X", "Router", "Bias", "Gate", "Up", "Down")
_MOE_TRAINED = tuple(s for s in _MOE_INPUTS if s != "Bias")
# the rows the router reads, where the program names another variable than
# X for them
_ROUTER_INPUT = "RouterInput"
_MOE_PRODUCTS = ("GateOut", "UpOut", "DownOut")


@register_op("moe_ffn")
def moe_ffn_op(ctx, ins, attrs):
    """X [T, H], Router [H, E], Gate / Up [E', H, F], Down [E', F, H] ->
    Out_t = sum over the top_k experts e of w_te * Down_e(silu(Gate_e x_t)
    * Up_e x_t); AuxLoss [1], ZLoss [1], ExpertIds [T, top_k],
    TokensPerExpert [E], RowsHeld [1]. Attributes, defaults OLMoE's:
    `score_func` softmax | sigmoid over the router's E logits; the chosen
    are the top_k by score, plus Bias [E] where that input is given (it
    takes no gradient; beside sigmoid scores it is added to the scores,
    beside softmax scores to the logits, where a step of usable size does
    not swamp scores near 1 / E); w is the chosen scores, divided by their
    sum (plus `norm_eps`, 1e-20 unless given) with `norm_topk`, times
    `routed_scale`. `activation` silu | relu
    is what gates an expert: silu(Gate_e x) or relu(Gate_e x) times Up_e x;
    `activation` relu2 is an UN-GATED expert, Down_e(relu(Up_e x)^2): the
    op then has no Gate input and leaves no GateOut (two stacked matrices
    a layer, six grouped kernels a step where a gated layer runs nine).
    RouterInput [T, H], where given, is what the router scores INSTEAD of
    X (a router placed before attention reads the layer's input, the
    experts the normed state after it): the routing then depends on no
    output of the ops between the two variables, X's gradient is the
    experts' alone and RouterInput's the router's alone. Without it the
    router reads X and X's gradient is the sum, as it has always been.
    The layer holds the E' =
    `held_experts` experts from `first_expert` on (0: all E): a chosen
    expert it does not hold adds nothing to Out, the router still scores
    all E and TokensPerExpert counts all E; RowsHeld is how many of the T
    * top_k rows the held experts received. Tokens are sorted by expert,
    the held ones first, and the three products are grouped over the rows
    routed to them (`parallel/grouped.py: grouped_mlp`): no capacity, no
    dropped token, no padding to a per-expert size.

    A layer that holds a share of its experts moves a BOUNDED number of
    rows around its products: B = `row_bound`(T * top_k, E', E), twice
    the rows an even load would send it, from the shapes alone. The
    dispatch gathers the first B sorted rows, the products and their
    epilogues run over the RowsHeld <= B of them, and the combine reads a
    table of B rows (a choice whose row lies past B adds nothing: it is
    no held expert's). In a step where RowsHeld > B (`lax.cond`, from the
    routing just computed) the same body runs over all T * top_k rows:
    nothing is dropped, and every output is what the full-size path
    gives for every RowsHeld in [0, T * top_k]. A layer that holds every
    expert has no bound and no `cond`.

    GateOut, UpOut and DownOut are the products as computed, kept for
    the backward op. Every expert held: [T * top_k, F], [T * top_k, H].
    A share held: DownOut [T * top_k, H] is zero past the rows the held
    experts received (its rows that are not all zero are RowsHeld, in
    either branch); GateOut and UpOut are [B, F] and hold past those rows
    whatever their buffers held on a TPU place, and after an overflow
    step only their first B rows, which the backward op does not read:
    it computes the products again over all the rows."""
    args = [first(ins, s) for s in _MOE_INPUTS]
    (o, aux, z, ids, counts, rows), products = _moe_ffn(
        *args, Routing(attrs, args[1].shape[1]),
        first(ins, _ROUTER_INPUT))
    return out(Out=o, AuxLoss=aux, ZLoss=z, ExpertIds=ids,
               TokensPerExpert=counts, RowsHeld=rows,
               **{s: p for s, p in zip(_MOE_PRODUCTS, products)
                  if p is not None})


set_stop_gradient_outputs(
    "moe_ffn", ["ExpertIds", "TokensPerExpert", "RowsHeld", *_MOE_PRODUCTS])


@register_grad_maker("moe_ffn")
def _moe_ffn_grad_maker(op, gout, gin):
    """Hand-written for the reason `causal_attention`'s is: the generic
    vjp evaluates the forward again, XLA does not merge two Mosaic calls,
    and the three forward kernels would run twice a step. The backward op
    takes the products as the forward left them."""
    inputs = {s: op.input(s) for s in (*_MOE_INPUTS, _ROUTER_INPUT)
              if op.input(s)}
    inputs.update({s: op.output(s) for s in _MOE_PRODUCTS if op.output(s)})
    for s in ("Out", "AuxLoss", "ZLoss"):
        if any(gout.get(s) or []):
            inputs[s + "@GRAD"] = [x or "" for x in gout[s]]
    return [dict(
        type="moe_ffn_grad", inputs=inputs,
        outputs={s + "@GRAD": list(names) for s, names in gin.items()
                 if s != "Bias"},
        attrs={k: v for k, v in op.attrs.items() if k != "op_role_var"})]


@register_op("moe_ffn_grad")
def moe_ffn_grad_op(ctx, ins, attrs):
    """The vjp of `moe_ffn`'s body at the forward's saved products (an op
    built without them computes them here): the router's part once, the
    experts' part over the rows the forward op took, chosen as it chose
    (the bounded rows from the saved products; after an overflow all the
    rows, the products computed again)."""
    x, router, gate, up, down = (first(ins, s) for s in _MOE_TRAINED)
    router_x = first(ins, _ROUTER_INPUT)
    r = Routing(attrs, router.shape[1])
    products = tuple(first(ins, s) for s in _MOE_PRODUCTS)
    # an un-gated expert ("relu2") has no Gate and leaves no GateOut
    if any(p is None for p in products[gate is None:]):
        products = None
    differentiated = jax.named_scope(_VJP)
    (top_p, aux, z), route_vjp, (_, _, held_counts, order, inv) = jax.vjp(
        differentiated(
            lambda x, router: _route(x, router, first(ins, "Bias"), r)),
        x if router_x is None else router_x, router, has_aux=True)
    d_o, d_aux, d_z = (
        jnp.zeros_like(o) if g is None else g.astype(o.dtype).reshape(o.shape)
        for o, g in zip((x, aux, z), (first(ins, s + "@GRAD")
                                      for s in ("Out", "AuxLoss", "ZLoss"))))

    def gradients(rows):
        # the saved gate and up products are of the rows the forward op
        # took: after an overflow they are computed again
        saved = products if products and products[1].shape[0] == rows \
            else None
        return jax.vjp(
            differentiated(
                lambda *a: _experts(*a, held_counts, order, inv, r, rows,
                                    saved)[0]),
            x, top_p, gate, up, down)[1](d_o)

    d_x, d_top_p, *d_weights = _held_rows_take(
        r, order.shape[0], router.shape[1], held_counts, gradients)
    d_x_routed, d_router = route_vjp((d_top_p, d_aux, d_z))
    grads = {s: g for s, g in zip(_MOE_TRAINED, (d_x, d_router, *d_weights))
             if g is not None}
    if router_x is None:
        with jax.named_scope(COMBINE):
            grads["X"] = d_x + d_x_routed
    else:
        # the router's term goes to the variable the router read, the
        # experts' alone to X
        grads[_ROUTER_INPUT] = d_x_routed
    return out(**{s + "@GRAD": g for s, g in grads.items()})


# ------------------------------------------------------- mhc_mix, mhc_update
def sinkhorn(m, iters, eps):
    """`iters` rounds of row then column normalisation of the positive
    [..., n, n] matrices m, `eps` in the denominators: towards the doubly
    stochastic matrix with m's pattern (Sinkhorn & Knopp, 1967)."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


_MHC_PARAMS = ("PhiPre", "PhiPost", "PhiRes", "Alpha", "BPre", "BPost",
               "BRes")


def _three_bf16(w):
    """A float32 matrix as three bfloat16 ones side by side (the value,
    what rounding it lost, what rounding that lost): their sum carries 24
    bits, so a product of bfloat16 rows with them, accumulated in float32
    and summed over the three, is the product with the float32 matrix. The
    rows are never widened: a [T, n * C] state in float32 is twice the
    state."""
    parts, rest = [], w.astype(jnp.float32)
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    return jnp.concatenate(parts, axis=1)


def _sum_of_three(p):
    k = p.shape[1] // 3
    return p[:, :k] + p[:, k:2 * k] + p[:, 2 * k:]


def _exact_dot(a, b, dims):
    """dot_general(a, b) to float32's precision, float32 out; b's free
    dimension is its last. Where a is bfloat16 and b float32 (the state
    under AMP against the mixers' float32 matrices, or against their
    float32 gradients) a stays as it is and b is `_three_bf16`; otherwise
    one product at the highest precision in the mixers' own dtype
    (float32, or bfloat16 in a study one precision down)."""
    if (a.dtype == jnp.bfloat16 and b.dtype == jnp.float32
            and F32 == jnp.float32):
        return _sum_of_three(lax.dot_general(
            a, _three_bf16(b), dims, preferred_element_type=jnp.float32))
    return lax.dot_general(a.astype(F32), b.astype(F32), dims,
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _mixers(proj, inv_rms, alpha, b_pre, b_post, b_res, n, opts):
    """The per-token coefficients from the raw projections [T, 2n + n*n]
    and the state's inverse rms [T, 1]: HPre [T, n], HPost [T, n], HRes
    [T, n, n]; a few values a token, all in the mixers' dtype."""
    eps, iters, lo, hi = opts
    proj = proj.astype(F32) * inv_rms.astype(F32)
    h_pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + b_pre)
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n] + b_post)
    res = jnp.clip(alpha[2] * proj[:, 2 * n:] + b_res, lo, hi)
    h_res = sinkhorn(jnp.exp(res).reshape(-1, n, n), iters, eps)
    return h_pre, h_post, h_res


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _mhc_mix(x, phi, alpha, b_pre, b_post, b_res, opts):
    return _mhc_mix_fwd(x, phi, alpha, b_pre, b_post, b_res, opts)[0]


def _mhc_mix_fwd(x, phi, alpha, b_pre, b_post, b_res, opts):
    """x [n, T, C] (the streams are the slow dimension: each is a plain
    [T, C] array to the compiler, where [T, n, C] put 4 rows in tiles of
    8 or 16), phi [n, C, 2n + n*n]."""
    n, T, C = x.shape
    mean_sq = sum(jnp.sum(jnp.square(x[i].astype(F32)), axis=-1,
                          keepdims=True) for i in range(n)) / (n * C)
    inv_rms = lax.rsqrt(mean_sq + opts[0])
    # the norm is one factor a row: applied to the 2n + n * n projections,
    # not to the n * C values
    proj = sum(_exact_dot(x[i], phi[i], (((1,), (0,)), ((), ())))
               for i in range(n))
    h_pre, h_post, h_res = _mixers(proj, inv_rms, alpha, b_pre, b_post,
                                   b_res, n, opts)
    u = sum(h_pre[:, i, None] * x[i].astype(F32) for i in range(n))
    return ((u.astype(x.dtype), h_post.astype(jnp.float32),
             h_res.astype(jnp.float32)),
            (x, phi, alpha, b_pre, b_post, b_res, proj, inv_rms))


def _mhc_mix_bwd(opts, saved, cots):
    """By hand, so that the state is read in its own dtype and nothing of
    its size is written but its gradient: the coefficients' part is the
    vjp of `_mixers` on a few values a token; a stream's gradient is one
    elementwise pass over HPre_i dU, the projections' gradient times
    Phi_i^T and the rms's share of x_i."""
    x, phi, alpha, b_pre, b_post, b_res, proj, inv_rms = saved
    d_u, d_post, d_res = cots
    n, T, C = x.shape
    (h_pre, _, _), small_vjp = jax.vjp(
        lambda *a: _mixers(*a, n, opts), proj, inv_rms, alpha.astype(F32),
        b_pre.astype(F32), b_post.astype(F32), b_res.astype(F32))
    d_uf = d_u.astype(F32)
    d_pre = jnp.stack([jnp.sum(d_uf * x[i].astype(F32), axis=-1)
                       for i in range(n)], axis=1)
    d_proj, d_inv, d_alpha, d_bpre, d_bpost, d_bres = small_vjp(
        (d_pre.astype(h_pre.dtype), d_post.astype(F32), d_res.astype(F32)))
    d_proj = d_proj.astype(jnp.float32)
    # d inv_rms / d x = -inv_rms^3 x / (n C)
    of_rms = (-d_inv.astype(jnp.float32) * inv_rms.astype(jnp.float32) ** 3
              / (n * C))
    d_x, d_phi = [], []
    for i in range(n):
        d_phi.append(_exact_dot(x[i], d_proj, (((0,), (0,)), ((), ()))))
        through = lax.dot_general(
            d_proj.astype(x.dtype), phi[i].astype(x.dtype),
            (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST)
        d_x.append((h_pre.astype(jnp.float32)[:, i, None] * d_uf
                    + through.astype(jnp.float32)
                    + of_rms * x[i].astype(jnp.float32)).astype(x.dtype))
    return (jnp.stack(d_x), jnp.stack(d_phi).astype(phi.dtype),
            d_alpha.astype(alpha.dtype), d_bpre.astype(b_pre.dtype),
            d_bpost.astype(b_post.dtype), d_bres.astype(b_res.dtype))


_mhc_mix.defvjp(_mhc_mix_fwd, _mhc_mix_bwd)


@register_op("mhc_mix")
def mhc_mix_op(ctx, ins, attrs):
    """The three mixers of a residual path of n streams (Xie et al., mHC:
    Manifold-Constrained Hyper-Connections, arXiv:2512.24880) before one
    sublayer. X [n, T, C], stream-major; with x~ = RMSNorm over all n * C
    values of a token (no learned scale, `epsilon`), laid side by side
    stream after stream:

        HPre  [T, n]    = sigmoid(Alpha[0] * x~ PhiPre + BPre)
        HPost [T, n]    = 2 sigmoid(Alpha[1] * x~ PhiPost + BPost)
        HRes  [T, n, n] = Sinkhorn(exp(clip(Alpha[2] * mat(x~ PhiRes) +
                          BRes, clamp_min, clamp_max)))
        U     [T, C]    = sum_i HPre_i x_i      (the sublayer's input)

    PhiPre, PhiPost [n * C, n], PhiRes [n * C, n * n], Alpha [3], BPre,
    BPost [n], BRes [n * n]. Everything but U in float32 whatever X's
    dtype (the projections to float32's precision, `_exact_dot`: a
    per-token matrix that multiplies the whole state); U in X's dtype."""
    x = first(ins, "X")
    phi_pre, phi_post, phi_res, alpha, b_pre, b_post, b_res = (
        first(ins, s) for s in _MHC_PARAMS)
    n, _, C = x.shape
    opts = (float(attrs.get("epsilon", 1e-6)),
            int(attrs.get("sinkhorn_iters", 20)),
            float(attrs.get("clamp_min", -30.0)),
            float(attrs.get("clamp_max", 30.0)))
    phi = jnp.concatenate([phi_pre, phi_post, phi_res], axis=1)
    u, h_post, h_res = _mhc_mix(x, phi.reshape(n, C, -1), alpha, b_pre,
                                b_post, b_res, opts)
    return out(U=u, HPost=h_post, HRes=h_res)


@register_op("mhc_expand")
def mhc_expand_op(ctx, ins, attrs):
    """X [T, C] -> Out [n, T, C]: the n streams of a residual path start
    as copies of the embedding (attr `streams`)."""
    x = first(ins, "X")
    return out(Out=jnp.broadcast_to(x[None], (int(attrs["streams"]),)
                                    + x.shape))


@jax.custom_vjp
def _mhc_update(x, h_res, h_post, y):
    return _mhc_update_fwd(x, h_res, h_post, y)[0]


def _mhc_update_fwd(x, h_res, h_post, y):
    n = x.shape[0]
    yf = y.astype(F32)
    h_res, h_post = h_res.astype(F32), h_post.astype(F32)
    o = jnp.stack([
        sum(h_res[:, i, j, None] * x[j].astype(F32) for j in range(n))
        + h_post[:, i, None] * yf for i in range(n)])
    return o.astype(x.dtype), (x, h_res, h_post, y)


def _mhc_update_bwd(saved, g):
    """By hand: each stream a plain [T, C] pass. Through the generic vjp
    of the forward the step spent 2.5 ms an update where this takes 0.2
    (v5e, 4096 x 4 x 3584 bf16; tools/mhc_sweep.py, PERF.md PR 30)."""
    x, h_res, h_post, y = saved
    n = x.shape[0]
    gf = [g[i].astype(F32) for i in range(n)]
    xf = [x[j].astype(F32) for j in range(n)]
    yf = y.astype(F32)
    d_x = jnp.stack([sum(h_res[:, i, j, None] * gf[i] for i in range(n))
                     for j in range(n)]).astype(x.dtype)
    d_y = sum(h_post[:, i, None] * gf[i] for i in range(n)).astype(y.dtype)
    d_res = jnp.stack([jnp.stack([jnp.sum(gf[i] * xf[j], axis=-1)
                                  for j in range(n)], axis=-1)
                       for i in range(n)], axis=1)
    d_post = jnp.stack([jnp.sum(gf[i] * yf, axis=-1) for i in range(n)],
                       axis=-1)
    return (d_x, d_res.astype(jnp.float32), d_post.astype(jnp.float32), d_y)


_mhc_update.defvjp(_mhc_update_fwd, _mhc_update_bwd)


@register_op("mhc_update")
def mhc_update_op(ctx, ins, attrs):
    """The residual path's step around one sublayer: X [n, T, C], HRes [T,
    n, n], HPost [T, n], Y [T, C] (the sublayer's output) -> Out_i = sum_j
    HRes_ij X_j + HPost_i Y, summed in float32, in X's dtype."""
    return out(Out=_mhc_update(*(first(ins, s)
                                 for s in ("X", "HRes", "HPost", "Y"))))


def _kernels_take(op, block, whole_mlp=False):
    """Whether `grouped_dot` takes this `moe_ffn`'s products to the Pallas
    kernels, from the shapes the program states (rows it leaves open, a
    batch dimension of -1, are taken to fit); `whole_mlp`: whether
    `grouped_mlp` runs them with the element-wise work between the
    products in their epilogues."""
    from ..parallel import grouped

    # an un-gated layer ("relu2") has no Gate; Up has its shape
    x, gate = (block.vars[op.input(s)[0]].shape for s in ("X", "Up"))
    rows = x[0] * int(op.attrs.get("top_k", 1)) if x[0] > 0 else None
    takes = grouped.mlp_takes if whole_mlp else grouped.takes
    return takes(rows, gate[1], gate[2])


def _holds_a_share(op, block):
    """A `moe_ffn` that holds some of the experts its router scores."""
    return bool(op.attrs.get("held_experts", 0)) and (
        op.attrs["held_experts"]
        < block.vars[op.input("Router")[0]].shape[1])


def _has_row_bound(op, block):
    """A `moe_ffn` that holds a share of its experts and works on fewer
    rows than top_k x tokens (`row_bound`; rows the program leaves open
    are taken to be many)."""
    if not _holds_a_share(op, block):
        return False
    tokens = block.vars[op.input("X")[0]].shape[0]
    rows = tokens * int(op.attrs.get("top_k", 1)) if tokens > 0 else 2 ** 30
    return row_bound(rows, op.attrs["held_experts"], block.vars[
        op.input("Router")[0]].shape[1]) < rows


def _sums_rows_by_token(op, block):
    """A `moe_ffn` with a row bound whose bounded sums (the combine, the
    dispatch's backward) the row-tile kernel takes by token
    (`parallel/row_sum.py: takes_choices`; tokens the program leaves open,
    a batch dimension of -1, are taken to be 1024, which every row tile
    divides and whose bound fits whatever the held share)."""
    from ..parallel import row_sum

    if not _has_row_bound(op, block):
        return False
    x = block.vars[op.input("X")[0]]
    T, k = x.shape[0] if x.shape[0] > 0 else 1024, int(op.attrs["top_k"])
    bound = row_bound(T * k, op.attrs["held_experts"], block.vars[
        op.input("Router")[0]].shape[1])
    low = amp.compute_dtype() if amp.is_enabled() else x.dtype
    return row_sum.takes_choices(T, k, x.shape[1], bound, low)


def _scan_chunks(op, block):
    """The chunks an `ssd_scan` walks a step: rows x ceil(seq_len / chunk)
    (rows the program leaves open are taken to be one)."""
    tokens = block.vars[op.input("X")[0]].shape[0]
    seq_len, chunk = int(op.attrs["seq_len"]), int(op.attrs["chunk"])
    return max(1, tokens // seq_len) * -(-seq_len // chunk)


def _has_window(op, block):
    return bool(op.attrs.get("window", 0))


def _has_head_groups(op, block):
    """A `causal_attention` (or its grad) whose K has fewer heads than its
    Q."""
    q, k = (block.vars[op.input(s)[0]].shape for s in ("Q", "K"))
    return q[2] != k[2]


def _grad_by_row_tiles(op, block):
    """A dense `lookup_table_grad` whose rows the row-tile kernel sums
    (`parallel/row_sum.py: takes`; ids the program leaves open, a batch
    dimension of -1, are taken to fit; ragged ids are not)."""
    from ..parallel import row_sum

    w, ids = (block.vars[op.input(s)[0]] for s in ("W", "Ids"))
    if op.attrs.get("is_sparse", False) or ids.lod_level or len(w.shape) != 2:
        return False
    n_ids = math.prod(ids.shape) if min(ids.shape) > 0 else None
    return row_sum.takes(w.shape[0], w.shape[1], n_ids, w.dtype)


def _conv_kernel_takes(op, block):
    """Whether the Pallas kernels take this `short_conv` (or its grad),
    from the shapes the program states (tokens it leaves open, a batch
    dimension of -1, are taken to be whole rows)."""
    from ..parallel import short_conv as kernels

    if op.attrs.get("gating"):
        return False
    x, w = (block.vars[op.input(s)[0]] for s in ("X", "Filter"))
    seq_len = int(op.attrs["seq_len"])
    tokens = x.shape[0] if x.shape[0] > 0 else seq_len
    low = amp.compute_dtype() if amp.is_enabled() else x.dtype
    return kernels.takes(tokens, x.shape[1] // 3, seq_len, w.shape[0], low)


def _conv_is_silu(op, block):
    """A `short_conv` (or its grad) of the variant silu(conv(x))."""
    return op.attrs.get("gating") == SILU


def _silu_kernel_takes(op, block):
    """Whether the Pallas kernels of the variant take this `short_conv` (or
    its grad), from the shapes the program states."""
    from ..parallel import short_conv as kernels

    if not _conv_is_silu(op, block) or op.input("Bias"):
        return False
    x, w = (block.vars[op.input(s)[0]] for s in ("X", "Filter"))
    seq_len = int(op.attrs["seq_len"])
    tokens = x.shape[0] if x.shape[0] > 0 else seq_len
    low = amp.compute_dtype() if amp.is_enabled() else x.dtype
    return kernels.silu_takes(tokens, x.shape[1], seq_len, w.shape[0], low)


def _gated_norm_kernel_takes(op, block):
    """Whether the Pallas kernels of `parallel/gated_norm.py` take this
    `gated_rms_norm` (or its grad), from the shapes the program states
    (tokens it leaves open, a batch dimension of -1, are taken to be whole
    blocks)."""
    from ..parallel import gated_norm

    x, gate = (block.vars[op.input(s)[0]] for s in ("X", "Gate"))
    tokens = x.shape[0] if x.shape[0] > 0 else gated_norm._BLOCKS[0]
    low = amp.compute_dtype() if amp.is_enabled() else x.dtype
    return gate.dtype == x.dtype and gated_norm.fits(
        (tokens,) + tuple(x.shape[1:]), low)


def _delta_kernel_takes(op, block):
    """Whether the Pallas kernels of `parallel/delta_parts.py` take this
    `gated_delta_rule` (or its grad), from the shapes the program states."""
    qkv = block.vars[op.input("QKV")[0]]
    tokens = qkv.shape[0] if qkv.shape[0] > 0 else int(op.attrs["seq_len"])
    low = amp.compute_dtype() if amp.is_enabled() else qkv.dtype
    return _delta_shapes_taken(tokens, low, op.attrs)


def _ssd_kernel_takes(op, block):
    """Whether the Pallas kernels of `parallel/ssd_parts.py` take this
    `ssd_scan` (or its grad), from the shapes the program states."""
    x = block.vars[op.input("X")[0]]
    tokens = x.shape[0] if x.shape[0] > 0 else int(op.attrs["seq_len"])
    low = amp.compute_dtype() if amp.is_enabled() else x.dtype
    return _ssd_shapes_taken(tokens, low, op.attrs)


def _index_loss_kernel_takes(op, block):
    """Whether the Pallas kernels of `parallel/index_loss.py` take this
    `indexer_loss`, from the shapes the program states."""
    from ..parallel import index_loss

    q, k, q_i = (block.vars[op.input(s)[0]] for s in ("Q", "K", "QI"))
    low = amp.compute_dtype() if amp.is_enabled() else q.dtype
    return index_loss.takes(q.shape[1], q.shape[2], k.shape[2], q.shape[3],
                            *q_i.shape[2:], low)


def _index_select_kernel_takes(op, block):
    """Whether the Pallas kernel of `parallel/index_select.py` takes the
    blocks of queries `sparse_index.select` hands `select_rows` for this
    `indexer_select`, from the shapes the program states."""
    from ..parallel import index_select, sparse_index

    S = block.vars[op.input("QI")[0]].shape[1]
    return index_select.takes(sparse_index._blocked(S, sparse_index.BLOCK)[0],
                              S, int(op.attrs["topk"]))


def _sparse_grad_is_one_kernel(op, block):
    """Whether this `sparse_attention_grad` runs as ONE masked flash kernel
    (`flash.fused_backward_fits`, which the dispatch asks too), from the
    shapes the program states."""
    from ..parallel import flash

    q, k, v = (block.vars[op.input(s)[0]] for s in ("Q", "K", "V"))
    return flash.fused_backward_fits(q.shape[1], k.shape[1], q.shape[3],
                                     v.shape[3])


def _reads_a_tied_table(op, block):
    """A `lookup_table` whose W a `matmul` of the block reads transposed as
    its Y, or such a `matmul`: one parameter [V, C] that is the embedding
    and the head (its gradient is the sum of the two readers')."""
    def readers(t, slot):
        return {o.input(slot)[0] for o in block.ops if o.type == t
                and (t != "matmul" or o.attrs.get("transpose_Y"))}

    if op.type == "matmul":
        return bool(op.attrs.get("transpose_Y")) and op.input("Y")[0] \
            in readers("lookup_table", "W")
    return op.input("W")[0] in readers("matmul", "Y")


def _flash_grid(op, block, backward=False):
    """(S, block_q, block_k, window or None) the flash kernels of a
    `causal_attention` / `causal_attention_grad` op are built with: from
    the row length the program states and `flash_blocks`, as the op's
    lowering calls them (a band as long as the row is the triangle)."""
    from ..parallel import flash

    S = int(block.vars[op.input("Q")[0]].shape[1])
    window = int(op.attrs.get("window", 0)) or None
    bq, bk = flash.normalize_blocks(**flash_blocks(window, backward),
                                    Sq=S, Sk=S)
    return S, bq, bk, window if window and window < S else None


def window_blocks(program):
    """(visited, of a full causal grid): score blocks the flash kernels of
    the program's window layers compute a step, forward, dK/dV and dQ,
    against those the same kernels with the same blocks would compute over
    the whole triangle. Static, from the shapes and the blocks the kernels
    are built with (`_flash_grid`, `flash.blocks_visited`); (0, 0) for a
    program without a window layer."""
    from ..parallel import flash

    visited = whole = 0
    for block in program.blocks:
        for op in block.ops:
            if op.type not in ("causal_attention", "causal_attention_grad") \
                    or not _has_window(op, block):
                continue
            back = op.type.endswith("_grad")
            S, bq, bk, window = _flash_grid(op, block, back)
            heads = int(block.vars[op.input("Q")[0]].shape[2])
            n = heads * (2 if back else 1)          # dK/dV and dQ
            visited += n * flash.blocks_visited(S, S, bq, bk, window)
            whole += n * flash.blocks_visited(S, S, bq, bk)
    return visited, whole


def _selection_pairs(op, block):
    """(chosen, causal) (query, key) pairs of an `indexer_select` op a
    step, from the shapes the program states: sum_t min(t + 1, topk) and S
    (S + 1) / 2 a row of S tokens (a batch dimension the program leaves
    open, -1, counts as one row)."""
    B, S = block.vars[op.input("QI")[0]].shape[:2]
    k = min(int(op.attrs["topk"]), S)
    rows = max(int(B), 1)
    return (rows * (k * (k + 1) // 2 + (S - k) * k),
            rows * (S * (S + 1) // 2))


def _forward_blocks(count):
    """`flash.<count>` (`blocks_visited`, `blocks_masked`) of the grid the
    forward kernel of a `causal_attention` op runs a head."""
    def of(op, block):
        from ..parallel import flash

        S, bq, bk, window = _flash_grid(op, block)
        return getattr(flash, count)(S, S, bq, bk, window)

    return of


# (op type, counter, whether the lowering exists on a TPU place only,
# how much each of those ops counts: 1 when None, else what this returns,
# a truth value or a number)
_LOWERED = (("moe_ffn", "moe_ffn_grouped", False, None),
            ("moe_ffn", "grouped_matmul_kernel", True, _kernels_take),
            ("moe_ffn", "grouped_mlp_epilogues", True,
             functools.partial(_kernels_take, whole_mlp=True)),
            ("causal_attention", "flash_attention", True, None),
            ("causal_attention_grad", "flash_attention_bwd", True, None),
            ("moe_ffn", "moe_ffn_held_experts", False, _holds_a_share),
            ("causal_attention", "flash_attention_window", True,
             _has_window),
            ("causal_attention", "flash_attention_head_groups", True,
             _has_head_groups),
            ("moe_ffn", "moe_ffn_row_bound", False, _has_row_bound),
            ("moe_ffn", "moe_ffn_kept_copies", False,
             lambda op, block: amp.reads_kept_copies(op)),
            ("moe_ffn", "moe_ffn_router_input", False,
             lambda op, block: bool(op.input(_ROUTER_INPUT))),
            ("moe_ffn", "moe_ffn_relu", False,
             lambda op, block: op.attrs.get("activation") == "relu"),
            ("lookup_table_grad", "lookup_table_grad_tiled", True,
             _grad_by_row_tiles),
            ("moe_ffn", "moe_ffn_rows_by_token", True, _sums_rows_by_token),
            ("short_conv", "short_conv_gated", False,
             lambda op, block: not _conv_is_silu(op, block)),
            ("short_conv_grad", "short_conv_grad_by_hand", False,
             lambda op, block: not _conv_is_silu(op, block)),
            ("short_conv", "short_conv_kernel", True, _conv_kernel_takes),
            ("short_conv_grad", "short_conv_grad_kernel", True,
             _conv_kernel_takes),
            ("lookup_table", "tied_table_lookup", False,
             _reads_a_tied_table),
            ("matmul", "tied_table_head", False, _reads_a_tied_table),
            ("causal_attention", "flash_fwd_masked_blocks", True,
             _forward_blocks("blocks_masked")),
            ("causal_attention", "flash_fwd_visited_blocks", True,
             _forward_blocks("blocks_visited")),
            ("short_conv", "short_conv_silu", False, _conv_is_silu),
            ("short_conv_grad", "short_conv_silu_grad_by_hand", False,
             _conv_is_silu),
            ("short_conv", "short_conv_silu_kernel", True,
             _silu_kernel_takes),
            ("short_conv_grad", "short_conv_silu_grad_kernel", True,
             _silu_kernel_takes),
            ("gated_delta_rule", "delta_rule_chunked", False, None),
            ("gated_delta_rule_grad", "delta_rule_grad_by_hand", False,
             None),
            ("gated_delta_rule", "delta_rule_kernel", True,
             _delta_kernel_takes),
            ("gated_delta_rule_grad", "delta_rule_grad_kernel", True,
             _delta_kernel_takes),
            ("causal_attention", "flash_attention_head_256", True,
             lambda op, block: block.vars[op.input("Q")[0]].shape[3] >= 256),
            ("indexer_select", "indexer_select_bisection", False, None),
            ("sparse_attention", "sparse_attention_plain", False, None),
            ("sparse_attention", "sparse_attention_kernel", True, None),
            ("sparse_attention_grad", "sparse_attention_grad_kernel", True,
             None),
            ("indexer_loss", "indexer_loss_with_grads", False, None),
            ("indexer_select", "sparse_attention_selected_pairs", False,
             lambda op, block: _selection_pairs(op, block)[0]),
            ("indexer_select", "sparse_attention_causal_pairs", False,
             lambda op, block: _selection_pairs(op, block)[1]),
            ("indexer_loss", "indexer_loss_kernel", True,
             _index_loss_kernel_takes),
            ("sparse_attention_grad", "sparse_attention_grad_fused", True,
             _sparse_grad_is_one_kernel),
            ("ssd_scan", "ssd_scan_chunked", False, None),
            ("ssd_scan_grad", "ssd_scan_grad_by_hand", False, None),
            ("ssd_scan", "ssd_scan_chunks", False, _scan_chunks),
            ("short_conv", "short_conv_silu_bias", False,
             lambda op, block: bool(op.input("Bias"))),
            ("moe_ffn", "moe_ffn_relu2", False,
             lambda op, block: op.attrs.get("activation") == "relu2"),
            ("ssd_scan", "ssd_scan_kernel", True, _ssd_kernel_takes),
            ("ssd_scan_grad", "ssd_scan_grad_kernel", True,
             _ssd_kernel_takes),
            ("gated_rms_norm", "gated_norm_one_op", False, None),
            ("gated_rms_norm_grad", "gated_norm_grad_by_hand", False, None),
            ("gated_rms_norm", "gated_norm_kernel", True,
             _gated_norm_kernel_takes),
            ("gated_rms_norm_grad", "gated_norm_grad_kernel", True,
             _gated_norm_kernel_takes),
            ("indexer_select", "indexer_select_kernel", True,
             _index_select_kernel_takes))


def lowered_counts(program, device):
    """{counter: n} for the step spans and the registry (the newest first:
    its `gated_rms_norm` ops, `gated_norm_one_op`, and their hand-written
    grads, `gated_norm_grad_by_hand`; on a TPU place those whose shapes the
    Pallas kernels of parallel/gated_norm.py take count as
    `gated_norm_kernel` / `gated_norm_grad_kernel` too, the others are plain
    `jax.numpy`; its `ssd_scan` ops, `ssd_scan_chunked`: the chunked form, one scan over
    the chunks, with the chunks they walk a step, static,
    `ssd_scan_chunks`, and their grads, `ssd_scan_grad_by_hand`; on a TPU
    place those whose in-chunk work the Pallas kernels of
    parallel/ssd_parts.py take count as `ssd_scan_kernel` /
    `ssd_scan_grad_kernel` too, the others are plain `jax.numpy`; its
    `short_conv` ops of the "silu" variant that take a bias, `short_conv_silu_bias`, which lower as shifted
    multiply-adds on every place; its `moe_ffn` ops of un-gated experts
    relu(x U)^2 D, `moe_ffn_relu2`, two stacked matrices and six grouped
    kernels a step): `moe_ffn` ops of
    the program (each lowers through the grouped products; on a TPU place
    those whose shapes the Pallas grouped-matmul kernels take count as
    `grouped_matmul_kernel` too, and as `grouped_mlp_epilogues` where
    `grouped_mlp` runs them with SiLU * up, its backward and the sum of
    the two d xs products in their epilogues; those that hold a share of
    their experts as `moe_ffn_held_experts`, and as `moe_ffn_row_bound`
    where that share gives them a row bound below top_k x tokens:
    `row_bound`; those all three of whose expert weights the step reads
    from the low-precision copies their updates keep, not from a cast of
    the float32 masters, as `moe_ffn_kept_copies`: `amp.kept_copy`; those
    whose router reads another variable than `X` as `moe_ffn_router_input`,
    those whose experts are gated by ReLU as `moe_ffn_relu`; on a TPU place
    those whose bounded sums the row-tile kernel takes by token as
    `moe_ffn_rows_by_token`: `row_sum.takes_choices`) and,
    on a TPU place, its dense `lookup_table_grad` ops whose table the
    row-tile kernel writes (`lookup_table_grad_tiled`: `row_sum.takes`), its
    `causal_attention` ops (each lowers through the flash kernel; those
    with a window count as `flash_attention_window` too, those whose K has
    fewer heads than their Q as `flash_attention_head_groups`) and
    `causal_attention_grad` ops (each through the two backward kernels).
    Its `short_conv` ops (`short_conv_gated`) and `short_conv_grad` ops,
    each the hand-written backward (`short_conv_grad_by_hand`); on a TPU
    place those whose shapes the Pallas kernels of parallel/short_conv.py
    take count as `short_conv_kernel` / `short_conv_grad_kernel` too, the
    others lower as L shifted multiply-adds along the token axis; the
    two readers of a TIED table, a parameter a `lookup_table` reads as W
    and a `matmul` reads transposed as Y (`tied_table_lookup`,
    `tied_table_head`). On a TPU place the score blocks the forward kernels
    of its `causal_attention` ops visit a head and those of them that pay
    for the mask, summed over the ops (`flash_fwd_visited_blocks`,
    `flash_fwd_masked_blocks`: `flash.blocks_visited`, `.blocks_masked`).
    Its `short_conv` ops of the variant silu(conv(x)) and their grads
    (`short_conv_silu`, `short_conv_silu_grad_by_hand`; on a TPU place
    those whose shapes the variant's Pallas kernels take count as
    `short_conv_silu_kernel` / `short_conv_silu_grad_kernel` too, the
    others lower as L shifted multiply-adds), its `gated_delta_rule`
    ops (`delta_rule_chunked`: the chunked form, one scan over the chunks,
    on every place) and their grads (`delta_rule_grad_by_hand`; on a TPU
    place those whose shapes the Pallas kernels of parallel/delta_parts.py
    take, which then form the in-chunk quantities and their transpose,
    count as `delta_rule_kernel` / `delta_rule_grad_kernel` too, the others
    form them as batched products a head group at a time), and on a TPU
    place its `causal_attention` ops at heads of 256 or more
    (`flash_attention_head_256`). Its `indexer_select` ops
    (`indexer_select_bisection`: the exact top-k by bisection on the
    scores' bits, on every place; on a TPU place those whose blocks of
    queries the Pallas kernel of parallel/index_select.py takes count as
    `indexer_select_kernel` too, the others count in XLA passes over the
    whole block) with the (query, key) pairs they choose
    and the causal pairs they choose among a step, static
    (`sparse_attention_selected_pairs`, `sparse_attention_causal_pairs`),
    its `sparse_attention` ops (`sparse_attention_plain`, each; on a TPU
    place the flash kernels of parallel/flash.py under the mask take every
    one and its grad: `sparse_attention_kernel`,
    `sparse_attention_grad_kernel`; a grad whose three float32
    accumulators fit VMEM for the length of a row is ONE kernel that visits
    each score block once, `sparse_attention_grad_fused`:
    `flash.fused_backward_fits`) and its `indexer_loss` ops
    (`indexer_loss_with_grads`: the loss and its gradient in one pass; on
    a TPU place those whose shapes the Pallas kernels of
    parallel/index_loss.py take count as `indexer_loss_kernel` too, the
    others scan over blocks of queries).
    A program without them reports none. Kept on the program until that
    is mutated or the mixed-precision policy changes, like
    `bn_pool.count`."""
    memo = getattr(program, "_lm_lowered", None)
    key = (program._mutation, amp.is_enabled(), amp.compute_dtype())
    if memo is None or memo[0] != key:
        ops = [(op, b) for b in program.blocks for op in b.ops]
        memo = program._lm_lowered = (key, [
            sum(1 if which is None else int(which(op, b))
                for op, b in ops if op.type == t)
            for t, _, _, which in _LOWERED])
    return {name: n for n, (_, name, tpu_only, _) in zip(memo[1], _LOWERED)
            if n and (device.platform == "tpu" or not tpu_only)}
