"""NN compute ops: conv/pool/norm/softmax/losses/dropout.

Reference parity: operators/{conv,conv_transpose,pool,batch_norm,layer_norm,
softmax,cross_entropy,softmax_with_cross_entropy,sigmoid_cross_entropy_with_
logits,dropout,lrn,squared_l2_norm,squared_l2_distance,smooth_l1_loss,
huber_loss,hinge_loss,rank_loss,margin_rank_loss,log_loss,bilinear_interp,
prelu,row_conv,nce}_op.cc (+ cudnn variants — here XLA/MXU plays cuDNN's role).

Convs/matmuls run in NCHW with OIHW filters (reference layout); XLA relayouts
internally for the MXU. bf16 inputs accumulate in f32 via
preferred_element_type.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op, register_grad_maker, set_stop_gradient_outputs
from .bn_pool import PER_SAMPLE_SUMS
from .util import first, many, out


def _pref(x):
    # bf16 needs no explicit fp32 accumulation hint: the TPU MXU accumulates
    # bf16 products in fp32 natively, and an explicit preferred_element_type
    # breaks jax's conv/dot transpose rule under AMP (fp32 cotangent meets
    # bf16 operand in the transposed conv). Keep the hint only for fp16.
    return jnp.float32 if x.dtype == jnp.float16 else None


# ---------------------------------------------------------------------------
# Convolution family
# ---------------------------------------------------------------------------
def _conv_nd(x, w, strides, paddings, dilations, groups, data_format="NCHW"):
    dims = x.ndim - 2
    # filters stay OIHW in EVERY layout so parameters (and checkpoints)
    # are layout-independent; only the activation layout changes
    if dims == 2:
        spec = ("NHWC", "OIHW", "NHWC") if data_format == "NHWC" \
            else ("NCHW", "OIHW", "NCHW")
    else:
        spec = ("NCDHW", "OIDHW", "NCDHW")
    dn = lax.conv_dimension_numbers(x.shape, w.shape, spec)
    o = lax.conv_general_dilated(
        x,
        w.astype(x.dtype),
        window_strides=tuple(strides),
        padding=[(p, p) for p in paddings],
        rhs_dilation=tuple(dilations),
        dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=_pref(x),
    )
    return o.astype(x.dtype)


@register_op("conv2d")
def conv2d_op(ctx, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    return out(
        Output=_conv_nd(
            x,
            w,
            attrs.get("strides", [1, 1]),
            attrs.get("paddings", [0, 0]),
            attrs.get("dilations", [1, 1]),
            attrs.get("groups", 1),
            attrs.get("data_format", "NCHW"),
        )
    )


@register_op("depthwise_conv2d")
def depthwise_conv2d_op(ctx, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    a = dict(attrs)
    a["groups"] = x.shape[
        -1 if a.get("data_format", "NCHW") == "NHWC" else 1]
    return conv2d_op(ctx, ins, a)


@register_op("conv3d")
def conv3d_op(ctx, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    return out(
        Output=_conv_nd(
            x,
            w,
            attrs.get("strides", [1, 1, 1]),
            attrs.get("paddings", [0, 0, 0]),
            attrs.get("dilations", [1, 1, 1]),
            attrs.get("groups", 1),
        )
    )


@register_op("conv2d_transpose")
def conv2d_transpose_op(ctx, ins, attrs):
    """reference operators/conv_transpose_op.cc; filter layout IOHW."""
    x, w = first(ins, "Input"), first(ins, "Filter")
    strides = tuple(attrs.get("strides", [1, 1]))
    paddings = attrs.get("paddings", [0, 0])
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    # grad-of-conv formulation: conv_transpose(x, w) = conv^T
    kh = (w.shape[2] - 1) * dilations[0] + 1
    kw = (w.shape[3] - 1) * dilations[1] + 1
    pad = [
        (kh - 1 - paddings[0], kh - 1 - paddings[0]),
        (kw - 1 - paddings[1], kw - 1 - paddings[1]),
    ]
    w_flip = jnp.flip(w, axis=(2, 3))  # IOHW
    w_t = jnp.swapaxes(w_flip, 0, 1)  # -> OIHW with O=out channels
    dn = lax.conv_dimension_numbers(x.shape, w_t.shape, ("NCHW", "OIHW", "NCHW"))
    o = lax.conv_general_dilated(
        x,
        w_t.astype(x.dtype),
        window_strides=(1, 1),
        padding=pad,
        lhs_dilation=strides,
        rhs_dilation=dilations,
        dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=_pref(x),
    )
    return out(Output=o.astype(x.dtype))


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------
@register_op("pool2d")
def pool2d_op(ctx, ins, attrs):
    x = first(ins, "X")
    ptype = attrs.get("pooling_type", "max")
    ksize = list(attrs.get("ksize", [2, 2]))
    strides = list(attrs.get("strides", [1, 1]))
    paddings = list(attrs.get("paddings", [0, 0]))
    nhwc = attrs.get("data_format", "NCHW") == "NHWC"
    h_ax, w_ax = (1, 2) if nhwc else (2, 3)
    if attrs.get("global_pooling", False):
        ksize = [x.shape[h_ax], x.shape[w_ax]]
        paddings = [0, 0]
        strides = [1, 1]

    def spatial(hv, wv, rest=(1, 1)):
        return (rest[0], hv, wv, rest[1]) if nhwc \
            else (rest[0], rest[1], hv, wv)

    window = spatial(ksize[0], ksize[1])
    strides_ = spatial(strides[0], strides[1])
    pads = spatial((paddings[0], paddings[0]), (paddings[1], paddings[1]),
                   rest=((0, 0), (0, 0)))
    if attrs.get("ceil_mode", False):
        # extend right/bottom padding so the window count rounds up
        def extra(size, k, s, p):
            n = math.ceil((size + 2 * p - k) / s) + 1
            return max(0, (n - 1) * s + k - size - 2 * p)

        pads = spatial(
            (paddings[0], paddings[0] + extra(
                x.shape[h_ax], ksize[0], strides[0], paddings[0])),
            (paddings[1], paddings[1] + extra(
                x.shape[w_ax], ksize[1], strides[1], paddings[1])),
            rest=((0, 0), (0, 0)))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        o = lax.reduce_window(x, np.asarray(init, x.dtype), lax.max, window, strides_, pads)
    else:
        s = lax.reduce_window(x, np.asarray(0.0, x.dtype), lax.add, window, strides_, pads)
        if attrs.get("exclusive", True):
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, np.asarray(0.0, x.dtype), lax.add, window, strides_, pads)
            o = s / cnt
        else:
            o = s / (ksize[0] * ksize[1])
    return out(Out=o)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------
@register_op("batch_norm")
def batch_norm_op(ctx, ins, attrs):
    """reference operators/batch_norm_op.cc. Outputs Y + updated running
    stats; training grads flow through the batch statistics via vjp."""
    x = first(ins, "X")
    scale, bias = first(ins, "Scale"), first(ins, "Bias")
    mean, var = first(ins, "Mean"), first(ins, "Variance")
    momentum = attrs.get("momentum", 0.9)
    eps = attrs.get("epsilon", 1e-5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    layout = attrs.get("data_layout", "NCHW")
    axes = tuple(i for i in range(x.ndim) if i != (1 if layout == "NCHW" else x.ndim - 1))
    shape = [1] * x.ndim
    shape[1 if layout == "NCHW" else -1] = x.shape[1 if layout == "NCHW" else -1]

    s1 = None
    if is_test:
        m, v = mean, var
        saved_mean, saved_var = mean, var
        mean_out, var_out = mean, var
    else:
        xf = x.astype(jnp.float32)
        # one-pass statistics: E[x] and E[x^2] reduce the SAME read of
        # the activation, so XLA fuses both into a single HBM sweep —
        # jnp.var's E[(x-mean)^2] forces a second full read. Numerical
        # boundary: the naive difference form loses the variance to fp32
        # cancellation when |mean|/std exceeds ~2^12 — far outside
        # post-conv BN inputs (tests/test_ops_nn.py holds the supported
        # regime). Shifted variants that close that corner can no longer
        # share the stats read with the normalize path; they ran slower
        # on an older stack and have not been re-measured on the current
        # host.
        # (bn_pool.PER_SAMPLE_SUMS: Y is read by a global average pool;
        # the mean goes through the per-sample sums, which XLA emits from
        # the fusion that makes x, and mean_hw(Y) is algebra on them)
        if attrs.get(PER_SAMPLE_SUMS):
            s1 = jnp.sum(xf, axis=axes[1:])  # [N, C]
            hw = x.size // s1.size
            m = jnp.sum(s1, axis=0) / (s1.shape[0] * hw)
        else:
            m = jnp.mean(xf, axis=axes)
        msq = jnp.mean(jnp.square(xf), axis=axes)
        v = jnp.maximum(msq - jnp.square(m), 0.0)
        mean_out = mean * momentum + m * (1 - momentum)
        var_out = var * momentum + v * (1 - momentum)
        saved_mean, saved_var = m, v
    inv = lax.rsqrt(v.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - m.reshape(shape)) * inv.reshape(shape)
    y = y * scale.reshape(shape) + bias.reshape(shape)
    outs = out(
        Y=y.astype(x.dtype),
        MeanOut=mean_out,
        VarianceOut=var_out,
        SavedMean=saved_mean,
        SavedVariance=jax.lax.stop_gradient(inv),
    )
    if s1 is not None:
        outs["SampleSum"] = [s1]
        outs["PooledY"] = [(s1 / hw - m) * inv * scale + bias]
    return outs


set_stop_gradient_outputs("batch_norm", ["MeanOut", "VarianceOut", "SavedMean", "SavedVariance"])


@register_op("layer_norm")
def layer_norm_op(ctx, ins, attrs):
    x = first(ins, "X")
    scale, bias = first(ins, "Scale"), first(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=axes, keepdims=True)
    v = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - m) * lax.rsqrt(v + eps)
    feat_shape = [1] * begin + list(x.shape[begin:])
    if scale is not None:
        y = y * scale.reshape(feat_shape)
    if bias is not None:
        y = y + bias.reshape(feat_shape)
    return out(Y=y.astype(x.dtype), Mean=m.squeeze(), Variance=v.squeeze())


set_stop_gradient_outputs("layer_norm", ["Mean", "Variance"])


@register_op("lrn")
def lrn_op(ctx, ins, attrs):
    x = first(ins, "X")  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(padded[:, i : i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return out(Out=x / jnp.power(mid, beta), MidOut=mid)


set_stop_gradient_outputs("lrn", ["MidOut"])


# ---------------------------------------------------------------------------
# Softmax + losses
# ---------------------------------------------------------------------------
@register_op("softmax")
def softmax_op(ctx, ins, attrs):
    return out(Out=jax.nn.softmax(first(ins, "X"), axis=-1))


@register_op("cross_entropy")
def cross_entropy_op(ctx, ins, attrs):
    """reference operators/cross_entropy_op.cc: X is probabilities."""
    x, label = first(ins, "X"), first(ins, "Label")
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), axis=-1, keepdims=True)
    else:
        idx = label.reshape(label.shape[0], -1)[:, 0].astype(jnp.int32)
        p = jnp.take_along_axis(x, idx[:, None], axis=-1)
        loss = -jnp.log(jnp.maximum(p, 1e-20))
    return out(Y=loss)


@register_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy_op(ctx, ins, attrs):
    logits, label = first(ins, "Logits"), first(ins, "Label")
    logp = jax.nn.log_softmax(logits, axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        idx = label.reshape(label.shape[0], -1)[:, 0].astype(jnp.int32)
        loss = -jnp.take_along_axis(logp, idx[:, None], axis=-1)
    return out(Softmax=jnp.exp(logp), Loss=loss)


@register_op("sigmoid_cross_entropy_with_logits")
def sigmoid_ce_op(ctx, ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return out(Out=loss)


@register_op("square_error_cost")
def square_error_cost_op(ctx, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    return out(Out=jnp.square(x - y))


@register_op("squared_l2_norm")
def squared_l2_norm_op(ctx, ins, attrs):
    return out(Out=jnp.sum(jnp.square(first(ins, "X"))).reshape(1))


@register_op("squared_l2_distance")
def squared_l2_distance_op(ctx, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    sub = x - y
    return out(sub_result=sub, Out=jnp.sum(jnp.square(sub), axis=-1, keepdims=True))


@register_op("smooth_l1_loss")
def smooth_l1_loss_op(ctx, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    iw, ow = first(ins, "InsideWeight"), first(ins, "OutsideWeight")
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if iw is not None:
        diff = diff * iw
    ad = jnp.abs(diff)
    val = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if ow is not None:
        val = val * ow
    return out(Diff=diff, Out=jnp.sum(val.reshape(val.shape[0], -1), axis=1, keepdims=True))


@register_op("huber_loss")
def huber_loss_op(ctx, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return out(Residual=r, Out=loss)


@register_op("hinge_loss")
def hinge_loss_op(ctx, ins, attrs):
    logits, label = first(ins, "Logits"), first(ins, "Labels")
    return out(Loss=jnp.maximum(0.0, 1.0 - (2.0 * label - 1.0) * logits))


@register_op("rank_loss")
def rank_loss_op(ctx, ins, attrs):
    label = first(ins, "Label")
    left, right = first(ins, "Left"), first(ins, "Right")
    d = left - right
    return out(Out=jnp.log1p(jnp.exp(d)) - label * d)


@register_op("margin_rank_loss")
def margin_rank_loss_op(ctx, ins, attrs):
    label = first(ins, "Label")
    x1, x2 = first(ins, "X1"), first(ins, "X2")
    margin = attrs.get("margin", 0.0)
    o = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return out(Out=o, Activated=(o > 0).astype(x1.dtype))


set_stop_gradient_outputs("margin_rank_loss", ["Activated"])


@register_op("log_loss")
def log_loss_op(ctx, ins, attrs):
    p, label = first(ins, "Predicted"), first(ins, "Labels")
    eps = attrs.get("epsilon", 1e-4)
    return out(Loss=-label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps))


# ---------------------------------------------------------------------------
# Dropout (explicit grad: must reuse the forward mask)
# ---------------------------------------------------------------------------
@register_op("dropout")
def dropout_op(ctx, ins, attrs):
    x = first(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    if is_test:
        return out(Out=x * (1.0 - p), Mask=jnp.ones_like(x))
    seed = attrs.get("seed", 0)
    key = jax.random.PRNGKey(seed) if seed else ctx.next_rng()
    mask = jax.random.bernoulli(key, 1.0 - p, x.shape).astype(x.dtype)
    return out(Out=x * mask, Mask=mask)


set_stop_gradient_outputs("dropout", ["Mask"])


@register_op("dropout_grad")
def dropout_grad_op(ctx, ins, attrs):
    g, mask = first(ins, "Out@GRAD"), first(ins, "Mask")
    return {"X@GRAD": [g * mask]}


@register_op("random_crop")
def random_crop_op(ctx, ins, attrs):
    """Per-instance random crop of the trailing dims to attrs["shape"].

    Fluid op semantics (this reference snapshot predates
    random_crop_op.cc; the layer facade shipped ahead of the kernel in r2):
    X has shape [batch..., d_1..d_k]; each batch instance is cropped to
    `shape` (= [c_1..c_k], one entry per trailing dim) at an independent
    uniform offset. seed attr 0 means "use the executor rng stream"; a
    nonzero seed is folded INTO that stream — deterministic per (program
    seed, step) yet still varying step to step, the role the reference's
    Seed->SeedOut chaining plays (a raw PRNGKey(seed) would repeat the
    same offsets every batch, silently degrading augmentation). Offsets
    live in lax dynamic_slice starts, so the op traces with static shapes
    (MXU-safe)."""
    x = first(ins, "X")
    crop = tuple(int(s) for s in attrs["shape"])
    k = len(crop)
    if not (1 <= k <= x.ndim):
        raise ValueError(
            f"random_crop: shape {crop} incompatible with input rank "
            f"{x.ndim}")
    for i in range(k):
        if crop[i] > x.shape[x.ndim - k + i]:
            raise ValueError(
                f"random_crop: crop dim {crop[i]} exceeds input dim "
                f"{x.shape[x.ndim - k + i]}")
    batch_shape = tuple(x.shape[:x.ndim - k])
    seed = int(attrs.get("seed", 0) or 0)
    key = ctx.next_rng()
    if seed:
        key = jax.random.fold_in(key, seed)
    n = int(np.prod(batch_shape)) if batch_shape else 1
    xf = x.reshape((n,) + tuple(x.shape[x.ndim - k:]))
    maxoff = jnp.asarray(
        [x.shape[x.ndim - k + i] - crop[i] for i in range(k)], jnp.int32)
    offs = jax.random.randint(key, (n, k), 0, maxoff + 1, dtype=jnp.int32)

    def crop_one(xi, oi):
        return lax.dynamic_slice(xi, [oi[i] for i in range(k)], crop)

    y = jax.vmap(crop_one)(xf, offs)
    return out(Out=y.reshape(batch_shape + crop))


@register_grad_maker("dropout")
def dropout_grad_maker(op, gout, gin):
    return [
        dict(
            type="dropout_grad",
            inputs={"Out@GRAD": gout["Out"], "Mask": op.output("Mask")},
            outputs={"X@GRAD": gin["X"]},
            attrs=dict(op.attrs),
        )
    ]


# ---------------------------------------------------------------------------
# Misc nn
# ---------------------------------------------------------------------------
@register_op("prelu")
def prelu_op(ctx, ins, attrs):
    x, alpha = first(ins, "X"), first(ins, "Alpha")
    return out(Out=jnp.where(x > 0, x, alpha * x))


@register_op("bilinear_interp")
def bilinear_interp_op(ctx, ins, attrs):
    x = first(ins, "X")  # NCHW
    h = attrs.get("out_h")
    w = attrs.get("out_w")
    out_size = first(ins, "OutSize")
    if out_size is not None and (h is None or w is None):
        # OutSize must be host-known (XLA needs static shapes); works in the
        # eager interpreter path, rejected with a clear error under jit
        import numpy as np

        try:
            h, w = (int(v) for v in np.asarray(out_size).reshape(-1)[:2])
        except Exception as e:
            raise ValueError(
                "bilinear_interp: traced OutSize is unsupported under jit; "
                "pass static out_h/out_w attrs"
            ) from e
    n, c = x.shape[:2]
    o = jax.image.resize(x, (n, c, h, w), method="bilinear")
    return out(Out=o.astype(x.dtype))


@register_op("row_conv", lod_aware=True)
def row_conv_op(ctx, ins, attrs):
    """reference operators/row_conv_op.cc — lookahead conv over sequences."""
    from ..core.registry import SeqTensor

    x, w = first(ins, "X"), first(ins, "Filter")
    future = w.shape[0]
    data = x.data if isinstance(x, SeqTensor) else x
    n, d = data.shape
    if isinstance(x, SeqTensor):
        # mask contributions that cross a sequence boundary
        seg = x.segment_ids()
        o = jnp.zeros_like(data)
        for i in range(future):
            shifted_seg = jnp.concatenate([seg[i:], jnp.full((i,), -1, seg.dtype)])
            m = (shifted_seg == seg)[:, None].astype(data.dtype)
            shifted = jnp.pad(data[i:], ((0, i), (0, 0)))
            o = o + shifted * w[i][None, :] * m
        return out(Out=SeqTensor(o, x.lengths))
    padded = jnp.pad(data, ((0, future - 1), (0, 0)))
    o = sum(padded[i : i + n] * w[i][None, :] for i in range(future))
    return out(Out=o)


@register_op("im2sequence", lod_aware=True)
def im2sequence_op(ctx, ins, attrs):
    """reference operators/im2sequence_op.cc: NCHW image -> sequence of
    flattened patches (one sequence per image)."""
    from ..core.registry import SeqTensor

    x = first(ins, "X")
    kh, kw = attrs.get("kernels", [1, 1])
    sh, sw = attrs.get("strides", [1, 1])
    pads = attrs.get("paddings", [0, 0, 0, 0])
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])))
    oh = (xp.shape[2] - kh) // sh + 1
    ow = (xp.shape[3] - kw) // sw + 1
    patches = lax.conv_general_dilated_patches(
        xp, (kh, kw), (sh, sw), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW")
    )  # [N, C*kh*kw, oh, ow]
    seq = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, c * kh * kw)
    lengths = jnp.full((n,), oh * ow, jnp.int32)
    return out(Out=SeqTensor(seq, lengths))


@register_op("spp")
def spp_op(ctx, ins, attrs):
    """Spatial pyramid pooling (reference operators/spp_op.{cc,h}): level p
    pools X [N,C,H,W] onto a bins x bins grid (bins = 2^p) with
    ksize = ceil(dim/bins) and the reference's centering padding, then the
    flattened levels concat to [N, C*(4^P-1)/3]. Each level is one
    lax.reduce_window — static shapes, XLA-fusable; P is tiny so the
    Python loop unrolls into the trace."""
    x = first(ins, "X")
    p_height = int(attrs.get("pyramid_height", 1))
    ptype = attrs.get("pooling_type", "max")
    n, c, h, w = x.shape
    levels = []
    for p in range(p_height):
        bins = 2 ** p
        kh, kw = -(-h // bins), -(-w // bins)
        ph, pw = (kh * bins - h + 1) // 2, (kw * bins - w + 1) // 2
        dims = (1, 1, kh, kw)
        strides = (1, 1, kh, kw)
        pads = ((0, 0), (0, 0), (ph, kh * bins - h - ph),
                (pw, kw * bins - w - pw))
        if ptype == "max":
            lvl = lax.reduce_window(
                x, -jnp.inf, lax.max, dims, strides, pads).astype(x.dtype)
        else:
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
            # divide by the REAL element count per window (padding
            # excluded), the reference Pool2dFunctor's clipped-window rule
            cnt = lax.reduce_window(
                jnp.ones_like(x), 0.0, lax.add, dims, strides, pads)
            lvl = (s / cnt).astype(x.dtype)
        levels.append(lvl.reshape(n, c * bins * bins))
    return out(Out=jnp.concatenate(levels, axis=1))


@register_op("unpool")
def unpool_op(ctx, ins, attrs):
    """Max-unpool 2d (reference operators/unpool_op.{cc,h}): scatter each
    pooled value back to the position its flat index names inside the
    unpooled H*W plane; everything else is zero. One batched scatter —
    the TPU-native form of the reference's per-element loop."""
    x = first(ins, "X")
    idx = first(ins, "Indices")
    n, c, h, w = x.shape
    ksize = attrs["ksize"]
    strides = attrs.get("strides", [1, 1])
    pads = attrs.get("paddings", [0, 0])
    ho = (h - 1) * strides[0] - 2 * pads[0] + ksize[0]
    wo = (w - 1) * strides[1] - 2 * pads[1] + ksize[1]
    flat_idx = idx.reshape(n, c, h * w).astype(jnp.int32)
    vals = x.reshape(n, c, h * w)
    bn = jnp.arange(n)[:, None, None]
    bc = jnp.arange(c)[None, :, None]
    o = jnp.zeros((n, c, ho * wo), x.dtype).at[bn, bc, flat_idx].set(vals)
    return out(Out=o.reshape(n, c, ho, wo))
