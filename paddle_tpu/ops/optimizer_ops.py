"""Optimizer ops — parameter updates expressed as IR ops, exactly like the
reference (operators/{sgd,momentum,adam,adamax,adagrad,decayed_adagrad,
adadelta,rmsprop,ftrl}_op.cc). Inside the traced step they fuse with the
backward pass into the same XLA computation, so the whole
forward+backward+update runs as one TPU program.
"""

import jax.numpy as jnp
from jax import lax

from .. import amp
from ..core import dtypes
from ..core.registry import register_op
from .util import first, out


@register_op("sgd")
def sgd_op(ctx, ins, attrs):
    """reference operators/sgd_op.cc: dense update, plus its two sparse
    paths — SelectedRows grad on a dense param (scatter-sub; the
    TPU-idiomatic in-trace form of a sparse embedding update) and
    SelectedRows grad on a pserver SparseTable (host hash-table update)."""
    p, g, lr = first(ins, "Param"), first(ins, "Grad"), first(ins, "LearningRate")
    from ..core.selected_rows import SelectedRows, SparseTable

    if isinstance(p, SparseTable):
        assert isinstance(g, SelectedRows), \
            f"SparseTable sgd needs a SelectedRows grad, got {type(g)}"
        p.sgd_update(g, float(jnp.asarray(lr).reshape(())))
        return out(ParamOut=p)
    if isinstance(g, SelectedRows):
        lr = jnp.asarray(lr).reshape(()).astype(p.dtype)
        upd = jnp.asarray(p).at[jnp.asarray(g.rows).reshape(-1)].add(
            -lr * jnp.asarray(g.values).astype(p.dtype))
        return out(ParamOut=upd)
    return out(ParamOut=(p - lr.reshape(()).astype(p.dtype) * g.astype(p.dtype)))


@register_op("momentum")
def momentum_op(ctx, ins, attrs):
    p, g, v = first(ins, "Param"), first(ins, "Grad"), first(ins, "Velocity")
    lr = first(ins, "LearningRate").reshape(()).astype(p.dtype)
    mu = attrs["mu"]
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return out(ParamOut=p_out, VelocityOut=v_out)


@register_op("adam")
def adam_op(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    m1, m2 = first(ins, "Moment1"), first(ins, "Moment2")
    b1p = first(ins, "Beta1Pow").reshape(()).astype(jnp.float32)
    b2p = first(ins, "Beta2Pow").reshape(()).astype(jnp.float32)
    b1, b2, eps = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999), attrs.get("epsilon", 1e-8)
    gf = g.astype(jnp.float32)
    m1o = b1 * m1 + (1 - b1) * gf
    m2o = b2 * m2 + (1 - b2) * jnp.square(gf)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_out = p.astype(jnp.float32) - lr_t * m1o / (jnp.sqrt(m2o) + eps)
    # AdamW (Loshchilov & Hutter, arXiv:1711.05101): the decay acts on the
    # parameter itself, scaled by the learning rate, outside the moments
    wd = attrs.get("weight_decay", 0.0)
    if wd:
        p_out = p_out - (lr * wd) * p.astype(jnp.float32)
    p_out = p_out.astype(p.dtype)
    outs = out(ParamOut=p_out, Moment1Out=m1o, Moment2Out=m2o)
    # the low-precision copy kept beside a master that a kernel reads
    # (amp.KERNEL_SLOTS): written from the value just computed, in the
    # same pass, bit for bit the cast the next step would have made
    if attrs.get("low_dtype"):
        outs[amp.LOW_OUT] = [
            p_out.astype(dtypes.to_jnp(attrs["low_dtype"]))]
    return outs


@register_op("adamax")
def adamax_op(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    m, inf = first(ins, "Moment"), first(ins, "InfNorm")
    b1p = first(ins, "Beta1Pow").reshape(()).astype(jnp.float32)
    b1, b2, eps = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999), attrs.get("epsilon", 1e-8)
    gf = g.astype(jnp.float32)
    m_out = b1 * m + (1 - b1) * gf
    inf_out = jnp.maximum(b2 * inf, jnp.abs(gf))
    p_out = p.astype(jnp.float32) - (lr / (1 - b1p)) * (m_out / (inf_out + eps))
    return out(ParamOut=p_out.astype(p.dtype), MomentOut=m_out, InfNormOut=inf_out)


@register_op("adagrad")
def adagrad_op(ctx, ins, attrs):
    p, g, mom = first(ins, "Param"), first(ins, "Grad"), first(ins, "Moment")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    eps = attrs.get("epsilon", 1e-6)
    gf = g.astype(jnp.float32)
    mom_out = mom + jnp.square(gf)
    p_out = p.astype(jnp.float32) - lr * gf / (jnp.sqrt(mom_out) + eps)
    return out(ParamOut=p_out.astype(p.dtype), MomentOut=mom_out)


@register_op("decayed_adagrad")
def decayed_adagrad_op(ctx, ins, attrs):
    p, g, mom = first(ins, "Param"), first(ins, "Grad"), first(ins, "Moment")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    gf = g.astype(jnp.float32)
    mom_out = decay * mom + (1 - decay) * jnp.square(gf)
    p_out = p.astype(jnp.float32) - lr * gf / (jnp.sqrt(mom_out) + eps)
    return out(ParamOut=p_out.astype(p.dtype), MomentOut=mom_out)


@register_op("adadelta")
def adadelta_op(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    asg, asu = first(ins, "AvgSquaredGrad"), first(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    gf = g.astype(jnp.float32)
    asg_out = rho * asg + (1 - rho) * jnp.square(gf)
    update = -jnp.sqrt((asu + eps) / (asg_out + eps)) * gf
    asu_out = rho * asu + (1 - rho) * jnp.square(update)
    return out(
        ParamOut=(p.astype(jnp.float32) + update).astype(p.dtype),
        AvgSquaredGradOut=asg_out,
        AvgSquaredUpdateOut=asu_out,
    )


@register_op("rmsprop")
def rmsprop_op(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    ms, mom = first(ins, "MeanSquare"), first(ins, "Moment")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    eps = attrs.get("epsilon", 1e-10)
    decay = attrs.get("decay", 0.9)
    momentum = attrs.get("momentum", 0.0)
    gf = g.astype(jnp.float32)
    ms_out = decay * ms + (1 - decay) * jnp.square(gf)
    mom_out = momentum * mom + lr * gf / jnp.sqrt(ms_out + eps)
    return out(
        ParamOut=(p.astype(jnp.float32) - mom_out).astype(p.dtype),
        MeanSquareOut=ms_out,
        MomentOut=mom_out,
    )


@register_op("ftrl")
def ftrl_op(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    sq, lin = first(ins, "SquaredAccumulator"), first(ins, "LinearAccumulator")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    gf = g.astype(jnp.float32)
    new_sq = sq + jnp.square(gf)
    sigma = (jnp.power(new_sq, -power) - jnp.power(sq, -power)) / lr
    lin_out = lin + gf - sigma * p.astype(jnp.float32)
    x = jnp.clip(lin_out, -l1, l1) - lin_out
    y = jnp.power(new_sq, -power) / lr + 2 * l2
    p_out = x / y
    return out(ParamOut=p_out.astype(p.dtype), SquaredAccumOut=new_sq, LinearAccumOut=lin_out)


def _soft_threshold(prox, lr, l1, l2):
    """The proximal operator of l1/l2 regularization (reference
    proximal_gd_op.h:49-58): soft-threshold by lr*l1, shrink by 1+lr*l2."""
    if l1 > 0:
        return (jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
                / (1.0 + lr * l2))
    return prox / (1.0 + lr * l2)


@register_op("proximal_gd")
def proximal_gd_op(ctx, ins, attrs):
    """reference operators/proximal_gd_op.{cc,h}."""
    p, g = first(ins, "Param"), first(ins, "Grad")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    l1, l2 = attrs.get("l1", 0.0), attrs.get("l2", 0.0)
    prox = p.astype(jnp.float32) - lr * g.astype(jnp.float32)
    return out(ParamOut=_soft_threshold(prox, lr, l1, l2).astype(p.dtype))


@register_op("proximal_adagrad")
def proximal_adagrad_op(ctx, ins, attrs):
    """reference operators/proximal_adagrad_op.{cc,h}."""
    p, g, m = first(ins, "Param"), first(ins, "Grad"), first(ins, "Moment")
    lr = first(ins, "LearningRate").reshape(()).astype(jnp.float32)
    l1, l2 = attrs.get("l1", 0.0), attrs.get("l2", 0.0)
    gf = g.astype(jnp.float32)
    m_out = m + gf * gf
    prox = p.astype(jnp.float32) - lr * gf / jnp.sqrt(m_out)
    return out(ParamOut=_soft_threshold(prox, lr, l1, l2).astype(p.dtype),
               MomentOut=m_out)
