"""Pallas TPU kernels for the horizontal fused weight update.

One bucket = one flat f32 lane holding every member parameter (or
zero1 shard) back to back. The kernels view that lane as (rows, 128)
with rows a multiple of 8 — the Mosaic (8, 128) register tile — and walk
it with a 1-D parallel grid, one (8, 128) block per step: parameter,
gradient and moment blocks stream VMEM-resident through a single
read-modify-write pass instead of XLA's generic loop fusion. Scalars
(learning rate, bias-corrected step size, betas) ride along as (1, 1)
blocks mapped to every grid step.

The bucket is zero-padded up to a whole number of (8, 128) blocks;
padded lanes compute garbage that the caller slices away (the ops layer
unpacks by exact member widths). Each block evaluates the scalar op's
expression tree in the same dtype. What parity that buys depends on who
compiles it: compiled by Mosaic on a TPU v5e the outputs are bitwise
equal to XLA's lowering of the same expressions (chip_smoke.py checks it
at n = 1029 and at ResNet-50's parameter count); interpreted on the CPU
the kernel body and the reference are two XLA:CPU compilations, so
momentum and the adam moments (multiply-add only) are bitwise but the
adam parameter, which goes through sqrt and a divide, agrees to a few
ulp (tests/test_fusion.py states the bound).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["momentum_bucket", "adam_bucket"]

_LANES = 128
_SUBLANES = 8
_BLOCK = _LANES * _SUBLANES


def _pad2d(x):
    """Flat [n] -> (rows, 128) with rows a multiple of 8, zero-padded."""
    n = int(x.shape[0])
    rows = max(_SUBLANES, (n + _BLOCK - 1) // _BLOCK * _SUBLANES)
    pad = rows * _LANES - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x.reshape(rows, _LANES)


def _tile_spec():
    return pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0))


def _scalar_spec():
    return pl.BlockSpec((1, 1), lambda i: (0, 0))


def _scalar(v):
    return jnp.asarray(v, jnp.float32).reshape(1, 1)


def _momentum_kernel(nesterov, p_ref, g_ref, v_ref, lr_ref, mu_ref,
                     po_ref, vo_ref):
    p, g, v = p_ref[...], g_ref[...], v_ref[...]
    lr, mu = lr_ref[0, 0], mu_ref[0, 0]
    v_out = mu * v + g
    if nesterov:
        po_ref[...] = p - (g + mu * v_out) * lr
    else:
        po_ref[...] = p - lr * v_out
    vo_ref[...] = v_out


def momentum_bucket(p, g, v, lr, mu, nesterov):
    """Fused momentum over one flat f32 bucket. p/g/v: [n] f32; lr: f32
    scalar; mu: python float; nesterov: static bool. Returns
    (param_out[n], velocity_out[n])."""
    n = int(p.shape[0])
    p2, g2, v2 = _pad2d(p), _pad2d(g), _pad2d(v)
    rows = int(p2.shape[0])
    po, vo = pl.pallas_call(
        functools.partial(_momentum_kernel, bool(nesterov)),
        grid=(rows // _SUBLANES,),
        in_specs=[_tile_spec(), _tile_spec(), _tile_spec(),
                  _scalar_spec(), _scalar_spec()],
        out_specs=[_tile_spec(), _tile_spec()],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=pallas_interpret(),
    )(p2, g2, v2, _scalar(lr), _scalar(mu))
    return po.reshape(-1)[:n], vo.reshape(-1)[:n]


def _adam_kernel(p_ref, g_ref, m1_ref, m2_ref, lrt_ref, b1_ref, omb1_ref,
                 b2_ref, omb2_ref, eps_ref, po_ref, m1o_ref, m2o_ref):
    p, g = p_ref[...], g_ref[...]
    m1, m2 = m1_ref[...], m2_ref[...]
    lr_t, eps = lrt_ref[0, 0], eps_ref[0, 0]
    b1, omb1 = b1_ref[0, 0], omb1_ref[0, 0]
    b2, omb2 = b2_ref[0, 0], omb2_ref[0, 0]
    m1o = b1 * m1 + omb1 * g
    m2o = b2 * m2 + omb2 * jnp.square(g)
    m1o_ref[...] = m1o
    m2o_ref[...] = m2o
    po_ref[...] = p - lr_t * m1o / (jnp.sqrt(m2o) + eps)


def adam_bucket(p, g, m1, m2, lr_t, b1, b2, eps):
    """Fused adam over one flat f32 bucket. p/g/m1/m2: [n] f32; lr_t: f32
    scalar (bias-corrected step size, computed by the caller with the
    scalar op's exact expression); b1/b2/eps: python floats. (1 - b1) and
    (1 - b2) are evaluated in python doubles here — exactly where the
    scalar kernel evaluates them — and only then rounded to f32, so the
    coefficients match the unfused op to the bit. Returns
    (param_out[n], m1_out[n], m2_out[n])."""
    n = int(p.shape[0])
    p2, g2, m12, m22 = _pad2d(p), _pad2d(g), _pad2d(m1), _pad2d(m2)
    rows = int(p2.shape[0])
    po, m1o, m2o = pl.pallas_call(
        _adam_kernel,
        grid=(rows // _SUBLANES,),
        in_specs=[_tile_spec(), _tile_spec(), _tile_spec(), _tile_spec(),
                  _scalar_spec(), _scalar_spec(), _scalar_spec(),
                  _scalar_spec(), _scalar_spec(), _scalar_spec()],
        out_specs=[_tile_spec(), _tile_spec(), _tile_spec()],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=pallas_interpret(),
    )(p2, g2, m12, m22, _scalar(lr_t), _scalar(b1), _scalar(1 - b1),
      _scalar(b2), _scalar(1 - b2), _scalar(eps))
    return po.reshape(-1)[:n], m1o.reshape(-1)[:n], m2o.reshape(-1)[:n]
