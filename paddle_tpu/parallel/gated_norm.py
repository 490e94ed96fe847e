"""The gated RMS norm of `ops/lm_ops.py: gated_rms_norm` as Pallas TPU
kernels, forward and backward:

    Y [T, H D] = X r w silu(Z),  r = rsqrt(mean_D(X^2) + eps) a token and
    head, w [D] a head's scale, X and Z [T, H D] (H heads of D side by side)

As three program ops with generic vjps (`rms_norm`, `swish`,
`elementwise_mul`) XLA spends eight and more passes over a [T, H D] array a
layer and keeps float32 copies for the backward: in the `qwen3_next_80b_a3b`
step 3.57 ms a layer where the op's least bytes (read X, Z, write Y; read
X, Z, d Y, write d X, d Z) are 0.66 ms at the HBM's peak (PERF.md, PR 44).
A kernel moves exactly those bytes: both run over blocks of `block` tokens,
all H heads wide, and work through a block a head (D lanes) at a time; the
mean over a head is a sum along the lanes. The backward forms r again from
X and accumulates d w over the whole grid in VMEM, eight sublane rows of
partial sums that the caller adds up. Everything is float32 inside, one
rounding on the way out.

X passes HEAD-MAJOR within a block, [T / block, H, block, D]: the delta
rule's output product leaves o as [chunks, heads, 128 tokens, dv] and turns
it token-major for its caller, and with `block` that chunk XLA cancels that
transposition against this one, so both kernels read the product's own
array; as [T, H D] the step paid two relayout copies a layer and kept the
second for the backward (v5e compile, PR 57). Where nothing cancels, the
transposition is a copy. Everything else is token-major [T, H D], as its
neighbours leave and take it: the gate, Y, d Y, d gate, and d X too (the
delta rule's backward kernel reads d o by column blocks of such an array).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["gated_norm_fwd", "gated_norm_bwd", "fits"]

# the kernels' names: Pallas puts them on the name stack, so a device trace
# reads `delta/gated_norm/gated_rms_norm/gated_norm_fwd`
KERNELS = ("gated_norm_fwd", "gated_norm_bwd")
_VMEM_LIMIT = 64 * 2 ** 20       # of the v5e's 128 MiB
_BLOCKS = (128, 64, 32, 16)      # tokens a block, tried in this order
_PARTIALS = 8        # rows of d w's partial sums: a float32 sublane tile
F32 = jnp.float32


def _block(n_tokens, width, itemsize):
    """The largest block of `_BLOCKS` that divides the tokens and whose five
    [block, width] arrays (the backward's X, Z, d Y, d X, d Z),
    double-buffered, fit 40 MiB of VMEM; None where none does."""
    return next((b for b in _BLOCKS if n_tokens % b == 0
                 and 5 * 2 * b * width * itemsize <= 40 * 2 ** 20), None)


def fits(shape, dtype):
    """Whether the kernels take X of `shape` [T, H, D] in `dtype` (the gate
    alike): heads of whole lane tiles, bf16 or float32, tokens a block
    divides (whole bf16 sublane tiles at the least) and that block within
    VMEM."""
    if len(shape) != 3 or str(dtype) not in ("bfloat16", "float32"):
        return False
    T, H, D = shape
    return bool(D % 128 == 0 and T > 0 and _block(
        T, H * D, jnp.dtype(dtype).itemsize))


def _stats(x, eps):
    return lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _fwd_kernel(x_ref, z_ref, w_ref, y_ref, *, H, D, eps):
    w = w_ref[...].astype(F32)
    for h in range(H):
        x = x_ref[h].astype(F32)
        z = z_ref[:, h * D:(h + 1) * D].astype(F32)
        y = x * _stats(x, eps) * w * (z * jax.nn.sigmoid(z))
        y_ref[:, h * D:(h + 1) * D] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, z_ref, g_ref, w_ref, dx_ref, dz_ref, dw_ref, *, H, D,
                eps):
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = w_ref[...].astype(F32)
    d_w = jnp.zeros(x_ref.shape[1:], F32)
    for h in range(H):
        x = x_ref[h].astype(F32)
        z = z_ref[:, h * D:(h + 1) * D].astype(F32)
        dy = g_ref[:, h * D:(h + 1) * D].astype(F32)
        r = _stats(x, eps)
        xr = x * r
        sig = jax.nn.sigmoid(z)
        dz_ref[:, h * D:(h + 1) * D] = (
            dy * (xr * w) * (sig * (1.0 + z * (1.0 - sig)))
        ).astype(dz_ref.dtype)
        dys = dy * (z * sig)
        g = dys * w
        # r g - x r^3 mean(g x), with x r formed once
        dx_ref[:, h * D:(h + 1) * D] = (r * (g - xr * jnp.mean(
            g * xr, axis=-1, keepdims=True))).astype(dx_ref.dtype)
        d_w = d_w + dys * xr
    dw_ref[...] += d_w.reshape(-1, _PARTIALS, D).sum(axis=0)


def _plan(x, block):
    T, H, D = x.shape
    block = block or _block(T, H * D, x.dtype.itemsize)
    return (T, H, D, block, (T // block,),
            pl.BlockSpec((None, H, block, D), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((block, H * D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)))


def _head_major(x, block):
    """[T, H, D] -> [T / block, H, block, D]."""
    T, H, D = x.shape
    return x.reshape(T // block, block, H, D).transpose(0, 2, 1, 3)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_LIMIT)


def gated_norm_fwd(x, gate, w, eps, block=None):
    """Y [T, H, D] in X's dtype from X [T, H, D], the gate [T, H D] or [T,
    H, D] and the scale w [D]; see the module's text. `fits` must hold (a
    `block` given must divide T)."""
    T, H, D, block, grid, heads, rows, scale = _plan(x, block)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, H=H, D=D, eps=eps),
        grid=grid, in_specs=[heads, rows, scale], out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((T, H * D), x.dtype),
        compiler_params=_params(), interpret=pallas_interpret(),
        name=KERNELS[0])(_head_major(x, block), gate.reshape(T, H * D),
                         w.reshape(1, D)).reshape(x.shape)


def gated_norm_bwd(x, gate, w, d_out, eps, block=None):
    """(d X in X's shape and dtype, d gate in the gate's, d w [D]
    float32)."""
    T, H, D, block, grid, heads, rows, scale = _plan(x, block)
    d_x, d_z, d_w = pl.pallas_call(
        functools.partial(_bwd_kernel, H=H, D=D, eps=eps),
        grid=grid, in_specs=[heads, rows, rows, scale],
        out_specs=[rows, rows, pl.BlockSpec((_PARTIALS, D),
                                            lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, H * D), x.dtype),
                   jax.ShapeDtypeStruct((T, H * D), gate.dtype),
                   jax.ShapeDtypeStruct((_PARTIALS, D), F32)],
        compiler_params=_params(), interpret=pallas_interpret(),
        name=KERNELS[1])(_head_major(x, block), gate.reshape(T, H * D),
                         d_out.astype(x.dtype).reshape(T, H * D),
                         w.reshape(1, D))
    return d_x.reshape(x.shape), d_z.reshape(gate.shape), d_w.sum(axis=0)
