"""The gated short convolution of `ops/lm_ops.py: short_conv` as Pallas TPU
kernels, forward and backward:

    Out [T, C] = C * conv(B * z),  X [T, 3C] = [B | C | z],  taps w [L, C]
    conv_t = sum_k w[L - 1 - k] v_{t - k},  v = B * z, zero before a row

XLA lowers the plain form (L shifted multiply-adds) as several passes: in
the `lfm2_8b_a1b` step it keeps a float32 convolution from the forward for
the backward (the common subexpression of the backward's recomputation),
and the backward is three fusions and a concatenate: 201 + 772 MB a layer
where the op's least bytes are 134 + 235 (v5e compile, PERF.md PR 39). A
kernel moves exactly the least bytes: the forward reads a block of X's rows
once and writes Out; the backward reads X and d Out once and writes the
three thirds of d X side by side, with d Filter accumulated in VMEM over
the whole grid.

Both run over blocks of `block_s` tokens of one row, all 3C channels wide,
and work through a block `_CHANNELS` channels at a time. What a block needs
of its neighbours (the L - 1 tokens before it for v, after it for d conv)
comes as a second, `_HALO`-row view of the same array, the 16 rows that
end where the block starts (or start where it ends); at a row's first
(last) block that halo counts as zero: rows are separate sequences. The
shifts are sublane rotations (`pltpu.roll`) of the block with its halo.
Gates and sums are float32, one rounding on the way out.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["short_conv_fwd", "short_conv_bwd", "takes", "silu_conv_fwd",
           "silu_conv_bwd", "silu_takes"]

# the kernels' names: Pallas puts them on the name stack, so a device trace
# reads `conv/short_conv/short_conv/short_conv_fwd`
KERNELS = ("short_conv_fwd", "short_conv_bwd")
# the variant silu(conv(x)) of a Gated DeltaNet layer (the op's attr
# `gating` = "silu"): X [T, C] whole, no gates
SILU_KERNELS = ("silu_conv_fwd", "silu_conv_bwd")
_VMEM_LIMIT = 64 * 2 ** 20       # of the v5e's 128 MiB
_BLOCKS = (256, 128, 64, 32, 16)     # tokens a block, tried in this order
_CHANNELS = (512, 256, 128)      # channels worked through at a time
_HALO = 16           # rows of a neighbour's view: a bf16 sublane tile
F32 = jnp.float32


def _first_dividing(n, sizes):
    return next((s for s in sizes if n % s == 0), None)


def takes(n_tokens, channels, seq_len, taps, dtype):
    """Whether the kernels take X [n_tokens, 3 x channels] in rows of
    `seq_len` with `taps` taps: whole lane tiles of channels, rows a block
    size divides, no more taps than a halo holds, bf16 or float32."""
    return bool(
        channels % 128 == 0 and seq_len and n_tokens % seq_len == 0
        and _first_dividing(seq_len, _BLOCKS) and 1 <= taps <= _HALO + 1
        and str(dtype) in ("bfloat16", "float32"))


def _roll(a, shift):
    """Rows of a rotated down by `shift` (row t holds a[t - shift])."""
    if not shift:
        return a
    return jnp.roll(a, shift, axis=0) if pallas_interpret() \
        else pltpu.roll(a, shift, 0)


def _gated(ref, c0, cc, C):
    """v = B * z of the rows `ref` views, channels [c0, c0 + cc), float32."""
    return ref[:, c0:c0 + cc].astype(F32) \
        * ref[:, 2 * C + c0:2 * C + c0 + cc].astype(F32)


def _shifts_back(v, v_before, L):
    """[v_{t - k} for k < L] over a block's rows, from the block's v and
    the halo's (the `_HALO` rows before it)."""
    if L == 1:
        return [v]
    both = jnp.concatenate([v_before, v], axis=0)
    return [v] + [_roll(both, k)[_HALO:] for k in range(1, L)]


def _fwd_kernel(x_ref, before_ref, w_ref, o_ref, *, L, C, cc):
    first = pl.program_id(1) == 0
    for c0 in range(0, C, cc):
        v_before = jnp.where(first, 0.0, _gated(before_ref, c0, cc, C))
        back = _shifts_back(_gated(x_ref, c0, cc, C), v_before, L)
        w = w_ref[:, c0:c0 + cc].astype(F32)
        conv = sum(w[L - 1 - k][None, :] * back[k] for k in range(L))
        gate = x_ref[:, C + c0:C + c0 + cc].astype(F32)
        o_ref[:, c0:c0 + cc] = (gate * conv).astype(o_ref.dtype)


def _bwd_kernel(x_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref,
                dx_ref, dw_ref, *, L, C, cc, block_s):
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & first)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    n = block_s + _HALO
    for c0 in range(0, C, cc):
        b = x_ref[:, c0:c0 + cc].astype(F32)
        z = x_ref[:, 2 * C + c0:2 * C + c0 + cc].astype(F32)
        gate = x_ref[:, C + c0:C + c0 + cc].astype(F32)
        g = g_ref[:, c0:c0 + cc].astype(F32)
        w = w_ref[:, c0:c0 + cc].astype(F32)
        v_before = jnp.where(first, 0.0, _gated(before_ref, c0, cc, C))
        back = _shifts_back(b * z, v_before, L)
        conv = sum(w[L - 1 - k][None, :] * back[k] for k in range(L))
        d_conv = g * gate
        # d v_t = sum_k w[L - 1 - k] d conv_{t + k}: the rows after
        d_v = w[L - 1][None, :] * d_conv
        if L > 1:
            after = jnp.where(
                last, 0.0, g_after_ref[:, c0:c0 + cc].astype(F32)
                * after_ref[:, C + c0:C + c0 + cc].astype(F32))
            both = jnp.concatenate([d_conv, after], axis=0)
            for k in range(1, L):
                d_v = d_v + w[L - 1 - k][None, :] * _roll(
                    both, n - k)[:block_s]
        dx_ref[:, c0:c0 + cc] = (d_v * z).astype(dx_ref.dtype)
        dx_ref[:, C + c0:C + c0 + cc] = (g * conv).astype(dx_ref.dtype)
        dx_ref[:, 2 * C + c0:2 * C + c0 + cc] = (d_v * b).astype(
            dx_ref.dtype)
        for k in range(L):
            j = L - 1 - k
            dw_ref[j:j + 1, c0:c0 + cc] += jnp.sum(
                back[k] * d_conv, axis=0, keepdims=True)


def _plan(x, seq_len, parts=3, block_s=None):
    """The grid and the index maps over X [T, parts x C] in rows of
    `seq_len`, in blocks of `block_s` tokens (the largest of `_BLOCKS` that
    divides a row where none is given)."""
    T, C = x.shape[0], x.shape[1] // parts
    block_s = block_s or _first_dividing(seq_len, _BLOCKS)
    n_blocks = seq_len // block_s
    per = block_s // _HALO            # halo views a block
    last_view = T // _HALO - 1

    def block(r, i):
        return (r * n_blocks + i, 0)

    def before(r, i):
        return (jnp.maximum((r * n_blocks + i) * per - 1, 0), 0)

    def after(r, i):
        return (jnp.minimum((r * n_blocks + i + 1) * per, last_view), 0)

    return (T, C, block_s, (T // seq_len, n_blocks),
            _first_dividing(C, _CHANNELS), block, before, after)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def short_conv_fwd(x, w, seq_len):
    """Out [T, C] in X's dtype; see the module's text. `takes` must hold."""
    T, C, block_s, grid, cc, block, before, _ = _plan(x, seq_len)
    L = w.shape[0]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, L=L, C=C, cc=cc),
        grid=grid,
        in_specs=[pl.BlockSpec((block_s, 3 * C), block),
                  pl.BlockSpec((_HALO, 3 * C), before),
                  pl.BlockSpec((L, C), lambda r, i: (0, 0))],
        out_specs=pl.BlockSpec((block_s, C), block),
        out_shape=jax.ShapeDtypeStruct((T, C), x.dtype),
        compiler_params=_params(), interpret=pallas_interpret(),
        name=KERNELS[0])(x, x, w)


def short_conv_bwd(x, w, d_out, seq_len):
    """(d X [T, 3C] in X's dtype, d Filter [L, C] float32)."""
    T, C, block_s, grid, cc, block, before, after = _plan(x, seq_len)
    L, g = w.shape[0], d_out.astype(x.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, L=L, C=C, cc=cc, block_s=block_s),
        grid=grid,
        in_specs=[pl.BlockSpec((block_s, 3 * C), block),
                  pl.BlockSpec((_HALO, 3 * C), before),
                  pl.BlockSpec((_HALO, 3 * C), after),
                  pl.BlockSpec((block_s, C), block),
                  pl.BlockSpec((_HALO, C), after),
                  pl.BlockSpec((L, C), lambda r, i: (0, 0))],
        out_specs=[pl.BlockSpec((block_s, 3 * C), block),
                   pl.BlockSpec((L, C), lambda r, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, 3 * C), x.dtype),
                   jax.ShapeDtypeStruct((L, C), jnp.float32)],
        compiler_params=_params(), interpret=pallas_interpret(),
        name=KERNELS[1])(x, x, x, g, g, w)


# ------------------------------------------------------ the silu variant
# Out [T, C] = silu(conv(X)), X [T, C]: the same blocks, halos and sublane
# rotations; what differs is that d conv = d Out * silu'(conv) needs the
# convolution of the L - 1 rows AFTER a block too, which the backward forms
# from the halo after the block and the block's own last rows.
def silu_takes(n_tokens, channels, seq_len, taps, dtype):
    """`takes` for the variant; a block holds all `channels` of X, Out (and
    in the backward d Out and d X) twice over in VMEM, so the block shrinks
    as the channels grow."""
    return takes(n_tokens, channels, seq_len, taps, dtype) \
        and _silu_block(seq_len, channels) is not None


def _silu_block(seq_len, channels):
    # five [block, channels] bf16 arrays, double-buffered, in 40 MiB
    fits = [b for b in _BLOCKS if 5 * 2 * 2 * b * channels <= 40 * 2 ** 20]
    return _first_dividing(seq_len, fits)


def _silu_grad(conv):
    s = jax.nn.sigmoid(conv)
    return s * (1.0 + conv * (1.0 - s))


def _silu_fwd_kernel(x_ref, before_ref, w_ref, o_ref, *, L, C, cc):
    first = pl.program_id(1) == 0
    for c0 in range(0, C, cc):
        before = jnp.where(first, 0.0,
                           before_ref[:, c0:c0 + cc].astype(F32))
        back = _shifts_back(x_ref[:, c0:c0 + cc].astype(F32), before, L)
        w = w_ref[:, c0:c0 + cc].astype(F32)
        conv = sum(w[L - 1 - k][None, :] * back[k] for k in range(L))
        o_ref[:, c0:c0 + cc] = (conv * jax.nn.sigmoid(conv)).astype(
            o_ref.dtype)


def _silu_bwd_kernel(x_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref,
                     dx_ref, dw_ref, *, L, C, cc, block_s):
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & first)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    n = block_s + _HALO
    for c0 in range(0, C, cc):
        x = x_ref[:, c0:c0 + cc].astype(F32)
        w = w_ref[:, c0:c0 + cc].astype(F32)
        before = jnp.where(first, 0.0,
                           before_ref[:, c0:c0 + cc].astype(F32))
        back = _shifts_back(x, before, L)
        conv = sum(w[L - 1 - k][None, :] * back[k] for k in range(L))
        d_conv = g_ref[:, c0:c0 + cc].astype(F32) * _silu_grad(conv)
        d_x = w[L - 1][None, :] * d_conv
        if L > 1:
            # the halo after the block: its convolution reads the block's
            # last rows, its d conv reaches back into the block
            x_after = after_ref[:, c0:c0 + cc].astype(F32)
            back_after = _shifts_back(x_after, x[block_s - _HALO:], L)
            conv_after = sum(w[L - 1 - k][None, :] * back_after[k]
                             for k in range(L))
            after = jnp.where(
                last, 0.0, g_after_ref[:, c0:c0 + cc].astype(F32)
                * _silu_grad(conv_after))
            both = jnp.concatenate([d_conv, after], axis=0)
            for k in range(1, L):
                d_x = d_x + w[L - 1 - k][None, :] * _roll(
                    both, n - k)[:block_s]
        dx_ref[:, c0:c0 + cc] = d_x.astype(dx_ref.dtype)
        for k in range(L):
            j = L - 1 - k
            dw_ref[j:j + 1, c0:c0 + cc] += jnp.sum(
                back[k] * d_conv, axis=0, keepdims=True)


def _silu_plan(x, seq_len):
    return _plan(x, seq_len, parts=1,
                 block_s=_silu_block(seq_len, x.shape[1]))


def silu_conv_fwd(x, w, seq_len):
    """Out [T, C] = silu(conv(X)) in X's dtype. `silu_takes` must hold."""
    T, C, block_s, grid, cc, block, before, _ = _silu_plan(x, seq_len)
    L = w.shape[0]
    return pl.pallas_call(
        functools.partial(_silu_fwd_kernel, L=L, C=C, cc=cc),
        grid=grid,
        in_specs=[pl.BlockSpec((block_s, C), block),
                  pl.BlockSpec((_HALO, C), before),
                  pl.BlockSpec((L, C), lambda r, i: (0, 0))],
        out_specs=pl.BlockSpec((block_s, C), block),
        out_shape=jax.ShapeDtypeStruct((T, C), x.dtype),
        compiler_params=_params(), interpret=pallas_interpret(),
        name=SILU_KERNELS[0])(x, x, w)


def silu_conv_bwd(x, w, d_out, seq_len):
    """(d X [T, C] in X's dtype, d Filter [L, C] float32)."""
    T, C, block_s, grid, cc, block, before, after = _silu_plan(x, seq_len)
    L, g = w.shape[0], d_out.astype(x.dtype)
    return pl.pallas_call(
        functools.partial(_silu_bwd_kernel, L=L, C=C, cc=cc,
                          block_s=block_s),
        grid=grid,
        in_specs=[pl.BlockSpec((block_s, C), block),
                  pl.BlockSpec((_HALO, C), before),
                  pl.BlockSpec((_HALO, C), after),
                  pl.BlockSpec((block_s, C), block),
                  pl.BlockSpec((_HALO, C), after),
                  pl.BlockSpec((L, C), lambda r, i: (0, 0))],
        out_specs=[pl.BlockSpec((block_s, C), block),
                   pl.BlockSpec((L, C), lambda r, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, C), x.dtype),
                   jax.ShapeDtypeStruct((L, C), jnp.float32)],
        compiler_params=_params(), interpret=pallas_interpret(),
        name=SILU_KERNELS[1])(x, x, x, g, g, w)
