"""The in-chunk work of the chunked state-space scan (`parallel/ssd.py`,
whose head holds the formulas) as Pallas TPU kernels: everything that is
[Q, Q] a head (the decays L, C B^T, L o C B^T and, in the backward, their
cotangents) lives and dies in VMEM.

One grid step is one chunk of Q tokens of one GROUP: x (or d y) [Q, H/G x P]
and the group's B, C [Q, N] read from [T, H P] and [T, G N] where they lie,
the steps delta and log-decays a = delta A of the group's heads as [8, Q]
float32 (a head a sublane, the tokens along the lanes: `by_head`; a's
running sum `cum` is formed here, log-step lane rotations: XLA's cumsum
over 128 tokens of a [T, H] array took 0.94 ms, as long as all the rest of
the forward pass, PERF.md, PR 55), the
states as [H/G x P, N] float32. C B^T is formed once a step; the heads are
walked one after the other, two side by side in a block of 128 lanes where
a head is 64 wide.

    chunk_states      S = (x delta w)^T B, what a chunk adds to the state
                      (given d y, C and no delta: d h0 = (d y e)^T C, what
                      the reverse scan adds)
    chunk_outputs     y = (L o C B^T)(delta x) + e (C h0) + D x
    chunk_grads       the transposes of both, from the chunk-start states
                      and the reverse scan's result

The `lax.scan` over the chunks stays in `ssd.py`, between the kernels.

Precision is the plain form's, decided in the same places: every decay is
`ssd.decay` of a float32 difference, CALLED IN THE KERNEL BODY (looked up
as the kernel is traced, so wrapping it from outside reaches the kernels);
a product that reads the state or its cotangent takes float32 operands at
`ssd.STATE_PRECISION` (Mosaic has no three-pass product: at HIGH both
operands are split into a high and a low bf16 half and the three passes
written out, a bf16 operand being its own high half; DEFAULT is one bf16
pass; HIGHEST Mosaic's float32 contraction); the other products take
operands in the inputs' dtype and accumulate in float32; delta x, L o C
B^T, x delta w, d y e and d(C B^T) are rounded to the inputs' dtype where
the plain form rounds them. Float32 inputs run the same kernels.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret
from . import ssd
from .delta_parts import _NN, _NT, _TN, _halves, _mm, _set

__all__ = ["chunk_states", "chunk_outputs", "chunk_grads", "by_head",
           "by_token", "takes", "KERNELS"]

# the kernels' names: Pallas puts them on the name stack, so a device trace
# reads `mamba/scan/ssd_scan/outputs/ssd_chunk_outputs`
KERNELS = ("ssd_chunk_states", "ssd_chunk_outputs", "ssd_chunk_grads")
_VMEM_LIMIT = 64 * 2 ** 20       # of the v5e's 128 MiB
_SUBLANES = 8        # a float32 tile's rows: the heads a group may hold
_LANES = 128
F32, BF16 = jnp.float32, jnp.bfloat16


def takes(rows, seq_len, heads, head_dim, groups, state, chunk, dtype):
    """Whether the kernels take `rows` rows of `seq_len` tokens at `heads`
    heads of `head_dim` in `groups` groups of state `state`, in chunks of
    `chunk`: rows of whole chunks of one lane tile (what they were swept
    at), at most 8 heads a group, the state and a group's heads whole lane
    tiles with no head astride two, bf16 or float32."""
    per = heads // max(groups, 1)
    return bool(
        rows >= 1 and groups >= 1 and heads == per * groups
        and 1 <= per <= _SUBLANES and chunk == _LANES and seq_len
        and seq_len % chunk == 0 and state % _LANES == 0
        and (per * head_dim) % _LANES == 0
        and (head_dim % _LANES == 0 or _LANES % head_dim == 0)
        and jnp.dtype(dtype).name in ("bfloat16", "float32"))


def _rounded(x, *, exponent_bits, mantissa_bits):
    """`lax.reduce_precision` of a float32 x to `mantissa_bits` by its
    bits, to the nearest and the even of a tie."""
    if x.dtype != F32 or exponent_bits != 8 or not 0 < mantissa_bits < 23:
        raise NotImplementedError("reduce_precision of float32 mantissas")
    drop = 23 - mantissa_bits
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    odd = lax.shift_right_logical(bits, jnp.uint32(drop)) & jnp.uint32(1)
    bits = (bits + jnp.uint32((1 << (drop - 1)) - 1) + odd) \
        & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return lax.bitcast_convert_type(bits, F32)


def _teach_mosaic_to_round():
    """Mosaic lowers no `lax.reduce_precision`, and that is what a study
    wraps `ssd.decay` in to round the decays inside these kernels
    (`chipbench/lower_precision_lm_ssd_share`: a convert there and back is
    elided): give it `_rounded`, unless it has learnt a rule of its own."""
    try:
        from jax._src.pallas.mosaic import lowering
        rules = lowering.lowering_rules[lowering.tpu_core.KernelType.TC]
        if lax.reduce_precision_p not in rules:
            rules[lax.reduce_precision_p] = lowering.lower_fun(
                _rounded, multiple_results=False)
    except (ImportError, AttributeError, KeyError):
        pass         # such a study then fails to lower, as it did before


_teach_mosaic_to_round()


# ------------------------------------------------------- inside the kernels
def _cols(rows):
    """[8, Q] (a head a sublane) -> [Q, 128] (a head a lane)."""
    pad = jnp.zeros((_LANES - rows.shape[0], rows.shape[1]), F32)
    return jnp.concatenate([rows, pad], axis=0).T


def _running_sum(x, reverse=False):
    """The inclusive running sum along the lanes of x [8, Q] (`reverse`:
    from the last lane down), log-step lane rotations: float32 adds."""
    n, s = x.shape[1], 1
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def roll(a, shift):
        return jnp.roll(a, shift, axis=1) if pallas_interpret() \
            else pltpu.roll(a, shift, 1)

    while s < n:
        if reverse:
            x = x + jnp.where(lane < n - s, roll(x, n - s), 0.0)
        else:
            x = x + jnp.where(lane >= s, roll(x, s), 0.0)
        s *= 2
    return x


class _Blocks:
    """A group's [Q, H/G x P] arrays are walked in blocks of whole lane
    tiles: a head where P is a multiple of 128, else the 128 / P heads that
    share a tile. `masks[t]` [Q, width]: the lanes of a block's t-th head
    (None where the block is one head)."""

    def __init__(self, q, per, p):
        self.width = max(p, _LANES)
        self.heads, self.p = self.width // p, p
        self.n = per * p // self.width
        lane = lax.broadcasted_iota(jnp.int32, (q, self.width), 1)
        self.masks = [None] if self.heads == 1 else [
            (lane >= t * p) & (lane < (t + 1) * p) for t in range(self.heads)]

    def lanes(self, k):
        return slice(k * self.width, (k + 1) * self.width)

    def head(self, k, t):
        return k * self.heads + t

    def spread(self, cols, k):
        """[Q or 1, 128] with head i's value in lane i -> block k's [.,
        width] (or [., 1]) with each head's value across its own lanes."""
        i = self.head(k, 0)
        out = cols[:, i:i + 1]
        for t in range(1, self.heads):
            out = jnp.where(self.masks[t][:cols.shape[0]],
                            cols[:, i + t:i + t + 1], out)
        return out

    def of_head(self, x, t):
        """Block x with the lanes of the other heads zeroed."""
        return x if self.masks[t] is None else jnp.where(
            self.masks[t][:x.shape[0]], x, jnp.zeros_like(x))


def _state_operand(a):
    """What `_state_mm` takes of an operand: its bf16 halves (a bf16
    operand is its own high half) where `ssd.STATE_PRECISION` is three
    passes, one bf16 copy where it is one, else the float32 operand."""
    if ssd.STATE_PRECISION == lax.Precision.HIGH:
        return (a, None) if a.dtype == BF16 else _halves(a.astype(F32))
    if ssd.STATE_PRECISION in (None, lax.Precision.DEFAULT):
        return (a.astype(BF16),)
    return (a.astype(F32),)


def _state_mm(a, b, dims=_NN):
    """a b of two `_state_operand`s at `ssd.STATE_PRECISION` (looked up as
    the kernel is traced). A product of bf16 halves says DEFAULT itself:
    under `jax.default_matmul_precision("highest")` it would ask Mosaic for
    a float32 contraction of bf16 operands, which it refuses."""
    def mm(u, v):
        return lax.dot_general(
            u, v, (dims, ((), ())), preferred_element_type=F32,
            precision=lax.Precision.DEFAULT if u.dtype == BF16
            else ssd.STATE_PRECISION)

    if len(a) == 1:
        return mm(a[0], b[0])
    passes = [(a[1], b[0]), (a[0], b[1]), (a[0], b[0])]
    return sum(mm(u, v) for u, v in passes
               if u is not None and v is not None)


def _lower(q):
    return lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _ell(cum_c, cum_r, i):
    """L of head i [Q, Q] ON AND BELOW THE DIAGONAL, exp(cum_i - cum_j);
    above it a decay too (of 0: a log-decay only falls), which the caller
    masks by what it multiplies L with: C B^T zeroed above the diagonal."""
    return ssd.decay(jnp.minimum(cum_c[:, i:i + 1] - cum_r[i:i + 1, :], 0.0))


def _states_kernel(x_ref, b_ref, a_ref, *refs, per, p, q, transposed):
    # forward: S = (x delta w)^T B, w = exp(cum_Q - cum); transposed (x is
    # d y, b is C): d h0 = (d y e)^T C, e = exp(cum)
    delta_ref, out_ref = (None, refs[0]) if transposed else refs
    low, blocks = x_ref.dtype, _Blocks(q, per, p)
    for j in range(x_ref.shape[0] // q):
        rows = slice(j * q, (j + 1) * q)
        cum_c = _cols(_running_sum(a_ref[:, rows]))
        if transposed:
            weight = ssd.decay(cum_c)
        else:
            weight = _cols(delta_ref[:, rows]) \
                * ssd.decay(cum_c[q - 1:, :] - cum_c)
        b = b_ref[rows, :]
        for k in range(blocks.n):
            at = blocks.lanes(k)
            xw = (x_ref[rows, at].astype(F32)
                  * blocks.spread(weight, k)).astype(low)
            out_ref[j, at, :] = _mm(xw, b, _TN)


def _outputs_kernel(x_ref, b_ref, c_ref, delta_ref, a_ref, d_ref,
                    starts_ref, y_ref, *, per, p, q):
    low, blocks, lower = x_ref.dtype, _Blocks(q, per, p), _lower(q)
    for j in range(x_ref.shape[0] // q):
        rows = slice(j * q, (j + 1) * q)
        cum_r = _running_sum(a_ref[:, rows])
        cum_c, delta_c = _cols(cum_r), _cols(delta_ref[:, rows])
        e_c = ssd.decay(cum_c)
        c = c_ref[rows, :]
        cb = jnp.where(lower, _mm(c, b_ref[rows, :], _NT), 0.0)
        c_op = _state_operand(c)
        for k in range(blocks.n):
            at = blocks.lanes(k)
            xf = x_ref[rows, at].astype(F32)
            xd = (xf * blocks.spread(delta_c, k)).astype(low)
            y = jnp.zeros(xf.shape, F32)
            for t in range(blocks.heads):
                m = (_ell(cum_c, cum_r, blocks.head(k, t)) * cb).astype(low)
                y = y + _mm(m, blocks.of_head(xd, t))
            # what came from before the chunk, then the skip term
            y = y + _state_mm(c_op, _state_operand(starts_ref[j, at, :]),
                              _NT) * blocks.spread(e_c, k)
            y = y + blocks.spread(d_ref[:1, :], k) * xf
            y_ref[rows, at] = y.astype(low)


def _grads_kernel(x_ref, dy_ref, b_ref, c_ref, delta_ref, a_ref, d_ref,
                  starts_ref, left_ref, dx_ref, db_ref, dc_ref, ddelta_ref,
                  da_ref, dd_ref, *, per, p, q):
    for j in range(x_ref.shape[0] // q):
        rows = slice(j * q, (j + 1) * q)
        _chunk_grads(
            x_ref.at[rows], dy_ref.at[rows], b_ref.at[rows], c_ref.at[rows],
            delta_ref[:, rows], a_ref[:, rows], d_ref, starts_ref.at[j],
            left_ref.at[j], dx_ref.at[rows], db_ref.at[rows],
            dc_ref.at[rows], ddelta_ref.at[:, rows], da_ref.at[:, rows],
            dd_ref.at[j], per, p, q)


def _chunk_grads(x_ref, dy_ref, b_ref, c_ref, delta_r, a_r, d_ref,
                 starts_ref, left_ref, dx_ref, db_ref, dc_ref, ddelta_ref,
                 da_ref, dd_ref, per, p, q):
    low, blocks, lower = x_ref.dtype, _Blocks(q, per, p), _lower(q)
    cum_r = _running_sum(a_r)
    cum_c, delta_c = _cols(cum_r), _cols(delta_r)
    e_c = ssd.decay(cum_c)
    weight_c = delta_c * ssd.decay(cum_c[q - 1:, :] - cum_c)
    b, c = b_ref[...], c_ref[...]
    cb = jnp.where(lower, _mm(c, b, _NT), 0.0)
    b_op, c_op = _state_operand(b), _state_operand(c)
    # what is a scalar a token and head is worked a head a sublane, the
    # tokens along the lanes, as it is read and written ([8, Q]: one tile):
    # a [Q, P] product of a head is summed over P by one transpose of the
    # block and adds down the sublanes (a sum along the lanes is a pass
    # through the cross-lane unit a tile of 8 tokens: 0.7 of this kernel's
    # 1.4 ms went there, PERF.md, PR 55)
    zeros = jnp.zeros((_SUBLANES, q), F32)
    d_cum_state, scaled, from_xd = zeros, zeros, zeros
    row_sums, col_sums = zeros, zeros
    carried = jnp.zeros((_SUBLANES, b.shape[1]), F32)
    d_cb = jnp.zeros((q, q), F32)
    d_b = jnp.zeros(b.shape, F32)
    d_c = jnp.zeros(c.shape, F32)

    def summed(acc, v, k, transposed=True):
        """acc with, in the sublane of each head of block k, v [Q, width]
        (`transposed` False: [width, .]) summed over that head's P."""
        vt = v.T if transposed else v
        for t in range(blocks.heads):
            acc = _set(acc, 0, blocks.head(k, t), jnp.sum(
                vt[t * p:(t + 1) * p], axis=0, keepdims=True))
        return acc

    for k in range(blocks.n):
        at = blocks.lanes(k)
        x, dy = x_ref[:, at], dy_ref[:, at]
        xf, dyf = x.astype(F32), dy.astype(F32)
        starts, left = starts_ref[at, :], left_ref[at, :]
        starts_op, left_op = _state_operand(starts), _state_operand(left)
        dd_ref[:, at] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        # y += e (C h0): d cum, d C; d h0 went to the reverse scan
        dye = dyf * blocks.spread(e_c, k)
        d_cum_state = summed(
            d_cum_state, dye * _state_mm(c_op, starts_op, _NT), k)
        d_c = d_c + _state_mm(_state_operand(dye), starts_op)
        # S = (x delta w)^T B with d S = `left`, the cotangent of the state
        # the next chunk starts from; d exp(cum_Q) = <d S, h0>, summed
        # along the lanes below
        carried = summed(carried, left * starts, k, transposed=False)
        weight = blocks.spread(weight_c, k)
        d_xw = _state_mm(b_op, left_op, _NT)
        d_b = d_b + _state_mm(_state_operand((xf * weight).astype(low)),
                              left_op)
        scaled = summed(scaled, d_xw * xf, k)
        # in-chunk: y += (L o C B^T)(delta x)
        delta = blocks.spread(delta_c, k)
        xd = (xf * delta).astype(low)
        d_xd = jnp.zeros(xf.shape, F32)
        for t in range(blocks.heads):
            i = blocks.head(k, t)
            ell = _ell(cum_c, cum_r, i)
            dy_t = blocks.of_head(dy, t)
            # (d_m is masked where it is used: by C B^T here, as d(C B^T)
            # once below)
            d_m = _mm(dy_t, xd, _NT) * ell
            d_xd = d_xd + _mm((ell * cb).astype(low), dy_t, _TN)
            d_cb = d_cb + d_m
            d_seg = d_m * cb
            row_sums = _set(row_sums, 0, i,
                            jnp.sum(d_seg.T, axis=0, keepdims=True))
            col_sums = _set(col_sums, 0, i,
                            jnp.sum(d_seg, axis=0, keepdims=True))
        from_xd = summed(from_xd, d_xd * xf, k)
        dx_ref[:, at] = (blocks.spread(d_ref[:1, :], k) * dyf
                         + d_xw * weight + d_xd * delta).astype(low)
    d_cb = jnp.where(lower, d_cb, 0.0).astype(low)
    dc_ref[...] = (d_c + _mm(d_cb, b)).astype(low)
    db_ref[...] = (d_b + _mm(d_cb, c, _TN)).astype(low)
    # d (delta w) = scaled: to delta, and through w = exp(cum_Q - cum) to
    # cum and cum_Q; cum_Q is cum's last entry, cum a's running sum
    last = cum_r[:, q - 1:]
    w_r = ssd.decay(last - cum_r)
    at_last = lax.broadcasted_iota(jnp.int32, zeros.shape, 1) == q - 1
    moved = scaled * delta_r * w_r
    d_last = ssd.decay(last) * jnp.sum(carried, axis=1, keepdims=True) \
        + jnp.sum(moved, axis=1, keepdims=True)
    d_cum = d_cum_state - moved + row_sums - col_sums \
        + jnp.where(at_last, d_last, 0.0)
    ddelta_ref[...] = scaled * w_r + from_xd
    da_ref[...] = _running_sum(d_cum, reverse=True)


# ------------------------------------------------------------- the calls
def by_head(a, rows, seq_len, groups):
    """[rows, chunks, Q, G, H/G] float32 -> [G, 8, T]: a group's heads
    down the sublanes, the tokens along the lanes (a [T, H/G] block would
    be padded to 128 lanes)."""
    per = a.shape[-1]
    a = jnp.moveaxis(a.reshape(rows * seq_len, groups, per), 0, -1)
    return jnp.pad(a, ((0, 0), (0, _SUBLANES - per), (0, 0)))


def by_token(a, rows, n_chunks, per):
    """The inverse of `by_head`: [G, 8, T] -> [rows, chunks, Q, G, H/G]."""
    groups = a.shape[0]
    a = jnp.moveaxis(a[:, :per], -1, 0)
    return a.reshape(rows, n_chunks, -1, groups, per)


# chunks a grid step: independent chains of dependent work (a running sum,
# a transpose, the decays, a product) walked side by side (swept on the v5e
# at the `nemotron_3_nano_30b_a3b` cell's shape over 1, 2, 4, 8: PERF.md,
# PR 55)
CHUNKS_A_STEP = 2


def _plan(x, name, seq_len, heads, head_dim, groups, state, chunk):
    rows, per = x.shape[0] // seq_len, heads // groups
    n = seq_len // chunk
    cb = next(c for c in range(min(CHUNKS_A_STEP, n), 0, -1) if n % c == 0)
    blocks, width = n // cb, per * head_dim

    def tokens(r, c, g):
        return (r * blocks + c, g)

    return dict(
        rows=rows, n=n, grid=(rows, blocks, groups), name=name,
        static=dict(per=per, p=head_dim, q=chunk),
        wide=pl.BlockSpec((cb * chunk, width), tokens),
        narrow=pl.BlockSpec((cb * chunk, state), tokens),
        side=pl.BlockSpec((None, _SUBLANES, cb * chunk),
                          lambda r, c, g: (g, 0, r * blocks + c)),
        head=pl.BlockSpec((None, _SUBLANES, _LANES),
                          lambda r, c, g: (g, 0, 0)),
        states=pl.BlockSpec((cb, None, None, width, state),
                            lambda r, c, g: (c, r, g, 0, 0)),
        states_shape=jax.ShapeDtypeStruct(
            (n, rows, groups, width, state), F32),
        sums=pl.BlockSpec((cb, None, 1, width),
                          lambda r, c, g: (r * blocks + c, g, 0, 0)),
        side_shape=jax.ShapeDtypeStruct(
            (groups, _SUBLANES, x.shape[0]), F32))


def _call(kernel, plan, in_specs, out_specs, out_shape):
    return pl.pallas_call(
        functools.partial(kernel, **plan["static"]), grid=plan["grid"],
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(), name=plan["name"])


def _per_head(d, groups):
    """[H] -> [G, 8, 128] float32, a group's heads along the lanes of every
    sublane."""
    d = d.astype(F32).reshape(groups, 1, -1)
    d = jnp.pad(d, ((0, 0), (0, 0), (0, _LANES - d.shape[2])))
    return jnp.broadcast_to(d, (groups, _SUBLANES, _LANES))


def _as_states(a, plan, head_dim):
    """[chunks, rows, G, H/G x P, N] <-> [chunks, rows, G, H/G, P, N]."""
    if a.ndim == 5:
        return a.reshape(a.shape[:3] + (-1, head_dim, a.shape[-1]))
    return a.reshape(a.shape[:3] + (-1, a.shape[-1]))


def chunk_states(x, b, a, delta=None, **shape):
    """What each chunk adds to the state, [chunks, rows, G, H/G, P, N]
    float32: (x delta w)^T B from x [T, H P], B [T, G N] and `by_head`'s
    delta and a; with `delta` None, x being d y and b C, (d y e)^T C.
    `takes` must hold."""
    plan = _plan(x, KERNELS[0], **shape)
    sides = (a,) if delta is None else (a, delta)
    own = _call(
        functools.partial(_states_kernel, transposed=delta is None), plan,
        [plan["wide"], plan["narrow"]] + [plan["side"]] * len(sides),
        plan["states"], plan["states_shape"])(x, b, *sides)
    return _as_states(own, plan, shape["head_dim"])


def chunk_outputs(x, b, c, delta, a, d, starts, **shape):
    """y [T, H P] in x's dtype from the op's inputs, `by_head`'s delta and
    a and the state each chunk starts from."""
    plan = _plan(x, KERNELS[1], **shape)
    return _call(
        _outputs_kernel, plan,
        [plan["wide"], plan["narrow"], plan["narrow"], plan["side"],
         plan["side"], plan["head"], plan["states"]],
        plan["wide"], jax.ShapeDtypeStruct(x.shape, x.dtype))(
            x, b, c, delta, a, _per_head(d, shape["groups"]),
            _as_states(starts, plan, None))


def chunk_grads(x, dy, b, c, delta, a, d, starts, left, **shape):
    """The cotangents of the in-chunk work from d y, the state each chunk
    starts from and `left`, the cotangent of the state it leaves: (d x, d
    B, d C in the inputs' dtype; d delta and d a as `by_head` lays them, d
    a with what reaches it through cum_Q and without what reaches delta
    through a = delta A; d D [H])."""
    plan = _plan(x, KERNELS[2], **shape)
    groups, sums = shape["groups"], jax.ShapeDtypeStruct(
        (plan["rows"] * plan["n"], shape["groups"], 1,
         plan["static"]["per"] * shape["head_dim"]), F32)
    narrow = jax.ShapeDtypeStruct(b.shape, b.dtype)
    d_x, d_b, d_c, d_delta, d_a, d_d = _call(
        _grads_kernel, plan,
        [plan["wide"], plan["wide"], plan["narrow"], plan["narrow"],
         plan["side"], plan["side"], plan["head"], plan["states"],
         plan["states"]],
        [plan["wide"], plan["narrow"], plan["narrow"], plan["side"],
         plan["side"], plan["sums"]],
        [jax.ShapeDtypeStruct(x.shape, x.dtype), narrow, narrow,
         plan["side_shape"], plan["side_shape"], sums])(
             x, dy, b, c, delta, a, _per_head(d, groups),
             _as_states(starts, plan, None), _as_states(left, plan, None))
    d_d = jnp.sum(d_d.reshape(-1, shape["heads"], shape["head_dim"]),
                  axis=(0, 2))
    return d_x, d_b, d_c, d_delta, d_a, d_d
