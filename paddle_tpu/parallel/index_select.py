"""The indexer's exact top-k selection as one Pallas TPU kernel: what
`sparse_index.select_rows` finds for a block of 256 queries in 34 XLA passes
over [256, S] arrays in HBM (32 counts of `key >= candidate`, two more for
the ties and a running count along the row), found here with a chunk of
query rows' keys RESIDENT IN VMEM and only the chunk's CAUSAL key tiles
visited.

    key[t, s] = the float32 score's bits folded so that signed int32
                compares order them as the floats (-0.0 as +0.0); a key
                above the diagonal is the least int32
    tau[t]    = the k-th largest key of row t, k = min(t + 1, topk): the
                largest v with at least k keys >= v, built from the top bit
                in 32 counts (`sparse_index._kth_largest`, whose uint32 key
                is this one with the top bit flipped)
    chosen    = key > tau, and of the keys == tau the first k - #(key > tau)

`index_select` (grid: chunks of `rows` queries of the block it is given; a
step takes the chunk's scores [rows, S] float32 as `sparse_index.scores`
formed them, the block's first query a prefetched scalar): a chunk whose
last query has no more than `topk` causal keys chooses them all and writes
the least as its threshold, with no search; any other folds its causal key
tiles into a [rows, S] int32 scratch and runs the 32 counts, each a loop
over the chunk's causal key tiles alone (the chunk's first query bounds the
trip count) that keeps the per-row counts as lane-wise partial sums [rows,
128] and adds them up across the lanes once a count; the candidate, the
threshold and every per-row quantity live as [rows, 128] int32, every lane
of a row the same. Where every row of the chunk has exactly as many keys ==
tau as it may keep (float32 sums of weighted ReLU products: nearly always)
the mask is `key >= tau`; a chunk with a row that has more gives the ties to
the lower positions by a running count, a key tile of 128 at a time on the
MXU (the 0/1 tile against a triangle of ones, exact). The mask leaves as
int8 with zeros above the diagonal, the threshold as the float32 the key
came from: both bit-equal to the plain `sparse_index.select_rows`'s.

Names without "flash": `KERNELS`. On a TPU place Mosaic compiles the
kernel; anywhere else (the tests) the Pallas interpreter runs it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["KERNELS", "BLOCKS", "LANE_SUMS", "takes", "select_rows"]

KERNELS = ("index_select",)
# (queries a chunk = a grid step, keys a loop step), from
# tools/select_sweep.py on the v5e at the keye_vl_2_0_30b_a3b cell's shape
# (PERF.md, PR 58)
BLOCKS = (128, 1024)
# how a count's lane-wise partial sums are added up: "lanes" (a sum along
# the lanes), "mxu" (the partial sums as bf16, exact up to 256 a lane,
# against a matrix of ones), "sublanes" (a transpose and adds down the
# sublanes; chunks of whole lane tiles only)
LANE_SUMS = ("lanes", "mxu", "sublanes")
F32, I32 = jnp.float32, jnp.int32
_LANES = 128
# the mask's int8 tile is [32, 128]
_MASK_ROWS = 32
_VMEM_LIMIT = 64 * 2 ** 20
_LEAST = np.int32(-2 ** 31)
_MOST = np.int32(2 ** 31 - 1)


def takes(n, S, topk, blocks=BLOCKS):
    """Whether the kernel takes a block of n queries of a row of S tokens:
    whole chunks of whole int8 tiles, whole key tiles that are whole lane
    tiles, lane-wise partial sums a bf16 holds exactly, and what a grid
    step keeps in VMEM (a chunk's scores, mask and thresholds twice, the
    pipeline's two buffers, and its keys) within three quarters of the
    limit."""
    rows, tk = blocks
    kept = rows * (2 * (4 + 1) * S + 2 * 4 * _LANES + 4 * S)
    return (topk >= 1 and n % rows == 0 and rows % _MASK_ROWS == 0
            and S % tk == 0 and tk % _LANES == 0 and S // _LANES <= 256
            and 4 * kept <= 3 * _VMEM_LIMIT)


def _flip(bits):
    """The fold's second half, its own inverse: int32 bits of a float32
    (no -0.0) <-> int32 that orders as the floats do."""
    return jnp.where(bits < 0, bits ^ _MOST, bits)


def _fold(x):
    """float32 -> int32 that orders as the floats do (-0.0 as +0.0)."""
    bits = lax.bitcast_convert_type(x, I32)
    return _flip(jnp.where(bits == _LEAST, 0, bits))


def _unfold(key):
    return lax.bitcast_convert_type(_flip(key), F32)


def _lane_total(acc, lane_sum):
    """acc [rows, _LANES] int32 -> each row's sum in every lane."""
    if lane_sum == "lanes":
        return jnp.broadcast_to(jnp.sum(acc, axis=1, keepdims=True),
                                acc.shape)
    if lane_sum == "mxu":
        return jnp.dot(acc.astype(F32).astype(jnp.bfloat16),
                       jnp.ones((_LANES, _LANES), jnp.bfloat16),
                       preferred_element_type=F32).astype(I32)
    total = jnp.sum(acc.T, axis=0, keepdims=True)
    return jnp.broadcast_to(total, (_LANES, acc.shape[0])).T


def _kernel(first_ref, I_ref, mask_ref, tau_ref, keys, *, topk, tk,
            lane_sum):
    rows, S = I_ref.shape
    per = tk // _LANES
    row0 = first_ref[0] + pl.program_id(0) * rows       # the first query
    n_full = row0 // tk                 # key tiles wholly below the diagonal
    n_tiles = jnp.minimum((row0 + rows + tk - 1) // tk, S // tk)  # causal
    k = jnp.minimum(row0 + 1 + lax.broadcasted_iota(I32, (rows, _LANES), 0),
                    topk)

    def tile(c):
        return pl.ds(pl.multiple_of(c * tk, tk), tk)

    def lane_tile(c, j):
        return pl.ds(pl.multiple_of(c * tk + j * _LANES, _LANES), _LANES)

    def causal(c):
        """Whether a key of tile c is at or below a query's diagonal."""
        return (c * tk + lax.broadcasted_iota(I32, (rows, tk), 1)
                <= row0 + lax.broadcasted_iota(I32, (rows, tk), 0))

    def count(cand, strict):
        def body(c, acc):
            for j in range(per):
                key = keys[:, lane_tile(c, j)]
                hit = key > cand if strict else key >= cand
                acc = acc + jnp.where(hit, 1, 0)
            return acc

        return _lane_total(lax.fori_loop(
            0, n_tiles, body, jnp.zeros((rows, _LANES), I32)), lane_sum)

    def write(c, chosen):
        mask_ref[:, tile(c)] = jnp.where(chosen, 1, 0).astype(jnp.int8)

    all_chosen = row0 + rows <= topk

    @pl.when(all_chosen)
    def _every_causal_key():
        def body(c, least):
            below = causal(c)
            key = jnp.where(below, _fold(I_ref[:, tile(c)]), _MOST)
            write(c, below)
            for j in range(per):
                least = jnp.minimum(least,
                                    key[:, j * _LANES:(j + 1) * _LANES])
            return least

        least = lax.fori_loop(0, n_tiles, body,
                              jnp.full((rows, _LANES), _MOST, I32))
        tau_ref[...] = _unfold(jnp.broadcast_to(
            jnp.min(least, axis=1, keepdims=True), least.shape))

    @pl.when(jnp.logical_not(all_chosen))
    def _search():
        def fold(c, _):
            keys[:, tile(c)] = _fold(I_ref[:, tile(c)])

        def fold_crossed(c, _):
            keys[:, tile(c)] = jnp.where(causal(c), _fold(I_ref[:, tile(c)]),
                                         _LEAST)

        lax.fori_loop(0, n_full, fold, None)
        lax.fori_loop(n_full, n_tiles, fold_crossed, None)

        def bit(i, carry):
            prefix, found = carry
            # the least int32 is the uint32 key 0: its top bit set wraps
            # to the int32 0, every lower bit adds
            cand = prefix + lax.shift_left(jnp.int32(1), 31 - i)
            n = count(cand, False)
            enough = n >= k
            return (jnp.where(enough, cand, prefix),
                    jnp.where(enough, n, found))

        tau, found = lax.fori_loop(
            0, 32, bit, (jnp.full((rows, _LANES), _LEAST, I32),
                         jnp.full((rows, _LANES), _MOST, I32)))
        tau_ref[...] = _unfold(tau)
        # some row has more keys at its threshold than it may keep
        ties = jnp.max(found - k) > 0

        @pl.when(jnp.logical_not(ties))
        def _at_or_above():
            tau_t = jnp.tile(tau, (1, per))        # [rows, tk]
            lax.fori_loop(
                0, n_tiles,
                lambda c, _: write(c, keys[:, tile(c)] >= tau_t), None)

        @pl.when(ties)
        def _ties_to_the_lower_position():
            left = (k - count(tau, True)).astype(F32)
            upto = (lax.broadcasted_iota(I32, (_LANES, _LANES), 0)
                    <= lax.broadcasted_iota(I32, (_LANES, _LANES), 1))
            upto = jnp.where(upto, 1.0, 0.0).astype(jnp.bfloat16)
            ones = jnp.ones((_LANES, _LANES), jnp.bfloat16)

            def body(c, seen):
                for j in range(per):
                    key = keys[:, lane_tile(c, j)]
                    at = key == tau
                    at_b = jnp.where(at, 1.0, 0.0).astype(jnp.bfloat16)
                    rank = seen + jnp.dot(at_b, upto,
                                          preferred_element_type=F32)
                    mask_ref[:, lane_tile(c, j)] = jnp.where(
                        (key > tau) | (at & (rank <= left)), 1, 0
                    ).astype(jnp.int8)
                    seen = seen + jnp.dot(at_b, ones,
                                          preferred_element_type=F32)
                return seen

            lax.fori_loop(0, n_tiles, body, jnp.zeros((rows, _LANES), F32))

    def nothing(c, _):
        mask_ref[:, tile(c)] = jnp.zeros((rows, tk), jnp.int8)

    lax.fori_loop(n_tiles, S // tk, nothing, None)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5), inline=True)
def select_rows(I, first, topk, blocks=BLOCKS, lane_sum=LANE_SUMS[0],
                interpret=None):
    """I [n, S] float32, the scores of queries first .. first + n - 1
    (`first` may be traced: a scan's step) -> (mask [n, S] int8: 1 on the
    causal keys while there are no more than `topk`, else on the `topk`
    causal keys of largest score, ties to the lower position; the
    THRESHOLD [n] float32, the least score chosen): the plain
    `sparse_index.select_rows`'s, bit for bit. n, S as `takes`."""
    n, S = I.shape
    rows, tk = blocks

    def chunk(width):
        return pl.BlockSpec((rows, width), lambda i, first_ref: (i, 0))

    mask, tau = pl.pallas_call(
        functools.partial(_kernel, topk=topk, tk=tk, lane_sum=lane_sum),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // rows,), in_specs=[chunk(S)],
            out_specs=[chunk(S), chunk(_LANES)],
            scratch_shapes=[pltpu.VMEM((rows, S), I32)]),
        out_shape=[jax.ShapeDtypeStruct((n, S), jnp.int8),
                   jax.ShapeDtypeStruct((n, _LANES), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret() if interpret is None else interpret,
        name=KERNELS[0],
    )(jnp.reshape(first, (1,)).astype(I32), I)
    return mask, tau[:, 0]
