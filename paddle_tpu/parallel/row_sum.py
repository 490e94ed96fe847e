"""Rows summed by id into a dense table, as a Pallas TPU kernel: the
gradient of an embedding (ops/sparse_ops.py: lookup_table_grad, dense
path),

    sum_rows_by_id(ids [T], rows [T, H], V) -> [V, H] float32
        = zeros([V, H]).at[ids].add(rows)

XLA lowers that `.at[].add` to a sort of the ids, a gather of the rows into
sorted order and a `scatter(indices_are_sorted=true)`: every update row a
read-modify-write against the table where it lies. That is fast exactly
where the table is assigned to the chip's fast memory (`S(1)`: Laguna's
103 MB, 0.13 us a row) and 0.4 to 1.9 us a row where it lies in HBM
(PERF.md, PR 38). Here the table is written once, a tile of `R` rows at a
time from VMEM:

  the ids are sorted (stable) and the rows gathered into that order, so the
  rows of one TILE of R consecutive table rows are one contiguous run; the
  runs' boundaries (`_bounds`) and the sorted ids go to the kernel as scalar
  prefetch;
  the kernel's grid runs over the table's tiles, the output block [R, H] in
  VMEM: zero it, copy the tile's run from HBM in chunks of `C` rows (two
  buffers, a dynamic trip count: with Zipf ids one tile's run is a seventh
  of all rows while most hold a few or none), add each row into row
  `id - tile * R` of the block in float32, and let the pipeline write the
  block out.

Float32 sums; the additions of one id run in the order of the rows'
positions (the sort is stable), which is the order XLA's sorted scatter
takes them in. An id outside [0, V) adds nothing, as in `.at[].add`
(negative ids count from the end there and here).

Since PR 40 the kernel has a second caller, `sum_sorted_rows` behind an
ordering of the caller's own: the bounded sums of an expert layer that holds a share
of its experts (ops/lm_ops.py: `_sum_by_token`, the combine and the
dispatch's backward), [B, H] sorted choice rows summed by token into [T,
H]. There the rows come as bf16, are widened a chunk at a time and weighed
by a scalar a row (the combine's), and the float32 sums are written as
bf16; `takes_choices` says which shapes.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["sum_rows_by_id", "sum_sorted_rows", "sort_by_id", "with_tail",
           "takes", "takes_choices"]

# the kernel's name: Pallas puts it on the name stack, so a device trace
# reads the call under `embed/lookup_table_grad/row_tile_sum`,
# `moe/moe_ffn/combine/row_tile_sum`, `moe/moe_ffn_grad/dispatch/row_tile_sum`
KERNEL = "row_tile_sum"
_VMEM_LIMIT = 64 * 2 ** 20       # of the v5e's 128 MiB
_ROW_TILES = (512, 256, 128)     # table rows a tile, tried in this order
_CHUNK_ROWS = 64
_ALIGN = 8           # rows of a float32 sublane tile
# most ids a call takes: they lie in SMEM whole, beside the boundaries
_MOST_IDS = 32768

# The smallest table the kernel takes, in bytes: the v5e's fast memory. A
# smaller table's gradient XLA may assign to `S(1)` (Laguna's 103 MB in its
# step and in this sweep's `consumed` form; 151 MB in neither), and there
# its sorted scatter pays 0.13 us a row. The readings that set it and the
# tiles (tools/embed_grad_sweep.py on the v5e, PERF.md PR 38; Zipf(1.1)
# ids, 8 runs a dispatch, ms a run of XLA's scatter / the kernel with its
# sort and gather, the table the result | a temporary an update reads):
#   SmallThinker [37984, 2560] 389 MB, 8192 ids  15.37 / 1.17 | 17.08 / 2.87
#   OLMoE        [50304, 2048] 412 MB, 8192       3.10 / 1.12 |  4.90 / 2.92
#   Xing         [16384, 3584] 235 MB, 4096       3.71 / 0.58 |  4.72 / 1.60
#                [24576, 2048] 201 MB, 8192       1.81 / 0.78 |  2.69 / 1.67
#                [18432, 2048] 151 MB, 8192       1.50 / 0.70 |  2.18 / 1.38
#   Laguna       [12544, 2048] 103 MB, 8192       1.21 / 0.63 |  1.39 / 1.09
# The kernel is the faster at every size standing alone; under the fast
# memory's size the rule keeps XLA's path all the same, because what the
# step's clip and update gain from reading a gradient that never left
# `S(1)` is not in a sweep of the op alone (Laguna's whole scope reads 1.06
# ms in its step: PERF.md section 7).
MIN_TABLE_BYTES = 128 * 2 ** 20


def on_tpu():
    """Whether the step being traced is compiled for a TPU place."""
    return not pallas_interpret()


def tiles_for(H):
    """(R table rows a tile, C rows a chunk) for a table of rows of H,
    None where no tile fits VMEM (the output block twice, the pipeline's
    two buffers, and two chunks). From the sweep at SmallThinker's shape
    (ms a run, R x C): 128 x 64 1.59, 256 x 32 1.19, 256 x 64 1.23, 256 x
    128 1.41, 512 x 64 1.17, 512 x 128 1.21, 1024 x 128 1.16; the other
    shapes order them alike. A tile's write-back (5 MB) hides the ~0.35 us
    a grid step costs; a tile holds ~30 rows of 8192 Zipf ids and copies
    whole chunks, so a long chunk reads rows it does not add."""
    for R in _ROW_TILES:
        if 4 * H * 2 * (R + _CHUNK_ROWS) <= _VMEM_LIMIT - 2 ** 22:
            return R, _CHUNK_ROWS
    return None


def takes(V, H, T, dtype):
    """Whether the kernel forms the [V, H] gradient of a table of `dtype`
    from T rows (None: not known yet, taken to fit), from the shapes alone:
    a float32 table of at least `MIN_TABLE_BYTES` whose rows are whole
    lane tiles, and ids that fit SMEM."""
    return (jnp.dtype(dtype) == jnp.float32 and H % 128 == 0
            and (T is None or 0 < T <= _MOST_IDS)
            and V * H * 4 >= MIN_TABLE_BYTES and tiles_for(H) is not None)


# Which bounded sums of a share-holding expert layer the kernel takes. The
# readings (tools/combine_sweep.py on the v5e, PERF.md PR 40; bf16 tables,
# the held experts' rows half of B, 8 runs a dispatch, ms a run inside a
# consumer `h + result`, the combine | the dispatch's backward):
#   [T, k, H] from B rows                k gathers    scatter-add  this kernel
#   SmallThinker [8192, 6, 2560] 24,576  2.66 | 2.65  6.00 | 5.85  1.61 | 1.43
#   Laguna       [8192, 8, 2048] 16,384  1.24 | 1.23  1.87 | 1.87  0.65 | 0.82
#   LFM2         [8192, 4, 2048] 16,384  0.70 | 0.69  1.87 | 1.87  0.65 | 0.82
#   Xing         [4096, 4, 3584]  4,096  0.48 | 0.48  1.47 | 1.34  0.40 | 0.38
# A call of the kernel form costs about what three to four gathers of T
# rows cost, whatever k: its one gather of B rows from HBM is most of it
# (0.81 ms of SmallThinker's, ~33 ns a 5 KB row against 8 ns out of `S(1)`).
# So it takes the layers that choose MORE than four experts a token; at k =
# 4 it is behind the gathers (LFM2: its cell read 64,398 -> 64,388 tokens/s
# with an earlier, faster form) or ahead by a tenth of a millisecond (Xing,
# whose step sits at the memory's edge and read 16.07 GB with the kernel's
# gathered copy against 16.006 without).
def takes_choices(T, k, H, B, dtype):
    """Whether a share-holding expert layer sums the B < T * k bounded rows
    of its [B, H] table of `dtype` by token through the kernel
    (`lm_ops._sum_by_token`) and not by k gathers of T rows, from the
    shapes alone: more than four choices a token, rows of whole lane tiles
    in bf16 or float32, the B slots' tokens and weights in SMEM, and a
    slot and a choice in the 15 + 16 bits `lm_ops._by_token` packs them
    into."""
    return (k > 4 and H % 128 == 0 and tiles_for(H) is not None
            and jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and B < T * k <= 2 ** 16 and B <= _MOST_IDS)


def _bounds(sorted_ids, V, R):
    """[ceil(V / R) + 1] int32: where each tile's run starts among the
    sorted ids, and where the last one ends (ids >= V lie past it)."""
    n_tiles = -(-V // R)
    starts = jnp.minimum(jnp.arange(n_tiles + 1, dtype=jnp.int32) * R, V)
    return jnp.sum(sorted_ids[None, :] < starts[:, None], axis=1,
                   dtype=jnp.int32)


def _kernel(*refs, R, C, weighted, narrow_out, align):
    bounds, ids, *refs = refs
    w = refs.pop(0) if weighted else None
    rows, out, buf, sem, *scratch = refs
    # float32 sums: in the output block itself where that is float32, in a
    # block of scratch where it is narrower; rows narrower than float32 are
    # widened a chunk at a time
    total = scratch.pop(0) if narrow_out else out
    wide = scratch.pop(0) if scratch else None
    t = pl.program_id(0)
    lo, hi = bounds[t], bounds[t + 1]
    first = lo // align * align
    n_chunks = jnp.where(hi > lo, (hi - first + C - 1) // C, 0)
    total[...] = jnp.zeros_like(total)

    def copy(c, slot):
        start = pl.multiple_of(first + c * C, align)
        return pltpu.make_async_copy(rows.at[pl.ds(start, C)], buf.at[slot],
                                     sem.at[slot])

    @pl.when(n_chunks > 0)
    def _():
        copy(0, 0).start()

    def chunk(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            copy(c + 1, 1 - slot).start()

        copy(c, slot).wait()
        start = first + c * C
        if wide is not None:
            wide[...] = buf[slot].astype(jnp.float32)

        def row(j, carry):
            r = ids[j] - t * R
            at = pl.ds(j - start, 1)
            v = buf[slot, at, :] if wide is None else wide[at, :]
            total[pl.ds(r, 1), :] += v * w[j] if weighted else v
            return carry

        return lax.fori_loop(jnp.maximum(lo, start),
                             jnp.minimum(hi, start + C), row, carry)

    lax.fori_loop(0, n_chunks, chunk, 0)
    if narrow_out:
        out[...] = total[...].astype(out.dtype)


def sort_by_id(ids, V):
    """(order [T], the ids in that order [T] int32): the stable sort the
    kernel's rows are gathered by. A negative id counts from the end."""
    ids = ids.astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + V, ids)
    order = jnp.argsort(ids, stable=True)
    return order, ids[order]


def with_tail(order, C):
    """`order` and C more entries: a chunk is C whole rows from an aligned
    start, and the last may reach past the sorted rows, into a tail the
    gather fills with row 0."""
    return jnp.concatenate([order, jnp.zeros((C,), order.dtype)])


def sum_rows_by_id(ids, rows, V, tiles=None, interpret=None):
    """ids [T] integers, rows [T, H] -> the [V, H] float32 table of the
    rows summed by id. `tiles`: (R, C), `tiles_for`'s when None."""
    R, C = tiles or tiles_for(rows.shape[1])
    order, sorted_ids = sort_by_id(ids, V)
    sorted_rows = rows[with_tail(order, C)].astype(jnp.float32)
    return sum_sorted_rows(sorted_ids, sorted_rows, V, (R, C), interpret)


def sum_sorted_rows(sorted_ids, sorted_rows, V, tiles, interpret=None,
                    weights=None, out_dtype=jnp.float32):
    """The kernel: sorted_rows [T + C, H] float32 or bfloat16 (times
    weights [T + C] float32), row i of the first T of id sorted_ids[i]
    (the ids of a tile of R in one run, the runs in the tiles' order;
    ascending ids are that) -> [V, H], float32 sums, written as
    `out_dtype`."""
    R, C = tiles
    H = sorted_rows.shape[1]
    T = sorted_ids.shape[0]
    n_tiles = -(-V // R)
    if interpret is None:
        interpret = pallas_interpret()
    out_dtype, in_dtype = jnp.dtype(out_dtype), sorted_rows.dtype
    narrow_out, narrow_in = out_dtype != jnp.float32, in_dtype != jnp.float32
    scratch = [pltpu.VMEM(shape, jnp.float32) for shape, narrow in (
        ((R, H), narrow_out), ((C, H), narrow_in)) if narrow]
    scalars = (_bounds(sorted_ids, V, R), sorted_ids) + (
        () if weights is None else (weights.astype(jnp.float32),))
    return pl.pallas_call(
        functools.partial(
            _kernel, R=R, C=C, weighted=weights is not None,
            narrow_out=narrow_out,
            # a chunk starts on a sublane tile of the rows
            align=_ALIGN * 4 // in_dtype.itemsize),
        out_shape=jax.ShapeDtypeStruct((V, H), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((R, H), lambda t, *scalars: (t, 0)),
            grid=(n_tiles,),
            scratch_shapes=[pltpu.VMEM((2, C, H), in_dtype),
                            pltpu.SemaphoreType.DMA((2,)), *scratch]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=T * H, transcendentals=0,
            bytes_accessed=H * (out_dtype.itemsize * V
                                + in_dtype.itemsize * T)),
        interpret=interpret,
        name=KERNEL,
    )(*scalars, sorted_rows)
