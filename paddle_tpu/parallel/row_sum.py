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
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["sum_rows_by_id", "takes"]

# the kernel's name: Pallas puts it on the name stack, so a device trace
# reads the call under `embed/lookup_table_grad/row_tile_sum`
KERNEL = "row_tile_sum"
_VMEM_LIMIT = 64 * 2 ** 20       # of the v5e's 128 MiB
_ROW_TILES = (512, 256, 128)     # table rows a tile, tried in this order
_CHUNK_ROWS = 64
_ALIGN = 8           # a chunk starts on a float32 sublane tile of the rows
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


def _bounds(sorted_ids, V, R):
    """[ceil(V / R) + 1] int32: where each tile's run starts among the
    sorted ids, and where the last one ends (ids >= V lie past it)."""
    n_tiles = -(-V // R)
    starts = jnp.minimum(jnp.arange(n_tiles + 1, dtype=jnp.int32) * R, V)
    return jnp.sum(sorted_ids[None, :] < starts[:, None], axis=1,
                   dtype=jnp.int32)


def _kernel(bounds, ids, rows, out, buf, sem, *, R, C):
    t = pl.program_id(0)
    lo, hi = bounds[t], bounds[t + 1]
    first = lo // _ALIGN * _ALIGN
    n_chunks = jnp.where(hi > lo, (hi - first + C - 1) // C, 0)
    out[...] = jnp.zeros_like(out)

    def copy(c, slot):
        start = pl.multiple_of(first + c * C, _ALIGN)
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

        def row(j, carry):
            r = ids[j] - t * R
            out[pl.ds(r, 1), :] += buf[slot, pl.ds(j - start, 1), :]
            return carry

        return lax.fori_loop(jnp.maximum(lo, start),
                             jnp.minimum(hi, start + C), row, carry)

    lax.fori_loop(0, n_chunks, chunk, 0)


def sum_rows_by_id(ids, rows, V, tiles=None, interpret=None):
    """ids [T] integers, rows [T, H] -> the [V, H] float32 table of the
    rows summed by id. `tiles`: (R, C), `tiles_for`'s when None."""
    T, H = rows.shape
    R, C = tiles or tiles_for(H)
    ids = ids.astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + V, ids)
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    # a chunk is C whole rows from an aligned start: the last may reach
    # past the T rows, into a tail the gather fills with row 0
    tail = jnp.zeros((C,), jnp.int32)
    sorted_rows = rows[jnp.concatenate([order, tail])].astype(jnp.float32)
    n_tiles = -(-V // R)
    if interpret is None:
        interpret = pallas_interpret()
    return pl.pallas_call(
        functools.partial(_kernel, R=R, C=C),
        out_shape=jax.ShapeDtypeStruct((V, H), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((R, H), lambda t, bounds, ids: (t, 0)),
            grid=(n_tiles,),
            scratch_shapes=[pltpu.VMEM((2, C, H), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=T * H, transcendentals=0,
            bytes_accessed=4 * H * (V + T)),
        interpret=interpret,
        name=KERNEL,
    )(_bounds(sorted_ids, V, R), sorted_ids, sorted_rows)
