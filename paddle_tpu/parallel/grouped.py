"""Grouped matrix products as Pallas TPU kernels: rows of `lhs` sorted by
group, group g's rows times its own matrix `rhs[g]`, no capacity and no
padding of a group (a sparse-expert layer's three products,
ops/lm_ops.py: moe_ffn).

    grouped_dot(lhs [N, K], rhs [E, K, M], counts [E]) -> [N, M]

is what `lax.ragged_dot` computes, and on any place but a TPU, or for
shapes the kernels refuse, it IS `lax.ragged_dot`. On a TPU place it is
three kernels behind one `custom_vjp`:

  forward   out[rows of g] = lhs[rows of g] @ rhs[g]           (`_gmm`)
  d lhs     the same kernel, the weight tile contracted over its LAST
            dimension: it reads the forward's `rhs` as it lies
  d rhs     drhs[g] = lhs[rows of g]^T @ dout[rows of g]       (`_tgmm`),
            reading `lhs` and `dout` as stored

    grouped_mlp(xs, gate, up, down, counts) -> ys, a, b

is the layer's whole MLP, ys = (silu(xs @ gate[g]) * (xs @ up[g])) @
down[g]: the same nine kernels a step behind ONE `custom_vjp`, with the
element-wise work between the products done on the tiles the kernels hold
in VMEM (PR 31): `_gmm` takes row-tiled side operands and stores an
epilogue of its float32 product (SiLU * up behind the up product; SiLU's
backward behind the d h product, whose own result is never written; the
second d xs product added onto the first, in place), `_tgmm` forms its
lhs tile silu(a) * b in front of d down. Composed of three `grouped_dot`s
those were XLA passes over [N, F] and [N, H] in HBM between the kernels:
at [65536, 2048] x [64, 2048, 1024] bf16 and a seeded step's group sizes
(tools/grouped_sweep.py epilogues; ms, the kernel alone / with the work in
it / alone and then the pass): up 1.78 / 1.86 / 2.55, d h 1.78 / 1.89 /
2.99, second d xs 1.82 / 1.85 / 3.04, d down 1.96 / 2.15 / 2.74; holding 8
of 64 experts at [16384, 3584] (tools/grouped_share_sweep.py --epilogues)
0.16 / 0.18 / 0.32, 0.16 / 0.18 / 0.42, 0.18 / 0.19 / 0.71, 0.21 / 0.22 /
0.37 (PERF.md, PR 31). The VPU work is not hidden under the MXU: with
even groups, where the plain kernels run at 184 TFLOP/s, each pays 0.16
to 0.49 ms for it, still less than the pass.

Adapted from jax.experimental.pallas.ops.tpu.megablox (gmm / tgmm, jax
0.9.0), not imported: the library's kernels have no name (the trace and
the benchmark's scopes could not tell them from any other Mosaic call),
leave VMEM at Mosaic's default scoped 16 MiB (no whole-K weight tile), and
its `tgmm` masks BOTH operands of EVERY tile in float32 and transposes the
float32 copy. What is dropped: sharded groups (`group_offset`), a K
remainder (`existing_out` came back in PR 31 as the "add" epilogue). XLA's
own lowering of `ragged_dot` (`ragged-dot-none`) takes its right operand
in one orientation only, so a training step held two bf16 copies of every
expert weight, and its row tile (512) is not the program's to choose
(PERF.md, PR 29).

Rows are walked in tiles of `tm`. A tile that a group boundary crosses is
visited once for each group that has rows in it (N / tm + boundaries
inside tiles visits, at most N / tm + E - 1), and such a visit computes
only the 128-row blocks of the tile that hold rows of its group, the rows
of other groups in them masked. The list of visits (group, row tile) is made from `counts`
on the device and handed to the kernels as scalar prefetch: their index
maps read it, so consecutive visits of one group find that group's weight
tile already in VMEM.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["grouped_dot", "grouped_matmul", "grouped_mlp", "tiles_for"]

# The kernels' names: Pallas puts a kernel's name on the name stack, so a
# device trace's op_name ends `.../grouped_matmul/pallas_call`
# (chipbench/layer_metrics/grouped_matmul_roofline.py finds them by these).
# JAX writes the first scope opened inside a function it differentiates as
# `jvp(<scope>)`, which chipbench/scopes.py drops with all it wraps: the
# kernels are called under a scope of their own, `_SCOPE`, for JAX to wrap
# where nothing else stands in front, and their names come after it. Under
# `moe_ffn_grad` the op's own `vjp` scope is the wrapped one (`lm_ops._VJP`),
# so the keys read `moe/moe_ffn/grouped/grouped_matmul` forward and
# `moe/moe_ffn_grad/grouped/grouped_matmul_nt` backward.
KERNELS = ("grouped_matmul", "grouped_matmul_nt", "grouped_matmul_tn")
_SCOPE = "grouped"
# the zeroing passes are filed with the row gathers around the kernels
# (`ops/lm_ops.py: DISPATCH`): what moving a bounded number of rows costs
ZEROING = "dispatch"

_VMEM_LIMIT = 96 * 2 ** 20       # of the v5e's 128 MiB
ROW_TILES = (512, 256, 128)      # tried in this order: `tiles_for`
_BLOCK_ROWS = 128                # of a tile that a group boundary crosses
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def on_tpu():
    """Whether the step being traced is compiled for a TPU place."""
    return not pallas_interpret()


def visits(counts, n_rows, tm, empty_groups):
    """The kernels' work list from the group sizes: (offsets [E + 1],
    group of each visit, row tile of each visit), both [n_rows // tm + E -
    1] int32, and the number of visits that are real (the grid's extent;
    the lists' tails repeat the last group and tile). A row tile is visited
    by each group that has rows in it, in group order, so the visits of a
    tile and the visits of a group are both consecutive. With
    `empty_groups` a group without rows gets one visit (the transposed
    product has to write its zeros)."""
    E = counts.shape[0]
    tiles_m = n_rows // tm
    ends = jnp.cumsum(counts)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    # tiles a group touches: from its start rounded down to its end
    # rounded up
    group_tiles = jnp.where(
        counts == 0, 0, (ends + tm - 1) // tm - starts // tm)
    if empty_groups:
        group_tiles = jnp.where(counts == 0, 1, group_tiles)
    n = tiles_m + E - 1
    group_ids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), group_tiles,
                           total_repeat_length=n)
    # a tile is visited once by the group that owns its first row, and
    # once more for each group that starts inside it (or, empty and
    # counted, sits inside it)
    extra = (starts % tm != 0) & (counts != 0)
    if empty_groups:
        extra = extra | (counts == 0)
    tile_visits = jnp.zeros(tiles_m, jnp.int32).at[
        jnp.where(extra, starts // tm, tiles_m)].add(1, mode="drop") + 1
    # an empty group at the very end of the rows sits in no tile: its visit
    # reads the last one
    tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), tile_visits,
                          total_repeat_length=n)
    return (offsets, group_ids, tile_ids), group_tiles.sum()


def _visit(offsets, group_ids, tile_ids, visit, tm):
    """Of a visit: the first and one-past-last row of its group, the first
    row of its tile, and whether every row of the tile is the group's."""
    g = group_ids[visit]
    lo, hi, row0 = offsets[g], offsets[g + 1], tile_ids[visit] * tm
    return lo, hi, row0, (lo <= row0) & (hi >= row0 + tm)


def _blocks_of_group(lo, hi, row0, tm, body):
    """`body(rows, mask_of)` for each block of `_BLOCK_ROWS` rows of the
    tile that holds rows of the group [lo, hi): `rows` the block's slice
    of the tile, `mask_of(width)` its [rows, width] mask of the group's
    rows. A tile a boundary crosses costs the blocks the group touches,
    not the whole tile. One loop body, not a copy a block: the kernels'
    code is held in HBM too (unrolled four ways it added 16 MB to the
    OLMoE step's program, PERF.md PR 29)."""
    n = min(tm, _BLOCK_ROWS)
    begin = (jnp.maximum(lo, row0) - row0) // n
    end = (jnp.minimum(hi, row0 + tm) - row0 + n - 1) // n

    def block(b, carry):
        start = pl.multiple_of(b * n, n)

        def mask_of(width):
            i = lax.broadcasted_iota(jnp.int32, (n, width), 0) + row0 + start
            return (i >= lo) & (i < hi)

        body(pl.ds(start, n), mask_of)
        return carry

    lax.fori_loop(begin, jnp.where(hi > lo, end, begin), block, None)


def _silu_mul(p, a):
    """The up product's epilogue: b = p, h = silu(a) * p."""
    return p, jax.nn.silu(a) * p


def _silu_mul_grad(p, a, b):
    """The d h product's epilogue, p = d h: d a = p * b * silu'(a), d b =
    p * silu(a)."""
    s = jax.nn.sigmoid(a)
    return p * b * (s * (1.0 + a * (1.0 - s))), p * (a * s)


def _relu_mul(p, a):
    """`_silu_mul` for experts gated by ReLU: b = p, h = relu(a) * p."""
    return p, jnp.maximum(a, 0.0) * p


def _relu_mul_grad(p, a, b):
    """`_silu_mul_grad` for ReLU, p = d h: d a = p * b where a > 0, d b =
    p * relu(a). No transcendental."""
    return jnp.where(a > 0.0, p * b, 0.0), p * jnp.maximum(a, 0.0)


def _relu_sq(p):
    """The up product's epilogue of an UN-GATED expert relu(x U)^2 D: a =
    p, h = relu(p)^2."""
    return p, jnp.square(jnp.maximum(p, 0.0))


def _relu_sq_grad(p, a):
    """The d h product's epilogue of relu(a)^2, p = d h: d a = 2 relu(a) p.
    No transcendental."""
    return (2.0 * jnp.maximum(a, 0.0) * p,)


def _add(p, existing):
    return (existing + p,)


# epilogues of `_gmm`: what is stored from the float32 product tile p and
# the side tiles (widened to float32), and how many tiles of each
_EPILOGUES = {None: (lambda p: (p,), 0, 1), "silu_mul": (_silu_mul, 1, 2),
              "silu_mul_grad": (_silu_mul_grad, 2, 2), "add": (_add, 1, 1),
              "relu_mul": (_relu_mul, 1, 2),
              "relu_mul_grad": (_relu_mul_grad, 2, 2),
              "relu_sq": (_relu_sq, 0, 2),
              "relu_sq_grad": (_relu_sq_grad, 1, 1)}
# what gates an expert (`grouped_mlp`'s `activation`), on float32 tiles;
# "relu2" is no gate: the un-gated expert's own activation relu(a)^2
RELU2 = "relu2"
_ACTIVATIONS = {"silu": jax.nn.silu, "relu": lambda a: jnp.maximum(a, 0.0),
                RELU2: lambda a: jnp.square(jnp.maximum(a, 0.0))}


def _named(epilogue):
    """The scope a kernel with this epilogue is called under: ReLU's name
    is on the op_name's path (`.../grouped/relu_mul/grouped_matmul`);
    SiLU's, the kernels' first, stand where they have always stood."""
    if epilogue in ("relu_mul", "relu_mul_grad", "relu_sq", "relu_sq_grad"):
        return jax.named_scope(epilogue)
    return contextlib.nullcontext()


def _gmm_kernel(offsets, group_ids, tile_ids, lhs, rhs, *refs, tm, tn,
                tiles_k, dims, epilogue):
    """One visit: the tile's rows of the visit's group times the group's
    weight tile. With all of K in one tile (`acc` empty) the product goes
    straight to the out blocks; else through float32 sums in `acc`. What
    is stored is the `epilogue` of the float32 product and the side tiles
    (`refs`: the sides, the outs, `acc`)."""
    values_of, n_sides, n_outs = _EPILOGUES[epilogue]
    sides, outs = refs[:n_sides], refs[n_sides:n_sides + n_outs]
    acc = refs[n_sides + n_outs:]
    visit, k_i = pl.program_id(1), pl.program_id(2)
    lo, hi, row0, whole = _visit(offsets, group_ids, tile_ids, visit, tm)
    if acc:
        @pl.when(k_i == 0)
        def _():
            acc[0][...] = jnp.zeros((tm, tn), jnp.float32)

    def rows_times_weights(rows, mask_of=None):
        p = lax.dot_general(lhs[rows, :], rhs[...], dims,
                            preferred_element_type=jnp.float32)
        if acc:
            acc[0][rows, :] += p

        def store():
            values = values_of(
                acc[0][rows, :] if acc else p,
                *(s[rows, :].astype(jnp.float32) for s in sides))
            for out, value in zip(outs, values):
                if mask_of is not None:
                    # the tile's other rows were, or will be, written by
                    # the visits of their own groups: the out block stays
                    # in VMEM between them
                    value = jnp.where(mask_of(tn), value,
                                      out[rows, :].astype(jnp.float32))
                out[rows, :] = value.astype(out.dtype)

        if acc:
            pl.when(k_i == tiles_k - 1)(store)
        else:
            store()

    pl.when(whole)(lambda: rows_times_weights(pl.ds(0, tm)))
    pl.when(jnp.logical_not(whole))(lambda: _blocks_of_group(
        lo, hi, row0, tm, rows_times_weights))


def _gmm(lhs, rhs, meta, n_visits, tiles, transpose_rhs, epilogue=None,
         sides=()):
    with _named(epilogue):
        return _gmm_call(lhs, rhs, meta, n_visits, sides, tiles,
                         transpose_rhs, epilogue, pallas_interpret())


# The kernel calls stand under an inlined `jit`: a call is traced once for
# each (shapes, tiles, epilogue) and its equations are bound at every call
# site as if traced there (the same names in the device trace). A step
# makes the same call once a sparse layer and once a branch of each
# layer's `cond` (lm_ops: `moe_ffn`), and tracing a Pallas kernel's body is
# the expensive part of lowering a step on the chip's host: a Laguna step
# of 84 grouped kernels traced for 17.3 s against the 9.5 s of the 36
# before the `cond`, and for 9.6 s with this (PERF.md, PR 33).
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8), inline=True)
def _gmm_call(lhs, rhs, meta, n_visits, sides, tiles, transpose_rhs,
              epilogue, interpret):
    """[N, K] x [E, K, M] -> [N, M] (`transpose_rhs`: rhs is [E, M, K],
    contracted over its last dimension). `epilogue` and its `sides` [N, M],
    read by row tiles as the out is written: "silu_mul" (a) -> (the
    product, silu(a) * product); "silu_mul_grad" (a, b) -> (d a, d b) of
    silu(a) * b, the product being its cotangent; "add" (existing) ->
    existing + product, written over `existing` in place; "relu_mul" and
    "relu_mul_grad" the same two for relu(a) * b. All taken on the float32
    product and rounded once."""
    tm, tk, tn = tiles
    (N, K), E = lhs.shape, rhs.shape[0]
    M = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles_k, tiles_n = K // tk, M // tn
    n_outs = _EPILOGUES[epilogue][2]

    def lhs_at(n_i, v, k_i, offsets, group_ids, tile_ids):
        return tile_ids[v], k_i

    def rhs_at(n_i, v, k_i, offsets, group_ids, tile_ids):
        if transpose_rhs:
            return group_ids[v], n_i, k_i
        return group_ids[v], k_i, n_i

    def out_at(n_i, v, k_i, offsets, group_ids, tile_ids):
        return tile_ids[v], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    item = lhs.dtype.itemsize
    max_visits = meta[1].shape[0]
    out_shape = jax.ShapeDtypeStruct((N, M), lhs.dtype)
    outs = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          dims=_NT if transpose_rhs else _NN,
                          epilogue=epilogue),
        out_shape=[out_shape] * n_outs,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_at),
                      pl.BlockSpec(rhs_block, rhs_at)]
            + [pl.BlockSpec((tm, tn), out_at)] * len(sides),
            out_specs=[pl.BlockSpec((tm, tn), out_at)] * n_outs,
            grid=(tiles_n, n_visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if tiles_k > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * M,
            transcendentals=N * M if "silu" in (epilogue or "") else 0,
            bytes_accessed=item * (
                N * K * tiles_n + N * M * (len(sides) + n_outs)
                + K * M * (E if tiles_k == 1 else max_visits))),
        # operands are counted with the three scalar-prefetch lists
        input_output_aliases={5: 0} if epilogue == "add" else {},
        interpret=interpret,
        name=KERNELS[1] if transpose_rhs else KERNELS[0],
    )(*meta, lhs, rhs, *sides)
    return outs[0] if n_outs == 1 else outs


def _tgmm_kernel(offsets, group_ids, tile_ids, *refs, tm, tk, tn, mask_lhs,
                 activation):
    """One visit: the tile's rows of the visit's group, lhs^T times rhs,
    summed in `acc` over the group's visits and written at its last.
    `refs`: lhs, or the two tiles a, b that lhs = `activation`(a) * b is
    formed from here (one tile a under "relu2": lhs = relu(a)^2); rhs, out,
    `acc`."""
    *lhs, rhs, out, acc = refs
    visit = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g = group_ids[visit]
    lo, hi, row0, whole = _visit(offsets, group_ids, tile_ids, visit, tm)

    @pl.when((visit == 0) | (group_ids[jnp.maximum(visit - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    def rows_transposed_times_rows(rows, mask_of=None):
        a, b = lhs[0][rows, :], rhs[rows, :]
        if len(lhs) == 2:
            a = (_ACTIVATIONS[activation](a.astype(jnp.float32))
                 * lhs[1][rows, :].astype(jnp.float32)).astype(a.dtype)
        elif activation == RELU2:
            a = _ACTIVATIONS[RELU2](a.astype(jnp.float32)).astype(a.dtype)
        # rows of other groups zeroed in ONE operand: a zero row of either
        # contributes nothing
        if mask_of is not None and mask_lhs:
            a = jnp.where(mask_of(tk), a, jnp.zeros_like(a))
        elif mask_of is not None:
            b = jnp.where(mask_of(tn), b, jnp.zeros_like(b))
        acc[...] += lax.dot_general(a, b, _TN,
                                    preferred_element_type=jnp.float32)

    pl.when(whole)(lambda: rows_transposed_times_rows(pl.ds(0, tm)))
    pl.when(jnp.logical_not(whole))(lambda: _blocks_of_group(
        lo, hi, row0, tm, rows_transposed_times_rows))

    @pl.when((visit == last) | (group_ids[jnp.minimum(visit + 1, last)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


def _tgmm(lhs, rhs, meta, n_visits, tiles, out_dtype, mask_lhs=None,
          activation="silu"):
    lhs = lhs if isinstance(lhs, tuple) else (lhs,)
    with _named("relu_sq" if activation == RELU2 else
                activation + "_mul" if len(lhs) == 2 else None):
        return _tgmm_call(lhs, rhs, meta, n_visits, tiles,
                          jnp.dtype(out_dtype), mask_lhs, pallas_interpret(),
                          activation)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8), inline=True)
def _tgmm_call(lhs, rhs, meta, n_visits, tiles, out_dtype, mask_lhs,
               interpret, activation):
    """[N, K], [N, M] -> [E, K, M]: group g's rows of lhs, transposed,
    times its rows of rhs. `lhs` a pair (a, b): lhs is `activation`(a) *
    b, formed from the two tiles in VMEM; one array under `activation`
    "relu2": lhs is relu(a)^2, formed likewise (under any other name one
    array is lhs as it stands). `mask_lhs`: which operand has the
    rows of other groups zeroed in a tile a boundary crosses; the narrower
    tile unless said (rows past the groups must be FINITE in the other
    one: a zero row times a NaN is a NaN)."""
    tm, tk, tn = tiles
    (N, K), M = lhs[0].shape, rhs.shape[1]
    E = meta[0].shape[0] - 1
    tiles_k, tiles_n = K // tk, M // tn

    def lhs_at(n_i, k_i, v, offsets, group_ids, tile_ids):
        return tile_ids[v], k_i

    def rhs_at(n_i, k_i, v, offsets, group_ids, tile_ids):
        return tile_ids[v], n_i

    def out_at(n_i, k_i, v, offsets, group_ids, tile_ids):
        return group_ids[v], k_i, n_i

    item = rhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn,
                          mask_lhs=tk <= tn if mask_lhs is None
                          else mask_lhs, activation=activation),
        out_shape=jax.ShapeDtypeStruct((E, K, M), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_at)] * len(lhs)
            + [pl.BlockSpec((tm, tn), rhs_at)],
            out_specs=pl.BlockSpec((None, tk, tn), out_at),
            grid=(tiles_n, tiles_k, n_visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * M,
            transcendentals=N * K * tiles_n
            if len(lhs) == 2 and activation == "silu" else 0,
            bytes_accessed=item * (
                N * K * tiles_n * len(lhs) + N * M * tiles_k)
            + E * K * M * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
        name=KERNELS[2],
    )(*meta, *lhs, rhs)


def _rows_of_groups(x, counts):
    """x with the rows past the groups' last set to zero: the kernels
    never visit them, so what the result holds there is whatever the
    buffer held (`lax.ragged_dot` writes zeros itself)."""
    with jax.named_scope(ZEROING):
        rows = lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
        return jnp.where(rows < jnp.sum(counts), x, jnp.zeros((), x.dtype))


def _product(lhs, rhs, counts, tiles, rows_past):
    if tiles is None:
        return lax.ragged_dot(lhs, rhs, group_sizes=counts,
                              preferred_element_type=lhs.dtype)
    tm, fwd, _, _ = tiles
    with jax.named_scope(_SCOPE):
        meta, n = visits(counts, lhs.shape[0], tm, False)
        out = _gmm(lhs, rhs, meta, n, (tm,) + fwd, False)
        return _rows_of_groups(out, counts) if rows_past else out


def _gradients(lhs, rhs, counts, tiles, rows_past, g):
    if tiles is None:
        return jax.vjp(lambda a, b: _product(a, b, counts, None, rows_past),
                       lhs, rhs)[1](g)
    tm, _, dlhs, drhs = tiles
    with jax.named_scope(_SCOPE):
        meta, n = visits(counts, lhs.shape[0], tm, False)
        d_lhs = _gmm(g, rhs, meta, n, (tm,) + dlhs, True)
        if rows_past:
            d_lhs = _rows_of_groups(d_lhs, counts)
        meta, n = visits(counts, lhs.shape[0], tm, True)
        return d_lhs, _tgmm(lhs, g, meta, n, (tm,) + drhs, rhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(lhs, rhs, counts, out, tiles, rows_past=False):
    """`grouped_dot` with its lowering named: `tiles` = (tm, (tk, tn) of
    the forward, of d lhs, of d rhs) for the kernels (`tiles_for`; N a
    multiple of tm, K and M of their tiles), None for `lax.ragged_dot`;
    `rows_past`: the groups may end before the rows do."""
    if out is not None:
        return out
    return _product(lhs, rhs, counts, tiles, rows_past)


def _matmul_fwd(lhs, rhs, counts, out, tiles, rows_past):
    return (grouped_matmul(lhs, rhs, counts, out, tiles, rows_past),
            (lhs, rhs, counts))


def _matmul_bwd(tiles, rows_past, res, g):
    lhs, rhs, counts = res
    return (*_gradients(lhs, rhs, counts, tiles, rows_past,
                        g.astype(lhs.dtype)), None, None)


grouped_matmul.defvjp(_matmul_fwd, _matmul_bwd)


# a dimension of whole HALF lane tiles that is no multiple of a lane tile
# (an expert width of 1856 = 29 x 64) is worked WHOLE, as one tile (a block
# that spans its array's dimension needs no alignment: Mosaic pads its last
# half tile in VMEM), from past one lane tile up to this many numbers
_WHOLE_MOST = 2048


def _taken_whole(dim):
    return dim % 128 != 0 and dim % 64 == 0 and 128 < dim <= _WHOLE_MOST


def _largest_tile(dim, most):
    """The largest multiple of 128 that divides `dim` and is <= `most`;
    `dim` itself where it is no multiple of 128 and `_taken_whole`."""
    if _taken_whole(dim):
        return dim
    for t in range(min(dim, most) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return None


def takes(n_rows, k, m):
    """Whether the kernels take [n_rows, k] x [E, k, m] and its two
    gradients: K and M multiples of 128 (a lane tile) or, between 128 and
    2048, of 64 and then worked whole (an expert width of 1856 = 14.5 lane
    tiles: PR 54), the rows a multiple of the smallest row tile (`n_rows`
    None: not known yet, taken to fit)."""
    return all(d % 128 == 0 or _taken_whole(d) for d in (k, m)) and (
        n_rows is None or n_rows % ROW_TILES[-1] == 0)


def tiles_for(n_rows, k, m, dtype):
    """(tm, (tk, tn) forward, (tk, tn) of d lhs, (tk, tn) of d rhs) for
    [n_rows, k] x [E, k, m] in `dtype`, or None where the kernels do not
    take the shapes (`takes`). The rule, from two sweeps on the v5e at
    [65536, 2048] x [64, 2048, 1024] and [65536, 1024] x [64, 1024, 2048]
    bf16 (tools/grouped_sweep.py; PERF.md, PR 29; ms a call of the
    forward, a seeded step's real group sizes / even groups;
    `lax.ragged_dot` 2.96 / 2.04):

      K and M tiles: the whole dimension up to 8 KiB of a row (4096 bf16;
        4 KiB until PR 30, which met K = 3584: [16384, 3584] x [8, 3584,
        1024] over the 2052 rows the 8 groups held, forward and both
        gradients, 1.005 ms with K whole against 1.031 split in two, and
        the same to 0.03 ms for the row tiles 512, 256, 128; `lax.
        ragged_dot` 1.07; tools/grouped_share_sweep.py, PERF.md PR 30).
        With all of K in one tile a group's weight tile keeps its block
        index from visit to visit and is fetched once a group, and the
        product needs no float32 scratch; a K tile of 1024 fetches the
        weights again every visit (2.52 against 1.95 at tm 256, 2.72 at
        512), a narrower M tile reads the rows once more for each (2.08
        at 512, 2.51 at 256).
      row tile: the longest of 512, 256, 128 that divides the rows. Long
        tiles use the MXU better (even groups: 1.49 at 512, 1.74 at 256,
        1.92 at 128; 1024 no better, 1.50). With a boundary tile costing
        a whole masked visit a group, 256 was best on real groups (1.95;
        2.24 at 512, 2.01 at 128, 2.94 at 1024); walked in blocks of 128
        rows, only those the group touches (`_blocks_of_group`), 512 is
        (1.81; 1.86 at 256, 1.84 at 1024; blocks of 256: 1.90). The
        number of groups no longer enters: a boundary costs a block, not
        a tile.
      with an epilogue (`grouped_mlp`, PR 31) the same tiles: its side
        and out tiles are row tiles of the out width, 1 to 3.5 MB each at
        the two cells' shapes, and the widest kernel (the second d xs
        product at M = 3584) asks about 51 MiB of the 96 (`mlp_takes`).
        An epilogue taken 128 or 64 rows at a time from float32 scratch,
        for a shorter kernel, was SLOWER than on the whole 512-row
        product at once (up 2.17 and 2.16 against 1.90 ms, d h 2.37 and
        2.29 against 1.94).
    """
    if not takes(n_rows, k, m):
        return None
    tm = next(t for t in ROW_TILES if n_rows % t == 0)
    wide = 8192 // jnp.dtype(dtype).itemsize
    tk, tn = _largest_tile(k, wide), _largest_tile(m, wide)
    return tm, (tk, tn), (tn, tk), (tk, tn)


def grouped_dot(lhs, rhs, counts, out=None, rows_past=False):
    """lhs [N, K] (rows sorted by group), rhs [E, K, M], counts [E] int32
    -> [N, M] in lhs's dtype, float32 accumulation. The counts sum to N,
    or, with `rows_past`, to the R <= N rows the groups hold (a layer that
    holds some of its experts sorts their rows first): rows from R on
    belong to no group, add nothing to d rhs, and come out zero in the
    product and in d lhs; the kernels' grids end with the groups' last
    visit, so the work follows R, not N. The Pallas kernels on a TPU place for
    shapes they take, `lax.ragged_dot` otherwise (as
    `_plain_causal_attention` is for attention). `out`: the product as an
    earlier call left it; it is returned as it is and the call only
    carries the gradients (a backward op that was handed the forward's
    result computes nothing twice)."""
    tiles = None
    if on_tpu() and lhs.dtype == rhs.dtype:
        tiles = tiles_for(lhs.shape[0], lhs.shape[1], rhs.shape[2],
                          lhs.dtype)
    return grouped_matmul(lhs, rhs, counts.astype(jnp.int32), out, tiles,
                          bool(rows_past))


def _vmem_bytes(tm, tk, tn, item, n_sides, n_outs):
    """What a `_gmm` call asks of VMEM: every block twice (the pipeline's
    two buffers), the side and out blocks beside the rows and the
    weights, and a float32 tile each for the product, the widened sides
    and the values stored."""
    tiles = n_sides + n_outs
    return (2 * item * (tm * tk + tk * tn + tiles * tm * tn)
            + 4 * tm * tn * (1 + tiles))


def mlp_takes(n_rows, h, f):
    """Whether `grouped_mlp` runs as the nine kernels with their
    epilogues: shapes the kernels take (`takes`), and the two widest fit
    VMEM with the tiles `tiles_for` gives a 2- or a 4-byte dtype: the d h
    product (rows of H in, the whole-K weight tile, a, b in and d a, d b
    out) and the second d xs product (the existing tile of H in, its sum
    out)."""
    if not takes(n_rows, h, f):
        return False
    for dtype in (jnp.bfloat16, jnp.float32):
        tm, (th, tf), _, _ = tiles_for(n_rows or ROW_TILES[0], h, f, dtype)
        item = jnp.dtype(dtype).itemsize
        if max(_vmem_bytes(tm, th, tf, item, 2, 2),
               _vmem_bytes(tm, tf, th, item, 1, 1)) > _VMEM_LIMIT:
            return False
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _mlp(xs, gate, up, down, counts, saved, tiles, rows_past,
         activation="silu"):
    return _mlp_fwd(xs, gate, up, down, counts, saved, tiles, rows_past,
                    activation)[0]


def _mlp_fwd(xs, gate, up, down, counts, saved, tiles, rows_past,
             activation):
    """-> (ys, a, b); nine kernels a step with `_mlp_bwd`, and between
    them no element-wise pass over [N, F] or [N, H] in HBM but the zeroing
    of ys and d xs under `rows_past`."""
    if saved is None:
        (tm, up_fwd, _, _), (_, down_fwd, _, _) = tiles
        with jax.named_scope(_SCOPE):
            meta, n = visits(counts, xs.shape[0], tm, False)
            a = _gmm(xs, gate, meta, n, (tm,) + up_fwd, False)
            b, h = _gmm(xs, up, meta, n, (tm,) + up_fwd, False,
                        activation + "_mul", (a,))
            ys = _gmm(h, down, meta, n, (tm,) + down_fwd, False)
            saved = (a, b, _rows_of_groups(ys, counts) if rows_past else ys)
    a, b, ys = saved
    return (ys, a, b), (xs, gate, up, down, counts, a, b)


def _mlp_bwd(tiles, rows_past, activation, res, cots):
    xs, gate, up, down, counts, a, b = res
    (tm, _, up_dlhs, up_drhs), (_, _, down_dlhs, down_drhs) = tiles
    d_ys = cots[0].astype(xs.dtype)
    with jax.named_scope(_SCOPE):
        meta, n = visits(counts, xs.shape[0], tm, False)
        d_a, d_b = _gmm(d_ys, down, meta, n, (tm,) + down_dlhs, True,
                        activation + "_mul_grad", (a, b))
        d_xs = _gmm(d_a, gate, meta, n, (tm,) + up_dlhs, True)
        d_xs = _gmm(d_b, up, meta, n, (tm,) + up_dlhs, True, "add",
                    (d_xs,))
        if rows_past:
            d_xs = _rows_of_groups(d_xs, counts)
        meta, n = visits(counts, xs.shape[0], tm, True)
        # the operand masked in a boundary tile is the one whose rows past
        # the groups no kernel wrote
        d_down = _tgmm((a, b), d_ys, meta, n, (tm,) + down_drhs,
                       down.dtype, mask_lhs=True, activation=activation)
        d_gate = _tgmm(xs, d_a, meta, n, (tm,) + up_drhs, gate.dtype,
                       mask_lhs=False)
        d_up = _tgmm(xs, d_b, meta, n, (tm,) + up_drhs, up.dtype,
                     mask_lhs=False)
    return d_xs, d_gate, d_up, d_down, None, None


_mlp.defvjp(_mlp_fwd, _mlp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _mlp_sq(xs, up, down, counts, saved, tiles, rows_past):
    return _mlp_sq_fwd(xs, up, down, counts, saved, tiles, rows_past)[0]


def _mlp_sq_fwd(xs, up, down, counts, saved, tiles, rows_past):
    """`_mlp_fwd` of an un-gated expert, ys = relu(xs up)^2 down -> (ys,
    a): two kernels, six a step with `_mlp_sq_bwd`."""
    if saved is None:
        (tm, up_fwd, _, _), (_, down_fwd, _, _) = tiles
        with jax.named_scope(_SCOPE):
            meta, n = visits(counts, xs.shape[0], tm, False)
            a, h = _gmm(xs, up, meta, n, (tm,) + up_fwd, False, "relu_sq")
            ys = _gmm(h, down, meta, n, (tm,) + down_fwd, False)
            saved = (a, _rows_of_groups(ys, counts) if rows_past else ys)
    a, ys = saved
    return (ys, a), (xs, up, down, counts, a)


def _mlp_sq_bwd(tiles, rows_past, res, cots):
    xs, up, down, counts, a = res
    (tm, _, up_dlhs, up_drhs), (_, _, down_dlhs, down_drhs) = tiles
    d_ys = cots[0].astype(xs.dtype)
    with jax.named_scope(_SCOPE):
        meta, n = visits(counts, xs.shape[0], tm, False)
        d_a = _gmm(d_ys, down, meta, n, (tm,) + down_dlhs, True,
                   "relu_sq_grad", (a,))
        d_xs = _gmm(d_a, up, meta, n, (tm,) + up_dlhs, True)
        if rows_past:
            d_xs = _rows_of_groups(d_xs, counts)
        meta, n = visits(counts, xs.shape[0], tm, True)
        d_down = _tgmm(a, d_ys, meta, n, (tm,) + down_drhs, down.dtype,
                       mask_lhs=True, activation=RELU2)
        d_up = _tgmm(xs, d_a, meta, n, (tm,) + up_drhs, up.dtype,
                     mask_lhs=False)
    return d_xs, d_up, d_down, None, None


_mlp_sq.defvjp(_mlp_sq_fwd, _mlp_sq_bwd)


def grouped_mlp(xs, gate, up, down, counts, saved=None, rows_past=False,
                activation="silu"):
    """A sparse-expert layer's MLP over rows sorted by group: xs [N, H],
    gate / up [E, H, F], down [E, F, H], counts [E] -> (ys [N, H], a, b
    [N, F]):

        a = xs @ gate[g]   b = xs @ up[g]   ys = (act(a) * b) @ down[g]

    with act = `activation`, "silu" or "relu" (below the kernels are
    written for silu: relu's run the `relu_mul` / `relu_mul_grad`
    epilogues in the same places, under a scope of that name). `saved`:
    (a, b, ys) as an earlier call left them; they are returned as they are
    and the call only carries the gradients. `counts`, `rows_past`: as
    `grouped_dot` takes them.

    Off a TPU place, and for shapes `mlp_takes` refuses, this IS three
    `grouped_dot`s and `jax.nn.silu`. On a TPU place it is one
    `custom_vjp` over the same nine kernels, the element-wise work
    between the products done on the tiles the kernels hold in VMEM (on
    the float32 sums, rounded once):

      forward   a = G(xs, gate);  b, h = G(xs, up) with h = silu(a) * b
                written beside b;  ys = G(h, down)
      backward  d a, d b = NT(d ys, down), silu's backward taken on the
                d h tile, which is never written;  d xs = NT(d a, gate),
                then NT(d b, up) added onto it in place;  d down = TN(h,
                d ys) with h formed again from the a and b tiles;  d gate
                = TN(xs, d a);  d up = TN(xs, d b)

    No gradient flows through the a and b it returns (an op's saved
    outputs). With `rows_past`, ys and d xs are zero past the groups'
    rows; a, b (and h, d a, d b inside) hold there whatever their buffers
    held: kernels alone read them, on visited rows.

    `activation` "relu2" with `gate` None is the UN-GATED expert, ys =
    relu(xs @ up[g])^2 @ down[g] -> (ys, None, a = xs @ up[g]); `saved` is
    (None, a, ys). Six kernels a step: a, h = G(xs, up) with h = relu(a)^2
    written beside a (`relu_sq`); ys = G(h, down); d a = NT(d ys, down)
    with 2 relu(a) taken on the d h tile (`relu_sq_grad`); d xs = NT(d a,
    up); d down = TN(h, d ys) with h formed again from a; d up = TN(xs,
    d a)."""
    counts = counts.astype(jnp.int32)
    if activation == RELU2:
        return _ungated_mlp(xs, up, down, counts, saved, rows_past)
    H, F = gate.shape[1:]
    if (on_tpu() and xs.dtype == gate.dtype == up.dtype == down.dtype
            and mlp_takes(xs.shape[0], H, F)):
        tiles = (tiles_for(xs.shape[0], H, F, xs.dtype),
                 tiles_for(xs.shape[0], F, H, xs.dtype))
        return _mlp(xs, gate, up, down, counts, saved, tiles,
                    bool(rows_past), activation)
    saved = saved or (None, None, None)
    a = grouped_dot(xs, gate, counts, saved[0], rows_past)
    b = grouped_dot(xs, up, counts, saved[1], rows_past)
    return grouped_dot(_ACTIVATIONS[activation](a) * b, down, counts,
                       saved[2], rows_past), a, b


def _ungated_mlp(xs, up, down, counts, saved, rows_past):
    """`grouped_mlp` under "relu2": (ys, None, a)."""
    H, F = up.shape[1:]
    saved = None if saved is None else saved[1:]
    if (on_tpu() and xs.dtype == up.dtype == down.dtype
            and mlp_takes(xs.shape[0], H, F)):
        tiles = (tiles_for(xs.shape[0], H, F, xs.dtype),
                 tiles_for(xs.shape[0], F, H, xs.dtype))
        ys, a = _mlp_sq(xs, up, down, counts, saved, tiles, bool(rows_past))
        return ys, None, a
    saved = saved or (None, None)
    a = grouped_dot(xs, up, counts, saved[0], rows_past)
    return grouped_dot(_ACTIVATIONS[RELU2](a), down, counts, saved[1],
                       rows_past), None, a
