"""Sparse / distributed-lookup-table ops.

Reference parity:
  - operators/lookup_table_op.cc grad (is_sparse -> SelectedRows gradient)
  - operators/split_ids_op.cc (mod-shard ids / SelectedRows rows)
  - operators/merge_ids_op.cc (reassemble prefetched rows in id order)
  - operators/prefetch_op.cc (RPC row fetch from pservers)
  - operators/lookup_sparse_table_op.cc (auto-grown pserver table gather)
  - operators/sgd_op.cc + sum_op.cc SelectedRows paths live in
    optimizer_ops.py / math_ops.py.

TPU-first shape: the trainer-side sparse gradient is a SelectedRows pytree
(ids + grad rows, both static-shape), so it flows out of the jit-traced step
without materializing a dense [vocab, dim] gradient; the host-side shard /
RPC ops then work on numpy. The pserver table is a SparseTable (auto-grow
hash table, core/selected_rows.py)."""

import numpy as np
import jax.numpy as jnp

from ..core.registry import register_op, register_grad_maker, SeqTensor
from ..core.selected_rows import SelectedRows, SparseTable
from ..parallel import row_sum
from .util import first, many, out


def _flat_ids(ids):
    """Ids tensor (maybe SeqTensor, maybe [N,1]) -> (flat ids [N], lengths)."""
    lengths = ids.lengths if isinstance(ids, SeqTensor) else None
    idx = ids.data if lengths is not None else ids
    if idx.ndim >= 2 and idx.shape[-1] == 1:
        idx = idx.reshape(idx.shape[:-1])
    return idx, lengths


@register_op("lookup_table_grad", lod_aware=True)
def lookup_table_grad_op(ctx, ins, attrs):
    """reference lookup_table_op.cc LookupTableGradKernel: dense scatter-add,
    or a SelectedRows gradient when is_sparse (rows=ids, values=dOut)."""
    w = first(ins, "W")
    ids = first(ins, "Ids")
    g = first(ins, "Out@GRAD")
    idx, lengths = _flat_ids(ids)
    gd = g.data if isinstance(g, SeqTensor) else g
    padding_idx = attrs.get("padding_idx", None)
    if padding_idx is not None and padding_idx >= 0:
        gd = jnp.where((idx == padding_idx)[..., None], 0.0, gd)
    if w is None:
        # distributed table: the trainer never materializes W — the
        # transpiler pruned it and recorded the vocab size as an attr
        assert attrs.get("is_sparse", False), \
            "lookup_table_grad without W requires is_sparse"
        height = int(attrs["height"])
    else:
        height = w.height if isinstance(w, SparseTable) else w.shape[0]
    if attrs.get("is_sparse", False):
        rows = idx.reshape(-1)
        values = gd.reshape((rows.shape[0],) + gd.shape[idx.ndim:])
        return out(**{"W@GRAD": SelectedRows(rows, values, height)})
    dim = w.shape[1:]
    rows = gd.reshape((-1,) + gd.shape[idx.ndim:])
    if (lengths is None and len(dim) == 1 and row_sum.on_tpu()
            and row_sum.takes(height, dim[0], rows.shape[0], w.dtype)):
        # a table too large for the chip's fast memory: the rows summed by
        # id a tile of the table at a time (parallel/row_sum.py)
        return out(**{"W@GRAD": row_sum.sum_rows_by_id(
            idx.reshape(-1), rows, height)})
    dense = jnp.zeros((height,) + tuple(dim), gd.dtype)
    dense = dense.at[idx.reshape(-1)].add(rows)
    return out(**{"W@GRAD": dense.astype(w.dtype)})


@register_op("split_ids", no_trace=True, lod_aware=True)
def split_ids_op(ctx, ins, attrs):
    """reference operators/split_ids_op.cc: mod-shard ids (deduped, sorted)
    or a SelectedRows gradient's rows across N outputs."""
    x = first(ins, "Ids")
    if x is None:
        x = first(ins, "X")
    n = len(ctx.current_op.output("Out"))
    if isinstance(x, SelectedRows):
        rows = np.asarray(x.rows).reshape(-1)
        values = np.asarray(x.values)
        parts = []
        for s in range(n):
            sel = (rows % n) == s
            parts.append(SelectedRows(rows[sel], values[sel], x.height))
        return out(Out=parts)
    idx, _ = _flat_ids(x)
    idx = np.unique(np.asarray(idx).reshape(-1))
    return out(Out=[idx[(idx % n) == s].astype(np.int64) for s in range(n)])


@register_op("merge_ids", no_trace=True, lod_aware=True)
def merge_ids_op(ctx, ins, attrs):
    """reference operators/merge_ids_op.cc: given the original Ids, the
    per-shard id lists and the per-shard fetched rows, emit rows in the
    original id order (the reference-era concat misorders mod-sharded ids;
    merge_ids is the correct join)."""
    ids = first(ins, "Ids")
    shard_ids = many(ins, "X")
    shard_rows = many(ins, "Rows")
    idx, lengths = _flat_ids(ids)
    idx = np.asarray(idx)
    row_of = {}
    for sid, srow in zip(shard_ids, shard_rows):
        for i, r in zip(np.asarray(sid).reshape(-1), np.asarray(srow)):
            row_of[int(i)] = r
    o = np.stack([row_of[int(i)] for i in idx.reshape(-1)])
    o = o.reshape(tuple(idx.shape) + o.shape[1:])
    if lengths is not None:
        return out(Out=SeqTensor(jnp.asarray(o), lengths))
    return out(Out=jnp.asarray(o))


@register_op("prefetch", no_trace=True, lod_aware=True)
def prefetch_op(ctx, ins, attrs):
    """reference operators/prefetch_op.cc: send shard ids to each pserver,
    receive embedding rows (served by the pserver's prefetch block)."""
    from . import rpc_ops
    shard_ids = many(ins, "X")
    epmap = attrs["epmap"]
    table_names = attrs.get("table_names") or [attrs["table_name"]] * len(epmap)
    rows = []
    for ids, ep, tname in zip(shard_ids, epmap, table_names):
        ids = np.asarray(ids).reshape(-1)
        if ids.size == 0:
            rows.append(np.zeros((0, int(attrs["emb_dim"])),
                                 np.dtype(attrs.get("dtype", "float32"))))
            continue
        rows.append(rpc_ops._client(ep).prefetch(tname, ids))
    return out(Out=rows)


@register_op("lookup_sparse_table", no_trace=True, lod_aware=True)
def lookup_sparse_table_op(ctx, ins, attrs):
    """reference operators/lookup_sparse_table_op.cc: gather from an
    auto-grown SparseTable; unseen ids are initialized on first touch."""
    w = first(ins, "W")
    ids = first(ins, "Ids")
    assert isinstance(w, SparseTable), \
        f"lookup_sparse_table expects a SparseTable param, got {type(w)}"
    idx, _ = _flat_ids(ids)
    return out(Out=w.gather(np.asarray(idx),
                            auto_grow=attrs.get("auto_grown_table", True)))


@register_op("init_sparse_table", no_trace=True)
def init_sparse_table_op(ctx, ins, attrs):
    """Startup-program op: create the pserver-side SparseTable (reference
    startup creates a SELECTED_ROWS var + uniform initializer; here init is
    deterministic-on-first-touch inside the table)."""
    return out(Out=SparseTable(
        value_dim=attrs["value_dim"],
        height=attrs.get("height"),
        dtype=attrs.get("dtype", "float32"),
        init_low=attrs.get("min", -0.05),
        init_high=attrs.get("max", 0.05),
        seed=attrs.get("seed", 0),
    ))


@register_grad_maker("lookup_table")
def lookup_table_grad_maker(op, gout, gin):
    """Same desc as the default maker — the explicit kernel above handles
    both the dense and the is_sparse path; Ids never gets a gradient."""
    return [dict(
        type="lookup_table_grad",
        inputs={"Ids": op.input("Ids"), "W": op.input("W"),
                "Out@GRAD": [x or "" for x in gout.get("Out", [])]},
        outputs={"W@GRAD": gin.get("W", [])},
        attrs=dict(op.attrs),
    )]
