"""The row-tile kernel of the embedding's gradient (`parallel/row_sum.py`,
PR 38), interpreted on the CPU, against `zeros.at[ids].add(rows)`; the
rule that decides where it runs (`takes`), and the counter that says it
engaged."""

import types
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.ops import lm_ops, sparse_ops
from paddle_tpu.parallel import row_sum

R, C = 64, 16          # a tile's table rows, a chunk's rows: small, so
V, T, H = 1000, 512, 256    # that runs cross both and V % R != 0


def _zipf(rs, V, T):
    weight = np.arange(1, V + 1, dtype=np.float64) ** -1.1
    return rs.permutation(V)[rs.choice(V, size=T, p=weight / weight.sum())]


def _ids(case, rs):
    if case == "all_distinct":
        return rs.permutation(V)[:T]
    if case == "all_equal":
        return np.full(T, 517)
    if case == "zipf":
        ids = _zipf(rs, V, T)
        top = np.bincount(ids).argmax()
        ids[: T // 8 + 1] = top         # one id on more than 1/8 of the rows
        return rs.permutation(ids)
    if case == "run_crosses_chunks":
        # 3 * C + 5 rows of one id from an unaligned start: four chunks
        return np.sort(np.concatenate([
            rs.permutation(100)[:11], np.full(3 * C + 5, 130),
            rs.integers(200, V, T - 3 * C - 16)]))
    if case == "run_crosses_tiles":
        # consecutive ids on both sides of a tile boundary, every row of
        # the run in one chunk
        return np.concatenate([np.arange(R - 4, R + 4),
                               np.arange(R - 4, R + 4)])
    if case == "empty_tiles":
        return rs.choice([3, 5 * R + 1, V - 1], size=T)
    if case == "last_partial_tile":
        return rs.integers(V - V % R, V, T)
    if case == "outside_the_table":
        return np.concatenate([rs.integers(-V, V, T - 8),
                               np.full(8, V + 3)])
    raise ValueError(case)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "all_distinct", "all_equal", "zipf", "run_crosses_chunks",
    "run_crosses_tiles", "empty_tiles", "last_partial_tile",
    "outside_the_table"])
def test_rows_summed_by_id_are_the_scatter_add(case, dtype):
    """Exact where an id occurs once; elsewhere within 2 ulp of the
    float32 sum (the additions of one id run in the order of the rows'
    positions in both, so on the CPU the two are equal to the bit: the
    bound is what the chip's scatter is allowed)."""
    rs = np.random.default_rng(zlib.crc32(case.encode()))
    ids = _ids(case, rs)
    rows = jnp.asarray(rs.standard_normal((len(ids), H)), dtype)
    got = np.asarray(row_sum.sum_rows_by_id(
        jnp.asarray(ids), rows, V, tiles=(R, C), interpret=True))
    want = np.asarray(jnp.zeros((V, H), jnp.float32).at[ids].add(
        rows.astype(jnp.float32)))
    assert got.dtype == np.float32 and got.shape == (V, H)
    inside = ids[(ids >= -V) & (ids < V)] % V
    once = np.flatnonzero(np.bincount(inside, minlength=V) <= 1)
    np.testing.assert_array_equal(got[once], want[once])
    assert np.all(np.abs(got - want) <= 2 * np.spacing(np.abs(want)))
    if case == "empty_tiles":
        assert not got[R:5 * R].any()
    if case == "zipf":
        assert np.bincount(ids).max() > T // 8


def _grad(monkeypatch, ids, table, attrs, dtype="float32"):
    """`lookup_table_grad` through the op's lowering on a TPU place (the
    place steered as tests/test_tpu_compile.py does, the kernel
    interpreted, the threshold patched to 1 MB): (W@GRAD, the tables the
    kernel was asked for)."""
    calls = []
    real = row_sum.sum_rows_by_id

    def interpreted(ids, rows, V, tiles=None, interpret=None):
        calls.append(V)
        return real(ids, rows, V, (R, C), interpret=True)

    monkeypatch.setattr(row_sum, "pallas_interpret", lambda: False)
    monkeypatch.setattr(row_sum, "sum_rows_by_id", interpreted)
    monkeypatch.setattr(row_sum, "MIN_TABLE_BYTES", 2 ** 20)
    rs = np.random.default_rng(7)
    g = jnp.asarray(rs.standard_normal(ids.shape[:-1] + table[1:]), dtype)
    got = sparse_ops.lookup_table_grad_op(
        None, {"W": [jnp.zeros(table, jnp.float32)],
               "Ids": [jnp.asarray(ids)], "Out@GRAD": [g]}, attrs)
    return (np.asarray(g, np.float32).reshape(-1, table[1]),
            np.asarray(got["W@GRAD"][0]), calls)


@pytest.mark.parametrize("padding_idx", [-1, 17])
def test_the_op_takes_the_kernel_on_a_tpu_place(padding_idx, monkeypatch):
    """A table of 1.5 MB: the op's dense path goes through the kernel,
    `padding_idx` rows zeroed in front of it as in front of XLA's
    scatter."""
    ids = np.random.default_rng(5).integers(0, 1536, (2, 256, 1))
    ids[0, :5] = 17
    g, got, calls = _grad(monkeypatch, ids, (1536, 256),
                          {"padding_idx": padding_idx})
    assert calls == [1536]
    flat = ids.reshape(-1)
    want = np.zeros((1536, 256), np.float32)
    np.add.at(want, flat, g * (flat != padding_idx)[:, None])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert got[17].any() == (padding_idx != 17)


def test_takes_refuses_what_the_kernel_was_not_written_for():
    big = (37984, 2560)
    assert row_sum.takes(*big, 8192, "float32")
    assert row_sum.takes(*big, None, "float32")          # ids left open
    assert row_sum.takes(50304, 2048, 8192, jnp.float32)  # OLMoE
    assert row_sum.takes(16384, 3584, 4096, jnp.float32)  # Xing
    assert not row_sum.takes(12544, 2048, 8192, "float32")    # Laguna: S(1)
    assert not row_sum.takes(37984, 2560 + 64, 8192, "float32")  # H % 128
    assert not row_sum.takes(*big, 8192, "bfloat16")
    assert not row_sum.takes(*big, 2 ** 20, "float32")   # ids past SMEM
    assert not row_sum.takes(1000, 256, 512, "float32")  # a small table
    assert row_sum.tiles_for(2560) == (512, 64)
    assert row_sum.tiles_for(16384) == (256, 64)
    assert not row_sum.tiles_for(2 ** 17)


def test_ragged_ids_and_a_cpu_place_keep_the_scatter(monkeypatch):
    from paddle_tpu.core.registry import SeqTensor

    def never(*a, **k):
        raise AssertionError("the kernel was taken")

    monkeypatch.setattr(row_sum, "MIN_TABLE_BYTES", 2 ** 20)
    monkeypatch.setattr(row_sum, "sum_rows_by_id", never)
    rs = np.random.default_rng(3)
    w = jnp.zeros((1536, 256), jnp.float32)
    ids = jnp.asarray(rs.integers(0, 1536, (64, 1)))
    g = jnp.asarray(rs.standard_normal((64, 256)), jnp.float32)
    lengths = jnp.asarray([40, 24])
    # a CPU place, dense ids
    sparse_ops.lookup_table_grad_op(
        None, {"W": [w], "Ids": [ids], "Out@GRAD": [g]}, {})
    # a TPU place, ragged ids
    monkeypatch.setattr(row_sum, "pallas_interpret", lambda: False)
    got = sparse_ops.lookup_table_grad_op(
        None, {"W": [w], "Ids": [SeqTensor(ids, lengths)],
               "Out@GRAD": [SeqTensor(g, lengths)]}, {})["W@GRAD"][0]
    want = np.zeros((1536, 256), np.float32)
    np.add.at(want, np.asarray(ids).reshape(-1), np.asarray(g))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    # the sparse gradient is no table at all
    rows = sparse_ops.lookup_table_grad_op(
        None, {"W": [w], "Ids": [ids], "Out@GRAD": [g]},
        {"is_sparse": True})["W@GRAD"][0]
    assert rows.values.shape == (64, 256)


@pytest.mark.parametrize("config,tiled", [
    ("olmoe_1b_7b", 1), ("xing4_0_29b_a4b", 2), ("laguna_xs_2", 0),
    ("smallthinker_21b_a3b", 1)])
@pytest.mark.parametrize("place", ["tpu", "cpu"])
def test_lowered_counts_name_the_tiled_gradients(config, tiled, place):
    """The four token cells' programs at their published widths: the
    counter reads 1 / 2 / 0 / 1 on a TPU place and nothing elsewhere."""
    import importlib
    import json
    import os

    from paddle_tpu import amp

    builder = importlib.import_module("chipbench.configs." + config)
    with open(os.path.join(os.path.dirname(builder.__file__),
                           config + ".json")) as f:
        cfg = json.load(f)
    amp.enable("bfloat16")
    try:
        prog = builder.build(fluid, cfg, 1)["prog"]
        got = lm_ops.lowered_counts(
            prog, types.SimpleNamespace(platform=place))
    finally:
        amp.disable()
    n_ops = sum(op.type == "lookup_table_grad"
                for op in prog.global_block().ops)
    assert n_ops == (2 if config == "xing4_0_29b_a4b" else 1)
    assert got.get("lookup_table_grad_tiled", 0) == (
        tiled if place == "tpu" else 0)


def test_the_word_embedding_models_keep_the_scatter():
    """A small table, ragged ids (the recurrent and word-embedding models
    of `models/` and `tests/`): the counter stays silent on a TPU place."""
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        words = fluid.layers.data("words", [1], dtype="int64", lod_level=1)
        emb = fluid.layers.embedding(words, [5000, 128])
        loss = fluid.layers.mean(fluid.layers.sequence_pool(emb, "sum"))
        fluid.optimizer.SGD(0.1).minimize(loss)
    assert any(op.type == "lookup_table_grad"
               for op in prog.global_block().ops)
    assert "lookup_table_grad_tiled" not in lm_ops.lowered_counts(
        prog, types.SimpleNamespace(platform="tpu"))
