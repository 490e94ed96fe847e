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


# ------------------------------------------- the bounded sums of `moe_ffn`
# (PR 40: `lm_ops._sum_by_token`, the kernel's second caller)
BT, BK = 96, 4                  # tokens, choices a token: 384 sorted slots
BB = 192                        # the row bound: the first 192 of them
HELD = {"none": 0, "one": 1, "under": BB - 37, "all": BB}


def _bounded(rs, held, H, dtype):
    """A layer's routing with `held` of its choice rows on held experts:
    (table [B, H] of `dtype` with rows past `held` left as whatever a
    buffer held, its zeroed copy, weights [T, k] zero off the held rows,
    order [B], inv [T * k])."""
    N = BT * BK
    order = rs.permutation(N).astype(np.int32)
    inv = np.argsort(order).astype(np.int32)
    dirty = rs.standard_normal((BB, H)).astype(np.float32)
    clean = dirty * (np.arange(BB)[:, None] < held)
    w = rs.random(N).astype(np.float32)
    w[order[held:]] = 0.0
    return (jnp.asarray(dirty, dtype), jnp.asarray(clean, dtype),
            jnp.asarray(w.reshape(BT, BK)), jnp.asarray(order[:BB]),
            jnp.asarray(inv))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H", [2048, 2560, 3584])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["combine", "dispatch_bwd"])
@pytest.mark.parametrize("held", sorted(HELD))
def test_rows_summed_by_token_are_the_sum_of_choices(held, weighted, H,
                                                     dtype, monkeypatch):
    """The B bounded rows summed by token through the kernel against the
    k gathers of T rows (`_sum_of_choices`), for RowsHeld 0, 1, under B
    and B, with the combine's weights and without (the dispatch's
    backward), at the three row widths of the share-holding cells. A slot
    at or past RowsHeld has the id T, which the kernel skips: the rows
    there are never read, whatever they hold. Float32 sums of the same
    terms in another order: a few ulps of the terms apart in float32, to
    the bit after the cast to bf16 but for a rounding tie."""
    monkeypatch.setattr(row_sum, "tiles_for", lambda H: (32, 16))
    rs = np.random.default_rng(zlib.crc32(
        f"{held}{weighted}{H}{dtype}".encode()))
    R = HELD[held]
    dirty, clean, w, order, inv = _bounded(rs, R, H, dtype)
    by_token = lm_ops._by_token(order, R, BT, BK, H)
    slots, token, choices = (np.asarray(a) for a in by_token)
    assert slots.shape == choices.shape == (BB + 16,)
    assert (token[:R] < BT).all() and (token[R:] == BT).all()
    # a tile's tokens in one run, its slots in their own order inside it
    assert (np.diff(token // 32) >= 0).all()
    assert all((np.diff(slots[:R][token[:R] // 32 == t]) > 0).all()
               for t in range(BT // 32))
    np.testing.assert_array_equal(choices[:BB], np.asarray(order)[slots[:BB]])
    np.testing.assert_array_equal(token[:R], choices[:R] // BK)
    got = lm_ops._sum_by_token(
        dirty, by_token, BT, w.reshape(-1) if weighted else None)
    want = lm_ops._sum_of_choices(clean, inv, BK, w if weighted else None)
    assert got.dtype == jnp.dtype(dtype) and got.shape == (BT, H)
    got, want32 = np.asarray(got, np.float32), np.asarray(want)
    if dtype == "float32":
        # up to k terms of size ~1: a few float32 ulps of the terms
        assert np.all(np.abs(got - want32) <= 2e-6)
    else:
        want = np.asarray(want.astype(dtype), np.float32)
        assert np.mean(got != want) < 1e-3
        assert np.all(np.abs(got - want) <= np.abs(want) * 2.0 ** -7 + 2e-6)
    if R == 0:
        assert not got.any()


def test_a_float32_out_and_a_narrow_out_hold_the_same_sums():
    """The kernel's two homes of the float32 sums: the output block where
    the result is float32, a block of scratch where it is bf16."""
    rs = np.random.default_rng(11)
    ids = np.sort(rs.integers(0, 200, 300)).astype(np.int32)
    rows = jnp.asarray(rs.standard_normal((300 + C, H)), jnp.bfloat16)
    w = jnp.asarray(rs.random(300 + C), jnp.float32)
    wide, narrow = (row_sum.sum_sorted_rows(
        jnp.asarray(ids), rows, 200, (R, C), interpret=True, weights=w,
        out_dtype=dt) for dt in (jnp.float32, jnp.bfloat16))
    np.testing.assert_array_equal(np.asarray(wide.astype(jnp.bfloat16)),
                                  np.asarray(narrow))
    want = np.zeros((200, H), np.float32)
    np.add.at(want, ids, np.asarray(rows[:300], np.float32)
              * np.asarray(w)[:300, None])
    np.testing.assert_allclose(np.asarray(wide), want, rtol=1e-6, atol=1e-6)


# (configuration, tokens, top_k, H, row bound B, held, experts, takes): the
# five token cells' expert layers as their cells run them
CELL_LAYERS = [("smallthinker_21b_a3b", 8192, 6, 2560, 24576, 16, 64, True),
               ("laguna_xs_2", 8192, 8, 2048, 16384, 32, 256, True),
               ("lfm2_8b_a1b", 8192, 4, 2048, 16384, 8, 32, False),
               ("xing4_0_29b_a4b", 4096, 4, 3584, 4096, 8, 64, False),
               ("olmoe_1b_7b", 8192, 8, 2048, 65536, 64, 64, False)]


@pytest.mark.parametrize("config,T,k,H,B,held,E,takes", CELL_LAYERS,
                         ids=[c[0] for c in CELL_LAYERS])
def test_takes_choices_at_the_cells_shapes(config, T, k, H, B, held, E,
                                           takes):
    """The rule's truth table (tools/combine_sweep.py set it, PR 40): from
    the shapes alone; a layer that holds every expert has no bounded
    table, and neither has an overflow step's full-size branch."""
    assert lm_ops.row_bound(T * k, held, E) == B
    for dtype in ("bfloat16", jnp.float32):
        assert row_sum.takes_choices(T, k, H, B, dtype) == takes
    assert not row_sum.takes_choices(T, k, H, T * k, "bfloat16")
    assert not row_sum.takes_choices(T, 4, H, B, "bfloat16")
    assert not row_sum.takes_choices(T, k, H + 64, B, "bfloat16")
    assert not row_sum.takes_choices(T, k, H, B, "float16")


def test_takes_choices_refuses_what_the_kernel_cannot_hold():
    assert row_sum.takes_choices(8192, 6, 2560, 24576, "bfloat16")
    # more slots than SMEM holds beside their weights
    assert not row_sum.takes_choices(32768, 6, 2560, 98304, "bfloat16")
    # no tile of such rows fits VMEM
    assert not row_sum.takes_choices(8192, 6, 2 ** 17, 24576, "bfloat16")
    # a choice t * k + j has 16 bits beside its slot
    assert row_sum.takes_choices(8192, 8, 2048, 16384, "bfloat16")
    assert not row_sum.takes_choices(8320, 8, 2048, 16384, "bfloat16")
    assert row_sum.takes_choices(8192, 5, 2560, 8192, "bfloat16")


@pytest.mark.parametrize("config", [c[0] for c in CELL_LAYERS]
                         + ["resnet50", "se_resnext50"])
@pytest.mark.parametrize("place", ["tpu", "cpu"])
def test_lowered_counts_name_the_layers_summed_by_token(config, place):
    """The seven configurations' programs at their published widths, built
    under the cells' policy: `moe_ffn_rows_by_token` counts the sparse
    layers whose bounded sums take the kernel on a TPU place (as
    `takes_choices` says of their shapes), and nothing anywhere else."""
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
    layers = {c[0]: c for c in CELL_LAYERS}.get(config)
    sparse = sum(op.type == "moe_ffn" for op in prog.global_block().ops)
    want = sparse if place == "tpu" and layers and layers[-1] else 0
    assert got.get("moe_ffn_rows_by_token", 0) == want
    assert ("moe_ffn_rows_by_token" in got) == bool(want)
    if config == "smallthinker_21b_a3b":
        assert sparse == 4
