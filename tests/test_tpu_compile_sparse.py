"""The `keye_vl_2_0_30b_a3b` step and the kernels it added, compiled for a
described v5e without the chip: `tests/test_tpu_compile.py`'s fixtures and
helpers, in a file of its own so that it shares no test worker with that
file (which alone runs for twelve minutes)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_tpu_compile as base  # noqa: E402
from test_tpu_compile import (no_compile_cache, one_chip,  # noqa: E402,F401
                              topo)


@pytest.mark.parametrize("kind", ["forward", "backward",
                                  "backward_past_the_fit"])
def test_masked_flash_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                              monkeypatch, kind):
    """Mosaic takes the kernels of `parallel/flash.py` under a mask, at the
    `keye_vl_2_0_30b_a3b` cell's shape, 32 query heads on 4 key/value heads
    of 128 over a row of 8192 with an int8 mask [8192, 8192]: one custom
    call forward, ONE backward (dQ, dK and dV accumulated in VMEM for the
    length of the row: `flash.fused_backward_fits`), and no [S, S]
    temporary of any type (the mask is read as it lies). A row of 16384,
    whose accumulators do not fit their share, gets the dK/dV and the dQ
    kernel with the mask's transposed copy between them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import flash

    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    S, H, Hkv, D = 8192, 32, 4, 128
    if kind == "backward_past_the_fit":
        S = 16384
    assert flash.fused_backward_fits(S, S, D, D) == (S == 8192)

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    q, kv = sds((1, H, S, D)), sds((1, Hkv, S, D))
    mask, lse = sds((1, S, S), "int8"), sds((1, H, S), "float32")
    if kind == "forward":
        compiled = jax.jit(
            lambda q, k, v, m: flash.flash_attention_fwd(
                q, k, v, causal=True, mask=m, block_q=1024, block_k=1024)
        ).lower(q, kv, kv, mask).compile()
        calls = ["sparse_flash_fwd"]
    else:
        compiled = jax.jit(
            lambda q, k, v, m, o, lse, do: flash.flash_attention_bwd(
                q, k, v, o, lse, do, causal=True, mask=m)
        ).lower(q, kv, kv, mask, q, lse, q).compile()
        calls = ["sparse_flash_bwd"] if kind == "backward" \
            else ["sparse_flash_dkv", "sparse_flash_dq"]
    assert base._custom_calls(compiled.as_text()) == calls
    temp = compiled.memory_analysis().temp_size_in_bytes
    if kind == "backward_past_the_fit":
        # the transposed mask (268 MB) and lse | delta as columns, 128
        # lanes a row (268 MB)
        assert S * S <= temp < 6.0e8, temp
    else:
        # d O in q's dtype, delta, lse | delta as rows: 2 MB
        assert temp < 1.0e7, temp


def test_index_loss_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                            monkeypatch):
    """Mosaic takes the two kernels of `parallel/index_loss.py` at the
    `keye_vl_2_0_30b_a3b` cell's shape (a row of 8192; q 32 heads on k 4
    heads of 128; an indexer of 16 heads of 64): two custom
    calls, neither of the plain scan's float32 blocks ([8, 256, 8192] of
    the attention's scores a key/value head, [256, 16, 8192] of the
    indexer's product), no float32 [heads, ., .] block of a tile's size
    either, and of temporaries p [S, S] float32 (268 MB, written once)
    beside the three [S, 128] statistics and the operands' small copies."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import index_loss

    monkeypatch.setattr(index_loss, "pallas_interpret", lambda: False)
    S, H, Hkv, D, Hi, Di = 8192, 32, 4, 128, 16, 64
    assert index_loss.takes(S, H, Hkv, D, Hi, Di, jnp.bfloat16)

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    compiled = jax.jit(
        lambda *a: index_loss.loss_and_grads(*a, D ** -0.5)).lower(
        sds((H, S, D)), sds((Hkv, S, D)), sds((H, S), "float32"),
        sds((S, Hi, Di)), sds((S, Di)), sds((S, Hi)),
        sds((S, S), "int8")).compile()
    text = compiled.as_text()
    assert base._custom_calls(text) == ["index_grads", "index_target"]
    bq, bk = index_loss.BLOCKS
    for block in ("f32[8,256,%d]" % S, "f32[256,16,%d]" % S,
                  "f32[%d,%d,%d]" % (Hi, bq, bk), "f32[%d,%d,%d]" % (H, bq, bk)):
        assert block not in text, block
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 4 * S * S <= temp < 4 * S * S + 8.0e7, temp


def test_index_select_kernel_compiles_for_v5e(one_chip, no_compile_cache,
                                              monkeypatch):
    """Mosaic takes the kernel of `parallel/index_select.py` at the
    `keye_vl_2_0_30b_a3b` cell's shape, as `sparse_index.select`'s scan
    hands it a block (256 queries of a row of 8192, the block's first query
    traced; topk 2048): one custom call, no [256, 8192] int32 or uint32
    array of the plain form's keys and running count beside it, and of
    temporaries the thresholds' [256, 128] tile alone."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import index_select, sparse_index

    monkeypatch.setattr(index_select, "pallas_interpret", lambda: False)
    n, S, topk = sparse_index.BLOCK, 8192, 2048
    assert index_select.takes(n, S, topk)
    compiled = jax.jit(
        lambda I, first: sparse_index.select_rows(I, first, topk)).lower(
        jax.ShapeDtypeStruct((n, S), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert base._custom_calls(text) == ["index_select"]
    for block in ("s32[%d,%d]" % (n, S), "u32[%d,%d]" % (n, S)):
        assert block not in text, block
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 3 * n * S, temp


def test_keye_vl_step_runs_the_masked_flash_kernels_and_fits(
        one_chip, no_compile_cache, monkeypatch):
    """The `keye_vl_2_0_30b_a3b` step at 1 x 8192 tokens (four layers of
    attention behind an indexer, each with 16 held experts of 768) compiles
    for one v5e chip with the masked flash kernels a layer (a forward and
    ONE backward: no causal flash kernel is left), the grouped kernels
    over the 16 held groups at K 2048 / F 768, the embedding's gradient by
    the row-tile kernel, no sort of an [S, S] operand (the selection is by
    bisection), no float32 [32, S, S] scores, the indexer's loss in its
    two kernels a layer; and it fits the chip's 15.75 GB."""
    cfg, compiled = base._lm_step(
        one_chip, monkeypatch, "keye_vl_2_0_30b_a3b", 1,
        lambda built: [built["routing"][0][1].name]
        + [r[2].name for r in built["routing"]])
    text = compiled.as_text()
    calls = base._custom_calls(text)
    layers = cfg["num_hidden_layers"]
    assert [c for c in calls if "flash" in c] == \
        ["sparse_flash_bwd"] * layers + ["sparse_flash_fwd"] * layers
    # the indexer's loss: its two kernels a layer, no scan over blocks of
    # 256 queries with their float32 [8, 256, S] and [256, 16, S] blocks
    # (and the selection's threshold and mask in ONE kernel a layer, in the
    # scan over blocks of 256 queries behind the score product)
    assert [c for c in calls if c.startswith("index_")] == \
        ["index_grads"] * layers + ["index_select"] * layers \
        + ["index_target"] * layers
    assert calls.count("row_tile_sum") >= 1
    assert base.ragged_dots(text) == []
    S = cfg["sequence_length"]
    # (the selection's score product, under `indexer_select`, keeps its)
    assert [ln for ln in text.splitlines() if "/indexer_loss/" in ln and (
        "f32[8,256,%d]" % S in ln or "f32[256,16,%d]" % S in ln)] == []
    assert [ln for ln in text.splitlines()
            if " sort(" in ln and "%d,%d]" % (S, S) in ln] == []
    assert "f32[32,%d,%d]" % (S, S) not in text
    mem = compiled.memory_analysis()
    # the file's `arithmetic`: 465.4 M parameters x 12 B (weights and two
    # moments; the gradients are temporaries) + 0.60 GB of kept copies
    assert 6.1e9 < mem.argument_size_in_bytes < 6.3e9
    print("temp bytes", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 9.0e9, mem.temp_size_in_bytes
