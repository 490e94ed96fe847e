"""The `nemotron_3_nano_30b_a3b` step and what it added, compiled for a
described v5e without the chip: `tests/test_tpu_compile.py`'s fixtures and
helpers, in a file of its own so that the two share no test worker (that
file alone runs for twelve minutes)."""

import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_tpu_compile as base  # noqa: E402
from test_tpu_compile import (no_compile_cache, one_chip,  # noqa: E402,F401
                              topo)

SHAPE = dict(seq_len=4096, heads=64, head_dim=64, groups=8, state=128,
             chunk=128)


@pytest.mark.parametrize("kind", ["forward", "backward"])
@pytest.mark.parametrize("form,low", [
    ("plain", "bfloat16"), ("kernels", "bfloat16"), ("kernels", "float32")])
def test_the_scan_compiles_for_v5e(one_chip, no_compile_cache, monkeypatch,
                                   kind, form, low):
    """The chunked scan at the `nemotron_3_nano_30b_a3b` cell's shape, x
    [4096, 64 heads of 64], B, C [4096, 8 groups of 128] in chunks of 128.
    THE KERNEL PATH (what a TPU place runs at this shape, bf16 as the step
    has it and float32 at full matmul precision as the comparison's probe
    has it): Mosaic takes `ssd_chunk_states` before the scan and
    `ssd_chunk_outputs` (backward: `ssd_chunk_grads`) behind it, and no
    array of [chunks, heads, Q, Q] is left. The plain form (a ragged row,
    another chunk): no custom call. Both: ONE `while` (the scan over the
    32 chunks), no array of [T, H, P, N] (a state a token) and a transient
    well under the step's spare memory."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import ssd, ssd_parts

    monkeypatch.setattr(ssd_parts, "pallas_interpret", lambda: False)
    T, H, P, G, N = 4096, 64, 64, 8, 128
    fwd, bwd = (ssd.ssd_fwd, ssd.ssd_bwd) if form == "plain" \
        else (ssd.kernels_fwd, ssd.kernels_bwd)

    def sds(*shape, dt=low):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    head = (sds(H, dt="float32"),) * 3
    ins = [sds(T, H * P), sds(T, G * N), sds(T, G * N), sds(T, H), *head]
    with jax.default_matmul_precision(
            "highest" if low == "float32" else "default"):
        if kind == "forward":
            compiled = jax.jit(lambda *a: fwd(*a, **SHAPE)).lower(
                *ins).compile()
        else:
            compiled = jax.jit(lambda *a: bwd(*a, **SHAPE)).lower(
                *ins, sds(*ssd.states_shape(1, T, H, P, G, N, 128),
                          dt="float32"), sds(T, H * P)).compile()
    text = compiled.as_text()
    behind = "ssd_chunk_outputs" if kind == "forward" else "ssd_chunk_grads"
    assert base._custom_calls(text) == (
        [] if form == "plain" else sorted(["ssd_chunk_states", behind]))
    assert len(re.findall(r"= .* while\(", text)) == 1
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for _, dims in base._ARRAY.findall(text)}
    assert not [s for s in shapes if len(s) >= 4 and s[-2:] == (P, N)
                and T in s]
    if form == "kernels":
        assert not [s for s in shapes if len(s) >= 4 and s[-2:] == (128, 128)
                    and H // G in s[:-2]]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_flash_groups_of_16_compile_for_v5e(one_chip, no_compile_cache,
                                            monkeypatch):
    """Mosaic takes the three flash kernels at the cell's attention shape,
    [1, 32 on 2, 4096, 128] bf16 over the whole triangle (16 query heads a
    key/value head, where the other cells have 4 to 8), at the blocks
    `lm_ops.flash_blocks` gives every full layer: a forward, a dK/dV and a
    dQ custom call, no loop, no copy of K or V the size of the query
    heads'. No kernel needed a change."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import flash

    monkeypatch.setattr(flash, "pallas_interpret", lambda: False)
    q_shape, k_shape = (1, 32, 4096, 128), (1, 2, 4096, 128)
    fwd, bwd = lm_ops.flash_blocks(None), lm_ops.flash_blocks(None, True)

    def sds(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def both(q, k, v, do):
        o, lse = flash.flash_attention_fwd(q, k, v, causal=True, **fwd)
        return o, flash.flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                                            **bwd)

    compiled = jax.jit(both).lower(sds(q_shape), sds(k_shape), sds(k_shape),
                                   sds(q_shape)).compile()
    text = compiled.as_text()
    assert base._custom_calls(text) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert not any(m.group(3) == "while"
                   for m in map(base._INSTR.match, text.splitlines()) if m)
    # never K or V repeated for the 16 heads of a group
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * 32 * 4096 * 128 * 4


def test_the_ungated_mlp_compiles_for_v5e(one_chip, no_compile_cache,
                                          monkeypatch):
    """Mosaic takes `grouped_mlp(activation="relu2")`'s SIX kernels at the
    cell's shapes, [3072 bounded rows, 2688] x [8, 2688, 1856] bf16 with
    the expert width 1856 = 14.5 lane tiles worked as ONE WHOLE tile
    (`grouped._taken_whole`): two forward, two d lhs, two d rhs, and no
    pass over the rows between them but the zeroing where the groups end
    before the rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import grouped

    monkeypatch.setattr(grouped, "pallas_interpret", lambda: False)
    bf, n, e, h, f = jnp.bfloat16, 3072, 8, 2688, 1856

    def sds(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(xs, up, down, counts, g):
        (ys, _, a), vjp = jax.vjp(lambda x, u, d: grouped.grouped_mlp(
            x, None, u, d, counts, None, True, "relu2"), xs, up, down)
        return ys, a, vjp((g, None, jnp.zeros_like(a)))

    assert f % 128 and grouped.mlp_takes(n, h, f)
    assert grouped.tiles_for(n, h, f, bf) == (512, (h, f), (f, h), (h, f))
    compiled = jax.jit(fn).lower(
        sds((n, h)), sds((e, h, f)), sds((e, f, h)), sds((e,), jnp.int32),
        sds((n, h))).compile()
    text = compiled.as_text()
    assert base._custom_calls(text) == (
        ["grouped_matmul"] * 2 + ["grouped_matmul_nt"] * 2
        + ["grouped_matmul_tn"] * 2)
    assert "relu_sq/grouped_matmul" in text
    assert "relu_sq_grad/grouped_matmul_nt" in text
    assert base.ragged_dots(text) == []


def test_nemotron_step_runs_flash_at_groups_of_16_and_six_grouped_kernels(
        one_chip, no_compile_cache, monkeypatch):
    """The `nemotron_3_nano_30b_a3b` step at 1 x 4096 tokens (published
    layers 0-8, MEMEM*EME) compiles for one v5e chip with the flash kernels
    at 32 query heads on 2 key/value heads of 128 (groups of 16: a forward,
    dK/dV and dQ, the kernels unchanged), the grouped kernels over the 8
    held groups at K 2688 / F 1856 as SIX a layer (`relu_sq`,
    `relu_sq_grad` among their scopes; no gate), the embedding's gradient
    by the row-tile kernel, the scan's in-chunk work as the Pallas kernels
    of `parallel/ssd_parts.py` under `mamba/scan/ssd_scan*` either side of
    `while` loops (forward and reverse, a mixer: `ssd_chunk_states` before
    each, `ssd_chunk_outputs` / `ssd_chunk_grads` behind), no more device
    memory than the step took with the plain form (PR 54's tree: arguments
    8,642,229,760 + temporaries 5,536,899,072 + code 311,612,928 bytes), the convolution with its bias as
    shifted multiply-adds (no Pallas conv kernel, no XLA convolution), no
    [S, S] scores; and it fits the chip's 15.75 GB."""
    from paddle_tpu.parallel import ssd_parts

    monkeypatch.setattr(ssd_parts, "pallas_interpret", lambda: False)
    cfg, compiled = base._lm_step(
        one_chip, monkeypatch, "nemotron_3_nano_30b_a3b", 1,
        lambda built: [built["routing"][0][1].name]
        + [r[2].name for r in built["routing"]])
    text = compiled.as_text()
    calls = base._custom_calls(text)
    assert [c for c in calls if c.startswith("flash")] == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    assert calls.count("row_tile_sum") >= 1
    assert not [c for c in calls if "conv" in c]
    # a mixer: the chunk's own states and d h0 before the two scans, the
    # outputs and the gradients behind them
    assert [c for c in calls if c.startswith("ssd_")] == (
        ["ssd_chunk_grads"] * 4 + ["ssd_chunk_outputs"] * 4
        + ["ssd_chunk_states"] * 8)
    under = {m.group(1) for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             for m in [re.search(r'op_name="[^"]*?(mamba/scan/[^"]*?)/'
                                 r'ssd_chunk_', ln)] if m}
    print("scan kernels under:", sorted(under))
    assert under == {
        "mamba/scan/ssd_scan/chunks", "mamba/scan/ssd_scan/outputs",
        "mamba/scan/ssd_scan_grad/chunks", "mamba/scan/ssd_scan_grad/outputs"}
    grouped = [c for c in calls if c.startswith("grouped_matmul")]
    # a layer's two `cond`s hold the kernels a branch each: forward 2 + 2;
    # backward 4 from the saved products (the bounded rows) and 2 + 4 with
    # the products formed again (all rows)
    assert len(grouped) == (2 + 2 + 4 + 6) * 4, grouped
    assert "relu_sq/grouped_matmul" in text
    assert "relu_sq_grad/grouped_matmul_nt" in text
    assert "silu_mul" not in text and "relu_mul" not in text
    assert base.ragged_dots(text) == []
    assert "feature_group_count=6144" not in text
    S = cfg["sequence_length"]
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for _, dims in base._ARRAY.findall(text)}
    assert (32, S, 128) in shapes and (2, S, 128) in shapes
    assert (8, 2688, 1856) in shapes and (8, 1856, 2688) in shapes
    assert [ln for ln in text.splitlines()
            if "%d,%d]" % (S, S) in ln and "/attn/causal_attention" in ln] \
        == []
    loops = [m.group(0) for ln in text.splitlines()
             for m in [re.search(r'op_name="[^"]*mamba/scan/[^"]*/while"',
                                 ln)] if m
             and re.match(r"\s*%?\S+ = .* while\(", ln)]
    assert len(loops) == 8, loops
    mem = compiled.memory_analysis()
    print("nemotron step memory:", mem.generated_code_size_in_bytes,
          mem.argument_size_in_bytes,
          mem.temp_size_in_bytes, mem.output_size_in_bytes)
    # the file's `arithmetic`: 8.00 GB of weights and two moments + 0.64 GB
    # of kept bf16 copies of the expert weights
    assert 8.5e9 < mem.argument_size_in_bytes < 8.8e9
    assert mem.temp_size_in_bytes < 6.5e9, mem.temp_size_in_bytes
    # what the step took with the scan in its plain form (PR 54's tree,
    # this same compile): the [chunks, H, Q, Q] arrays were ~1 GB of it
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes) \
        <= 8_642_229_760 + 5_536_899_072 + 311_612_928
