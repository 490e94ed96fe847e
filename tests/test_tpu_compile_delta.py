"""The `qwen3_next_80b_a3b` step and the kernels it added, compiled for a
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

@pytest.mark.parametrize("kind", ["forward", "backward"])
def test_silu_conv_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                           monkeypatch, kind):
    """Mosaic takes the two kernels of the variant silu(conv(x)) at the
    `qwen3_next_80b_a3b` cell's shape, X [8192, 8192] bf16 with 4 float32
    taps: one custom call each, no temporary of an activation's size."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import short_conv

    monkeypatch.setattr(short_conv, "pallas_interpret", lambda: False)
    S, C = 8192, 8192
    assert short_conv.silu_takes(S, C, S, 4, "bfloat16")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    x, w = sds((S, C), "bfloat16"), sds((4, C), "float32")
    if kind == "forward":
        compiled = jax.jit(
            lambda x, w: short_conv.silu_conv_fwd(x, w, S)).lower(
                x, w).compile()
    else:
        compiled = jax.jit(
            lambda x, w, g: short_conv.silu_conv_bwd(x, w, g, S)).lower(
                x, w, x).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kind", ["forward", "forward_in_the_backward",
                                  "backward"])
def test_delta_parts_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                             monkeypatch, kind, dtype):
    """Mosaic takes the delta rule's two kernels at the `qwen3_next_80b_a3b`
    cell's shape, [8192 tokens, 16 key / 32 value heads of 128] in chunks
    of 128, all heads at once, in bf16 (the step) and in float32 (the
    comparison's probe): the forward as the forward op calls it, as the
    grad op calls it (Q'^T d O, N, exp(G_C) and the inverse out), and the
    transpose reading that inverse; one custom call each, no temporary of
    an activation's size."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import delta_parts, delta_rule

    monkeypatch.setattr(delta_parts, "pallas_interpret", lambda: False)
    S, hk, hv, d, chunk = 8192, 16, 32, 128, delta_rule.CHUNK
    assert chunk == 128 and delta_rule.takes(1, S, hk, hv, d, d, chunk,
                                             dtype)

    def sds(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    n, dims = S // chunk, dict(rows=1, seq_len=S, hk=hk, hv=hv, dk=d, dv=d,
                               chunk=chunk, eps=1e-6)
    ins = [sds(S, (2 * hk + hv) * d), sds(S, hv, dt="float32"),
           sds(S, hv, dt="float32")]
    d_out, state = sds(S, hv * d), sds(n, hv, d, d, dt="float32")
    if kind == "forward":
        compiled = jax.jit(lambda *a: delta_parts.delta_parts_fwd(
            *a, **dims)).lower(*ins).compile()
    elif kind == "forward_in_the_backward":
        compiled = jax.jit(lambda *a: delta_parts.delta_parts_fwd(
            *a, **dims, outputs=("r_mat", "n_mat", "g_end", "t"))).lower(
                *ins, d_out).compile()
    else:
        compiled = jax.jit(lambda *a: delta_parts.delta_parts_bwd(
            *a, **dims)).lower(
                *ins, d_out, state, state,
                sds(n, hv, chunk, chunk)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kind", ["forward", "backward"])
def test_gated_norm_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                            monkeypatch, kind, dtype):
    """Mosaic takes the gated norm's two kernels at the `qwen3_next_80b_a3b`
    cell's shape, X [8192, 32 heads of 128] and the gate [8192, 4096] with a
    float32 scale, in bf16 (the step) and in float32: one custom call each,
    no temporary of an activation's size (the backward's d Scale leaves as
    eight rows of partial sums). X arrives as the delta rule's output
    product leaves o, [chunks, H, 128 tokens, D], turned token-major inside
    the program as `delta_rule._tokens_first` does: XLA cancels that
    transposition against the kernels' own, which read X head-major within
    a block of that chunk; d X leaves token-major [T, H D], as the delta
    rule's backward kernel takes it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import delta_rule, gated_norm

    monkeypatch.setattr(gated_norm, "pallas_interpret", lambda: False)
    T, H, D = 8192, 32, 128
    chunk = delta_rule.CHUNK
    assert gated_norm.fits((T, H, D), dtype)
    assert gated_norm._block(T, H * D, jnp.dtype(dtype).itemsize) == chunk

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def heads(o):       # [chunks, H, C, D] -> [T, H, D]
        return jnp.moveaxis(o, 2, 1).reshape(T, H, D)

    o, z, w = sds((T // chunk, H, chunk, D), dtype), \
        sds((T, H * D), dtype), sds((D,), "float32")
    if kind == "forward":
        compiled = jax.jit(lambda o, z, w: gated_norm.gated_norm_fwd(
            heads(o), z, w, 1e-6).reshape(T, H * D)).lower(o, z, w).compile()
    else:
        def backward(o, z, w, g):
            d_x, d_z, d_w = gated_norm.gated_norm_bwd(
                heads(o), z, w, g.reshape(T, H, D), 1e-6)
            return d_x.reshape(T, H * D), d_z, d_w

        compiled = jax.jit(backward).lower(o, z, w, z).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def test_qwen3_next_step_runs_the_delta_kernels_and_flash_at_256(
        one_chip, no_compile_cache, monkeypatch):
    """The `qwen3_next_80b_a3b` step at 1 x 8192 tokens (one period: three
    Gated DeltaNet layers and an output-gated attention layer, each with 32
    held experts of 512 and a shared one) compiles for one v5e chip with
    the flash kernels at 16 query heads on 2 key/value heads of 256 (a
    forward, dK/dV and dQ), the grouped kernels over the 32 held groups at
    K 2048 / F 512, the embedding's gradient by the row-tile kernel, the
    delta rule's in-chunk work by `delta_parts_fwd` (once a layer forward
    and once more in its backward) and `delta_parts_bwd`, its chunk scan as
    `while` loops under `delta/delta_rule/` (forward and reverse, a delta
    layer, over all heads at once: no loop over head groups), the
    convolution's silu variant by its two kernels, the gated norm by its
    two (one op a layer: `gated_norm_fwd`, `gated_norm_bwd`), no XLA
    convolution, no [S, S] scores; and it fits the chip's 15.75 GB."""
    cfg, compiled = base._lm_step(
        one_chip, monkeypatch, "qwen3_next_80b_a3b", 1,
        lambda built: [built["routing"][0][1].name]
        + [r[2].name for r in built["routing"]])
    text = compiled.as_text()
    calls = base._custom_calls(text)
    assert [c for c in calls if c.startswith("flash")] == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    assert calls.count("row_tile_sum") >= 1
    assert [c for c in calls if c.startswith("silu_conv")] == \
        ["silu_conv_bwd"] * 3 + ["silu_conv_fwd"] * 3
    assert [c for c in calls if c.startswith("delta_parts")] == \
        ["delta_parts_bwd"] * 3 + ["delta_parts_fwd"] * 6
    assert [c for c in calls if c.startswith("gated_norm")] == \
        ["gated_norm_bwd"] * 3 + ["gated_norm_fwd"] * 3
    assert base.ragged_dots(text) == []
    assert "feature_group_count=8192" not in text
    S = cfg["sequence_length"]
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for _, dims in base._ARRAY.findall(text)}
    assert (16, S, 256) in shapes and (2, S, 256) in shapes
    assert (32, 2048, 512) in shapes and (32, 512, 2048) in shapes
    # (W_qg's product is [T, 2 x 16 x 256] = [S, S] here: not a score)
    assert [ln for ln in text.splitlines()
            if "%d,%d]" % (S, S) in ln and "/attn/causal_attention" in ln] \
        == []
    loops = [m.group(0) for ln in text.splitlines()
             for m in [re.search(r'op_name="[^"]*delta/delta_rule/[^"]*'
                                 r'/while"', ln)] if m
             and re.match(r"\s*%?\S+ = .* while\(", ln)]
    # the chunk scan, forward and backward, a delta layer
    assert len(loops) == 6, loops
    mem = compiled.memory_analysis()
    # the file's `arithmetic`: 8.31 GB of arguments (weights, two moments,
    # the kept copies). That the compile returned says the step fits the
    # compiler's 15.75 GB (before the delta rule's backward worked the heads
    # in groups it did not: 17.04 GB); `memory_analysis` adds temporaries up
    # to more than that (9.3 GB), and on the chip the window closes at 15.09
    assert 8.2e9 < mem.argument_size_in_bytes < 8.4e9
    assert mem.temp_size_in_bytes < 9.8e9, mem.temp_size_in_bytes
