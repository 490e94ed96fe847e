"""Pallas flash attention: exactness vs dense attention (forward + all
gradients), causal masking, non-block-multiple padding, bf16, and the lse
residual. Runs in Pallas interpret mode on the CPU test platform; the same
kernel compiles via Mosaic on a TPU place, where chip_smoke.py compares it
with a float32 jax.numpy reference.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel.flash import flash_attention


def _dense(q, k, v, causal=False):
    D = q.shape[-1]
    S = q.shape[2]
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        m = jnp.arange(Sk)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(m[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", [64, 100])  # 100: exercises block padding
def test_flash_matches_dense(causal, S):
    rng = np.random.RandomState(0)
    B, H, D = 2, 3, 32
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def _grads(fn, q, k, v, cot):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * cot),
                    argnums=(0, 1, 2))(q, k, v)


def _assert_grads_match_dense(q, k, v, cot, causal, blocks=(32, 32)):
    gf = _grads(lambda *a: flash_attention(*a, causal=causal,
                                           block_q=blocks[0],
                                           block_k=blocks[1]),
                q, k, v, cot)
    gd = _grads(lambda *a: _dense(*a, causal), q, k, v, cot)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-3,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", [96, 100])  # 100: padded queries and keys
def test_flash_gradients_match_dense(causal, S):
    rng = np.random.RandomState(1)
    B, H, D = 1, 2, 16
    q, k, v, cot = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
                    for _ in range(4))
    _assert_grads_match_dense(q, k, v, cot, causal)


def test_flash_gradients_cross_attention_lengths():
    """Sq != Sk, neither a block multiple: the last key block is masked
    in the kernels, the padded query rows add nothing."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 2, 40, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 72, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 72, 16).astype(np.float32))
    cot = jnp.asarray(rng.randn(1, 2, 40, 16).astype(np.float32))
    _assert_grads_match_dense(q, k, v, cot, causal=False)


@pytest.mark.parametrize("blocks", [(32, 64), (64, 32)])
def test_flash_gradients_unequal_blocks(blocks):
    """block_q != block_k: the causal frontier crosses blocks off their
    corners, which the skipped steps and their clamped index maps have to
    follow."""
    rng = np.random.RandomState(6)
    q, k, v, cot = (jnp.asarray(rng.randn(1, 2, 192, 16).astype(np.float32))
                    for _ in range(4))
    _assert_grads_match_dense(q, k, v, cot, causal=True, blocks=blocks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_bf16(causal):
    """bf16 operands, float32 softmax and accumulators inside the kernels:
    against the float32 dense gradients at bf16 tolerance."""
    rng = np.random.RandomState(8)
    shape = (1, 2, 96, 32)
    q, k, v, cot = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                    for _ in range(4))
    gf = _grads(lambda *a: flash_attention(*a, causal=causal, block_q=32,
                                           block_k=32).astype(jnp.float32),
                q, k, v, cot.astype(jnp.float32))
    gd = _grads(lambda *a: _dense(*a, causal),
                *(t.astype(jnp.float32) for t in (q, k, v, cot)))
    for a, b, name in zip(gf, gd, "qkv"):
        assert a.dtype == jnp.bfloat16, name
        b = np.asarray(b)
        worst = np.max(np.abs(np.asarray(a, np.float32) - b)) / np.max(
            np.abs(b))
        assert worst <= 2.5e-2, (name, worst)


def test_flash_backward_is_pallas_not_scan():
    """The backward is the two kernels (dK/dV, dQ) beside the forward's
    call, and no `scan` walks key blocks through HBM."""
    q = jnp.zeros((1, 2, 96, 16), jnp.float32)

    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True,
                                                block_q=32, block_k=32)),
        argnums=(0, 1, 2)))(q, q, q))
    assert text.count("pallas_call") >= 3
    assert "scan" not in text and "while" not in text


def test_flash_bf16():
    rng = np.random.RandomState(2)
    B, H, S, D = 1, 2, 64, 32
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    want = _dense(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2, rtol=5e-2)


def test_flash_cross_attention_lengths():
    """Sq != Sk (decoder cross-attention shape)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 2, 40, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 72, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 72, 16).astype(np.float32))
    got = flash_attention(q, k, v, block_q=32, block_k=32)
    want = _dense(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_flash_small_sequences_autoshrink():
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 1, 5, 8).astype(np.float32))
    got = flash_attention(q, q, q)  # blocks auto-shrink below defaults
    want = _dense(q, q, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(causal):
    """Flash-per-hop ring attention over the 8-device mesh equals dense
    attention on the unsharded sequence."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.ring import ring_flash_attention

    rng = np.random.RandomState(7)
    B, H, S, D = 1, 2, 128, 16  # 8 shards of 16
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    mesh = make_mesh({"sp": 8})
    got = ring_flash_attention(q, k, v, mesh, axis_name="sp", causal=causal,
                               block_q=16, block_k=16)
    want = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)



# ------------------------------------------------ window, grouped-query heads
def _dense_band(q, k, v, window=None, scale=None):
    """The plain composition: k, v [B, Hkv, S, .] repeated for the query
    heads of each group, the [S, S] mask written out (j <= i, and with
    `window` i - j < window: the token itself counts)."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    S, Sk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    s = s / np.sqrt(q.shape[-1]) if scale is None else s * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(Sk)[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _band_case(S, heads, kv_heads, d=16, dv=None, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    dv = dv or d
    return tuple(jnp.asarray(rng.randn(*shape).astype(dtype)) for shape in (
        (2, heads, S, d), (2, kv_heads, S, d), (2, kv_heads, S, dv),
        (2, heads, S, dv)))


# window against a block of 32: none, smaller than the block, equal to it,
# two and a half blocks, longer than the row
WINDOWS = [None, 8, 32, 80, 1000]
# (query heads, key/value heads): groups of 1, 6 and 8
HEADS = [(2, 2), (6, 1), (8, 1), (4, 2)]


@pytest.mark.parametrize("which", ["forward", "dkv", "dq"])
@pytest.mark.parametrize("S", [96, 100])      # 100: rows no block divides
@pytest.mark.parametrize("heads", HEADS, ids=lambda h: f"{h[0]}over{h[1]}")
@pytest.mark.parametrize("window", WINDOWS, ids=lambda w: f"w{w}")
def test_band_and_head_groups_match_the_plain_composition(window, heads, S,
                                                          which):
    q, k, v, cot = _band_case(S, *heads)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                               window=window)

    def plain(q, k, v):
        return _dense_band(q, k, v, window)

    if which == "forward":
        got, want = [flash(q, k, v)], [plain(q, k, v)]
    else:
        got, want = (_grads(f, q, k, v, cot) for f in (flash, plain))
        pick = slice(1, 3) if which == "dkv" else slice(0, 1)
        got, want = got[pick], want[pick]
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-3)


@pytest.mark.parametrize("which", ["forward", "dkv", "dq"])
@pytest.mark.parametrize("window", [128, 130, None],
                         ids=["half_the_row", "four_blocks_and_two", "w_none"])
def test_a_wide_band_and_a_group_of_seven(window, which):
    """The `smallthinker_21b_a3b` cell's regime at a small size: a band of
    half the row (4 key blocks of 32 a query block, of which the inner ones
    carry no mask at all, where a band of one block has a mask on both),
    7 query heads on one key/value head, and the same heads over the whole
    triangle (its full layers)."""
    q, k, v, cot = _band_case(256, 7, 1, seed=5)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                               window=window)

    def plain(q, k, v):
        return _dense_band(q, k, v, window)

    if which == "forward":
        got, want = [flash(q, k, v)], [plain(q, k, v)]
    else:
        got, want = (_grads(f, q, k, v, cot) for f in (flash, plain))
        pick = slice(1, 3) if which == "dkv" else slice(0, 1)
        got, want = got[pick], want[pick]
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-3)


@pytest.mark.parametrize("which", ["forward", "dkv", "dq"])
@pytest.mark.parametrize("S,dtype", [(128, "float32"), (100, "float32"),
                                     (128, "bfloat16")],
                         ids=["two_blocks", "padded", "bf16"])
def test_heads_of_64_thirty_two_on_eight(S, dtype, which):
    """The `lfm2_8b_a1b` cell's head shape at short rows: 32 query heads on
    8 key/value heads (groups of 4) of D = 64, half a lane tile, over the
    whole triangle: the forward kernel and both backward kernels against
    the plain composition."""
    import ml_dtypes

    np_dtype = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    q, k, v, cot = _band_case(S, 32, 8, d=64, seed=9)
    q, k, v, cot = (t.astype(np_dtype) for t in (q, k, v, cot))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64)

    def plain(q, k, v):
        f = (t.astype(jnp.float32) for t in (q, k, v))
        return _dense_band(*f, None).astype(q.dtype)

    if which == "forward":
        got, want = [flash(q, k, v)], [plain(q, k, v)]
    else:
        got, want = (_grads(f, q, k, v, cot) for f in (flash, plain))
        pick = slice(1, 3) if which == "dkv" else slice(0, 1)
        got, want = got[pick], want[pick]
    tol = dict(atol=5e-5, rtol=1e-3) if dtype == "float32" \
        else dict(atol=0.15, rtol=0.05)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


@pytest.mark.parametrize("blocks", [(32, 64), (64, 32), (16, 32)])
@pytest.mark.parametrize("window", [24, 40])
def test_band_with_unequal_blocks(window, blocks):
    q, k, v, cot = _band_case(128, 6, 1, seed=3)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=blocks[0],
                               block_k=blocks[1], window=window)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(_dense_band(q, k, v, window)),
        atol=5e-5, rtol=1e-3)
    for a, b in zip(_grads(flash, q, k, v, cot),
                    _grads(lambda *a: _dense_band(*a, window), q, k, v, cot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-3)


@pytest.mark.parametrize("window,heads", [(None, (4, 4)), (24, (4, 4)),
                                          (24, (6, 1))])
def test_keys_wider_than_values_still_pass(window, heads):
    """Latent attention's case: keys of 24 numbers, values of 16."""
    q, k, v, cot = _band_case(96, *heads, d=24, dv=16, seed=5)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=0.2, block_q=32,
                               block_k=32, window=window)

    def plain(q, k, v):
        return _dense_band(q, k, v, window, scale=0.2)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(plain(q, k, v)), atol=5e-5,
                               rtol=1e-3)
    for a, b in zip(_grads(flash, q, k, v, cot),
                    _grads(plain, q, k, v, cot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-3)


def _calls(fn, *args):
    """The pallas_call equations of `fn`'s jaxpr: (name, grid, in block
    index at a few grid points, kernel's static parameters)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("causal", [False, True])
def test_without_window_and_group_the_grid_and_index_maps_are_as_before(
        causal):
    """A call with no window and as many key/value heads as query heads
    builds what it built before windows and head groups existed: the grids
    (B * H, blocks, blocks), no parameter of the new kind in any kernel,
    and index maps that read, written out here as they stood: the forward
    key block j; dK/dV the query block min(max(i, j bk // bq), nq - 1); dQ
    the key block min(j, (i bq + bq - 1) // bk)."""
    from paddle_tpu.parallel import flash

    B, H, S, D, bq, bk = 2, 3, 128, 16, 32, 64
    nq, nk = S // bq, S // bk
    q, k, v, cot = (jnp.ones((B, H, S, D), jnp.float32),) * 4
    calls = _calls(
        lambda q, k, v: _grads(lambda *a: flash_attention(
            *a, causal=causal, block_q=bq, block_k=bk), q, k, v, cot),
        q, k, v)
    assert [c.params["grid_mapping"].grid for c in calls] == [
        (B * H, nq, nk), (B * H, nk, nq), (B * H, nq, nk)]
    for c in calls:
        text = str(c.params["jaxpr"])
        assert "window" not in text and "group" not in text
    q_of, k_of = flash._band(bq, bk, nq, nk, causal, None)
    kv = flash._of_head(1)
    for b in range(B * H):
        assert kv(b) == b
    for i in range(nq):
        for j in range(nk):
            if causal:
                assert int(q_of(j, i)) == min(max(i, j * bk // bq), nq - 1)
                assert int(k_of(i, j)) == min(j, (i * bq + bq - 1) // bk)
            else:
                assert (q_of(j, i), k_of(i, j)) == (i, j)
    # the forward names key block j itself, visited or not
    fwd_maps = calls[0].params["grid_mapping"].block_mappings
    for i in range(nq):
        for j in range(nk):
            idx = jax.core.eval_jaxpr(
                fwd_maps[1].index_map_jaxpr.jaxpr,
                fwd_maps[1].index_map_jaxpr.consts, 4, i, j)
            assert [int(x) for x in idx] == [4, j, 0]


@pytest.mark.parametrize("S,block,window,want", [
    (8192, 1024, None, 36), (8192, 1024, 512, 15), (8192, 512, 512, 31),
    (8192, 256, 512, 93), (8192, 128, 512, 310), (100, 32, 8, 7),
    (100, 32, 1000, 10), (8192, 1024, 4096, 30), (8192, 512, 4096, 108)])
def test_blocks_visited_counts_the_band(S, block, window, want):
    """The count the window kernels' grids are held to: the steps that the
    kernels' own `_for_block` predicate lets compute."""
    from paddle_tpu.parallel import flash

    assert flash.blocks_visited(S, S, block, block, window) == want
    n = -(-S // block)
    seen = 0
    for i in range(n):
        for j in range(n):
            visited = i * block + block - 1 >= j * block
            if window is not None:
                visited &= i * block - (j * block + block - 1) < window
            seen += visited
    assert seen == want
