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
# two and a half blocks, longer than the row; two blocks (PR 41): the first
# block a query block visits leaves its last row empty, and the next one
# carries no mask (the forward's unguarded path takes m_prev = -inf)
WINDOWS = [None, 8, 32, 80, 1000, 64]
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
                                     (128, "bfloat16"), (256, "float32")],
                         ids=["two_blocks", "padded", "bf16", "four_blocks"])
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


# ------------------------- masked and unmasked blocks in one forward (PR 41)
def _plain_out_lse(q, k, v, causal=True, window=None, scale=None):
    """Out and the scores' logsumexp by the plain composition, float32;
    k, v [B, Hkv, Sk, .] repeated for the query heads of each group."""
    group = q.shape[1] // k.shape[1]
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    s = s / np.sqrt(q.shape[-1]) if scale is None else s * scale
    if causal:
        i = jnp.arange(q.shape[2])[:, None]
        j = jnp.arange(k.shape[2])[None, :]
        keep = j <= i
        if window is not None:
            keep = keep & (i - j < window)
        s = jnp.where(keep[None, None], s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v), lse


# (id, query heads, key/value heads, Sq, Sk, D, Dv, block, causal, window,
# scale, dtype): every case but the last two puts blocks of BOTH kinds in
# one forward, the masked ones the diagonal (or the band's far edge)
# crosses and the unmasked ones under it
BOTH_KINDS = [
    ("d64_four_blocks", 4, 4, 128, 128, 64, 64, 32, True, None, None,
     "float32"),
    ("d128_four_blocks", 2, 2, 128, 128, 128, 128, 32, True, None, None,
     "float32"),
    ("keys_192_values_128", 2, 2, 128, 128, 192, 128, 32, True, None, 0.1,
     "float32"),
    ("group_of_4", 8, 2, 128, 128, 64, 64, 32, True, None, None, "float32"),
    ("group_of_7", 7, 1, 128, 128, 32, 32, 32, True, None, None, "float32"),
    ("d64_bf16", 8, 2, 128, 128, 64, 64, 32, True, None, None, "bfloat16"),
    ("d128_bf16", 2, 2, 128, 128, 128, 128, 32, True, None, None,
     "bfloat16"),
    # Sk no multiple of the block: the last key block holds padded keys,
    # which the bias channel neutralises (finite scores on the unguarded
    # path where the diagonal does not cross it; the ones before it plain)
    ("padded_keys_causal", 2, 2, 100, 100, 32, 32, 32, True, None, None,
     "float32"),
    ("padded_keys_non_causal", 2, 2, 72, 100, 32, 32, 32, False, None, None,
     "float32"),
    ("padded_keys_group_bf16", 4, 1, 100, 100, 64, 64, 32, True, None, None,
     "bfloat16"),
    # a band of two blocks' length: the first block a query block visits
    # holds keys for every row but the LAST, whose band starts with the
    # next block, and that next block carries no mask at all: a row whose
    # m_prev is still -inf goes into the unguarded path (exp(-inf) = 0, no
    # NaN); the test below asserts that the case is that one
    ("band_empty_row_then_unmasked", 2, 1, 128, 128, 32, 32, 16, True, 32,
     None, "float32"),
    ("band_empty_row_then_unmasked_bf16", 6, 1, 128, 128, 64, 64, 32, True,
     64, None, "bfloat16"),
    ("non_causal_four_blocks", 2, 2, 128, 128, 64, 64, 32, False, None, None,
     "float32"),
    ("non_causal_cross_lengths_bf16", 2, 2, 64, 128, 128, 128, 32, False,
     None, None, "bfloat16"),
]


@pytest.mark.parametrize("case", BOTH_KINDS, ids=lambda c: c[0])
def test_forward_out_and_lse_with_masked_and_unmasked_blocks(case):
    """`Out` and `Lse` of the forward kernel alone against the plain
    composition where one forward runs both of its bodies: the masked one
    with the guards of an empty row, the unmasked one without."""
    import ml_dtypes

    from paddle_tpu.parallel import flash

    (_, heads, kv_heads, Sq, Sk, D, Dv, block, causal, window, scale,
     dtype) = case
    bq, bk = block if isinstance(block, tuple) else (block, block)
    rng = np.random.RandomState(41)
    np_dtype = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    q, k, v = (jnp.asarray(rng.randn(*shape).astype(np_dtype))
               for shape in ((2, heads, Sq, D), (2, kv_heads, Sk, D),
                             (2, kv_heads, Sk, Dv)))
    if causal:
        # both bodies run, or with padded keys in a masked last block
        # alone the case is not what its name says
        masked = flash.blocks_masked(Sq, Sk, bq, bk, window)
        assert 0 < masked < flash.blocks_visited(Sq, Sk, bq, bk, window)
    if window is not None:
        # the last query block: its first visited key block leaves its
        # last row empty and the block after that one is unmasked
        i = Sq // bq - 1
        first = max(i * bq - (window - 1), 0) // bk
        assert Sq - 1 - (first * bk + bk - 1) >= window
        assert flash._crossed(i, first, bq, bk, None, window) == (True, True)
        assert flash._crossed(i, first + 1, bq, bk, None, window) == (
            True, False)
    out, lse = flash.flash_attention_fwd(
        q, k, v, causal=causal, scale=scale, block_q=bq, block_k=bk,
        window=window)
    want_out, want_lse = _plain_out_lse(q, k, v, causal, window, scale)
    assert out.shape == want_out.shape and out.dtype == q.dtype
    assert lse.shape == want_lse.shape and lse.dtype == jnp.float32
    assert not np.isnan(np.asarray(out, np.float32)).any()
    assert np.isfinite(np.asarray(lse)).all()
    tol = dict(atol=2e-5, rtol=1e-4) if dtype == "float32" \
        else dict(atol=3e-2, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5 if dtype == "float32" else 2e-2,
                               rtol=1e-5 if dtype == "float32" else 1e-2)


@pytest.mark.parametrize("S,block,window,masked,visited", [
    (8192, 1024, None, 8, 36),          # LFM2, SmallThinker, Laguna: full
    (4096, 1024, None, 4, 10),          # OLMoE, Xing
    (8192, 1024, 4096, 12, 30),         # SmallThinker's band
    (8192, 512, 512, 31, 31),           # Laguna's band: every block
    (128, 32, None, 4, 10), (100, 32, None, 4, 10), (128, 16, 33, 14, 21)])
def test_blocks_masked_is_the_set_all_three_kernels_mask(S, block, window,
                                                         masked, visited):
    """`blocks_masked` counts what `_for_block` hands `accumulate(True)`,
    and the forward, the dK/dV and the dQ kernel go through `_for_block`
    with the same arguments where no key is padded: 8 of 36 at the
    8k cells' full layers, 4 of 10 at the 4k cells'."""
    from paddle_tpu.parallel import flash

    assert flash.blocks_masked(S, S, block, block, window) == masked
    assert flash.blocks_visited(S, S, block, block, window) == visited
    # the predicate written out: a visited block that holds a pair above
    # the diagonal or one `window` or more back
    n, crossed = -(-S // block), 0
    for i in range(n):
        for j in range(n):
            rows = np.arange(i * block, (i + 1) * block)[:, None]
            cols = np.arange(j * block, (j + 1) * block)[None, :]
            keep = cols <= rows
            if window is not None:
                keep &= rows - cols < window
            crossed += bool(keep.any() and not keep.all())
    assert crossed == masked
    # padded keys: the backward kernels mask a block that holds them by
    # `kv_len`, the forward does not (its channel); in a square causal grid
    # only the diagonal's last block holds any
    assert flash.blocks_masked(S, S, block, block, window, kv_len=S) == masked
    assert (flash.blocks_masked(160, 100, 32, 32),
            flash.blocks_masked(160, 100, 32, 32, kv_len=100)) == (4, 5)


@pytest.mark.parametrize("window", [None, 40], ids=["triangle", "band_40"])
def test_the_three_kernels_mask_the_same_blocks(window, monkeypatch):
    """Traced, the three kernels' bodies call `_for_block` with the same
    block sizes, `causal`, `window` and no `kv_len` (no key is padded):
    the set `blocks_masked` counts is one set."""
    from paddle_tpu.parallel import flash

    seen = []
    real = flash._for_block

    def spy(accumulate, qi, ki, block_q, block_k, causal, kv_len,
            window=None, inside=True):
        seen.append((block_q, block_k, causal, kv_len, window))
        return real(accumulate, qi, ki, block_q, block_k, causal, kv_len,
                    window, inside)

    monkeypatch.setattr(flash, "_for_block", spy)
    q, k, v, cot = _band_case(128, 4, 2, seed=2)
    jax.make_jaxpr(lambda q, k, v: _grads(
        lambda *a: flash_attention(*a, causal=True, block_q=32, block_k=32,
                                   window=window), q, k, v, cot))(q, k, v)
    assert len(seen) == 3 and len(set(seen)) == 1, seen
    assert seen[0] == (32, 32, True, None, window)
