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

