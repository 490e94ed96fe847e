"""Flash attention as a Pallas TPU kernel.

The hot-op kernel path the reference implements with cuDNN/hand-written
CUDA: exact attention computed block-by-block in VMEM with the streaming
softmax (running max + normalizer), never materializing the [S, S] score
matrix in HBM. Complements parallel/ring.py: ring attention shards the
sequence ACROSS chips and streams K/V around the ICI ring; flash_attention
is the WITHIN-chip kernel.

Layout [B, H, S, D]. The kernel runs a (batch*heads, q-blocks, k-blocks)
grid with the k dimension innermost ("arbitrary" semantics — sequential
per core) carrying the running (m, l, acc) in VMEM scratch.

The backward pass is two more kernels in the same idiom, wired through
jax.custom_vjp from the saved output and logsumexp: dK/dV on a (batch*heads,
k-blocks, q-blocks) grid with float32 dk, dv accumulators in scratch, dQ on
(batch*heads, q-blocks, k-blocks) with a float32 dq accumulator. Each
recomputes its score block in VMEM (s, p = exp(s - lse), dp, ds = p (dp -
delta), all float32; p and ds are rounded to the operands' dtype only as
they enter the MXU) and writes nothing of the square to HBM. Causal: blocks
above the diagonal are skipped and their index maps name a block already
resident, so a skipped step moves nothing; only blocks that cross the
diagonal (or hold padded keys) pay for the mask. On the v5e at [2, 16, 4096,
128] bf16 causal, 1024 x 1024 blocks: forward 2.24 ms, backward 3.75 ms
(dK/dV 2.11 = 130 TFLOP/s over the triangle, dQ 1.80 = 114), where the
lax.scan of float32 einsums over the whole square that they replaced took
14.85 (PERF.md, PR 27).

On a TPU place Mosaic compiles the kernels; on any other place (CPU tests)
they run in Pallas interpret mode (core.places.pallas_interpret).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["flash_attention", "flash_attention_fwd", "flash_attention_bwd"]


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, block_q, block_k, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _accumulate():
        q = q_ref[0]                   # [bq, D]
        k = k_ref[0]                   # [bk, D]
        v = v_ref[0]                   # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)

        m_prev = m_scr[:]              # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)  # masked rows
        p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_safe[:, None]))
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
        l_scr[:] = corr * l_scr[:] + jnp.sum(p, axis=1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    if causal:
        # skip k-blocks entirely above the causal frontier (half the grid)
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)
        lse = jnp.where(
            jnp.isneginf(m_scr[:]), -jnp.inf, m_scr[:] + jnp.log(l))
        # lse rides in an [8, block_q] tile: Mosaic requires the last two
        # block dims to be (8, 128)-aligned, so broadcast over 8 sublanes
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    """q [BH, Sq, D] (Sq % block_q == 0), k/v [BH, Sk, D] (Sk % block_k
    == 0) -> (out [BH, Sq, D], lse [BH, Sq])."""
    BH, Sq, Dq = q.shape  # Dq may carry the +1 padding-mask channel
    Sk = k.shape[1]
    Dv = v.shape[-1]
    nq, nk = Sq // block_q, Sk // block_k
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dq), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dq), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )(q, k, v)


def _fwd_padded(q, k, v, scale, causal, block_q, block_k):
    """Pad S to block multiples; padded KEYS are neutralized by extending D
    with a bias channel (q gains a 1, real keys a 0, padded keys -BIG), so
    their scores vanish under exp without any in-kernel mask plumbing."""
    B, H, Sq, _ = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qw, kw, vw = q, k, v
    if pad_q:
        qw = jnp.pad(qw, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kw = jnp.pad(kw, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vw = jnp.pad(vw, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        BIG = jnp.asarray(3e4 / max(scale, 1e-6), jnp.float32).astype(q.dtype)
        qw = jnp.concatenate([qw, jnp.ones_like(qw[..., :1])], axis=-1)
        maskch = jnp.where(
            (jnp.arange(kw.shape[2]) < Sk)[None, None, :, None],
            jnp.zeros((), q.dtype), -BIG)
        kw = jnp.concatenate(
            [kw, jnp.broadcast_to(maskch, kw.shape[:3] + (1,))], axis=-1)
    BH = B * H
    Dk = qw.shape[-1]
    out, lse = _flash_fwd(
        qw.reshape(BH, Sq + pad_q, Dk), kw.reshape(BH, Sk + pad_k, Dk),
        vw.reshape(BH, Sk + pad_k, Dv), scale, causal, block_q, block_k)
    out = out.reshape(B, H, Sq + pad_q, Dv)[:, :, :Sq]
    lse = lse[:, 0, :].reshape(B, H, Sq + pad_q)[:, :, :Sq]
    return out, lse


def normalize_blocks(block_q, block_k, Sq, Sk):
    """Mosaic block-alignment rule: every block dim must be (8, 128)-aligned
    in its (sublane, lane) position OR equal to the (padded) array dim. So a
    block is legal when it is a multiple of 128 (the lse tile's lane dim) or
    when it covers the whole padded sequence (n=1). Auto-shrink short
    sequences to a single 8-rounded block; round user blocks up to 128 when
    Mosaic compiles the kernel (interpret mode has no constraint). Callers
    that reach _fwd_padded directly (ring_flash_attention) must use this
    too."""
    on_tpu = not pallas_interpret()

    def _pick(block, S):
        S8 = -(-max(S, 1) // 8) * 8
        block = int(block)
        if on_tpu and block % 128:
            block = -(-block // 128) * 128
        return S8 if block >= S8 else block

    return _pick(block_q, Sq), _pick(block_k, Sk)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=256, block_k=256):
    """Exact attention [B, H, S, D] -> [B, H, S, D]; differentiable.

    Defaults (256, 256) measured fastest on a v5e chip at S=1024 D=128 —
    faster than XLA's fused dense attention there, with O(S * block) memory
    instead of the dense [S, S] score matrix (S >= 16k runs comfortably).
    The backward kernels take the same blocks; at S=4096 both directions
    are fastest at (1024, 1024) (ops/lm_ops.py carries the sweeps). Blocks
    auto-shrink for short sequences."""
    scale, block_q, block_k = _resolve(q, k, scale, block_q, block_k)
    return _flash(q, k, v, scale, bool(causal), block_q, block_k)


def _resolve(q, k, scale, block_q, block_k):
    block_q, block_k = normalize_blocks(block_q, block_k,
                                        q.shape[2], k.shape[2])
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return float(scale), int(block_q), int(block_k)


def flash_attention_fwd(q, k, v, causal=False, scale=None,
                        block_q=256, block_k=256):
    """The forward kernel alone: (out [B, H, S, D], lse [B, H, S]). For a
    caller that keeps `lse` itself and calls `flash_attention_bwd` later
    (an op whose backward is another op), so the kernel runs once."""
    scale, block_q, block_k = _resolve(q, k, scale, block_q, block_k)
    return _fwd_padded(q, k, v, scale, bool(causal), block_q, block_k)


def flash_attention_bwd(q, k, v, out, lse, do, causal=False, scale=None,
                        block_q=1024, block_k=1024):
    """(dq, dk, dv) from the saved output and logsumexp of
    `flash_attention_fwd` with the same q, k, v, causal and scale. The
    blocks are the backward kernels' own; the default is the fastest of a
    sweep on the v5e at [2, 16, 4096, 128] bf16 causal (ops/lm_ops.py)."""
    scale, block_q, block_k = _resolve(q, k, scale, block_q, block_k)
    return _flash_vjp_bwd(scale, bool(causal), block_q, block_k,
                          (q, k, v, out, lse), do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    out, _ = _fwd_padded(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _fwd_padded(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


# The backward kernels hold four float32 [block_q, block_k] tiles at once
# (s, p, dp, ds: 16 MiB at 1024 x 1024), past Mosaic's default scoped 16 MiB
# of the v5e's 128 MiB of VMEM.
_BWD_VMEM_LIMIT = 64 * 2 ** 20

# dot_general dimension numbers on 2-d blocks: a @ b.T and a @ b
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _weights(s, lse, masked, q0, k0, q_axis, causal, kv_len):
    """p = exp(s - lse) on one float32 score block; where `masked`, zero
    for the pairs above the diagonal and for padded keys. Queries run
    along `q_axis` of the block and keys along the other."""
    p = jnp.exp(s - lse)
    if not masked:
        return p
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    keep = None if kv_len is None else k_pos < kv_len
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        keep = q_pos >= k_pos if keep is None else keep & (q_pos >= k_pos)
    return jnp.where(keep, p, 0.0)


def _for_block(accumulate, qi, ki, block_q, block_k, causal, kv_len):
    """Run `accumulate(masked)` for the score block (qi, ki): not at all if
    the block lies wholly above the causal frontier, and with the mask only
    if some pair of it carries no weight (the block crosses the diagonal, or
    holds keys at or past `kv_len`, which are padding)."""
    if not causal and kv_len is None:
        accumulate(False)
        return
    visited, edge = True, False
    if causal:
        visited = qi * block_q + block_q - 1 >= ki * block_k
        edge = qi * block_q < ki * block_k + block_k - 1
    if kv_len is not None:
        edge = edge | ((ki + 1) * block_k > kv_len)
    pl.when(visited & edge)(lambda: accumulate(True))
    pl.when(visited & jnp.logical_not(edge))(lambda: accumulate(False))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, ld_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, block_q, block_k, nq,
                kv_len):
    """One key block against the query blocks at or below it. Scores are
    held transposed, [bk, bq], so that every product is a plain one (no
    block is transposed on its way into the MXU) and the per-query `lse`
    and `delta` broadcast along sublanes from their lane-major rows."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = _dot(k, q, _NT) * scale                       # [bk, bq]
        pt = _weights(st, ld_ref[0, 0:1, :], masked, qi * block_q,
                      ki * block_k, 1, causal, kv_len)
        dv_scr[:] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v, do, _NT)
        dst = pt * (dpt - ld_ref[0, 1:2, :])
        dk_scr[:] += _dot(dst.astype(q.dtype), q, _NN)

    _for_block(_accumulate, qi, ki, block_q, block_k, causal, kv_len)

    @pl.when(qi == nq - 1)
    def _finish():
        # ds = p (dp - delta) scale: the scale once, on the float32 sum
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, ld_ref, dq_ref, dq_scr, *,
               scale, causal, block_q, block_k, nk, kv_len):
    """One query block against the key blocks at or below the diagonal;
    scores [bq, bk], `lse` and `delta` as columns."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _dot(q, k, _NT) * scale                        # [bq, bk]
        p = _weights(s, ld_ref[0, :, 0:1], masked, qi * block_q,
                     ki * block_k, 0, causal, kv_len)
        dp = _dot(do, v, _NT)
        ds = p * (dp - ld_ref[0, :, 1:2])
        dq_scr[:] += _dot(ds.astype(k.dtype), k, _NN)

    _for_block(_accumulate, qi, ki, block_q, block_k, causal, kv_len)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, do, lse, delta, scale, causal, block_q, block_k,
               kv_len):
    """q [BH, Sq, D], k [BH, Sk, D], v [BH, Sk, Dv], do [BH, Sq, Dv] (Dv
    may differ from D, as in the forward), lse, delta [BH, Sq] float32
    (Sq % block_q == 0, Sk % block_k == 0; keys at and past `kv_len`, if
    given, are padding) -> dq, dk, dv. Two kernels: dK/dV with the query
    blocks innermost, dQ with the key blocks innermost, each recomputing
    its score block in VMEM from the saved logsumexp. A step above the
    causal frontier is skipped, and its index maps name the block of the
    nearest visited step, so it moves nothing either."""
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    nq, nk = Sq // block_q, Sk // block_k
    if causal:
        # dK/dV: the first query block that sees key block j (skipped steps
        # come first); dQ: the last key block query block i sees (last)
        def q_of(j, i):
            return jnp.minimum(jnp.maximum(i, (j * block_k) // block_q),
                               nq - 1)

        def k_of(i, j):
            return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
    else:
        def q_of(j, i):
            return i

        def k_of(i, j):
            return j
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=kv_len)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_BWD_VMEM_LIMIT)
    ld = jnp.stack([lse, delta], axis=1)                   # [BH, 2, Sq]

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **static),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, q_of(j, i), 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, Dv),
                         lambda b, j, i: (b, q_of(j, i), 0)),
            pl.BlockSpec((1, 2, block_q), lambda b, j, i: (b, 0, q_of(j, i))),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=params,
        interpret=pallas_interpret(),
    )(q, k, v, do, ld)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **static),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, k_of(i, j), 0)),
            pl.BlockSpec((1, block_k, Dv),
                         lambda b, i, j: (b, k_of(i, j), 0)),
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 2), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=params,
        interpret=pallas_interpret(),
    )(q, k, v, do, jnp.swapaxes(ld, 1, 2))
    return dq, dk, dv


def _flash_vjp_bwd(scale, causal, block_q, block_k, res, do):
    """Pad as `_fwd_padded` does and run the two backward kernels. A
    padded key gets no weight (the kernels mask keys past Sk); a padded
    query row carries dO = 0 and delta = 0, so with any finite lse it adds
    nothing to dK or dV, and its dQ row is cut off."""
    q, k, v, out, lse = res
    B, H, Sq, _ = q.shape
    Sk = k.shape[2]
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)

    def rows(x, pad):
        x = x.reshape((B * H,) + x.shape[2:])
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x

    dq, dk, dv = _flash_bwd(
        rows(q, pad_q), rows(k, pad_k), rows(v, pad_k),
        rows(do.astype(q.dtype), pad_q), rows(lse, pad_q), rows(delta, pad_q),
        scale, causal, block_q, block_k, Sk if pad_k else None)
    return (dq[:, :Sq].reshape(q.shape), dk[:, :Sk].reshape(k.shape),
            dv[:, :Sk].reshape(v.shape))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
