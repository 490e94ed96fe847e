"""The indexer's loss and its gradients as two Pallas TPU kernels: what
`sparse_index.loss_and_grads` computes a block of 256 queries at a time
through [heads, 256, S] float32 arrays in HBM, worked here a (query block,
key block) tile at a time over the blocks at and below the diagonal, with
nothing of shape [heads, block, block] leaving VMEM.

    p[t, s]  = mean_h exp(q_h[t] . k_{h // group}[s] x scale - lse_h[t])
               on the chosen pairs, 0 elsewhere            (the target)
    I[t, s]  = sum_j w[t, j] relu(q_I[t, j] . k_I[s])
    L        = sum_t sum_{s chosen, p > 0} p (log p - I + log_z_t),
               log_z_t the logsumexp of I[t, .] over the chosen keys
    dI       = softmax over the chosen keys of I x (sum_s p) - p
    d q_I[t, j] = sum_s dI w[t, j] [q_I[t, j] . k_I[s] > 0] k_I[s]
    d w[t, j]   = sum_s dI relu(q_I[t, j] . k_I[s])
    d k_I[s]    = sum_{t, j} dI w[t, j] [q_I[t, j] . k_I[s] > 0] q_I[t, j]

`index_target` (grid: query blocks x key blocks, the keys innermost) forms
a tile of p with the loop over the query heads INSIDE (a head of q and its
key/value head by a leading index, the head's logsumexp from a [heads,
block_q, 128] scratch filled once a query block), and the tile of I beside
it: p leaves as [S, S] float32, written once (the blocks above the diagonal
never, and never read), and each query's log_z, sum_s p and sum_s p (log p
- I) by a running logsumexp over the key blocks. `index_grads` (the same
grid) reads p's tile back, forms the indexer's products again (kept ReLU'd
in VMEM, a head each), dI from the finished log_z, and all three gradients:
d q_I and d w accumulate in the query block's own output and scratch, d k_I
in ONE [S, Di] float32 output that stays in VMEM for the whole grid (2 MB
at 8192 x 64), so the score product is formed once a tile for both sides.
The products take the operands' dtype and accumulate in float32; p, I, the
softmax, the loss and dI are float32; dI w [. > 0] is rounded to the
operands' dtype as it enters the MXU, which is what XLA's default precision
gives the plain lowering's float32 einsums on the chip. The loops over the
heads are written out: rolled into a `fori_loop` no head's products run
under another's element-wise work, and the two kernels take 9.3 ms a layer
where these take 5.9 (PERF.md, PR 50).

Names without "flash": `KERNELS`. On a TPU place Mosaic compiles them;
anywhere else (the tests) the Pallas interpreter runs them.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["KERNELS", "BLOCKS", "takes", "target", "grads",
           "loss_and_grads"]

KERNELS = ("index_target", "index_grads")
# (block_q, block_k), from tools/index_loss_sweep.py on the v5e at the
# keye_vl_2_0_30b_a3b cell's shape (PERF.md, PR 50)
BLOCKS = (512, 512)
F32 = jnp.float32
_LANES = 128
# `index_grads` keeps sixteen [block_q, block_k] float32 ReLU'd products
# (16 MiB at 512 x 512), `index_target` a query block of all 32 heads (4
# MiB, twice) and their logsumexps across lanes (8 MiB)
_VMEM_LIMIT = 100 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def takes(S, heads, kv_heads, head_dim, index_heads, index_dim, dtype,
          blocks=BLOCKS):
    """Whether the kernels take a row of S tokens in `dtype`: whole blocks
    that are whole lane tiles, whole groups of query heads, heads of whole
    lane tiles, an indexer's head within one, bf16 operands (what Mosaic
    was asked to compile; a float32 program keeps the plain lowering), and
    what a grid step keeps in VMEM within three quarters of the limit: the
    indexer's ReLU'd products, a query block of every head twice, the
    heads' logsumexps across lanes."""
    bq, bk = blocks
    kept = 4 * index_heads * bq * bk + heads * bq * (4 * head_dim
                                                     + 4 * _LANES)
    return (S % bq == 0 and S % bk == 0 and bq % _LANES == 0
            and bk % _LANES == 0 and heads % kv_heads == 0
            and head_dim % _LANES == 0 and index_dim <= _LANES
            and index_dim % 8 == 0 and 4 * kept <= 3 * _VMEM_LIMIT
            and jnp.dtype(dtype) == jnp.bfloat16)


def _visited(qi, ki, block_q, block_k):
    """Whether tile (qi, ki) holds a pair at or below the diagonal."""
    return qi * block_q + block_q - 1 >= ki * block_k


def _last_key_block(block_q, block_k):
    """i, j -> the key block step j of query block i names: j up to the
    diagonal's, that one after (a skipped step moves nothing)."""
    return lambda i, j: jnp.minimum(j, (i * block_q + block_q - 1) // block_k)


def _across(x, n):
    """x [rows, _LANES], every lane of a row the same -> [rows, n]."""
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _columns_across(ref, scr):
    """scr[j] = column j of the [rows, n] block `ref`, float32, in every
    lane: once a query block, so that the loops over the heads read a
    head's column by a leading index."""
    for j in range(scr.shape[0]):
        scr[j] = jnp.broadcast_to(ref[:, j:j + 1].astype(F32), scr.shape[1:])


def _indexer_scores(qi_ref, ki_ref, w_scr, block_q, block_k, relu_scr=None):
    """I [bq, bk] float32 = sum_j w_j relu(q_I[:, j] . k_I^T); each ReLU'd
    product is left in `relu_scr[j]` where one is given."""
    k_i = ki_ref[...]
    total = jnp.zeros((block_q, block_k), F32)
    for j in range(qi_ref.shape[0]):
        relu = jnp.maximum(_dot(qi_ref[j], k_i, _NT), 0.0)
        if relu_scr is not None:
            relu_scr[j] = relu
        total = total + _across(w_scr[j], block_k) * relu
    return total


def _target_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, mask_ref,
                   p_ref, lz_ref, ps_ref, a_ref, m_scr, l_scr, lse_scr,
                   w_scr, *, scale, group, block_q, block_k, nk):
    qi, ki = pl.program_id(0), pl.program_id(1)
    heads = q_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        ps_ref[...] = jnp.zeros_like(ps_ref)
        a_ref[...] = jnp.zeros_like(a_ref)
        _columns_across(lse_ref, lse_scr)
        _columns_across(w_ref, w_scr)

    @pl.when(_visited(qi, ki, block_q, block_k))
    def _tile():
        chosen = mask_ref[...].astype(jnp.int32) != 0

        total = jnp.zeros((block_q, block_k), F32)
        for h in range(heads):
            s = _dot(q_ref[h], k_ref[h // group], _NT) * scale
            total = total + jnp.exp(s - _across(lse_scr[h], block_k))
        p = jnp.where(chosen, total / heads, 0.0)
        p_ref[...] = p
        I = _indexer_scores(qi_ref, ki_ref, w_scr, block_q, block_k)
        # the running logsumexp of I over the chosen keys; a row may have
        # chosen none of this block's
        masked = jnp.where(chosen, I, -jnp.inf)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(masked, axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
        l_scr[...] = corr * l_scr[...] + jnp.sum(
            jnp.exp(masked - _across(m_safe, block_k)), axis=1,
            keepdims=True)
        m_scr[...] = m_new
        weigh = p > 0
        ps_ref[...] += jnp.sum(p, axis=1, keepdims=True)
        a_ref[...] += jnp.sum(jnp.where(
            weigh, p * (jnp.log(jnp.where(weigh, p, 1.0)) - I), 0.0),
            axis=1, keepdims=True)

    @pl.when(ki == nk - 1)
    def _finish():
        m, l = m_scr[...], l_scr[...]
        lz_ref[...] = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)),
                                0.0)


def _grads_kernel(qi_ref, ki_ref, w_ref, mask_ref, p_ref, lz_ref, ps_ref,
                  dq_ref, dw_ref, dk_ref, w_scr, dw_scr, relu_scr, *,
                  block_q, block_k, nk):
    qi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when((qi == 0) & (ki == 0))
    def _init_keys():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(ki == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_scr[...] = jnp.zeros_like(dw_scr)
        _columns_across(w_ref, w_scr)

    @pl.when(_visited(qi, ki, block_q, block_k))
    def _tile():
        chosen = mask_ref[...].astype(jnp.int32) != 0
        I = _indexer_scores(qi_ref, ki_ref, w_scr, block_q, block_k,
                            relu_scr)
        soft = jnp.exp(jnp.where(chosen, I, -jnp.inf)
                       - _across(lz_ref[...], block_k))
        d_i = soft * _across(ps_ref[...], block_k) - p_ref[...]
        k_i = ki_ref[...]
        d_k = jnp.zeros((block_k, k_i.shape[1]), F32)
        for j in range(qi_ref.shape[0]):
            relu = relu_scr[j]
            dw_scr[j] += jnp.sum(d_i * relu, axis=1, keepdims=True)
            d_s = jnp.where(relu > 0, d_i * _across(w_scr[j], block_k),
                            0.0).astype(k_i.dtype)
            dq_ref[j] += _dot(d_s, k_i, _NN)
            d_k = d_k + _dot(d_s, qi_ref[j], _TN)
        rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        dk_ref[rows, :] += d_k

    @pl.when(ki == nk - 1)
    def _finish():
        for j in range(dw_scr.shape[0]):
            dw_ref[:, j:j + 1] = dw_scr[j][:, :1]


def _specs(S, Hi, Di, bq, bk):
    """(a [bq, width] block of query rows, the indexer's three operands,
    a [bq, bk] tile of an [S, S] array) for the grid (query block, key
    block): a step above the diagonal names the diagonal's key block, so
    it moves nothing."""
    k_of = _last_key_block(bq, bk)

    def rows(width):
        return pl.BlockSpec((bq, width), lambda i, j: (i, 0))

    indexer = [pl.BlockSpec((Hi, bq, Di), lambda i, j: (0, i, 0)),
               pl.BlockSpec((bk, Di), lambda i, j: (k_of(i, j), 0)),
               rows(Hi)]
    return rows, indexer, pl.BlockSpec((bq, bk),
                                       lambda i, j: (i, k_of(i, j))), k_of


# sequential: d k_I's one block is every step's
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def target(q, k, lse_t, qi_h, k_i, w, mask, scale, blocks=BLOCKS,
           interpret=None):
    """q [H, S, D], k [Hkv, S, D], lse_t [S, H] float32, qi_h [Hi, S, Di],
    k_i [S, Di], w [S, Hi] float32, mask [S, S] int8 -> (p [S, S] float32,
    nothing written above the diagonal's blocks; each query's log_z, sum_s
    p and sum_s p (log p - I), [S, 128] float32, every lane of a row the
    same)."""
    (H, S, D), Hkv = q.shape, k.shape[0]
    (Hi, _, Di), (bq, bk) = qi_h.shape, blocks
    rows, indexer, tile, k_of = _specs(S, Hi, Di, bq, bk)
    stat = jax.ShapeDtypeStruct((S, _LANES), F32)
    return pl.pallas_call(
        functools.partial(_target_kernel, scale=scale, group=H // Hkv,
                          block_q=bq, block_k=bk, nk=S // bk),
        grid=(S // bq, S // bk),
        in_specs=[pl.BlockSpec((H, bq, D), lambda i, j: (0, i, 0)),
                  pl.BlockSpec((Hkv, bk, D),
                               lambda i, j: (0, k_of(i, j), 0)),
                  rows(H)] + indexer + [tile],
        out_specs=[tile, rows(_LANES), rows(_LANES), rows(_LANES)],
        out_shape=[jax.ShapeDtypeStruct((S, S), F32), stat, stat, stat],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), F32),
                        pltpu.VMEM((bq, _LANES), F32),
                        pltpu.VMEM((H, bq, _LANES), F32),
                        pltpu.VMEM((Hi, bq, _LANES), F32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret() if interpret is None else interpret,
        name=KERNELS[0],
    )(q, k, lse_t, qi_h, k_i, w, mask)


def grads(qi_h, k_i, w, mask, p, log_z, p_sum, blocks=BLOCKS,
          interpret=None):
    """The loss's gradient with respect to (qi_h [Hi, S, Di], w [S, Hi],
    k_i [S, Di]), float32, from `target`'s p, log_z and sum_s p."""
    (Hi, S, Di), (bq, bk) = qi_h.shape, blocks
    rows, indexer, tile, _ = _specs(S, Hi, Di, bq, bk)
    return pl.pallas_call(
        functools.partial(_grads_kernel, block_q=bq, block_k=bk,
                          nk=S // bk),
        grid=(S // bq, S // bk),
        in_specs=indexer + [tile, tile, rows(_LANES), rows(_LANES)],
        out_specs=[pl.BlockSpec((Hi, bq, Di), lambda i, j: (0, i, 0)),
                   rows(Hi),
                   pl.BlockSpec((S, Di), lambda i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((Hi, S, Di), F32),
                   jax.ShapeDtypeStruct((S, Hi), F32),
                   jax.ShapeDtypeStruct((S, Di), F32)],
        scratch_shapes=[pltpu.VMEM((Hi, bq, _LANES), F32),
                        pltpu.VMEM((Hi, bq, _LANES), F32),
                        pltpu.VMEM((Hi, bq, bk), F32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret() if interpret is None else interpret,
        name=KERNELS[1],
    )(qi_h, k_i, w, mask, p, log_z, p_sum)


# Traced once a signature: a step's four layers, and the two or three
# programs a process lowers, call with the same shapes, and tracing the
# written-out loops is what the kernels cost the host (as `grouped._gmm_call`).
@functools.partial(jax.jit, static_argnums=(7, 8, 9), inline=True)
def _loss_and_grads(q, k, lse, q_i, k_i, w, mask, scale, blocks, interpret):
    qi_h, w32 = jnp.swapaxes(q_i, 0, 1), w.astype(F32)
    p, log_z, p_sum, rest = target(q, k, lse.T, qi_h, k_i, w32, mask, scale,
                                   blocks, interpret)
    d_q, d_w, d_k = grads(qi_h, k_i, w32, mask, p, log_z, p_sum, blocks,
                          interpret)
    total = jnp.sum(rest[:, 0] + log_z[:, 0] * p_sum[:, 0])
    return total, jnp.swapaxes(d_q, 0, 1), d_k, d_w


def loss_and_grads(q, k, lse, q_i, k_i, w, mask, scale, blocks=BLOCKS):
    """One row of tokens, what `sparse_index.loss_and_grads` takes and
    gives: q [H, S, D], k [Hkv, S, D], lse [H, S] float32, q_i [S, Hi, Di],
    k_i [S, Di], w [S, Hi], mask [S, S] int8 -> (sum over the queries of
    KL(p || softmax_S I), float32, and its gradient with respect to q_i,
    k_i, w, float32). S a multiple of both blocks (`takes`)."""
    return _loss_and_grads(q, k, lse, q_i, k_i, w, mask, float(scale),
                           tuple(blocks), pallas_interpret())
