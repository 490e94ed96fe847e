"""The lightning indexer of DeepSeek Sparse Attention (DeepSeek-V3.2-Exp's
report, equations 1 and 2), as Keye-VL-2.0's language model carries it in
front of grouped-query attention: its scores, the exact top-k selection
they make, and the loss that trains it. Plain `jax.numpy`, worked A BLOCK
OF QUERY ROWS AT A TIME so that no [S, S] float32 array and no [S, heads,
S] product outlives a step of a `lax.scan` (at S 8192 one such square is
268 MB and the indexer's product over its 16 heads 4.3 GB).

    I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t   (float32;
              the products take the operands' dtype and accumulate in
              float32; w carries the model's scale already)
    S_t     = the k values of s <= t with the largest I[t, s] (all of them
              while t < k; of equal scores the lower s)
    p[t, s] = mean over the heads of the attention's probabilities over S_t
    L       = mean_t sum_{s in S_t} p (log p - log softmax_{S_t}(I[t, .]))

THE SELECTION IS EXACT AND NEEDS NO SORT: a float32's bits, with the sign
folded, order as unsigned integers, so the k-th largest score of a row is
found bit by bit, 32 counts of `key >= candidate` over the block
(`_kth_largest`), and the ties at that threshold are given to the lowest
positions by one running count. (`lax.top_k` and `lax.sort` over [8192,
8192] compile for seconds an operand on the TPU and run as XLA's sort;
`lax.approx_max_k` is another function.) The result is a mask [S, S] int8,
the causal triangle included: what `parallel/flash.py`'s kernels read as
`mask`. (On a TPU place a block's threshold and mask are ONE Pallas kernel,
`parallel/index_select.py`, which `select_rows` hands the block's scores:
the same bisection with the keys in VMEM, bit-equal.)

`loss_and_grads` returns the loss AND its gradient with respect to q_I,
k_I and w in one pass over the blocks (the loss is a scalar: its
cotangent only scales them), so the head-mean probabilities p, which cost
a pass over the main attention's scores, are formed once a step.
"""

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
# query rows a scan step works on: [BLOCK, 16, 8192] float32 is 134 MB
BLOCK = 256
# the score product inside `select`, under a scope of its own: a device
# trace tells the indexer's product from the bisection behind it
SCORES_SCOPE = "indexer_scores"


def scores(q_i, k_i, w):
    """q_i [n, Hi, Di], k_i [S, Di], w [n, Hi] -> I [n, S] float32 over
    ALL keys (the caller knows which are causal)."""
    s = jnp.einsum("qhd,kd->qhk", q_i, k_i, preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(s) * w.astype(F32)[:, :, None], axis=1)


def _keys(x):
    """float32 -> uint32 that orders as the floats do (-0.0 as +0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _scores_of(keys):
    """`_keys` undone: the float32 a key came from."""
    bits = jnp.where(keys >> 31 == 1, keys ^ jnp.uint32(1 << 31), ~keys)
    return lax.bitcast_convert_type(bits, F32)


def _kth_largest(keys, k):
    """keys [n, S] uint32, k [n] >= 1 -> the k-th largest key of each row:
    the largest value v with at least k keys >= v, built from the top
    bit."""
    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, prefix)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[0], jnp.uint32))


def select_rows(I, first, topk):
    """I [n, S] float32, the scores of queries first .. first + n - 1 ->
    ([n, S] bool: the causal keys while there are no more than `topk`, else
    the `topk` causal keys of largest score, ties to the lower position;
    the THRESHOLD [n] float32: the least score chosen, as the bisection
    found it). On a TPU place, for the shapes `index_select.takes`, ONE
    Pallas kernel that keeps a chunk's keys in VMEM and counts only its
    causal key tiles (`parallel/index_select.py`), bit-equal to this."""
    from . import index_select

    if not index_select.pallas_interpret() \
            and index_select.takes(*I.shape, topk):
        mask, tau = index_select.select_rows(I, first, topk)
        return mask != 0, tau
    n, S = I.shape
    t = first + jnp.arange(n)
    causal = jnp.arange(S)[None, :] <= t[:, None]
    # a key above the diagonal orders below every score (a score's key is
    # never 0: that is the image of a NaN with every bit set)
    keys = jnp.where(causal, _keys(I), jnp.uint32(0))
    k = jnp.minimum(t + 1, topk)
    tau = _kth_largest(keys, k)[:, None]
    above, at = keys > tau, keys == tau
    left = (k - jnp.sum(above, axis=1))[:, None]
    return (above | (at & (jnp.cumsum(at, axis=1, dtype=jnp.int32) <= left)),
            _scores_of(tau[:, 0]))


def _blocked(S, block):
    """(rows a step, steps, padding of the query axis)."""
    rows = min(block, S)
    steps = -(-S // rows)
    return rows, steps, steps * rows - S


def _by_blocks(x, rows, steps, pad, axis=0):
    """x with `axis` (the queries) padded and split [steps, rows]."""
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    x = x.reshape(x.shape[:axis] + (steps, rows) + x.shape[axis + 1:])
    return jnp.moveaxis(x, axis, 0)


def select(q_i, k_i, w, topk, block=BLOCK):
    """One row of tokens: q_i [S, Hi, Di], k_i [S, Di], w [S, Hi] -> (mask
    [S, S] int8 (query, key), 1 where the query attends to the key; the
    threshold [S] float32, each query's least chosen score: what a check
    can hold of the scores the step itself formed, which it never
    writes)."""
    S = q_i.shape[0]
    rows, steps, pad = _blocked(S, block)

    def step(_, xs):
        i, q_b, w_b = xs
        with jax.named_scope(SCORES_SCOPE):
            I = scores(q_b, k_i, w_b)
        chosen, tau = select_rows(I, i * rows, topk)
        return None, (chosen.astype(jnp.int8), tau)

    _, (mask, tau) = lax.scan(step, None, (
        jnp.arange(steps), _by_blocks(q_i, rows, steps, pad),
        _by_blocks(w, rows, steps, pad)))
    return mask.reshape(steps * rows, S)[:S], tau.reshape(-1)[:S]


def head_mean_rows(q, k, lse, chosen, scale):
    """The attention's probabilities averaged over the heads, of a block
    of queries: q [H, n, D], k [Hkv, S, D], lse [H, n] float32 (the
    softmax's logsumexp over the chosen keys), chosen [n, S] bool -> p [n,
    S] float32, zero off the selection. A key/value head at a time."""
    H, group = q.shape[0], q.shape[0] // k.shape[0]
    total = jnp.zeros(chosen.shape, F32)
    for g in range(k.shape[0]):
        heads = slice(g * group, (g + 1) * group)
        s = jnp.einsum("hqd,kd->hqk", q[heads], k[g],
                       preferred_element_type=F32) * scale
        total = total + jnp.sum(jnp.exp(s - lse[heads][:, :, None]), axis=0)
    return jnp.where(chosen, total / H, 0.0)


def _block_loss(q_b, k_i, w_b, p, chosen):
    """sum over the block's queries of KL(p || softmax over the chosen keys
    of I); a row without a chosen key (padding) adds nothing."""
    I = jnp.where(chosen, scores(q_b, k_i, w_b), -jnp.inf)
    some = jnp.any(chosen, axis=1, keepdims=True)
    log_z = jnp.where(some, jax.nn.logsumexp(
        jnp.where(some, I, 0.0), axis=1, keepdims=True), 0.0)
    weigh = chosen & (p > 0)
    log_ratio = jnp.log(jnp.where(weigh, p, 1.0)) \
        - (jnp.where(weigh, I, 0.0) - log_z)
    return jnp.sum(jnp.where(weigh, p * log_ratio, 0.0))


def head_mean(q, k, lse, mask, scale, block=BLOCK):
    """p [S, S] float32 of one row of tokens, whole (the comparison's)."""
    S = q.shape[1]
    rows, steps, pad = _blocked(S, block)
    _, p = lax.scan(
        lambda _, xs: (None, head_mean_rows(xs[0], k, xs[1], xs[2] != 0,
                                            scale)), None,
        (_by_blocks(q, rows, steps, pad, 1),
         _by_blocks(lse, rows, steps, pad, 1),
         _by_blocks(mask, rows, steps, pad)))
    return p.reshape(steps * rows, S)[:S]


def loss_and_grads(q, k, lse, q_i, k_i, w, mask, scale, block=BLOCK):
    """One row of tokens: the main attention's q [H, S, D], k [Hkv, S, D]
    and lse [H, S] (all three constants here: the target is detached),
    the indexer's q_i [S, Hi, Di], k_i [S, Di], w [S, Hi], mask [S, S]
    int8 -> (sum over the queries of KL(p || softmax_S I), float32, and
    its gradient with respect to q_i, k_i, w, float32)."""
    S = q_i.shape[0]
    rows, steps, pad = _blocked(S, block)
    grad = jax.value_and_grad(_block_loss, argnums=(0, 1, 2))
    k_f = k_i.astype(F32)

    def step(carry, xs):
        total, d_k = carry
        q_b, lse_b, qi_b, w_b, m_b = xs
        chosen = m_b != 0
        p = head_mean_rows(q_b, k, lse_b, chosen, scale)
        value, (d_q, d_kb, d_w) = grad(qi_b.astype(F32), k_f,
                                       w_b.astype(F32), p, chosen)
        return (total + value, d_k + d_kb), (d_q, d_w)

    (total, d_k), (d_q, d_w) = lax.scan(
        step, (jnp.zeros((), F32), jnp.zeros(k_i.shape, F32)),
        (_by_blocks(q, rows, steps, pad, 1),
         _by_blocks(lse, rows, steps, pad, 1),
         _by_blocks(q_i, rows, steps, pad),
         _by_blocks(w, rows, steps, pad),
         _by_blocks(mask, rows, steps, pad)))
    return (total, d_q.reshape((steps * rows,) + q_i.shape[1:])[:S], d_k,
            d_w.reshape(steps * rows, -1)[:S])
