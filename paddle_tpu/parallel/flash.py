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
diagonal (or hold padded keys) pay for the mask, in the forward (since PR
41) as in the backward kernels. On the v5e at [2, 16, 4096, 128] bf16
causal, 1024 x 1024 blocks: forward 1.50 ms (2.16 until PR 41, with the
mask on every block and its running max and normaliser as 1-D scratch),
backward 3.75 ms (dK/dV 2.11 = 130 TFLOP/s over the triangle, dQ 1.80 =
114), where the lax.scan of float32 einsums over the whole square that
they replaced took 14.85 (PERF.md, PRs 27 and 41).

Grouped-query heads (PR 32): k, v [B, Hkv, S, .] with H a multiple of Hkv
are read IN PLACE by the H / Hkv consecutive query heads of a group (an
index map `h -> h // group`, no repeated copy), and the dK/dV kernel sums
over the group inside: its innermost axis runs over (head of the group,
query block). A sliding window (causal only: query i sees the keys 0 <= i
- j < window): the innermost axis of each kernel then runs over the BAND's
blocks alone, from the first block the row sees (`_band_extents`; a grid
step costs ~0.3 us even when it computes nothing, and a visit ~1.4 us
beside its arithmetic), and the mask is applied only where the diagonal or
the band's far edge crosses a block. Without a window and with one
key/value head a query head the kernels lower as they did before either
existed (tests/test_flash_attention.py holds the grids and index maps).

A SELECTION (PR 49): with `mask` [B, S, S] int8 (query, key; nonzero = the
query sees the key; the same for every head; nothing above the diagonal)
the predicate on a pair is not a function of the two positions but a byte
of the mask, which the kernels take as one more operand: the softmax is
over the chosen keys ALONE (`sparse_attention`, the attention behind a
learned indexer). Blocks above the diagonal are skipped by block index as
ever; every other block is visited and takes the mask's block, also one
none of whose pairs is chosen (its weights come out zero: skipping those
takes a table of occupied blocks a later change can prefetch). Padding
needs no channel of its own there: padded keys and padded query rows are
zero bytes of the mask, and a query row with no chosen key gets output 0,
logsumexp -inf and weight 0 everywhere in the backward. Under their own
names (`SPARSE_KERNELS`). A caller that passes no mask lowers as before.

The masked BACKWARD is ONE kernel (PR 53, `SPARSE_BWD_KERNEL`) wherever the
three float32 accumulators fit VMEM for the length of a row
(`fused_backward_fits`: dQ [Sq, D] of a query head, dK and dV [Sk, .] of
its key/value head, together at most a quarter of the kernels' VMEM limit;
12 of 16 MiB at one row of 8192 and heads of 128): grid (key/value head,
head of its group, key block, query block), each score block visited ONCE,
s, exp, dO V^T, the mask's block and ds formed once a visit for all three
gradients, five products a pair where the two kernels spend seven (dK/dV
four, dQ three, each forming the block again). The visit is the dK/dV
kernel's, scores [bk, bq], plus dQ[i] += ds^T k with the first axis of
both operands contracted; the mask's block is read as it lies and transposed in
VMEM, so no transposed copy of the mask is written. On the v5e at [1, 32 on
4, 8192, 128] bf16 under a top-2048 mask: 8.82 ms a layer, 7.30 ps a
visited pair, 89% of the MXU's peak on its five products, where the two
kernels with the mask's transposed copy took 14.80 (12.25 ps, 74% on
seven); the same bits out (PERF.md, PR 53). A longer row (16k at heads of
128) keeps the two kernels, dK/dV reading a transposed copy of the mask
formed once on the caller's side here. The plain causal backward is the two
kernels as they were: its callers do not share a block size, a band or a
memory margin (ROADMAP Speed 5(a)).

On a TPU place Mosaic compiles the kernels; on any other place (CPU tests)
they run in Pallas interpret mode (core.places.pallas_interpret).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["flash_attention", "flash_attention_fwd", "flash_attention_bwd",
           "blocks_visited", "blocks_masked"]

# The kernels' names: Pallas puts a kernel's name on the name stack, so a
# device trace's op_name ends `.../causal_attention/flash_fwd/pallas_call`
# (forward), `.../causal_attention_grad/flash_dkv/...` and `.../flash_dq/...`
KERNELS = ("flash_fwd", "flash_dkv", "flash_dq")
# and with a mask (`.../sparse_attention/sparse_flash_fwd/pallas_call`)
SPARSE_KERNELS = tuple("sparse_" + name for name in KERNELS)
# and the masked backward as ONE kernel, where its accumulators fit
# (`fused_backward_fits`): `.../sparse_attention_grad/sparse_flash_bwd/...`
SPARSE_BWD_KERNEL = "sparse_flash_bwd"


# The running max and normaliser of the forward live in VMEM as [block_q,
# _LANES] float32, every lane of a row holding the row's value: what a
# reduction along a score block's rows gives, broadcast over one lane tile
# (the upstream Pallas TPU flash kernel keeps them so).
_LANES = 128


def _across(x, n):
    """x [rows, _LANES], every lane of a row the same -> [rows, n]."""
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return x[:, :n] if n < _LANES else jnp.broadcast_to(
        x[:, :1], (x.shape[0], n))


def _chosen(ref, transposed=False):
    """A mask's block as booleans (int8 has no compare on every chip:
    widened first), `transposed` for scores held the other way round."""
    wide = ref[0].astype(jnp.int32)
    return (wide.T if transposed else wide) != 0


def _kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k, nk,
            window=None, n_keys=None):
    """One query block against the key blocks it sees: the streaming
    softmax, float32. `rest`: the mask's block if the caller gave one, then
    the outputs and the scratch. A block no edge crosses (`_for_block`)
    pays neither for the mask nor for the guards of an empty row: its scores
    are all finite, so its row maxima are, and `exp(m_prev - m_new)` is 0 for a
    row that has seen nothing yet (m_prev = -inf), not NaN. m and l stay in
    the layout the row reductions give and `s - m`, `acc * corr` consume
    (`_LANES`): kept as 1-D [block_q] scratch, the relayout into lanes and
    back at every step cost a third of the kernel (PERF.md, PR 41)."""
    *mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    step = ki = pl.program_id(2)
    if window is not None:
        # the grid's last axis runs over the band's key blocks alone
        ki = _first_key_block(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate(masked):
        q = q_ref[0]                   # [bq, D]
        k = k_ref[0]                   # [bk, D]
        v = v_ref[0]                   # [bk, Dv]
        s = _dot(q, k, _NT) * scale    # [bq, bk]
        if masked and mask_ref:
            s = jnp.where(_chosen(mask_ref[0]), s, -jnp.inf)
        elif masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = q_pos >= k_pos
            if window is not None:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, -jnp.inf)

        m_prev = m_scr[...]            # [bq, _LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = m_new
        if masked:                     # a row of the block may be empty
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - _across(m_safe, block_k))
        corr = jnp.exp(m_prev - m_safe)
        if masked:
            p = jnp.where(jnp.isneginf(s), 0.0, p)
            corr = jnp.where(jnp.isneginf(m_prev), 0.0, corr)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _across(corr, acc_scr.shape[1]) \
            + _dot(p.astype(v.dtype), v, _NN)
        m_scr[...] = m_new

    # the mask where the diagonal or the band's far edge crosses, and
    # nowhere else (a selection's: on every visited block); padded keys are
    # finite scores (`_fwd_padded`), not masked
    if mask_ref:
        _for_chosen(_accumulate, qi, ki, block_q, block_k)
    else:
        _for_block(_accumulate, qi, ki, block_q, block_k, causal, None,
                   window, True if window is None else ki < n_keys)

    @pl.when(step == nk - 1)
    def _finish():
        m, l = m_scr[...], jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / _across(l, acc_scr.shape[1])).astype(
            o_ref.dtype)
        lse = jnp.where(jnp.isneginf(m), -jnp.inf, m + jnp.log(l))
        # lse rides in an [8, block_q] tile (Mosaic requires the last two
        # block dims to be (8, 128)-aligned): the one relayout a query block
        lse_ref[0] = lse.T[:8]


def _first_key_block(i, block_q, block_k, window):
    """The first key block query block i of a band sees."""
    return jnp.maximum(i * block_q - (window - 1), 0) // block_k


def _first_query_block(j, block_q, block_k):
    """The first query block that sees key block j."""
    return (j * block_k) // block_q


def _key_blocks_seen(i, block_q, block_k, nk, window):
    """(first, last) key block that query block i computes with, as Python
    ints: up to the diagonal, and with `window` from the band's far edge."""
    first = 0 if window is None else max(
        i * block_q - (window - 1), 0) // block_k
    return first, min((i * block_q + block_q - 1) // block_k, nk - 1)


def _band_extents(block_q, block_k, nq, nk, window):
    """(key blocks a query block of the band sees at most, query blocks
    that see a key block at most): the extents of the kernels' innermost
    grid axes, which run over the band alone. On the v5e a grid step costs
    ~0.3 us whether it computes or not: at [4, 8, 8192, 128] and 256 x 256
    blocks a forward over the whole (32 x 32) grid with the band's 93
    blocks computed took 10.0 ms, 3.4 at 1024 x 1024 (PERF.md, PR 32)."""
    n_k = max(last - first + 1 for first, last in (
        _key_blocks_seen(i, block_q, block_k, nk, window)
        for i in range(nq)))
    n_q = max(min((j * block_k + block_k + window - 2) // block_q, nq - 1)
              - (j * block_k) // block_q + 1 for j in range(nk))
    return max(n_k, 1), max(n_q, 1)


def _band(block_q, block_k, nq, nk, causal, window):
    """(q_of(j, i), k_of(i, j)): the query block the dK/dV kernel's operands
    name at step i of key block j, and the key block the dQ kernel's (and a
    window's forward kernel's) name at step j of query block i. Without a
    window the steps run over every block and one above the diagonal names
    the block of the nearest visited step, so it moves nothing. With one
    they run over the band alone (`_band_extents`), from its first block,
    and a step past its last names the last."""
    if not causal:
        return (lambda j, i: i), (lambda i, j: j)
    if window is None:
        def q_of(j, i):
            return jnp.minimum(jnp.maximum(i, (j * block_k) // block_q),
                               nq - 1)

        def k_of(i, j):
            return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
    else:
        def q_of(j, i):
            last = jnp.minimum(
                (j * block_k + block_k + window - 2) // block_q, nq - 1)
            return jnp.minimum(
                _first_query_block(j, block_q, block_k) + i, last)

        def k_of(i, j):
            last = jnp.minimum((i * block_q + block_q - 1) // block_k,
                               nk - 1)
            return jnp.minimum(
                _first_key_block(i, block_q, block_k, window) + j, last)

    return q_of, k_of


def blocks_visited(Sq, Sk, block_q, block_k, window=None):
    """How many (query block, key block) steps of a causal grid over [Sq,
    Sk] compute something: those the diagonal and, with `window`, the
    band's far edge leave. Static, from the shapes a kernel is built
    with; the forward, the dK/dV and the dQ kernel visit the same set."""
    nq, nk = -(-Sq // block_q), -(-Sk // block_k)
    return sum(max(last - first + 1, 0) for first, last in (
        _key_blocks_seen(i, block_q, block_k, nk, window)
        for i in range(nq)))


def blocks_masked(Sq, Sk, block_q, block_k, window=None, kv_len=None):
    """How many of those steps pay for the mask (`accumulate(True)`): the
    blocks the diagonal or, with `window`, the band's far edge crosses, and
    with `kv_len` those that hold padded keys. Static, as `blocks_visited`;
    the same set for the forward, the dK/dV and the dQ kernel wherever no
    key is padded (the backward kernels mask a padded block by `kv_len`, the
    forward neutralises it through `_fwd_padded`'s bias channel: no
    `kv_len`). 8 of the 36 blocks of 1024 x 1024 a row of 8192 visits."""
    nq, nk = -(-Sq // block_q), -(-Sk // block_k)
    return sum(all(_crossed(i, j, block_q, block_k, kv_len, window))
               for i in range(nq) for j in range(nk))


def _of_head(group):
    """Index of the key/value head that query head b (batch and heads
    folded, heads fastest) reads: `group` consecutive query heads share
    one."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _mask_spec(mask, heads, rows, cols, at):
    """The operand list's tail for a mask [B, ., .] read in [rows, cols]
    blocks by a grid whose first axis folds `heads` heads into each batch
    entry; `at(i, j)` names the block of grid step (i, j). Nothing without
    a mask."""
    if mask is None:
        return [], ()
    return [pl.BlockSpec((1, rows, cols),
                         lambda b, i, j: (b // heads, *at(i, j)))], (mask,)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, window=None,
               mask=None):
    """q [BH, Sq, D] (Sq % block_q == 0), k/v [BHkv, Sk, D] (Sk % block_k
    == 0; BH a multiple of BHkv: that many consecutive query heads read one
    key/value head, in place), mask [B, Sq, Sk] int8 or None -> (out [BH,
    Sq, D], lse [BH, Sq])."""
    BH, Sq, Dq = q.shape  # Dq may carry the +1 padding-mask channel
    Sk = k.shape[1]
    Dv = v.shape[-1]
    nq, nk = Sq // block_q, Sk // block_k
    kv = _of_head(BH // k.shape[0])
    # without a window the forward names key block j itself, visited or not
    # (a mask's blocks are a MiB each: a skipped step names a resident one)
    _, k_of = _band(block_q, block_k, nq, nk,
                    window is not None or mask is not None, window)
    mask_spec, mask_arg = _mask_spec(
        mask, BH // (1 if mask is None else mask.shape[0]), block_q, block_k,
        lambda i, j: (i, k_of(i, j)))
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k)
    if window is not None:
        n_keys, nk = nk, _band_extents(block_q, block_k, nq, nk, window)[0]
        static.update(causal=True, window=window, n_keys=n_keys)
    kernel = functools.partial(_kernel, nk=nk, **static)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dq), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dq),
                         lambda b, i, j: (kv(b), k_of(i, j), 0)),
            pl.BlockSpec((1, block_k, Dv),
                         lambda b, i, j: (kv(b), k_of(i, j), 0)),
        ] + mask_spec,
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **({} if mask is None else {"vmem_limit_bytes": _BWD_VMEM_LIMIT})),
        interpret=pallas_interpret(),
        name=(KERNELS if mask is None else SPARSE_KERNELS)[0],
    )(q, k, v, *mask_arg)


def _folded(x, pad=0):
    """[B, H, S, ...] -> [B * H, S + pad, ...], the new rows zero."""
    x = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x


def _padded_mask(mask, pad_q, pad_k):
    """mask [B, Sq, Sk] with zero bytes (nothing chosen) for the padding."""
    if mask is not None and (pad_q or pad_k):
        mask = jnp.pad(mask, ((0, 0), (0, pad_q), (0, pad_k)))
    return mask


def _fwd_padded(q, k, v, scale, causal, block_q, block_k, window=None,
                mask=None):
    """Pad S to block multiples; padded KEYS are neutralized by extending D
    with a bias channel (q gains a 1, real keys a 0, padded keys -BIG), so
    their scores vanish under exp without any in-kernel mask plumbing (under
    a mask they are zero bytes of it, and no channel).
    k, v [B, Hkv, Sk, .]: H / Hkv consecutive query heads read one
    key/value head."""
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
    if pad_k and mask is None:
        BIG = jnp.asarray(3e4 / max(scale, 1e-6), jnp.float32).astype(q.dtype)
        qw = jnp.concatenate([qw, jnp.ones_like(qw[..., :1])], axis=-1)
        maskch = jnp.where(
            (jnp.arange(kw.shape[2]) < Sk)[None, None, :, None],
            jnp.zeros((), q.dtype), -BIG)
        kw = jnp.concatenate(
            [kw, jnp.broadcast_to(maskch, kw.shape[:3] + (1,))], axis=-1)
    out, lse = _flash_fwd(_folded(qw), _folded(kw), _folded(vw), scale,
                          causal, block_q, block_k, window,
                          _padded_mask(mask, pad_q, pad_k))
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
                    block_q=256, block_k=256, window=None):
    """Exact attention [B, H, S, D] -> [B, H, S, D]; differentiable.

    Defaults (256, 256) measured fastest on a v5e chip at S=1024 D=128 —
    faster than XLA's fused dense attention there, with O(S * block) memory
    instead of the dense [S, S] score matrix (S >= 16k runs comfortably).
    The backward kernels take the same blocks; at S=4096 both directions
    are fastest at (1024, 1024) (ops/lm_ops.py carries the sweeps). Blocks
    auto-shrink for short sequences.

    k, v [B, Hkv, S, .] with H a multiple of Hkv: grouped-query heads, H /
    Hkv consecutive query heads read one key/value head in place (no
    repeated copy) and its dK, dV are summed over them inside the dK/dV
    kernel. `window` (causal only): query i sees the keys j with 0 <= i -
    j < window, itself among them; the blocks outside the band are
    skipped as those above the diagonal are."""
    scale, block_q, block_k, window = _resolve(q, k, scale, block_q,
                                               block_k, causal, window)
    return _flash(q, k, v, scale, bool(causal), block_q, block_k, window)


def _resolve(q, k, scale, block_q, block_k, causal=True, window=None,
             mask=None):
    if mask is not None and (not causal or window is not None):
        raise ValueError("a mask names causal pairs, and no band")
    block_q, block_k = normalize_blocks(block_q, block_k,
                                        q.shape[2], k.shape[2])
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} "
                         "key/value heads: not a whole group each")
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError("a window is a causal band of >= 1 positions")
        # a band as long as the row is the triangle: the kernels as they
        # are without one
        window = int(window) if int(window) < k.shape[2] else None
    return float(scale), int(block_q), int(block_k), window


def flash_attention_fwd(q, k, v, causal=False, scale=None,
                        block_q=256, block_k=256, window=None, mask=None):
    """The forward kernel alone: (out [B, H, S, D], lse [B, H, S]). For a
    caller that keeps `lse` itself and calls `flash_attention_bwd` later
    (an op whose backward is another op), so the kernel runs once. With
    `mask` [B, S, S] int8 (causal only): the softmax over each query's
    chosen keys alone."""
    scale, block_q, block_k, window = _resolve(q, k, scale, block_q,
                                               block_k, causal, window, mask)
    return _fwd_padded(q, k, v, scale, bool(causal), block_q, block_k,
                       window, mask)


def flash_attention_bwd(q, k, v, out, lse, do, causal=False, scale=None,
                        block_q=1024, block_k=1024, window=None, mask=None):
    """(dq, dk, dv) from the saved output and logsumexp of
    `flash_attention_fwd` with the same q, k, v, causal, scale, window and
    mask. The blocks are the backward kernels' own; the default is the
    fastest of a sweep on the v5e at [2, 16, 4096, 128] bf16 causal
    (ops/lm_ops.py)."""
    scale, block_q, block_k, window = _resolve(q, k, scale, block_q,
                                               block_k, causal, window, mask)
    return _flash_vjp_bwd(scale, bool(causal), block_q, block_k, window,
                          (q, k, v, out, lse), do, mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, window=None):
    out, _ = _fwd_padded(q, k, v, scale, causal, block_q, block_k, window)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, window=None):
    out, lse = _fwd_padded(q, k, v, scale, causal, block_q, block_k, window)
    return out, (q, k, v, out, lse)


# The backward kernels hold four float32 [block_q, block_k] tiles at once
# (s, p, dp, ds: 16 MiB at 1024 x 1024), past Mosaic's default scoped 16 MiB
# of the v5e's 128 MiB of VMEM.
_BWD_VMEM_LIMIT = 64 * 2 ** 20

# dot_general dimension numbers on 2-d blocks: a @ b.T, a @ b and a.T @ b
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _weights(s, lse, masked, q0, k0, q_axis, causal, kv_len, window=None,
             chosen=(), chosen_transposed=False):
    """p = exp(s - lse) on one float32 score block; where `masked`, zero
    for the pairs above the diagonal, for those `window` or more positions
    back, and for padded keys, or with `chosen` (a mask's block, laid out
    as the scores are, or with `chosen_transposed` the other way round)
    for the pairs it does not name. Queries run along `q_axis` of the
    block and keys along the other."""
    p = jnp.exp(s - lse)
    if not masked:
        return p
    if chosen:
        return jnp.where(_chosen(chosen[0], chosen_transposed), p, 0.0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    keep = None if kv_len is None else k_pos < kv_len
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        keep = q_pos >= k_pos if keep is None else keep & (q_pos >= k_pos)
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
    return jnp.where(keep, p, 0.0)


def _crossed(qi, ki, block_q, block_k, kv_len=None, window=None):
    """(visited, edge) of the causal score block (qi, ki), for grid indices
    in a kernel and for Python ints alike: whether any pair of it carries
    weight (the block does not lie wholly above the diagonal nor, with
    `window`, wholly behind the band), and whether some pair of it carries
    none (the block crosses the diagonal or the band's far edge, or holds
    keys at or past `kv_len`, which are padding)."""
    visited = qi * block_q + block_q - 1 >= ki * block_k
    edge = qi * block_q < ki * block_k + block_k - 1
    if window is not None:
        # the nearest pair is inside the band; the farthest is not
        visited = visited & (
            qi * block_q - (ki * block_k + block_k - 1) < window)
        edge = edge | (qi * block_q + block_q - 1 - ki * block_k >= window)
    if kv_len is not None:
        edge = edge | ((ki + 1) * block_k > kv_len)
    return visited, edge


def _for_block(accumulate, qi, ki, block_q, block_k, causal, kv_len,
               window=None, inside=True):
    """Run `accumulate(masked)` for the score block (qi, ki): not at all if
    the block lies wholly above the causal frontier or, with `window`,
    wholly behind the band; and with the mask only if some pair of it
    carries no weight (`_crossed`). `inside`: whether the step's block
    exists at all (a band's grid axis runs a fixed number of steps from the
    band's first block, past the array's end for the last rows)."""
    if not causal and kv_len is None:
        accumulate(False)
        return
    if causal:
        visited, edge = _crossed(qi, ki, block_q, block_k, kv_len, window)
        visited = inside & visited
    else:
        visited, edge = inside, (ki + 1) * block_k > kv_len
    pl.when(visited & edge)(lambda: accumulate(True))
    pl.when(visited & jnp.logical_not(edge))(lambda: accumulate(False))


def _for_chosen(accumulate, qi, ki, block_q, block_k):
    """Under a selection's mask: `accumulate(True)` for every score block
    (qi, ki) that does not lie wholly above the diagonal."""
    pl.when(_crossed(qi, ki, block_q, block_k)[0])(lambda: accumulate(True))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, ld_ref, *rest, scale, causal,
                block_q, block_k, nq, kv_len, window=None, group=1,
                n_queries=None):
    """One key block against the query blocks at or below it (within the
    band, with `window`), of each of the `group` query heads that read
    it: the innermost axis runs over (head of the group, query block).
    Scores are held transposed, [bk, bq], so that every product is a plain
    one (no block is transposed on its way into the MXU) and the per-query
    `lse` and `delta` broadcast along sublanes from their lane-major
    rows; a mask's block arrives transposed too, (key, query)."""
    *mask_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)
    step = pl.program_id(2)
    qi = step if group == 1 else step % nq
    if window is not None:
        qi = _first_query_block(ki, block_q, block_k) + qi

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = _dot(k, q, _NT) * scale                       # [bk, bq]
        pt = _weights(st, ld_ref[0, 0:1, :], masked, qi * block_q,
                      ki * block_k, 1, causal, kv_len, window, mask_ref)
        dv_scr[:] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v, do, _NT)
        dst = pt * (dpt - ld_ref[0, 1:2, :])
        dk_scr[:] += _dot(dst.astype(q.dtype), q, _NN)

    if mask_ref:
        _for_chosen(_accumulate, qi, ki, block_q, block_k)
    else:
        _for_block(_accumulate, qi, ki, block_q, block_k, causal, kv_len,
                   window, True if window is None else qi < n_queries)

    @pl.when(step == group * nq - 1)
    def _finish():
        # ds = p (dp - delta) scale: the scale once, on the float32 sum
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, ld_ref, *rest, scale, causal,
               block_q, block_k, nk, kv_len, window=None, n_keys=None):
    """One query block against the key blocks at or below the diagonal
    (within the band, with `window`); scores [bq, bk], `lse` and `delta`
    as columns."""
    *mask_ref, dq_ref, dq_scr = rest
    qi = pl.program_id(1)
    step = ki = pl.program_id(2)
    if window is not None:
        ki = _first_key_block(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _dot(q, k, _NT) * scale                        # [bq, bk]
        p = _weights(s, ld_ref[0, :, 0:1], masked, qi * block_q,
                     ki * block_k, 0, causal, kv_len, window, mask_ref)
        dp = _dot(do, v, _NT)
        ds = p * (dp - ld_ref[0, :, 1:2])
        dq_scr[:] += _dot(ds.astype(k.dtype), k, _NN)

    if mask_ref:
        _for_chosen(_accumulate, qi, ki, block_q, block_k)
    else:
        _for_block(_accumulate, qi, ki, block_q, block_k, causal, kv_len,
                   window, True if window is None else ki < n_keys)

    @pl.when(step == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, do, lse, delta, scale, causal, block_q, block_k,
               kv_len, window=None, mask=None):
    """q [BH, Sq, D], k [BHkv, Sk, D], v [BHkv, Sk, Dv], do [BH, Sq, Dv]
    (Dv may differ from D, as in the forward; BH / BHkv consecutive query
    heads read one key/value head), lse, delta [BH, Sq] float32 (Sq %
    block_q == 0, Sk % block_k == 0; keys at and past `kv_len`, if given,
    are padding), mask [B, Sq, Sk] int8 or None -> dq, dk, dv. Two
    kernels: dK/dV with the query blocks (of every query head of the
    group) innermost, dQ with the key blocks innermost, each recomputing
    its score block in VMEM from the saved logsumexp. Without a window a
    step above the causal frontier is skipped, and its index maps name the
    block of the nearest visited step, so it moves nothing either; with
    one the innermost axes run over the band's blocks alone, and a step
    past its last block names that block and computes nothing (`_band`,
    `_band_extents`)."""
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    nq, nk = Sq // block_q, Sk // block_k
    group = BH // k.shape[0]
    kv = _of_head(group)
    q_of, k_of = _band(block_q, block_k, nq, nk, causal, window)
    if group == 1:
        def q_at(b, j, i):
            return b, q_of(j, i)
    else:
        # step i of key/value head b: the (i % steps_q)-th query block of
        # the group's head i // steps_q
        def q_at(b, j, i):
            return b * group + i // steps_q, q_of(j, i % steps_q)
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=kv_len)
    # the innermost axes: every block, or with a window the band's alone
    steps_k, steps_q, band_k, band_q = nk, nq, {}, {}
    if window is not None:
        static["window"] = window
        steps_k, steps_q = _band_extents(block_q, block_k, nq, nk, window)
        band_k, band_q = {"n_keys": nk}, {"n_queries": nq}
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_BWD_VMEM_LIMIT)
    ld = jnp.stack([lse, delta], axis=1)                   # [BH, 2, Sq]
    names = KERNELS if mask is None else SPARSE_KERNELS
    batch = 1 if mask is None else mask.shape[0]
    # the dK/dV kernel holds its scores transposed and reads the mask so
    mask_t_spec, mask_t_arg = _mask_spec(
        None if mask is None else jnp.swapaxes(mask, 1, 2),
        k.shape[0] // batch, block_k, block_q,
        lambda j, i: (j, q_at(0, j, i)[1]))
    mask_spec, mask_arg = _mask_spec(mask, BH // batch, block_q, block_k,
                                     lambda i, j: (i, k_of(i, j)))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=steps_q, **static, **band_q,
                          **({} if group == 1 else {"group": group})),
        grid=(k.shape[0], nk, group * steps_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda b, j, i: (*q_at(b, j, i), 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, Dv),
                         lambda b, j, i: (*q_at(b, j, i), 0)),
            pl.BlockSpec((1, 2, block_q),
                         lambda b, j, i: (q_at(b, j, i)[0], 0,
                                          q_at(b, j, i)[1])),
        ] + mask_t_spec,
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
        name=names[1],
    )(q, k, v, do, ld, *mask_t_arg)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=steps_k, **static, **band_k),
        grid=(BH, nq, steps_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j: (kv(b), k_of(i, j), 0)),
            pl.BlockSpec((1, block_k, Dv),
                         lambda b, i, j: (kv(b), k_of(i, j), 0)),
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 2), lambda b, i, j: (b, i, 0)),
        ] + mask_spec,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=params,
        interpret=pallas_interpret(),
        name=names[2],
    )(q, k, v, do, jnp.swapaxes(ld, 1, 2), *mask_arg)
    return dq, dk, dv


# The share of `_BWD_VMEM_LIMIT` that the one-kernel masked backward's three
# float32 accumulators may take, whole rows of a head each: dQ [Sq, D], dK
# [Sk, D], dV [Sk, Dv]. 12 MiB of 16 at one row of 8192 and heads of 128;
# the rest holds the operands' double buffers (the mask's int8 block 2 x 1
# MiB among them), the three outputs' and a visit's four float32 tiles.
_FUSED_ACCUMULATORS_SHARE = 0.25


def fused_backward_fits(Sq, Sk, D, Dv):
    """Whether a masked backward over rows of Sq queries and Sk keys, as
    the caller states them (the padding to whole blocks, less than a block
    a row, is the share's slack), is ONE kernel (`_fused_bwd`): its
    accumulators fit their share of the VMEM limit. From shapes alone; the
    dispatch (`_flash_vjp_bwd`) and the program's counter
    (`lm_ops.lowered_counts`) both ask here."""
    return (Sq * D + Sk * (D + Dv)) * 4 \
        <= _FUSED_ACCUMULATORS_SHARE * _BWD_VMEM_LIMIT


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, ld_ref, mask_ref, dq_ref,
                      dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, scale,
                      block_q, block_k, nq, nk, group):
    """Every score block of one key/value head's `group` query heads, each
    visited ONCE for all three gradients: grid (key/value head, head of the
    group, key block, query block). The visit is the dK/dV kernel's
    (scores transposed, [bk, bq]; `lse` and `delta` lane-major rows) and
    one product more, dQ[i] += dst^T k, which contracts the first axis of
    both operands; the mask's block arrives as it lies, (query, key), and
    is transposed here, widened (no transposed copy of the mask in HBM).
    dQ [Sq, D] of the query head and dK, dV [Sk, .] of the key/value head
    stay in VMEM, float32: a query block's sum runs over the key blocks in
    ascending order and a key block's over (head, query block), as in the
    two kernels."""
    g, ki, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first = (ki == 0) & (qi == 0)
    last = (ki == nk - 1) & (qi == nq - 1)

    @pl.when(first)
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(first & (g == 0))
    def _init_dkv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        rows_q = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        rows_k = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        st = _dot(k, q, _NT) * scale                       # [bk, bq]
        pt = _weights(st, ld_ref[0, 0:1, :], masked, qi * block_q,
                      ki * block_k, 1, True, None, chosen=[mask_ref],
                      chosen_transposed=True)
        dv_scr[rows_k, :] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v, do, _NT)
        dst = (pt * (dpt - ld_ref[0, 1:2, :])).astype(q.dtype)
        dk_scr[rows_k, :] += _dot(dst, q, _NN)
        dq_scr[rows_q, :] += _dot(dst, k, _TN)

    _for_chosen(_accumulate, qi, ki, block_q, block_k)

    @pl.when(last)
    def _finish_dq():
        # ds = p (dp - delta) scale: the scale once, on the float32 sums
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)

    @pl.when(last & (g == group - 1))
    def _finish_dkv():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fused_bwd(q, k, v, do, lse, delta, scale, block_q, block_k, mask):
    """`_flash_bwd`'s operands under a mask [B, Sq, Sk] -> dq, dk, dv
    through ONE kernel (`SPARSE_BWD_KERNEL`), for the shapes
    `fused_backward_fits`. Steps above the diagonal are skipped by block
    index and name the block of the nearest visited step, as in the two
    kernels; each output block is a head's whole row, so it leaves VMEM
    once, as the head's (the group's) last step ends."""
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    nq, nk = Sq // block_q, Sk // block_k
    group = BH // k.shape[0]
    heads = k.shape[0] // mask.shape[0]
    q_of, _ = _band(block_q, block_k, nq, nk, True, None)

    def q_at(b, g, j, i):
        return b * group + g, q_of(j, i)

    return pl.pallas_call(
        functools.partial(_fused_bwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, nq=nq, nk=nk, group=group),
        grid=(k.shape[0], group, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda *at: (*q_at(*at), 0)),
            pl.BlockSpec((1, block_k, D), lambda b, g, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, g, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda *at: (*q_at(*at), 0)),
            pl.BlockSpec((1, 2, block_q),
                         lambda *at: (q_at(*at)[0], 0, q_at(*at)[1])),
            pl.BlockSpec((1, block_q, block_k),
                         lambda b, g, j, i: (b // heads, q_of(j, i), j)),
        ],
        out_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, g, j, i: (b * group + g, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, g, j, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, Dv), lambda b, g, j, i: (b, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((Sq, D), jnp.float32),
                        pltpu.VMEM((Sk, D), jnp.float32),
                        pltpu.VMEM((Sk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=pallas_interpret(),
        name=SPARSE_BWD_KERNEL,
    )(q, k, v, do, jnp.stack([lse, delta], axis=1), mask)


def _flash_vjp_bwd(scale, causal, block_q, block_k, window, res, do,
                   mask=None):
    """Pad as `_fwd_padded` does and run the backward kernels: dK/dV and
    dQ, or under a mask whose accumulators fit (`fused_backward_fits`) the
    one kernel for all three. A
    padded key gets no weight (the kernels mask keys past Sk, or read the
    padded mask's zero bytes); a padded query row carries dO = 0 and delta
    = 0, so with any finite lse it adds nothing to dK or dV, and its dQ
    row is cut off."""
    q, k, v, out, lse = res
    Sq, Sk = q.shape[2], k.shape[2]
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    operands = (_folded(q, pad_q), _folded(k, pad_k), _folded(v, pad_k),
                _folded(do.astype(q.dtype), pad_q), _folded(lse, pad_q),
                _folded(delta, pad_q))
    mask = _padded_mask(mask, pad_q, pad_k)
    if mask is not None and fused_backward_fits(Sq, Sk, q.shape[3],
                                                v.shape[3]):
        dq, dk, dv = _fused_bwd(*operands, scale, block_q, block_k, mask)
    else:
        dq, dk, dv = _flash_bwd(
            *operands, scale, causal, block_q, block_k,
            Sk if pad_k and mask is None else None, window, mask)
    return (dq[:, :Sq].reshape(q.shape), dk[:, :Sk].reshape(k.shape),
            dv[:, :Sk].reshape(v.shape))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
