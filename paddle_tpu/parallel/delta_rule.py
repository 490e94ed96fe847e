"""The gated delta rule (Gated DeltaNet: Yang, Kautz & Hatamizadeh,
arXiv:2412.06464) in its CHUNKED form, forward and hand-written backward.

Per value head, a state S [dk, dv] carried along a row's tokens, zero at a
row's first token:

    S <- exp(g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;  o_t = S^T q_t

with g_t <= 0 (a log-decay) and beta_t in (0, 1) one scalar a value head,
q and k L2-normalised over a head's dk numbers (q times dk^-1/2 as well);
key head j serves the value heads j * (Hv / Hk) ... (j + 1) * (Hv / Hk) - 1.

A row is worked in chunks of tokens (the WY / UT-transform form; `CHUNK`
where the op's attr gives none).
With G the running sum of g inside a chunk and S0 the state the chunk
starts from, everything below but the carried state is independent of
the other chunks (`_parts`, one batch of small products over all chunks):

    D_ij  = exp(G_i - G_j), i >= j           (never exp(G_i) exp(-G_j): a
                                              chunk's sum reaches -100)
    A     = strict_lower(beta_i D_ij k_i.k_j);  T = (I + A)^-1
    W     = T (beta exp(G) k);  U0 = T (beta v)        (U = U0 - W S0)
    P     = lower(D_ij q_i.k_j)
    Q'    = exp(G) q - P W;  O0 = P U0                 (o = Q' S0 + O0)
    Kh    = exp(G_C - G) k;  N = Kh^T W;  B = Kh^T U0
    S1    = exp(G_C) S0 + B - N S0                     (the next chunk's S0)

so what is sequential is ONE [dk, dk] x [dk, dv] product a chunk and head
(`_states`, a `lax.scan` over the chunks), and the outputs are one more
batched product over all chunks behind it. T is formed as the product
(I - A)(I + A^2)(I + A^4)... (A is nilpotent: six squarings at 128), on
float32 operands (`INVERSE_PRECISION`); the other products take operands in the
inputs' dtype and accumulate in float32; g, beta, every decay and the
carried state are float32.

ON A TPU PLACE the in-chunk quantities and their transpose are two Pallas
kernels (`parallel/delta_parts.py`; `kernels_fwd`, `kernels_bwd` below) for
the shapes those take; the rest of this file is the same on both paths.

THE BACKWARD is written by hand where the sequence is: from the
chunk-start states the forward saved ([groups, chunks, rows x Hv / groups,
dk, dv] float32: chunks leading, so neither scan transposes anything)
it forms the in-chunk quantities again, runs the REVERSE recurrence of the
state's cotangent

    lam_n = Q'_n^T dO_n + exp(G_C) lam_{n+1} - N_n^T lam_{n+1}

(one product a chunk and head again), and hands d Q' = dO S_n^T, d O0 =
dO, d N = -lam_{n+1} S_n^T, d B = lam_{n+1}, d exp(G_C) = <lam_{n+1}, S_n>
to the transpose of `_parts` (no scan inside it: products, element-wise
work and the triangular inverse, whose own transpose is written out: d rhs
= T^T dX, d A = -d rhs X^T). `jax.vjp` is taken of no scan.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["delta_rule_fwd", "delta_rule_bwd", "gates", "states_shape",
           "CHUNK"]

F32 = jnp.float32
# tokens a chunk, where the op's attr gives none. Swept on the v5e at the
# `qwen3_next_80b_a3b` cell's shape ([8192, 16 key / 32 value heads of 128],
# tools/delta_rule_sweep.py; PERF.md, PR 43; ms forward + backward of one
# layer): 10.8 + 26.1 at chunks of 64, 4 head groups and the inverse at
# HIGHEST; 8.9 + 16.8 at 128 and HIGH (no [64, 64] array padded to a lane
# tile, half the scan's steps; twice the in-chunk operations); 12.4 + 21.1
# at 256
CHUNK = 128
# the parts of the op's lowering, each under a `jax.named_scope` inside the
# op's own (`delta/delta_rule/gated_delta_rule/states`)
PARTS, STATES, OUTPUTS = "parts", "states", "outputs"


def gates(ba, a_log, dt_bias):
    """[b | a] [..., 2 Hv] and A_log, dt_bias [Hv] -> (g = -exp(A_log)
    softplus(a + dt_bias), beta = sigmoid(b)), both [..., Hv], float32."""
    hv = a_log.shape[0]
    b, a = ba[..., :hv].astype(F32), ba[..., hv:].astype(F32)
    g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(a + dt_bias.astype(F32))
    return g, jax.nn.sigmoid(b)


def l2_normalized(x, eps, scale=1.0):
    """x / sqrt(sum(x^2, last axis) + eps) * scale, float32 inside, in x's
    dtype."""
    xf = x.astype(F32)
    inv = lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (inv * scale)).astype(x.dtype)


def _dot(a, b, low):
    return jnp.matmul(a.astype(low), b.astype(low),
                      preferred_element_type=F32)


def _t(a):
    return jnp.swapaxes(a, -1, -2)


# the triangular inverse's products: float32 operands in three bf16 passes
# (its result is rounded to the inputs' dtype as a product's operand: at
# HIGHEST, six passes, the op's output moves by 0.13% rms and a layer costs
# 2.7 ms more; in one pass by 0.23%)
INVERSE_PRECISION = lax.Precision.HIGH


def _exact(a, b):
    return jnp.matmul(a, b, precision=INVERSE_PRECISION)


def _inverse(a):
    """(I + A)^-1 of strictly lower triangular A [..., C, C], float32: the
    product of (I + M^(2^i)), M = -A, until the power passes C."""
    n = a.shape[-1]
    m = -a
    t = jnp.eye(n, dtype=a.dtype) + m
    p = 1
    while 2 * p < n:
        m = _exact(m, m)
        t = t + _exact(t, m)
        p *= 2
    return t


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _solve(a, rhs, low):
    """X = (I + A)^-1 rhs, in `low` (every reader takes it as a product's
    operand); A float32, rhs in `low`."""
    return _dot(_inverse(a), rhs, low).astype(low)


def _solve_fwd(a, rhs, low):
    t = _inverse(a).astype(low)
    x = _dot(t, rhs, low).astype(low)
    return x, (t, x)


def _solve_bwd(low, saved, d_x):
    t, x = saved
    d_rhs = _dot(_t(t), d_x, low).astype(low)
    return -_dot(d_rhs, _t(x), low), d_rhs


_solve.defvjp(_solve_fwd, _solve_bwd)


def _parts(q, k, v, g, beta):
    """What a chunk needs beside the state it starts from, for all chunks
    at once: q, k [N, B, C, dk] and v [N, B, C, dv] in the inputs' dtype, g
    and beta [N, B, C] float32 (N chunks of C tokens, B = rows x heads) ->
    Q' [N, B, C, dk] and N [N, B, dk, dk] (in the inputs' dtype: products'
    operands), O0 [N, B, C, dv], B [N, B, dk, dv] and exp(G_C) [N, B],
    float32."""
    low, dk = v.dtype, q.shape[-1]
    i = jnp.arange(q.shape[-2])
    lower, strict = i[:, None] >= i[None, :], i[:, None] > i[None, :]
    gf, bf = g.astype(F32), beta.astype(F32)
    run = jnp.cumsum(gf, axis=-1)
    decay = jnp.exp(jnp.where(
        lower, run[..., :, None] - run[..., None, :], -jnp.inf))
    gamma = jnp.exp(run)
    to_end = jnp.exp(run[..., -1:] - run)
    qf, kf, vf = (x.astype(F32) for x in (q, k, v))
    a = jnp.where(strict, bf[..., :, None] * decay * _dot(k, _t(k), low),
                  0.0)
    wu = _solve(a, jnp.concatenate(
        [(bf * gamma)[..., None] * kf, bf[..., None] * vf],
        axis=-1).astype(low), low)
    w, u0 = wu[..., :dk], wu[..., dk:]
    p = jnp.where(lower, decay * _dot(q, _t(k), low), 0.0).astype(low)
    q_p = (gamma[..., None] * qf - _dot(p, w, low)).astype(low)
    k_end = _t(to_end[..., None] * kf).astype(low)
    return (q_p, _dot(p, u0, low), _dot(k_end, w, low).astype(low),
            _dot(k_end, u0, low), gamma[..., -1])


def _states(n_mat, b_mat, g_end, s0):
    """The carried state over the chunks (the leading axis), in s0's dtype:
    (the state each chunk starts from [N, B, dk, dv], the state behind the
    last chunk [B, dk, dv])."""
    low = n_mat.dtype

    def step(s, x):
        n, b, c = x
        s1 = c[:, None, None] * s.astype(F32) + b - _dot(n, s, low)
        return s1.astype(s.dtype), s

    last, starts = lax.scan(step, s0, (n_mat, b_mat, g_end))
    return starts, last


def _states_transposed(n_mat, g_end, r_mat, d_last):
    """The reverse recurrence of the state's cotangent: with R_n = Q'_n^T
    dO_n, in d_last's dtype: (the cotangent of the state chunk n LEAVES [N,
    B, dk, dv], that of the state the first chunk starts from)."""
    low = n_mat.dtype

    def step(lam, x):
        n, c, r = x
        before = r + c[:, None, None] * lam.astype(F32) - _dot(_t(n), lam,
                                                                low)
        return before.astype(lam.dtype), lam

    first, left = lax.scan(step, d_last, (n_mat, g_end, r_mat),
                           reverse=True)
    return left, first


def _split(qkv, hk, hv, dk, dv):
    q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
    return (q.reshape(q.shape[:-1] + (hk, dk)),
            k.reshape(k.shape[:-1] + (hk, dk)),
            v.reshape(v.shape[:-1] + (hv, dv)))


def _chunked(x, chunk):
    """[rows, S, H, ...] -> [N, rows x H, C, ...]: chunks leading (what the
    scans run over, so nothing is transposed for them), a row padded to
    whole chunks with zeros."""
    rows, seq_len, heads = x.shape[:3]
    pad = -seq_len % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((rows, -1, chunk, heads) + x.shape[3:])
    x = jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)    # [N, rows, H, C, ...]
    return x.reshape((x.shape[0], rows * heads) + x.shape[3:])


def _prepared(qkv, ba, a_log, dt_bias, *, seq_len, hk, hv, dk, dv, chunk,
              eps):
    """The op's inputs as `_parts` takes them: [q | k | v] [T, 2 Hk dk + Hv
    dv] and [b | a] [T, 2 Hv] -> q, k, v [N, rows x Hv, C, d], g, beta [N,
    rows x Hv, C]; q and k normalised, a key head repeated for the value
    heads it serves, a row padded to whole chunks with tokens that neither
    decay nor write the state (g = 0, beta = 0)."""
    rows = qkv.shape[0] // seq_len
    q, k, v = _split(qkv.reshape(rows, seq_len, -1), hk, hv, dk, dv)
    q = l2_normalized(q, eps, dk ** -0.5)
    k = l2_normalized(k, eps)
    if hv != hk:
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    g, beta = gates(ba.reshape(rows, seq_len, -1), a_log, dt_bias)
    return tuple(_chunked(x, chunk) for x in (q, k, v, g, beta))


def _tokens_first(o, rows, seq_len):
    """[N, rows x Hv, C, dv] -> [T, Hv dv]."""
    n, _, chunk, dv = o.shape
    o = o.reshape(n, rows, -1, chunk, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)    # [rows, N, C, Hv, dv]
    return o.reshape(rows, n * chunk, -1)[:, :seq_len].reshape(
        rows * seq_len, -1)


def _one_group_fwd(qkv, ba, a_log, dt_bias, shape):
    """(Out [T, Hv dv], the chunk-start states [N, rows x Hv, dk, dv], the
    last state [rows, Hv, dk, dv]) of the heads given."""
    rows = qkv.shape[0] // shape["seq_len"]
    with jax.named_scope(PARTS):
        q_p, o0, n_mat, b_mat, g_end = _parts(
            *_prepared(qkv, ba, a_log, dt_bias, **shape))
    with jax.named_scope(STATES):
        starts, last = _states(n_mat, b_mat, g_end,
                               jnp.zeros(b_mat.shape[1:], F32))
    with jax.named_scope(OUTPUTS):
        o = _dot(q_p, starts, qkv.dtype) + o0
        out = _tokens_first(o, rows, shape["seq_len"]).astype(qkv.dtype)
    return (out, starts.astype(F32),
            last.astype(F32).reshape((rows, shape["hv"]) + last.shape[1:]))


def _one_group_bwd(qkv, ba, a_log, dt_bias, starts, d_out, shape):
    low, seq_len = qkv.dtype, shape["seq_len"]
    rows, hv, dv = qkv.shape[0] // seq_len, shape["hv"], shape["dv"]
    with jax.named_scope(PARTS):
        (q_p, _, n_mat, _, g_end), parts_vjp = jax.vjp(
            lambda *a: _parts(*_prepared(*a, **shape)),
            qkv, ba, a_log, dt_bias)
        d_o = _chunked(d_out.reshape(rows, seq_len, hv, dv).astype(low),
                       shape["chunk"])
    with jax.named_scope(STATES):
        left, _ = _states_transposed(
            n_mat, g_end, _dot(_t(q_p), d_o, low),
            jnp.zeros(starts.shape[1:], F32))
    with jax.named_scope(OUTPUTS):
        left = left.astype(F32)
        d_parts = (_dot(d_o, _t(starts), low).astype(low), d_o.astype(F32),
                   (-_dot(left, _t(starts), low)).astype(low), left,
                   jnp.sum(left * starts.astype(F32), axis=(-1, -2)))
    with jax.named_scope(PARTS):
        d_qkv, d_ba, d_a_log, d_dt_bias = parts_vjp(d_parts)
    return d_qkv, d_ba, d_a_log.astype(F32), d_dt_bias.astype(F32)


# ------------------------------------------------------------- head groups
# The in-chunk quantities of all chunks of ALL heads at once are gigabytes
# at [8192 tokens, 32 heads] (a dozen [heads, chunks, C, C] float32 arrays
# and their cotangents in the backward): the heads are worked in groups one
# after the other (`lax.map`; the groups are independent, a key head and
# the value heads it serves lie in one group), which divides that by the
# number of groups and multiplies the scans' steps by it. FEWER heads at a
# time is also FASTER on the v5e, all the way down (the sweep above, ms
# forward + backward at chunks of 128): 8.9 + 16.8 in 4 groups, 7.4 + 12.1
# in 8, 6.3 + 10.1 in 16, one key head and its value heads at a time; in 2
# groups at chunks of 64 14.3 + 29.6 against 10.8 + 26.1 in 4.
HEAD_GROUPS = 16


def _groups(hk):
    """The largest number of groups not above HEAD_GROUPS that divides the
    key heads."""
    return next(g for g in range(min(HEAD_GROUPS, hk), 0, -1) if hk % g == 0)


def _by_group(x, groups):
    """[T, heads x d] -> [groups, T, heads / groups x d]."""
    return jnp.moveaxis(x.reshape(x.shape[0], groups, -1), 1, 0)


def _from_groups(x):
    """[groups, T, w] -> [T, groups x w]."""
    return jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1)


def _grouped_inputs(qkv, ba, a_log, dt_bias, groups, hk, hv, dk, **_):
    q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
    return (jnp.concatenate([_by_group(x, groups) for x in (q, k, v)], -1),
            jnp.concatenate([_by_group(x, groups)
                             for x in (ba[:, :hv], ba[:, hv:])], -1),
            a_log.reshape(groups, -1), dt_bias.reshape(groups, -1))


def _group_shape(shape, groups):
    return dict(shape, hk=shape["hk"] // groups, hv=shape["hv"] // groups)


def delta_rule_fwd(qkv, ba, a_log, dt_bias, **shape):
    """(Out [T, Hv dv] in qkv's dtype; the chunk-start states, float32,
    [groups, N, rows x Hv / groups, dk, dv]: chunks leading, as the scans
    run over them and as the backward reads them; the state behind each
    row's last token [rows, Hv, dk, dv], float32). `shape`: seq_len, hk,
    hv, dk, dv, chunk, eps."""
    groups = _groups(shape["hk"])
    out, starts, last = lax.map(
        lambda a: _one_group_fwd(*a, _group_shape(shape, groups)),
        _grouped_inputs(qkv, ba, a_log, dt_bias, groups, **shape))
    # [groups, rows, Hv / groups, dk, dv] -> [rows, Hv, dk, dv]
    last = jnp.moveaxis(last, 0, 1)
    return (_from_groups(out), starts,
            last.reshape(last.shape[:1] + (-1,) + last.shape[3:]))


def delta_rule_bwd(qkv, ba, a_log, dt_bias, starts, d_out, **shape):
    """(d qkv, d ba in their dtypes, d A_log, d dt_bias float32) from the
    op's inputs, the chunk-start states the forward saved and d Out."""
    groups = _groups(shape["hk"])
    part = _group_shape(shape, groups)
    d_qkv, d_ba, d_a_log, d_dt_bias = lax.map(
        lambda a: _one_group_bwd(*a, part),
        _grouped_inputs(qkv, ba, a_log, dt_bias, groups, **shape)
        + (starts, _by_group(d_out, groups)))
    cuts = [part["hk"] * shape["dk"], 2 * part["hk"] * shape["dk"]]
    return (jnp.concatenate([_from_groups(x) for x in jnp.split(
                d_qkv, cuts, axis=-1)], -1),
            jnp.concatenate([_from_groups(x) for x in jnp.split(
                d_ba, 2, axis=-1)], -1),
            d_a_log.reshape(-1), d_dt_bias.reshape(-1))


def states_shape(rows, seq_len, hk, hv, dk, dv, chunk, kernels=False):
    """The shape of `delta_rule_fwd`'s chunk-start states (`kernels`: of
    `kernels_fwd`'s, which works all heads at once)."""
    n_chunks = -(-seq_len // chunk)
    if kernels:
        return (n_chunks, rows * hv, dk, dv)
    groups = _groups(hk)
    return (groups, n_chunks, rows * hv // groups, dk, dv)


# -------------------------------------------------------- the kernel path
# On a TPU place, for the shapes `takes` holds for, `_parts` and its
# transpose are the two Pallas kernels of `parallel/delta_parts.py`, which
# read q, k, v from [q | k | v], d Out and the gates [T, Hv] where they lie:
# no split by head group, no repeat of a key head, no chunk-major copy of
# an input. The backward's kernels also form what stands between `_parts`
# and the scans there (Q'^T d O for the reverse scan; d Q', d N, d exp(G_C)
# from the saved states and the scan's result). `gates`, `l2_normalized`
# (called INSIDE the kernels, a head's [C, dk] block at a time, its vjp by
# `jax.vjp` there too), both scans, the output product and
# `INVERSE_PRECISION` stay the names above, looked up on this module as the
# step is traced: a study plants its faults on them from outside
# (`chipbench/lower_precision_lm_delta_share.py`), and
# tests/test_qwen3_next.py holds that every plant still bites here. The
# plain form above is what the kernels are tested against and what every
# other place and shape runs.
#
# All heads are worked at once: Q', O0, N, B between the kernel and the
# output product are 0.4 GB a layer at the `qwen3_next_80b_a3b` cell's
# [8192 tokens, 32 value heads], and the step still fits (the window
# closes at 15.11 GB of the compiler's 15.75, 15.09 on the plain path;
# PERF.md, PR 44). Head groups bought nothing here (`lax.map` over 4
# groups: 7.59 + 16.6 ms a layer against 7.82 + 16.6, first form of the
# kernels): XLA no longer holds a group's [C, C] arrays.

# whether the backward's forward kernel hands its triangular inverse to the
# backward kernel ([N, rows x Hv, C, C] in the inputs' dtype through HBM, 67
# MB a layer at [8192, 32 heads] in bf16) or that kernel forms it again (36
# [C]^3 bf16 passes a head and chunk: 3.98 ms against 2.48, v5e)
KEEPS_INVERSE = True


def takes(rows, seq_len, hk, hv, dk, dv, chunk, dtype):
    """Whether the kernel path takes these shapes (`delta_parts.takes`)."""
    from . import delta_parts

    return delta_parts.takes(rows, seq_len, hk, hv, dk, dv, chunk, dtype)


def _dims(qkv, seq_len, **shape):
    return dict(shape, seq_len=seq_len, rows=qkv.shape[0] // seq_len)


def kernels_fwd(qkv, ba, a_log, dt_bias, **shape):
    """`delta_rule_fwd` through the kernels; the chunk-start states [N,
    rows x Hv, dk, dv]. `takes` must hold."""
    from .delta_parts import delta_parts_fwd

    low, dims = qkv.dtype, _dims(qkv, **shape)
    rows, seq_len = dims["rows"], dims["seq_len"]
    with jax.named_scope(PARTS):
        g, beta = gates(ba, a_log, dt_bias)
        parts = delta_parts_fwd(qkv, g, beta, **dims)
    with jax.named_scope(STATES):
        starts, last = _states(
            parts["n_mat"], parts["b_mat"], parts["g_end"],
            jnp.zeros(parts["b_mat"].shape[1:], F32))
    with jax.named_scope(OUTPUTS):
        o = _dot(parts["q_p"], starts, low) + parts["o0"]
        out = _tokens_first(o, rows, seq_len).astype(low)
    return (out, starts.astype(F32),
            last.astype(F32).reshape((rows, -1) + last.shape[1:]))


def kernels_bwd(qkv, ba, a_log, dt_bias, starts, d_out, **shape):
    """`delta_rule_bwd` through the kernels, from `kernels_fwd`'s states."""
    from .delta_parts import delta_parts_bwd, delta_parts_fwd

    dims = _dims(qkv, **shape)
    with jax.named_scope(PARTS):
        (g, beta), gates_vjp = jax.vjp(gates, ba, a_log, dt_bias)
        parts = delta_parts_fwd(
            qkv, g, beta, d_out, **dims,
            outputs=("r_mat", "n_mat", "g_end") + ("t",) * KEEPS_INVERSE)
    with jax.named_scope(STATES):
        left, _ = _states_transposed(
            parts["n_mat"], parts["g_end"], parts["r_mat"],
            jnp.zeros(starts.shape[1:], F32))
    with jax.named_scope(PARTS):
        d_q, d_k, d_v, d_g, d_beta = delta_parts_bwd(
            qkv, g, beta, d_out, starts, left, parts.get("t"), **dims)
        d_ba, d_a_log, d_dt_bias = gates_vjp((d_g, d_beta))
    return (jnp.concatenate([d_q, d_k, d_v], axis=-1), d_ba,
            d_a_log.astype(F32), d_dt_bias.astype(F32))
