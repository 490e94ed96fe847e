"""The in-chunk quantities of the chunked gated delta rule
(`parallel/delta_rule.py`: `_parts`, whose head holds the formulas) and
their transpose as two Pallas TPU kernels.

One grid step is `CHUNKS_A_STEP` chunks of one KEY head: their q and k [C,
dk] and the v [C, Hv / Hk x dv] of the value heads that key head serves,
all three read from [q | k | v] where they lie (q and k normalised here, by
`delta_rule.l2_normalized`), the gates' [C, Hv] block read whole and a
head's column picked from it. k k^T and q k^T are formed once a key head;
everything of size [C, C] (the decays D, A, the triangular inverse T, P)
lives and dies in VMEM. What leaves is what the chunk scan and the output
product take, chunks leading: Q', O0, N, B, exp(G_C).

A step's (chunk, value head) pairs are INDEPENDENT chains of dependent
products (the inverse is six squarings, each waiting for the last), so the
kernels walk them side by side: the inverses a squaring at a time over all
chains (`_inverses`), and what is a scalar a token (g, beta, the running
sums, d g, d beta) as ONE [C, 128] array a step, chain 8 c + i in lane
8 c + i, with one transpose a step where a chain needs its row form. One
chain a step left the MXU waiting: 4.74 ms a layer at [8192 tokens, 32
heads] against 2.26 with eight (v5e, PERF.md, PR 44).

IN THE BACKWARD `delta_parts_fwd` runs again, handed d Out, and writes what
the reverse scan takes (Q'^T d O, N, exp(G_C)) and the inverse T;
`delta_parts_bwd` reads that T (or forms it again), forms the cotangents of
the forward's five results from d Out, the saved chunk-start states and the
reverse scan's result, and transposes the in-chunk work by hand, every
line the cotangent of a line of the forward; d q and d k are summed over
the value heads of the key head and taken through the normalisation's vjp
inside the kernel.

Precision is `_parts`': g, beta, every decay float32; the triangular
inverse on float32 operands at `delta_rule.INVERSE_PRECISION` (Mosaic has
no three-pass product, so at HIGH the three bf16 passes are written out:
both operands split into a high and a low bf16 half; HIGHEST is Mosaic's
float32 contraction); the other products take operands in the inputs'
dtype and accumulate in float32. Float32 inputs run the same kernels.

Running sums along a chunk are log-step sublane rotations (`pltpu.roll`),
not a product with a triangle of ones: float32 adds, no precision to state.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import pallas_interpret

__all__ = ["delta_parts_fwd", "delta_parts_bwd", "takes", "KERNELS"]

# the kernels' names: Pallas puts them on the name stack, so a device trace
# reads `delta/delta_rule/gated_delta_rule/parts/delta_parts_fwd`
KERNELS = ("delta_parts_fwd", "delta_parts_bwd")
_VMEM_LIMIT = 64 * 2 ** 20       # of the v5e's 128 MiB
_SUBLANES = 8        # a float32 tile's rows: the value heads a key head serves
F32, BF16 = jnp.float32, jnp.bfloat16
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
# what the forward kernel can write, in this order
OUTPUTS = ("q_p", "o0", "n_mat", "b_mat", "g_end", "t", "r_mat")


def takes(rows, seq_len, hk, hv, dk, dv, chunk, dtype):
    """Whether the kernels take `rows` rows of `seq_len` tokens at `hk` key
    and `hv` value heads of `dk` / `dv` in chunks of `chunk`: whole lane
    tiles a head and a chunk, rows of whole chunks, the value heads of a
    key head one column block of [q | k | v], bf16 or float32."""
    rep = hv // max(hk, 1)
    return bool(
        rows >= 1 and hk >= 1 and hv == rep * hk and rep <= _SUBLANES
        and dk % 128 == 0
        and dv % 128 == 0 and chunk % 128 == 0 and seq_len
        and seq_len % chunk == 0 and (2 * hk * dk) % (rep * dv) == 0
        and str(dtype) in ("bfloat16", "float32"))


def _mm(a, b, dims=_NN):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=F32)


def _dot(a, b, low, dims=_NN):
    return _mm(a.astype(low), b.astype(low), dims)


def _halves(a):
    hi = a.astype(BF16)
    return hi, (a - hi.astype(F32)).astype(BF16)


def _operand(a):
    """What `_exact` takes of a float32 operand: its two bf16 halves where
    the three passes are written out, else the operand itself."""
    from . import delta_rule

    if delta_rule.INVERSE_PRECISION == lax.Precision.HIGH:
        return _halves(a)
    return (a,)


def _exact(a, b):
    """a b of two `_operand`s at `delta_rule.INVERSE_PRECISION` (looked up
    as the kernel is traced)."""
    from . import delta_rule

    if len(a) == 1:
        return jnp.dot(a[0], b[0], precision=delta_rule.INVERSE_PRECISION,
                       preferred_element_type=F32)
    return _mm(a[1], b[0]) + _mm(a[0], b[1]) + _mm(a[0], b[0])


def _roll(a, shift):
    """Rows of a rotated down by `shift` (row t holds a[t - shift])."""
    return jnp.roll(a, shift, axis=0) if pallas_interpret() \
        else pltpu.roll(a, shift, 0)


def _running_sum(x, reverse=False):
    """The inclusive running sum down (`reverse`: up) the rows of x."""
    n, s = x.shape[0], 1
    rows = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    while s < n:
        if reverse:
            x = x + jnp.where(rows < n - s, _roll(x, n - s), 0.0)
        else:
            x = x + jnp.where(rows >= s, _roll(x, s), 0.0)
        s *= 2
    return x


def _set(acc, axis, at, value):
    """acc with index `at` of `axis` replaced by value (broadcast)."""
    where = lax.broadcasted_iota(jnp.int32, acc.shape, axis)
    return jnp.where(where == at, value, acc)


def _lane(c, i):
    """The lane of chunk c's value head i in a step's [C, 128] arrays."""
    return _SUBLANES * c + i


def _gate_columns(ref, head, cb, rep):
    """[C, 128] float32: in lane `_lane(c, i)` the column of value head
    `head` x rep + i of chunk c of the [cb x C, Hv] block `ref` views."""
    n = ref.shape[0] // cb
    acc = jnp.zeros((n, 128), F32)
    for c in range(cb):
        x = ref[c * n:(c + 1) * n, :].astype(F32)
        lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
        for i in range(rep):
            acc = _set(acc, 1, _lane(c, i), jnp.sum(
                jnp.where(lane == head * rep + i, x, 0.0), axis=1,
                keepdims=True))
    return acc


def _inverses(mats, eye):
    """`delta_rule._inverse` of each of `mats` (strictly lower triangular,
    float32; `eye` the identity), a squaring at a time over all of them:
    (I + A)^-1 as (I + M)(I + M^2)(I + M^4)..., M = -A."""
    n = mats[0].shape[-1]
    ms = [_operand(-a) for a in mats]
    ts = [eye - a for a in mats]
    p = 1
    while 2 * p < n:
        ms = [_operand(_exact(m, m)) for m in ms]
        ts = [t + _exact(_operand(t), m) for t, m in zip(ts, ms)]
        p *= 2
    return ts


def _normalized(q, k, eps):
    """A head's q and k [C, dk] as the recurrence takes them:
    `delta_rule.l2_normalized`, looked up as the kernel is traced."""
    from . import delta_rule

    return (delta_rule.l2_normalized(q, eps, q.shape[-1] ** -0.5),
            delta_rule.l2_normalized(k, eps))


def _chains(qs, ks, v_ref, g_ref, beta_ref, head, rep, dv, t_of=None):
    """The in-chunk quantities of a step's cb x rep (chunk, value head)
    chains, as a list of dicts: `qs`, `ks` the normalised q and k [C, dk] of
    each of the step's cb chunks; `t_of(c, i)`: the triangular inverse where
    it is read and not formed."""
    cb, (n, dk), low = len(qs), qs[0].shape, qs[0].dtype
    rows = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    lower, strict = rows >= cols, rows > cols
    beta_all = _gate_columns(beta_ref, head, cb, rep)
    run_all = _running_sum(_gate_columns(g_ref, head, cb, rep))
    run_rows = run_all.T                             # [128, C]
    chains = []
    for c, (q, k) in enumerate(zip(qs, ks)):
        kk, qk = _dot(k, k, low, _NT), _dot(q, k, low, _NT)
        for i in range(rep):
            at = _lane(c, i)
            run, beta = run_all[:, at:at + 1], beta_all[:, at:at + 1]
            decay = jnp.exp(jnp.where(
                lower, run - run_rows[at:at + 1, :], -jnp.inf))
            chains.append(dict(
                c=c, i=i, at=at, q=q, k=k, kk=kk, qk=qk, beta=beta,
                v=v_ref[c * n:(c + 1) * n, i * dv:(i + 1) * dv],
                decay=decay, gamma=jnp.exp(run),
                to_end=jnp.exp(run[n - 1:, :] - run)))
    if t_of is None:
        ts = _inverses(
            [jnp.where(strict, ch["beta"] * ch["decay"] * ch["kk"], 0.0)
             for ch in chains], jnp.where(rows == cols, 1.0, 0.0))
    for j, ch in enumerate(chains):
        kf = ch["k"].astype(F32)
        ch["t"] = ts[j].astype(low) if t_of is None \
            else t_of(ch["c"], ch["i"])
        rhs = jnp.concatenate([(ch["beta"] * ch["gamma"]) * kf,
                               ch["beta"] * ch["v"].astype(F32)], axis=1)
        ch["wu"] = _dot(ch["t"], rhs, low).astype(low)       # [W | U0]
        ch["p"] = jnp.where(lower, ch["decay"] * ch["qk"], 0.0).astype(low)
        ch["k_end"] = (ch["to_end"] * kf).astype(low)
    return chains, lower, strict, dk


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *refs,
                rep, dv, cb, eps, outputs):
    # (`r_mat` = Q'^T d O, what the reverse scan starts from, needs d Out)
    do_ref, out_refs = (refs[0], refs[1:]) if "r_mat" in outputs \
        else (None, refs)
    out = dict(zip(outputs, out_refs))
    low, n = q_ref.dtype, q_ref.shape[0] // cb
    qs, ks = zip(*(_normalized(q_ref[c * n:(c + 1) * n, :],
                               k_ref[c * n:(c + 1) * n, :], eps)
                   for c in range(cb)))
    chains, _, _, dk = _chains(qs, ks, v_ref, g_ref, beta_ref,
                               pl.program_id(1), rep, dv)
    g_end = [jnp.zeros((1, rep), F32)] * cb
    for ch in chains:
        c, i = ch["c"], ch["i"]
        if "o0" in out:
            # Q' = gamma q - P W, O0 = P U0
            p_wu = _dot(ch["p"], ch["wu"], low)
            out["o0"][c, i] = p_wu[:, dk:]
        else:
            p_wu = _dot(ch["p"], ch["wu"][:, :dk], low)
        q_p = (ch["gamma"] * ch["q"].astype(F32) - p_wu[:, :dk]).astype(low)
        if "q_p" in out:
            out["q_p"][c, i] = q_p
        if "r_mat" in out:
            out["r_mat"][c, i] = _dot(
                q_p, do_ref[c * n:(c + 1) * n, i * dv:(i + 1) * dv], low,
                _TN)
        if "b_mat" in out:
            # N = Ke^T W, B = Ke^T U0
            n_b = _dot(ch["k_end"], ch["wu"], low, _TN)
            out["n_mat"][c, i] = n_b[:, :dk].astype(low)
            out["b_mat"][c, i] = n_b[:, dk:]
        elif "n_mat" in out:
            out["n_mat"][c, i] = _dot(ch["k_end"], ch["wu"][:, :dk], low,
                                      _TN).astype(low)
        if "t" in out:
            out["t"][c, i] = ch["t"]
        g_end[c] = _set(g_end[c], 1, i, ch["gamma"][n - 1:, :])
    if "g_end" in out:
        for c in range(cb):
            out["g_end"][c] = g_end[c]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref,
                starts_ref, left_ref, *refs, rep, dv, cb, eps, reads_t):
    t_ref = refs[0] if reads_t else None
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref = refs[1 if reads_t else 0:]
    low, n = q_ref.dtype, q_ref.shape[0] // cb
    qs, ks, normalized_vjp = [], [], []
    for c in range(cb):
        (q, k), vjp = jax.vjp(
            functools.partial(_normalized, eps=eps),
            q_ref[c * n:(c + 1) * n, :], k_ref[c * n:(c + 1) * n, :])
        qs.append(q)
        ks.append(k)
        normalized_vjp.append(vjp)
    chains, lower, strict, dk = _chains(
        qs, ks, v_ref, g_ref, beta_ref, pl.program_id(1), rep, dv,
        t_of=None if t_ref is None else (lambda c, i: t_ref[c, i]))
    d_q = [jnp.zeros((n, dk), F32)] * cb
    d_k = [jnp.zeros((n, dk), F32)] * cb
    # what is a scalar a token, chain `at` in lane (row) `at`: d beta and the
    # cotangent of the running sum of g by columns, the column sums of a
    # chain's [C, C] cotangent by rows (transposed once, below)
    d_beta, d_run = jnp.zeros((n, 128), F32), jnp.zeros((n, 128), F32)
    col_sums = jnp.zeros((128, n), F32)
    last_row = lax.broadcasted_iota(jnp.int32, (n, 1), 0) == n - 1

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    for ch in chains:
        c, i, at = ch["c"], ch["i"], ch["at"]
        q, k, beta, gamma = ch["q"], ch["k"], ch["beta"], ch["gamma"]
        decay, to_end, wu = ch["decay"], ch["to_end"], ch["wu"]
        qf, kf = q.astype(F32), k.astype(F32)
        # o = Q' S + O0 and S1 = exp(G_C) S + B - N S with S the state the
        # chunk starts from and lam the cotangent of the state it leaves:
        # d Q' = d o S^T, d O0 = d o, d N = -lam S^T, d B = lam, d exp(G_C)
        # = <lam, S>
        d_o = do_ref[c * n:(c + 1) * n, i * dv:(i + 1) * dv]
        state, lam = starts_ref[c, i], left_ref[c, i]
        d_qp = _dot(d_o, state, low, _NT).astype(low)
        d_g_end = jnp.sum(jnp.sum(lam * state, axis=1, keepdims=True),
                          axis=0, keepdims=True)
        # x = [-d Q' | d O0], y = [d N | d B]: O0 = P U0, Q' = gamma q - P W,
        # N = Ke^T W, B = Ke^T U0 with [W | U0] one array
        x = jnp.concatenate([-d_qp, d_o], axis=1)
        y = jnp.concatenate([(-_dot(lam, state, low, _NT)).astype(low),
                             lam.astype(low)], axis=1)
        d_p = jnp.where(lower, _dot(x, wu, low, _NT), 0.0)
        d_wu = _dot(ch["p"], x, low, _TN) + _dot(ch["k_end"], y, low)
        d_k_end = _dot(wu, y, low, _NT)
        d_qpf = d_qp.astype(F32)
        d_q[c] = d_q[c] + gamma * d_qpf
        d_gamma = rowsum(d_qpf * qf)
        d_k[c] = d_k[c] + to_end * d_k_end
        d_to_end = rowsum(d_k_end * kf)
        # P = lower(D q k^T)
        d_decay = d_p * ch["qk"]
        d_qk = (d_p * decay).astype(low)
        d_q[c] = d_q[c] + _dot(d_qk, k, low)
        d_k[c] = d_k[c] + _dot(d_qk, q, low, _TN)
        # [W | U0] = T rhs: d rhs = T^T d[W | U0], d A = -d rhs [W | U0]^T
        d_rhs = _dot(ch["t"], d_wu, low, _TN).astype(low)
        d_a = jnp.where(strict, -_dot(d_rhs, wu, low, _NT), 0.0)
        d_rhs = d_rhs.astype(F32)
        d_rhs_w, d_rhs_u = d_rhs[:, :dk], d_rhs[:, dk:]
        # rhs = [beta gamma k | beta v]
        d_k[c] = d_k[c] + (beta * gamma) * d_rhs_w
        d_bg = rowsum(d_rhs_w * kf)
        dv_ref[c * n:(c + 1) * n, i * dv:(i + 1) * dv] = (
            beta * d_rhs_u).astype(dv_ref.dtype)
        d_gamma = d_gamma + beta * d_bg
        # A = strict(beta D k k^T)
        d_decay = d_decay + beta * d_a * ch["kk"]
        d_kk = beta * decay * d_a
        d_k[c] = d_k[c] + _dot(d_kk + d_kk.T, k, low)
        d_beta = _set(d_beta, 1, at, rowsum(
            d_rhs_u * ch["v"].astype(F32)) + gamma * d_bg
            + rowsum(d_a * decay * ch["kk"]))
        # D_ij = exp(G_i - G_j), gamma = exp(G), to_end = exp(G_C - G),
        # exp(G_C) = gamma_C; G the running sum of g
        m = d_decay * decay
        col_sums = _set(col_sums, 0, at, jnp.sum(m, axis=0, keepdims=True))
        at_end = jnp.sum(d_to_end * to_end, axis=0, keepdims=True) \
            + d_g_end * gamma[n - 1:, :]
        d_run = _set(d_run, 1, at,
                     rowsum(m) + d_gamma * gamma - d_to_end * to_end
                     + jnp.where(last_row, at_end, 0.0))
    d_g = _running_sum(d_run - col_sums.T, reverse=True).T       # [128, C]
    d_beta = d_beta.T
    for c in range(cb):
        dq_ref[c * n:(c + 1) * n, :], dk_ref[c * n:(c + 1) * n, :] = \
            normalized_vjp[c]((d_q[c].astype(low), d_k[c].astype(low)))
        of_c = slice(_SUBLANES * c, _SUBLANES * (c + 1))
        dg_ref[:, c * n:(c + 1) * n] = d_g[of_c]
        dbeta_ref[:, c * n:(c + 1) * n] = d_beta[of_c]


# chunks a grid step: eight chains side by side at two value heads a key
# head (swept on the v5e at [8192, 16 / 32 heads of 128], PERF.md, PR 44)
CHUNKS_A_STEP = 4


def _chunks_a_step(n_chunks):
    return next(c for c in range(min(CHUNKS_A_STEP, 128 // _SUBLANES,
                                     n_chunks), 0, -1) if n_chunks % c == 0)


def _plan(rows, seq_len, hk, hv, dk, dv, chunk):
    """The grid (row, key head, block of chunks) and the index maps."""
    rep, n_chunks = hv // hk, seq_len // chunk
    cb = _chunks_a_step(n_chunks)
    n_blocks = n_chunks // cb
    v_first = 2 * hk * dk // (rep * dv)

    def tokens_of(head_block):
        return lambda r, j, n: (r * n_blocks + n, head_block(j))

    return dict(
        rep=rep, n_chunks=n_chunks, cb=cb, grid=(rows, hk, n_blocks),
        q=tokens_of(lambda j: j), k=tokens_of(lambda j: hk + j),
        v=tokens_of(lambda j: v_first + j), gates=tokens_of(lambda j: 0),
        parts=lambda r, j, n: (n, r * hk + j, 0, 0),
        by_head=lambda r, j, n: (j, 0, r * n_blocks + n))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _inputs(pl_, hv, dk, dv, chunk):
    rep, block = pl_["rep"], pl_["cb"] * chunk
    return [pl.BlockSpec((block, dk), pl_["q"]),
            pl.BlockSpec((block, dk), pl_["k"]),
            pl.BlockSpec((block, rep * dv), pl_["v"]),
            pl.BlockSpec((block, hv), pl_["gates"]),
            pl.BlockSpec((block, hv), pl_["gates"])]


def _d_out_spec(pl_, dv, chunk):
    """d Out [T, Hv dv] where it lies: the columns of a key head's value
    heads."""
    return pl.BlockSpec((pl_["cb"] * chunk, pl_["rep"] * dv), pl_["q"])


def _parts_specs(pl_, rows, hk, dk, dv, chunk, low):
    """{name: (BlockSpec, ShapeDtypeStruct)} of the chunk-major arrays
    between the kernels and the scans: [N, rows x Hv, ...]."""
    rep, n, cb = pl_["rep"], pl_["n_chunks"], pl_["cb"]
    b = rows * hk * rep

    def of(tail, dtype):
        return (pl.BlockSpec((cb, rep) + tail, pl_["parts"]),
                jax.ShapeDtypeStruct((n, b) + tail, dtype))

    return {"q_p": of((chunk, dk), low), "o0": of((chunk, dv), F32),
            "n_mat": of((dk, dk), low), "b_mat": of((dk, dv), F32),
            "t": of((chunk, chunk), low), "r_mat": of((dk, dv), F32),
            "g_end": (pl.BlockSpec((cb, None, 1, rep), pl_["parts"]),
                      jax.ShapeDtypeStruct((n, rows * hk, 1, rep), F32))}


def delta_parts_fwd(qkv, g, beta, d_out=None, *, rows, seq_len, hk, hv, dk,
                    dv, chunk, eps, outputs=OUTPUTS[:5]):
    """The in-chunk quantities of every head: [q | k | v] [T, 2 Hk dk +
    Hv dv] as the convolution left it (q and k are normalised a head here,
    with `eps`), g and beta [T, Hv] float32
    -> {name: array} for the names in `outputs`: Q' [N, B, C, dk] and N [N,
    B, dk, dk] in the inputs' dtype, O0 [N, B, C, dv], B [N, B, dk, dv] and
    exp(G_C) [N, B] float32, `t` the triangular inverse [N, B, C, C] in the
    inputs' dtype, `r_mat` = Q'^T d O [N, B, dk, dv] float32 (from `d_out`
    [T, Hv dv], read where it lies like v); N chunks leading, B = rows x
    Hv. `takes` must hold."""
    pl_ = _plan(rows, seq_len, hk, hv, dk, dv, chunk)
    specs = _parts_specs(pl_, rows, hk, dk, dv, chunk, qkv.dtype)
    outputs = tuple(o for o in OUTPUTS if o in outputs)
    ins, in_specs = [qkv, qkv, qkv, g, beta], _inputs(pl_, hv, dk, dv,
                                                      chunk)
    if "r_mat" in outputs:
        ins.append(d_out.astype(qkv.dtype))
        in_specs.append(_d_out_spec(pl_, dv, chunk))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, rep=pl_["rep"], dv=dv, cb=pl_["cb"],
                          eps=eps, outputs=outputs),
        grid=pl_["grid"], in_specs=in_specs,
        out_specs=[specs[o][0] for o in outputs],
        out_shape=[specs[o][1] for o in outputs],
        compiler_params=_params(), interpret=pallas_interpret(),
        name=KERNELS[0])(*ins)
    res = dict(zip(outputs, res))
    if "g_end" in res:
        res["g_end"] = res["g_end"].reshape(pl_["n_chunks"], -1)
    return res


def delta_parts_bwd(qkv, g, beta, d_out, starts, left, t=None, *, rows,
                    seq_len, hk, hv, dk, dv, chunk, eps):
    """The transpose of `delta_parts_fwd` with the cotangents of its
    results formed inside: its inputs, d Out [T, Hv dv] (read where it
    lies), the state each chunk starts from and the cotangent of the state
    it leaves (`starts`, `left` [N, rows x Hv, dk, dv] float32) and `t`,
    the forward's triangular inverse, where that is not to be formed again
    -> (d q, d k [T, Hk dk] in the inputs' dtype, of [q | k | v]'s own q
    and k (through the normalisation), summed over the value heads a key
    head serves, d v [T, Hv dv], d g and d beta [T, Hv] float32)."""
    pl_ = _plan(rows, seq_len, hk, hv, dk, dv, chunk)
    rep, low, T = pl_["rep"], qkv.dtype, qkv.shape[0]
    block = pl_["cb"] * chunk
    specs = _parts_specs(pl_, rows, hk, dk, dv, chunk, low)
    ins = [d_out.astype(low), starts.astype(F32), left.astype(F32)]
    in_specs = _inputs(pl_, hv, dk, dv, chunk) + [
        _d_out_spec(pl_, dv, chunk), specs["b_mat"][0], specs["b_mat"][0]]
    if t is not None:
        ins.append(t)
        in_specs.append(specs["t"][0])
    # a key head's value heads down the sublanes, tokens along the lanes (a
    # [T, 2] array would be padded to 128 lanes in HBM)
    gate = (pl.BlockSpec((None, _SUBLANES, block), pl_["by_head"]),
            jax.ShapeDtypeStruct((hk, _SUBLANES, T), F32))
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        functools.partial(_bwd_kernel, rep=rep, dv=dv, cb=pl_["cb"],
                          eps=eps, reads_t=t is not None),
        grid=pl_["grid"], in_specs=in_specs,
        out_specs=[pl.BlockSpec((block, dk), pl_["q"]),
                   pl.BlockSpec((block, dk), pl_["q"]),
                   pl.BlockSpec((block, rep * dv), pl_["q"]),
                   gate[0], gate[0]],
        out_shape=[jax.ShapeDtypeStruct((T, hk * dk), low),
                   jax.ShapeDtypeStruct((T, hk * dk), low),
                   jax.ShapeDtypeStruct((T, hv * dv), low),
                   gate[1], gate[1]],
        compiler_params=_params(), interpret=pallas_interpret(),
        name=KERNELS[1])(qkv, qkv, qkv, g, beta, *ins)

    def by_token(x):          # [Hk, 8, T] -> [T, Hv]
        return jnp.moveaxis(x[:, :rep], 2, 0).reshape(T, -1)

    return d_q, d_k, d_v, by_token(d_g), by_token(d_beta)
