"""The selective state-space scan of a Mamba-2 mixer (Dao & Gu,
arXiv:2405.21060) in its CHUNKED (state-space dual, SSD) form, forward and
hand-written backward.

Per head a state h [P, N] carried along a row's tokens, zero at a row's
first token:

    h_t = exp(delta_t A) h_{t-1} + delta_t x_t B_t^T;  y_t = h_t C_t + D x_t

with delta_t = softplus(dt_t + dt_bias) > 0 and A = -exp(A_log) < 0 one
scalar a head, x_t [P] a head, B_t, C_t [N] shared by the H / G heads of a
group (head h reads group h // (H / G)).

A row is worked in chunks of Q tokens (`CHUNK` where the op's attr gives
none). With a = delta A, `cum` its running sum inside a chunk and h0 the
state the chunk starts from:

    L_ij  = exp(cum_i - cum_j), i >= j      (never exp(cum_i) exp(-cum_j))
    CB    = C B^T                            [Q, Q], once a GROUP
    y     = (L o CB) (delta x)               in-chunk, a product a head
          + exp(cum) (C h0)                  what came from before the chunk
          + D x
    S     = sum_j exp(cum_Q - cum_j) delta_j x_j B_j^T     the chunk's own
    h0'   = exp(cum_Q) h0 + S                the next chunk's h0

Everything but the last line is independent of the other chunks and runs
as batched products over all of them; the last line is a `lax.scan` over
the chunks of element-wise work on [rows, H, P, N] float32. The in-chunk
products take operands in the inputs' dtype and accumulate in float32;
delta, a, every decay and THE CARRIED STATE are float32, and a product
that reads the state or its cotangent takes float32 operands at
`STATE_PRECISION` (three bf16 passes).

THE BACKWARD is written by hand: from the chunk-start states the forward
saved ([chunks, rows, G, H / G, P, N] float32: chunks leading, so neither
scan transposes anything) it forms every in-chunk quantity again (L, CB,
the decays; nothing of [T, H, P, N] or [T, T] is kept), runs the REVERSE
recurrence of the state's cotangent

    dh_c = exp(cum) C^T dy (of chunk c) + exp(cum_Q) dh_{c+1}

and hands d S = dh_{c+1} and d exp(cum_Q) = <dh_{c+1}, h0_c> to the
transposes of the products above. `jax.vjp` is taken of no scan.

`ssd_fwd` / `ssd_bwd` are plain `jax.numpy`, the lowering of every place.
ON A TPU PLACE, for shapes `takes` takes, `kernels_fwd` / `kernels_bwd` are
the same algorithm with everything that is [Q, Q] a head (L, C B^T, L o C
B^T and their cotangents) inside the Pallas kernels of
`parallel/ssd_parts.py`: one before each scan over the chunks (the chunk's
own S; d h0), one behind it (y; the gradients), the scans and the per-token
scalars (`steps`, the softplus' backward) left to XLA. Written as above,
those arrays cost the `nemotron_3_nano_30b_a3b` step 13% of its busy time
at a tenth of the scan's roofline (PERF.md, PR 54; the kernels: PR 55).
Both paths decide precision in the same four places, which a study wraps
from outside: `steps`, `decay`, `carried`, `STATE_PRECISION`.
"""

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssd_fwd", "ssd_bwd", "kernels_fwd", "kernels_bwd", "takes",
           "steps", "states_shape", "CHUNK"]

F32 = jnp.float32
# tokens a chunk where the op's attr gives none: the published kernels'
# `chunk_size`, a whole lane tile
CHUNK = 128
# products that read the float32 state or its cotangent: float32 operands
# in three bf16 passes
STATE_PRECISION = lax.Precision.HIGH
# the parts of the op's lowering, each under a `jax.named_scope` inside the
# op's own (`mamba/scan/ssd_scan/states`)
CHUNKS, STATES, OUTPUTS = "chunks", "states", "outputs"


def steps(dt, a_log, dt_bias):
    """dt [..., H] and A_log, dt_bias [H] -> (delta = softplus(dt +
    dt_bias), a = -exp(A_log) delta), both [..., H], float32."""
    delta = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    return delta, -jnp.exp(a_log.astype(F32)) * delta


def decay(log_decay):
    """exp of a float32 log-decay <= 0."""
    return jnp.exp(log_decay.astype(F32))


def carried(g, h, s):
    """The state the next chunk starts from: g h + s, float32."""
    return g[..., None, None] * h + s


def states_shape(rows, seq_len, heads, head_dim, groups, state, chunk):
    """The shape of `States`: the state every chunk starts from."""
    return (-(-seq_len // chunk), rows, groups, heads // groups, head_dim,
            state)


def _over_chunks(g, s, reverse=False):
    """The scan over the chunks, g [c, R, G, H/G] and s [c, R, G, H/G, P,
    N]: h' = `carried`(g_c, h, s_c) from zero -> (the last h, the h each
    chunk started from); `reverse`: from the last chunk down (the state's
    cotangent)."""
    return lax.scan(lambda h, c: (carried(c[0], h, c[1]), h),
                    jnp.zeros(s.shape[1:], F32), (g, s), reverse=reverse)


def _state_dot(spec, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=STATE_PRECISION, preferred_element_type=F32)


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _chunks(a, rows, seq_len, chunk, *shape):
    """[T, ...] -> [rows, chunks, Q, *shape], a row's tail padded with
    zeros to whole chunks."""
    a = a.reshape(rows, seq_len, *shape)
    pad = -seq_len % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * len(shape))
    return a.reshape(rows, -1, chunk, *shape)


def _tokens(a, seq_len):
    """The inverse of `_chunks`: [rows, chunks, Q, ...] -> [T, prod(...)]."""
    rows = a.shape[0]
    a = a.reshape(rows, a.shape[1] * a.shape[2], -1)[:, :seq_len]
    return a.reshape(rows * seq_len, -1)


def _parts(x, b, c, dt, a_log, dt_bias, seq_len, heads, head_dim, groups,
           state, chunk):
    """The chunked inputs and what both passes form from them: x [R, c, Q,
    G, H/G, P], B, C [R, c, Q, G, N], delta, cum [R, c, Q, G, H/G] float32
    (delta zero on a row's padding), the in-chunk matrix CB [R, c, G, Q, Q]
    float32 and L [R, c, G, H/G, Q, Q] float32 (zero above the diagonal)."""
    rows = x.shape[0] // seq_len
    per = heads // groups
    xc = _chunks(x, rows, seq_len, chunk, groups, per, head_dim)
    bc = _chunks(b, rows, seq_len, chunk, groups, state)
    cc = _chunks(c, rows, seq_len, chunk, groups, state)
    delta, a = steps(_chunks(dt, rows, seq_len, chunk, groups, per),
                     a_log.reshape(groups, per), dt_bias.reshape(groups, per))
    if seq_len % chunk:
        real = (jnp.arange(xc.shape[1] * chunk) < seq_len).reshape(
            1, -1, chunk, 1, 1)
        delta, a = jnp.where(real, delta, 0.0), jnp.where(real, a, 0.0)
    cum = jnp.cumsum(a, axis=2)
    cb = _dot("rcign,rcjgn->rcgij", cc, bc)
    by_head = jnp.moveaxis(cum, 2, -1)                   # [R, c, G, H/G, Q]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = by_head[..., :, None] - by_head[..., None, :]
    ell = jnp.where(lower, decay(jnp.where(lower, seg, 0.0)), 0.0)
    return xc, bc, cc, delta, cum, cb, ell


def ssd_fwd(x, b, c, dt, a_log, dt_bias, d, *, seq_len, heads, head_dim,
            groups, state, chunk):
    """x [T, H P], B, C [T, G N], dt [T, H], A_log, dt_bias, D [H] -> (y
    [T, H P] in x's dtype, the chunk-start states [chunks, rows, G, H/G, P,
    N] float32, the state behind each row's last token [rows, H, P, N]
    float32)."""
    low = x.dtype
    with jax.named_scope(CHUNKS):
        xc, bc, cc, delta, cum, cb, ell = _parts(
            x, b, c, dt, a_log, dt_bias, seq_len, heads, head_dim, groups,
            state, chunk)
        last = cum[:, :, -1]                                # [R, c, G, H/G]
        xf = xc.astype(F32)
        xd = (xf * delta[..., None]).astype(low)
        y = _dot("rcghij,rcjghp->rcighp",
                 (ell * cb[:, :, :, None]).astype(low), xd)
        to_end = decay(last[:, :, None] - cum)
        own = _dot("rcjghp,rcjgn->rcghpn",
                   (xf * (delta * to_end)[..., None]).astype(low), bc)
    with jax.named_scope(STATES):
        final, starts = _over_chunks(jnp.moveaxis(decay(last), 1, 0),
                                     jnp.moveaxis(own, 1, 0))
    with jax.named_scope(OUTPUTS):
        y = y + _state_dot("rcign,crghpn->rcighp", cc, starts) \
            * decay(cum)[..., None]
        y = y + d.astype(F32).reshape(groups, -1, 1) * xf
        return (_tokens(y, seq_len).astype(low), starts,
                final.reshape(final.shape[0], heads, head_dim, state))


def ssd_bwd(x, b, c, dt, a_log, dt_bias, d, starts, dy, *, seq_len, heads,
            head_dim, groups, state, chunk):
    """(d x, d B, d C, d dt, d A_log, d dt_bias, d D), float32, of `ssd_fwd`'s
    y from its inputs, the chunk-start states it left and d y [T, H P]."""
    low = x.dtype
    rows, per = x.shape[0] // seq_len, heads // groups
    with jax.named_scope(CHUNKS):
        xc, bc, cc, delta, cum, cb, ell = _parts(
            x, b, c, dt, a_log, dt_bias, seq_len, heads, head_dim, groups,
            state, chunk)
        dyc = _chunks(dy.astype(low), rows, seq_len, chunk, groups, per,
                      head_dim)
        last = cum[:, :, -1]
        xf, dyf = xc.astype(F32), dyc.astype(F32)
        d_f = d.astype(F32).reshape(groups, per, 1)
        d_d = jnp.sum(dyf * xf, axis=(0, 1, 2, 5)).reshape(heads)
        # what came from before the chunk: y += e (C h0), e = exp(cum)
        e = decay(cum)
        dye = dyf * e[..., None]
        d_cum = jnp.sum(
            dye * _state_dot("rcign,crghpn->rcighp", cc, starts), axis=-1)
        d_c = _state_dot("rcighp,crghpn->rcign", dye, starts)
        d_h0 = _dot("rcighp,rcign->crghpn", dye.astype(low), cc)
    with jax.named_scope(STATES):
        g = jnp.moveaxis(decay(last), 1, 0)                 # [c, R, G, H/G]
        # d_own[c]: the cotangent of the state chunk c + 1 starts from
        _, d_own = _over_chunks(g, d_h0, reverse=True)
    with jax.named_scope(OUTPUTS):
        d_last = jnp.moveaxis(g * jnp.sum(d_own * starts, axis=(-1, -2)),
                              0, 1)
        # own = (x delta w) B^T, w = exp(cum_Q - cum)
        w = decay(last[:, :, None] - cum)
        weight = (delta * w)[..., None]
        d_xw = _state_dot("crghpn,rcjgn->rcjghp", d_own, bc)
        d_b = _state_dot("rcjghp,crghpn->rcjgn",
                         (xf * weight).astype(low), d_own)
        scaled = jnp.sum(d_xw * xf, axis=-1)                # d (delta w)
        d_x = d_f * dyf + d_xw * weight
        d_delta = scaled * w
        moved = scaled * delta * w
        d_cum = d_cum - moved
        d_last = d_last + jnp.sum(moved, axis=2)
        # in-chunk: y += (L o CB) (delta x)
        xd = (xf * delta[..., None]).astype(low)
        d_m = _dot("rcighp,rcjghp->rcghij", dyc, xd) * ell
        d_xd = _dot("rcghij,rcighp->rcjghp",
                    (ell * cb[:, :, :, None]).astype(low), dyc)
        d_cb = jnp.sum(d_m, axis=3).astype(low)
        d_seg = d_m * cb[:, :, :, None]
        d_cum = d_cum + jnp.moveaxis(
            jnp.sum(d_seg, axis=-1) - jnp.sum(d_seg, axis=-2), -1, 2)
        d_c = d_c + _dot("rcgij,rcjgn->rcign", d_cb, bc)
        d_b = d_b + _dot("rcgij,rcign->rcjgn", d_cb, cc)
        d_x = d_x + d_xd * delta[..., None]
        d_delta = d_delta + jnp.sum(d_xd * xf, axis=-1)
        # cum_Q is cum's last entry; cum is a's running sum
        d_cum = d_cum.at[:, :, -1].add(d_last)
        d_a = jnp.cumsum(d_cum[:, :, ::-1], axis=2)[:, :, ::-1]
        return (_tokens(d_x, seq_len), _tokens(d_b, seq_len),
                _tokens(d_c, seq_len)) + _steps_bwd(
                    d_delta, d_a, delta, dt, a_log, dt_bias, seq_len,
                    chunk) + (d_d,)


def _steps_bwd(d_delta, d_a, delta, dt, a_log, dt_bias, seq_len, chunk):
    """(d dt [T, H], d A_log, d dt_bias [H]) from the cotangents of delta
    and of a = delta A [R, c, Q, G, H/G], delta = softplus(dt + dt_bias)."""
    rows, _, _, groups, per = delta.shape
    neg_a = -jnp.exp(a_log.astype(F32)).reshape(groups, per)
    d_delta = d_delta + d_a * neg_a
    d_a_log = jnp.sum(d_a * delta, axis=(0, 1, 2)) * neg_a
    pre = _chunks(dt, rows, seq_len, chunk, groups, per).astype(F32) \
        + dt_bias.astype(F32).reshape(groups, per)
    d_dt = _tokens(d_delta * jax.nn.sigmoid(pre), seq_len)
    return d_dt, d_a_log.reshape(-1), jnp.sum(d_dt, axis=0)


# ------------------------------------------------------- the kernel path
def takes(rows, seq_len, heads, head_dim, groups, state, chunk, dtype):
    """Whether the kernel path takes these shapes (`ssd_parts.takes`)."""
    from . import ssd_parts

    return ssd_parts.takes(rows, seq_len, heads, head_dim, groups, state,
                           chunk, dtype)


def _by_head(dt, a_log, dt_bias, rows, seq_len, heads, groups, chunk):
    """delta [R, c, Q, G, H/G] as `_parts` forms it (rows of whole chunks),
    delta and a = delta A as the kernels read them (`ssd_parts.by_head`:
    they form a's running sum themselves) and exp(cum_Q), chunks leading
    [c, R, G, H/G]."""
    from .ssd_parts import by_head

    per = heads // groups
    delta, a = steps(_chunks(dt, rows, seq_len, chunk, groups, per),
                     a_log.reshape(groups, per), dt_bias.reshape(groups, per))
    return (delta, by_head(delta, rows, seq_len, groups),
            by_head(a, rows, seq_len, groups),
            jnp.moveaxis(decay(jnp.sum(a, axis=2)), 1, 0))


def kernels_fwd(x, b, c, dt, a_log, dt_bias, d, **shape):
    """`ssd_fwd` with the in-chunk work in the Pallas kernels of
    `parallel/ssd_parts.py`. `takes` must hold."""
    from . import ssd_parts

    seq_len, heads = shape["seq_len"], shape["heads"]
    with jax.named_scope(CHUNKS):
        _, delta, a, g = _by_head(dt, a_log, dt_bias, x.shape[0] // seq_len,
                                  seq_len, heads, shape["groups"],
                                  shape["chunk"])
        own = ssd_parts.chunk_states(x, b, a, delta, **shape)
    with jax.named_scope(STATES):
        final, starts = _over_chunks(g, own)
    with jax.named_scope(OUTPUTS):
        y = ssd_parts.chunk_outputs(x, b, c, delta, a, d, starts, **shape)
        return y, starts, final.reshape(final.shape[0], heads,
                                        *final.shape[-2:])


def kernels_bwd(x, b, c, dt, a_log, dt_bias, d, starts, dy, **shape):
    """`ssd_bwd` through the kernels: d x, d B and d C come in the inputs'
    dtype, the rest float32."""
    from . import ssd_parts

    seq_len, heads, groups = shape["seq_len"], shape["heads"], shape["groups"]
    rows, dy = x.shape[0] // seq_len, dy.astype(x.dtype)
    with jax.named_scope(CHUNKS):
        delta_c, delta, a, g = _by_head(dt, a_log, dt_bias, rows, seq_len,
                                        heads, groups, shape["chunk"])
        d_h0 = ssd_parts.chunk_states(dy, c, a, **shape)
    with jax.named_scope(STATES):
        # left[c]: the cotangent of the state chunk c + 1 starts from
        _, left = _over_chunks(g, d_h0, reverse=True)
    with jax.named_scope(OUTPUTS):
        d_x, d_b, d_c, d_delta, d_a, d_d = ssd_parts.chunk_grads(
            x, dy, b, c, delta, a, d, starts, left, **shape)
        n, per = seq_len // shape["chunk"], heads // groups
        return (d_x, d_b, d_c) + _steps_bwd(
            ssd_parts.by_token(d_delta, rows, n, per),
            ssd_parts.by_token(d_a, rows, n, per), delta_c, dt, a_log,
            dt_bias, seq_len, shape["chunk"]) + (d_d,)
