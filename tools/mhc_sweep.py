"""The residual path's two ops alone, on the chip: `mhc_mix` then
`mhc_update` around a stand-in sublayer, forward and backward, at the
`xing4_0_29b_a4b` cell's state (4096 tokens x 4 streams x 3584, bf16), in
the formulations tried for ops/lm_ops.py. PERF.md (PR 30) holds what this
printed.

    chiprun -- python tools/mhc_sweep.py
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402
import numpy as np                              # noqa: E402

from paddle_tpu.ops import lm_ops               # noqa: E402

T, N, C = 4096, 4, 3584
OPTS = (1e-6, 20, -30.0, 30.0)
F32 = jnp.float32


# ---- the update on a token-major state [T, n, C], through the generic vjp
# (the op's first form)
def update_minor(x, h_res, h_post, y):
    n = x.shape[1]
    xf, yf = x.astype(F32), y.astype(F32)
    o = sum(h_res[:, :, j, None] * xf[:, None, j, :] for j in range(n))
    return (o + h_post[:, :, None] * yf[:, None, :]).astype(x.dtype)


# ---- stream-major [n, T, C], by hand, the reductions as einsums (tried)
@jax.custom_vjp
def update_major_einsum(x, h_res, h_post, y):
    return lm_ops._mhc_update_fwd(x, h_res, h_post, y)[0]


def _einsum_bwd(res, g):
    x, h_res, h_post, y = res
    d_x, _, _, d_y = lm_ops._mhc_update_bwd(res, g)
    return (d_x, jnp.einsum("itc,jtc->tij", g, x, preferred_element_type=F32),
            jnp.einsum("itc,tc->ti", g, y, preferred_element_type=F32), d_y)


update_major_einsum.defvjp(lm_ops._mhc_update_fwd, _einsum_bwd)
update_major = lm_ops._mhc_update          # the op as it is


def mix_major(x, phi, alpha, b_pre, b_post, b_res):
    """The op as it is: stream-major, per-stream passes."""
    return lm_ops._mhc_mix(x, phi.reshape(N, C, -1), alpha, b_pre, b_post,
                           b_res, OPTS)


def mix_minor(x, phi, alpha, b_pre, b_post, b_res):
    """The op on a token-major state: the transpose it would cost."""
    return mix_major(jnp.swapaxes(x, 0, 1), phi, alpha, b_pre, b_post,
                     b_res)


def main():
    rs = np.random.default_rng(0)
    x_minor = jnp.asarray(rs.normal(0, 1, (T, N, C)), jnp.bfloat16)
    x_major = jnp.swapaxes(x_minor, 0, 1)
    ct = jnp.asarray(rs.normal(0, 1, (T, N, C)), jnp.bfloat16)
    y = jnp.asarray(rs.normal(0, 1, (T, C)), jnp.bfloat16)
    phi = jnp.asarray(rs.normal(0, .02, (N * C, 2 * N + N * N)), F32)
    alpha = jnp.full(3, 0.01, F32)
    b_pre, b_post = (jnp.asarray(rs.normal(0, 1, N), F32) for _ in range(2))
    b_res = jnp.asarray(rs.normal(0, 1, N * N), F32)
    h_res = jax.nn.softmax(jnp.asarray(rs.normal(0, 1, (T, N, N)), F32), -1)
    h_post = jnp.asarray(rs.uniform(0, 2, (T, N)), F32)
    cases = {}

    def grad_of(fn, *args, argnums):
        return jax.jit(jax.grad(fn, argnums=argnums)), args

    for name, upd, x, c in (("update [T,n,C] generic vjp", update_minor,
                             x_minor, ct),
                            ("update [n,T,C] by hand", update_major, x_major,
                             jnp.swapaxes(ct, 0, 1)),
                            ("update [n,T,C] by hand, einsum reductions",
                             update_major_einsum, x_major,
                             jnp.swapaxes(ct, 0, 1))):
        cases[name + ", forward"] = (jax.jit(upd), (x, h_res, h_post, y))
        cases[name + ", forward + backward"] = grad_of(
            lambda x_, r, p, y_, upd=upd, c=c: jnp.sum(
                upd(x_, r, p, y_).astype(F32) * c), x, h_res, h_post, y,
            argnums=(0, 1, 2, 3))
    u_ct = jnp.asarray(rs.normal(0, 1, (T, C)), jnp.bfloat16)
    for name, mix, x in (("mix [T,n,C] through a transpose", mix_minor,
                          x_minor),
                         ("mix [n,T,C]", mix_major, x_major)):
        def loss(x_, phi_, a, b1, b2, b3, mix=mix):
            u, post, res = mix(x_, phi_, a, b1, b2, b3)
            return (jnp.sum(u.astype(F32) * u_ct) + jnp.sum(post * h_post)
                    + jnp.sum(res * h_res))
        cases[name + ", forward"] = (jax.jit(mix), (x, phi, alpha, b_pre,
                                                    b_post, b_res))
        cases[name + ", forward + backward"] = grad_of(
            loss, x, phi, alpha, b_pre, b_post, b_res,
            argnums=(0, 1, 2, 3, 4, 5))
    rows = []
    for name, (fn, args) in cases.items():
        jax.block_until_ready(fn(*args))
        t = time.perf_counter()
        for _ in range(20):
            out = fn(*args)
        jax.block_until_ready(out)
        rows.append({"what": name,
                     "ms": (time.perf_counter() - t) / 20 * 1e3})
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out", "pr30"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "pr30", "mhc_sweep.json"),
              "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
