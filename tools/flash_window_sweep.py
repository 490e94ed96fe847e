"""The flash kernels of a sliding-window layer and of a grouped-query full
layer, alone, on the chip: paddle_tpu/parallel/flash.py at the
`laguna_xs_2` cell's shapes (rows of 8192, head size 128, bf16; a band of
512 over 8 query heads on one key/value head; the triangle over 6 on one),
over the block sizes, forward and backward apart. `--batch` rows at once
make a call long enough that the host's dispatch (0.2 ms) is not what is
timed; a kernel's time is linear in it (the grid's parallel axis). PERF.md
(PR 32) holds what this printed; `ops/lm_ops.py: flash_blocks` the choice.

    chiprun -- python tools/flash_window_sweep.py
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (name, query heads, window, [(block_q, block_k)])
CASES = (
    ("window_512_8_on_1", 8, 512,
     [(128, 128), (256, 256), (512, 512), (1024, 1024), (256, 128),
      (128, 256), (512, 256), (256, 512)]),
    ("full_6_on_1", 6, None, [(1024, 1024), (512, 512), (512, 1024)]),
    ("full_6_on_6_repeated", 6, None, [(1024, 1024)]),
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--seed", type=int, default=3201)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel import flash

    B, S, D, bf = args.batch, args.seq, 128, jnp.bfloat16
    rs = np.random.default_rng(args.seed)

    def timed(f, *a):
        jax.block_until_ready(f(*a))
        t = time.perf_counter()
        for _ in range(args.calls):
            r = f(*a)
        jax.block_until_ready(r)
        return (time.perf_counter() - t) / args.calls * 1e3

    rows = []
    for name, heads, window, blocks in CASES:
        kv = heads if name.endswith("repeated") else 1
        q, do = (jnp.asarray(rs.normal(0, 1, (B, heads, S, D)), bf)
                 for _ in range(2))
        k, v = (jnp.asarray(rs.normal(0, 1, (B, kv, S, D)), bf)
                for _ in range(2))
        for bq, bk in blocks:
            kw = dict(causal=True, window=window, block_q=bq, block_k=bk)
            fwd = jax.jit(lambda q, k, v, kw=kw: flash.flash_attention_fwd(
                q, k, v, **kw))
            o, lse = fwd(q, k, v)
            bwd = jax.jit(lambda q, k, v, o, lse, do, kw=kw:
                          flash.flash_attention_bwd(q, k, v, o, lse, do,
                                                    **kw))
            row = {"case": name, "batch": B, "heads": heads, "kv_heads": kv,
                   "window": window, "blocks": [bq, bk],
                   "visited": flash.blocks_visited(S, S, bq, bk, window),
                   "of_full_causal": flash.blocks_visited(S, S, bq, bk)}
            try:
                row["fwd_ms"] = timed(fwd, q, k, v)
                row["bwd_ms"] = timed(bwd, q, k, v, o, lse, do)
            except Exception as e:        # what Mosaic refuses
                row["refused"] = str(e)[:300]
            print(json.dumps(row), flush=True)
            rows.append(row)
    out = os.path.join(REPO, "chiprun_out", "pr32")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "flash_window_sweep.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
