"""% of the score blocks of a full causal grid that the flash kernels of
the window layers compute, forward, dK/dV and dQ, at the blocks they were
built with: a program counter, static, from the shapes
(`paddle_tpu.ops.lm_ops.window_blocks` of the program the window times).
100 would be grids built over the whole triangle; the band's own share of
the triangle's pairs is the floor. The counter is sized by the helpers
that size the grids: it says what the kernels were BUILT to visit, and
whether they skip is read from their time (`swa.window_attention_roofline`).
None where the result holds no such counter."""


def read(obs):
    blocks = obs.get("window_blocks")
    if not blocks or not blocks.get("full_causal"):
        return None
    return 100.0 * blocks["visited"] / blocks["full_causal"]
