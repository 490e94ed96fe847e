"""Operations and bytes of the matmul-shaped work of a decoder language
model that holds ONE CHIP'S SHARE of each layer (latent attention with
keys wider than values, a residual path of several streams, held and
shared experts, a multi-token-prediction module), as functions of the
configuration's shapes and of the rows the held experts really received.
`costs_lm.py` has the generic pieces (`matmul_flops`, `least_seconds`)
and stays as it is: its layer is one attention of equal head sizes and one
expert layer over every expert.

As there, nothing recomputed is counted: a least time built on these is
never too high, so a roofline share built on it is never too good.
"""

from chipbench.costs_lm import BF16, least_seconds, matmul_flops


def blocks(cfg):
    """(dense blocks, expert blocks) the program runs: the decoder layers
    and, as one more expert block, the multi-token-prediction module."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, (cfg["num_hidden_layers"] - dense
                   + int(cfg.get("num_nextn_predict_layers", 0)))


# --------------------------------------------------------------- attention
def head_sizes(cfg):
    """(queries' and keys' size, values' size) of a head."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def causal_attention_flops(rows, heads, seq, d_qk, d_v, train):
    """Forward Q K^T (d_qk) and P V (d_v) over half the square; in
    training also dV, dP (d_v each) and dQ, dK (d_qk each): three times
    the forward. The scores a flash backward recomputes are not counted."""
    forward = 2 * seq * seq * (d_qk + d_v) // 2
    return rows * heads * forward * (3 if train else 1)


def causal_attention_bytes(rows, heads, seq, d_qk, d_v, train, elem=BF16):
    """Forward reads q, k (d_qk), v and writes o (d_v); the backward reads
    q, k, v, o, do and writes dq, dk, dv."""
    wide, narrow = (rows * heads * seq * d * elem for d in (d_qk, d_v))
    return (2 * wide + 2 * narrow) * (3 if train else 1)


def attention_least_seconds(cfg, train, peaks):
    """Of the flash kernels of ONE block over a step's rows."""
    d_qk, d_v = head_sizes(cfg)
    args = (cfg["rows_per_step"], cfg["num_attention_heads"],
            cfg["sequence_length"], d_qk, d_v, train)
    return least_seconds(causal_attention_flops(*args),
                         causal_attention_bytes(*args), peaks)


def flash_kernels_per_step(cfg):
    """(forward, backward) Pallas calls a training step makes: one forward
    a block; dK/dV and dQ a block."""
    n = sum(blocks(cfg))
    return n, 2 * n


# ------------------------------------------------------------ expert layer
def grouped_kernels_per_step(cfg):
    """Grouped-matmul Pallas calls a training step makes: nine an expert
    block."""
    return 9 * blocks(cfg)[1]


def expert_layer_least_seconds(cfg, rows_held, train, peaks):
    """Of the grouped products of ONE expert block over the rows the held
    experts received: gate, up, down, in training each one's two
    gradients; each the larger of its operations and its bytes (the rows
    in, the held experts' matrices, the rows out)."""
    C, F, E = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["n_routed_experts"])
    one = least_seconds(
        matmul_flops(rows_held, C, F),
        (rows_held * C + E * C * F + rows_held * F) * BF16, peaks)
    return (9 if train else 3) * one


# ------------------------------------------------------------- whole model
def forward_flops_per_token(cfg, seq, rows_held_per_token):
    """Operations one token's forward pass needs, by part (norms, rotary,
    softmax, Sinkhorn, top-k and the optimizer are left out, so a
    utilization built on this is slightly low, never high).
    `rows_held_per_token`: rows the held experts of a layer received over
    the tokens of the step (top_k x held / all if routing is even)."""
    C, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d_qk, d_v = head_sizes(cfg)
    n, F = cfg["hc_mult"], cfg["moe_intermediate_size"]
    dense, expert = blocks(cfg)
    every = dense + expert
    latent = (matmul_flops(1, C, cfg["q_lora_rank"])
              + matmul_flops(1, cfg["q_lora_rank"], heads * d_qk)
              + matmul_flops(1, C, cfg["kv_lora_rank"]
                             + cfg["qk_rope_head_dim"])
              + matmul_flops(1, cfg["kv_lora_rank"],
                             heads * (cfg["qk_nope_head_dim"] + d_v))
              + matmul_flops(1, heads * d_v, C))
    # a mixer: the 2n + n*n projections of the n*C state, U, and the
    # update's n*n + n products of C values
    mixer = (matmul_flops(1, n * C, 2 * n + n * n) + 2 * n * C
             + 2 * (n * n + n) * C)
    parts = {
        "latent_projections": every * latent,
        "attention": every * causal_attention_flops(
            1, heads, seq, d_qk, d_v, False) // seq,
        "mixers": every * 2 * mixer,
        "dense_mlp": dense * 3 * matmul_flops(1, C, cfg["intermediate_size"]),
        "router": expert * matmul_flops(
            1, C, cfg["deployment"]["n_routed_experts"]),
        "held_experts": expert * rows_held_per_token * 3 * matmul_flops(
            1, C, F),
        "shared_expert": expert * cfg["n_shared_experts"] * 3 * matmul_flops(
            1, C, F),
        "head": matmul_flops(1, C, cfg["vocab_size"]),
    }
    if cfg.get("num_nextn_predict_layers"):
        parts["mtp_projection_and_head"] = (
            matmul_flops(1, 2 * C, C) + matmul_flops(1, C, cfg["vocab_size"]))
    return parts


def train_flops_per_token(cfg, seq, rows_held_per_token):
    """Forward + backward (every product has two gradients): 3 x forward."""
    return 3 * sum(forward_flops_per_token(cfg, seq,
                                           rows_held_per_token).values())
