"""Operations and bytes of the matmul-shaped work of a decoder language
model that holds ONE CHIP'S SHARE of each layer and whose layers alternate
between full causal attention and a sliding window, with different head
counts and grouped-query heads (`laguna_xs_2`), as functions of the
configuration's shapes and of the rows the held experts really received.
`costs_lm.py` has the generic pieces (`matmul_flops`, `least_seconds`).

A BAND IS COUNTED AS A BAND: a window layer's head computes S W - W^2 / 2
pairs (the first W queries see a triangle, every later one W keys), a full
layer's S^2 / 2; K and V are read ONCE a key/value head, not once a query
head. As in `costs_lm.py` nothing recomputed is counted (the scores a
flash backward forms again, the pairs of a visited block that the mask
then drops): a least time built on these is never too high, so a roofline
share built on it is never too good.
"""

from chipbench.costs_lm import BF16, least_seconds, matmul_flops

FULL, WINDOW = "full_attention", "sliding_attention"


def layers(cfg):
    """[(attention kind, query heads, "dense" | "sparse")] of the layers
    the program runs: the first `num_hidden_layers` entries of the
    configuration's three lists."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def sparse_layers(cfg):
    return sum(1 for _, _, mlp in layers(cfg) if mlp == "sparse")


# --------------------------------------------------------------- attention
def attention_pairs(seq, window=None):
    """(query, key) pairs a head's causal attention over a row of `seq`
    needs: the triangle seq^2 / 2, or with a window W < seq the band
    seq W - W^2 / 2. (W >= seq is the triangle.)"""
    if window is None or window >= seq:
        return seq * seq // 2
    return seq * window - window * window // 2


def attention_flops(rows, heads, seq, head_dim, window, train):
    """Forward Q K^T and P V over the pairs, 2 operations a pair and
    number each; in training also dV, dP, dQ, dK: three times the
    forward."""
    forward = 2 * 2 * attention_pairs(seq, window) * head_dim
    return rows * heads * forward * (3 if train else 1)


def attention_bytes(rows, heads, kv_heads, seq, head_dim, train, elem=BF16):
    """Forward reads q and writes o (a query head each), reads k and v (a
    key/value head each, once for its whole group); the backward reads q,
    o, do and writes dq, reads k, v and writes dk, dv."""
    tensor = rows * seq * head_dim * elem
    return (heads + kv_heads) * tensor * (6 if train else 2)


def attention_least_seconds(cfg, kind, heads, train, peaks):
    """Of the flash kernels of ONE layer of `kind` over a step's rows."""
    rows, seq, d = (cfg["rows_per_step"], cfg["sequence_length"],
                    cfg["head_dim"])
    window = cfg["sliding_window"] if kind == WINDOW else None
    return least_seconds(
        attention_flops(rows, heads, seq, d, window, train),
        attention_bytes(rows, heads, cfg["num_key_value_heads"], seq, d,
                        train), peaks)


def attention_least_seconds_of(cfg, kind, train, peaks):
    """Summed over every layer of `kind` the program runs."""
    return sum(attention_least_seconds(cfg, k, heads, train, peaks)
               for k, heads, _ in layers(cfg) if k == kind)


# ------------------------------------------------------------ expert layer
def grouped_kernels_per_step(cfg):
    """Grouped-matmul Pallas calls a training step makes: nine a sparse
    layer."""
    return 9 * sparse_layers(cfg)


def expert_layer_least_seconds(cfg, rows_held, train, peaks):
    """Of the grouped products of ONE sparse layer over the rows the held
    experts received: gate, up, down, in training each one's two
    gradients; each the larger of its operations and its bytes (the rows
    in, the held experts' matrices, the rows out)."""
    C, F, E = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    one = least_seconds(
        matmul_flops(rows_held, C, F),
        (rows_held * C + E * C * F + rows_held * F) * BF16, peaks)
    return (9 if train else 3) * one


# ------------------------------------------------------------- whole model
def forward_flops_per_token(cfg, seq, rows_held_per_token):
    """Operations one token's forward pass needs, by part (norms, rotary,
    softmax, the gates' sigmoid, top-k and the optimizer are left out, so
    a utilization built on this is slightly low, never high).
    `rows_held_per_token`: rows the held experts of a layer received over
    the tokens of the step (top_k x held / all if routing is even)."""
    C, d, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    F = cfg["moe_intermediate_size"]
    parts = dict.fromkeys(("projections", "attention_full",
                           "attention_window", "dense_mlp", "router",
                           "held_experts", "shared_expert"), 0)
    for kind, heads, mlp in layers(cfg):
        gate = heads if cfg.get("gating") else 0
        parts["projections"] += (
            matmul_flops(1, C, heads * d) + 2 * matmul_flops(1, C, kv * d)
            + matmul_flops(1, C, gate) + matmul_flops(1, heads * d, C))
        window = cfg["sliding_window"] if kind == WINDOW else None
        parts["attention_full" if kind == FULL else "attention_window"] += \
            attention_flops(1, heads, seq, d, window, False) // seq
        if mlp == "dense":
            parts["dense_mlp"] += 3 * matmul_flops(
                1, C, cfg["intermediate_size"])
        else:
            parts["router"] += matmul_flops(
                1, C, cfg["deployment"]["num_experts"])
            parts["held_experts"] += rows_held_per_token * 3 * matmul_flops(
                1, C, F)
            parts["shared_expert"] += 3 * matmul_flops(
                1, C, cfg["shared_expert_intermediate_size"])
    parts["head"] = matmul_flops(1, C, cfg["vocab_size"])
    return parts


def train_flops_per_token(cfg, seq, rows_held_per_token):
    """Forward + backward (every product has two gradients): 3 x forward."""
    return 3 * sum(forward_flops_per_token(cfg, seq,
                                           rows_held_per_token).values())
