"""Operations and bytes of the matmul-shaped work of a decoder language
model with sparse experts, as functions of the shapes alone: a matrix
product, causal attention (half the square), the expert layer (the rows
really routed x its three products) and the head. The peaks they are set
against are `chipbench/peaks.json` (`costs.peaks_for`).

As in `costs.py` the compiler's own cost analysis is not used, and nothing
recomputed is counted: a least time built on these is never too high, so
a roofline share built on it is never too good.
"""

BF16 = 2


def matmul_flops(m, k, n):
    """[m, k] x [k, n]: multiply-adds x 2."""
    return 2 * m * k * n


def matmul_bytes(m, k, n, elem=BF16):
    """Both operands and the result, each moved once."""
    return (m * k + k * n + m * n) * elem


def least_seconds(flops, nbytes, peaks):
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


# --------------------------------------------------------------- attention
def attention_products(train):
    """Products over the (half) square a pass needs: Q K^T and P V
    forward; in training also dV = P^T dO, dP = dO V^T, dQ = dS K and
    dK = dS^T Q. The scores a flash backward recomputes are not counted."""
    return 6 if train else 2


def causal_attention_flops(rows, heads, seq, head_dim, train):
    """Each product is 2 * seq^2 * head_dim operations a head over the
    whole square; a causal row needs the lower triangle, half of it."""
    square = 2 * seq * seq * head_dim
    return rows * heads * attention_products(train) * square // 2


def causal_attention_bytes(rows, heads, seq, head_dim, train, elem=BF16):
    """Forward reads q, k, v and writes o; the backward reads q, k, v, o,
    do and writes dq, dk, dv. The scores never leave the chip."""
    tensor = rows * heads * seq * head_dim * elem
    return tensor * (12 if train else 4)


# ------------------------------------------------------------ expert layer
def expert_products(train):
    """Grouped products a step makes: gate, up, down forward; in training
    the gradient of each to its input rows and to its weights."""
    return 9 if train else 3


def expert_product_flops(rows_routed, hidden, width):
    """One grouped product over the rows really routed (tokens x top_k,
    nothing dropped, nothing padded): every row meets ONE expert."""
    return matmul_flops(rows_routed, hidden, width)


def expert_product_bytes(rows_routed, hidden, width, experts, elem=BF16):
    """The routed rows in, all experts' matrices, the rows out."""
    return (rows_routed * hidden + experts * hidden * width
            + rows_routed * width) * elem


def expert_layer_least_seconds(cfg, tokens, train, peaks):
    """Least time of the grouped products of one expert layer over
    `tokens` tokens: each the larger of its operations and its bytes."""
    rows = tokens * cfg["num_experts_per_tok"]
    one = least_seconds(
        expert_product_flops(rows, cfg["hidden_size"],
                             cfg["intermediate_size"]),
        expert_product_bytes(rows, cfg["hidden_size"],
                             cfg["intermediate_size"], cfg["num_experts"]),
        peaks)
    return expert_products(train) * one


def attention_least_seconds(cfg, rows, seq, train, peaks):
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    return least_seconds(
        causal_attention_flops(rows, heads, seq, d, train),
        causal_attention_bytes(rows, heads, seq, d, train), peaks)


# ------------------------------------------------------------- whole model
def forward_flops_per_token(cfg, seq):
    """Operations one token's forward pass needs, by part (norms, rotary,
    softmax, router top-k and the optimizer are under 1% and left out, so
    a utilization built on this is slightly low, never high)."""
    H, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    layer = {
        "projections": 4 * matmul_flops(1, H, H),
        "attention": causal_attention_flops(1, heads, seq, H // heads,
                                            False) // seq,
        "router": matmul_flops(1, H, cfg["num_experts"]),
        "experts": cfg["num_experts_per_tok"] * 3 * matmul_flops(
            1, H, cfg["intermediate_size"]),
    }
    parts = {k: v * cfg["num_hidden_layers"] for k, v in layer.items()}
    parts["head"] = matmul_flops(1, H, cfg["vocab_size"])
    return parts


def train_flops_per_token(cfg, seq):
    """Forward + backward (every product has two gradients): 3 x forward."""
    return 3 * sum(forward_flops_per_token(cfg, seq).values())
