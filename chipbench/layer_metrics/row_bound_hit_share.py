"""% of the window's (step, sparse layer) pairs in which the held experts
received no more rows than the layer's row bound, so that the layer moved
the bounded number of rows around its products and not all top_k x tokens
(`paddle_tpu/ops/lm_ops.py: row_bound`, from the cell's shapes; `RowsHeld`
of every sparse layer is fetched each step). 100: the overflow branch ran
in no step of the window. None where the program has no row bound."""


def read(obs):
    by_layer = obs.get("held_rows_by_layer")
    try:
        from paddle_tpu.ops.lm_ops import row_bound
    except ImportError:
        return None
    cfg = obs.get("cfg") or {}
    key = next((k for k in ("num_experts", "n_routed_experts") if k in cfg),
               None)
    pairs = [rows for step in by_layer or () for rows in step]
    if not pairs or key is None or not obs.get("tokens_per_step"):
        return None
    bound = row_bound(obs["tokens_per_step"] * cfg["num_experts_per_tok"],
                      cfg[key], cfg["deployment"][key])
    return 100.0 * sum(rows <= bound for rows in pairs) / len(pairs)
