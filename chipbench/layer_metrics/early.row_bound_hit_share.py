"""`row_bound_hit_share` under this configuration's key names (the base
reader knows the counts of experts as `num_experts` / `n_routed_experts`):
% of the (step, layer) pairs of the window in which the held experts' rows
fitted the layer's static row bound (`paddle_tpu.ops.lm_ops.row_bound`),
so that the bounded body ran and not the full-size overflow. None where
the program has no such bound to import."""


def read(obs):
    try:
        from paddle_tpu.ops.lm_ops import row_bound
    except ImportError:
        return None
    cfg = obs.get("cfg") or {}
    pairs = [rows for step in obs.get("held_rows_by_layer") or ()
             for rows in step]
    if not pairs or not obs.get("tokens_per_step"):
        return None
    bound = row_bound(
        obs["tokens_per_step"] * cfg["moe_num_active_primary_experts"],
        cfg["moe_num_primary_experts"],
        cfg["deployment"]["moe_num_primary_experts"])
    return 100.0 * sum(rows <= bound for rows in pairs) / len(pairs)
