"""moe.load_imbalance: how much more the busiest expert is given than the
mean, summed over the window's MoE layer-steps: the summed rows of each
layer-step's busiest expert (``moe_max_load``) over the summed mean rows an
expert gets (``moe_rows`` / routed experts).  1 is an even load; the grouped
matmul's time follows the busiest expert where rows are many.  From the
serving engine's MoE counters, as deltas over the window."""


def read(ctx):
    rec = ctx["rec"]
    if not rec.get("moe_rows"):
        return None
    return (rec["moe_max_load"] * ctx["cfg"]["n_routed_experts"]
            / rec["moe_rows"])
