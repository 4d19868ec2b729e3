"""Model layer: the busiest held expert's rows over the mean rows a held
expert and layer, the mean over the traced window's steps, from the
program's counters ``expert_rows_max`` and ``expert_rows``. 1 is perfect
balance. The grouped products' work is their buffer's rows, whatever the
routing, so this says how uneven the routing is, and what an exchange
across chips would have to carry, not what this step pays."""
from benchlib import kernels_lm


def read(ctx):
    rows = kernels_lm.counter_mean(ctx, "expert_rows")
    busiest = kernels_lm.counter_mean(ctx, "expert_rows_max")
    if not rows or busiest is None:
        return None
    c = ctx.config
    slots = (int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"])
             ) * int(c["n_routed_experts"])     # expert layers x held here
    return busiest / (rows / slots)
