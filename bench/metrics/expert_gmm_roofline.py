"""Share of their roofline the grouped-matmul kernels over the held
experts (megablox ``gmm`` and ``tgmm``) reach: the least time their work
in the traced window could take on this chip (``bench/lmflops.py``, at
the rows the program routed to the held experts, the evaluation's at
their expectation) over their device time in the trace. Nothing is
returned when the trace holds no such call or the rows were not
counted."""
from bench import flops, lmflops


def read(m):
    k = m.trace["kernels"].get("expert_gmm")
    if not k or not k["calls"] or m.raw.get("expert_rows") is None:
        return None
    c, tr = m.config, m.traffic
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    steps, batch = tr["physical_steps_batch"]
    eval_layers = m.raw["evals"] * n_moe
    eval_rows = (eval_layers * tr["eval_records"] * tr["seq_len"]
                 * c["num_experts_per_tok"] * c["n_routed_experts"]
                 / c["router_experts"])
    cost = lmflops.expert_gmm(
        c, m.raw["expert_rows"],
        m.raw["rounds"] * m.raw["cohort"] * steps * n_moe,
        eval_rows, eval_layers)
    return 100.0 * flops.roofline_seconds(cost, m.peaks) / k["seconds"]
