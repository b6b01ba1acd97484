"""Model FLOP/s utilization of the language model's federated rounds:
the cut's training operations (``bench/lmflops.py``: three forward
passes a token, causal attention, the held experts at the rows the
program routed to them, remat recomputation left out) and its fused
evaluation's forward passes, at the traced window's round rate, over the
chip's bf16 peak. Nothing is returned without the routed-row count."""
from bench import lmflops


def read(m):
    if not m.raw.get("rounds") or m.raw.get("expert_rows") is None:
        return None
    tr = m.traffic
    steps, batch = tr["physical_steps_batch"]
    seqs = m.raw["rounds"] * m.raw["cohort"] * steps * batch
    work = (lmflops.train_flops(m.config, seqs, tr["seq_len"],
                                m.raw["expert_rows"])
            + m.raw["evals"] * lmflops.forward_flops(
                m.config, tr["eval_records"], tr["seq_len"], None))
    return 100.0 * work / m.raw["seconds"] / m.peaks["bf16_flops_per_s"]
