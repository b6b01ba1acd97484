"""Share of their roofline the splash-attention kernels of the latent
attention reach: for each kernel (forward in training and in the fused
evaluation, and the two backward kernels) its calls times the least time
one call could take on this chip (``bench/lmflops.py``, the causal pairs
of all heads), summed, over their device time in the trace. Nothing is
returned when the trace holds no such call."""
from bench import flops, lmflops

KINDS = {"mla_fwd_train": "fwd", "mla_fwd_eval": "fwd", "mla_dq": "dq",
         "mla_dkv": "dkv"}


def read(m):
    tr = m.traffic
    batch = {"mla_fwd_eval": tr["eval_records"]}
    least = seconds = 0.0
    for name, kind in KINDS.items():
        k = m.trace["kernels"].get(name)
        if not k or not k["calls"]:
            continue
        cost = lmflops.mla_attention(
            m.config, kind, batch.get(name, tr["physical_steps_batch"][1]),
            tr["seq_len"])
        least += k["calls"] * flops.roofline_seconds(cost, m.peaks)
        seconds += k["seconds"]
    if not seconds:
        return None
    return 100.0 * least / seconds
