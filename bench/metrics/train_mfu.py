"""Model FLOP/s utilization of the whole round program: the detector's
training operations (6 per weight per sample, for every cohort slot's
steps × batch) and its evaluation operations (2 per weight per eval
sample, on the rounds that evaluate), at the traced window's round rate,
over the chip's bf16 peak. f32 matmuls at default precision run as bf16
passes on the MXU, so bf16 is the peak they are held to."""
from bench import flops, reference


def read(m):
    if not m.raw.get("rounds"):
        return None
    dims = reference.layer_dims(m.config)
    steps, batch = m.traffic["physical_steps_batch"]
    work = (m.raw["rounds"] * flops.train_flops(
                dims, m.raw["cohort"] * steps * batch)
            + m.raw["evals"] * flops.eval_flops(dims,
                                                m.traffic["eval_records"]))
    return 100.0 * work / m.raw["seconds"] / m.peaks["bf16_flops_per_s"]
