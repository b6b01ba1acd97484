"""Share of its roofline the ``sign_align`` Pallas kernel reaches: the
least time its calls could take on this chip (bench/flops.py, at the
cohort width and the configuration's arena rows) over their device time
in the trace. Nothing is returned when the trace holds no such call."""
from bench import flops


def read(m):
    k = m.trace["kernels"].get("sign_align")
    if not k or not k["calls"] or not m.raw.get("cohort"):
        return None
    cost = flops.sign_align(m.raw["cohort"], m.config["arena_rows"])
    least = k["calls"] * flops.roofline_seconds(cost, m.peaks)
    return 100.0 * least / k["seconds"]
