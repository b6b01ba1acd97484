"""Share of the traced window in which no operation ran on the device."""


def read(m):
    return 100.0 * (1.0 - m.trace["busy_s"] / m.trace["window_s"])
