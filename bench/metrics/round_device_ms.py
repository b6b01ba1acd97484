"""Device busy time per federated round: the union of the device's op
intervals in the traced window over the rounds completed in it."""


def read(m):
    if not m.raw.get("rounds"):
        return None
    return 1e3 * m.trace["busy_s"] / m.raw["rounds"]
