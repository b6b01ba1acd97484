"""Compiled dispatches per federated round in the traced window: the
engine's ``sim.dispatches`` counter over the rounds completed."""


def read(m):
    if not m.raw.get("rounds"):
        return None
    return m.raw["dispatches"] / m.raw["rounds"]
