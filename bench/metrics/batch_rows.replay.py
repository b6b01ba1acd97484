"""Flows per scoring dispatch in the traced window:
``ServeStats.by_bucket`` rows over dispatches."""


def read(m):
    return m.raw.get("batch_rows")
