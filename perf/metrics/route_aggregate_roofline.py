"""Least work of route + aggregate (placement), per chip and window.

Each offered event is read with its meta word and its destination
(4 + 4 + 4 B), and each placed event is written to its destination's
row with its meta word (4 + 4 B).  No arithmetic to speak of: bytes
bound it.
"""


def work(ctx):
    st = ctx["stats"]
    n = st["offered"].size
    return {"flops": 0.0,
            "bytes": (12 * st["offered"].sum() + 8 * st["sent"].sum()) / n}
