"""Least work of the wire codec, per chip and window.

Each shipped event's word and meta word are read (4 + 4 B) and its
64-bit wire word written (8 B); each delivered event's wire word is read
and its word and meta word written.  Bit operations only: bytes bound it.
"""


def work(ctx):
    st = ctx["stats"]
    n = st["sent"].size
    return {"flops": 0.0,
            "bytes": 16 * (st["sent"].sum() + st["delivered"].sum()) / n}
