"""Share of the traced span that the chip spent in collective permutes,
the butterfly TSQR reduce's exchanges (``lax.ppermute``): the device
seconds of the trace's ``collective-permute*`` operations over the traced
span. The trace is read on one chip of the mesh."""

PREFIX = "collective-permute"


def read(rec):
    tr = rec.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    spent = sum(v for k, v in tr.op_seconds.items() if k.startswith(PREFIX))
    if spent <= 0:
        return None
    return 100.0 * spent / tr.window_s
