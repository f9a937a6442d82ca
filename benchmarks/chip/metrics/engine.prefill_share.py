"""Share of the traced window that the engine's batched suffix prefill
took: the growth of its steady-state prefill timer
(``serve_prefill_seconds_total``, host clock around dispatch and
``block_until_ready``) over the traced steps, over the traced window."""


def read(rec):
    steps, tr = rec.get("steps"), rec.get("trace")
    if not steps or tr is None:
        return None
    t0, t1 = rec["host_window"]
    if t1 is None or t1 <= t0:
        return None
    return 100.0 * sum(s.prefill_seconds for s in steps) / (t1 - t0)
