"""Simulated ticks retired by all harts of the window's batches, over the
window's wall seconds (host clock)."""


def read(rec):
    if "hart_ticks" not in rec:
        return None
    return rec["hart_ticks"] / rec["window_s"]
