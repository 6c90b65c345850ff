"""Share of the traced batch window in which no operation ran on the
device, averaged over the chips (profiler trace)."""


def read(rec):
    t = rec.get("trace")
    if t is None or "hart_ticks" not in rec:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
