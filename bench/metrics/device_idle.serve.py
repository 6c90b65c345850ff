"""Share of the traced serving window in which no operation ran on the
device (profiler trace)."""


def read(rec):
    t = rec.get("trace")
    if t is None or "jobs_ok" not in rec:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
