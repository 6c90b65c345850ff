"""Microseconds of engine run per loop tick executed in the serving
window (host clock around each slice, loop ticks from the tick counters)."""


def read(rec):
    if "jobs_ok" not in rec or not rec["loop_ticks"]:
        return None
    return 1e6 * rec["engine_s"] / rec["loop_ticks"]
