"""Microseconds of engine run per loop tick of the slowest shard in the
four-chip batch window (host clock around each run, loop ticks from the
harts' tick counters)."""


def read(rec):
    if "shards" not in rec or not rec["loop_ticks"]:
        return None
    return 1e6 * rec["engine_s"] / rec["loop_ticks"]
