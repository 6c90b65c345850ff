"""95th percentile of time-to-result over every job completed in the
window: from its due arrival to the control round that harvested it, in
wall seconds (host clock)."""


def read(rec):
    return rec.get("ttr_p95_s")
