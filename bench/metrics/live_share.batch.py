"""Live hart-ticks over harts times loop ticks: the share of the lockstep
loop's work that advanced a hart that had not finished (counters)."""


def read(rec):
    if "hart_ticks" not in rec or not rec["hart_loop_ticks"]:
        return None
    return 100.0 * rec["hart_ticks"] / rec["hart_loop_ticks"]
