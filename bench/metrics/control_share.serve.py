"""Share of the serving window spent in control rounds outside their
engine run: harvest, recover, place, snapshot (host spans)."""


def read(rec):
    if "control_s" not in rec:
        return None
    return 100.0 * rec["control_s"] / rec["window_s"]
