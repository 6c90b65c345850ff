"""Served jobs completed in the window with the reference checksum, over
the window's wall seconds (host clock)."""


def read(rec):
    if "jobs_ok" not in rec:
        return None
    return rec["jobs_ok"] / rec["window_s"]
