"""Set-up seconds: from process start to the window opening (host clock)."""


def read(rec):
    return rec.get("setup_s")
