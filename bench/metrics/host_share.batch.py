"""Share of the batch window spent outside engine runs: booting each batch
and reading its counters back (host spans around ``engine.run``)."""


def read(rec):
    if "hart_ticks" not in rec:
        return None
    return 100.0 * (rec["window_s"] - rec["engine_s"]) / rec["window_s"]
