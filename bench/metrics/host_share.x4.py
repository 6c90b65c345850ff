"""Share of the four-chip batch window spent outside the engine's loop:
each batch's gather onto the mesh, its counter read-back and any layout
the engine did itself (host spans: the window minus ``engine.run`` spans,
plus the ``engine.place`` spans inside them)."""


def read(rec):
    if "shards" not in rec or rec.get("place_s") is None:
        return None
    return 100.0 * (rec["window_s"] - rec["engine_s"] + rec["place_s"]) \
        / rec["window_s"]
