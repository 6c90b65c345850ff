"""Chunk-scans the shards ran over those the slowest shard of each run
set the batch's time by: 100 x the sum of each run's mean over the sum of
each run's maximum (the engine's ``shard_chunks`` counter)."""


def read(rec):
    chunks = rec.get("shard_chunks") if "shards" in rec else None
    if not chunks:
        return None
    attempted = sum(max(c) for c in chunks)
    if not attempted:
        return None
    return 100.0 * sum(sum(c) / len(c) for c in chunks) / attempted
