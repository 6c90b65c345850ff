"""The one traffic generator: it reads a mix's data file and the seed.

Every seed gets the same work in another order.  A closed loop of batches
gets the same multiset of jobs in each batch, placed on harts by a
permutation.  A closed loop of served jobs gets the mix's kernels in
blocks, each block the whole list in a seed-drawn order.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int) -> np.random.Generator:
    """Any whole number, negative or past 64 bits, names a stream."""
    return np.random.default_rng(abs(int(seed)))


def batch_orders(mix: dict, seed: int, jobs: int, harts: int):
    """Endless batches: each is the ``jobs`` distinct jobs repeated to
    fill ``harts``, as job indices in a seed-drawn hart order."""
    if mix["kind"] != "closed_batches":
        raise ValueError(f"mix kind {mix['kind']!r} is not closed_batches")
    if harts % jobs:
        raise ValueError(f"{harts} harts do not hold whole copies of "
                         f"{jobs} jobs")
    base = np.tile(np.arange(jobs, dtype=np.int32), harts // jobs)
    r = rng(seed)
    while True:
        yield r.permutation(base)


def closed_jobs(mix: dict, seed: int):
    """Endless kernel names for a closed loop of served jobs: the mix's
    ``kernels`` in blocks, each block the whole list in a seed-drawn
    order, so any stretch of the stream is the paper's matrix of kernels
    to within one block."""
    if mix["kind"] != "closed_jobs":
        raise ValueError(f"mix kind {mix['kind']!r} is not closed_jobs")
    kernels = list(mix["kernels"])
    r = rng(seed)
    while True:
        for i in r.permutation(len(kernels)):
            yield kernels[int(i)]
