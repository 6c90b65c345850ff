"""The shared run loop's exit and tick budget (``engine._run_loop``).

The loop checks ``all(done)`` every ``engine._block(chunk, ips)`` ticks,
while the budget stays whole chunks.  Harts that finish at staggered
ticks must give the same state whatever the chunk and ``ips``, equal to
the oracle's in every field; the loop must stop within one block of the
last hart's done; and a hart that never finishes must still advance
exactly ``n_chunks * chunk`` ticks.
"""
import numpy as np
import pytest

from repro.core.hext import engine, programs
from repro.core.hext.sim import Fleet

MEM_WORDS = 256
COUNTS = (3, 40, 333, 1200)        # loop iterations of each hart: 2 ticks each


def _countdown(n: int, val: int):
    """Count ``n`` down to 0, report ``val`` through the done mailbox,
    then spin."""
    a = programs.Asm(0)
    a.li('t0', n)
    a.label('loop')
    a.addi('t0', 't0', -1)
    a.bnez('t0', 'loop')
    a.li('a0', val)
    a.li('t6', programs.MMIO_DONE)
    a.sd('a0', 0, 't6')
    a.label('spin')
    a.j('spin')
    im = programs.Image(MEM_WORDS)
    im.place_code(0, a.assemble())
    return im.mem


def _spin():
    """A ``j .`` loop from the first instruction: never done."""
    a = programs.Asm(0)
    a.label('spin')
    a.j('spin')
    im = programs.Image(MEM_WORDS)
    im.place_code(0, a.assemble())
    return im.mem


STAGGERED = [_countdown(n, 100 + i) for i, n in enumerate(COUNTS)]
BUDGET = 8192                       # every countdown hart ends well inside


@pytest.fixture(scope="module")
def oracle_arrays():
    fleet = Fleet.from_images(STAGGERED, mem_words=MEM_WORDS,
                              engine="oracle").run(BUDGET, chunk=BUDGET)
    return engine.state_arrays(fleet.harts.unwrap())


@pytest.mark.parametrize("chunk,ips,block", [
    (4096, 1, 64), (64, 1, 64), (4096, 2, 128)],
    ids=["chunk4096", "chunk64", "chunk4096-ips2"])
def test_run_loop_exits_within_a_block_on_the_whole_chunk_budget(
        oracle_arrays, chunk, ips, block):
    assert engine._block(chunk, ips) == block

    eng = engine.JitEngine(instrs_per_step=ips)
    fleet = Fleet.from_images(STAGGERED, mem_words=MEM_WORDS,
                              engine=eng).run(BUDGET, chunk=chunk)
    got = engine.state_arrays(fleet.harts.unwrap())
    assert [k for k in got if not np.array_equal(got[k], oracle_arrays[k])] \
        == []
    assert got["done"].all()
    assert list(got["exit_code"]) == [100 + i for i in range(len(COUNTS))]
    # the loop stops in the block where the last hart finished
    longest = int(got["ticks"].max())
    assert eng.stats["loop_ticks"] == -(-longest // block) * block
    if chunk > block:
        assert eng.stats["loop_ticks"] < -(-longest // chunk) * chunk

    # never done: a budget inside the second chunk's last block still
    # runs that chunk whole
    budget = 2 * chunk - block + 1
    spun = Fleet.from_images([_spin()] * len(COUNTS), mem_words=MEM_WORDS,
                             engine=eng).run(budget, chunk=chunk)
    whole = engine._n_chunks(budget, chunk) * chunk
    assert whole == 2 * chunk
    ticks = engine.state_arrays(spun.harts.unwrap())["ticks"]
    assert list(ticks) == [whole] * len(COUNTS)
    assert eng.stats["loop_ticks"] == -(-longest // block) * block + whole


@pytest.mark.parametrize("chunk,ips,block", [
    (8192, 1, 64), (512, 1, 64), (64, 1, 64), (96, 1, 32), (8, 1, 8),
    (4096, 2, 128), (12, 3, 12), (6, 2, 2)])
def test_block_divides_the_chunk_and_is_a_multiple_of_ips(chunk, ips, block):
    assert engine._block(chunk, ips) == block
    assert chunk % block == 0 and block % ips == 0


def test_budget_of_more_blocks_than_the_counter_holds_still_exits(
        oracle_arrays):
    """``2**40`` ticks in chunks of 4096 is ``2**28`` chunks, ``2**34``
    blocks: past the loop's int32 counter, which the exit test must not
    multiply out."""
    eng = engine.JitEngine()
    fleet = Fleet.from_images(STAGGERED, mem_words=MEM_WORDS,
                              engine=eng).run(1 << 40, chunk=4096)
    got = engine.state_arrays(fleet.harts.unwrap())
    assert [k for k in got if not np.array_equal(got[k], oracle_arrays[k])] \
        == []
    assert eng.stats["loop_ticks"] == -(-int(got["ticks"].max()) // 64) * 64
