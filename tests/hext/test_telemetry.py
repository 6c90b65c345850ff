"""Host spans (``telemetry``) and the tick pipeline's stage scopes."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.hext import engine, telemetry
from repro.core.hext.bits import x64
from repro.core.hext.sim import HartState

STAGE_SCOPES = ("timers", "fetch", "walk", "decode", "execute", "system",
                "retire", "trap")


@pytest.fixture
def recorder():
    rec = telemetry.Recorder()
    telemetry.install(rec)
    try:
        yield rec
    finally:
        telemetry.install(None)


def test_span_without_a_sink_is_the_shared_noop():
    rec = telemetry.Recorder()              # made, never installed
    telemetry.install(None)
    first, second = telemetry.span("a"), telemetry.span("b")
    assert first is second
    with first:
        with second:
            pass
    assert rec.items == []


def test_recorder_keeps_nested_spans_and_dumps_them(recorder, tmp_path):
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
    with telemetry.span("outer"):
        pass
    (inner, o1, o2) = recorder.items       # appended as each span closes
    assert [inner[0], o1[0], o2[0]] == ["inner", "outer", "outer"]
    assert o1[1] <= inner[1] <= inner[2] <= o1[2] <= o2[1] <= o2[2]
    assert recorder.total("outer", o1[1], o2[1] + 1) == pytest.approx(
        (o1[2] - o1[1]) + (o2[2] - o2[1]))
    assert recorder.total("outer", o1[1], o2[1]) == pytest.approx(
        o1[2] - o1[1])
    path = tmp_path / "spans.json"
    recorder.dump(str(path))
    assert json.loads(path.read_text()) == [list(it) for it in
                                            recorder.items]


def test_stage_scopes_name_the_ops_of_the_run_loop():
    """Each stage's ``named_scope`` reaches the op metadata of the lowered
    run loop (``engine._run_impl``) on a 2-hart state."""
    st = HartState.fresh(1024)
    with x64():
        two = jax.tree.map(lambda x: jnp.stack([x, x]), st)
        text = engine._run_jit_donating.lower(
            two, jnp.int32(1), 8, 1).as_text(debug_info=True)
    parts = {p for name in re.findall(r'loc\("([^"]*)"', text)
             for p in name.split("/")}
    assert set(STAGE_SCOPES) <= parts
