"""The program's span recorder: it keeps nothing with the profiler off and
no recording scope open, keeps name, ids, parent and arguments when on,
and holds at most its bound."""

from __future__ import annotations

import jax
import pytest

from repro import spans


@pytest.fixture(autouse=True)
def _empty():
    spans.clear()
    yield
    spans.clear()


def test_nothing_is_kept_with_the_profiler_off():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with spans.span("serve.emit") as s:
        s.args["syncs"] = 3
    assert spans.recorded() == []


def test_a_recording_scope_keeps_name_ids_parent_and_args():
    with spans.recording():
        with spans.span("serve.generate") as outer:
            with spans.span("serve.emit") as inner:
                inner.args["syncs"] = 3
            with spans.span("serve.dispatch"):
                pass
    with spans.span("serve.emit"):           # the scope is closed
        pass
    emit, dispatch, generate = spans.recorded()
    assert [emit.name, dispatch.name, generate.name] == [
        "serve.emit", "serve.dispatch", "serve.generate"]
    assert generate.parent_id is None
    assert emit.parent_id == dispatch.parent_id == generate.span_id
    assert len({emit.span_id, dispatch.span_id, generate.span_id}) == 3
    assert emit.args == {"syncs": 3} and generate.args == {}
    assert (generate.start_ns <= emit.start_ns <= emit.end_ns
            <= dispatch.start_ns <= dispatch.end_ns <= generate.end_ns)
    assert outer.args is generate.args


def test_the_profiler_alone_turns_keeping_on(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("serve.prefill"):
            pass
    finally:
        jax.profiler.stop_trace()
    with spans.span("serve.prefill"):
        pass
    assert [s.name for s in spans.recorded()] == ["serve.prefill"]


def test_the_buffer_holds_at_most_its_bound():
    with spans.recording():
        for i in range(spans.CAPACITY + 10):
            with spans.span("serve.emit", i=i):
                pass
    kept = spans.recorded()
    assert len(kept) == spans.CAPACITY
    assert kept[0].args == {"i": 10}
    assert kept[-1].args == {"i": spans.CAPACITY + 9}


def test_a_span_left_by_an_exception_is_kept_and_closed():
    with spans.recording():
        with pytest.raises(ValueError):
            with spans.span("serve.generate"):
                raise ValueError
        with spans.span("serve.prefill"):
            pass
    generate, prefill = spans.recorded()
    assert prefill.parent_id is None
