"""The program's span facility (``repro.trace``): a shared no-op while
off, profiler annotations while on."""

import jax

from repro import trace


def test_span_is_one_shared_noop_while_off():
    assert not trace.enabled()
    a = trace.span("index.query", rows=3)
    assert a is trace.span("trueknn.fetch")
    with a:
        pass


def test_enable_turns_spans_into_profiler_annotations():
    trace.enable(True)
    try:
        assert trace.enabled()
        s = trace.span("index.query", rows=3, search=0)
        assert isinstance(s, jax.profiler.TraceAnnotation)
        with s:
            pass
    finally:
        trace.enable(False)
    assert not trace.enabled()


def test_span_names_carry_a_program_prefix():
    assert all(p.endswith(".") for p in trace.PREFIXES)
    assert "index.query".startswith(trace.PREFIXES)
    assert not "bench.query".startswith(trace.PREFIXES)
