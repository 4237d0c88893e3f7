"""step_ahead_share's reader on a hand-made trace.Trace, in the pattern of
test_program_spans.py: the stats of the program's 'decode/step' spans come
from the trace file, which a hand-made run has none of, so the test stands
in for the file's reader."""
import types

import pytest

from benchmark import trace
from benchmark.layer_metrics import _spans, step_ahead_share

MS = 1000000    # nanoseconds


def _run():
    host = [(0, 40 * MS, trace.WINDOW_SPAN, 'main')]
    t = trace.Trace([], host, (0, 40 * MS))
    return {'trace': t, 'runner': None,
            'ctx': types.SimpleNamespace(tracer=types.SimpleNamespace(
                path=None)),
            'result': {'counters_traced': {'steps': 4, 'chunk_slices': 0}}}


def _with_steps(monkeypatch, halves):
    """`halves`: the stats of the decode/step spans of the window, in time
    order, as _spans._read_span_stats would find them in a trace file."""
    monkeypatch.setattr(
        _spans, '_read_span_stats',
        lambda run: {'decode/step': [(k * MS, dict(st))
                                     for k, st in enumerate(halves)],
                     'decode/tick': [(0, {'tick': 1})]})


def test_no_trace_file_gives_nothing():
    assert step_ahead_share.reduce(_run()) is None


def test_a_program_without_the_stat_gives_nothing(monkeypatch):
    """The parent's decode/step spans carry `active` only, on both halves:
    nothing to read, and the harness leaves the metric out."""
    _with_steps(monkeypatch, [{'active': 3}] * 6)
    assert step_ahead_share.reduce(_run()) is None


def test_the_share_is_over_the_dispatch_halves_only(monkeypatch):
    """Four steps: the first dispatched with nothing unread, three ahead;
    each has a read half (no `ahead`) a tick later, which is not counted;
    a tick whose rows all wait for their read opens the span with no
    dispatch and no stat."""
    d0, d1 = {'active': 3, 'ahead': 0}, {'active': 3, 'ahead': 1}
    r = {'active': 3}
    _with_steps(monkeypatch, [d0, d1, r, d1, r, d1, r, {'active': 0}, r])
    assert step_ahead_share.reduce(_run()) == pytest.approx(75.0)
    _with_steps(monkeypatch, [d1, r] * 5)
    assert step_ahead_share.reduce(_run()) == pytest.approx(100.0)
    _with_steps(monkeypatch, [d0, r] * 5)       # a beam live, or a drafter
    assert step_ahead_share.reduce(_run()) == 0.0
