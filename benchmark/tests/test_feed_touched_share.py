"""feed_touched_share's reader on a hand-made trace.Trace, in the pattern of
test_step_ahead_share.py: the stats of the program's 'decode/build_feed'
spans come from the trace file, which a hand-made run has none of, so the
test stands in for the file's reader."""
import types

import pytest

from benchmark import trace
from benchmark.layer_metrics import _spans, feed_touched_share

MS = 1000000    # nanoseconds


def _run():
    host = [(0, 40 * MS, trace.WINDOW_SPAN, 'main')]
    t = trace.Trace([], host, (0, 40 * MS))
    return {'trace': t, 'runner': None,
            'ctx': types.SimpleNamespace(tracer=types.SimpleNamespace(
                path=None)),
            'result': {'counters_traced': {'steps': 4, 'chunk_slices': 0}}}


def _with_feeds(monkeypatch, feeds):
    """`feeds`: the stats of the decode/build_feed spans of the window, in
    time order, as _spans._read_span_stats would find them in a trace
    file."""
    monkeypatch.setattr(
        _spans, '_read_span_stats',
        lambda run: {'decode/build_feed': [(k * MS, dict(st))
                                           for k, st in enumerate(feeds)],
                     'decode/tick': [(0, {'tick': 1})]})


def test_no_trace_file_gives_nothing():
    assert feed_touched_share.reduce(_run()) is None


def test_a_program_without_the_stat_gives_nothing(monkeypatch):
    """The parent's decode/build_feed spans carry no stats at all: nothing
    to read, and the harness leaves the metric out."""
    _with_feeds(monkeypatch, [{}] * 6)
    assert feed_touched_share.reduce(_run()) is None


def test_a_window_with_no_live_row_gives_nothing(monkeypatch):
    _with_feeds(monkeypatch, [{}, {'active': 0, 'touched': 0}] * 3)
    assert feed_touched_share.reduce(_run()) is None


def test_the_share_is_rows_rewritten_over_rows_live(monkeypatch):
    """Each step opens the span twice — draft collection (no stats), then
    the feed (`active`, `touched`): 125 rows live a step, eight of them at
    a block boundary, one more in the step a request joins."""
    draft = {}
    _with_feeds(monkeypatch, [draft, {'active': 125, 'touched': 8},
                              draft, {'active': 125, 'touched': 9},
                              draft, {'active': 0, 'touched': 0}])
    assert feed_touched_share.reduce(_run()) == pytest.approx(
        100.0 * 17 / 250)
    _with_feeds(monkeypatch, [{'active': 4, 'touched': 4}] * 5)  # a beam
    assert feed_touched_share.reduce(_run()) == pytest.approx(100.0)
    _with_feeds(monkeypatch, [{'active': 3, 'touched': 0}] * 5)
    assert feed_touched_share.reduce(_run()) == 0.0
