"""slices_per_chunk_dispatch's reader on a hand-made trace.Trace, in the
pattern of test_feed_touched_share.py: the stats of the program's
'decode/dispatch' spans come from the trace file, which a hand-made run has
none of, so the test stands in for the file's reader."""
import types

import pytest

from benchmark import trace
from benchmark.layer_metrics import _spans, slices_per_chunk_dispatch

MS = 1000000    # nanoseconds


def _run():
    host = [(0, 40 * MS, trace.WINDOW_SPAN, 'main')]
    t = trace.Trace([], host, (0, 40 * MS))
    return {'trace': t, 'runner': None,
            'ctx': types.SimpleNamespace(tracer=types.SimpleNamespace(
                path=None)),
            'result': {'counters_traced': {'steps': 4, 'chunk_slices': 9}}}


def _with_dispatches(monkeypatch, calls):
    """`calls`: the stats of the decode/dispatch spans of the window, in
    time order, as _spans._read_span_stats would find them in a trace
    file."""
    monkeypatch.setattr(
        _spans, '_read_span_stats',
        lambda run: {'decode/dispatch': [(k * MS, dict(st))
                                         for k, st in enumerate(calls)],
                     'decode/tick': [(0, {'tick': 1})]})


def test_no_trace_file_gives_nothing():
    assert slices_per_chunk_dispatch.reduce(_run()) is None


def test_a_program_without_the_stat_gives_nothing(monkeypatch):
    """The parent's decode/dispatch spans say which program and what was
    handed over, never how many rows: nothing to read, and the harness
    leaves the metric out."""
    _with_dispatches(monkeypatch, [
        {'program': 'step', 'feeds': 3, 'feed_bytes': 67072},
        {'program': 'chunk_128', 'feeds': 5, 'feed_bytes': 9228},
        {'program': 'chunk_32', 'feeds': 5, 'feed_bytes': 8460}] * 3)
    assert slices_per_chunk_dispatch.reduce(_run()) is None


def test_an_interval_without_a_slice_gives_nothing(monkeypatch):
    _with_dispatches(monkeypatch, [{'program': 'step'}] * 5)
    assert slices_per_chunk_dispatch.reduce(_run()) is None


def test_the_ratio_is_real_rows_over_chunk_calls(monkeypatch):
    """A tick's step, then its slices: three requests' in one call of the
    row program, one alone through its bucket's program, six in two calls.
    A step's call counts for nothing, with the stat or without."""
    step = {'program': 'step', 'feeds': 3}
    _with_dispatches(monkeypatch, [
        step, {'program': 'chunk_128x4', 'rows': 3},
        step, {'program': 'chunk_32', 'rows': 1},
        dict(step, rows=125),
        {'program': 'chunk_128x4', 'rows': 4},
        {'program': 'chunk_128x4', 'rows': 2},
        {'program': 'blockcopy'}, {'program': 'verify'}])
    assert slices_per_chunk_dispatch.reduce(_run()) == pytest.approx(
        (3 + 1 + 4 + 2) / 4)
    _with_dispatches(monkeypatch,
                     [step, {'program': 'chunk_512', 'rows': 1}] * 4)
    assert slices_per_chunk_dispatch.reduce(_run()) == 1.0
