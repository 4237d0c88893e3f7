"""The working-or-waiting readers (benchmark/layer_metrics/_oncpu.py and the
seven metrics over it) on hand-made timelines and a stub runner, in the
pattern of test_step_ahead_share.py: the spans' `cpu_us` comes from the
trace file, which a hand-made run has none of, so the tests stand in for the
file's reader; the tick log comes from the runner's predictor, so a stub
stands in for that."""
import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, trace
from benchmark.layer_metrics import _oncpu

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1000000    # nanoseconds
SCHED, CALLER, OTHER = 0, 1, 2      # threads: lines of the host plane

DECODE_CELLS = ['transformer_base_lm.chat_open',
                'transformer_base_lm.batch_closed',
                'olmoe_1b_7b.gen_closed',
                'k_exaone_236b_a23b.longgen_closed']
# the readers of spans' cpu_us are filed where a 3 s trace holds enough
# steps of a coarse CPU clock for them (_oncpu.resolves): the cells whose
# host work fills the tick, and the executor's 60 ms calls on four chips
HOST_CELLS = DECODE_CELLS[:2]
NEW = {'tick_offcpu_ms': HOST_CELLS, 'dispatch_offcpu_share': HOST_CELLS,
       'sched_offcpu_share': DECODE_CELLS, 'tick_gc_share': DECODE_CELLS,
       'tick_ms_p99': DECODE_CELLS, 'tick_ms_max': DECODE_CELLS,
       'exe_dispatch_offcpu_ms': ['resnet50.train_dp4']}
TICK_ROW = np.dtype([(k, np.float64) for k in (
    't0', 'wall_s', 'cpu_s', 'wait_s', 'gc_s', 'dispatches', 'rows',
    'cpu_wall_s', 'tick')])
NAN = float('nan')


def _reader(name):
    return importlib.import_module('benchmark.layer_metrics.' + name).reduce


def _run(runner=None, t_open=100.0, window_s=10.0):
    host = [(0, 40 * MS, trace.WINDOW_SPAN, 'main')]
    return {'trace': trace.Trace([], host, (0, 40 * MS)), 'runner': runner,
            'ctx': types.SimpleNamespace(tracer=types.SimpleNamespace(
                path=None)),
            'result': {'counters_traced': {'steps': 2, 'chunk_slices': 2},
                       'counters_window': {'busy_s': 1.0},
                       't_open': t_open, 'window_s': window_s,
                       't_close': t_open + window_s + 3.0}}


def _ev(a, b, name, cpu_ms=None, thread=SCHED, **stats):
    """One event from a to b (ms); a program span where cpu_ms is given."""
    if cpu_ms is not None:
        stats['cpu_us'] = cpu_ms * 1e3
    program = name.startswith(_oncpu.PREFIXES)
    return (int(a * MS), int(b * MS), name, thread,
            stats if program else None)


def _tick(t0, cpu=True):
    """One 10 ms tick: a step's dispatch half (feed 1 ms all work; the call
    2 ms, half of it waiting, with the runtime's own events inside), a
    4 ms wait for the device with 0.5 ms of CPU in it, a 0.5 ms copy, 1 ms
    of advance of which 0.75 waiting, a slice with a second call, and
    0.5 ms of the tick's own time, 0.3 of it waiting."""
    def span(a, b, name, cpu_ms, **stats):
        return _ev(t0 + a, t0 + b, name, cpu_ms if cpu else None,
                   **(stats if cpu else {}))
    return [
        span(0, 10, 'decode/tick', 1.0 + 1.0 + 0.5 + 0.25 + 0.25 + 0.75 + 0.2),
        span(0, 3, 'decode/step', 2.0),
        span(0, 1, 'decode/build_feed', 1.0),
        span(1, 3, 'decode/dispatch', 1.0, program='step', feeds=3,
             feed_bytes=1000),
        _ev(t0 + 1.1, t0 + 2.9, 'PjitFunction(jit_call)'),
        _ev(t0 + 1.2, t0 + 1.4, 'ParseArguments'),
        span(3, 7, 'decode/device_wait', 0.5),
        span(7, 7.5, 'decode/d2h', 0.25),
        span(7.5, 8.5, 'decode/advance', 0.25),
        span(8.5, 9.5, 'decode/prefill_slice', 0.75),
        span(8.5, 9.5, 'decode/dispatch', 0.75, program='chunk_32', feeds=5,
             feed_bytes=300),
    ]


def _with_events(monkeypatch, events):
    monkeypatch.setattr(_oncpu, '_read_events',
                        lambda run: sorted(events,
                                           key=lambda x: (x[0], -x[1])))


# -- BENCHMARK.json ----------------------------------------------------------

def test_the_new_metrics_are_the_last_entries_and_have_readers():
    bench = harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    last = bench['per_layer'][-len(NEW):]
    assert [m['name'] for m in last] == list(NEW)
    end_to_end = {m['name']: m for m in bench['end_to_end']}
    for m in last:
        assert m['workloads'] == NEW[m['name']]
        assert m['better'] == 'lower'
        assert set(m['workloads']) <= set(end_to_end[m['moves']]['workloads'])
        assert callable(_reader(m['name']))
    assert {m['layer'] for m in last} == {'Decode scheduler', 'Executor'}


# -- the trace side ----------------------------------------------------------

@pytest.mark.parametrize('name', sorted(NEW))
def test_nothing_to_read_gives_nothing(name):
    """No trace file, no runner: None, and the harness leaves it out."""
    assert _reader(name)(_run()) is None


@pytest.mark.parametrize('name', ['tick_offcpu_ms', 'dispatch_offcpu_share',
                                  'exe_dispatch_offcpu_ms'])
def test_a_program_whose_spans_carry_no_cpu_time_gives_nothing(
        monkeypatch, name):
    """The parent's spans are all there, without the stat."""
    _with_events(monkeypatch, _tick(0, cpu=False) + [
        _ev(20, 25, 'exe/dispatch', thread=OTHER)])
    assert _reader(name)(_run()) is None


def test_tick_table_takes_each_spans_own_time(monkeypatch):
    _with_events(monkeypatch, _tick(0) + _tick(12) + [
        # a caller's span and a collection on another thread: not the tick's
        _ev(1, 2, 'decode/submit', 0.5, thread=CALLER),
        _ev(4, 5, 'py/gc', 1.0, thread=OTHER, generation=0),
        # a tick cut by the window's end is dropped by the file's reader;
        # what is left of it lies under no tick
        _ev(30, 31, 'decode/build_feed', 1.0)])
    table, ticks = _oncpu.tick_table(_run())
    assert ticks == 2
    assert set(table) == {'decode/tick', 'decode/step', 'decode/build_feed',
                          'decode/dispatch', 'decode/device_wait',
                          'decode/d2h', 'decode/advance',
                          'decode/prefill_slice'}
    tick = table['decode/tick']
    assert (tick.n, tick.wall, tick.cpu) == (2, 20 * MS, 2 * 3.95 * MS)
    # its own: 10 - (3 + 4 + 0.5 + 1 + 1) = 0.5 ms, 0.2 of it on the CPU
    assert tick.own_wall == pytest.approx(2 * 0.5 * MS)
    assert tick.own_cpu == pytest.approx(2 * 0.2 * MS)
    # a holder whose children fill it has nothing of its own
    assert table['decode/prefill_slice'].own_wall == 0
    assert table['decode/step'].own_cpu == pytest.approx(0)
    assert table['decode/dispatch'].n == 4
    # the parts add up to the whole, on both clocks
    assert sum(r.own_wall for r in table.values()) == tick.wall
    assert sum(r.own_cpu for r in table.values()) == pytest.approx(tick.cpu)


def test_tick_offcpu_ms_leaves_the_waits_for_the_device_out(monkeypatch,
                                                            capsys):
    """A tick is off the CPU 10 - 3.95 = 6.05 ms; 3.5 of that is the wait
    for the device and 0.25 the copy. The rest, 2.3 ms a tick — the call
    1 + 0.25, the advance 0.75, the tick's own 0.3 — over the interval's 4
    dispatches. Beside it: the table, and how good the reading is — the
    CPU clock was seen to move by 0.25 ms (the smallest cpu_us), so the
    6.4 ms of CPU outside the waits are 25.6 steps: good to root(25.6) x
    0.25 = 1.26 ms of the 11 ms they are taken from."""
    _with_events(monkeypatch, _tick(0) + _tick(12))
    assert _reader('tick_offcpu_ms')(_run()) == pytest.approx(
        2 * (6.05 - 3.5 - 0.25) / 4)
    said = capsys.readouterr().out
    assert 'sum of own wall 10.000 against decode/tick 10.000' in said
    assert 'tick_offcpu_ms: a reading clock_step_us=250 steps=25.6 ' \
        'wall_ms=11 plus_minus_points=11.5 ' in said


def test_dispatch_offcpu_share_and_what_is_inside_the_call(monkeypatch,
                                                           capsys):
    _with_events(monkeypatch, _tick(0) + _tick(12) + _tick(24))
    # per tick: the step's call 2 ms with 1 waiting, the slice's 1 with 0.25
    assert _reader('dispatch_offcpu_share')(_run()) == pytest.approx(
        100 * 1.25 / 3)
    said = capsys.readouterr().out
    assert 'PjitFunction' in said and 'ParseArguments' in said
    assert 'chunk_32' in said and 'feed_bytes=300' in said


def _calls(n, wall_ms, every):
    """n executor calls of wall_ms; a CPU clock that moves in 10 ms steps
    books a whole step on one call in `every`."""
    return [_ev(0, 1, 'exe/run', 0.0, thread=OTHER)] + [
        _ev(100 * k, 100 * k + wall_ms, 'exe/dispatch',
            10.0 if k % every == 0 else 0.0, thread=OTHER) for k in range(n)]


def test_exe_dispatch_offcpu_is_the_mean_call_less_its_mean_cpu_time(
        monkeypatch):
    """One call in three books a 10 ms step: the calls' mean is still their
    CPU time — 60 ms calls, 3.33 of them on the CPU. Five steps against
    900 ms of calls are good to root(5) x 10 = 22 ms: 2.5 points."""
    _with_events(monkeypatch, _calls(15, 60, 3))
    assert _reader('exe_dispatch_offcpu_ms')(_run()) == pytest.approx(
        60 - 10 / 3)


@pytest.mark.parametrize('name,events', [
    # 24 calls of 4 ms that hold 6 steps of a 10 ms clock: 24 ms of error
    # in 96 ms of calls
    ('exe_dispatch_offcpu_ms', _calls(24, 4, 4)),
    # two ticks whose every span read 0 but one, which read a whole step
    ('tick_offcpu_ms', [
        e if e[2] != 'decode/tick' else e[:4] + (dict(e[4], cpu_us=1e4),)
        for e in _tick(0) + _tick(12)]),
    ('dispatch_offcpu_share', [
        e if e[2] != 'decode/dispatch' else e[:4] + (dict(e[4], cpu_us=1e4),)
        for e in _tick(0)[:4]]),
])
def test_a_cpu_clock_too_coarse_for_the_spans_files_nothing(
        monkeypatch, capsys, name, events):
    """Where root(steps) x step passes MAX_SIGMA of the wall time the CPU
    sum is taken from, the reader says so and returns None."""
    _with_events(monkeypatch, events)
    assert _reader(name)(_run()) is None
    assert '%s: NOT FILED, the CPU clock is too coarse' % name \
        in capsys.readouterr().out


def test_no_span_saw_the_clock_move(monkeypatch, capsys):
    _with_events(monkeypatch, [_ev(0, 5, 'exe/dispatch', 0.0, thread=OTHER)])
    assert _reader('exe_dispatch_offcpu_ms')(_run()) is None
    assert 'no span saw the CPU clock move' in capsys.readouterr().out


# -- the tick log ------------------------------------------------------------

def _stub(rows, log=True, dtype=TICK_ROW):
    """A runner whose predictor's stats hold `rows` as their tick log (or,
    log=False, no tick log at all: the parent)."""
    rows = np.array(rows, dtype)

    def tick_log(since=None):
        return rows.copy() if since is None else rows[rows['t0'] >= since]
    stats = types.SimpleNamespace(busy_s=1.0)
    if log:
        stats.tick_log = tick_log
    return types.SimpleNamespace(served=types.SimpleNamespace(
        pred=types.SimpleNamespace(stats=stats)))


_LOG = ([(99.0, 0.5, 0.5, 0, 0, 1, 8, 0.5, 0)]  # the ramp's: before t_open
        # 1999 ordinary ticks of 10 ms, 1 of them waiting for the device;
        # every third carries a reading of the CPU clock: 18 ms of CPU
        # over the 30 ms of busy time it spans
        + [(100.0 + 0.005 * k, 0.010, 0.018 if k % 3 == 2 else NAN, 0.001,
            0, 3, 100, 0.030 if k % 3 == 2 else NAN, 1 + k)
           for k in range(1999)]
        # and a 100 ms tick during which the process was not running: its
        # reading spans the tick before it too
        + [(102.5, 0.100, 0.008, 0, 0.001, 1, 100, 0.110, 2000)]
        # the traced part of the window, and what came after it
        + [(110.0, 0.5, 0, 0.5, 0, 1, 1, 0.5, 2001),
           (200.0, 0.9, 0.9, 0, 0.9, 1, 1, 0.9, 2002)])

LOG_READERS = ('sched_offcpu_share', 'tick_gc_share', 'tick_ms_p99',
               'tick_ms_max')


@pytest.mark.parametrize('name', LOG_READERS)
def test_a_program_without_a_tick_log_gives_nothing(name):
    assert _reader(name)(_run(_stub(_LOG, log=False))) is None
    # a train cell's runner has no predictor at all
    assert _reader(name)(_run(types.SimpleNamespace())) is None
    # nor does a window that held no tick have anything to read
    assert _reader(name)(_run(_stub(_LOG), t_open=300.0)) is None


def test_the_log_is_read_over_the_rate_part_of_the_window_only():
    rows = _oncpu.window_ticks(_run(_stub(_LOG)))
    assert len(rows) == 2000
    assert rows['t0'].min() == 100.0 and rows['t0'].max() < 110.0


def test_the_tick_log_readers_arithmetic(capsys):
    run = _run(_stub(_LOG))
    wall = 1999 * 0.010 + 0.100
    # 666 readings of 18 ms over 30 and the long tick's 8 over 110; the
    # wait is 1 ms of every ordinary tick
    on = (666 * 0.018 + 0.008) / (666 * 0.030 + 0.110)
    assert _reader('sched_offcpu_share')(run) == pytest.approx(
        100 * (1 - on - 1999 * 0.001 / wall))
    assert _reader('tick_gc_share')(run) == pytest.approx(100 * 0.001 / wall)
    assert _reader('tick_ms_max')(run) == pytest.approx(100.0)
    # one long tick in 2000 does not reach the 99th percentile
    assert _reader('tick_ms_p99')(run) == pytest.approx(10.0)
    said = capsys.readouterr().out
    assert 'was=not running' in said and 'ticks=2000' in said
    assert 'readings=667' in said and 'was=no reading' in said
    assert 'the mean tick at or above p99 ticks=2000' in said


def test_rows_without_a_reading_give_the_share_nothing():
    run = _run(_stub([(100.0 + k, 0.01, NAN, 0, 0, 1, 1, NAN, k)
                      for k in range(200)]))
    assert _reader('sched_offcpu_share')(run) is None
    assert _reader('tick_gc_share')(run) == 0


def test_too_few_ticks_for_a_p99_give_nothing():
    run = _run(_stub([(100.0 + k, 0.01, 0.01, 0, 0, 1, 1, 0.01, k)
                      for k in range(8)]))
    assert _reader('tick_ms_p99')(run) is None
    assert _reader('tick_ms_max')(run) == pytest.approx(10.0)


@pytest.mark.parametrize('row,was', [
    ((0, 0.100, 0.002, 0.000, 0.095, 1, 1, 0.110, 1), 'collector'),
    ((0, 0.100, 0.003, 0.095, 0.000, 1, 1, 0.110, 1), 'device/runtime'),
    ((0, 0.100, 0.090, 0.000, 0.000, 9, 1, 0.110, 1), 'working'),
    ((0, 0.100, 0.012, 0.000, 0.000, 1, 1, 0.110, 1), 'not running'),
    ((0, 0.015, NAN, 0.000, 0.000, 1, 1, NAN, 1), 'no reading'),
])
def test_a_long_tick_is_named_from_its_row(row, was):
    assert _oncpu.name_of(np.array([row], TICK_ROW)[0]) == was


def test_traced_ticks_are_matched_to_their_rows(monkeypatch, capsys):
    """A row carries its tick's number and so does the tick's span: the
    traced ticks are joined to their rows on it. A tick that found nothing
    to do has a span and no row."""
    log = [(111.0 + 0.01 * k, 0.007, 0.004, 0.001, 0, 3, 100, 0.007,
            3000 + k) for k in range(40) if k != 15]
    _with_events(monkeypatch, [
        _ev(10 * k + 1, 10 * k + 8.01, 'decode/tick', 4.0, tick=3000 + k)
        for k in range(10, 30)])
    _oncpu.say_log_against_trace(_run(_stub(_LOG[:-2] + log)))
    said = capsys.readouterr().out
    assert 'spans=20 rows=19' in said
    assert 'row_wall_s=0.133' in said and 'row_cpu_s=0.076' in said
    # a log without the column (a program between the two): nothing said
    _oncpu.say_log_against_trace(_run(_stub(
        [r[:8] for r in log], dtype=np.dtype(TICK_ROW.descr[:8]))))
    assert capsys.readouterr().out == ''
