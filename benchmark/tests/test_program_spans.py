"""The readers of the program's own spans (benchmark/layer_metrics/_spans.py
and the metrics over it) on hand-made timelines in the style of
test_trace.py, on the recorded v5e trace, and end to end under
--rehearsal --trace 1."""
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import trace
from benchmark.layer_metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, 'fixture_v5e.xplane.pb')
MS = 1000000    # nanoseconds
SCHED = 'python3'

NEW_DECODE = ('tick_feed_ms', 'tick_d2h_ms', 'tick_advance_ms',
              'tick_admit_ms', 'step_d2h_bytes', 'idle_attributed_share')
NEW_TRAIN = ('exe_dispatch_ms_p50', 'exe_self_ms_p50')


def _reader(name):
    return importlib.import_module('benchmark.layer_metrics.' + name).reduce


def _tick(t0):
    """One 10 ms scheduler tick starting at t0 (ms): 1 ms of feed building,
    0.5 ms in the jitted call, 6.5 ms waiting for the device, 0.5 ms of
    copy, 1.4 ms of advance (with a finish inside), 0.1 ms of its own."""
    def span(a, b, name):
        return (int((t0 + a) * MS), int((t0 + b) * MS), name, SCHED)
    return [span(0, 10, 'decode/tick'), span(0, 0.05, 'decode/expire'),
            span(0.05, 0.1, 'decode/admit'), span(0.1, 9.9, 'decode/step'),
            span(0.1, 1, 'decode/build_feed'), span(1, 1.5, 'decode/dispatch'),
            span(1.5, 8, 'decode/device_wait'), span(8, 8.5, 'decode/d2h'),
            span(8.5, 9.9, 'decode/advance'), span(8.6, 8.8, 'decode/finish')]


def _decode_run(skew_ms=0.0):
    """Two ticks with 2 ms of scheduler idleness between them (10-12 ms),
    a submit on a caller's thread of the same name, and the step program
    on the device 0.1 ms after each dispatch began, 6.9 ms long; the
    device's clock `skew_ms` off the host's."""
    host = _tick(0) + _tick(12) + [
        (0, 22 * MS, trace.WINDOW_SPAN, 'main'),
        (int(9.5 * MS), int(12.5 * MS), 'decode/submit', SCHED)]

    def prog(a, b):
        return (int((a + skew_ms) * MS), int((b + skew_ms) * MS),
                'jit_decode_step(1)')
    dev = trace.Device('/device:TPU:0', [], [prog(1.1, 8), prog(13.1, 20)])
    t = trace.Trace([dev], sorted(host), (0, 22 * MS))
    return {'trace': t, 'runner': None,
            'ctx': types.SimpleNamespace(tracer=types.SimpleNamespace(
                path=None)),
            'result': {'counters_traced': {'steps': 2, 'chunk_slices': 0,
                                           'busy_s': 0.020}}}


def test_the_four_phases_sum_to_the_ticks_host_share():
    run = _decode_run()
    feed = _reader('tick_feed_ms')(run)
    d2h = _reader('tick_d2h_ms')(run)
    adv = _reader('tick_advance_ms')(run)
    rest = _reader('tick_admit_ms')(run)
    assert feed == pytest.approx(0.9 + 0.5)
    assert d2h == pytest.approx(0.5)
    assert adv == pytest.approx(1.4)
    # expire + admit + the step's and the tick's own time
    assert rest == pytest.approx(0.1 + 0.1)
    # a tick is the phases plus the wait for the device
    assert feed + d2h + adv + rest == pytest.approx(10.0 - 6.5)
    # tick_host_ms subtracts device-BUSY time (6.9 ms), not the wait
    host = _reader('tick_host_ms')(run)
    assert host == pytest.approx(10.0 - 6.9)
    assert feed + d2h + adv + rest - host == pytest.approx(6.9 - 6.5)


def test_innermost_segments_flatten_nested_spans():
    segs = _spans.innermost_segments(_tick(0))
    assert [(a // 10000, b // 10000, n) for a, b, n in segs] == [
        (0, 5, 'decode/expire'), (5, 10, 'decode/admit'),
        (10, 100, 'decode/build_feed'), (100, 150, 'decode/dispatch'),
        (150, 800, 'decode/device_wait'), (800, 850, 'decode/d2h'),
        (850, 860, 'decode/advance'), (860, 880, 'decode/finish'),
        (880, 990, 'decode/advance'), (990, 1000, 'decode/tick')]


def test_idle_is_attributed_on_the_hosts_clock():
    """Idle gaps of the unskewed timeline: 0-1.1 ms (build_feed), 8-13.1 ms
    (middle at 10.55 ms: the scheduler had nothing to do, no span) and
    20-22 ms (advance). A device clock 1.2 ms ahead moves the long gap's
    middle to 9.35 ms — inside the first tick's advance, unless the reader
    moves it back."""
    run = _decode_run()
    named_s, idle_s, by_name, _ = _spans.idle_attribution(run['trace'])
    assert _spans.clock_offset_ns(run['trace']) == 0     # +0.1 ms: latency
    assert idle_s == pytest.approx(0.0082)
    assert by_name == {'decode/build_feed': pytest.approx(0.0011),
                       'decode/advance': pytest.approx(0.0020)}
    assert _reader('idle_attributed_share')(run) == pytest.approx(
        100 * 3.1 / 8.2)

    skewed = _decode_run(skew_ms=-1.2)
    assert _spans.clock_offset_ns(skewed['trace']) == -1100000
    _, _, by_name, off = _spans.idle_attribution(skewed['trace'])
    assert off == -1100000
    assert 'decode/submit' not in by_name       # a caller's thread
    assert by_name['decode/advance'] == pytest.approx(0.0032)   # 18.8-22
    share = _reader('idle_attributed_share')(skewed)
    # the gap before the first program is off the window's edge now
    assert share == pytest.approx(100 * 3.2 / 8.3)


def test_without_the_skew_estimate_the_long_gap_gets_a_wrong_name(
        monkeypatch):
    skewed = _decode_run(skew_ms=-1.2)
    monkeypatch.setattr(_spans, 'clock_offset_ns', lambda *a, **k: 0)
    _, _, by_name, _ = _spans.idle_attribution(skewed['trace'])
    assert by_name == {'decode/advance': pytest.approx(0.0051),
                       'decode/d2h': pytest.approx(0.0032)}


def test_the_recorded_v5e_trace_shows_its_skew():
    """The fixture's programs show on the device 1.1 ms before the host
    span that launched them begins (test_trace.py); its spans are the
    benchmark's own, so the estimator is told their name."""
    t = trace.load(FIXTURE)
    off = _spans.clock_offset_ns(t, dispatch=('bench/exe_run',))
    assert -1300000 < off < -1000000
    assert _spans.clock_offset_ns(t) is None     # no program span in it


def test_op_provenance_of_the_recorded_v5e_trace():
    """An operation's op_name lives in its event METADATA's 'tf_op' stat,
    which ProfileData does not show; the wire-format walk finds it for 24
    of the fixture's 26 distinct operations (copy-start / copy-done have
    none), all of them 'jit(fixture_step)/dot_general:'-rooted fusions."""
    import re
    from benchmark.layer_metrics import (_xplane_meta,
                                         decode_attention_device_share as m)
    prov = _xplane_meta.op_provenance(FIXTURE)
    ops = prov['/device:TPU:0']
    assert len(ops) == 24
    assert all(v.startswith('jit(fixture_step)/') for v in ops.values())
    t = trace.load(FIXTURE)
    assert {n for _, _, n in t.devices[0].ops} >= set(ops)
    share = m.scope_share(t, FIXTURE, re.compile('dot_general'))
    assert 99.9 < share <= 100.0
    # no Fluid attention op made any of it: nothing to read, not 0 % —
    # and the unscoped-gather rule counts only beside a named op
    assert m.scope_share(t, FIXTURE, m.ATTENTION) is None
    assert m.scope_share(t, FIXTURE, m.ATTENTION,
                         also=re.compile('dot_general')) is None
    assert m.UNSCOPED_GATHER.search('gather:')      # as the chip prints it
    assert m.UNSCOPED_GATHER.search('jit(decode_step)/gather:')
    assert m.UNSCOPED_GATHER.search('jit(decode_step)/jit(_take)/gather:')
    assert m.UNSCOPED_GATHER.search(
        'jit(decode_step)/vmap(jit(_take))/gather:')
    assert not m.UNSCOPED_GATHER.search('jit(decode_step)/lookup_table/gather:')
    assert m.ATTENTION.search(
        'jit(decode_step)/kv_block_attention/sht,sthd->shd/dot_general:')
    run = _decode_run()         # no device operations, no trace file
    assert m.reduce(run) is None


def _train_run():
    host = [(0, 300 * MS, trace.WINDOW_SPAN, 'main')]
    for k, (run_ms, dispatch_ms) in enumerate([(10, 7), (12, 8), (30, 9)]):
        s = k * 100 * MS
        host += [(s, s + run_ms * MS, 'exe/run', 'main'),
                 (s + MS, s + (1 + dispatch_ms) * MS, 'exe/dispatch', 'main')]
    # one call straddles the window's end: left out
    host += [(295 * MS, 305 * MS, 'exe/run', 'main'),
             (296 * MS, 304 * MS, 'exe/dispatch', 'main')]
    dev = trace.Device('/device:TPU:0', [], [(5 * MS, 95 * MS, 'jit_s(1)')])
    return {'trace': trace.Trace([dev], sorted(host), (0, 300 * MS))}


def test_exe_run_splits_into_dispatch_and_self():
    run = _train_run()
    assert _spans.exe_runs(run['trace']) == [
        (pytest.approx(0.010), pytest.approx(0.007)),
        (pytest.approx(0.012), pytest.approx(0.008)),
        (pytest.approx(0.030), pytest.approx(0.009))]
    assert _reader('exe_dispatch_ms_p50')(run) == pytest.approx(8.0)
    assert _reader('exe_self_ms_p50')(run) == pytest.approx(4.0)
    # programs queue behind one another in training: no skew to read
    assert _spans.clock_offset_ns(run['trace']) == 0


@pytest.mark.parametrize('name', NEW_DECODE + ('queue_wait_ms_p50',)
                         + NEW_TRAIN)
def test_a_program_without_spans_gives_the_reader_nothing(name):
    """The parent of the PR that added the spans: the same trace with no
    program span in it. The reader returns None; the line leaves the
    metric out."""
    run = _decode_run()
    run['trace'].host = [x for x in run['trace'].host
                         if not x[2].startswith(_spans.PREFIXES)]
    assert _reader(name)(run) is None


def _rehearse(cell):
    env = dict(os.environ)
    env.pop('XLA_FLAGS', None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', cell, '--seed', '5', '--seconds', '3', '--trace', '1',
         '--rehearsal'], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


@pytest.mark.parametrize('cell,names', [
    ('transformer_base_lm.chat_open', NEW_DECODE + ('queue_wait_ms_p50',)),
    ('resnet50.train_1chip', NEW_TRAIN)])
def test_rehearsal_prints_every_new_metric(cell, names):
    line, out = _rehearse(cell)
    assert line['rehearsal_checks_passed'] is True, out[-3000:]
    m = line['metrics']
    assert set(names) <= set(m), sorted(m)
    assert all(m[n]['value'] >= 0 for n in names)
    if 'step_d2h_bytes' in names:
        with open(os.path.join(ROOT, 'benchmark', 'configs',
                               'transformer_base_lm.json')) as f:
            cfg = json.load(f)['rehearsal']
        assert m['step_d2h_bytes']['value'] == \
            cfg['max_slots'] * cfg['vocab'] * 4
        assert 0 <= m['idle_attributed_share']['value'] <= 100
        assert 'host-device clock offset in this trace' in out
        assert 'cost of tracing while on' in out
        # the existing breakdown names the program's spans with no edit
        assert any('decode/' in label
                   for label, _ in line['breakdown']['idle_gaps'])
    else:
        # the executor's call from inside (8 calls of the traced second)
        # and from outside (every call of a 3 s window on a busy cpu):
        # the same thing, loosely here; PERF.md holds the chip to 10 %
        inside = m['exe_dispatch_ms_p50']['value'] \
            + m['exe_self_ms_p50']['value']
        assert inside == pytest.approx(m['exe_call_ms_p50']['value'],
                                       rel=0.5)
