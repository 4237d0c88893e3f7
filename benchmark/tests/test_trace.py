"""(a) The trace reduction: interval arithmetic on hand-made timelines, and
the recorded v5e fixture against numbers checked by hand."""
import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, 'fixture_v5e.xplane.pb')
MS = 1000000    # nanoseconds


def test_union_subtract_total():
    u = trace.union([(5, 9), (0, 3), (2, 4), (9, 9), (20, 30)])
    assert u == [(0, 4), (5, 9), (20, 30)]
    assert trace.total(u) == 18
    assert trace.subtract([(0, 40)], u) == [(4, 5), (9, 20), (30, 40)]
    assert trace.subtract(u, [(0, 40)]) == []
    assert trace.clip(u, 3, 25) == [(3, 4), (5, 9), (20, 25)]


def _device():
    # two dispatches of 'step' (10 ms and 12 ms spans) and one of 'slice';
    # the second step waits 2 ms inside an all-reduce with nothing running,
    # and 1 ms of another all-reduce is hidden behind a fusion
    ops = [(0, 4 * MS, 'fusion.1'), (4 * MS, 10 * MS, 'convolution.2'),
           (20 * MS, 26 * MS, 'fusion.1'),
           (25 * MS, 26 * MS, 'all-reduce-start.1'),
           (26 * MS, 28 * MS, 'all-reduce-done.1'),
           (28 * MS, 32 * MS, 'convolution.2'),
           (40 * MS, 41 * MS, 'fusion.9')]
    modules = [(0, 10 * MS, 'jit_call(1)'), (20 * MS, 32 * MS, 'jit_call(1)'),
               (40 * MS, 41 * MS, 'jit_call(2)')]
    return trace.Device('/device:TPU:0', ops, modules)


def test_busy_gaps_programs_and_collectives_on_a_hand_made_timeline():
    d = _device()
    lo, hi = 0, 50 * MS
    assert trace.busy_seconds(d, lo, hi) == pytest.approx(0.023)
    assert trace.idle_gaps(d, lo, hi) == [(10 * MS, 20 * MS),
                                          (32 * MS, 40 * MS),
                                          (41 * MS, 50 * MS)]
    progs = trace.program_times(d, lo, hi)
    assert progs['jit_call(1)'] == pytest.approx([0.010, 0.012])
    assert progs['jit_call(2)'] == pytest.approx([0.001])
    name, times = trace.main_program(d, lo, hi)
    assert name == 'jit_call(1)' and len(times) == 2
    # a dispatch that straddles the window's edge is left out
    assert 'jit_call(2)' not in trace.program_times(d, lo, 40 * MS + 1)
    assert trace.collective_exposed_seconds(d, lo, hi) == pytest.approx(0.002)


def test_idle_is_labelled_by_the_innermost_benchmark_span():
    d = _device()
    host = [(0, 50 * MS, trace.WINDOW_SPAN, 'python3'),
            (9 * MS, 19 * MS, 'bench/sync', 'python3'),
            (12 * MS, 13 * MS, 'np.asarray(jax.Array)', 'python3'),
            (33 * MS, 39 * MS, 'SomeRuntimeCall', 'worker/7')]
    t = trace.Trace([d], sorted(host), (0, 50 * MS))
    got = dict(trace.idle_by_host_activity(t))
    assert got['bench/sync'] == pytest.approx(0.010)
    assert got['unattributed:worker/7/SomeRuntimeCall'] == pytest.approx(0.008)
    assert got['unattributed'] == pytest.approx(0.009)
    assert trace.top_ops(t, 2) == [['fusion.1', pytest.approx(0.010)],
                                   ['convolution.2', pytest.approx(0.010)]]
    assert trace.mean_busy_seconds(t) == pytest.approx(0.023)
    assert trace.window_seconds(t) == pytest.approx(0.050)


def test_short_op_drops_layouts_and_operands():
    text = ('%fusion.2 = f32[16384,16,512]{2,1,0:T(8,128)} '
            'fusion(f32[16385,16,512]{2,1,0:T(8,128)} %p), kind=kLoop')
    assert trace.short_op(text) == '%fusion.2 = f32[16384,16,512] fusion'
    assert trace.short_op('convolution.45') == 'convolution.45'


# -- the recorded v5e trace (benchmark/tests/record_fixture.py) -------------
# Read by hand from a dump of the file's events (PR 22): the window span
# 'bench/traced_window' runs from 48,270,109 ns for 20,097,690 ns. Three
# dispatches of jit_fixture_step(13195243457901153647), 26 operations each,
# start at 47,197,249 / 54,238,630 / 60,767,006 ns. The first lies BEFORE
# the window although the host dispatched it inside: the device's clock
# runs about 1.2 ms ahead of the host's in this trace (each program shows
# on the device 1.1 ms before the host span that launched it begins), so
# only two dispatches fall inside. Their operations add up to 280,067 and
# 280,018 ns.

@pytest.fixture(scope='module')
def recorded():
    return trace.load(FIXTURE)


def test_fixture_planes_and_window(recorded):
    assert len(recorded.devices) == 1
    dev = recorded.devices[0]
    assert dev.name == '/device:TPU:0'
    assert len(dev.modules) == 3 and len(dev.ops) == 78
    assert recorded.window == (48270109, 48270109 + 20097690)
    assert trace.window_seconds(recorded) == pytest.approx(0.02009769)
    spans = [n for _, _, n, _ in recorded.host if n.startswith('bench/')]
    assert spans.count('bench/exe_run') == 3
    assert spans.count('bench/sync') == 3


def test_fixture_busy_share_and_program_time(recorded):
    lo, hi = recorded.window
    dev = recorded.devices[0]
    assert trace.mean_busy_seconds(recorded) == pytest.approx(
        560085e-9, rel=1e-9)
    share = trace.mean_busy_seconds(recorded) / trace.window_seconds(recorded)
    assert share == pytest.approx(0.027868, rel=1e-4)
    name, times = trace.main_program(dev, lo, hi)
    assert name == 'jit_fixture_step(13195243457901153647)'
    assert times == pytest.approx([280067e-9, 280018e-9], rel=1e-9)
    assert trace.collective_exposed_seconds(dev, lo, hi) == 0.0


def test_fixture_gap_list(recorded):
    lo, hi = recorded.window
    gaps = [(s - lo, e - lo)
            for s, e in trace.idle_gaps(recorded.devices[0], lo, hi)
            if e - s > 100000]
    assert gaps == [(0, 5968527), (6248634, 12496903), (12776948, 20097690)]
    # the host slept through each gap's middle: no span or event is open
    assert trace.idle_by_host_activity(recorded) == [
        ['unattributed', pytest.approx((20097690 - 560085) * 1e-9)]]
    top = trace.top_ops(recorded, 1)[0]
    assert top[0] == '%fusion = bf16[1024,1024] fusion'
    assert top[1] == pytest.approx(25287e-9, rel=1e-3)
