"""slice_deferred_share's reader on a stubbed tick log, in the pattern of
test_host_wait.py's tick-log tests: the program's log is reached through
the runner's predictor, and a program whose rows lack the columns (the
parent of the PR that added them) gives nothing. And the metric's entry in
BENCHMARK.json, BY NAME."""
import json
import os
import types

import numpy as np
import pytest

from benchmark.layer_metrics import slice_deferred_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PARENT_ROW = np.dtype([(k, np.float64) for k in (
    't0', 'wall_s', 'cpu_s', 'wait_s', 'gc_s', 'dispatches', 'rows',
    'cpu_wall_s', 'tick')])
TICK_ROW = np.dtype(PARENT_ROW.descr + [('slices', np.float64),
                                        ('deferred', np.float64)])
DECODE_CELLS = ['transformer_base_lm.chat_open',
                'transformer_base_lm.batch_closed',
                'olmoe_1b_7b.gen_closed',
                'k_exaone_236b_a23b.longgen_closed',
                'joyai_llm_flash.reason_closed',
                'qwen3_next_80b_a3b.reason_closed',
                'phi4_mini_flash_reasoning.reason_closed']


def _run(rows, dtype=TICK_ROW, log=True):
    """A run whose window is [100, 110) and whose predictor's stats hold
    `rows` — (t0, slices, deferred) — as their tick log."""
    full = np.zeros(len(rows), dtype)
    for k, (t0, slices, deferred) in enumerate(rows):
        full[k]['t0'], full[k]['wall_s'] = t0, 0.03
        if 'slices' in dtype.names:
            full[k]['slices'], full[k]['deferred'] = slices, deferred

    def tick_log(since=None):
        return full.copy() if since is None else full[full['t0'] >= since]
    stats = types.SimpleNamespace()
    if log:
        stats.tick_log = tick_log
    runner = types.SimpleNamespace(served=types.SimpleNamespace(
        pred=types.SimpleNamespace(stats=stats)))
    return {'runner': runner, 'result': {'t_open': 100.0, 'window_s': 10.0}}


# the ramp's ticks, the window's, the traced part's: only the window counts
_ROWS = ([(50.0 + k, 1, 5) for k in range(20)]
         + [(100.0 + 0.03 * k, 0, 0) for k in range(200)]     # steps alone
         + [(106.0 + 0.03 * k, 1, 0) for k in range(40)]      # one admits
         + [(108.0 + 0.03 * k, 1, 1) for k in range(8)]       # two at once
         + [(109.0, 1, 2)]                                    # three
         + [(110.0 + k, 1, 7) for k in range(5)])


def test_the_share_is_waits_over_waits_and_slices_in_the_window():
    assert slice_deferred_share.reduce(_run(_ROWS)) == pytest.approx(
        100.0 * 10 / (10 + 49))


def test_nobody_ever_waited_reads_zero():
    rows = [(t0, slices, 0) for t0, slices, _ in _ROWS]
    assert slice_deferred_share.reduce(_run(rows)) == 0.0


@pytest.mark.parametrize('run', [
    _run(_ROWS, dtype=PARENT_ROW),          # the parent's log: no columns
    _run(_ROWS, log=False),                 # a program without a tick log
    _run([(50.0, 1, 1), (120.0, 1, 1)]),    # no tick in the window
    _run([(100.0 + k, 0, 0) for k in range(5)]),    # no slice in it
    {'runner': None, 'result': {'t_open': 0.0, 'window_s': 1.0}},
], ids=['parent', 'no_log', 'no_tick', 'no_slice', 'no_predictor'])
def test_nothing_to_read_gives_nothing(run):
    assert slice_deferred_share.reduce(run) is None


def test_the_entry_by_name():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    entry, = [m for m in bench['per_layer']
              if m['name'] == 'slice_deferred_share']
    assert entry == {'name': 'slice_deferred_share', 'unit': '%',
                     'better': 'lower', 'source': 'program_counter',
                     'layer': 'Decode scheduler', 'moves': 'itl_p99_ms',
                     'workloads': DECODE_CELLS}
    # every cell it is filed for reports the metric it moves
    itl, = [m for m in bench['end_to_end'] if m['name'] == 'itl_p99_ms']
    assert set(DECODE_CELLS) == set(itl['workloads'])
    assert os.path.exists(os.path.join(
        ROOT, 'benchmark', 'layer_metrics', 'slice_deferred_share.py'))
