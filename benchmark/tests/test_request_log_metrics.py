"""The readers of the tick log's delivery columns and of the request log
on a synthetic pair of rings, in the pattern of
test_slice_deferred_share.py: both logs are reached through the runner's
predictor, and a program whose rings lack the columns, or that keeps no
request log (the parent of the PR that added them), gives every reader
nothing. And the eight entries in BENCHMARK.json, BY NAME."""
import json
import os
import types

import numpy as np
import pytest

from benchmark.layer_metrics import (
    _requests, emit_gap_ms_p99, gap_p99_prefill_tokens, gap_p99_wait_share,
    request_ttft_p95_ms, slice_read_wait_ms, ttft_p95_prefill_ms,
    ttft_p95_queue_ms, ttft_p95_read_ms)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PARENT_ROW = np.dtype([(k, np.float64) for k in (
    't0', 'wall_s', 'cpu_s', 'wait_s', 'gc_s', 'dispatches', 'rows',
    'cpu_wall_s', 'tick', 'slices', 'deferred')])
TICK_ROW = np.dtype(PARENT_ROW.descr + [(k, np.float64) for k in (
    'emit_t', 'emit_rows', 'wait_step_s', 'wait_slice_s', 'slice_tokens')])
REQUEST_ROW = np.dtype([(k, np.float64) for k in (
    'request', 't_submit', 't_admit', 't_last_slice', 't_first', 't_end',
    'prompt_len', 'prefix_covered', 'slices', 'deferred', 'tokens',
    'gap_max_s', 'admit_tick', 'first_tick', 'outcome')])
DECODE_CELLS = ['transformer_base_lm.chat_open',
                'transformer_base_lm.batch_closed',
                'olmoe_1b_7b.gen_closed',
                'k_exaone_236b_a23b.longgen_closed',
                'joyai_llm_flash.reason_closed',
                'qwen3_next_80b_a3b.reason_closed',
                'phi4_mini_flash_reasoning.reason_closed']
T_OPEN, WINDOW_S = 100.0, 10.0
STEP, SLICE = 0.010, 0.020


def _run(ticks=None, requests=None, itl_ms=(), ttft_ms=()):
    """A run whose window is [100, 110) and whose predictor's stats hold
    `ticks` as their tick log and `requests` as their request log (None:
    the program has no such method)."""
    stats = types.SimpleNamespace()
    if ticks is not None:
        stats.tick_log = lambda since=None: (
            ticks.copy() if since is None else ticks[ticks['t0'] >= since])
    if requests is not None:
        stats.request_log = lambda since=None: (
            requests.copy() if since is None
            else requests[requests['t_submit'] >= since])
    runner = types.SimpleNamespace(served=types.SimpleNamespace(
        pred=types.SimpleNamespace(stats=stats)))
    return {'runner': runner, 'result': {
        't_open': T_OPEN, 'window_s': WINDOW_S, 'itl_ms': list(itl_ms),
        'ttft_ms': list(ttft_ms)}}


def _device_bound_ticks(slice_every=25, n=1200, live=64, dtype=TICK_ROW):
    """A device-bound closed loop from 95 s on: every tick dispatches a
    step of 10 ms, every `slice_every`-th a 512-token slice of 20 ms
    behind it, and reads what the tick before dispatched — so the
    delivery of tick k waits for the slices of tick k-2. The tick that
    reads a last slice waits for it BEHIND its delivery."""
    rows = np.zeros(n, dtype)
    sliced = (np.arange(n) % slice_every) == 3
    device = np.zeros(n)        # when step(k)'s ids are on the host
    free = 95.0
    for k in range(n):
        free += STEP
        device[k] = free
        free += SLICE * sliced[k]
    t = 95.0
    for k in range(n):
        rows[k]['tick'], rows[k]['t0'] = k + 7, t
        wait_step = wait_slice = 0.0
        emit = np.nan
        if k >= 1:
            # host work 1 ms, then the step dispatched a tick ago
            wait_step = max(device[k - 1] - (t + 0.001), 0.0)
            emit = t + 0.001 + wait_step
            end = emit + 0.0005
            if sliced[k - 1]:
                wait_slice = max(device[k - 1] + SLICE - end, 0.0)
                end += wait_slice
        else:
            end = t + 0.001
        if 'emit_t' in dtype.names:
            rows[k]['emit_t'] = emit
            rows[k]['emit_rows'] = 0 if np.isnan(emit) else live
            rows[k]['wait_step_s'], rows[k]['wait_slice_s'] = \
                wait_step, wait_slice
            rows[k]['slice_tokens'] = 512 * sliced[k]
        rows[k]['wait_s'] = wait_step + wait_slice
        rows[k]['slices'] = sliced[k]
        rows[k]['wall_s'] = end + 0.0005 - t
        t = end + 0.0005
    return rows


def test_the_p99_gap_is_step_plus_one_slice_and_two_rows_back_holds_it():
    """One tick in 25 dispatches a slice: 4 % of the gaps are step +
    slice, so p99 is; the slices that made them lie TWO rows before the
    closing row — not one, not three — and the median gap has none."""
    ticks = _device_bound_ticks()
    run = _run(ticks)
    assert emit_gap_ms_p99.reduce(run) == pytest.approx(
        (STEP + SLICE) * 1e3, abs=1e-6)
    assert gap_p99_prefill_tokens.reduce(run) == 512.0
    rows, closing, gap, p99 = _requests.p99_gaps(run)
    assert p99 == pytest.approx(STEP + SLICE) and len(closing) > 10
    back = [gap_p99_prefill_tokens.tokens_back(rows, closing, n)
            for n in range(4)]
    assert back == [0.0, 0.0, 512.0, 0.0]
    _, every, gaps, _ = _requests.window_gaps(run)
    assert gap_p99_prefill_tokens.tokens_back(
        rows, every[gaps <= STEP + 1e-9], 2) == 0.0


def test_the_device_owns_the_tail_where_the_host_waits_for_it():
    """A gap of step + slice: the opening tick's wait for its slice read
    and the closing tick's wait for its step fill it but for the host's
    1.5 ms a tick."""
    run = _run(_device_bound_ticks())
    share = gap_p99_wait_share.reduce(run)
    assert share == pytest.approx(100.0 * (1 - 0.002 / (STEP + SLICE)),
                                  abs=0.5)
    assert slice_read_wait_ms.reduce(run) == pytest.approx(
        (SLICE - 0.0005) * 1e3, abs=0.1)


def test_the_host_owns_the_tail_where_it_never_waits():
    """A host-bound loop: the gaps are the host's own time, no wait in
    them — the share reads 0 and the gap is still read."""
    n = 1500
    ticks = np.zeros(n, TICK_ROW)
    ticks['t0'] = 99.0 + 0.008 * np.arange(n)
    ticks['wall_s'] = 0.0079
    ticks['emit_t'] = ticks['t0'] + 0.002 + 0.004 * (np.arange(n) % 50 == 0)
    ticks['emit_rows'] = 128
    run = _run(ticks)
    assert gap_p99_wait_share.reduce(run) == 0.0
    assert emit_gap_ms_p99.reduce(run) == pytest.approx(12.0, abs=1e-6)
    assert slice_read_wait_ms.reduce(run) is None       # no slice was read


def test_a_gap_counts_once_for_every_row_that_saw_it():
    """Weighted by the closing tick's `emit_rows`: 30 long gaps that 100
    rows saw outweigh 970 short ones that one row saw; unweighted they
    would be 3 %."""
    n = 1001
    ticks = np.zeros(n, TICK_ROW)
    ticks['t0'] = 100.0 + 0.009 * np.arange(n)
    gaps = np.where(np.arange(1, n) % 33 == 0, 0.040, 0.005)
    ticks['emit_t'] = 100.001 + np.concatenate([[0], np.cumsum(gaps)])
    ticks['t0'] = ticks['emit_t'] - 0.001
    ticks['emit_rows'][1:] = np.where(gaps > 0.01, 100, 1)
    ticks['emit_rows'][0] = 1
    window = ticks[ticks['t0'] < T_OPEN + WINDOW_S]
    long_ = (np.diff(window['emit_t']) > 0.01)
    assert 0.02 < long_.mean() < 0.04
    _, closing, gap, weight = _requests.window_gaps(_run(ticks))
    assert len(closing) == len(window) - 1
    assert _requests.weighted_percentile(gap, weight, 50) == pytest.approx(
        0.040)
    assert _requests.weighted_percentile(
        gap, np.ones_like(weight), 50) == pytest.approx(0.005)
    # and equal to the percentile of the gaps written out a row each
    seen = np.repeat(gap, weight.astype(int))
    for q in (50, 90, 99):
        assert _requests.weighted_percentile(gap, weight, q) == \
            np.percentile(seen, q, method='inverted_cdf')


def test_a_tick_without_a_delivery_bounds_no_gap():
    """An idle stretch: the ticks on its two sides are not adjacent
    deliveries, and the gap across it is nobody's."""
    ticks = _device_bound_ticks(slice_every=10 ** 6)
    ticks['emit_t'][600] = np.nan
    ticks['emit_rows'][600] = 0
    _, closing, gap, _ = _requests.window_gaps(_run(ticks))
    window = ticks[(ticks['t0'] >= T_OPEN) & (ticks['t0'] < T_OPEN + WINDOW_S)]
    assert len(closing) == len(window) - 1 - 2
    assert gap.max() == pytest.approx(STEP)


def _requests_ring(n=400, dtype=REQUEST_ROW):
    """Open-loop requests, one every 40 ms from 95 s on: a prompt of k
    slices prefills for 10 ms a slice, queues 1 ms, its read takes 12 ms;
    the 6 % with 8 slices also wait 3 ticks under the budget."""
    rows = np.zeros(n, dtype)
    k = np.arange(n)
    long_ = k % 17 == 5
    rows['request'] = k + 1
    rows['t_submit'] = 95.0 + 0.040 * k
    rows['t_admit'] = rows['t_submit'] + 0.001
    rows['slices'] = np.where(long_, 8, 1 + k % 3)
    rows['deferred'] = np.where(long_, 3, 0)
    rows['prompt_len'] = 128 * rows['slices']
    rows['t_last_slice'] = rows['t_admit'] + 0.010 * (
        rows['slices'] - 1 + rows['deferred'])
    rows['t_first'] = rows['t_last_slice'] + 0.012
    rows['t_end'] = rows['t_first'] + 1.0
    rows['tokens'] = 100
    return rows


def test_the_p95_first_tokens_are_the_long_prompts_and_their_time_prefill():
    reqs = _requests_ring()
    run = _run(requests=reqs)
    parts = {'queue': ttft_p95_queue_ms.reduce(run),
             'prefill': ttft_p95_prefill_ms.reduce(run),
             'read': ttft_p95_read_ms.reduce(run)}
    assert parts == pytest.approx({'queue': 1.0, 'prefill': 100.0,
                                   'read': 12.0})
    # over the requests submitted in the window alone, whatever ended when
    window = reqs[(reqs['t_submit'] >= T_OPEN)
                  & (reqs['t_submit'] < T_OPEN + WINDOW_S)]
    assert len(_requests.window_requests(run)) == len(window) == 250
    assert request_ttft_p95_ms.reduce(run) == pytest.approx(113.0)
    # worked out once a run
    assert run['_ttft_p95_parts'] is _requests.ttft_p95_parts(run)


def test_a_request_without_a_first_token_is_left_out():
    """Cut while it queued, shed, failed: a row, but no time to a first
    token — and nothing to break the sum of the parts."""
    reqs = _requests_ring()
    cut = np.arange(len(reqs)) % 9 == 0
    for column in ('t_admit', 't_last_slice', 't_first'):
        reqs[column][cut] = np.nan
    run = _run(requests=reqs)
    kept = _requests.window_requests(run)
    assert not np.isnan(kept['t_first']).any() and len(kept) < 250
    assert ttft_p95_queue_ms.reduce(run) == pytest.approx(1.0)


_GAP_READERS = [emit_gap_ms_p99, gap_p99_prefill_tokens, gap_p99_wait_share,
                slice_read_wait_ms]
_REQUEST_READERS = [ttft_p95_queue_ms, ttft_p95_prefill_ms, ttft_p95_read_ms,
                    request_ttft_p95_ms]


_NOTHING = {
    # the parent's program: a tick log without the columns, no request log
    'parent': lambda: _run(_device_bound_ticks(dtype=PARENT_ROW)),
    'no_rings': lambda: _run(),
    'no_predictor': lambda: {'runner': None,
                             'result': {'t_open': 0.0, 'window_s': 1.0}},
    'nothing_in_the_window': lambda: _run(_device_bound_ticks(n=100),
                                          _requests_ring(n=20)),
    # too little in the window for a percentile (slice_read_wait_ms is a
    # mean over the ticks that read a slice: it needs none)
    'too_few': lambda: _run(_device_bound_ticks(live=1)[:505],
                            _requests_ring()[:130]),
}


@pytest.mark.parametrize('reader,case', [
    (reader, case) for reader in _GAP_READERS + _REQUEST_READERS
    for case in _NOTHING
    if (reader, case) != (slice_read_wait_ms, 'too_few')],
    ids=lambda v: v if isinstance(v, str) else v.__name__.rsplit('.', 1)[-1])
def test_nothing_to_read_gives_nothing(reader, case):
    assert reader.reduce(_NOTHING[case]()) is None


def test_the_wait_for_a_slice_is_a_mean_and_needs_no_percentile():
    run = _NOTHING['too_few']()
    assert emit_gap_ms_p99.reduce(run) is None
    assert slice_read_wait_ms.reduce(run) == pytest.approx(
        (SLICE - 0.0005) * 1e3, abs=0.1)


_ENTRIES = [
    ('emit_gap_ms_p99', 'ms', 'lower', 'itl_p99_ms', DECODE_CELLS),
    ('gap_p99_prefill_tokens', 'tokens', 'lower', 'itl_p99_ms', DECODE_CELLS),
    ('gap_p99_wait_share', '%', 'higher', 'itl_p99_ms', DECODE_CELLS),
    ('slice_read_wait_ms', 'ms', 'lower', 'itl_p99_ms', DECODE_CELLS),
    ('ttft_p95_queue_ms', 'ms', 'lower', 'ttft_p95_ms', DECODE_CELLS[:1]),
    ('ttft_p95_prefill_ms', 'ms', 'lower', 'ttft_p95_ms', DECODE_CELLS[:1]),
    ('ttft_p95_read_ms', 'ms', 'lower', 'ttft_p95_ms', DECODE_CELLS[:1]),
    ('request_ttft_p95_ms', 'ms', 'lower', 'itl_p99_ms', DECODE_CELLS),
]


@pytest.mark.parametrize('name,unit,better,moves,cells', _ENTRIES,
                         ids=[e[0] for e in _ENTRIES])
def test_the_entry_by_name(name, unit, better, moves, cells):
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    entry, = [m for m in bench['per_layer'] if m['name'] == name]
    assert entry == {'name': name, 'unit': unit, 'better': better,
                     'source': 'program_counter',
                     'layer': 'Decode scheduler', 'moves': moves,
                     'workloads': cells}
    # every cell it is filed for reports the metric it moves
    moved, = [m for m in bench['end_to_end'] if m['name'] == moves]
    assert set(cells) <= set(moved['workloads'])
    assert os.path.exists(os.path.join(
        ROOT, 'benchmark', 'layer_metrics', name + '.py'))


def test_the_eight_entries_are_the_last_and_in_this_order():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    assert [m['name'] for m in bench['per_layer'][-8:]] == \
        [e[0] for e in _ENTRIES]
