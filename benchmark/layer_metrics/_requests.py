"""The program's own account of its two tails, tracing off, over the rate
part of the window: what the readers of the tick log's delivery columns and
of the request log share.

THE TICK LOG (`DecodeStats.tick_log()`, `_oncpu.window_ticks`) says of every
tick when its step read delivered its tokens (`emit_t` on
time.perf_counter(), NaN where the tick read no step) and to how many rows
(`emit_rows`), what it waited for (`wait_step_s`: the step's ids, in front
of the deliveries; `wait_slice_s`: a prompt's last slice, BEHIND them) and
how much prefill it dispatched (`slice_tokens`, by bucket size). The
difference of `emit_t` between two ADJACENT rows that both delivered is the
gap every decoding stream saw there, `emit_rows` of the closing row times
over: `window_gaps`. Under the scheduler's one-step-ahead rule the device
runs step(k-2), slices(k-2), step(k-1), slices(k-1), ... and tick k reads
step(k-1): the deliveries of ticks k-1 and k are the ends of step(k-2) and
step(k-1), and what the device ran between them is the slices of tick k-2 —
TWO rows before the closing one — and step(k-1).

THE REQUEST LOG (`DecodeStats.request_log()`) holds one row for every
request that ENDED — by the time a reader runs the runner has cut what was
still streaming and closed the predictor, so every request has — with
`t_submit`, `t_admit`, `t_last_slice` (the dispatch of its prompt's last
slice), `t_first` (NaN where it got no token), `prompt_len`, `slices`,
`deferred`: `window_requests` are those submitted in the rate part of the
window.

A program without the columns or the ring (the parent of the PR that added
them) gives every function here None, and the readers return None."""
from __future__ import annotations

import numpy as np

from .. import harness
from . import _oncpu


def weighted_percentile(values, weights, q, min_beyond=10):
    """The smallest of `values` at or under which q % of the weight lies
    (each value counted `weights` times, no interpolation); ValueError
    where less than `min_beyond` of the weight lies beyond it, as
    harness.percentile."""
    total = float(weights.sum())
    if total * (100.0 - q) / 100.0 < min_beyond:
        raise ValueError('p%g needs a weight of %d beyond it; have %g in all'
                         % (q, min_beyond, total))
    order = np.argsort(values)
    cum = np.cumsum(weights[order])
    at = int(np.searchsorted(cum, q / 100.0 * total))
    return float(values[order][min(at, len(cum) - 1)])


def window_gaps(run):
    """(rows, closing, gap_s, weight): the tick log's rows of the rate part
    of the window, the indices of those that closed a gap — they and the
    row before them both delivered a step's tokens — the gaps in seconds
    and the rows each closing tick delivered to. None where there is no
    log, no `emit_t` column or no gap."""
    rows = _oncpu.window_ticks(run)
    if rows is None or 'emit_t' not in (rows.dtype.names or ()):
        return None
    t = rows['emit_t']
    closing = 1 + np.flatnonzero(~(np.isnan(t[1:]) | np.isnan(t[:-1])))
    if not len(closing):
        return None
    return rows, closing, t[closing] - t[closing - 1], \
        rows['emit_rows'][closing]


def p99_gaps(run):
    """(rows, closing, gap_s, p99_s) of the gaps at or above the p99 gap
    (weighted by the rows that saw each), or None — also where the window
    holds too few deliveries for a p99."""
    found = window_gaps(run)
    if found is None:
        return None
    rows, closing, gap, weight = found
    try:
        p99 = weighted_percentile(gap, weight, 99)
    except ValueError:
        return None
    tail = gap >= p99
    return rows, closing[tail], gap[tail], p99


def window_requests(run):
    """The request log's rows submitted in the rate part of the window
    (t_open <= t_submit < t_open + window_s) that got a first token, or
    None: no predictor, a program without the log, no such request."""
    served = getattr(run.get('runner'), 'served', None)
    log = getattr(getattr(getattr(served, 'pred', None), 'stats', None),
                  'request_log', None)
    if log is None:
        return None
    r = run['result']
    rows = log(since=r['t_open'])
    rows = rows[(rows['t_submit'] < r['t_open'] + r['window_s'])
                & ~np.isnan(rows['t_first'])]
    return rows if len(rows) else None


def ttft_ms(rows):
    return (rows['t_first'] - rows['t_submit']) * 1e3


def ttft_p95_parts(run):
    """{'queue', 'prefill', 'read'}: over the window's requests whose time
    to their first token (t_first - t_submit) is at or above its p95, the
    mean milliseconds queued (t_admit - t_submit), from admission to the
    dispatch of the prompt's last slice (t_last_slice - t_admit) and from
    there to the token delivered (t_first - t_last_slice). The three add
    up to the mean time to first token of those requests — checked here.
    Beside them, for people: what those requests were (prompt length,
    slices, ticks waited under the prefill budget), the set's smallest
    value and the consumers' p95 from the instant each was DUE, which
    holds the generator's lag and the consumer's wake-up on top. None
    where there is no request log or too few requests for a p95. Worked
    out once a run."""
    if '_ttft_p95_parts' not in run:
        run['_ttft_p95_parts'] = _ttft_p95_parts(run)
    return run['_ttft_p95_parts']


def _ttft_p95_parts(run):
    rows = window_requests(run)
    if rows is None:
        return None
    ttft = ttft_ms(rows)
    try:
        p95 = harness.percentile(ttft.tolist(), 95)
    except ValueError:
        return None
    tail = rows[ttft >= p95]
    parts = {
        'queue': float((tail['t_admit'] - tail['t_submit']).mean() * 1e3),
        'prefill': float((tail['t_last_slice']
                          - tail['t_admit']).mean() * 1e3),
        'read': float((tail['t_first'] - tail['t_last_slice']).mean() * 1e3)}
    whole = float(ttft_ms(tail).mean())
    if abs(sum(parts.values()) - whole) > 1e-6 * max(whole, 1.0):
        raise AssertionError('the parts of the p95 requests\' first-token '
                             'time add up to %r, their mean is %r'
                             % (sum(parts.values()), whole))
    seen = run['result'].get('ttft_ms') or []
    try:
        consumers = harness.percentile(seen, 95)
    except ValueError:
        consumers = float('nan')
    harness.say('  the requests at or above the program\'s p95 first token',
                requests=len(tail), of=len(rows), p95_ms=p95,
                smallest_ms=float(ttft_ms(tail).min()), mean_ms=whole,
                prompt_len=float(tail['prompt_len'].mean()),
                slices=float(tail['slices'].mean()),
                deferred=float(tail['deferred'].mean()),
                consumers_p95_from_due_ms=consumers, **parts)
    return parts
