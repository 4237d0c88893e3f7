"""Decode scheduler: over the gaps between two steps' deliveries that are at
or above their p99 (emit_gap_ms_p99), the mean prompt tokens, by bucket
size, of the prefill slices the device ran between the two steps whose
deliveries bound the gap — tracing off, the rate part of the window.

THE OFFSET. What a tick dispatches is read in the next tick, behind that
tick's step: the device runs step(k-2), slices(k-2), step(k-1), slices(k-1),
and the deliveries of ticks k-1 and k are the ends of step(k-2) and
step(k-1). Between them lie the slices that tick k-2 dispatched: the
`slice_tokens` of the row TWO before the closing one, where the device is
the slower side (where the host is, the slices of tick k-2 are over before
tick k-1 reads, and the gap is the host's own time). Beside the number, for
the check of that offset: the same mean at 0, 1, 2 and 3 rows back, and at
two rows back over the gaps at or under the median.

512 — one call of the largest chunk program — where a tick's prefill
budget owns the tail; 1,024 where two admissions' slices still met. None
where the tick log lacks the columns (the parent of the PR that added
them) or the window holds too few deliveries for a p99."""
from .. import harness
from . import _requests

ROWS_BACK = 2


def tokens_back(rows, closing, back):
    """Mean `slice_tokens` of the rows `back` before the `closing` ones
    (those the window's first rows do not cut off), or None."""
    at = closing[closing >= back] - back
    return float(rows['slice_tokens'][at].mean()) if len(at) else None


def reduce(run):
    tail = _requests.p99_gaps(run)
    if tail is None:
        return None
    rows, closing, gap, p99 = tail
    _, every, gaps, weight = _requests.window_gaps(run)
    median = _requests.weighted_percentile(gaps, weight, 50, min_beyond=0)
    harness.say(
        '  prefill tokens dispatched N rows before the closing one, over '
        'the gaps at or above p99', gaps=len(closing), p99_ms=p99 * 1e3,
        at_the_median_gap_2_back=tokens_back(
            rows, every[gaps <= median], ROWS_BACK),
        **{'back_%d' % n: tokens_back(rows, closing, n) for n in range(4)})
    return tokens_back(rows, closing, ROWS_BACK)
