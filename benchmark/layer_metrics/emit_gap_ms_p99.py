"""Decode scheduler: the p99 gap between two steps' deliveries, in
milliseconds, over the rate part of the window, tracing off — the
differences of the tick log's `emit_t` between adjacent ticks that both
delivered a step's tokens, each counted once for every row the closing
tick delivered to (`emit_rows`): what itl_p99_ms is on the consumers'
side, read inside the program, without the consumer's wake-up and without
a stream's first gap (first token, from a slice's read, to second). Where
tick_ms_p99 counts a tick's wait for a prompt's last slice, which lies
BEHIND the tick's deliveries, this does not. Beside the number: the median
gap and the consumers' own p99. None where the program keeps no tick log
or its rows lack the column (the parent of the PR that added it), or the
window holds too few deliveries for a p99."""
from .. import harness
from . import _requests


def reduce(run):
    found = _requests.window_gaps(run)
    if found is None:
        return None
    _, closing, gap, weight = found
    try:
        p99 = _requests.weighted_percentile(gap, weight, 99)
    except ValueError:
        return None
    try:
        consumers = harness.percentile(run['result'].get('itl_ms') or [], 99)
    except ValueError:
        consumers = float('nan')
    harness.say('  gaps between two steps\' deliveries', gaps=len(closing),
                rows_that_saw_them=float(weight.sum()),
                p50_ms=_requests.weighted_percentile(gap, weight, 50) * 1e3,
                p99_ms=p99 * 1e3, longest_ms=float(gap.max()) * 1e3,
                consumers_itl_p99_ms=consumers)
    return p99 * 1e3
