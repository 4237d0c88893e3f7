"""Decode scheduler: the p99 scheduler tick of the rate part of the window
in milliseconds, tracing off — p99 of `wall_s` over the tick log's rows that
began in [t_open, t_open + window_s). A closed loop's p99 inter-token gap
IS its busiest ticks. Beside the number: what the ticks at or above it are
made of on average (wait for the device, collector, dispatches, rows, and
the on-CPU share of the CPU readings that end in them) against the mean
tick. None where the program keeps no tick log, or too few ticks for a
p99."""
from .. import harness
from . import _oncpu


def _mean(rows, msg):
    harness.say(
        '  %s' % msg, ticks=len(rows), wall_ms=rows['wall_s'].mean() * 1e3,
        wait_ms=rows['wait_s'].mean() * 1e3, gc_ms=rows['gc_s'].mean() * 1e3,
        dispatches=rows['dispatches'].mean(), rows=rows['rows'].mean(),
        oncpu_share_of_the_readings_in_them=_oncpu.oncpu_share(rows))


def reduce(run):
    rows = _oncpu.window_ticks(run)
    if rows is None:
        return None
    try:
        p99 = harness.percentile(rows['wall_s'].tolist(), 99)
    except ValueError:
        return None
    _mean(rows, 'the mean tick')
    _mean(rows[rows['wall_s'] >= p99], 'the mean tick at or above p99')
    return p99 * 1e3
