"""Decode scheduler: of the scheduler's busy time in the rate part of the
window — tracing OFF — the share its thread was neither on a CPU nor
waiting for the device, from the tick log's rows (DecodeStats.tick_log)
that began in [t_open, t_open + window_s): 100 - 100 x sum(cpu_s) over
sum(cpu_wall_s) of the rows that carry a reading of the thread's CPU clock
(the program reads it every 20 ms: the readings span the busy time end to
end) - 100 x sum(wait_s) over sum(wall_s). CPU time used inside the wait is
in both terms: the share reads low by that much. The untraced twin of
`tick_offcpu_ms`. Beside the number: the log against `busy_s`, which times
the same ticks, and the traced ticks against their own rows in the log.
None where the program keeps no tick log, or no row there has a reading."""
import numpy as np

from .. import harness
from . import _oncpu


def reduce(run):
    rows = _oncpu.window_ticks(run)
    if rows is None:
        return None
    r = run['result']
    wall = float(rows['wall_s'].sum())
    # the closed loop's counters_window ends with the rate part, the open
    # loop's with the window
    to_close = _oncpu.tick_log(run)(since=r['t_open'])
    harness.say(
        'tick log over the rate part of the window', ticks=len(rows),
        wall_s=wall, busy_s=r['counters_window']['busy_s'],
        wall_s_to_close=float(
            to_close['wall_s'][to_close['t0'] < r['t_close']].sum()),
        cpu_s=float(np.nansum(rows['cpu_s'])),
        cpu_over_s=float(np.nansum(rows['cpu_wall_s'])),
        readings=int((~np.isnan(rows['cpu_s'])).sum()),
        wait_s=float(rows['wait_s'].sum()),
        gc_s=float(rows['gc_s'].sum()),
        dispatches=int(rows['dispatches'].sum()), rows=int(rows['rows'].sum()),
        tick_p50_ms=harness.median(rows['wall_s'].tolist()) * 1e3)
    _oncpu.say_log_against_trace(run)
    on = _oncpu.oncpu_share(rows)
    return None if on is None else \
        100.0 - on - _oncpu.share_of_wall(rows, rows['wait_s'])
