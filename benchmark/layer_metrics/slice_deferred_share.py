"""Decode scheduler: the share of (due prefill slice, tick) pairs that ended
in a wait, in percent, over the rate part of the window, tracing off. While
any request decodes a tick dispatches at most one largest chunk call's worth
of prefill, oldest admission first (rows x largest chunk prompt tokens by
bucket size); a due slice that finds no room waits for the next tick, its
bucket unchanged. The program's tick log carries, a tick, `slices` (the
slices it dispatched; DecodeStats.chunk_slices) and `deferred` (the due
ones it left waiting; DecodeStats.slices_deferred): 100 x sum of `deferred`
over sum of `deferred` + `slices`, over the rows that began in [t_open,
t_open + window_s). 0 where no two admissions ever met in a tick; what it
buys is a tick of step + ONE such call (tick_ms_p99, itl_p99_ms) and what it
costs is the waiting requests' time to their first token. None where the
program keeps no tick log or its rows lack the columns (the parent of the PR
that added them), or where the window dispatched no slice."""
from . import _oncpu


def reduce(run):
    rows = _oncpu.window_ticks(run)
    if rows is None or 'deferred' not in (rows.dtype.names or ()):
        return None
    deferred, slices = rows['deferred'].sum(), rows['slices'].sum()
    if not deferred + slices:
        return None
    return 100.0 * deferred / (deferred + slices)
