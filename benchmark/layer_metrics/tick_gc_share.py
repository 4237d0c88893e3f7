"""Decode scheduler: of the scheduler's busy time in the rate part of the
window, the share during which python's cyclic collector ran — on ANY
thread: a collection holds the GIL from start to stop. 100 x sum(gc_s)
over sum(wall_s) of the tick log's rows that began in [t_open, t_open +
window_s); `gc_s` is the gain of the process-wide `gc.callbacks` counter
over the tick. None where the program keeps no tick log."""
from . import _oncpu


def reduce(run):
    rows = _oncpu.window_ticks(run)
    return None if rows is None else _oncpu.share_of_wall(rows, rows['gc_s'])
