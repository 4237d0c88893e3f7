"""Decode scheduler: the longest scheduler tick of the rate part of the
window in milliseconds, tracing off — the stop that a run of this system
takes now and then (50-130 ms, every python thread at once). Beside the
number: the rows of the five longest ticks, each named from its own
columns — most of it `gc_s`: python's collector; most of it `wait_s`: the
device or the runtime; else by the CPU reading that ends in it (`cpu_s`
over `cpu_wall_s`): work, or the thread — or the whole process — not
running. None where the program keeps no tick log."""
import numpy as np

from . import _oncpu


def reduce(run):
    rows = _oncpu.window_ticks(run)
    if rows is None:
        return None
    longest = rows[np.argsort(rows['wall_s'])[::-1][:5]]
    _oncpu.say_rows('one of the five longest ticks', longest)
    return float(longest['wall_s'][0]) * 1e3
