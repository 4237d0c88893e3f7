"""Decode scheduler: milliseconds per dispatch that the scheduler's thread
was NOT on a CPU inside 'decode/tick' and not waiting for the device
either. Every program span carries `cpu_us`, its thread's CPU time inside
it; a tick's wall time minus that is time the thread did not run — the
GIL held by a consumer it woke, a lock, the run queue, a blocking runtime
call. 'decode/device_wait' and 'decode/d2h' are off the CPU by design (the
device is working), so theirs is taken out. Over the ticks inside the
traced interval, by `tick_host_ms`'s own denominator. Beside the number:
the table of every span under the tick, working and waiting, and how good
the reading is. None where no tick carries the stat (the parent of the PR
that added it), or where the CPU clock's steps are too coarse for the
ticks' time outside the waits (`_oncpu.resolves`: filed for the cells
whose host work fills the tick)."""
from . import _oncpu, _spans


def reduce(run):
    table, ticks = _oncpu.tick_table(run)
    n = _spans.tick_dispatches(run)
    if not ticks or not n:
        return None
    _oncpu.say_tick_table(run)
    wall, cpu = table['decode/tick'].wall, table['decode/tick'].cpu
    for name in _oncpu.DEVICE_WAITS:
        if name in table:
            wall -= table[name].wall
            cpu -= table[name].cpu
    if not _oncpu.resolves(run, 'tick_offcpu_ms', cpu, wall):
        return None
    return (wall - cpu) / 1e6 / n
