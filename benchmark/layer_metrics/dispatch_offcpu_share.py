"""Decode scheduler: of the time inside 'decode/dispatch' — the one call
that hands a program and its feeds to the runtime — the share the calling
thread was not on a CPU: 100 x sum(wall - `cpu_us`) over sum(wall), over
the dispatch spans inside the traced interval. Low: the call is jax's
argument handling, and fewer calls win it back. High: the thread waits
in there — for the GIL a woken consumer took when the call let go of it,
or for the runtime — and fewer calls win nothing. Beside the number: the
call by program, the runtime's own events nested in it, and how good the
reading is. None where no dispatch span carries the stat, or where the
CPU clock's steps are too coarse for the calls' time (`_oncpu.resolves`:
filed for the cells whose traced calls add up to a second or more)."""
from . import _oncpu


def reduce(run):
    spans = _oncpu.timed(run, 'decode/dispatch')
    if not spans:
        return None
    _oncpu.say_inside_dispatch(run)
    if not _oncpu.resolves(run, 'dispatch_offcpu_share',
                           sum(c for _, c, _ in spans),
                           sum(w for w, _, _ in spans)):
        return None
    return _oncpu.offcpu_share(spans)
