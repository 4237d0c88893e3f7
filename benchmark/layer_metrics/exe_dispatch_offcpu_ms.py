"""Executor: milliseconds of the mean 'exe/dispatch' — the call of the
compiled step — during which the calling thread was NOT on a CPU: sum(wall
- `cpu_us`) over the dispatch spans inside the traced interval, over their
count. A mean, because a thread's CPU clock may move in steps far longer
than one call (10 ms under a sandboxed kernel): a single call's reading is
a sample, their sum is the measurement. Near 0: the call is python and
jax's argument handling. Near the call's length: the thread is blocked in
the runtime. None where no dispatch span carries the stat (the parent of
the PR that added it), or where the clock's steps are too coarse for the
calls' time (`_oncpu.resolves`: filed for `train_dp4`, whose 60 ms calls
add up to a second; `train_1chip`'s 24 calls of 4 ms hold 6 steps)."""
from .. import harness
from . import _oncpu


def reduce(run):
    spans = _oncpu.timed(run, 'exe/dispatch')
    if not spans:
        return None
    wall = sum(w for w, _, _ in spans)
    cpu = sum(c for _, c, _ in spans)
    harness.say('exe/dispatch a call', calls=len(spans),
                wall_ms=wall / len(spans) / 1e6,
                oncpu_ms=cpu / len(spans) / 1e6,
                offcpu_share=_oncpu.offcpu_share(spans))
    if not _oncpu.resolves(run, 'exe_dispatch_offcpu_ms', cpu, wall):
        return None
    return (wall - cpu) / len(spans) / 1e6
