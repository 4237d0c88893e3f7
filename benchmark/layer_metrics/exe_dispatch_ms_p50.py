"""Executor: median milliseconds in 'exe/dispatch', the call of the
compiled step (input pinning or mesh placement, then the enqueue), over the
Executor.run calls inside the traced interval."""
from .. import harness
from . import _spans


def reduce(run):
    runs = _spans.exe_runs(run['trace'])
    return harness.median([d for _, d in runs]) * 1e3 if runs else None
