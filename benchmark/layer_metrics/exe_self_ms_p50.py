"""Executor: median milliseconds of an Executor.run call outside its
'exe/dispatch' child — python in the executor: feed placement, verify,
state gathering, cache key, scope writes."""
from .. import harness
from . import _spans


def reduce(run):
    runs = _spans.exe_runs(run['trace'])
    return harness.median([r - d for r, d in runs]) * 1e3 if runs else None
