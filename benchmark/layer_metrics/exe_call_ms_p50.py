"""Executor: median time for one Executor.run / ParallelExecutor.run call to
return (the enqueue), from the benchmark-side span around each call inside
the window."""
from .. import harness


def reduce(run):
    r = run['result']
    calls = run['ctx'].spans.durations('exe_run', r['t_open'], r['t_close'])
    return harness.median(calls) * 1e3
