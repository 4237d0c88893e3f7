"""Parallel: time on a chip with a collective running and no compute
operation running, as a share of the traced window; the worst chip."""
from .. import trace as trace_mod


def reduce(run):
    trace = run['trace']
    if not trace.devices:
        return None
    lo, hi = trace.window
    worst = max(trace_mod.collective_exposed_seconds(d, lo, hi)
                for d in trace.devices)
    return 100.0 * worst / ((hi - lo) / 1e9)
