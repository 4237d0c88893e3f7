"""Decode scheduler: the host's share of a scheduler iteration:
(DecodeStats busy_s - device-busy seconds from the trace) / (steps +
chunk_slices), all as deltas over the traced window."""
from .. import trace as trace_mod


def reduce(run):
    c = run['result']['counters_traced']
    busy = trace_mod.mean_busy_seconds(run['trace'])
    return (c['busy_s'] - busy) / (c['steps'] + c['chunk_slices']) * 1e3
