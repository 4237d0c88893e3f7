"""Load generator (the benchmark itself): how late submit() ran against
its due time, 99th percentile over the requests due in the window (one
that was never submitted counts as the worst value). A value that is not
small against ttft_p95_ms voids the run's tails (PERF.md says at what
value)."""
from .. import harness


def reduce(run):
    return harness.percentile(run['result']['generator_lag_ms'], 99,
                              min_beyond=0)
