"""Time from the instant a request was DUE on the arrival schedule to its
first token on the consumer side: 95th percentile over the requests due
inside the window by the schedule. A failed, shed, unfinished or never
submitted request counts as the worst value (window + drain limit)."""
from .. import harness


def reduce(run):
    return harness.tail(run, run['result']['ttft_ms'], 95)
