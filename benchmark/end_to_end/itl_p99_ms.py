"""Gap between consecutive token deliveries of one stream on the consumer
side: 99th percentile over all gaps that end inside the window."""
from .. import harness


def reduce(run):
    return harness.tail(run, run['result']['itl_ms'], 99)
