"""Decode scheduler, closed loop: median time from submit() to the first
token on the consumer side, over first tokens inside the window."""
from .. import harness


def reduce(run):
    return harness.median(run['result']['ttft_ms'])
