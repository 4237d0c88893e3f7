"""Decode scheduler: how long a request waited between submit() and its
admission to a slot — the median 'waited_us' stat of the
'decode/admit_request' spans in the traced interval, in milliseconds."""
from . import _spans


def reduce(run):
    us = _spans.stat_median(run, 'decode/admit_request', 'waited_us')
    return None if us is None else us / 1e3
