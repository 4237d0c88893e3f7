"""Decode scheduler: of the requests whose time to their first token is at
or above its p95 — over the requests submitted in the rate part of the
window, tracing off, from the program's request log — the mean
milliseconds such a request spent PREFILLING: t_last_slice - t_admit,
from its admission to the dispatch of its prompt's last slice — its slices,
a tick each, and the ticks they waited under the prefill budget (the
request log's `slices` and `deferred`).
One of three parts (ttft_p95_queue_ms, ttft_p95_prefill_ms,
ttft_p95_read_ms) that add up to those requests' mean time to first token:
is the tail of ttft_p95_ms queueing, prefill or the read?
_requests.ttft_p95_parts works them out, checks the sum and prints what
those requests were. None where the program keeps no request log (the
parent of the PR that added it) or the window holds too few requests for a
p95."""
from . import _requests


def reduce(run):
    parts = _requests.ttft_p95_parts(run)
    return None if parts is None else parts['prefill']
