"""Decode scheduler: the p95 time to first token, in milliseconds, over the
requests submitted in the rate part of the window that got one — t_first -
t_submit of the program's request log, tracing off. The price of a tick's
prefill budget or of a fused slice, read in the cells that file no
end-to-end time to first token (a closed loop's request is submitted the
instant the one before it ended: admission, its prompt's slices a tick
each, the read). Where a window turns over few requests — 64 slots whose
answers take 40 s each submit some 50 in it — two or three lie beyond the
p95 and it is close to a maximum: the line beside the number says how
many it was taken over. None where the program keeps no request log (the
parent of the PR that added it) or the window holds fewer than 20 such
requests (not one whole sample beyond the p95)."""
from .. import harness
from . import _requests


def reduce(run):
    rows = _requests.window_requests(run)
    if rows is None:
        return None
    ttft = _requests.ttft_ms(rows)
    try:
        p95 = harness.percentile(ttft.tolist(), 95, min_beyond=1)
    except ValueError:
        return None
    harness.say('  first tokens of the requests submitted in the window',
                requests=len(rows), p50_ms=harness.median(ttft.tolist()),
                p95_ms=p95, longest_ms=float(ttft.max()),
                slices=float(rows['slices'].mean()),
                deferred=float(rows['deferred'].mean()),
                queued_ms=float((rows['t_admit']
                                 - rows['t_submit']).mean()) * 1e3)
    return p95
