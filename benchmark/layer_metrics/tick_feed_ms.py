"""Decode scheduler: milliseconds per dispatch spent building the feed and
enqueuing the program — the program's spans 'decode/build_feed' (draft
collection, block preflight, CoW, filling tokens / pos / tables) and
'decode/dispatch' (the jitted call returning) over tick_host_ms's own
denominator, step + prefill-slice dispatches in the traced interval."""
from . import _spans


def reduce(run):
    return _spans.tick_phase_ms(run, _spans.TICK_FEED)
