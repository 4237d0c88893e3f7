"""Decode scheduler: milliseconds per dispatch of a tick that are in none
of build_feed, dispatch, device_wait, d2h, advance, first_token: the time
of 'decode/tick' itself, 'decode/expire', 'decode/admit' and the
bookkeeping of a prefill slice."""
from . import _spans


def reduce(run):
    return _spans.tick_rest_ms(run)
