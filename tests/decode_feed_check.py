"""Test-side check of the decode scheduler's KEPT step feed (ISSUE 35):
the scheduler keeps tokens / pos / block_tables / window_tables and its
live rows between ticks and re-writes a row only at an event of that row;
`watch_feed(pred)` rebuilds all of them from the request objects alone —
the loop over every live row that the scheduler ran each tick before —
behind every `_step_feed`, and requires the kept ones to equal the
rebuilt ones, idle rows included.

And of its per-tick PREFILL BUDGET (ISSUE 52): `watch_slices(pred)` lists,
for every tick that had a slice due, what was due and what went;
`fits_first` is the rule they are held to, `alone_slices` the slices a
prompt takes with nobody beside it. `gated(pred)` holds the scheduler so
that a test decides what one tick finds waiting."""
import threading

import numpy as np


def rebuilt_feed(pred, skip=()):
    """The plain step's feed and rows from the requests alone: a fresh
    all-idle feed, then every decoding request's unfinished beams in
    slot order. Also how many of those rows have an event this step —
    their first step, a position that opens a block or moves a window,
    or a request the host alone can advance — which is what the
    scheduler may have re-written."""
    S, maxb, trash, bs = pred._S, pred._maxb, pred._trash, pred._bs
    tokens = np.zeros((S, 1), np.int64)
    pos = np.zeros((S, 1), np.int32)
    live = np.zeros((S, 1), np.int32)
    tables = np.full((S, maxb), trash, np.int32)
    wtables = (np.full((S, maxb), trash, np.int32)
               if pred._window else None)
    rows, events = {}, 0
    for req in dict.fromkeys(e[0] for e in pred._slots if e is not None):
        if req.prefilling or req in skip or req.dispatched >= req.max_new:
            continue
        hosted = (req.beam is not None or pred._drafter is not None
                  or req.shared)
        for bi, s in enumerate(req.slots):
            if req.beam is not None and req.finished[bi]:
                continue
            p = int(req.prompt.size) + req.dispatched - 1
            tokens[s, 0] = (req.last_tokens[bi] if req.beam is not None
                            else req.tokens[-1] if pred._drafter is not None
                            else -1)
            pos[s, 0] = p
            live[s, 0] = 1
            table = req.tables[bi]
            tables[s, :len(table)] = table
            if wtables is not None:
                req.wtable.fill(wtables[s])
            rows[s] = (req, bi)
            events += int(
                hosted or req.dispatched == 1 or p % bs == 0
                or bool(pred._window
                        and (p - pred._window + 1) % bs == 0))
    return (tokens, pos, live, tables, wtables,
            [rows[s] for s in sorted(rows)], events)


class FeedWatch(object):
    """Counts of the steps watched: `steps` feeds built, `live` rows in
    them, `events` rows with an event (rebuilt_feed)."""

    def __init__(self):
        self.steps = self.live = self.events = 0


def watch_feed(pred):
    """Behind every feed the scheduler builds (on its own thread, in
    front of the dispatch): kept arrays == rebuilt arrays, kept live
    rows == rebuilt rows. Returns the FeedWatch it counts into."""
    step_feed, watch = pred._step_feed, FeedWatch()

    def checked(waiting, drafted):
        out = step_feed(waiting, drafted)
        tokens, pos, live, tables, wtables, rows, events = rebuilt_feed(
            pred, skip=drafted)
        np.testing.assert_array_equal(pred._feed_tokens, tokens)
        np.testing.assert_array_equal(pred._feed_pos, pos)
        np.testing.assert_array_equal(pred._feed_live, live)
        np.testing.assert_array_equal(pred._feed_tables, tables)
        if wtables is not None:
            np.testing.assert_array_equal(pred._feed_wtables, wtables)
        assert out[1] == rows == pred._live_rows()
        watch.steps += 1
        watch.live += len(rows)
        watch.events += events
        return out
    pred._step_feed = checked
    return watch


def alone_slices(chunks, plen, covered=0):
    """[(bucket, start)] of the prefill slices a prompt of `plen` tokens
    takes served alone: the smallest bucket that holds what remains, the
    largest while more remains than it holds."""
    out, at = [], covered
    while at < plen:
        size = next((c for c in chunks if c >= plen - at), chunks[-1])
        out.append((size, at))
        at += size
    return out


def fits_first(due, budget):
    """The entries of `due` — (request, bucket, start), oldest admission
    first — that go in one tick of `budget` prompt tokens: each in turn
    where its bucket fits what is left, so the oldest always, and a
    smaller one behind one that did not fit."""
    went, left = [], budget
    for entry in due:
        if entry[1] <= left:
            went.append(entry)
            left -= entry[1]
    return went


def watch_slices(pred):
    """Behind every _prefill_tick with a slice due (on the scheduler's
    thread): {'decoding': whether any request held a decoding row, 'due':
    [(request seq, the bucket it takes alone, start)] oldest admission
    first, 'went': the same of every slice dispatched, in dispatch order}.
    Returns the list it appends to."""
    ticks = []
    prefill_tick = pred._prefill_tick
    prefill_slice, write_row = pred._prefill_slice, pred._write_row

    def tick():
        active = pred._active_requests()
        due = [(r.seq,) + alone_slices(pred._chunks, int(r.prompt.size),
                                       r.next_start)[0]
               for r in sorted(active, key=lambda r: r.seq) if r.prefilling]
        if due:
            ticks.append({'decoding': any(not r.prefilling for r in active),
                          'due': due, 'went': []})
        return prefill_tick()

    def one_row(req, size, take, last):
        ticks[-1]['went'].append((req.seq, size, req.next_start))
        return prefill_slice(req, size, take, last)

    def a_row(k, req, take, last):
        ticks[-1]['went'].append((req.seq, pred._chunks[-1],
                                  req.next_start))
        return write_row(k, req, take, last)
    pred._prefill_tick = tick
    pred._prefill_slice, pred._write_row = one_row, a_row
    return ticks


def gated(pred, before=None):
    """Run `before(pred)` on the scheduler's own thread in front of
    every tick, and hold the FIRST tick until the test has queued
    its whole batch and set the gate this returns: tick 1 then admits
    what it had drained before it was held (the first request alone, or
    nothing beside a running batch) and tick 2 finds every other one
    waiting — admissions staggered, and the same in every run."""
    run_tick, gate = pred._run_tick, threading.Event()

    def tick(waiting):
        assert gate.wait(60)
        if before is not None:
            before(pred)
        run_tick(waiting)
    pred._run_tick = tick
    return gate
