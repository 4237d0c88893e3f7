"""Working or waiting: what the readers of the scheduler's (and the
executor's) host time share. Two instruments of the program, one question.

IN THE TRACE every program span carries `cpu_us`, the CPU time its thread
used inside it; its wall time minus that is the time the thread was not
running — waiting for the GIL, a lock, the run queue or a blocking runtime
call. `_spans.span_stats` keeps a span's stats and drops its end and its
thread, so `events(run)` opens the trace file once more and keeps both; a
holder's OWN time is its time minus its children's, which `tick_table`
works out for everything under 'decode/tick'.

A thread's CPU clock may move in steps — 10 ms under a sandboxed kernel —
so one span's `cpu_us` is a sample and a sum of them a count of steps, good
to about the root of the count: `resolves` says whether that is enough for
the wall time the sum is set against, and a reader files nothing where it
is not.

WITH TRACING OFF `DecodeStats.tick_log()` holds one row for every tick of
the whole run (`tick`, the number its 'decode/tick' span carries; `t0` on
time.perf_counter(), `wall_s`, `wait_s` inside block_until_ready, `gc_s`,
`dispatches`, `rows`); the thread's CPU clock is a system call, so the
program reads it every 20 ms and not every tick: a row with a reading holds
the CPU time since the reading before (`cpu_s`) and the busy seconds that
spans (`cpu_wall_s`), the rows in between NaN — the readings follow one
another end to end, so their sum is off by one step of the clock at most,
however coarse. `window_ticks(run)` are the rows of the rate part of the window, before the
profiler started. The runners' counters() is a fixed list, so the log is
reached through the runner's predictor.

A program without the stat, the span or the method (the parent of the PR
that added them) gives every function here nothing: the readers return
None."""
from __future__ import annotations

import math

import numpy as np

from .. import harness
from . import _spans

PREFIXES = _spans.PREFIXES + ('py/',)
# off the CPU by design: the thread waits for the device there
DEVICE_WAITS = ('decode/device_wait', 'decode/d2h')
# a sum of cpu_us is filed against its wall time only where one standard
# error of it — root of its count of clock steps, times the step — is at
# most this share of that wall time: enough to tell a thread that mostly
# works from one that mostly waits
MAX_SIGMA = 0.15


# -- the trace: spans with their end, their thread and cpu_us ----------------

def _read_events(run):
    """[(start_ns, end_ns, name, thread, stats or None)] of every host
    event of the trace file that lies inside the traced window, sorted;
    stats (a dict) for the program's spans only. Every python thread's
    line has the process's name, so a thread is its line's number."""
    path = getattr(run['ctx'].tracer, 'path', None)
    if not path:
        return []
    from jax.profiler import ProfileData
    lo, hi = run['trace'].window
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith('/host:CPU'):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if s < lo or t > hi:
                    continue
                out.append((s, t, e.name, k, dict(e.stats)
                            if e.name.startswith(PREFIXES) else None))
    out.sort(key=lambda x: (x[0], -x[1]))
    return out


def events(run):
    if '_oncpu_events' not in run:
        run['_oncpu_events'] = _read_events(run)
    return run['_oncpu_events']


def timed(run, name):
    """[(wall_ns, cpu_ns, stats)] of the spans called `name` inside the
    window that carry `cpu_us`."""
    return [(t - s, st['cpu_us'] * 1e3, st)
            for s, t, n, _, st in events(run)
            if n == name and st is not None and 'cpu_us' in st]


def offcpu_share(spans):
    """100 x the time the thread was not running over the spans' time."""
    wall = sum(w for w, _, _ in spans)
    return 100.0 * sum(w - c for w, c, _ in spans) / wall if wall else None


def clock_step_ns(run):
    """The smallest movement of a thread's CPU clock that any program span
    of the trace saw: one step of it, or more. None where none moved."""
    moved = [st['cpu_us'] for _, _, _, _, st in events(run)
             if st is not None and st.get('cpu_us', 0) > 0]
    return min(moved) * 1e3 if moved else None


def resolves(run, what, cpu_ns, wall_ns):
    """Whether `cpu_ns`, a sum of spans' cpu_us, is a reading against the
    `wall_ns` it is taken from: its standard error, root(steps) x step of
    the clock, within MAX_SIGMA of the wall time. Says which, and how good
    the reading is, in points of that wall time."""
    step = clock_step_ns(run)
    if step is None or not wall_ns:
        harness.say('%s: no span saw the CPU clock move' % what)
        return False
    sigma = math.sqrt(max(cpu_ns / step, 1.0)) * step / wall_ns
    harness.say('%s: %s' % (what, 'a reading' if sigma <= MAX_SIGMA else
                            'NOT FILED, the CPU clock is too coarse for it'),
                clock_step_us=step / 1e3, steps=cpu_ns / step,
                wall_ms=wall_ns / 1e6, plus_minus_points=100.0 * sigma,
                filed_up_to_points=100.0 * MAX_SIGMA)
    return sigma <= MAX_SIGMA


class Row(object):
    """What one span name adds up to under the ticks."""
    __slots__ = ('n', 'wall', 'cpu', 'own_wall', 'own_cpu')

    def __init__(self):
        self.n = self.wall = self.cpu = self.own_wall = self.own_cpu = 0


def tick_table(run):
    """({span name: Row}, ticks) over the 'decode/tick' spans inside the
    window that carry `cpu_us` and every program span nested in them on
    their thread, in nanoseconds; a span's own time is its time minus its
    children's. ({}, 0) where no tick carries the stat."""
    table, ticks = {}, 0
    by_thread = {}
    for ev in events(run):
        if ev[4] is not None and 'cpu_us' in ev[4]:
            by_thread.setdefault(ev[3], []).append(ev)
    for evs in by_thread.values():
        stack = []      # [end, name, wall, cpu, children wall, children cpu]

        def close(until):
            while stack and stack[-1][0] <= until:
                _, name, wall, cpu, cw, cc = stack.pop()
                row = table.setdefault(name, Row())
                row.n += 1
                row.wall += wall
                row.cpu += cpu
                row.own_wall += wall - cw
                row.own_cpu += cpu - cc
                if stack:
                    stack[-1][4] += wall
                    stack[-1][5] += cpu

        for s, t, name, _, st in evs:
            close(s)
            if not stack and name != 'decode/tick':
                continue        # outside any tick: a caller's span
            ticks += name == 'decode/tick'
            stack.append([t, name, t - s, st['cpu_us'] * 1e3, 0, 0])
        close(float('inf'))
    return table, ticks


def say_tick_table(run):
    """The table under the tick, for people: per span name its calls, wall,
    on-CPU and off-CPU time and its own share of both, in ms a tick — the
    own wall times add up to the tick's wall time."""
    table, ticks = tick_table(run)
    if not ticks:
        return
    harness.say('under decode/tick, ms a tick over %d traced ticks '
                '(name: calls a tick | wall = on-CPU + off-CPU | own wall '
                '= own on-CPU + own off-CPU)' % ticks)
    per = 1e6 * ticks
    for name, r in sorted(table.items(), key=lambda kv: -kv[1].own_wall):
        harness.say('  %-22s %6.2f | %7.3f = %7.3f + %7.3f | %7.3f = %7.3f '
                    '+ %7.3f' % (name, r.n / ticks, r.wall / per,
                                 r.cpu / per, (r.wall - r.cpu) / per,
                                 r.own_wall / per, r.own_cpu / per,
                                 (r.own_wall - r.own_cpu) / per))
    harness.say('  sum of own wall %.3f against decode/tick %.3f; spans '
                'with more cpu_us than wall time: %d'
                % (sum(r.own_wall for r in table.values()) / per,
                   table['decode/tick'].wall / per,
                   sum(1 for s, t, _, _, st in events(run) if st is not None
                       and st.get('cpu_us', 0) * 1e3 > t - s)))
    gcs = [(t - s, st) for s, t, n, _, st in events(run) if n == 'py/gc']
    if gcs:
        longest, stats = max(gcs, key=lambda g: g[0])
        harness.say('  py/gc on any thread: %d collections, %.3f ms a tick, '
                    'the longest %.3f ms (generation %s)'
                    % (len(gcs), sum(w for w, _ in gcs) / per,
                       longest / 1e6, stats.get('generation')))


def say_inside_dispatch(run):
    """For people: 'decode/dispatch' by program (wall, on-CPU, off-CPU a
    call, what was handed over) and the runtime's own events nested in
    the spans (PjitFunction, ParseArguments, DevicePut, ...), by name."""
    evs = events(run)
    by_program, inside = {}, {}
    open_until, thread, calls = 0, None, 0
    for s, t, name, th, st in evs:
        if name == 'decode/dispatch' and st is not None and 'cpu_us' in st:
            by_program.setdefault(st.get('program'), []).append(
                (t - s, st['cpu_us'] * 1e3, st))
            open_until, thread, calls = t, th, calls + 1
        elif st is None and th == thread and t <= open_until:
            key = name.split('(')[0][:40]
            n, wall = inside.get(key, (0, 0))
            inside[key] = n + 1, wall + t - s
    for program, spans in sorted(by_program.items(), key=lambda kv: str(kv[0])):
        n = len(spans)
        harness.say(
            '  decode/dispatch %-10s' % program, calls=n,
            wall_us=sum(w for w, _, _ in spans) / n / 1e3,
            oncpu_us=sum(c for _, c, _ in spans) / n / 1e3,
            offcpu_us=sum(w - c for w, c, _ in spans) / n / 1e3,
            feeds=harness.median([st.get('feeds', 0) for _, _, st in spans]),
            feed_bytes=harness.median([st.get('feed_bytes', 0)
                                       for _, _, st in spans]))
    for key, (n, wall) in sorted(inside.items(), key=lambda kv: -kv[1][1])[:12]:
        harness.say('  inside decode/dispatch: %-40s' % key,
                    events_a_call=n / calls, us_a_call=wall / calls / 1e3)


# -- the tick log: every tick of the window, tracing off ---------------------

def tick_log(run):
    served = getattr(run.get('runner'), 'served', None)
    return getattr(getattr(getattr(served, 'pred', None), 'stats', None),
                   'tick_log', None)


def window_ticks(run):
    """The tick log's rows that began in the rate part of the window
    (t_open <= t0 < t_open + window_s), or None: no predictor, a program
    without the log, no tick there."""
    log = tick_log(run)
    if log is None:
        return None
    r = run['result']
    rows = log(since=r['t_open'])
    rows = rows[rows['t0'] < r['t_open'] + r['window_s']]
    return rows if len(rows) else None


def say_log_against_trace(run):
    """For people: the two instruments on the same ticks — a row of the
    log carries its tick's number, and so does the tick's span."""
    log = tick_log(run)
    spans = {st['tick']: (t - s, st['cpu_us'])
             for s, t, n, _, st in events(run)
             if n == 'decode/tick' and st is not None and 'cpu_us' in st}
    if log is None or not spans:
        return
    r = run['result']
    rows = log(since=r['t_open'] + r['window_s'])
    if 'tick' not in (rows.dtype.names or ()):
        return
    rows = rows[np.isin(rows['tick'], list(spans))]
    harness.say('traced ticks against their rows in the tick log',
                spans=len(spans), rows=len(rows),
                span_wall_s=sum(w for w, _ in spans.values()) / 1e9,
                row_wall_s=float(rows['wall_s'].sum()),
                span_cpu_s=sum(c for _, c in spans.values()) / 1e6,
                row_cpu_s=float(np.nansum(rows['cpu_s'])),
                row_cpu_over_s=float(np.nansum(rows['cpu_wall_s'])))


def share_of_wall(rows, seconds):
    wall = float(rows['wall_s'].sum())
    return 100.0 * float(seconds.sum()) / wall if wall else None


def oncpu_share(rows):
    """100 x the CPU time of the rows' readings over the busy seconds those
    readings span, or None where no row carries a reading."""
    spanned = float(np.nansum(rows['cpu_wall_s']))
    return 100.0 * float(np.nansum(rows['cpu_s'])) / spanned \
        if spanned else None


def name_of(row):
    """What a long tick was, from its row: most of it in python's
    collector, waiting for the device (or the runtime), or — by the CPU
    reading that ends in it, which spans at most 20 ms of the ticks before
    it too — on the CPU, or none of them: the thread, or the process, not
    running. A tick too short to carry a reading is not named."""
    wall = row['wall_s']
    if row['gc_s'] >= 0.5 * wall:
        return 'collector'
    if row['wait_s'] >= 0.5 * wall:
        return 'device/runtime'
    if np.isnan(row['cpu_s']):
        return 'no reading'
    if row['cpu_s'] >= 0.5 * row['cpu_wall_s']:
        return 'working'
    return 'not running'


def say_rows(msg, rows):
    for row in rows:
        harness.say(
            '  %s' % msg, at_s=row['t0'] - harness.T0,
            wall_ms=row['wall_s'] * 1e3, wait_ms=row['wait_s'] * 1e3,
            gc_ms=row['gc_s'] * 1e3, cpu_ms=row['cpu_s'] * 1e3,
            cpu_over_ms=row['cpu_wall_s'] * 1e3,
            dispatches=int(row['dispatches']), rows=int(row['rows']),
            was=name_of(row))
