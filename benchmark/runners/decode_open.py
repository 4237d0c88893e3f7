"""Runner 'decode_open': independent users of one model replica. Requests
fall due on a schedule drawn from the seed (the traffic file's arrival
process at its FIXED rate) and are submitted then, whether or not earlier
ones have finished. Every latency is taken from the instant a request was
DUE, on the consumer side, and how late submit() ran is reported.

What the run is held to is the SCHEDULE, not what got submitted: a request
that fell due in the window and was never submitted (the generator lagged,
blocked or died) is attempted, failed, and counts with the worst TTFT and
lag; without tracing the window must hold exactly round(rate x seconds)
requests or the run refuses to report. An exception on the generator's or
a consumer's thread is kept and makes the run not correct.

A ramp of the same traffic runs as set-up for `ramp_seconds`. After the
window, requests due inside it are followed until each has its first token,
up to `drain_seconds`: one that has none by then, or that ended in an
error, is failed; one that is still streaming is cut (its gaps after the
window would not count anyway, and following a 512-token answer to its end
would add 45 s of chip time to every run of every check).

Traffic file fields: runner, arrivals, prompt_len, output_len,
shared_prefix (optional), ramp_seconds, drain_seconds, consumers,
trace_seconds.
"""
from __future__ import annotations

import queue
import threading
import time

from .. import harness, traffic as traffic_gen
from ..harness import say
from . import decode_common as common


class Runner(object):
    def __init__(self, ctx):
        self.ctx = ctx
        self.result = {}
        self.served = None
        self.stop = threading.Event()
        self.thread_errors = []     # (thread name, exception)

    def setup(self, served=None):
        """`served`: a predictor that is already up (the knee sweep runs
        several rates on one); by default the runner loads its own."""
        ctx = self.ctx
        self.served = served or common.Served(ctx)
        self.ramp = float(ctx.traffic['ramp_seconds'])
        self.schedule = traffic_gen.open_schedule(
            ctx.traffic, ctx.seed, self.ramp, ctx.seconds, self.served.vocab)
        self.records = [None] * len(self.schedule)
        self.work = queue.Queue()
        n = int(ctx.traffic.get('consumers',
                                2 * self.served.pred.max_slots))
        self.consumers = [threading.Thread(target=self._consume,
                                           name='bench-consumer-%d' % i,
                                           daemon=True) for i in range(n)]
        for t in self.consumers:
            t.start()
        self.t_first = time.perf_counter() + 0.05
        self.gen = threading.Thread(target=self._generate,
                                    name='bench-generator', daemon=True)
        self.gen.start()
        time.sleep(max(self.t_first + self.ramp - time.perf_counter(), 0))
        say('open loop ramped', rate=ctx.traffic['arrivals']['rate_per_s'],
            **self.served.counters())

    def _guarded(self, body):
        """Run a thread's body; an exception is kept, never lost."""
        try:
            body()
        except Exception as e:
            self.thread_errors.append((threading.current_thread().name, e))

    def _generate(self):
        self._guarded(self._submit_on_schedule)

    def _submit_on_schedule(self):
        """Submit each request at its due time, from this one thread."""
        served, spans = self.served, self.ctx.spans
        for i, (due, prompt, max_new) in enumerate(self.schedule):
            t_due = self.t_first + due
            while True:
                wait = t_due - time.perf_counter()
                if wait <= 0 or self.stop.is_set():
                    break
                time.sleep(min(wait, 0.05) if wait > 0.002 else 0)
            if self.stop.is_set():
                return
            t_sub = time.perf_counter()
            with spans.span('submit'):
                stream = served.pred.submit(prompt, max_new_tokens=max_new)
            rec = {'due': t_due, 'submit': t_sub, 'times': [],
                   'error': None, 'done': None, 'stream': stream,
                   'max_new': max_new, 'plen': len(prompt)}
            self.records[i] = rec
            self.work.put(rec)

    def _consume(self):
        self._guarded(self._consume_streams)

    def _consume_streams(self):
        while True:
            rec = self.work.get()
            if rec is None:
                return
            rec['error'] = self.served.consume(rec['stream'], rec['times'])
            rec['done'] = time.perf_counter()

    def window(self, seconds):
        from paddle_tpu.core import compile_cache
        ctx, served = self.ctx, self.served
        traced_s = ctx.trace_seconds() if ctx.trace else 0.0
        t_open = self.t_first + self.ramp
        cc0 = compile_cache.stats()
        c0 = served.counters()
        time.sleep(max(t_open + seconds - traced_s - time.perf_counter(), 0))
        # with tracing on, the consumer-side numbers come from the part of
        # the window before it: starting and stopping the profiler stalls
        # every python thread, the generator among them
        t_rate_end = time.perf_counter() if ctx.trace else t_open + seconds
        traced = None
        if ctx.trace:
            traced = common.sample_traced(
                served, ctx.tracer,
                max(t_open + seconds - time.perf_counter(), 0.1))
        time.sleep(max(t_open + seconds - time.perf_counter(), 0))
        t_close = t_open + seconds
        c1 = served.counters()
        cc1 = compile_cache.stats()
        self.gen.join(5)
        # what fell due in the window, by the schedule: a request that was
        # never submitted has no record and is failed at the worst value
        lo = self.ramp
        hi = (t_rate_end - self.t_first) if ctx.trace else lo + seconds
        due_idx = [i for i, (due, _, _) in enumerate(self.schedule)
                   if lo <= due < hi]
        offered = int(round(float(
            ctx.traffic['arrivals']['rate_per_s']) * seconds))
        if not ctx.trace and len(due_idx) != offered:
            raise RuntimeError(
                'the schedule holds %d requests due in the window, the cell '
                'offers %d: not the same work' % (len(due_idx), offered))
        due_in = self.due_in = [self.records[i] for i in due_idx]
        # follow them until each has its first token (or has ended), up to
        # the drain limit; what is then still streaming is cut
        limit = time.perf_counter() + float(ctx.traffic['drain_seconds'])
        while time.perf_counter() < limit and any(
                r is not None and r['done'] is None and not r['times']
                for r in due_in):
            time.sleep(0.02)
        self.stop.set()
        cut = [r for r in self.records
               if r is not None and r['done'] is None]
        for r in cut:
            r['cut'] = True
            r['stream'].cancel()
        for _ in self.consumers:
            self.work.put(None)
        for t in self.consumers + [self.gen]:
            t.join(60)
            if t.is_alive():
                self.thread_errors.append(
                    (t.name, RuntimeError('thread did not end')))
        worst = (seconds + float(ctx.traffic['drain_seconds'])) * 1e3
        ttft, lag, failed = [], [], 0
        for r in due_in:
            if r is None:
                failed += 1
                ttft.append(worst)
                lag.append(worst)
                continue
            failed += bool(not r['times'] or (r['error'] is not None
                                              and not r.get('cut')))
            ttft.append(worst if not r['times']
                        else (r['times'][0] - r['due']) * 1e3)
            lag.append((r['submit'] - r['due']) * 1e3)
        all_recs = [r for r in self.records if r is not None]
        streams = [r['times'] for r in all_recs]
        self.result = {
            'window_s': t_rate_end - t_open,
            'ttft_ms': ttft, 'generator_lag_ms': lag,
            'itl_ms': common.itl_gaps_ms(streams, t_open, t_rate_end),
            'tokens': common.tokens_in(streams, t_open, t_rate_end),
            'attempted': len(due_in), 'failed': failed,
            'counters_window': common.delta(c0, c1),
            'compiles_in_window':
                cc1['xla_compiles_net'] - cc0['xla_compiles_net'],
            't_open': t_open, 't_close': t_close,
        }
        if traced is not None:
            t0, t1, (lo, hi) = traced
            self.result['counters_traced'] = common.delta(t0, t1)
            # what the configuration's step_floor_seconds is asked about
            self.result['floor_arg'] = common.cached_rows(all_recs, lo, hi)
        r = self.result
        r['tokens_per_s'] = r['tokens'] / r['window_s']
        say('open window', due=len(due_in), failed=failed,
            tokens_per_s=r['tokens_per_s'],
            ttft_p50_ms=harness.median(ttft) if ttft else -1,
            itl_p50_ms=harness.median(r['itl_ms']) if r['itl_ms'] else -1,
            lag_max_ms=max(lag) if lag else -1,
            blocks_open=c0['blocks_in_use'], blocks_close=c1['blocks_in_use'],
            **r['counters_window'])
        return r

    def verify(self):
        return common.verify(self.served, self.result, self.thread_errors)

    def close(self):
        self.stop.set()
        if self.served is not None:
            self.served.close()
