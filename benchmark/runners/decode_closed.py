"""Runner 'decode_closed': offline batch generation. `clients` callers (the
predictor's max_slots when the traffic file says "max_slots") each submit
their next request the moment the last one ends. No arrival schedule, so
admission and queueing are bypassed and the device step does the work.

The first requests of all clients arrive together; the window opens after
`ramp_seconds` of the same traffic, once their lifetimes have spread.

Traffic file fields: runner, clients, prompt_len, output_len,
shared_prefix (optional), ramp_seconds, trace_seconds.
"""
from __future__ import annotations

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

    def setup(self):
        ctx = self.ctx
        self.served = common.Served(ctx)
        n = ctx.traffic['clients']
        self.n_clients = (self.served.pred.max_slots if n == 'max_slots'
                          else int(n))
        # per finished (or cut) request: (submit, [token times], error)
        self.records = []
        self.threads = [threading.Thread(target=self._client, args=(i,),
                                         name='bench-client-%d' % i,
                                         daemon=True)
                        for i in range(self.n_clients)]
        self.requests = [traffic_gen.closed_requests(
            ctx.traffic, ctx.seed, i, self.served.vocab)
            for i in range(self.n_clients)]
        for t in self.threads:
            t.start()
        time.sleep(float(ctx.traffic['ramp_seconds']))
        say('closed loop ramped', clients=self.n_clients,
            **self.served.counters())

    def _client(self, i):
        """A client's thread; an exception on it is kept, never lost."""
        try:
            self._submit_and_wait(i)
        except Exception as e:
            self.thread_errors.append((threading.current_thread().name, e))

    def _submit_and_wait(self, i):
        served = self.served
        for prompt, max_new in self.requests[i]:
            if self.stop.is_set():
                return
            t_sub = time.perf_counter()
            stream = served.pred.submit(prompt, max_new_tokens=max_new)
            rec = {'submit': t_sub, 'times': [], 'error': None,
                   'stream': stream, 'done': None, 'plen': len(prompt)}
            self.records.append(rec)
            err = served.consume(stream, rec['times'])
            rec['done'] = time.perf_counter()
            if err is not None and not self.stop.is_set():
                rec['error'] = err

    def window(self, seconds):
        from paddle_tpu.core import compile_cache
        ctx, served = self.ctx, self.served
        traced_s = ctx.trace_seconds() if ctx.trace else 0.0
        cc0 = compile_cache.stats()
        c0 = served.counters()
        t_open = time.perf_counter()
        time.sleep(seconds - traced_s)
        t_rate_end = time.perf_counter()
        c_rate = served.counters()
        traced = None
        if ctx.trace:
            traced = common.sample_traced(served, ctx.tracer, traced_s)
        t_close = time.perf_counter()
        cc1 = compile_cache.stats()
        recs = list(self.records)
        # in-flight streams hold their times on the client threads: stop,
        # cut them, and read every record once the threads have ended
        self.stop.set()
        for r in recs:
            if not r['done']:
                r['stream'].cancel()
        for t in self.threads:
            t.join(120)
            if t.is_alive():
                self.thread_errors.append(
                    (t.name, RuntimeError('thread did not end')))
        streams = [list(r['times']) for r in recs]
        done_in = [r for r in recs if r['done'] and not r['error']
                   and t_open <= r['done'] < t_rate_end]
        failed = [r for r in recs if r['error'] is not None]
        ttft = [(r['times'][0] - r['submit']) * 1e3 for r in recs
                if r['times'] and t_open <= r['times'][0] < t_rate_end]
        self.result = {
            'window_s': t_rate_end - t_open,
            'tokens': common.tokens_in(streams, t_open, t_rate_end),
            'itl_ms': common.itl_gaps_ms(streams, t_open, t_rate_end),
            'ttft_ms': ttft,
            'attempted': (len(done_in) + len(failed)
                          + len(self.thread_errors)),
            'failed': len(failed) + len(self.thread_errors),
            'counters_window': common.delta(c0, c_rate),
            'compiles_in_window':
                cc1['xla_compiles_net'] - cc0['xla_compiles_net'],
            't_open': t_open, 't_close': t_close,
        }
        if traced is not None:
            t0, t1, (lo, hi) = traced
            self.result['counters_traced'] = common.delta(t0, t1)
            # what the configuration's step_floor_seconds is asked about
            self.result['floor_arg'] = common.cached_rows(recs, lo, hi)
        r = self.result
        r['tokens_per_s'] = r['tokens'] / r['window_s']
        say('closed window', tokens_per_s=r['tokens_per_s'],
            requests_done=len(done_in), failed=len(failed),
            itl_p50_ms=harness.median(r['itl_ms']) if r['itl_ms'] else -1,
            **r['counters_window'])
        return r

    def verify(self):
        return common.verify(self.served, self.result, self.thread_errors)

    def close(self):
        self.stop.set()
        if self.served is not None:
            self.served.close()
