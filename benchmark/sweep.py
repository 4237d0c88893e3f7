#!/usr/bin/env python3
"""Finds the knee of an open-loop cell, once, when the cell is defined:

    python3 benchmark/sweep.py --workload <cell> --rates 2,3,4,5,6 --seconds 30

runs the cell's traffic at each rate in turn on ONE loaded predictor and
prints one JSON line per rate. The knee is the highest rate at which the
backlog at the window's end is no larger than at its start and at least
90 % of the requests due in the window saw TTFT <= --ttft-limit-ms and a
mean inter-token gap <= --itl-limit-ms. The cell's traffic file then fixes
its rate at 0.8 x the knee, rounded down to 0.5 req/s; a check never
searches for a rate.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    import argparse
    from benchmark import run as run_mod
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=30.0)
    ap.add_argument('--ttft-limit-ms', type=float, default=1000.0)
    ap.add_argument('--itl-limit-ms', type=float, default=100.0)
    ap.add_argument('--rehearsal', action='store_true')
    args = ap.parse_args(argv)
    args.trace = 0
    ctx = run_mod.prepare(args)
    if isinstance(ctx, int):
        return ctx
    from benchmark import harness
    from benchmark.runners import decode_common, decode_open
    served = decode_common.Served(ctx)
    base = ctx.traffic
    for rate in [float(r) for r in args.rates.split(',')]:
        ctx.traffic = harness.overlay(
            base, {'arrivals': {'rate_per_s': rate}})
        runner = decode_open.Runner(ctx)
        runner.setup(served)
        r = runner.window(args.seconds)
        due = runner.due_in      # by the schedule; None = never submitted
        met = 0
        for x, ttft in zip(due, r['ttft_ms']):
            if x is None:
                continue
            t = x['times']
            mean_itl = ((t[-1] - t[0]) / (len(t) - 1) * 1e3
                        if len(t) > 1 else 0.0)
            met += (x['error'] is None and bool(t)
                    and ttft <= args.ttft_limit_ms
                    and mean_itl <= args.itl_limit_ms)

        def backlog(t):      # submitted and not finished at instant t
            return sum(1 for x in runner.records if x is not None
                       and x['submit'] <= t
                       and (x['done'] is None or x['done'] > t))
        c = r['counters_window']
        line = {
            'rate_per_s': rate, 'due': len(due), 'failed': r['failed'],
            'share_meeting_limits': met / max(len(due), 1),
            'backlog_open': backlog(r['t_open']),
            'backlog_close': backlog(r['t_close']),
            'tokens_per_s': r['tokens_per_s'],
            'ttft_p50_ms': harness.median(r['ttft_ms']),
            'ttft_p95_ms': harness.percentile(r['ttft_ms'], 95, 0),
            'itl_p50_ms': harness.median(r['itl_ms']),
            'itl_p99_ms': harness.percentile(r['itl_ms'], 99, 0),
            'slot_occupancy': 100.0 * c['active_slot_steps']
            / max(c['slot_steps'], 1),
            'steps': c['steps'], 'chunk_slices': c['chunk_slices'],
            'lag_p99_ms': harness.percentile(r['generator_lag_ms'], 99, 0),
        }
        print('SWEEP ' + json.dumps(line), flush=True)
        # let what is left finish before the next rate
        t_end = time.perf_counter() + 60
        while time.perf_counter() < t_end and \
                served.pred.stats.snapshot()['queue_depth']:
            time.sleep(0.2)
        time.sleep(1.0)
    served.close()
    return 0


if __name__ == '__main__':
    sys.exit(main())
