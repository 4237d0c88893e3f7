#!/usr/bin/env python
"""Measure the HTTP gateway's wire overhead (ISSUE 19, PERF_NOTES
round 21).

    python scripts/gateway_bench.py [N]

Four closed-loop arms over the same decode artifact, same prompts,
same max_new_tokens (sequential, so the numbers are per-request
latency, not throughput):

  direct            DecodingPredictor.submit().result()   (in-process)
  gateway/direct    POST /v1/decode stream=false over HTTP loopback
  fleet             FleetRouter.submit().result()         (1 replica)
  gateway/fleet     POST /v1/decode stream=false -> FleetRouter

plus one SSE arm (gateway/direct, stream=true) so the streaming path's
first-token and total latency are on the record. Prints a markdown
table of p50/p99 per arm and the gateway-minus-backend delta — the
price of the HTTP door.
"""
import json
import os
import sys
import tempfile
import time
import urllib.request
import warnings

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.inference import (DecodingPredictor,  # noqa: E402
                                  FleetRouter, Gateway, export_decode)

VOCAB = 211
MAX_NEW = 24


def _export(art):
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(vocab=VOCAB, d_model=48, n_head=4,
                                 n_layer=2, d_ff=96, max_slots=4,
                                 max_cache_len=128, chunk_sizes=(4, 8),
                                 block_size=16,
                                 eos_id=1)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'])
        export_decode(spec, art, scope=scope)


def _prompts(n, seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, VOCAB, rng.randint(2, 9)) for _ in range(n)]


def _pcts(ms):
    a = np.sort(np.asarray(ms))
    return (float(np.percentile(a, 50)), float(np.percentile(a, 99)))


def _bench_backend(target, prompts):
    ms = []
    for p in prompts:
        t0 = time.perf_counter()
        target.submit(p, max_new_tokens=MAX_NEW).result(300)
        ms.append((time.perf_counter() - t0) * 1e3)
    return ms


def _bench_http(url, prompts, stream):
    ms = []
    for p in prompts:
        body = json.dumps({'prompt': [int(t) for t in p],
                           'max_new_tokens': MAX_NEW,
                           'stream': stream}).encode()
        req = urllib.request.Request(url + '/v1/decode', data=body,
                                     method='POST')
        req.add_header('Content-Type', 'application/json')
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=300) as r:
            r.read()
        ms.append((time.perf_counter() - t0) * 1e3)
    return ms


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200
    tmp = tempfile.mkdtemp(prefix='ptpu_gateway_bench_')
    art = os.path.join(tmp, 'decode_art')
    _export(art)
    warm, prompts = _prompts(16, seed=3), _prompts(n)
    rows = []

    with DecodingPredictor(art, platform='cpu') as pred:
        pred.warmup()
        _bench_backend(pred, warm)
        direct = _bench_backend(pred, prompts)
        rows.append(('direct', _pcts(direct), None))
        with Gateway(pred) as gw:
            _bench_http(gw.url, warm, stream=False)
            gw_direct = _bench_http(gw.url, prompts, stream=False)
            rows.append(('gateway/direct', _pcts(gw_direct), 'direct'))
            _bench_http(gw.url, warm, stream=True)
            gw_sse = _bench_http(gw.url, prompts, stream=True)
            rows.append(('gateway/direct SSE', _pcts(gw_sse), 'direct'))

    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with FleetRouter(art, replicas=1, platform='cpu',
                         inflight_per_replica=4) as router:
            router.hb_timeout_s = 60.0
            _bench_backend(router, warm)
            fleet = _bench_backend(router, prompts)
            rows.append(('fleet', _pcts(fleet), None))
            with Gateway(router) as gw:
                _bench_http(gw.url, warm, stream=False)
                gw_fleet = _bench_http(gw.url, prompts, stream=False)
                rows.append(('gateway/fleet', _pcts(gw_fleet), 'fleet'))

    base = {name: p for name, p, _ in rows}
    print('\n%d sequential requests/arm, %d new tokens each '
          '(CPU dispatch-floor proxy)\n' % (n, MAX_NEW))
    print('| arm                | p50 ms | p99 ms | door cost p50 | p99 |')
    print('|--------------------|-------:|-------:|--------------:|----:|')
    for name, (p50, p99), ref in rows:
        if ref:
            d50, d99 = p50 - base[ref][0], p99 - base[ref][1]
            print('| %-18s | %6.2f | %6.2f | %+12.2f | %+3.2f |'
                  % (name, p50, p99, d50, d99))
        else:
            print('| %-18s | %6.2f | %6.2f | %13s | %3s |'
                  % (name, p50, p99, '-', '-'))
    print()


if __name__ == '__main__':
    main()
