#!/usr/bin/env python
"""Smoke the HTTP serving gateway (ISSUE 19 CI satellite).

    python scripts/gateway_smoke.py

Asserts, on the CPU dispatch-floor proxy:

  A. END-TO-END SERVE — `serve.py gateway` brings a 2-replica decode
     fleet up behind HTTP; SSE streams come back BYTE-IDENTICAL to a
     direct in-process DecodingPredictor, token-for-token; a dense
     /v1/infer npz round trip is bit-exact against Predictor.run.
  B. ADMISSION — unknown API key 401s; a burst-1 tenant's second
     request 429s with Retry-After; a zero-quota tenant 429s; none of
     these ever reach the fleet.
  C. CHAOS — SIGKILL one replica while SSE streams are mid-flight:
     only that replica's in-flight streams end in an `event: error`
     502 (loud, request_id attached), every surviving stream completes
     bit-identical, and the gateway keeps serving on the survivor.
  D. DRAIN — SIGTERM the serving process while streams are in flight:
     every in-flight stream runs to its `done` event (zero dropped),
     the process exits 0, and the listener is gone afterwards.

Exits non-zero on any failed bar.
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
import warnings

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.inference import (BatchingPredictor, Config,  # noqa: E402
                                  DecodingPredictor, FleetRouter,
                                  Gateway, create_predictor,
                                  export_compiled, export_decode)
from paddle_tpu.inference import gateway as gateway_mod  # noqa: E402

VOCAB = 211
MAX_NEW = 24


def _export_decode_artifact(art):
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


def _prompts(n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, VOCAB, rng.randint(2, 9)) for _ in range(n)]


def _post(url, path, body, key=None, rid=None, timeout=300):
    req = urllib.request.Request(url + path,
                                 data=json.dumps(body).encode(),
                                 method='POST')
    req.add_header('Content-Type', 'application/json')
    if key:
        req.add_header('X-API-Key', key)
    if rid:
        req.add_header('X-Request-Id', rid)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read().decode('utf-8')
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read().decode('utf-8')


def _sse(raw):
    """-> (tokens, done-dict-or-None, error-dict-or-None)."""
    toks, done, err = [], None, None
    for block in raw.strip().split('\n\n'):
        ev, data = None, None
        for line in block.split('\n'):
            if line.startswith('event: '):
                ev = line[len('event: '):]
            elif line.startswith('data: '):
                data = json.loads(line[len('data: '):])
        if ev is None and data and 'toks' in data:
            toks.extend(data['toks'])
        elif ev == 'done':
            done = data
        elif ev == 'error':
            err = data
    return toks, done, err


def _decode_body(prompt, **kw):
    body = {'prompt': [int(t) for t in prompt], 'max_new_tokens': MAX_NEW}
    body.update(kw)
    return body


def part_a_dense_infer(tmp):
    """Dense /v1/infer: base64-npz feeds over HTTP, outputs bit-exact
    against the direct predictor."""
    art = os.path.join(tmp, 'dense_art')
    with fluid.scope_guard(fluid.core.Scope()), fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name='img', shape=[16],
                                    dtype='float32')
            h = fluid.layers.fc(img, 32, act='relu')
            out = fluid.layers.fc(h, 8, act='softmax')
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        model_dir = os.path.join(tmp, 'dense_model')
        fluid.io.save_inference_model(model_dir, ['img'], [out], exe,
                                      main)
        pred = create_predictor(Config(model_dir))
        x = np.random.RandomState(3).randn(8, 16).astype(np.float32)
        export_compiled(pred, [x], art, batch_sizes=[8])
    ref, = pred.run([x])
    with BatchingPredictor(art, platform='cpu') as bp:
        bp.warmup()
        with Gateway(bp) as gw:
            code, _, raw = _post(
                gw.url, '/v1/infer',
                {'npz': gateway_mod.encode_arrays({'img': x})})
            assert code == 200, raw[:300]
            outs = gateway_mod.decode_arrays(json.loads(raw)['npz'])
    assert np.array_equal(outs['o0'], ref), \
        'dense infer over HTTP must be bit-exact'
    print('A. dense /v1/infer npz round trip bit-exact vs '
          'Predictor.run (batch 8)')


def part_a_b_serve_and_admission(art, want, prompts):
    tenants_path = os.path.join(os.path.dirname(art), 'tenants.json')
    with open(tenants_path, 'w') as f:
        json.dump({
            'k-admin': {'tenant': 'admin', 'admin': True},
            'k-burst1': {'tenant': 'burst1', 'rate': 0.001, 'burst': 1},
            'k-zero': {'tenant': 'zero', 'max_inflight': 0},
        }, f)
    serve = os.path.join(REPO, 'paddle_tpu', 'inference', 'serve.py')
    proc = subprocess.Popen(
        [sys.executable, serve, 'gateway', art, '0', '--replicas', '2',
         '--tenants', tenants_path],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO)
    hello = {}

    def _read_hello():
        hello['line'] = proc.stdout.readline()

    t = threading.Thread(target=_read_hello, daemon=True)
    t.start()
    t.join(300)
    assert hello.get('line'), 'serve.py gateway never printed its URL'
    url = json.loads(hello['line'])['url']

    with urllib.request.urlopen(url + '/healthz', timeout=30) as r:
        health = json.loads(r.read().decode())
    assert health['ok'] and health['kind'] == 'decoding', health

    t0 = time.perf_counter()
    n_tok = 0
    for i, p in enumerate(prompts[:24]):
        code, hdrs, raw = _post(url, '/v1/decode', _decode_body(p),
                                key='k-admin', rid='smoke-%d' % i)
        assert code == 200, raw[:300]
        assert hdrs.get('X-Request-Id') == 'smoke-%d' % i
        toks, done, err = _sse(raw)
        assert err is None, err
        assert toks == want[i] and done['tokens'] == want[i], \
            'stream %d diverged from the direct predictor' % i
        n_tok += len(toks)
    dt = time.perf_counter() - t0
    print('A. serve.py gateway up at %s: 24/24 SSE streams '
          'byte-identical to the direct predictor (%d tokens, %.2fs)'
          % (url, n_tok, dt))

    code, _, raw = _post(url, '/v1/decode', _decode_body(prompts[0]))
    assert code == 401, 'no key must 401, got %d' % code
    code, _, _ = _post(url, '/v1/decode', _decode_body(prompts[0]),
                       key='k-wrong')
    assert code == 401
    code, _, _ = _post(url, '/v1/decode',
                       _decode_body(prompts[0], stream=False),
                       key='k-burst1')
    assert code == 200
    code, hdrs, raw = _post(url, '/v1/decode', _decode_body(prompts[0]),
                            key='k-burst1')
    assert code == 429, 'burst-1 second request must 429, got %d' % code
    assert float(hdrs.get('Retry-After', 0)) >= 1
    code, _, _ = _post(url, '/v1/decode', _decode_body(prompts[0]),
                       key='k-zero')
    assert code == 429, 'zero-quota tenant must 429, got %d' % code
    with urllib.request.urlopen(url + '/metrics', timeout=30) as r:
        metrics = r.read().decode()
    assert 'ptpu_gateway_requests_total' in metrics
    assert 'ptpu_fleet_' in metrics
    print('B. admission: 401 unknown key, 429 + Retry-After on the '
          'burst-1 tenant, 429 on the zero-quota tenant; /metrics '
          'exposes gateway + fleet counters')
    return proc, url


def part_c_chaos(art, want, prompts):
    results = [None] * 16
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        router = FleetRouter(art, replicas=2, platform='cpu',
                             hb_timeout_s=3.0, inflight_per_replica=4)
        with Gateway(router) as gw:
            def one(i):
                code, _, raw = _post(gw.url, '/v1/decode',
                                     _decode_body(prompts[i]),
                                     rid='chaos-%d' % i)
                results[i] = (code, _sse(raw))

            threads = [threading.Thread(target=one, args=(i,),
                                        daemon=True)
                       for i in range(16)]
            for t in threads:
                t.start()
            time.sleep(0.05)  # streams mid-flight
            victim = max(router._replicas.values(),
                         key=lambda r: len(r.outstanding)
                         if r.state == 'serving' else -1).rid
            os.kill(router._replicas[victim].proc.pid, signal.SIGKILL)
            for t in threads:
                t.join(300)
            assert all(not t.is_alive() for t in threads)
            ok, failed = [], []
            for i, (code, (toks, done, err)) in enumerate(results):
                if code == 502:
                    # failed before the first token: clean HTTP 502
                    failed.append(i)
                    continue
                assert code == 200, 'stream %d: HTTP %d' % (i, code)
                if err is not None:
                    # failed mid-stream: loud SSE error event
                    assert err['code'] == 502, err
                    assert err['request_id'] == 'chaos-%d' % i
                    failed.append(i)
                else:
                    assert toks == want[i] and done['tokens'] == want[i]
                    ok.append(i)
            assert len(failed) <= 4, \
                'only the victim\'s in-flight streams may 502: %r' % failed
            assert len(ok) + len(failed) == 16
            # the gateway keeps serving on the survivor
            code, _, raw = _post(gw.url, '/v1/decode',
                                 _decode_body(prompts[0]))
            toks, done, err = _sse(raw)
            assert code == 200 and err is None and toks == want[0]
            snap = gw.snapshot()
            assert snap['failed'] == len(failed)
        router.close()
    print('C. chaos SIGKILL replica %d mid-stream: %d/16 streams '
          'completed bit-identical, %d ended in a loud 502, '
          'gateway kept serving on the survivor'
          % (victim, len(ok), len(failed)))


def part_d_drain(proc, url, want, prompts):
    streams = [None] * 8
    body = [_decode_body(p, max_new_tokens=96) for p in prompts[:8]]

    def one(i):
        try:
            code, _, raw = _post(url, '/v1/decode', body[i],
                                 key='k-admin')
            streams[i] = (code, _sse(raw))
        except Exception as e:  # loud placeholder, not a None unpack
            streams[i] = (type(e).__name__, ([], None, None))

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    # SIGTERM only once all 8 streams are provably admitted — drain
    # must then finish every one of them
    deadline = time.time() + 60
    while time.time() < deadline:
        with urllib.request.urlopen(url + '/healthz', timeout=30) as r:
            if int(json.loads(r.read().decode())['inflight']) >= 8:
                break
        time.sleep(0.02)
    else:
        raise AssertionError('8 streams never went in-flight')
    proc.send_signal(signal.SIGTERM)
    for t in threads:
        t.join(300)
    assert all(not t.is_alive() for t in threads)
    dropped = [i for i, (code, (toks, done, err)) in enumerate(streams)
               if code != 200 or done is None or err is not None]
    assert not dropped, \
        'drain must finish every in-flight stream: dropped %r' % dropped
    _out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, \
        'drained gateway must exit 0: rc=%s\n%s' \
        % (proc.returncode, err[-2000:])
    try:
        urllib.request.urlopen(url + '/healthz', timeout=5)
        raise AssertionError('listener must be gone after drain')
    except (urllib.error.URLError, ConnectionError, OSError):
        pass
    print('D. SIGTERM drain: 8/8 in-flight streams ran to their done '
          'event (zero dropped), process exited 0, listener gone')


def main():
    t0 = time.time()
    tmp = tempfile.mkdtemp(prefix='ptpu_gateway_smoke_')
    art = os.path.join(tmp, 'decode_art')
    _export_decode_artifact(art)
    prompts = _prompts(24, seed=5)
    with DecodingPredictor(art, platform='cpu') as ref:
        want = [[int(t) for t in ref.generate(p, max_new_tokens=MAX_NEW)]
                for p in prompts]

    part_a_dense_infer(tmp)
    proc, url = part_a_b_serve_and_admission(art, want, prompts)
    try:
        part_c_chaos(art, want, prompts)
        part_d_drain(proc, url, want, prompts)
    finally:
        if proc.poll() is None:
            proc.kill()
    print('GATEWAY SMOKE OK (%.0fs)' % (time.time() - t0))


if __name__ == '__main__':
    main()
