"""HTTP serving gateway (ISSUE 19): codec round trips, SSE streaming
byte-identity vs a direct DecodingPredictor, multi-tenant admission
(API keys, token-bucket 429s, inflight quotas), the full error-code
contract (never a silent drop), deadline propagation shed at all three
sites (gateway door / router queue / mid-decode), graceful drain,
Prometheus /metrics validity, the profiler gateway table, and the
gateway_ctl CLI.

The acceptance scenario rides a 2-replica decode fleet: a 64-request
mixed-tenant Poisson run where every request resolves to an HTTP
status and the per-tenant ledgers reconcile with the fleet's
served/shed totals.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.inference import (BatchingPredictor, Config,
                                  DecodingPredictor, FleetRouter,
                                  Gateway, TenantConfig,
                                  create_predictor, export_compiled,
                                  export_decode, render_metrics,
                                  tenants_from_json)
from paddle_tpu.inference import gateway as gateway_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 8
VOCAB = 61


@pytest.fixture(scope='module')
def dense_art(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp('gw_dense'))
    with fluid.scope_guard(fluid.core.Scope()), fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name='img', shape=[DIM],
                                    dtype='float32')
            h = fluid.layers.fc(img, 32, act='relu')
            out = fluid.layers.fc(h, 4, act='softmax')
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        model_dir = os.path.join(tmp, 'model')
        fluid.io.save_inference_model(model_dir, ['img'], [out], exe,
                                      main)
        pred = create_predictor(Config(model_dir))
        x0 = np.random.RandomState(3).randn(8, DIM).astype(np.float32)
        art = os.path.join(tmp, 'art')
        export_compiled(pred, [x0], art, batch_sizes=[8])
    return {'art': art, 'pred': pred}


@pytest.fixture(scope='module')
def decode_art(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp('gw_decode'))
    art = os.path.join(tmp, 'decode')
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(vocab=VOCAB, d_model=8, n_head=2,
                                 n_layer=1, d_ff=16, max_slots=4,
                                 max_cache_len=40, chunk_sizes=(4,),
                                 block_size=4,
                                 eos_id=1)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'])
        export_decode(spec, art, scope=scope)
    return art


@pytest.fixture(scope='module')
def direct_pred(decode_art):
    with DecodingPredictor(decode_art, platform='cpu') as pred:
        pred.warmup()
        yield pred


@pytest.fixture(scope='module')
def decode_fleet(decode_art):
    """One 2-replica decode fleet shared by the fleet-backed tests."""
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        router = FleetRouter(decode_art, replicas=2, platform='cpu',
                             inflight_per_replica=4)
        router.hb_timeout_s = 60.0  # busy-CI != hung (test_fleet idiom)
        yield router
        router.close()


def _prompts(n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, VOCAB, rng.randint(2, 5)) for _ in range(n)]


def _req(url, path, body=None, key=None, rid=None, method=None):
    """One HTTP round trip -> (status, headers, parsed-or-raw body).
    HTTP errors come back as a status, never an exception: the tests
    assert the full error-code contract."""
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(
        url + path, data=data,
        method=method or ('POST' if body is not None else 'GET'))
    if body is not None:
        r.add_header('Content-Type', 'application/json')
    if key:
        r.add_header('X-API-Key', key)
    if rid:
        r.add_header('X-Request-Id', rid)
    try:
        with urllib.request.urlopen(r, timeout=120) as resp:
            raw = resp.read().decode('utf-8')
            ctype = resp.headers.get('Content-Type', '')
            hdrs = dict(resp.headers)
            return resp.status, hdrs, (json.loads(raw)
                                       if 'json' in ctype else raw)
    except urllib.error.HTTPError as e:
        raw = e.read().decode('utf-8')
        try:
            parsed = json.loads(raw)
        except ValueError:
            parsed = raw
        return e.code, dict(e.headers), parsed


def _sse_events(raw):
    """Parse one SSE response body -> [(event-or-None, data dict)]."""
    out = []
    for block in raw.strip().split('\n\n'):
        ev, data = None, None
        for line in block.split('\n'):
            if line.startswith('event: '):
                ev = line[len('event: '):]
            elif line.startswith('data: '):
                data = json.loads(line[len('data: '):])
        out.append((ev, data))
    return out


def _sse_tokens(raw):
    evs = _sse_events(raw)
    toks = [t for ev, d in evs if ev is None and d and 'toks' in d
            for t in d['toks']]
    done = [d for ev, d in evs if ev == 'done']
    errs = [d for ev, d in evs if ev == 'error']
    return toks, (done[0] if done else None), (errs[0] if errs else None)


# -- codec units -------------------------------------------------------------

def test_npz_codec_roundtrip():
    arrays = {'a': np.arange(12, dtype=np.float32).reshape(3, 4),
              'b': np.array([1, 2, 3], np.int64)}
    got = gateway_mod.decode_arrays(gateway_mod.encode_arrays(arrays))
    for k in arrays:
        np.testing.assert_array_equal(got[k], arrays[k])


def test_feeds_from_arrays_lod_convention():
    feeds = gateway_mod._feeds_from_arrays({
        'w': np.arange(5, dtype=np.float32),
        'w.lod0': np.array([0, 2, 5], np.int32),
        'x': np.ones(3, np.float32)})
    data, offs = feeds['w']
    np.testing.assert_array_equal(offs[0], [0, 2, 5])
    assert isinstance(feeds['x'], np.ndarray)
    with pytest.raises(ValueError):
        gateway_mod._feeds_from_arrays(
            {'q.lod0': np.array([0, 1], np.int32)})


def test_status_mapping():
    from paddle_tpu.inference import (DeadlineExceeded, ReplicaFailed,
                                      ServerOverloaded,
                                      FleetUnavailable)
    assert gateway_mod.status_for(DeadlineExceeded('x')) == 504
    assert gateway_mod.status_for(ReplicaFailed('x')) == 502
    assert gateway_mod.status_for(ServerOverloaded('x')) == 503
    assert gateway_mod.status_for(FleetUnavailable('x')) == 503
    assert gateway_mod.status_for(ValueError('x')) == 400
    assert gateway_mod.status_for(TimeoutError('x')) == 504
    assert gateway_mod.status_for(RuntimeError('x')) == 500


def test_token_bucket_and_tenants_json(tmp_path):
    t = TenantConfig('t', rate=2.0, burst=2)
    ok1, _ = t.acquire()
    ok2, _ = t.acquire()
    ok3, retry = t.acquire()
    assert ok1 and ok2 and not ok3 and retry > 0
    cfg = {'key-a': {'tenant': 'alpha', 'rate': 5, 'admin': True},
           'key-b': {'max_inflight': 3}}
    path = tmp_path / 'tenants.json'
    path.write_text(json.dumps(cfg))
    tenants = tenants_from_json(str(path))
    assert tenants['key-a'].name == 'alpha' and tenants['key-a'].admin
    assert tenants['key-b'].max_inflight == 3
    assert tenants['key-b'].rate is None


# -- Prometheus text exposition ----------------------------------------------

_PROM_METRIC = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'            # metric name
    r'(\{([a-zA-Z_][a-zA-Z0-9_]*="[^"]*")'   # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'  # more labels
    r' [-+]?[0-9.eE+-]+$')                   # value
_PROM_COMMENT = re.compile(r'^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+$')


def _assert_prometheus_valid(text):
    assert text.endswith('\n')
    seen = 0
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith('#'):
            assert _PROM_COMMENT.match(line), line
        else:
            assert _PROM_METRIC.match(line), line
            seen += 1
    assert seen > 0


def test_render_metrics_is_valid_prometheus(direct_pred):
    gw = Gateway(direct_pred)
    try:
        snap = gw.snapshot()
        text = render_metrics(snap, snap.get('backend'))
        _assert_prometheus_valid(text)
        assert 'ptpu_gateway_inflight' in text
        assert 'ptpu_decode_' in text  # backend counters flattened
    finally:
        gw.close()


# -- HTTP over a direct DecodingPredictor ------------------------------------

def test_sse_stream_byte_identical_to_direct(direct_pred):
    """The tentpole acceptance bar: an SSE decode stream served over
    HTTP carries exactly the transcript a direct DecodingPredictor
    produces — token-for-token and in the done event."""
    prompt = _prompts(4, seed=11)[0]
    want = [int(t) for t in
            direct_pred.submit(prompt, max_new_tokens=10).result(120)]
    with Gateway(direct_pred) as gw:
        code, hdrs, raw = _req(gw.url, '/v1/decode',
                               {'prompt': [int(p) for p in prompt],
                                'max_new_tokens': 10}, rid='sse-1')
        assert code == 200
        assert hdrs.get('X-Request-Id') == 'sse-1'
        toks, done, err = _sse_tokens(raw)
        assert err is None
        assert toks == want
        assert done['tokens'] == want
        assert done['request_id'] == 'sse-1'
        snap = gw.snapshot()
        assert snap['streams'] == 1 and snap['ok'] == 1
        assert snap['ttft_p99_ms'] > 0.0


def test_nonstream_and_beam_decode(direct_pred):
    prompt = _prompts(4, seed=12)[0]
    want = [int(t) for t in
            direct_pred.submit(prompt, max_new_tokens=6).result(120)]
    ids, scores = direct_pred.submit(prompt, max_new_tokens=6,
                                     beam=2).result(120)
    with Gateway(direct_pred) as gw:
        code, _, body = _req(gw.url, '/v1/decode',
                             {'prompt': [int(p) for p in prompt],
                              'max_new_tokens': 6, 'stream': False})
        assert code == 200 and body['tokens'] == want
        code, _, body = _req(gw.url, '/v1/decode',
                             {'prompt': [int(p) for p in prompt],
                              'max_new_tokens': 6, 'beam': 2})
        assert code == 200
        assert body['ids'] == np.asarray(ids).tolist()


def test_bad_requests_400_and_404(direct_pred):
    with Gateway(direct_pred) as gw:
        code, _, body = _req(gw.url, '/v1/decode', {})
        assert code == 400 and body['etype'] == 'ValueError'
        code, _, body = _req(gw.url, '/v1/decode', {'prompt': []})
        assert code == 400
        code, _, body = _req(gw.url, '/v1/infer', {'prompt': [1]})
        assert code == 400  # decode artifact behind /v1/infer
        code, _, _ = _req(gw.url, '/no/such/route')
        assert code == 404
        snap = gw.snapshot()
        assert snap['bad'] == 3


def test_auth_rate_limit_and_quota(direct_pred):
    tenants = {
        'k-fast': TenantConfig('fast', admin=True),
        'k-slow': TenantConfig('slow', rate=0.001, burst=1),
        'k-zero': TenantConfig('zero', max_inflight=0),
    }
    prompt = [5, 7]
    with Gateway(direct_pred, tenants=tenants) as gw:
        # no key / unknown key -> 401, never reaches the backend
        code, _, body = _req(gw.url, '/v1/decode', {'prompt': prompt})
        assert code == 401 and body['etype'] == 'Unauthorized'
        code, _, _ = _req(gw.url, '/v1/decode', {'prompt': prompt},
                          key='k-wrong')
        assert code == 401
        # token bucket: burst of 1 admits one, then 429 + Retry-After
        code, _, _ = _req(gw.url, '/v1/decode',
                          {'prompt': prompt, 'max_new_tokens': 2,
                           'stream': False}, key='k-slow')
        assert code == 200
        code, hdrs, body = _req(gw.url, '/v1/decode',
                                {'prompt': prompt}, key='k-slow',
                                rid='rl-1')
        assert code == 429
        assert int(hdrs.get('Retry-After')) >= 1
        assert 'rl-1' in body['error']
        # per-tenant inflight quota
        code, hdrs, _ = _req(gw.url, '/v1/decode', {'prompt': prompt},
                             key='k-zero')
        assert code == 429 and 'Retry-After' in hdrs
        # admin gating on /admin/drain
        code, _, _ = _req(gw.url, '/admin/drain', {}, key='k-slow')
        assert code == 403
        snap = gw.snapshot()
        assert snap['tenants']['slow']['rate_limited'] == 1
        assert snap['tenants']['zero']['quota'] == 1
        assert snap['rate_limited'] == 1 and snap['quota'] == 1


def test_dense_infer_roundtrip(dense_art):
    x = np.random.RandomState(5).randn(8, DIM).astype(np.float32)
    want, = dense_art['pred'].run([x])
    with BatchingPredictor(dense_art['art'], platform='cpu') as pred:
        pred.warmup()
        with Gateway(pred) as gw:
            code, _, body = _req(
                gw.url, '/v1/infer',
                {'npz': gateway_mod.encode_arrays({'img': x})})
            assert code == 200
            outs = gateway_mod.decode_arrays(body['npz'])
            np.testing.assert_array_equal(outs['o0'], want)
            # decode route on a dense artifact: 400, not a crash
            code, _, _ = _req(gw.url, '/v1/decode', {'prompt': [1, 2]})
            assert code == 400


def test_graceful_drain_and_healthz(direct_pred):
    with Gateway(direct_pred) as gw:
        code, _, body = _req(gw.url, '/healthz')
        assert code == 200 and body['ok']
        # admin drain flips healthz and sheds new data requests 503
        code, _, body = _req(gw.url, '/admin/drain', {})
        assert code == 202 and body['draining']
        assert gw.drain_requested.is_set()
        code, hdrs, body = _req(gw.url, '/v1/decode',
                                {'prompt': [5, 7]})
        assert code == 503 and 'draining' in body['error']
        assert 'Retry-After' in hdrs
        code, _, body = _req(gw.url, '/healthz')
        assert code == 503 and body['draining']
        assert gw.drain(timeout=10) is True


def test_profiler_gateway_report(direct_pred, capsys):
    with Gateway(direct_pred) as gw:
        _req(gw.url, '/v1/decode', {'prompt': [5, 7],
                                    'max_new_tokens': 2,
                                    'stream': False})
        sources = list(profiler._gateway_sources)
        assert any(s.startswith('gateway:') for s in sources)
        out = profiler.gateway_report()
        printed = capsys.readouterr().out
        assert 'Gateway source' in printed and 'tenant' in printed
        name = [s for s in sources if s.startswith('gateway:')][-1]
        assert out[name]['ok'] >= 1
    # close() unregisters: a dead gateway never haunts the report
    assert name not in profiler._gateway_sources


def test_gateway_ctl_cli(direct_pred):
    ctl = [sys.executable, os.path.join(REPO, 'tools',
                                        'gateway_ctl.py')]
    with Gateway(direct_pred) as gw:
        r = subprocess.run(ctl + ['status', gw.url, '--json'],
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        js = json.loads(r.stdout)
        assert js['healthy'] and js['stats']['kind'] == 'gateway'
        r = subprocess.run(ctl + ['drain', gw.url, '--timeout', '30'],
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert gw.drain_requested.is_set()
    # unreachable -> 1; usage -> 2
    r = subprocess.run(ctl + ['status', 'http://127.0.0.1:9'],
                       capture_output=True, timeout=60)
    assert r.returncode == 1
    r = subprocess.run(ctl + ['bogus'], capture_output=True,
                       timeout=60)
    assert r.returncode == 2


# -- deadline propagation: all three shed sites over HTTP (satellite) --------

def test_deadline_sheds_at_gateway_door(direct_pred):
    """Site 1: budget already spent when the gateway reads the body —
    504 before the backend ever sees the request."""
    with Gateway(direct_pred) as gw:
        before = direct_pred.stats.snapshot()['expired']
        code, _, body = _req(gw.url, '/v1/decode',
                             {'prompt': [5, 7], 'deadline_ms': 0},
                             rid='door-1')
        assert code == 504
        assert 'gateway door' in body['error']
        assert body['request_id'] == 'door-1'
        snap = gw.snapshot()
        assert snap['expired'] == 1
        # the backend never saw it
        assert direct_pred.stats.snapshot()['expired'] == before


def test_deadline_expires_mid_decode_slot_freed(direct_pred):
    """Site 3: the budget survives admission + first tokens but not the
    full decode — DeadlineExceeded names the mid-decode site and the
    request id, the slot frees, the expired counter increments, and
    follow-up traffic is unaffected. Counted, not timed: the request's
    deadline moves into the past on the scheduler's own thread at the
    first tick that finds five of its tokens emitted."""
    prompt = _prompts(4, seed=13)[0]
    want = [int(t) for t in
            direct_pred.submit(prompt, max_new_tokens=30).result(300)]
    assert len(want) > 5
    before = direct_pred.stats.snapshot()['expired']
    run_tick = direct_pred._run_tick

    def tick(waiting):
        for req in direct_pred._active_requests():
            if req.request_id == 'mid-1' and req.produced >= 5:
                req.deadline = 0.0
        run_tick(waiting)
    direct_pred._run_tick = tick
    with Gateway(direct_pred) as gw:
        try:
            code, _, raw = _req(gw.url, '/v1/decode',
                                {'prompt': [int(p) for p in prompt],
                                 'max_new_tokens': 30,
                                 'deadline_ms': 3.6e6},
                                rid='mid-1')
        finally:
            direct_pred._run_tick = run_tick
        toks, done, err = _sse_tokens(raw)
        assert done is None and toks == want[:5]
        assert err is not None and err['code'] == 504
        assert 'mid-decode after 5 token' in err['error']
        assert '(request mid-1)' in err['error']
        assert err['request_id'] == 'mid-1'
        assert direct_pred.stats.snapshot()['expired'] == before + 1
        # recent_failures carries the trace id (satellite 3)
        fails = direct_pred.stats.snapshot()['recent_failures']
        assert any(f['request_id'] == 'mid-1' for f in fails)
        assert gw.snapshot()['expired'] == 1
        # slot freed: the same decode completes afterwards
        code, _, body = _req(gw.url, '/v1/decode',
                             {'prompt': [int(p) for p in prompt],
                              'max_new_tokens': 30, 'stream': False})
        assert code == 200 and body['tokens'] == want


def test_deadline_expires_in_router_queue(decode_art):
    """Site 2: the budget outlives the gateway door but dies in the
    FleetRouter's pending queue behind a saturated replica — 504 naming
    the router-queue site and the request id, router expired counter
    incremented, and the slot reuse proven by a follow-up request."""
    import signal
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with FleetRouter(decode_art, replicas=1, platform='cpu',
                         inflight_per_replica=1) as router:
            router.hb_timeout_s = 60.0  # paused != hung for this test
            with Gateway(router) as gw:
                # prove the replica serves, then pause it: the next
                # dispatch occupies the single frame slot forever and
                # the victim behind it can only die in the router queue
                code, _, _ = _req(gw.url, '/v1/decode',
                                  {'prompt': [5, 7],
                                   'max_new_tokens': 2,
                                   'stream': False})
                assert code == 200
                rid_ = router.serving_replicas()[0]
                pid = router._replicas[rid_].proc.pid
                os.kill(pid, signal.SIGSTOP)
                try:
                    hog = router.submit(_prompts(1, seed=14)[0],
                                        max_new_tokens=8)
                    code, _, body = _req(
                        gw.url, '/v1/decode',
                        {'prompt': [5, 7], 'max_new_tokens': 2,
                         'stream': False, 'deadline_ms': 250},
                        rid='rq-1')
                finally:
                    os.kill(pid, signal.SIGCONT)
                assert code == 504, body
                assert 'router queue' in body['error']
                assert '(request rq-1)' in body['error']
                assert router.stats.snapshot()['expired'] >= 1
                assert gw.snapshot()['expired'] == 1
                hog.result(600)
                # queue healthy again: the same request now serves
                code, _, body = _req(
                    gw.url, '/v1/decode',
                    {'prompt': [5, 7], 'max_new_tokens': 2,
                     'stream': False})
                assert code == 200


# -- fleet-backed serving ----------------------------------------------------

def test_fleet_sse_byte_identical_and_request_id(decode_fleet,
                                                 direct_pred):
    """SSE over the 2-replica fleet matches the direct predictor
    token-for-token, and the request id rides the wire frames into the
    replica (the fleet stats event log sees tagged failures; here the
    happy path just round-trips)."""
    prompts = _prompts(6, seed=21)
    with Gateway(decode_fleet) as gw:
        for i, p in enumerate(prompts):
            want = [int(t) for t in direct_pred.submit(
                p, max_new_tokens=8).result(300)]
            code, _, raw = _req(gw.url, '/v1/decode',
                                {'prompt': [int(t) for t in p],
                                 'max_new_tokens': 8},
                                rid='fleet-%d' % i)
            assert code == 200
            toks, done, err = _sse_tokens(raw)
            assert err is None
            assert toks == want and done['tokens'] == want
        assert gw.snapshot()['streams'] == len(prompts)


def test_poisson_mixed_tenant_zero_silent_drops(decode_fleet):
    """The acceptance scenario: 64 concurrent mixed-tenant requests in
    a Poisson arrival pattern over the 2-replica fleet. EVERY request
    resolves to one of 200/400/429/502/503/504 (no silent drops, no
    transport errors), and the gateway's per-tenant ledgers reconcile:
    codes sum to the request count, admitted = requests - door
    rejections, and every 200 maps onto a fleet completion."""
    N = 64
    tenants = {
        'k-alpha': TenantConfig('alpha'),
        'k-beta': TenantConfig('beta', rate=20.0, burst=4),
        'k-gamma': TenantConfig('gamma', max_inflight=2),
    }
    keys = ['k-alpha', 'k-beta', 'k-gamma']
    rng = np.random.RandomState(77)
    prompts = _prompts(N, seed=22)
    fleet_before = decode_fleet.stats.snapshot()
    results = [None] * N
    with Gateway(decode_fleet, tenants=tenants) as gw:
        def one(i):
            body = {'prompt': [int(t) for t in prompts[i]],
                    'max_new_tokens': int(rng.randint(2, 6)),
                    'stream': False}
            if i % 16 == 7:
                body['deadline_ms'] = 0  # deterministic door 504s
            code, _, _ = _req(gw.url, '/v1/decode', body,
                              key=keys[i % 3], rid='poisson-%d' % i)
            results[i] = code

        threads = []
        for i in range(N):
            t = threading.Thread(target=one, args=(i,), daemon=True)
            threads.append(t)
            t.start()
            time.sleep(float(rng.exponential(0.01)))
        for t in threads:
            t.join(300)
        assert all(not t.is_alive() for t in threads)
        snap = gw.snapshot()
    # zero silent drops: every request produced a terminal status
    allowed = {200, 400, 429, 502, 503, 504}
    assert None not in results
    assert set(results) <= allowed, sorted(set(results))
    n_ok = sum(1 for c in results if c == 200)
    assert n_ok >= N // 2  # the fleet actually served the bulk
    assert sum(1 for c in results if c == 504) >= 1  # forced door sheds
    # ledger reconciliation, per tenant and in total
    assert snap['requests'] == N
    for t in snap['tenants'].values():
        assert sum(t['codes'].values()) == t['requests']
        assert (t['ok'] + t['bad'] + t['rate_limited'] + t['quota']
                + t['shed'] + t['expired'] + t['failed']
                ) == t['requests']
    assert snap['ok'] == n_ok
    assert snap['inflight'] == 0
    # fleet-side reconciliation: door rejections never reached the
    # fleet; every gateway 200 is a fleet completion
    fleet_after = decode_fleet.stats.snapshot()
    door_rejected = (snap['rate_limited'] + snap['quota']
                     + snap['expired'] + snap['bad'])
    submitted = fleet_after['submitted'] - fleet_before['submitted']
    completed = fleet_after['completed'] - fleet_before['completed']
    assert submitted == N - door_rejected
    assert completed == n_ok


def test_fleet_metrics_endpoint_valid(decode_fleet):
    with Gateway(decode_fleet) as gw:
        _req(gw.url, '/v1/decode', {'prompt': [5, 7],
                                    'max_new_tokens': 2,
                                    'stream': False})
        code, hdrs, text = _req(gw.url, '/metrics')
        assert code == 200
        assert hdrs.get('Content-Type', '').startswith('text/plain')
        _assert_prometheus_valid(text)
        assert 'ptpu_gateway_requests_total' in text
        assert 'ptpu_fleet_' in text
        assert 'ptpu_fleet_replica_' in text
