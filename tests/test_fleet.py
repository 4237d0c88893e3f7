"""Serving-fleet control plane (ISSUE 12): FleetRouter routing /
failover / drain semantics, Autoscaler decisions, RollingRollout
promote + loud rollback, predictor drain() hooks, decode tier
plumbing, the profiler fleet table, and the fleet_ctl CLI.

Chaos contract under test: killing one of N replicas mid-stream loses
ONLY that replica's in-flight requests (every other request completes
bit-identical to a single-replica reference); a hung (SIGSTOP) replica
is detected by the heartbeat watchdog in bounded time and its queue
re-routes; scale-in drains with zero dropped in-flight streams.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.inference import (Autoscaler, BatchingPredictor, Config,
                                  DecodingPredictor, FleetRouter,
                                  ReplicaFailed, RollingRollout,
                                  RolloutRolledBack, ServerOverloaded,
                                  create_predictor, export_compiled,
                                  export_decode)
from paddle_tpu.inference import fleet as fleet_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 8
VOCAB = 61


def _patient(router):
    """Raise every fleet timeout that only exists to bound wall-clock:
    under a loaded CI host a busy (not hung) replica must never be
    declared dead by a test."""
    router.hb_timeout_s = 60.0
    return router


@pytest.fixture(scope='module')
def dense_art(tmp_path_factory):
    """One tiny classifier exported single-bucket [8] (requests of
    exactly 8 rows route through the same compiled shape everywhere —
    strict bit-identity) with a calibrated int8 tier, plus the
    in-framework predictor as reference."""
    tmp = str(tmp_path_factory.mktemp('fleet_dense'))
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
        rng = np.random.RandomState(3)
        calib = [[rng.randn(8, DIM).astype(np.float32)]
                 for _ in range(4)]
        art = os.path.join(tmp, 'art')
        export_compiled(pred, calib[0], art, batch_sizes=[8],
                        quantize='int8', calibration=calib)
    return {'art': art, 'pred': pred, 'calib': calib}


@pytest.fixture(scope='module')
def decode_art(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp('fleet_decode'))
    art = os.path.join(tmp, 'decode')
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(vocab=VOCAB, d_model=8, n_head=2,
                                 n_layer=1, d_ff=16, max_slots=4,
                                 max_cache_len=40, chunk_sizes=(4,),
                                 eos_id=1, block_size=4)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'])
        export_decode(spec, art, scope=scope)
    return art


def _x(seed, rows=8):
    return np.random.RandomState(100 + seed).randn(
        rows, DIM).astype(np.float32)


def _prompts(n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, VOCAB, rng.randint(2, 5)) for _ in range(n)]


# -- wire protocol / routing units (no subprocesses) -------------------------

def test_frame_roundtrip_and_bounds():
    import socket as socketlib
    a, b = socketlib.socketpair()
    hdr = {'op': 'infer', 'id': 7, 'deadline_ms': 12.5}
    arrays = {'x': np.arange(12, dtype=np.float32).reshape(3, 4),
              'y': np.array([b'ab', b'cd'])}
    fleet_mod._send_frame(a, hdr, arrays)
    fleet_mod._send_frame(a, {'op': 'stop'})
    got_hdr, got_arrays = fleet_mod._recv_frame(b)
    assert got_hdr == hdr
    np.testing.assert_array_equal(got_arrays['x'], arrays['x'])
    np.testing.assert_array_equal(got_arrays['y'], arrays['y'])
    hdr2, arrays2 = fleet_mod._recv_frame(b)
    assert hdr2 == {'op': 'stop'} and arrays2 == {}
    a.close()
    assert fleet_mod._recv_frame(b) is None  # clean EOF
    b.close()
    # corrupt length prefix -> loud IOError, not a hang
    c, d = socketlib.socketpair()
    c.sendall(b'\xff' * 8 + b'junk')
    with pytest.raises(IOError):
        fleet_mod._recv_frame(d)
    c.close()
    d.close()


def test_detect_kind(dense_art, decode_art, tmp_path):
    assert fleet_mod.detect_kind(dense_art['art']) == 'batching'
    assert fleet_mod.detect_kind(decode_art) == 'decoding'
    with pytest.raises(ValueError):
        fleet_mod.detect_kind(str(tmp_path))


def test_agreement_measures():
    a = [np.arange(8, dtype=np.float32).reshape(2, 4)]
    assert fleet_mod.bit_agreement(a, [a[0].copy()]) == 1.0
    b = [a[0] + 1e-6]
    assert fleet_mod.bit_agreement(a, b) == 0.0
    assert fleet_mod.top1_agreement(a, b) == 1.0  # argmax unchanged
    c = [a[0][:, ::-1].copy()]
    assert fleet_mod.top1_agreement(a, c) == 0.0
    # greedy transcripts compare exactly — in BOTH measures ('top1' on
    # a decode fleet is the round-14 transcript-agreement fraction)
    assert fleet_mod.bit_agreement([3, 1, 2], [3, 1, 2]) == 1.0
    assert fleet_mod.bit_agreement([3, 1, 2], [3, 1]) == 0.0
    assert fleet_mod.top1_agreement([3, 1, 2], [3, 1, 2]) == 1.0
    assert fleet_mod.top1_agreement([3, 1, 2], [3, 1, 9]) == 0.0
    assert fleet_mod.top1_agreement([3, 1, 2], [3, 1]) == 0.0


# -- predictor drain() hooks (in-process, the fleet's scale-in lever) --------

def test_batching_drain_sheds_queue_finishes_inflight(dense_art):
    """drain(): queued requests shed loudly (shed+drained counters),
    the in-flight dispatch delivers, submit() afterwards raises. The
    first dispatch is gated on an Event so a real queue backlog exists
    at drain time."""
    batcher = BatchingPredictor(dense_art['art'], batch_timeout_ms=1.0,
                                max_batch_size=8)
    batcher.warmup()
    gate = threading.Event()
    real = batcher._preds[8]._call_flat

    def gated(args):
        gate.wait(30)
        return real(args)
    batcher._preds[8]._call_flat = gated
    # full-bucket requests: each dispatches alone; r0 blocks in the
    # gated dispatch while r1..r4 sit QUEUED behind it
    futs = [batcher.submit([_x(i)]) for i in range(5)]
    drainer = threading.Thread(target=batcher.drain)
    time.sleep(0.2)
    drainer.start()
    time.sleep(0.2)
    gate.set()
    drainer.join(60)
    assert not drainer.is_alive()
    outs = futs[0].result(60)     # the in-flight dispatch delivered
    want, = dense_art['pred'].run([_x(0)])
    np.testing.assert_array_equal(outs[0], want)
    shed = 0
    for f in futs[1:]:
        with pytest.raises(ServerOverloaded, match='draining'):
            f.result(60)
        shed += 1
    snap = batcher.stats.snapshot()
    assert snap['drained'] == shed == 4
    assert snap['shed'] >= 4
    with pytest.raises(RuntimeError):
        batcher.submit([_x(0)])
    batcher.close()  # idempotent after drain


def test_decoding_drain_finishes_active_sheds_waiting(decode_art):
    """drain(): ACTIVE streams decode to completion (zero drops),
    waiting queue sheds re-routably, new submissions shed."""
    with DecodingPredictor(decode_art, platform='cpu') as ref:
        want = ref.generate(_prompts(1)[0], max_new_tokens=24)
    pred = DecodingPredictor(decode_art, platform='cpu')
    try:
        # 4 slots: 4 active + 3 waiting
        streams = [pred.submit(_prompts(1)[0], max_new_tokens=24)
                   for _ in range(7)]
        time.sleep(0.05)
        assert pred.drain(timeout=120)
        results, shed = [], 0
        for s in streams:
            try:
                results.append(s.result(60))
            except ServerOverloaded:
                shed += 1
        assert len(results) >= 4 and shed == 7 - len(results)
        assert all(r == want for r in results)
        snap = pred.stats.snapshot()
        assert snap['drained'] == shed
        # draining endpoint admits nothing, sheds loudly
        with pytest.raises(ServerOverloaded):
            pred.submit(_prompts(1)[0]).result(60)
        assert pred.stats.snapshot()['drained'] == shed + 1
    finally:
        pred.close()


def test_compiled_predictor_drain_hook(dense_art):
    from paddle_tpu.inference import CompiledPredictor
    p = CompiledPredictor(dense_art['art'])
    assert p.drain() is p  # synchronous predictor: no queue, no-op


# -- decode tier plumbing (satellite) ----------------------------------------

def test_decoding_tier_contract(decode_art, tmp_path):
    """DecodingPredictor(tier=): explicit missing tier raises (the
    BatchingPredictor contract); a present tier subdir resolves; the
    env preference degrades silently."""
    with pytest.raises(ValueError, match="has no 'int8' tier"):
        DecodingPredictor(decode_art, tier='int8')
    # build a tier: the quantized-KV artifact exported under int8/
    import shutil
    tiered = str(tmp_path / 'tiered')
    shutil.copytree(decode_art, tiered)
    shutil.copytree(decode_art, os.path.join(tiered, 'int8'))
    sig_p = os.path.join(tiered, 'int8',
                         'decode_signature.json')
    with open(sig_p) as f:
        sig = json.load(f)
    sig['kv_cache_dtype'] = 'int8'  # mark the tier copy
    with open(sig_p, 'w') as f:
        json.dump(sig, f)
    p = DecodingPredictor(tiered, tier='int8', platform='cpu')
    assert p.stats.tier == 'int8'
    p.close()
    # env preference resolves the tier; on artifacts without one it
    # degrades silently to the top level
    os.environ['PTPU_SERVE_TIER'] = 'int8'
    try:
        p = DecodingPredictor(tiered, platform='cpu')
        assert p.stats.tier == 'int8'
        p.close()
        p = DecodingPredictor(decode_art, platform='cpu')
        assert p.stats.tier == 'bf16'
        p.close()
    finally:
        del os.environ['PTPU_SERVE_TIER']


def test_serve_decode_cli_tier_flag(decode_art, tmp_path):
    """serve.py decode --tier: explicit missing tier exits loudly."""
    prompts = np.zeros((2, 4), np.int64)
    prompts[:, :2] = 5
    in_p = str(tmp_path / 'p.npz')
    np.savez(in_p, prompts=prompts, lens=np.array([2, 2], np.int64))
    out_p = str(tmp_path / 'o.npz')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    serve_py = os.path.join(REPO, 'paddle_tpu', 'inference', 'serve.py')
    r = subprocess.run(
        [sys.executable, serve_py, 'decode', decode_art, in_p, out_p,
         '4', '--tier', 'int8'], capture_output=True, text=True,
        env=env)
    assert r.returncode != 0 and "has no 'int8' tier" in r.stderr
    r = subprocess.run(
        [sys.executable, serve_py, 'decode', decode_art, in_p, out_p,
         '4'], capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line['tier'] == 'bf16' and line['requests'] == 2
    assert os.path.exists(out_p)


# -- fleet end-to-end --------------------------------------------------------

@pytest.fixture(scope='module')
def dense_fleet(dense_art):
    """One 2-replica batching fleet shared by the read-only tests."""
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        router = _patient(FleetRouter(dense_art['art'], replicas=2,
                                      platform='cpu',
                                      inflight_per_replica=4))
        yield router
        router.close()


def test_fleet_routes_bit_identical(dense_fleet, dense_art):
    xs = [_x(i) for i in range(10)]
    futs = [dense_fleet.submit({'img': x}) for x in xs]
    res = [f.result(120) for f in futs]
    for x, r in zip(xs, res):
        want, = dense_art['pred'].run([x])
        np.testing.assert_array_equal(r[0], want)
    # replica-side serving counters flow back through the heartbeat
    # files (0.5s interval) — poll until they account for the work
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        st = dense_fleet.status()
        if sum(s['requests'] for s in st['replicas'].values()) >= 10:
            break
        time.sleep(0.2)
    served = [s['requests'] for s in st['replicas'].values()]
    assert sum(served) >= 10 and st['serving'] == 2


def test_fleet_warm_spinup_zero_compiles_framework_free(dense_fleet):
    snap = dense_fleet.fleet_snapshot()
    for rid, s in snap['replicas'].items():
        assert s['compiles'] == 0, (rid, s)
    for rep in dense_fleet._replicas.values():
        assert rep.hello.get('framework_free') is True


def test_fleet_deadline_propagates(dense_fleet):
    from paddle_tpu.inference import DeadlineExceeded
    fut = dense_fleet.submit({'img': _x(0)}, deadline_ms=0.0)
    with pytest.raises(DeadlineExceeded):
        fut.result(120)
    assert dense_fleet.fleet_snapshot()['expired'] >= 1


def test_fleet_submit_validation(dense_fleet):
    with pytest.raises(ValueError):
        dense_fleet.submit({'img': _x(0)}, beam=2)  # not a decode fleet
    fut = dense_fleet.submit({'wrong_feed': _x(0)})
    with pytest.raises(Exception):  # replica-side validation, loudly
        fut.result(120)


def test_fleet_report_renders(dense_fleet, capsys):
    name = 'fleet:test#0'
    profiler.register_fleet_source(name, dense_fleet.fleet_snapshot)
    try:
        out = profiler.fleet_report()
        printed = capsys.readouterr().out
    finally:
        profiler.unregister_fleet_source(name)
    assert name in out
    assert 'Fleet source' in printed and 'replica' in printed
    assert out[name]['serving'] == 2
    assert 'p99_ms' in out[name] and 'ttft_p99_ms' in out[name]


def test_fleet_status_json_and_ctl_cli(dense_fleet):
    st = dense_fleet.status()
    assert st['serving'] == 2 and st['kind'] == 'batching'
    status_path = os.path.join(dense_fleet.fleet_dir, 'status.json')
    deadline = time.monotonic() + 10
    while not os.path.exists(status_path) \
            and time.monotonic() < deadline:
        time.sleep(0.1)
    ctl = [sys.executable, os.path.join(REPO, 'tools', 'fleet_ctl.py')]
    r = subprocess.run(ctl + ['status', dense_fleet.fleet_dir,
                              '--json'],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    js = json.loads(r.stdout)
    assert js['healthy'] and js['status']['serving'] == 2
    # usage errors exit 2
    assert subprocess.run(
        ctl + ['status', '/not/a/fleet'],
        capture_output=True).returncode == 2
    assert subprocess.run(
        ctl + ['drain', dense_fleet.fleet_dir, '99'],
        capture_output=True).returncode == 2


def test_fleet_chaos_sigkill_loses_only_victim_inflight(decode_art):
    """SIGKILL one replica mid-stream: bounded-time detection, only its
    in-flight requests fail (loudly), everything else bit-identical,
    the fleet keeps serving."""
    prompts = _prompts(48, seed=5)
    with DecodingPredictor(decode_art, platform='cpu') as ref:
        want = [ref.generate(p, max_new_tokens=24) for p in prompts]
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with _patient(FleetRouter(decode_art, replicas=2,
                                  platform='cpu',
                                  inflight_per_replica=4)) as router:
            futs = [router.submit(p, max_new_tokens=24)
                    for p in prompts]
            time.sleep(0.1)
            victim = max(router._replicas.values(),
                         key=lambda r: len(r.outstanding)).rid
            os.kill(router._replicas[victim].proc.pid, signal.SIGKILL)
            t0 = time.perf_counter()
            done, failed = {}, []
            for i, f in enumerate(futs):
                try:
                    done[i] = f.result(300)
                except ReplicaFailed:
                    failed.append(i)
            assert time.perf_counter() - t0 < 120
            assert router._replicas[victim].state == 'dead'
            assert len(failed) <= 4, failed       # inflight cap
            assert len(done) + len(failed) == len(prompts)
            for i, r in done.items():
                assert r == want[i], 'request %d diverged' % i
            snap = router.fleet_snapshot()
            assert snap['replica_deaths'] == 1
            # survivors keep serving
            assert router.run(prompts[0], max_new_tokens=24,
                              timeout=300) == want[0]


def test_mid_stream_eviction_is_not_requeueable():
    """ISSUE 13: a block-pool eviction of an IN-FLIGHT stream raises
    MidStreamEvicted — still a ServerOverloaded for local callers, but
    the worker's post-dispatch re-route decision must refuse it: tokens
    may already have streamed, so a re-route would replay them on
    another replica and blindly retry device work. Door sheds (base
    ServerOverloaded) stay re-routable."""
    import paddle_tpu.inference.fleet_worker as fw
    door = fw._batching.ServerOverloaded('queue full')
    mid = fw._decoding.MidStreamEvicted('evicted mid-decode')
    assert isinstance(mid, fw._batching.ServerOverloaded)
    assert fw._stream_requeueable(door)
    assert not fw._stream_requeueable(mid)
    assert not fw._stream_requeueable(RuntimeError('dispatch failed'))


def test_fleet_block_paged_artifact_unchanged_protocol(decode_art):
    """ISSUE 13: a block-paged decode artifact routes through
    FleetRouter/fleet_worker UNCHANGED — detect_kind sees the decode
    signature and transcripts stay bit-identical to a direct in-process
    serve. The hello frame surfaces the mesh tag (None here) so
    fleet_ctl can audit the tier, and replica heartbeats carry the
    block-cache gauges."""
    prompts = _prompts(12, seed=21)
    with DecodingPredictor(decode_art, platform='cpu') as ref:
        assert ref.block_manager is not None and ref.mesh_tag is None
        want = [ref.generate(p, max_new_tokens=12) for p in prompts]
        want_beam = ref.generate(prompts[0], max_new_tokens=8, beam=3)
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with _patient(FleetRouter(decode_art, replicas=2,
                                  platform='cpu')) as router:
            assert router.kind == 'decoding'
            futs = [router.submit(p, max_new_tokens=12)
                    for p in prompts]
            got = [f.result(300) for f in futs]
            assert got == want
            ids, scores = router.run(prompts[0], max_new_tokens=8,
                                     beam=3, timeout=300)
            np.testing.assert_array_equal(ids, want_beam[0])
            np.testing.assert_array_equal(scores, want_beam[1])
            st = router.status()
            for s in st['replicas'].values():
                assert 'layout' not in s
                assert s['mesh'] is None
            # worker heartbeats surface the block-cache gauges
            # (serving_report's columns work fleet-wide)
            deadline = time.time() + 30
            while time.time() < deadline:
                stats = [s.get('stats', {})
                         for s in router.status()['replicas'].values()]
                if any('blocks_in_use' in x for x in stats):
                    break
                time.sleep(0.2)
            assert any('blocks_in_use' in x for x in stats)


def test_fleet_hung_replica_sigstop_watchdog(decode_art):
    """SIGSTOP (hung, not dead): no socket EOF — the heartbeat watchdog
    detects staleness in bounded time, SIGKILLs the replica, re-routes
    its queued work; the fleet keeps serving."""
    prompts = _prompts(8, seed=9)
    with DecodingPredictor(decode_art, platform='cpu') as ref:
        want = ref.generate(prompts[0], max_new_tokens=12)
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with FleetRouter(decode_art, replicas=2, platform='cpu',
                         hb_timeout_s=2.5, poll_s=0.1) as router:
            victim = router.serving_replicas()[0]
            os.kill(router._replicas[victim].proc.pid, signal.SIGSTOP)
            t0 = time.perf_counter()
            while router._replicas[victim].state != 'dead' \
                    and time.perf_counter() - t0 < 30:
                time.sleep(0.05)
            detect = time.perf_counter() - t0
            assert router._replicas[victim].state == 'dead'
            assert detect < 30, detect
            ev = [e for e in router.stats.events
                  if e['kind'] == 'replica_dead']
            assert ev and 'heartbeat stale' in ev[0]['reason']
            assert router.run(prompts[0], max_new_tokens=12,
                              timeout=300) == want


def test_fleet_scale_in_drains_zero_drops(decode_art):
    """scale_in: the victim finishes its in-flight streams, hands its
    queue back for re-routing; every submitted future resolves."""
    prompts = _prompts(24, seed=13)
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with _patient(FleetRouter(decode_art, replicas=2,
                                  platform='cpu',
                                  inflight_per_replica=3)) as router:
            futs = [router.submit(p, max_new_tokens=16)
                    for p in prompts]
            assert router.scale_in(timeout=300)
            results = [f.result(300) for f in futs]
            assert len(results) == len(prompts)
            snap = router.fleet_snapshot()
            assert snap['failed'] == 0 and snap['scale_in'] == 1
            assert len(router.serving_replicas()) == 1
            states = [r.state for r in router._replicas.values()]
            assert 'retired' in states


def test_autoscaler_decisions(dense_art):
    """Autoscaler.step() against synthetic router metrics: out on
    queue pressure, out on failover below min, in after a sustained
    idle streak, bounded by min/max, cooldown respected."""

    class FakeRouter(object):
        def __init__(self):
            self.n = 1
            self.queue = 0
            self.shed = 0
            self.events = []
            self._closed = False
            self.stats = fleet_mod.FleetStats()

        def status(self):
            reps = {i: {'state': 'serving', 'pending': self.queue
                        if i == 0 else 0, 'outstanding': 0,
                        'queue_depth': 0, 'occupancy': 0.5,
                        'shed': self.shed}
                    for i in range(self.n)}
            return {'replicas': reps, 'counters': {'shed': 0}}

        def scale_out(self, reason=None):
            self.n += 1
            self.events.append('out')

        def scale_in(self, reason=None):
            self.n -= 1
            self.events.append('in')

    r = FakeRouter()
    a = Autoscaler(r, min_replicas=1, max_replicas=3,
                   high_queue_per_replica=4.0, idle_steps=2,
                   cooldown_s=0.0)
    assert a.step() is None          # calm: no action
    r.queue = 10
    assert a.step() == 'out' and r.n == 2
    assert a.step() == 'out' and r.n == 3
    assert a.step() is None          # max_replicas bound
    r.queue = 0
    assert a.step() is None          # idle streak 1 < idle_steps
    assert a.step() == 'in' and r.n == 2
    a.cooldown_s = 3600.0
    assert a.step() is None          # cooldown gates further scale-in
    a.cooldown_s = 0.0
    r.n = 0
    assert a.step() == 'out'         # failover replacement below min
    r.queue = 1
    r.shed += 5
    a.step()
    assert a._idle_streak == 0       # sheds break the idle streak


def test_rolling_rollout_promote_and_loud_rollback(dense_art):
    """int8 canary promotes on top-1 parity over the calibration set at
    unchanged replica count; an injected parity failure (bit agreement
    across tiers) rolls back loudly and leaves the fleet untouched."""
    probes = [{'img': c[0]} for c in dense_art['calib']]
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with _patient(FleetRouter(dense_art['art'], replicas=2,
                                  platform='cpu')) as router:
            # 0.95: of the 32 random probe rows one is a float-tier
            # near-tie that int8 flips under jax 0.9.0's random stream
            # (31/32 = 0.969); the bar separates parity from the injected
            # bit-agreement failure below, not 32/32 from 31/32
            report = RollingRollout(
                router, tier='int8', probes=probes, agreement='top1',
                min_agreement=0.95, latency_budget=100.0).run()
            assert report['promoted'] and report['deterministic']
            assert report['agreement'] >= 0.95
            tiers = {rid: s['tier'] for rid, s in
                     router.fleet_snapshot()['replicas'].items()
                     if s['state'] == 'serving'}
            assert len(tiers) == 2 and set(tiers.values()) == {'int8'}
            assert router.stats.rollout['state'] == 'promoted'
            # injected failure: int8 logits can never bit-match bf16
            with pytest.raises(RolloutRolledBack, match='agreement'):
                RollingRollout(router, tier=None, probes=probes,
                               agreement='bit',
                               latency_budget=100.0).run()
            tiers2 = {rid: s['tier'] for rid, s in
                      router.fleet_snapshot()['replicas'].items()
                      if s['state'] == 'serving'}
            assert tiers2 == tiers, 'rollback must not touch the fleet'
            assert router.stats.rollout['state'] == 'rolled_back'
            # the fleet still serves after the rollback
            router.run(probes[0], timeout=120)


def test_worker_without_its_device_fails_the_spawn_at_once(decode_art,
                                                           capfd):
    """One process per chip: a replica that cannot get its device says
    why on the router's stderr and exits, and the spawn raises with its
    exit code at once — not after the 300 s spin-up timeout."""
    t0 = time.monotonic()
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with pytest.raises(RuntimeError, match='exited with code 3'):
            FleetRouter(decode_art, replicas=1, platform='tpu')
    assert time.monotonic() - t0 < 60
    assert 'cannot get its device' in capfd.readouterr().err


def test_serve_fleet_cli_decode_artifact(decode_art, tmp_path):
    """serve.py fleet on a DECODE artifact: prompts npz convention."""
    prompts = np.zeros((3, 4), np.int64)
    prompts[:, :2] = [[5, 7], [9, 3], [2, 8]]
    in_p = str(tmp_path / 'p.npz')
    np.savez(in_p, prompts=prompts, lens=np.array([2, 2, 2], np.int64))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    serve_py = os.path.join(REPO, 'paddle_tpu', 'inference', 'serve.py')
    r = subprocess.run(
        [sys.executable, serve_py, 'fleet', decode_art, in_p, '6', '2'],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line['requests'] == 6 and line['failed'] == 0
    assert all(s['compiles'] == 0
               for s in line['per_replica'].values())


def test_fleet_submit_rejects_object_arrays(dense_fleet):
    """Object arrays need pickle, which the worker's np.load refuses:
    the request must fail at submit, not poison a replica's stream."""
    with pytest.raises(ValueError, match='object array'):
        dense_fleet.submit({'img': np.array([['a'], [None]],
                                            dtype=object)})


def test_fleet_bad_ctl_file_never_kills_watchdog(dense_fleet):
    """A malformed control file warns and is removed; the watchdog
    (the fleet's failure detector) keeps running."""
    ctl = os.path.join(dense_fleet.fleet_dir, 'ctl')
    bad = os.path.join(ctl, 'drain_x.json')
    with open(bad, 'w') as f:
        f.write('{"cmd": "drain", "replica": "abc"}')
    with open(os.path.join(ctl, 'noise.json'), 'w') as f:
        f.write('not json at all')
    deadline = time.monotonic() + 15
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        while os.listdir(ctl) and time.monotonic() < deadline:
            time.sleep(0.1)
    assert os.listdir(ctl) == []
    assert dense_fleet._watchdog_t.is_alive()
    # and the fleet still serves
    dense_fleet.run({'img': _x(3)}, timeout=120)


def test_fleet_spawn_failure_fails_fast(dense_art, tmp_path):
    """A replica that crashes during spin-up (broken artifact) raises
    within the watchdog poll, not after the full spin-up timeout."""
    import shutil
    broken = str(tmp_path / 'broken')
    os.makedirs(broken)
    shutil.copy(os.path.join(dense_art['art'], 'signature.json'),
                broken)  # looks like an artifact; module is missing
    t0 = time.monotonic()
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        with pytest.raises(RuntimeError, match='failed to start'):
            FleetRouter(broken, replicas=1, platform='cpu',
                        spinup_timeout_s=300.0).close()
    assert time.monotonic() - t0 < 60


def test_fleet_close_fails_pending_loudly(dense_art):
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        router = _patient(FleetRouter(dense_art['art'], replicas=1,
                                      platform='cpu'))
        fut = router.submit({'img': _x(0)})
        router.close()
        with pytest.raises(Exception):
            fut.result(30)
        with pytest.raises(RuntimeError):
            router.submit({'img': _x(1)})
        # idempotent
        router.close()
