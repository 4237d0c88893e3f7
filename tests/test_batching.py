"""Dynamic-batching serving (ISSUE 1): BatchingPredictor coalescing,
multi-bucket artifacts, partial dense-batch padding in CompiledPredictor,
serving metrics through the profiler, and the serve.py bench CLI.

Determinism contract under test: per-request outputs are bit-identical to
an unbatched CompiledPredictor.run through the SAME bucket (row position
inside a compiled batch never changes per-row results); across different
buckets only allclose holds, as with any XLA batch-size change.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.inference import (BatchingPredictor, CompiledPredictor,
                                  Config, create_predictor, export_compiled)
from paddle_tpu.inference.batching import select_bucket

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 8


def _build_predictor(tmp, reduce_fetch=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name='img', shape=[DIM], dtype='float32')
        h = fluid.layers.fc(img, 32, act='relu')
        out = fluid.layers.fc(h, 4, act='softmax')
        fetches = [out]
        if reduce_fetch:
            fetches.append(fluid.layers.reduce_mean(out))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    model_dir = os.path.join(tmp, 'model')
    fluid.io.save_inference_model(model_dir, ['img'],
                                  fetches, exe, main)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    return create_predictor(cfg)


@pytest.fixture(scope='module')
def artifacts(tmp_path_factory):
    """One model, exported three ways: multi-bucket {1,8,32}, single
    bucket {16} (for strict bit-identity), and a simulated legacy v2
    single-bucket artifact (no fetch shapes, no buckets key)."""
    tmp = str(tmp_path_factory.mktemp('batching'))
    with fluid.scope_guard(fluid.core.Scope()), fluid.unique_name.guard():
        pred = _build_predictor(tmp)
        sample = np.random.RandomState(0).randn(4, DIM).astype(np.float32)
        multi = os.path.join(tmp, 'multi')
        export_compiled(pred, [sample], multi, batch_sizes=[1, 8, 32])
        single = os.path.join(tmp, 'single')
        export_compiled(pred, [sample], single, batch_sizes=[16])
        legacy = os.path.join(tmp, 'legacy')
        export_compiled(pred, [np.resize(sample, (8, DIM))], legacy)
        sig_path = os.path.join(legacy, 'signature.json')
        with open(sig_path) as f:
            sig = json.load(f)
        sig['version'] = 2  # v2 artifacts carried no fetch shapes
        for e in sig['fetches']:
            e.pop('shape', None)
        with open(sig_path, 'w') as f:
            json.dump(sig, f)
    return {'multi': multi, 'single': single, 'legacy': legacy,
            'pred': pred}


def _x(seed, rows):
    return np.random.RandomState(100 + seed).randn(
        rows, DIM).astype(np.float32)


# -- multi-bucket export round-trip -----------------------------------------

def test_multibucket_layout_and_signature(artifacts):
    multi = artifacts['multi']
    sig = json.load(open(os.path.join(multi, 'signature.json')))
    assert sig['buckets'] == [1, 8, 32]
    assert sig['feeds'][0]['shape'] == [32, DIM]  # top mirrors largest
    assert sig['fetches'][0]['shape'] == [32, 4]  # v3 records fetch shapes
    for b in (1, 8, 32):
        bdir = os.path.join(multi, 'bucket_%05d' % b)
        bsig = json.load(open(os.path.join(bdir, 'signature.json')))
        assert bsig['feeds'][0]['shape'] == [b, DIM]
        assert 'buckets' not in bsig  # each bucket is a plain artifact


def test_multibucket_loads_in_old_and_new_entry_points(artifacts):
    multi, pred = artifacts['multi'], artifacts['pred']
    x = _x(0, 32)
    want, = pred.run([x])
    # old entry point: CompiledPredictor sees the largest bucket
    old = CompiledPredictor(multi)
    got, = old.run([x])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # each bucket dir is itself a loadable standard artifact
    b8 = CompiledPredictor(os.path.join(multi, 'bucket_00008'))
    got8, = b8.run([x[:8]])
    np.testing.assert_allclose(got8, want[:8], rtol=1e-6, atol=1e-6)
    # new entry point
    with BatchingPredictor(multi, batch_timeout_ms=1.0) as batcher:
        assert batcher.buckets == [1, 8, 32]
        assert batcher.get_input_names() == ['img']
        res, = batcher.run([x[:3]])
        np.testing.assert_allclose(res, want[:3], rtol=1e-6, atol=1e-6)


def test_v2_single_bucket_artifact_still_loads(artifacts):
    legacy, pred = artifacts['legacy'], artifacts['pred']
    x = _x(1, 8)
    want, = pred.run([x])
    got, = CompiledPredictor(legacy).run([x])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    with BatchingPredictor(legacy, batch_timeout_ms=1.0) as batcher:
        assert batcher.buckets == [8]
        res, = batcher.run([x[:2]])
        np.testing.assert_allclose(res, want[:2], rtol=1e-6, atol=1e-6)


# -- partial dense-batch padding in CompiledPredictor ------------------------

def test_compiled_predictor_pads_partial_dense_batch(artifacts):
    pred = artifacts['pred']
    served = CompiledPredictor(artifacts['single'])  # compiled for 16 rows
    x = _x(2, 5)
    got, = served.run([x])
    assert got.shape == (5, 4)
    want, = pred.run([x])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_partial_batch_row_dependent_fetch_errors_loudly(tmp_path):
    with fluid.scope_guard(fluid.core.Scope()), fluid.unique_name.guard():
        pred = _build_predictor(str(tmp_path), reduce_fetch=True)
    art = str(tmp_path / 'artifact')
    export_compiled(pred, [_x(3, 8)], art)
    served = CompiledPredictor(art)
    # exact batch: fine, both fetches come back
    outs = served.run([_x(3, 8)])
    assert outs[0].shape == (8, 4) and outs[1].size == 1
    # partial batch: the scalar reduce_mean depends on padded rows —
    # must error loudly, not silently average in zeros
    with pytest.raises(ValueError, match='not batch-aligned'):
        served.run([_x(3, 3)])


# -- batcher core ------------------------------------------------------------

def test_select_bucket_unsorted_prefers_smallest_fit():
    """Regression (ISSUE 8 satellite): with an UNSORTED bucket list the
    old prefix walk returned the first fit, not the smallest — a
    hand-edited signature once routed 2-row batches to the 128 bucket.
    select_bucket is now order-independent; loaders still sort once at
    load so the common path stays a prefix walk."""
    import random
    buckets = [1, 8, 32, 128]
    for seed in range(6):
        shuffled = list(buckets)
        random.Random(seed).shuffle(shuffled)
        for rows, want in ((1, 1), (2, 8), (8, 8), (9, 32), (33, 128),
                           (128, 128)):
            assert select_bucket(shuffled, rows) == want, shuffled
    with pytest.raises(ValueError):
        select_bucket([128, 1, 32, 8], 129)


def test_batcher_routes_through_smallest_bucket_with_shuffled_sig(
        artifacts):
    """A signature whose bucket list is NOT sorted ascending (hand-edited
    or produced by an older exporter) still routes each batch to the
    smallest fitting bucket: the predictor sorts once at load."""
    import shutil
    shuffled_dir = artifacts['multi'] + '_shuffled'
    if not os.path.isdir(shuffled_dir):
        shutil.copytree(artifacts['multi'], shuffled_dir)
        sig_path = os.path.join(shuffled_dir, 'signature.json')
        with open(sig_path) as f:
            sig = json.load(f)
        sig['buckets'] = [32, 1, 8]
        with open(sig_path, 'w') as f:
            json.dump(sig, f)
    b = BatchingPredictor(shuffled_dir, batch_timeout_ms=1.0)
    try:
        assert b.buckets == [1, 8, 32]
        b.run([_x(77, 2)])
        snap = b.stats.snapshot()
        # 2 rows padded into the 8-bucket (occupancy 2/8), never 32
        assert snap['occupancy'] == pytest.approx(0.25)
    finally:
        b.close()


def test_select_bucket_boundaries():
    buckets = [1, 8, 32]
    assert select_bucket(buckets, 1) == 1
    assert select_bucket(buckets, 2) == 8
    assert select_bucket(buckets, 8) == 8
    assert select_bucket(buckets, 9) == 32
    assert select_bucket(buckets, 32) == 32
    with pytest.raises(ValueError, match='exceeds the largest'):
        select_bucket(buckets, 33)


def test_coalescing_routes_results_to_the_right_caller(artifacts):
    pred = artifacts['pred']
    with BatchingPredictor(artifacts['multi'],
                           batch_timeout_ms=20.0) as batcher:
        reqs = [(_x(10 + i, 1 + i % 3)) for i in range(12)]
        futs = [batcher.submit([x]) for x in reqs]
        for x, fut in zip(reqs, futs):
            got, = fut.result(timeout=30)
            assert got.shape == (x.shape[0], 4)
            want, = pred.run([x])
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        snap = batcher.stats.snapshot()
        assert snap['requests'] == 12
        assert snap['batches'] <= 12  # some coalescing happened or not —
        # but every row was accounted
        assert snap['queue_depth'] == 0


def test_timeout_flushes_lone_request(artifacts):
    # single bucket of 16: a lone 1-row request can only leave the queue
    # via the timeout flush (rows < max never fills the bucket)
    with BatchingPredictor(artifacts['single'],
                           batch_timeout_ms=60.0) as batcher:
        t0 = time.perf_counter()
        got, = batcher.run([_x(20, 1)], timeout=30)
        dt = time.perf_counter() - t0
    assert got.shape == (1, 4)
    assert dt >= 0.055  # held for the full coalescing window before flush
    want, = artifacts['pred'].run([_x(20, 1)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_per_request_error_isolation(artifacts):
    with BatchingPredictor(artifacts['multi'],
                           batch_timeout_ms=20.0) as batcher:
        good1 = batcher.submit([_x(30, 2)])
        bad_shape = batcher.submit([_x(31, 2).reshape(2, 2, DIM // 2)])
        too_big = batcher.submit([_x(32, 64)])  # > largest bucket
        good2 = batcher.submit([_x(33, 3)])
        with pytest.raises(ValueError, match='per-request shape'):
            bad_shape.result(timeout=30)
        with pytest.raises(ValueError, match='exceeds max_batch_size'):
            too_big.result(timeout=30)
        for fut, seed, rows in ((good1, 30, 2), (good2, 33, 3)):
            got, = fut.result(timeout=30)
            want, = artifacts['pred'].run([_x(seed, rows)])
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_cancelled_future_does_not_poison_the_batch(artifacts):
    # queued futures are never marked running, so a client cancel() always
    # wins; delivery must skip it without killing the worker thread or
    # stranding the batch's other requests
    pred = artifacts['pred']
    with BatchingPredictor(artifacts['single'],
                           batch_timeout_ms=40.0) as batcher:
        doomed = batcher.submit([_x(80, 1)])
        assert doomed.cancel()
        live = batcher.submit([_x(81, 2)])
        got, = live.result(timeout=30)
        want, = pred.run([_x(81, 2)])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        got2, = batcher.run([_x(82, 1)], timeout=30)  # next batch serves too
        assert got2.shape == (1, 4)


def test_caller_buffer_reuse_does_not_corrupt_request(artifacts):
    # dispatch is async: a client that refills its own buffer right after
    # submit() (standard producer pattern) must not corrupt the in-flight
    # request — submit snapshots caller-owned arrays
    pred = artifacts['pred']
    buf = _x(90, 2)
    want, = pred.run([buf.copy()])
    with BatchingPredictor(artifacts['multi'],
                           batch_timeout_ms=30.0) as batcher:
        fut = batcher.submit([buf])
        buf[:] = -1e9  # refill for the "next" request while in flight
        got, = fut.result(timeout=30)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pad_partial_false_restores_strict_shapes(artifacts):
    served = CompiledPredictor(artifacts['single'])
    with pytest.raises(ValueError, match='expected shape'):
        served.run([_x(21, 5)], pad_partial=False)


def test_submit_after_close_raises(artifacts):
    batcher = BatchingPredictor(artifacts['single'], batch_timeout_ms=1.0)
    batcher.run([_x(40, 1)], timeout=30)
    batcher.close()
    batcher.close()  # idempotent
    with pytest.raises(RuntimeError, match='closed'):
        batcher.submit([_x(40, 1)])


def test_batcher_rejects_lod_and_unaligned_artifacts(tmp_path):
    with fluid.scope_guard(fluid.core.Scope()), fluid.unique_name.guard():
        pred = _build_predictor(str(tmp_path), reduce_fetch=True)
    art = str(tmp_path / 'artifact')
    export_compiled(pred, [_x(3, 8)], art)
    # the scalar reduce_mean fetch cannot be sliced per request: load-time
    # refusal (v3 signatures record fetch shapes)
    with pytest.raises(ValueError, match='not batch-aligned'):
        BatchingPredictor(art)


# -- acceptance: throughput + bit-identity ----------------------------------

def test_64_concurrent_requests_4x_faster_and_bit_identical(tmp_path):
    """ISSUE 1 acceptance: 64 concurrent bs-1 requests through the batcher
    achieve >= 4x the request throughput of sequential
    CompiledPredictor.run calls, with bit-identical per-request outputs
    (single 32-row bucket: every path runs the same compiled module).

    The model carries real per-bucket compute (4 fc layers of 2048 —
    heavy enough that the padded-bucket forward, not Python overhead,
    dominates both sides) so the comparison measures what batching
    amortizes: sequential serving pays a FULL padded-bucket forward per
    bs-1 request, the batcher pays it once per ~32 coalesced requests."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name='img', shape=[DIM], dtype='float32')
        h = img
        for _ in range(4):
            h = fluid.layers.fc(h, 2048, act='relu')
        out = fluid.layers.fc(h, 4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    model_dir = str(tmp_path / 'model')
    fluid.io.save_inference_model(model_dir, ['img'], [out], exe, main)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    art = str(tmp_path / 'artifact')
    export_compiled(pred, [_x(49, 4)], art, batch_sizes=[32])
    xs = [_x(50 + i, 1) for i in range(64)]

    seq = CompiledPredictor(art)
    seq.run([xs[0]])  # warm the compile cache
    t0 = time.perf_counter()
    seq_out = [seq.run([x])[0] for x in xs]
    seq_dt = time.perf_counter() - t0

    # barrier: all 64 clients submit in one burst, so the coalescing
    # window races the sub-ms submits, not 64 thread startups (which can
    # exceed the window and split the batch — the flush is then measuring
    # thread-spawn time, not serving)
    with BatchingPredictor(art, batch_timeout_ms=250.0) as batcher:
        batcher.warmup()
        results = [None] * 64
        gate = threading.Barrier(64)

        def client(i):
            gate.wait(timeout=60)
            results[i] = batcher.submit([xs[i]]).result(timeout=60)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(64)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        bat_dt = time.perf_counter() - t0
        snap = batcher.stats.snapshot()

    for i in range(64):
        got, = results[i]
        assert np.array_equal(got, seq_out[i]), (
            'request %d not bit-identical to its unbatched run' % i)
    assert snap['requests'] == 64
    speedup = seq_dt / bat_dt
    assert speedup >= 4.0, (
        'batched serving only %.1fx sequential (%.3fs vs %.3fs, '
        'occupancy %.2f)' % (speedup, bat_dt, seq_dt, snap['occupancy']))


# -- serving metrics ---------------------------------------------------------

def test_serving_stats_and_profiler_report(artifacts):
    batcher = BatchingPredictor(artifacts['multi'], batch_timeout_ms=5.0)
    name = batcher._profiler_name
    assert name and name in profiler._serving_sources  # auto-registered
    for i in range(6):
        batcher.run([_x(60 + i, 2)], timeout=30)
    report = profiler.serving_report()
    snap = report[name]
    assert snap['requests'] == 6
    assert snap['queue_depth'] == 0
    assert 0.0 < snap['occupancy'] <= 1.0
    assert snap['p99_ms'] >= snap['p50_ms'] > 0.0
    batcher.close()
    assert name not in profiler._serving_sources


# -- load shedding + per-request deadlines (ISSUE 6 satellite) ---------------

def test_overloaded_queue_sheds_requests_fast(artifacts):
    """Beyond max_queue, submit() resolves to ServerOverloaded instead of
    queueing into unbounded latency; shed requests are counted and never
    cost a padded batch slot."""
    from paddle_tpu.inference import ServerOverloaded
    batcher = BatchingPredictor(artifacts['multi'], max_queue=2,
                                batch_timeout_ms=5.0)
    with batcher.stats._lock:
        batcher.stats.queue_depth = 2       # simulate a standing backlog
    fut = batcher.submit([_x(0, 1)])
    with pytest.raises(ServerOverloaded, match='shed'):
        fut.result(5)
    with batcher.stats._lock:
        batcher.stats.queue_depth = 0
    out, = batcher.run([_x(1, 1)], timeout=30)  # back under: serves fine
    assert out.shape[0] == 1
    assert batcher.stats.snapshot()['shed'] == 1
    batcher.close()


def test_overload_flood_all_requests_resolve(artifacts):
    """Under a flood with a tight max_queue every future resolves — to a
    result or to ServerOverloaded — and the sum adds up; nothing hangs."""
    from paddle_tpu.inference import ServerOverloaded
    batcher = BatchingPredictor(artifacts['multi'], max_queue=4,
                                batch_timeout_ms=1.0)
    batcher.warmup()
    futs = [batcher.submit([_x(i, 1)]) for i in range(64)]
    served = shed = 0
    for f in futs:
        try:
            f.result(60)
            served += 1
        except ServerOverloaded:
            shed += 1
    assert served + shed == 64 and served >= 1
    snap = batcher.stats.snapshot()
    assert snap['shed'] == shed and snap['requests'] == served
    assert snap['queue_depth'] == 0
    batcher.close()


def test_expired_deadline_fails_before_dispatch(artifacts):
    from paddle_tpu.inference import DeadlineExceeded
    batcher = BatchingPredictor(artifacts['multi'], batch_timeout_ms=5.0)
    batcher.warmup()
    fut = batcher.submit([_x(2, 1)], deadline_ms=0.0)
    with pytest.raises(DeadlineExceeded, match='expired'):
        fut.result(5)
    out, = batcher.run([_x(3, 1)], timeout=30)   # no-deadline peer serves
    assert out.shape[0] == 1
    snap = batcher.stats.snapshot()
    assert snap['expired'] == 1 and snap['queue_depth'] == 0
    assert snap['requests'] == 1   # the expired one never dispatched
    batcher.close()


def test_generous_deadline_is_met(artifacts):
    batcher = BatchingPredictor(artifacts['multi'], batch_timeout_ms=1.0)
    batcher.warmup()
    out, = batcher.run([_x(4, 2)], timeout=30, deadline_ms=60000.0)
    assert out.shape[0] == 2
    assert batcher.stats.snapshot()['expired'] == 0
    batcher.close()


def test_shed_and_expired_in_profiler_serving_report(artifacts):
    from paddle_tpu.inference import ServerOverloaded, DeadlineExceeded
    batcher = BatchingPredictor(artifacts['multi'], max_queue=1,
                                batch_timeout_ms=5.0)
    batcher.warmup()
    with batcher.stats._lock:
        batcher.stats.queue_depth = 1
    with pytest.raises(ServerOverloaded):
        batcher.submit([_x(5, 1)]).result(5)
    with batcher.stats._lock:
        batcher.stats.queue_depth = 0
    with pytest.raises(DeadlineExceeded):
        batcher.submit([_x(6, 1)], deadline_ms=0.0).result(5)
    snap = profiler.serving_report()[batcher._profiler_name]
    assert snap['shed'] == 1 and snap['expired'] == 1
    batcher.close()


# -- serve.py bench CLI (framework-free process) -----------------------------

def test_serve_bench_cli_fresh_process_framework_free(artifacts, tmp_path):
    in_path = str(tmp_path / 'in.npz')
    np.savez(in_path, img=_x(70, 1))
    probe = (
        "import runpy, sys\n"
        "sys.argv = ['serve.py', 'bench', %r, %r, '24', '5']\n"
        "try:\n"
        "    runpy.run_path(%r, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert (e.code or 0) == 0, e.code\n"
        "bad = [m for m in sys.modules if m.startswith('paddle_tpu')]\n"
        "assert not bad, 'framework leaked into serving: %%r' %% bad\n"
        % (artifacts['multi'], in_path,
           os.path.join(REPO, 'paddle_tpu', 'inference', 'serve.py')))
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    r = subprocess.run([sys.executable, '-c', probe], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    last = [l for l in r.stdout.splitlines() if l.strip()][-1]
    stats = json.loads(last)
    assert stats['req_s'] > 0 and stats['p99_ms'] >= stats['p50_ms']


# -- slow tier: threaded stress + Poisson bench scenario ---------------------

@pytest.mark.slow
def test_threaded_stress(artifacts):
    pred = artifacts['pred']
    wants = {}
    for i in range(40):
        rows = 1 + i % 5
        wants[i] = (rows, pred.run([_x(200 + i, rows)])[0])
    with BatchingPredictor(artifacts['multi'],
                           batch_timeout_ms=2.0) as batcher:
        errors = []

        def client(tid):
            try:
                for i in range(tid, 40, 8):
                    rows, want = wants[i]
                    got, = batcher.submit(
                        [_x(200 + i, rows)]).result(timeout=60)
                    np.testing.assert_allclose(got, want, rtol=1e-5,
                                               atol=1e-6)
            except Exception as e:  # surfaced after join
                errors.append((tid, e))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = batcher.stats.snapshot()
    assert not errors, errors[:3]
    assert snap['requests'] == 40
    assert snap['queue_depth'] == 0
