"""Compiled bulk-inference loop (ISSUE 3 tentpole): run_batches scans the
per-batch compiled program over K pre-staged batches in ONE dispatch,
bit-identical per batch to K sequential run() calls through the same
bucket — the inference mirror of Executor.run_steps. Covers: exact
bit-identity (dense matmul model, in-framework Predictor AND the
framework-free CompiledPredictor), a LoD bucket artifact, partial-tail
flush through a smaller compiled group, donation safety (no
caller-visible buffer reuse), partial dense-batch padding, the profiler
bulk-infer report, and a fresh-process CLI loop round-trip."""
import json
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.inference import (Config, create_predictor, export_compiled,
                                  load_compiled)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_and_save(dirname, seed=3):
    """Dense matmul-only model: XLA compiles scan bodies bit-identically
    to top-level code for matmuls (PERF_NOTES.md conv-in-scan caveat is
    why this is NOT a conv net), so run_batches must match run() EXACTLY."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name='img', shape=[8], dtype='float32')
        h = fluid.layers.fc(img, 16, act='relu')
        out = fluid.layers.fc(h, 4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(dirname, ['img'], [out], exe, main)


def _predictor(tmp_path):
    model_dir = str(tmp_path / 'model')
    _build_and_save(model_dir)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    return create_predictor(cfg)


def test_predictor_run_batches_bit_identity(tmp_path):
    pred = _predictor(tmp_path)
    rng = np.random.RandomState(0)
    xs = [rng.randn(5, 8).astype(np.float32) for _ in range(6)]
    seq = [pred.run([x])[0] for x in xs]
    bulk = pred.run_batches([[x] for x in xs])
    assert len(bulk) == 6
    for i, (s, b) in enumerate(zip(seq, bulk)):
        assert np.array_equal(s, b[0]), i
    # dict-form batches and list-form batches agree
    bulk2 = pred.run_batches([{'img': x} for x in xs])
    for b, b2 in zip(bulk, bulk2):
        assert np.array_equal(b[0], b2[0])


def test_predictor_run_batches_validates(tmp_path):
    pred = _predictor(tmp_path)
    x = np.zeros((5, 8), np.float32)
    assert pred.run_batches([]) == []
    try:
        pred.run_batches([{'wrong': x}])
        assert False, 'missing feed must raise'
    except ValueError as e:
        assert 'img' in str(e)


def test_compiled_run_batches_bit_identity_and_tail(tmp_path):
    pred = _predictor(tmp_path)
    art = str(tmp_path / 'artifact')
    rng = np.random.RandomState(1)
    xs = [rng.randn(5, 8).astype(np.float32) for _ in range(5)]
    export_compiled(pred, [xs[0]], art)
    served = load_compiled(art)
    seq = [served.run([x])[0] for x in xs]

    bulk = served.run_batches([[x] for x in xs])
    for i, (s, b) in enumerate(zip(seq, bulk)):
        assert np.array_equal(s, b[0]), i
    st = served.bulk_stats()
    assert st['dispatches'] == 1 and st['batches'] == 5
    assert st['tail_flushes'] == 0

    # group=2 over 5 batches: 3 dispatches, the last a PARTIAL tail (1
    # batch) flushed through a smaller compiled group — same results
    bulk2 = served.run_batches([[x] for x in xs], group=2)
    for i, (s, b) in enumerate(zip(seq, bulk2)):
        assert np.array_equal(s, b[0]), i
    st = served.bulk_stats()
    assert st['dispatches'] == 4 and st['batches'] == 10
    assert st['tail_flushes'] == 1
    assert st['batches_per_dispatch'] == 2.5

    # group > K is a single smaller chunk, NOT a tail flush (no full
    # chunk preceded it — only its own size ever compiled)
    served.run_batches([[xs[0]], [xs[1]]], group=8)
    assert served.bulk_stats()['tail_flushes'] == 1


def test_compiled_run_batches_donation_safety(tmp_path):
    """Stacked loop inputs are donated to XLA — but they are staged
    copies: the caller's own arrays must stay intact and reusable, and
    repeated calls over the same arrays must reproduce bit-identically."""
    import jax
    pred = _predictor(tmp_path)
    art = str(tmp_path / 'artifact')
    rng = np.random.RandomState(2)
    x_np = rng.randn(5, 8).astype(np.float32)
    export_compiled(pred, [x_np], art)
    served = load_compiled(art)

    x_dev = jax.device_put(x_np)  # a caller-owned DEVICE array
    keep_np = x_np.copy()
    first = served.run_batches([[x_np], [x_dev], [x_np]])
    assert not x_dev.is_deleted()  # donation never consumed caller buffers
    assert np.array_equal(np.asarray(x_dev), keep_np)
    assert np.array_equal(x_np, keep_np)
    second = served.run_batches([[x_np], [x_dev], [x_np]])
    for a, b in zip(first, second):
        assert np.array_equal(a[0], b[0])


def test_compiled_run_batches_partial_dense_pad(tmp_path):
    """A partial dense batch (rows < compiled bucket) pads per-batch and
    slices back — run()'s pad_partial discipline, inside the loop."""
    pred = _predictor(tmp_path)
    art = str(tmp_path / 'artifact')
    rng = np.random.RandomState(3)
    full = rng.randn(5, 8).astype(np.float32)
    part = rng.randn(2, 8).astype(np.float32)
    export_compiled(pred, [full], art)
    served = load_compiled(art)
    want_full, = served.run([full])
    want_part, = served.run([part])  # padded by run()
    bulk = served.run_batches([[full], [part], [full]])
    assert np.array_equal(bulk[0][0], want_full)
    assert bulk[1][0].shape == (2, 4)
    assert np.array_equal(bulk[1][0], want_part)
    assert np.array_equal(bulk[2][0], want_full)


def _build_lod_model(dirname):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data('ids', shape=[1], dtype='int64', lod_level=1)
        emb = fluid.layers.embedding(input=ids, size=[50, 8])
        pooled = fluid.layers.sequence_pool(emb, 'average')
        out = fluid.layers.fc(pooled, size=4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(dirname, ['ids'], [out], exe, main)


def _ids_pair(lens, bucket_rows, seed):
    rng = np.random.RandomState(seed)
    total = int(sum(lens))
    data = rng.randint(0, 50, (total, 1)).astype(np.int64)
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    padded = np.zeros((bucket_rows, 1), np.int64)
    padded[:total] = data
    return (padded, [offs])


def test_compiled_run_batches_lod_bucket(tmp_path):
    """LoD feeds ride the scan as stacked runtime data+offsets (the
    traced-lod artifact convention): one bucket artifact serves K batches
    with DIFFERENT lod patterns in one dispatch, matching sequential
    run() per batch."""
    model_dir = str(tmp_path / 'model')
    art = str(tmp_path / 'artifact')
    _build_lod_model(model_dir)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    bucket = 12
    pairs = [_ids_pair(lens, bucket, seed=i) for i, lens in
             enumerate([[3, 5, 2], [4, 1, 6], [2, 2, 2]])]
    export_compiled(pred, {'ids': pairs[0]}, art)
    served = load_compiled(art)
    seq = [served.run({'ids': p})[0] for p in pairs]
    bulk = served.run_batches([{'ids': p} for p in pairs])
    for i, (s, b) in enumerate(zip(seq, bulk)):
        assert np.array_equal(s, b[0]), i


def test_profiler_infer_report_sources(tmp_path):
    from paddle_tpu import profiler
    pred = _predictor(tmp_path)
    art = str(tmp_path / 'artifact')
    x = np.random.RandomState(4).randn(5, 8).astype(np.float32)
    export_compiled(pred, [x], art)
    served = load_compiled(art)
    served.run_batches([[x], [x]])
    pred.run_batches([[x], [x], [x]])
    rep = profiler.infer_report()
    bulk = [v for k, v in rep.items() if k.startswith('bulk_infer:')]
    execs = [v for k, v in rep.items() if k.startswith('executor@')
             and v.get('batches') == 3]
    assert bulk and bulk[-1]['batches'] >= 2
    assert 0.0 < bulk[-1]['occupancy'] <= 1.0
    assert execs and execs[-1]['dispatches'] >= 1
    assert 'batches_per_dispatch' in execs[-1]


def test_fresh_process_loop_roundtrip(tmp_path):
    """serve.py loop in a FRESH process (run by file path — the package
    __init__ never executes): run_batches over a stacked npz must match
    in-process sequential run(), and the framework must never load."""
    pred = _predictor(tmp_path)
    art = str(tmp_path / 'artifact')
    rng = np.random.RandomState(6)
    xs = np.stack([rng.randn(5, 8).astype(np.float32) for _ in range(4)])
    export_compiled(pred, [xs[0]], art)
    served = load_compiled(art)
    want = np.stack([served.run([x])[0] for x in xs])
    np.savez(str(tmp_path / 'in.npz'), img=xs)

    probe = (
        "import runpy, sys\n"
        "sys.argv = ['serve.py', 'loop', %r, %r, %r, '3']\n"
        "try:\n"
        "    runpy.run_path(%r, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert (e.code or 0) == 0, e.code\n"
        "bad = [m for m in sys.modules if m.startswith('paddle_tpu')]\n"
        "assert not bad, 'framework leaked into serving: %%r' %% bad\n"
        % (art, str(tmp_path / 'in.npz'), str(tmp_path / 'out.npz'),
           os.path.join(REPO, 'paddle_tpu', 'inference', 'serve.py')))
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    r = subprocess.run([sys.executable, '-c', probe], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    with np.load(str(tmp_path / 'out.npz')) as out:
        got = out[list(out.files)[0]]
    # group='3' over 4 batches exercised the tail path cross-process too
    assert np.array_equal(got, want)
