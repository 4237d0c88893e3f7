"""LoD-capable compiled-artifact export (VERDICT r4 missing #3): the
reference's deployment API carries lod in PaddleTensor
(inference/api/paddle_api.h:1); here LoD feeds export in traced-offset
form (offsets are runtime inputs — one artifact per BUCKET shape serves
every batch), and LoD fetches come back as (values, [offsets]) pairs.
CRNN — the LoD north-star model — must serve tracer-free with output
parity against the Python Predictor on two bucket shapes."""
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.inference import (Config, create_predictor, export_compiled,
                                  load_compiled)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# LoD FEEDS: a text classifier over variable-length token sequences
# ---------------------------------------------------------------------------
def _build_text_model(dirname):
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


def _ids_batch(lens, bucket_rows, seed):
    rng = np.random.RandomState(seed)
    total = int(sum(lens))
    data = rng.randint(0, 50, (total, 1)).astype(np.int64)
    lt = fluid.create_lod_tensor(data, [list(lens)], traced=True,
                                 bucket_rows=bucket_rows)
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    padded = np.zeros((bucket_rows, 1), np.int64)
    padded[:total] = data
    return lt, (padded, [offs])


def test_lod_feed_export_two_buckets(tmp_path):
    model_dir = str(tmp_path / 'model')
    _build_text_model(model_dir)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    pred = create_predictor(cfg)

    # bucket A: 3 sequences, 12 padded rows; bucket B: 2 sequences, 20 rows
    for bi, (bucket_rows, lens1, lens2) in enumerate(
            [(12, [3, 5, 2], [4, 1, 6]), (20, [8, 9], [12, 5])]):
        art = str(tmp_path / ('artifact%d' % bi))
        lt1, pair1 = _ids_batch(lens1, bucket_rows, seed=bi)
        want1, = pred.run([lt1])
        export_compiled(pred, {'ids': pair1}, art)
        served = load_compiled(art)
        got1, = served.run({'ids': pair1})
        np.testing.assert_allclose(got1[:len(lens1)], want1,
                                   rtol=1e-5, atol=1e-6)
        # same artifact, DIFFERENT lod values in the same bucket: the
        # compiled module is lod-generic (offsets are runtime inputs)
        lt2, pair2 = _ids_batch(lens2, bucket_rows, seed=10 + bi)
        want2, = pred.run([lt2])
        got2, = served.run({'ids': pair2})
        np.testing.assert_allclose(got2[:len(lens2)], want2,
                                   rtol=1e-5, atol=1e-6)


def test_lod_feed_partial_bucket_pads_in_serve(tmp_path):
    """A LoD feed arriving BELOW the bucket capacity is padded up by
    serve.py itself (the executor's bucket_rows discipline) — the values
    array does not need host-side pre-padding. Regression: the dense
    partial-batch pad detection must not clobber this path."""
    model_dir = str(tmp_path / 'model')
    _build_text_model(model_dir)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    bucket_rows, lens = 12, [3, 5, 2]
    lt, (padded, offs) = _ids_batch(lens, bucket_rows, seed=3)
    want, = pred.run([lt])
    art = str(tmp_path / 'artifact')
    export_compiled(pred, {'ids': (padded, offs)}, art)
    served = load_compiled(art)
    got, = served.run({'ids': (padded[:sum(lens)], offs)})  # 10 < 12 rows
    np.testing.assert_allclose(got[:len(lens)], want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# LoD FETCHES: CRNN serves tracer-free (north star #4)
# ---------------------------------------------------------------------------
def _build_crnn_infer(dirname, img_w):
    from models.crnn import ctc_encoder
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        images = fluid.layers.data('pixel', shape=[1, 32, img_w],
                                   dtype='float32')
        logits = ctc_encoder(images, num_classes=10, rnn_hidden=16,
                             is_train=False)
        decoded = fluid.layers.ctc_greedy_decoder(input=logits, blank=10)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(dirname, ['pixel'], [decoded], exe, main)


def test_crnn_serves_tracer_free_two_buckets(tmp_path):
    """Output parity vs the Python Predictor on two bucket (image width)
    shapes: decoded token values AND lod offsets must match."""
    for img_w in (64, 96):
        model_dir = str(tmp_path / ('model%d' % img_w))
        art = str(tmp_path / ('artifact%d' % img_w))
        _build_crnn_infer(model_dir, img_w)
        cfg = Config(model_dir)
        cfg.disable_gpu()
        pred = create_predictor(cfg)
        x = np.random.RandomState(img_w).randn(3, 1, 32, img_w) \
            .astype(np.float32)
        want = pred.run([x], return_numpy=False)[0]   # LoDArray
        want_data = np.asarray(want.data)
        want_off = np.asarray(want.lod[0])

        export_compiled(pred, [x], art)
        served = load_compiled(art)
        (got_data, got_lod), = served.run([x])
        np.testing.assert_array_equal(got_data, want_data)
        np.testing.assert_array_equal(got_lod[0], want_off)


def test_crnn_artifact_fresh_process_no_framework(tmp_path):
    """The CRNN artifact (LoD output) runs via serve.py in a process that
    never imports the framework — npz carries '<name>.lod<i>' arrays."""
    model_dir = str(tmp_path / 'model')
    art = str(tmp_path / 'artifact')
    _build_crnn_infer(model_dir, 64)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    x = np.random.RandomState(3).randn(2, 1, 32, 64).astype(np.float32)
    want = pred.run([x], return_numpy=False)[0]
    export_compiled(pred, [x], art)
    np.savez(str(tmp_path / 'in.npz'), pixel=x)

    probe = (
        "import runpy, sys\n"
        "sys.argv = ['serve.py', %r, %r, %r]\n"
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
        name = [k for k in out.files if not k.endswith('.lod0')][0]
        np.testing.assert_array_equal(out[name], np.asarray(want.data))
        np.testing.assert_array_equal(out[name + '.lod0'],
                                      np.asarray(want.lod[0]))
