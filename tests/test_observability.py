"""Observability tail: profiler report + Chrome export, evaluators,
debugger/graphviz, teacher_student loss, new datasets."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid


def test_profiler_events_and_chrome_export(tmp_path, capsys):
    from paddle_tpu import profiler
    profiler.reset_profiler()
    with profiler.profiler():
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.fc(x, size=2)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        for _ in range(3):
            exe.run(feed={'x': np.ones((2, 4), np.float32)},
                    fetch_list=[y])
    out = capsys.readouterr().out
    # the aggregate report lists the executor's per-run spans
    assert 'exe/run' in out and 'exe/dispatch' in out and 'Calls' in out
    path = profiler.export_chrome_tracing(str(tmp_path / 'trace.json'))
    with open(path) as f:
        trace = json.load(f)
    for name in ('exe/run', 'exe/dispatch'):
        evs = [e for e in trace['traceEvents'] if e['name'] == name]
        assert len(evs) >= 3
        assert all(e['ph'] == 'X' and e['dur'] >= 0 for e in evs)
    # ids ride in stats, never in names
    assert not any('#' in e['name'] for e in trace['traceEvents'])


def test_chunk_evaluator_accumulates():
    inf = fluid.layers.data(name='i', shape=[1], dtype='int64', lod_level=1)
    lab = fluid.layers.data(name='l', shape=[1], dtype='int64', lod_level=1)
    ev = fluid.evaluator.ChunkEvaluator(inf, lab, chunk_scheme='IOB',
                                        num_chunk_types=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    gold = np.array([0, 1, 2, 3, 0], np.int64).reshape(-1, 1)
    pred = np.array([0, 1, 0, 1, 0], np.int64).reshape(-1, 1)
    feed = {'i': fluid.create_lod_tensor(pred, [[5]]),
            'l': fluid.create_lod_tensor(gold, [[5]])}
    for _ in range(2):  # two batches accumulate
        exe.run(feed=feed, fetch_list=[ev.metrics[0]])
    p, r, f1 = ev.eval(exe)
    assert p[0] == pytest.approx(2 / 3)
    assert r[0] == pytest.approx(2 / 3)
    ev.reset(exe)
    p, r, f1 = ev.eval(exe)
    assert p[0] == 0.0


def test_debugger_outputs(tmp_path):
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.fc(x, size=2, act='relu')
    path = fluid.debugger.draw_block_graphviz(
        fluid.default_main_program().global_block(),
        path=str(tmp_path / 'g.dot'))
    dot = open(path).read()
    assert 'digraph' in dot and 'mul' in dot and 'relu' in dot
    text = fluid.debugger.pprint_program_codes(
        fluid.default_main_program())
    assert 'mul' in text


def test_teacher_student_sigmoid_loss_values():
    x = fluid.layers.data(name='x', shape=[1], dtype='float32')
    lab = fluid.layers.data(name='lab', shape=[1], dtype='float32')
    loss = fluid.layers.teacher_student_sigmoid_loss(x, lab)
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.array([[0.5], [0.5], [0.5], [0.5]], np.float32)
    # labels: no-teacher clk0 (-2), no-teacher clk1 (-1),
    #         teacher 0.3 clk0 (0.3), teacher 0.3 clk1 (1.3)
    labs = np.array([[-2.0], [-1.0], [0.3], [1.3]], np.float32)
    got, = exe.run(feed={'x': xs, 'lab': labs}, fetch_list=[loss])
    got = np.asarray(got).reshape(-1)
    b = lambda x_, z: max(x_, 0) - x_ * z + np.log1p(np.exp(-abs(x_)))
    want = [b(0.5, 0), b(0.5, 1), b(0.5, 0) + b(0.5, 0.3),
            b(0.5, 1) + b(0.5, 0.3)]
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_new_datasets_learnable():
    from paddle_tpu.dataset import sentiment, mq2007, voc2012
    s = list(sentiment.test()())
    assert len(s) == 400 and {lab for _, lab in s[:10]} <= {0, 1}
    pair = next(mq2007.train_reader('pairwise')())
    assert pair[0].shape == (46,) and pair[1].shape == (46,)
    listw = next(mq2007.train_reader('listwise')())
    assert listw[0].ndim == 2
    img, seg = next(voc2012.train()())
    assert img.shape[0] == 3 and seg.shape == img.shape[1:]
    assert seg.max() < voc2012.CLASS_NUM
