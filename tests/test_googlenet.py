"""GoogLeNet + SE-ResNeXt model families build and train (parity with the
reference's benchmark/paddle/image/googlenet.py and
benchmark/fluid/models/se_resnext.py; the committed Xeon numbers they
bench against live in BASELINE.md)."""
import numpy as np

import paddle_tpu as fluid
from models.googlenet import build_train_net, googlenet


def test_googlenet_trains_one_batch():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        # lr=0.01 + momentum 0.9 diverges on a 2-sample random batch
        # (loss 2.36 -> 7.83 -> 325.8); 1e-3 descends monotonically
        images, label, loss, acc = build_train_net(
            dshape=(3, 64, 64), class_dim=10, lr=0.001)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    r = np.random.RandomState(0)
    feed = {'data': r.randn(2, 3, 64, 64).astype(np.float32),
            'label': r.randint(0, 10, (2, 1)).astype(np.int64)}
    vals = []
    for _ in range(3):
        l, = exe.run(main, feed=feed, fetch_list=[loss])
        vals.append(float(np.asarray(l).reshape(-1)[0]))
    assert np.isfinite(vals).all(), vals
    assert vals[-1] < vals[0], vals


def test_googlenet_infer_deterministic():
    """is_train=False kills dropout: two runs agree bit-for-bit."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        images = fluid.layers.data(name='data', shape=[3, 64, 64],
                                   dtype='float32')
        logits = googlenet(images, class_dim=10, is_train=False)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    x = np.random.RandomState(1).randn(2, 3, 64, 64).astype(np.float32)
    a, = exe.run(main, feed={'data': x}, fetch_list=[logits])
    b, = exe.run(main, feed={'data': x}, fetch_list=[logits])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.shape(a) == (2, 10)


def test_se_resnext_grouped_conv_shapes():
    """Cardinality-32 grouped 3x3s produce the documented stage shapes."""
    from models.se_resnext import se_resnext
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        images = fluid.layers.data(name='data', shape=[3, 64, 64],
                                   dtype='float32')
        logits = se_resnext(images, class_dim=7, depth=50, is_train=False)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    x = np.random.RandomState(2).randn(2, 3, 64, 64).astype(np.float32)
    out, = exe.run(main, feed={'data': x}, fetch_list=[logits])
    assert np.shape(out) == (2, 7)
    assert np.isfinite(np.asarray(out)).all()
