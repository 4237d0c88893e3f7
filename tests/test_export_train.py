"""Tracer-free TRAINING deploy path (VERDICT r4 missing #1): the
reference trains from a saved program with no Python
(train/demo_trainer.cc:1, train/test_train_recognize_digits.cc:1); here
export_train_step serializes the full train step (params + optimizer
state as inputs/outputs, rng as input) and serve.py's CompiledTrainer
runs it — losses must bit-match the in-framework Executor step for step,
and the serving process must never import the framework."""
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.inference import export_train_step, load_trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 3


def _build():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[12], dtype='float32')
        label = fluid.layers.data('label', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, 24, act='relu')
        h = fluid.layers.dropout(h, dropout_prob=0.3)  # rng is exercised
        logits = fluid.layers.fc(h, 5)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=label))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
    return main, startup, loss


def _feed():
    rng = np.random.RandomState(0)
    return {'x': rng.randn(16, 12).astype(np.float32),
            'label': rng.randint(0, 5, (16, 1)).astype(np.int64)}


def _init_scope(startup):
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return {n: np.asarray(scope.get(n)) for n in scope.local_var_names()
            if scope.get(n) is not None}


def _framework_losses(main, init, loss, feed, steps=STEPS):
    scope = fluid.core.Scope()
    for n, v in init.items():
        scope.set(n, v)
    exe = fluid.Executor(fluid.CPUPlace())
    out = []
    with fluid.scope_guard(scope):
        for _ in range(steps):
            l, = exe.run(main, feed=feed, fetch_list=[loss])
            out.append(np.asarray(l))
    final = {n: np.asarray(scope.get(n)) for n in init}
    return np.stack(out), final


def _export(main, init, loss, feed, art_dir):
    scope = fluid.core.Scope()
    for n, v in init.items():
        scope.set(n, v)
    export_train_step(main, feed, [loss], art_dir, scope=scope)


def test_trainer_bitmatches_executor(tmp_path):
    main, startup, loss = _build()
    init = _init_scope(startup)
    feed = _feed()
    want, want_final = _framework_losses(main, init, loss, feed)

    art = str(tmp_path / 'train_art')
    _export(main, init, loss, feed, art)
    trainer = load_trainer(art)
    got = np.stack([trainer.step(feed)[0] for _ in range(STEPS)])
    np.testing.assert_array_equal(got, want)
    # the carried state equals the in-framework scope after 3 steps
    final = trainer.state
    for n in want_final:
        np.testing.assert_array_equal(final[n], want_final[n], err_msg=n)


def test_trainer_checkpoint_roundtrip(tmp_path):
    """save_state/load_state: resume continues the exact trajectory."""
    main, startup, loss = _build()
    init = _init_scope(startup)
    feed = _feed()
    want, _ = _framework_losses(main, init, loss, feed, steps=4)

    art = str(tmp_path / 'train_art')
    _export(main, init, loss, feed, art)
    t1 = load_trainer(art)
    first = np.stack([t1.step(feed)[0] for _ in range(2)])
    ckpt = str(tmp_path / 'ckpt.npz')
    t1.save_state(ckpt)

    t2 = load_trainer(art)
    t2.load_state(ckpt)  # restores state AND the rng step counter
    rest = np.stack([t2.step(feed)[0] for _ in range(2)])
    np.testing.assert_array_equal(np.concatenate([first, rest]), want)


def test_train_fresh_process_never_imports_framework(tmp_path):
    main, startup, loss = _build()
    init = _init_scope(startup)
    feed = _feed()
    want, want_final = _framework_losses(main, init, loss, feed)

    art = str(tmp_path / 'train_art')
    _export(main, init, loss, feed, art)
    np.savez(str(tmp_path / 'feeds.npz'), **feed)

    probe = (
        "import runpy, sys\n"
        "sys.argv = ['serve.py', 'train', %r, %r, %r, '%d', %r]\n"
        "try:\n"
        "    runpy.run_path(%r, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert (e.code or 0) == 0, e.code\n"
        "bad = [m for m in sys.modules if m.startswith('paddle_tpu')]\n"
        "assert not bad, 'framework leaked into training: %%r' %% bad\n"
        % (art, str(tmp_path / 'feeds.npz'), str(tmp_path / 'out.npz'),
           STEPS, str(tmp_path / 'ckpt.npz'),
           os.path.join(REPO, 'paddle_tpu', 'inference', 'serve.py')))
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    r = subprocess.run([sys.executable, '-c', probe], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    with np.load(str(tmp_path / 'out.npz')) as out:
        got = out[list(out.files)[0]]
    np.testing.assert_array_equal(got.reshape(want.shape), want)
    # checkpoint written by the framework-free process matches the
    # in-framework final state
    with np.load(str(tmp_path / 'ckpt.npz')) as z:
        for n in want_final:
            np.testing.assert_array_equal(z[n], want_final[n], err_msg=n)
