"""MFU-pass smoke for CI (ISSUE 16): both round-18 rewrites A/B'd in one
session on CPU.

1. GoogLeNet horizontal_fuse: the widened train program must track the
   unfused one to ~1e-5 relative per step (XLA:CPU reduces the widened
   conv with a different grouping than three narrow convs — last-ulp
   drift, tests/test_horizontal_fuse.py documents the same tolerance;
   matmul nets are bit-exact). Speedup is NOT asserted on CPU: XLA:CPU
   runs conv bodies through a different code path and the MXU-padding
   win this pass targets does not exist there (PERF_NOTES round 6/18) —
   the A/B table is emitted for the log instead.
2. Stacked-LSTM fuse_layers: the single-scan multi-layer body must be
   BIT-IDENTICAL to the per-layer path across Adam steps (same rng
   stream, same gate math). Speedup is also not asserted: the fused win
   is scan-loop dispatch overhead on the accelerator; on CPU the two
   bodies are within noise of each other. Table emitted.

Exits non-zero on any parity violation. Runtime: ~60 s on 2 CPU cores.
"""
import json
import os
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _emit_table(title, headers, rows):
    print('\n%s' % title)
    print('| ' + ' | '.join(headers) + ' |')
    print('|' + '|'.join('---' for _ in headers) + '|')
    for row in rows:
        print('| ' + ' | '.join(str(c) for c in row) + ' |')
    print('', flush=True)


def _timed_ms(run, warmup=1, reps=3):
    for _ in range(warmup):
        run()
    t0 = time.perf_counter()
    for _ in range(reps):
        run()
    return (time.perf_counter() - t0) / reps * 1e3


def googlenet_ab():
    import paddle_tpu as fluid
    from paddle_tpu.passes.horizontal_fuse import horizontal_fuse_program
    from models.googlenet import build_train_net

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        _img, _lab, loss, _acc = build_train_net(
            dshape=(3, 64, 64), class_dim=10, lr=0.001)
    fused, report = horizontal_fuse_program(main, fetch_names=[loss.name])
    if report.details['convs_fused'] != 27:
        raise SystemExit('expected 27 inception convs fused, got %r'
                         % report.details['convs_fused'])

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        snap = {k: np.asarray(v) for k, v in scope._vars.items()
                if v is not None}
    rng = np.random.RandomState(0)
    feed = {'data': rng.randn(4, 3, 64, 64).astype(np.float32),
            'label': rng.randint(0, 10, (4, 1)).astype(np.int64)}

    arms = {}
    for name, prog in (('base', main), ('hfused', fused)):
        sc = fluid.core.Scope()
        for k, v in snap.items():
            sc.set(k, v)
        with fluid.scope_guard(sc):
            losses = [float(np.asarray(
                exe.run(prog, feed=feed, fetch_list=[loss.name])[0])
                .reshape(-1)[0]) for _ in range(2)]
            ms = _timed_ms(lambda: np.asarray(
                exe.run(prog, feed=feed, fetch_list=[loss.name],
                        return_numpy=False)[0]))
        arms[name] = {'losses': losses, 'ms_step': ms}

    base, hf = arms['base'], arms['hfused']
    dloss = max(abs(a - b) for a, b in zip(base['losses'], hf['losses']))
    rel = dloss / max(abs(v) for v in base['losses'])
    _emit_table(
        'googlenet horizontal_fuse A/B (train, batch 4, 64x64, CPU)',
        ['arm', 'convs fused', 'ms/step', 'speedup', 'parity rel |d|'],
        [['base', 0, '%.1f' % base['ms_step'], '1.00', '-'],
         ['hfused', report.details['convs_fused'],
          '%.1f' % hf['ms_step'],
          '%.2f' % (base['ms_step'] / hf['ms_step']),
          '%.2e' % rel]])
    if rel > 1e-5:
        raise SystemExit('googlenet hfused parity %.3e > 1e-5: %r vs %r'
                         % (rel, base['losses'], hf['losses']))
    return {'smoke': 'googlenet_hfuse_ab',
            'convs_fused': report.details['convs_fused'],
            'parity_rel': rel,
            'speedup_cpu': round(base['ms_step'] / hf['ms_step'], 3),
            'ok': True}


def lstm_ab():
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from models.stacked_lstm import build_stacked_lstm_train

    def build(fuse):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with unique_name.guard():
            with fluid.program_guard(main, startup):
                _ids, _lab, loss, _fl = build_stacked_lstm_train(
                    batch=8, vocab=200, emb_dim=16, hidden=16,
                    num_layers=3, seq_len=12, fuse_layers=fuse)
        return main, startup, loss

    rng = np.random.RandomState(1)
    feed = {'ids': rng.randint(1, 200, (8, 12)).astype(np.int64),
            'label': rng.randint(0, 2, (8, 1)).astype(np.int64)}
    arms = {}
    for name, fuse in (('perlayer', False), ('fused', True)):
        main, startup, loss = build(fuse)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [float(np.asarray(
                exe.run(main, feed=feed, fetch_list=[loss])[0])
                .reshape(-1)[0]) for _ in range(3)]
            ms = _timed_ms(lambda: np.asarray(
                exe.run(main, feed=feed, fetch_list=[loss],
                        return_numpy=False)[0]))
        arms[name] = {'losses': losses, 'ms_step': ms}

    pl, fu = arms['perlayer'], arms['fused']
    _emit_table(
        'stacked-LSTM fuse_layers A/B (3 layers, batch 8, CPU)',
        ['arm', 'ms/step', 'speedup', 'losses bit-equal'],
        [['perlayer', '%.1f' % pl['ms_step'], '1.00', '-'],
         ['fused', '%.1f' % fu['ms_step'],
          '%.2f' % (pl['ms_step'] / fu['ms_step']),
          pl['losses'] == fu['losses']]])
    if pl['losses'] != fu['losses']:
        raise SystemExit('fused lstm losses diverged: %r vs %r'
                         % (pl['losses'], fu['losses']))
    return {'smoke': 'lstm_fuse_layers_ab',
            'speedup_cpu': round(pl['ms_step'] / fu['ms_step'], 3),
            'ok': True}


def main():
    print(json.dumps(googlenet_ab()), flush=True)
    print(json.dumps(lstm_ab()), flush=True)
    print('mfu smoke OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
