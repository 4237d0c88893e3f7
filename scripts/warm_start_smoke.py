#!/usr/bin/env python
"""Warm-start smoke (ISSUE 5, wired into scripts/ci.sh): cold A/B warm in
FRESH subprocesses against a tmp cache dir.

Serving half (the acceptance bar): export a 3-bucket artifact WITHOUT
sidecars, measure a cold replica (load + first answer per bucket =
3 XLA compiles), prewarm it with `tools/cache_ctl.py prewarm`, then
measure a warm replica — which must perform ZERO XLA compiles, answer
with byte-identical fetches, and cut the cold-start wall time >= 3x.

Executor half: tests/compile_cache_worker.py twice against one
PTPU_COMPILE_CACHE dir — run 2 must hit the executable tier for every
entry (zero compiles) with byte-identical fetches.

Also exercises cache_ctl stats/prune/prewarm exit codes.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MIN_SPEEDUP = float(os.environ.get('PTPU_WARM_START_MIN_SPEEDUP', '3'))

# a fresh serving replica, framework-free (serve.py by path): loads every
# bucket of the artifact and answers one request per bucket; prints wall
# time (post-import, the compile-dominated cold-start cost) and the net
# XLA compile count
PROBE = r'''
import json, sys, time
import numpy as np
sys.path.insert(0, sys.argv[3])
from jax._src import monitoring
n = [0, 0]
monitoring.register_event_duration_secs_listener(
    lambda ev, s, **kw: n.__setitem__(0, n[0] + 1)
    if ev == '/jax/core/compile/backend_compile_duration' else None)
monitoring.register_event_listener(
    lambda ev, **kw: n.__setitem__(1, n[1] + 1)
    if ev == '/jax/compilation_cache/cache_hits' else None)
import serve
art, out_path = sys.argv[1], sys.argv[2]
t0 = time.perf_counter()
with open(art + '/signature.json') as f:
    buckets = json.load(f)['buckets']
outs = {}
for b in buckets:
    pred = serve.CompiledPredictor(art + '/' + serve._BUCKET_DIR % b)
    feed = {e['name']: np.ones(e['shape'], dtype=np.dtype(e['dtype']))
            for e in pred._sig['feeds']}
    outs['b%d' % b] = np.asarray(pred.run(feed)[0])
wall = time.perf_counter() - t0
assert not any(m.startswith('paddle_tpu') for m in sys.modules)
np.savez(out_path, **outs)
print('PROBE ' + json.dumps({'wall_s': round(wall, 4),
                             'xla_compiles_net': n[0] - n[1]}))
'''


def run(cmd, env_extra=None, tag=''):
    env = dict(os.environ)
    env.update(env_extra or {})
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=900)
    if p.returncode != 0:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit('%s failed (exit %d)' % (tag or cmd[0],
                                                  p.returncode))
    return p.stdout


def parse(stdout, marker):
    line = [l for l in stdout.splitlines() if l.startswith(marker)][0]
    return json.loads(line[len(marker):])


def main():
    import numpy as np
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    tmp = tempfile.mkdtemp(prefix='ptpu_warm_smoke_')
    art = os.path.join(tmp, 'artifact')
    cache = os.path.join(tmp, 'cache')
    ctl = os.path.join(REPO, 'tools', 'cache_ctl.py')
    try:
        # -- build + export the 3-bucket artifact, NO sidecars (cold) ----
        import paddle_tpu as fluid
        from paddle_tpu.inference import (Config, create_predictor,
                                          export_compiled)
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = startup.random_seed = 21
        with fluid.program_guard(main_p, startup):
            # deep enough that the cold path's 3 bucket compiles dominate
            # the measurement (the warm path's cost is load-only and does
            # not grow with model size — the smoke's >=3x margin widens
            # with depth)
            x = fluid.layers.data(name='x', shape=[64], dtype='float32')
            h = fluid.layers.fc(x, size=1024, act='relu')
            h = fluid.layers.fc(h, size=1024, act='relu')
            h = fluid.layers.fc(h, size=1024, act='relu')
            out = fluid.layers.fc(h, size=16, act='softmax')
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        model_dir = os.path.join(tmp, 'model')
        fluid.io.save_inference_model(model_dir, ['x'], [out], exe, main_p)
        cfg = Config(model_dir)
        cfg.disable_gpu()
        pred = create_predictor(cfg)
        export_compiled(pred, {'x': np.ones((32, 64), np.float32)},
                        art, batch_sizes=[8, 16, 32], precompile=False)

        inference_dir = os.path.join(REPO, 'paddle_tpu', 'inference')
        probe = [sys.executable, '-c', PROBE]

        # -- cold replica -----------------------------------------------
        cold = parse(run(probe + [art, os.path.join(tmp, 'cold.npz'),
                                  inference_dir], tag='cold probe'),
                     'PROBE ')
        assert cold['xla_compiles_net'] > 0, \
            'cold replica performed no compiles?! %r' % cold

        # -- prewarm via the CLI, then the warm replica ------------------
        run([sys.executable, ctl, 'prewarm', art], tag='cache_ctl prewarm')
        warm = parse(run(probe + [art, os.path.join(tmp, 'warm.npz'),
                                  inference_dir], tag='warm probe'),
                     'PROBE ')
        assert warm['xla_compiles_net'] == 0, \
            'warm replica still compiled: %r' % warm
        with np.load(os.path.join(tmp, 'cold.npz')) as a, \
                np.load(os.path.join(tmp, 'warm.npz')) as b:
            for k in a.files:
                assert a[k].tobytes() == b[k].tobytes(), \
                    'fetch %s differs cold vs warm' % k
        speedup = cold['wall_s'] / max(warm['wall_s'], 1e-9)
        print('artifact cold-start: cold=%.3fs (%d compiles)  '
              'warm=%.3fs (0 compiles)  speedup=%.1fx'
              % (cold['wall_s'], cold['xla_compiles_net'], warm['wall_s'],
                 speedup))
        assert speedup >= MIN_SPEEDUP, \
            'warm start must cut artifact cold-start wall time >= %.1fx, ' \
            'got %.2fx' % (MIN_SPEEDUP, speedup)

        # -- executor warm start through the persistent cache ------------
        worker = os.path.join(REPO, 'tests', 'compile_cache_worker.py')
        c = parse(run([sys.executable, worker, cache,
                       os.path.join(tmp, 'exe_cold.npz')],
                      tag='executor cold'), 'CC_STATS ')
        w = parse(run([sys.executable, worker, cache,
                       os.path.join(tmp, 'exe_warm.npz')],
                      tag='executor warm'), 'CC_STATS ')
        assert c['misses'] >= 3 and c['compiles'] == c['misses'], c
        assert w['misses'] == 0 and w['compiles'] == 0, w
        assert w['xla_compiles_net'] == 0, w
        with np.load(os.path.join(tmp, 'exe_cold.npz')) as a, \
                np.load(os.path.join(tmp, 'exe_warm.npz')) as b:
            for k in a.files:
                assert a[k].tobytes() == b[k].tobytes(), k
        print('executor warm start: cold=%.2fs (%d compiles, %.2fs '
              'compiling)  warm=%.2fs (0 compiles, %d exec hits)'
              % (c['wall_s'], c['compiles'], c['compile_s'], w['wall_s'],
                 w['exec_hits']))

        # -- cache_ctl exit codes ---------------------------------------
        run([sys.executable, ctl, 'stats', '--dir', cache],
            tag='cache_ctl stats')
        run([sys.executable, ctl, 'prune', '--dir', cache, '--all'],
            tag='cache_ctl prune')
        rc = subprocess.run([sys.executable, ctl, 'prewarm',
                             os.path.join(tmp, 'missing')],
                            capture_output=True).returncode
        assert rc == 2, 'prewarm on a missing dir must exit 2, got %d' % rc
        print('WARM_START_SMOKE_OK speedup=%.1fx' % speedup)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == '__main__':
    main()
