"""CI smoke for fault-tolerant training (ISSUE 6):

1. Kill-and-resume: SIGKILL a trainer mid-epoch at a step boundary
   (racing the async checkpoint writer), restart it on the same
   checkpoint dir with the persistent compile cache on, and assert
   (a) the restart actually resumed from a committed checkpoint,
   (b) seconds-scale resume (startup+restore bounded), and
   (c) BIT parity: every loss — including re-run overlap steps — and
       the final params digest match an uninterrupted run.
2. Chaos loop: tools/chaos.py, 2 kill rounds with random checkpoint
   corruption between incarnations — restore must fall back loudly,
   never load a damaged checkpoint.
3. Checkpoint-stall budget: the smallnet multi-step loop with
   checkpointing every dispatch group reports ckpt stall < 2% of step
   time via profiler.training_report() (the ISSUE 6 acceptance bar).
"""
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

WORKER = os.path.join(REPO, 'tests', 'checkpoint_kill_worker.py')
TOTAL, K, EVERY, KILL_AT = 24, 4, 4, 12
RESUME_BUDGET_S = 60.0      # "seconds-scale": startup+restore+cache-warm


def read_out(path):
    resume, startup_s, losses, sha = None, None, {}, None
    for line in open(path):
        parts = line.split()
        if parts[0] == 'RESUME':
            resume = int(parts[1])
            startup_s = float(parts[2]) if len(parts) > 2 else None
        elif parts[0] == 'DONE':
            sha = parts[1]
        else:
            losses[int(parts[0])] = float(parts[1])
    return resume, startup_s, losses, sha


def run_worker(env, ckpt, out, kill_at=0):
    argv = [sys.executable, WORKER, ckpt, out, str(TOTAL), str(K),
            str(EVERY)]
    if kill_at:
        argv += [str(kill_at), '1']
    t0 = time.time()
    r = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=600)
    return r, time.time() - t0


def kill_resume_phase(work):
    env = dict(os.environ)
    env['PTPU_COMPILE_CACHE'] = '1'
    env['JAX_COMPILATION_CACHE_DIR'] = os.path.join(work, 'cache')

    r, ref_wall = run_worker(env, '-', os.path.join(work, 'ref.txt'))
    assert r.returncode == 0, r.stderr[-2000:]
    _, _, ref_losses, ref_sha = read_out(os.path.join(work, 'ref.txt'))
    assert len(ref_losses) == TOTAL and ref_sha

    out1 = os.path.join(work, 'run1.txt')
    ckpt = os.path.join(work, 'ckpts')
    r, _ = run_worker(env, ckpt, out1, kill_at=KILL_AT)
    assert r.returncode == -signal.SIGKILL, \
        'worker survived its own SIGKILL? rc=%s' % r.returncode
    _, _, losses1, sha1 = read_out(out1)
    assert sha1 is None and len(losses1) >= KILL_AT

    out2 = os.path.join(work, 'run2.txt')
    r, resume_wall = run_worker(env, ckpt, out2)
    assert r.returncode == 0, r.stderr[-2000:]
    resume, startup_s, losses2, sha2 = read_out(out2)
    assert resume and 0 < resume <= KILL_AT, \
        'no committed checkpoint was restored (resume=%r)' % resume
    assert startup_s is not None and startup_s < RESUME_BUDGET_S, \
        'restore took %.1fs — not seconds-scale' % (startup_s or -1)
    assert sha2 == ref_sha, 'final params diverged after kill+resume'
    for idx, v in {**losses1, **losses2}.items():
        assert v == ref_losses[idx], 'loss diverged at step %d' % idx
    for idx in set(losses1) & set(losses2):
        assert losses1[idx] == losses2[idx], \
            'overlap step %d not reproducible' % idx
    print('[crash_resume] kill@%d -> resumed@%d: %d/%d losses bit-match, '
          'params digest equal; restore %.2fs, resumed run wall %.1fs '
          '(ref %.1fs)' % (KILL_AT, resume, len(losses1) + len(losses2
                           ) - len(set(losses1) & set(losses2)), TOTAL,
                           startup_s, resume_wall, ref_wall))


def chaos_phase(work):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'chaos.py'),
         '--rounds', '2', '--corrupt', 'random',
         '--workdir', os.path.join(work, 'chaos')],
        capture_output=True, text=True, timeout=600)
    sys.stdout.write(r.stdout)
    assert r.returncode == 0, 'chaos loop failed:\n%s%s' % (
        r.stdout[-2000:], r.stderr[-2000:])


def stall_budget_phase(work):
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.core.checkpoint import CheckpointManager
    sys.path.insert(0, os.path.join(REPO, 'models'))
    from smallnet import build_train_net

    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = 7
    with fluid.program_guard(main_p, startup_p):
        _img, _lab, avg_loss, _acc = build_train_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    r = np.random.RandomState(0)
    bs, dispatches = 32, 4

    def feed(d):
        return {'data': np.stack([r.randn(bs, 3, 32, 32).astype(np.float32)
                                  for _ in range(K)]),
                'label': np.stack([r.randint(0, 10, (bs, 1))
                                   for _ in range(K)])}

    with fluid.scope_guard(scope):
        exe.run(startup_p)
        with CheckpointManager(os.path.join(work, 'smallnet-ckpts'),
                               every_steps=K, keep_last_n=2) as mgr:
            for d in range(dispatches):
                exe.run_steps(main_p, feed=feed(d), fetch_list=[avg_loss],
                              steps=K, checkpoint=mgr)
            mgr.flush()
            committed = mgr.stats['commits']
    snap = profiler.training_report()['executor@%x' % id(exe)]
    exe.close()
    assert committed >= 1, 'no checkpoint committed during the loop'
    assert snap['ckpt_stall_pct'] < 2.0, \
        'checkpoint stall %.2f%% of step time exceeds the 2%% budget' \
        % snap['ckpt_stall_pct']
    print('[crash_resume] smallnet multi-step: %d commits, checkpoint '
          'stall %.3f%% of step time (< 2%% budget), %.1f ms total stall'
          % (committed, snap['ckpt_stall_pct'], snap['ckpt_stall_ms']))


def main():
    work = tempfile.mkdtemp(prefix='ptpu-crash-resume-')
    kill_resume_phase(work)
    chaos_phase(work)
    stall_budget_phase(work)
    print('[crash_resume] OK')


if __name__ == '__main__':
    main()
