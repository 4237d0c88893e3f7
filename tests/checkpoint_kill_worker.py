"""Subprocess worker for test_checkpoint.py, scripts/crash_resume_smoke.py
and tools/chaos.py: one trainer incarnation that can be SIGKILLed at an
exact step boundary and later restarted on the same checkpoint dir.

usage: checkpoint_kill_worker.py CKPT_DIR OUT_FILE TOTAL_STEPS K EVERY \
           [KILL_AT_STEP [MIN_COMMITS]]

CKPT_DIR '-' disables checkpointing (the uninterrupted reference run).
KILL_AT_STEP > 0: SIGKILL self once that many steps are trained (after
their losses are flushed to OUT_FILE) — the kill lands at a step
boundary, racing the background checkpoint writer exactly like a real
preemption. MIN_COMMITS (default 1) delays the kill until that many
checkpoints have committed, so the restart provably has something to
resume from while the race with the in-flight write stays live.

OUT_FILE lines (append, flushed+fsynced per dispatch):
    RESUME <step>          restore point of this incarnation (0 = cold)
    <step_idx> <loss>      one per trained step (bit-reproducible)
    DONE <params_sha256>   end of training (digest over sorted params)

The net, data, and seeds are pure functions of the step index, so a
killed+resumed run must reproduce the uninterrupted run's losses and
final params BIT-EXACTLY (run_steps' rng stream is keyed by the restored
step counter).
"""
import hashlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ['JAX_PLATFORMS'] = 'cpu'

BATCH = 8


def build(seed=17):
    import paddle_tpu as fluid
    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = seed
    with fluid.program_guard(main_p, startup_p):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        lab = fluid.layers.data(name='lab', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=32, act='relu')
        h = fluid.layers.dropout(h, dropout_prob=0.3)
        logits = fluid.layers.fc(h, size=5)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=lab))
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    return main_p, startup_p, loss


def feed_for(step0, k):
    import numpy as np
    xs, labs = [], []
    for s in range(step0, step0 + k):
        r = np.random.RandomState(1000 + s)
        xs.append(r.randn(BATCH, 16).astype(np.float32))
        labs.append(r.randint(0, 5, (BATCH, 1)))
    return {'x': np.stack(xs), 'lab': np.stack(labs)}


def params_sha(program, scope):
    import numpy as np
    h = hashlib.sha256()
    for v in sorted(v.name for v in program.list_vars() if v.persistable):
        val = scope.get(v)
        if val is not None:
            h.update(v.encode())
            h.update(np.ascontiguousarray(np.asarray(val)).tobytes())
    return h.hexdigest()


def main():
    ckpt_dir, out_path = sys.argv[1], sys.argv[2]
    total, k, every = int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5])
    kill_at = int(sys.argv[6]) if len(sys.argv) > 6 else 0
    min_commits = int(sys.argv[7]) if len(sys.argv) > 7 else 1

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core.checkpoint import CheckpointManager
    from paddle_tpu.parallel import MultiStepTrainer
    from paddle_tpu.testing import faults

    main_p, startup_p, loss = build()
    mgr = None
    if ckpt_dir != '-':
        mgr = CheckpointManager(ckpt_dir, every_steps=every, keep_last_n=3,
                                retry_backoff_s=0.05)
    trainer = MultiStepTrainer(main_p, steps_per_dispatch=k,
                               fetch_list=[loss], fetch_policy='stack',
                               place=fluid.CPUPlace(), checkpoint=mgr,
                               # PTPU_PREEMPTIBLE=1: SIGTERM drains one
                               # final checkpoint at the next step
                               # boundary and exits 0 (test_pod_ft)
                               preemptible=os.environ.get(
                                   'PTPU_PREEMPTIBLE') == '1')
    import time
    t0 = time.perf_counter()
    trainer.startup(startup_p)
    startup_s = time.perf_counter() - t0
    out = open(out_path, 'a')

    def emit(line):
        out.write(line + '\n')
        out.flush()
        os.fsync(out.fileno())

    emit('RESUME %d %.3f' % (trainer.resume_step, startup_s))
    # a resumed incarnation provably has a committed checkpoint on disk;
    # only a cold start must wait for its first commit before dying
    if trainer.resume_step > 0:
        min_commits = 0
    step = trainer.resume_step
    while step < total:
        vals, = trainer.step_group(feed=feed_for(step, k))
        for i, v in enumerate(np.asarray(vals).reshape(-1)):
            emit('%d %.17g' % (step + i, float(v)))
        step += k
        if kill_at and step >= kill_at:
            if mgr is not None:
                # ensure the restart has min_commits checkpoints to find
                # (only while a write is actually in flight); any write
                # beyond that still races the SIGKILL
                deadline = time.time() + 30
                st = mgr.stats
                while st['commits'] < min_commits \
                        and st['snapshots'] - st['commits'] - st['failed'] \
                        > 0 and time.time() < deadline:
                    time.sleep(0.005)
            faults.kill_self()
        faults.maybe_kill_at_step(step)
    if mgr is not None:
        mgr.save(main_p, fluid.global_scope(), step, blocking=True,
                 executor=trainer.executor)
        mgr.close()
    emit('DONE %s' % params_sha(main_p, fluid.global_scope()))


if __name__ == '__main__':
    main()
