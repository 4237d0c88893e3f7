"""One pod-member incarnation for the pod-scale fault-tolerance tests
(tests/test_pod_ft.py, scripts/pod_ft_smoke.py, tools/chaos.py --pod).

usage: pod_ft_worker.py CKPT_DIR OUT_FILE TOTAL_STEPS EVERY \
           [KILL_AT_STEP [MIN_POD_COMMITS]]

env contract (set by the driver):
    PADDLE_TRAINERS / PADDLE_TRAINER_ID / PADDLE_COORDINATOR   pod shape
    PTPU_POD_RUN_ID     incarnation token (fresh per pod launch)
    PTPU_POD_HB_TIMEOUT watchdog heartbeat timeout (default 6s)

Each process joins the simulated pod (2 virtual cpu devices per host),
builds the SAME composed-mesh program (dp spans hosts x mp shards the fc
weight), feeds its LOCAL batch shard, and trains TOTAL steps with a
PodCheckpointManager policy every EVERY steps. KILL_AT_STEP > 0 SIGKILLs
this host once that many steps are trained (after MIN_POD_COMMITS pod
commits exist, so a restart provably has something to resume from) —
survivors detect the death through the heartbeat watchdog and exit 3 in
bounded time instead of blocking forever in the next collective.

OUT_FILE lines (append, flushed per step):
    RESUME <step> <startup_s>    restore point of this incarnation
    <step_idx> <loss>            replicated loss: identical on all hosts
    STALL <ckpt_stall_pct>       checkpoint stall as % of run time
    DONE <params_sha256>         full-pod-gathered params digest
"""
import hashlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault('XLA_FLAGS', '--xla_force_host_platform_device_count=2')
os.environ['JAX_PLATFORMS'] = 'cpu'

from paddle_tpu.parallel import multihost

# join the pod BEFORE any backend use
N, RANK = multihost.init_distributed(platform='cpu')

import numpy as np
import paddle_tpu as fluid
from paddle_tpu.core.checkpoint import PodCheckpointManager, HostWatchdog
from paddle_tpu.parallel import shard_parameter
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.compiler import CompiledProgram
from paddle_tpu.testing import faults

LOCAL_BS = 4


def build(seed=17):
    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = seed
    with fluid.program_guard(main_p, startup_p):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        lab = fluid.layers.data(name='lab', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=32, act='relu',
                            param_attr=fluid.ParamAttr(name='fc1_w'))
        h = fluid.layers.dropout(h, dropout_prob=0.2)
        logits = fluid.layers.fc(h, size=5,
                                 param_attr=fluid.ParamAttr(name='fc2_w'))
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=lab))
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    # composed sharding: fc1_w column-parallel over mp (within a host),
    # fc2_w row-sharded over dp — the axis that SPANS hosts — so the pod
    # checkpoint has genuinely cross-host mesh-local shards to write
    # (and its optimizer slots inherit the annotations, executor._build)
    shard_parameter(main_p.global_block().var('fc1_w'), (None, 'mp'))
    shard_parameter(main_p.global_block().var('fc2_w'), ('dp', None))
    return main_p, startup_p, loss


def feed_for(step, rank):
    r = np.random.RandomState(1000 + 10 * step + rank)  # per-host shard
    return {'x': r.randn(LOCAL_BS, 16).astype(np.float32),
            'lab': r.randint(0, 5, (LOCAL_BS, 1))}


def params_sha(program, scope):
    from paddle_tpu.io import _full_value
    from paddle_tpu.core.lod import unwrap
    h = hashlib.sha256()
    for name in sorted(v.name for v in program.list_vars() if v.persistable):
        val = scope.get(name)
        if val is not None:
            h.update(name.encode())
            h.update(np.ascontiguousarray(
                np.asarray(unwrap(_full_value(val)))).tobytes())
    return h.hexdigest()


def main():
    ckpt_dir, out_path = sys.argv[1], sys.argv[2]
    total, every = int(sys.argv[3]), int(sys.argv[4])
    kill_at = int(sys.argv[5]) if len(sys.argv) > 5 else 0
    min_commits = int(sys.argv[6]) if len(sys.argv) > 6 else 1

    import time
    run_id = multihost.pod_run_id()
    hb_timeout = float(os.environ.get('PTPU_POD_HB_TIMEOUT', '6'))

    main_p, startup_p, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)
    mesh = make_mesh(axes={'dp': N, 'mp': 2})
    prog = CompiledProgram(main_p).with_data_parallel(loss_name=loss.name,
                                                      mesh=mesh)

    t0 = time.perf_counter()
    mgr = PodCheckpointManager(ckpt_dir, rank=RANK, num_hosts=N,
                               every_steps=every, keep_last_n=3,
                               commit_timeout_s=30,
                               heartbeat_interval_s=0.2, run_id=run_id)
    wd = HostWatchdog(ckpt_dir, rank=RANK, num_hosts=N,
                      timeout_s=hb_timeout, run_id=run_id,
                      action='exit', exit_code=3).start()
    info = mgr.restore(executor=exe, program=prog)
    startup_s = time.perf_counter() - t0
    step = int(info['step']) if info else 0

    out = open(out_path, 'a')

    def emit(line):
        out.write(line + '\n')
        out.flush()
        os.fsync(out.fileno())

    emit('RESUME %d %.3f' % (step, startup_s))
    # a resumed incarnation provably has a pod-committed checkpoint
    if step > 0:
        min_commits = 0
    while step < total:
        l, = exe.run(prog, feed=feed_for(step, RANK), fetch_list=[loss],
                     checkpoint=mgr)
        step += 1
        emit('%d %.17g' % (step - 1, float(np.asarray(l).reshape(-1)[0])))
        if kill_at and step >= kill_at:
            # wait until a POD-committed checkpoint exists ON DISK (the
            # coordinator writes POD_COMMIT — stats only count it on rank
            # 0), so the restart provably has something to resume from;
            # any write beyond that still races the SIGKILL
            import glob
            deadline = time.time() + 30
            while min_commits and time.time() < deadline and not glob.glob(
                    os.path.join(ckpt_dir, 'ckpt-*', 'POD_COMMIT.json')):
                time.sleep(0.01)
            faults.kill_self()
        faults.maybe_kill_at_step(step)
    mgr.save(prog, fluid.global_scope(), step, blocking=True, executor=exe)
    st = exe._dispatch_stats
    emit('STALL %.4f' % (100.0 * st['ckpt_stall_s'] / st['run_s']
                         if st['run_s'] else 0.0))
    emit('DONE %s' % params_sha(main_p, fluid.global_scope()))
    # belt over the close() tombstone: every host clears the finish line
    # together (mgr.barrier salts the name with the run_id)
    mgr.barrier('done', timeout_s=60)
    wd.stop()
    mgr.close()


if __name__ == '__main__':
    main()
