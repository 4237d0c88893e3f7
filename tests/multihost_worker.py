"""Worker process for the multi-host test (spawned by test_multihost.py).

Each process joins the distributed runtime (PADDLE_TRAINERS /
PADDLE_TRAINER_ID / PADDLE_COORDINATOR), builds the SAME program, feeds its
LOCAL batch shard, and prints per-step losses — the in-process port of the
reference's test_dist_base subprocess methodology.
"""
import os
import sys

os.environ.setdefault('XLA_FLAGS', '--xla_force_host_platform_device_count=4')
os.environ['JAX_PLATFORMS'] = 'cpu'
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.parallel import multihost

# join the pod BEFORE any backend use; 'cpu' pins the simulated pod platform
multihost.init_distributed(platform='cpu')

import numpy as np
import paddle_tpu as fluid
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.compiler import CompiledProgram

from models.bert import build_bert_pretrain, shard_for_mesh

TRAINER_ID = int(os.environ['PADDLE_TRAINER_ID'])
TRAINERS = int(os.environ['PADDLE_TRAINERS'])
LOCAL_BS = 8
S = 16


def main():
    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = 7
    with fluid.program_guard(main_p, startup_p):
        feeds, loss = build_bert_pretrain(
            vocab=500, max_len=S, d_model=32, d_ff=64, n_head=2, n_layer=2,
            dropout=0.0, lr=1e-3)
    shard_for_mesh(main_p)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)

    # dp spans both hosts (4 local devices x 2 hosts = dp 4 x mp 2)
    mesh = make_mesh(axes={'dp': 4, 'mp': 2})
    prog = CompiledProgram(main_p).with_data_parallel(loss_name=loss.name,
                                                      mesh=mesh)
    rng = np.random.RandomState(100 + TRAINER_ID)  # per-host data shard
    losses = []
    for _ in range(3):
        feed = {'tok_ids': rng.randint(1, 500, (LOCAL_BS, S)),
                'seg_ids': rng.randint(0, 2, (LOCAL_BS, S)),
                'mlm_labels': rng.randint(1, 500, (LOCAL_BS, S)),
                'mlm_weights': (rng.rand(LOCAL_BS, S) < 0.15)
                .astype(np.float32)}
        l, = exe.run(prog, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(l).reshape(-1)[0]))
    # one preformatted write: Gloo's C++ logging shares this fd and can
    # interleave between separate write() calls
    print('MHLOSSES %d %s'
          % (TRAINER_ID, ' '.join('%.6f' % v for v in losses)), flush=True)

    # dist_save_load equivalence (ref: tests/unittests/dist_save_load.py):
    # process 0 alone writes; the load broadcasts from process 0, so wipe
    # the scope first and prove the broadcast restores identical state
    ckpt = os.environ.get('PTPU_MH_CKPT')
    if ckpt:
        from paddle_tpu.core.scope import global_scope
        written = fluid.io.save_persistables(exe, ckpt, main_p)
        print('MHSAVED %d %d' % (TRAINER_ID, len(written)), flush=True)
        scope = global_scope()
        names = [p.name for p in main_p.global_block().all_parameters()]
        before = {n: np.asarray(scope.get(n)) for n in names}
        for n in names:  # corrupt local state; load must repair it
            scope.set(n, np.zeros_like(before[n]))
        fluid.io.load_persistables(exe, ckpt, main_p)
        for n in names:
            np.testing.assert_array_equal(np.asarray(scope.get(n)),
                                          before[n])
        print('MHLOADOK %d' % TRAINER_ID, flush=True)


if __name__ == '__main__':
    main()
