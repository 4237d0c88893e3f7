"""CI smoke for activation rematerialization (ISSUE 18):

Same-seed A/B on BERT-tiny (2 layers, d=32): arm A trains without
recompute, arm B with explicit per-layer checkpoints
(build_bert_pretrain(checkpoints=True)). Asserts

1. BIT parity: with dropout ON, every loss over 3 steps is bitwise
   identical across the arms (recompute replays the same _op_uid rng
   folds — it changes what is STORED, never what is computed), and
2. the saving is MEASURED, not estimated: XLA's buffer assignment for
   the compiled train step (compiled_memory_stats) plans >= 30% fewer
   temp bytes for the remat arm at the same batch — the ISSUE 18
   acceptance bar, gated on the CPU proxy backend.
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
import models.bert  # noqa: E402
from paddle_tpu.executor import compiled_memory_stats  # noqa: E402

STEPS = 3
BATCH = 8
REDUCTION_BAR = 0.30


def _feed(batch=BATCH, S=16, vocab=1000, seed=0):
    rng = np.random.RandomState(seed)
    return {
        'tok_ids': rng.randint(0, vocab, (batch, S)).astype(np.int64),
        'seg_ids': rng.randint(0, 2, (batch, S)).astype(np.int64),
        'mlm_labels': rng.randint(0, vocab, (batch, S)).astype(np.int64),
        'mlm_weights': (rng.rand(batch, S) < 0.15).astype(np.float32),
    }


def _run_arm(checkpoints, feed):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        _, loss = models.bert.build_bert_pretrain(
            vocab=1000, max_len=16, d_model=32, d_ff=64, n_head=2,
            n_layer=2, checkpoints=checkpoints)
    n_seg = 0
    rep = getattr(main, '_recompute_report', None)
    if rep is not None:
        n_seg = len(rep.details['segments'])
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        stats = compiled_memory_stats(main, feed=feed, fetch_list=[loss],
                                      scope=scope, exe=exe)
        losses = [np.asarray(exe.run(main, feed=feed,
                                     fetch_list=[loss])[0])
                  for _ in range(STEPS)]
    return np.stack(losses), stats, n_seg


def main():
    feed = _feed()
    base, base_mem, base_seg = _run_arm(None, feed)
    remat, remat_mem, remat_seg = _run_arm(True, feed)

    assert base_seg == 0, base_seg
    assert remat_seg > 0, \
        "checkpoints=True applied 0 segments (pass regressed)"
    print("remat arm: %d recompute segment(s)" % remat_seg)

    # 1. bit parity, dropout on
    assert np.isfinite(base).all() and np.isfinite(remat).all()
    if not np.array_equal(base, remat):
        raise AssertionError(
            "losses diverged (must be BITWISE identical):\n"
            "  base  %s\n  remat %s" % (base.ravel(), remat.ravel()))
    print("bit parity over %d steps OK: %s" % (STEPS, base.ravel()))

    # 2. measured temp-bytes reduction at the acceptance bar
    if base_mem is None or remat_mem is None:
        print("backend exposes no memory_analysis(); skipping the "
              "reduction gate")
        return
    bt, rt = base_mem['temp_bytes'], remat_mem['temp_bytes']
    cut = 1.0 - rt / float(bt)
    print("compiled temp bytes (batch=%d): base %d -> remat %d "
          "(-%.1f%%); peak %d -> %d" % (BATCH, bt, rt, 100 * cut,
                                        base_mem['peak_bytes'],
                                        remat_mem['peak_bytes']))
    assert cut >= REDUCTION_BAR, (
        "measured temp-bytes reduction %.1f%% below the %.0f%% bar"
        % (100 * cut, 100 * REDUCTION_BAR))
    print("remat smoke OK")


if __name__ == '__main__':
    main()
