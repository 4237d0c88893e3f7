#!/usr/bin/env python
"""Smoke the int8 quantized serving tiers (ISSUE 11 CI satellite):
calibrate a small conv net, export BOTH artifact tiers
(export_compiled(quantize='int8')), and drive the quantized decode tier
at fixed cache HBM.

    python scripts/quant_smoke.py

Asserts, on the CPU proxy:
  * the quantize PassReport audits cleanly: >0 ops quantized, every op
    left in float carries a machine-checkable reason code;
  * TOP-1 PARITY on the calibration set between the int8 and bf16 tiers
    (>= 99% of rows agree; abs-max observer on a conv/fc net);
  * a WARM FRESH REPLICA of the int8 tier performs 0 XLA compiles and
    reproduces the in-process int8 fetches bit-exactly (per-tier AOT
    sidecars + tier-aware prewarm);
  * decode THROUGHPUT RATIO >= 1.3x: the int8 paged KV cache costs
    ~(1+4/D)/2 the bytes per slot, so a FIXED cache-HBM budget holds 2x
    max_slots — under saturating load the doubled occupancy amortizes
    the fixed per-step cost across twice the streams (tokens/s ratio vs
    the fp-KV artifact at equal cache bytes);
  * int8-KV transcripts match the fp-KV reference (shared weights)
    within tolerance: >= 90% greedy token agreement.
Exits non-zero on any failed bar.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import passes  # noqa: E402
from paddle_tpu.inference import (Config, create_predictor,  # noqa: E402
                                  export_compiled, export_decode,
                                  CompiledPredictor, DecodingPredictor)

# 2 fp slots (int8 gets 4): the smaller the per-step tensor work, the
# more the fixed per-step cost dominates — the regime the slot-doubling
# bar measures (on TPU the same role is played by the per-dispatch
# floor at serving batch sizes). Enough total work that each measured
# arm runs a few hundred ms on the CPU proxy: tens-of-ms windows make
# the capacity ratio hostage to scheduler noise on a loaded CI host.
SLOTS = int(os.environ.get('PTPU_QUANT_SMOKE_SLOTS', '2'))
N_REQ = int(os.environ.get('PTPU_QUANT_SMOKE_REQS', '128'))
MAX_NEW = int(os.environ.get('PTPU_QUANT_SMOKE_MAX_NEW', '24'))
RATIO_BAR = 1.3
PARITY_BAR = 0.99
MATCH_BAR = 0.90


def fail(msg):
    print('FAIL: %s' % msg, file=sys.stderr)
    sys.exit(1)


# ---------------------------------------------------------------------------
# arm 1: bucket tier — calibrate, export both tiers, parity + 0-compile
# ---------------------------------------------------------------------------
def bucket_tier_arm(d):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name='img', shape=[3, 24, 24],
                                dtype='float32')
        c1 = fluid.layers.conv2d(img, 16, 3, padding=1, act='relu')
        p1 = fluid.layers.pool2d(c1, 2, 'max', pool_stride=2)
        c2 = fluid.layers.conv2d(p1, 32, 3, padding=1, act='relu')
        p2 = fluid.layers.pool2d(c2, 2, 'max', pool_stride=2)
        fc = fluid.layers.fc(p2, 64, act='relu')
        logits = fluid.layers.fc(fc, 10, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mdir, adir = os.path.join(d, 'model'), os.path.join(d, 'artifact')
    fluid.io.save_inference_model(mdir, ['img'], [logits], exe, main)
    pred = create_predictor(Config(mdir))
    rng = np.random.RandomState(0)
    calib = [{'img': rng.randn(8, 3, 24, 24).astype(np.float32)}
             for _ in range(4)]
    export_compiled(pred, [calib[0]['img']], adir, batch_sizes=[1, 8],
                    quantize='int8', calibration=calib)

    with open(os.path.join(adir, 'signature.json')) as f:
        sig = json.load(f)
    if sig.get('tiers') != ['bf16', 'int8']:
        fail('top signature lacks the tier inventory: %r'
             % sig.get('tiers'))
    q = sig['quantization']
    if q['quantized_ops'] <= 0:
        fail('quantize pass quantized nothing')
    bad = [e for e in q['float_ops']
           if e.get('reason') not in passes.quantize.REASON_CODES]
    if bad:
        fail('float ops without machine-checkable reasons: %r' % bad)
    print('quantized_ops=%d float_ops=%d reasons=%s'
          % (q['quantized_ops'], len(q['float_ops']),
             q['float_op_reasons']))

    # -- top-1 parity over the calibration set ---------------------------
    p_b = CompiledPredictor(adir)                 # bf16 tier
    p_q = CompiledPredictor(adir, tier='int8')
    agree = total = 0
    q_ref_outs = []
    for c in calib:
        ob = p_b.run([c['img']])[0]
        oq = p_q.run([c['img']])[0]
        q_ref_outs.append(oq)
        agree += int((ob.argmax(1) == oq.argmax(1)).sum())
        total += ob.shape[0]
    parity = agree / total
    print('top-1 parity on calibration set: %.4f (%d/%d rows)'
          % (parity, agree, total))
    if parity < PARITY_BAR:
        fail('top-1 parity %.4f < %.2f' % (parity, PARITY_BAR))

    # -- warm fresh int8 replica: 0 compiles, bit-identical --------------
    in_npz = os.path.join(d, 'in.npz')
    np.savez(in_npz, img=calib[0]['img'])
    worker = os.path.join(REPO, 'tests', 'quant_serve_worker.py')
    out = subprocess.run([sys.executable, worker, adir, in_npz, 'int8'],
                         capture_output=True, text=True, timeout=300)
    if out.returncode or 'QUANT_OK' not in out.stdout:
        fail('int8 warm-replica worker failed:\n%s\n%s'
             % (out.stdout, out.stderr))
    payload = json.loads(next(l for l in out.stdout.splitlines()
                              if l.startswith('QUANT '))[len('QUANT '):])
    if payload['compiles'] != 0:
        fail('warm int8 replica performed %d XLA compiles (want 0)'
             % payload['compiles'])
    import hashlib
    digest = hashlib.sha256()
    digest.update(np.ascontiguousarray(q_ref_outs[0]).tobytes())
    if payload['sha'] != digest.hexdigest():
        fail('warm int8 replica fetches differ from the in-process tier')
    print('warm int8 replica: 0 XLA compiles, bit-identical fetches')


# ---------------------------------------------------------------------------
# arm 2: decode tier — int8 KV at fixed cache HBM, >= 1.3x tokens/s
# ---------------------------------------------------------------------------
def _build_decode(kv, slots):
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        # small d_model keeps the per-step cost dispatch-floor-dominated
        # (the regime the slot-doubling bar is about — on TPU the same
        # role is played by the fixed per-dispatch cost at serving batch)
        spec = build_decode_spec(vocab=251, d_model=32, n_head=4,
                                 n_layer=2, d_ff=64, max_slots=slots,
                                 max_cache_len=48, chunk_sizes=(4, 8),
                                 block_size=16,
                                 eos_id=1, kv_cache_dtype=kv)
        # seeded init: the transcript-agreement bar must measure the
        # quantization step, not a fresh weight draw per run
        spec['startup'].random_seed = 7
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'], scope=scope)
    return spec, scope


def decode_tier_arm(d):
    fp_spec, fp_scope = _build_decode('float32', SLOTS)
    q_spec, q_scope = _build_decode('int8', 2 * SLOTS)
    cache_names = set(q_spec['cache_vars'])
    for n in q_scope.local_var_names():   # shared weights: honest parity
        if n not in cache_names and fp_scope.get(n) is not None:
            q_scope.set(n, fp_scope.get(n))
    rng = np.random.RandomState(5)
    prompts = [rng.randint(2, 251, int(rng.randint(2, 9)))
               for _ in range(N_REQ)]

    def load(spec, scope, art):
        with fluid.scope_guard(scope):
            export_decode(spec, art, scope=scope)
        with open(os.path.join(art, 'decode_signature.json')) as f:
            sig = json.load(f)
        return DecodingPredictor(art).warmup(), sig

    def measure(pred):
        pred.stats.reset()
        t0 = time.perf_counter()   # saturating load: submit all
        streams = [pred.submit(p, max_new_tokens=MAX_NEW)
                   for p in prompts]
        outs = [s.result(600) for s in streams]
        tok_s = sum(len(t) for t in outs) / (time.perf_counter() - t0)
        return outs, tok_s, pred.stats.snapshot()

    fp_pred, fp_sig = load(fp_spec, fp_scope, os.path.join(d, 'fp'))
    q_pred, q_sig = load(q_spec, q_scope, os.path.join(d, 'int8'))
    try:
        # INTERLEAVED best-of-3 capacity per arm: the ratio bar measures
        # slot-doubling against the fixed per-step cost; alternating the
        # arms round by round keeps a shared-CI-host load spike from
        # landing on one arm only, and best-of filters the spike itself
        fp_tok_s = q_tok_s = 0.0
        fp_out = q_out = fp_snap = q_snap = None
        for _ in range(3):
            outs, tok_s, snap = measure(fp_pred)
            if tok_s > fp_tok_s:
                fp_out, fp_tok_s, fp_snap = outs, tok_s, snap
            outs, tok_s, snap = measure(q_pred)
            if tok_s > q_tok_s:
                q_out, q_tok_s, q_snap = outs, tok_s, snap
    finally:
        fp_pred.close()
        q_pred.close()

    if q_sig['cache_bytes'] > fp_sig['cache_bytes']:
        fail('int8 cache (%d B, %d slots) costs MORE than fp (%d B, %d '
             'slots) — the fixed-HBM premise broke'
             % (q_sig['cache_bytes'], q_sig['max_slots'],
                fp_sig['cache_bytes'], fp_sig['max_slots']))
    match = float(np.mean([
        np.mean(np.asarray(a[:min(len(a), len(b))])
                == np.asarray(b[:min(len(a), len(b))]))
        for a, b in zip(fp_out, q_out)]))
    ratio = q_tok_s / fp_tok_s
    print('decode @fixed cache HBM: fp %d slots %.0f B -> int8 %d slots '
          '%.0f B' % (fp_sig['max_slots'], fp_sig['cache_bytes'],
                      q_sig['max_slots'], q_sig['cache_bytes']))
    print('tokens/s: fp %.0f (occ %.2f) vs int8 %.0f (occ %.2f) — '
          'ratio %.2fx; transcript agreement %.3f; int8 tier=%s'
          % (fp_tok_s, fp_snap['occupancy'], q_tok_s,
             q_snap['occupancy'], ratio, match, q_snap['tier']))
    if q_snap['tier'] != 'int8':
        fail('decode stats report tier %r, want int8' % q_snap['tier'])
    if match < MATCH_BAR:
        fail('int8-KV transcripts agree %.3f < %.2f with the fp-KV '
             'reference' % (match, MATCH_BAR))
    if ratio < RATIO_BAR:
        fail('int8 tier serves %.2fx fp tokens/s at fixed cache HBM '
             '(bar %.1fx)' % (ratio, RATIO_BAR))


def main():
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as d:
        bucket_tier_arm(d)
        decode_tier_arm(d)
    print('QUANT SMOKE OK (%.1fs): both tiers exported, parity + '
          '0-compile warm replica + >=%.1fx fixed-HBM decode throughput'
          % (time.perf_counter() - t0, RATIO_BAR))


if __name__ == '__main__':
    main()
