"""Int8 quantized inference (ISSUE 11): the quantize_program pass
(calibration sweep, per-channel weights, def-use-safe activation quant,
machine-checkable float-op reasons), the quantized artifact tier
(export/load/serve + tier metrics), and the int8 paged KV cache
(fixed-HBM slot doubling, fp-KV transcript tolerance)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import passes
from paddle_tpu.passes import quantize as quant


def _build_small_net():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name='img', shape=[3, 16, 16],
                                dtype='float32')
        c = fluid.layers.conv2d(img, 8, 3, padding=1, act='relu')
        p = fluid.layers.pool2d(c, 2, 'max', pool_stride=2)
        fc = fluid.layers.fc(p, 32, act='relu')
        logits = fluid.layers.fc(fc, 10, act='softmax')
    return main, startup, logits


def _calibrated(n_batches=3, batch=4):
    main, startup, logits = _build_small_net()
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    batches = [{'img': rng.randn(batch, 3, 16, 16).astype(np.float32)}
               for _ in range(n_batches)]
    with fluid.scope_guard(scope):
        exe.run(startup)
        calib = passes.calibrate_program(main, batches, exe, scope=scope)
    return main, logits, scope, exe, calib, batches


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------
def test_calibration_targets_and_sweep():
    main, logits, scope, exe, calib, batches = _calibrated()
    targets = passes.calibration_targets(main)
    assert 'img' in targets            # conv activation input
    assert len(targets) == 3           # conv + two fc (mul) inputs
    for t in targets:
        ent = calib.stats[t]
        assert ent['batches'] == 3
        assert ent['abs_max'] >= ent['percentile'] > 0.0
        assert calib.scale(t, 'abs_max') >= calib.scale(t, 'percentile')
    # round-trips through dicts (the signature serialization path)
    back = quant.CalibrationResult.from_dict(calib.as_dict())
    assert back.scale('img') == calib.scale('img')


def test_quantize_weight_per_channel():
    w = np.random.RandomState(0).randn(4, 3, 3, 3).astype(np.float32)
    w[2] = 0.0                                    # dead output channel
    q, s = quant.quantize_weight(w)               # conv OIHW: axis 0
    assert q.dtype == np.int8 and q.shape == w.shape
    assert s.shape == (4,) and s[2] == 1.0        # zero channel -> 1.0
    deq = q.reshape(4, -1).astype(np.float32) * s[:, None]
    assert np.abs(deq.reshape(w.shape) - w).max() <= s.max() * 0.5 + 1e-7
    # mul weights: per output column of the [K, N] form
    w2 = np.random.RandomState(1).randn(6, 5).astype(np.float32)
    q2, s2 = quant.quantize_weight(w2, flatten_cols=1)
    assert s2.shape == (5,)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------
def test_quantize_program_parity_and_report():
    main, logits, scope, exe, calib, batches = _calibrated()
    qprog, report = passes.quantize_program(
        main, calib, scope, fetch_names=[logits.name])
    d = report.details
    assert d['quantized_ops'] == 3
    assert d['float_weights_pruned'] == 3
    assert d['weight_bytes_after'] < d['weight_bytes_before']
    types = [op.type for op in qprog.global_block().ops]
    assert 'conv2d_int8' in types and 'mul_int8' in types
    assert 'conv2d' not in types and 'mul' not in types
    # every float op left carries a machine-checkable reason
    for e in d['float_ops']:
        assert e['reason'] in quant.REASON_CODES
    # parity through the executor
    with fluid.scope_guard(scope):
        ref = exe.run(main, feed=batches[0], fetch_list=[logits.name])[0]
        out = exe.run(qprog, feed=batches[0], fetch_list=[logits.name])[0]
    assert (out.argmax(1) == ref.argmax(1)).all()
    assert np.abs(out - ref).max() < 0.05
    # the rewrite is verifier-clean (registry sweep included)
    assert not passes.verify_program(qprog, fetch_names=[logits.name],
                                     level='full')
    # ...and the original program is untouched
    assert 'conv2d' in [op.type for op in main.global_block().ops]


def test_quantize_reason_codes():
    main, logits, scope, exe, calib, batches = _calibrated()
    # no calibration at all: every candidate reports no_calibration
    _, rep = passes.quantize_program(main, None, scope,
                                     fetch_names=[logits.name])
    reasons = rep.details['float_op_reasons']
    assert reasons.get(quant.REASON_NO_CALIBRATION) == 3
    assert rep.details['quantized_ops'] == 0
    # user skip by weight name
    w_names = [op.inputs['Filter'][0]
               for op in main.global_block().ops if op.type == 'conv2d']
    _, rep2 = passes.quantize_program(main, calib, scope,
                                      fetch_names=[logits.name],
                                      skip_vars=w_names)
    assert rep2.details['float_op_reasons'].get(quant.REASON_USER_SKIP) == 1
    assert rep2.details['quantized_ops'] == 2
    # missing weight value in the scope
    empty = fluid.core.Scope()
    _, rep3 = passes.quantize_program(main, calib, empty,
                                      fetch_names=[logits.name])
    assert rep3.details['float_op_reasons'].get(
        quant.REASON_W_VALUE_MISSING) == 3


def test_quantize_rebound_activation_gets_fresh_quant():
    """A var REWRITTEN between two consumers must not reuse the stale
    quantized copy — the def-use chain keys the quant cache."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[6], dtype='float32',
                              append_batch_size=False)
        x.shape = [4, 6]
        w1 = fluid.layers.create_parameter([6, 5], 'float32', name='w1')
        w2 = fluid.layers.create_parameter([6, 5], 'float32', name='w2')
    block = main.global_block()
    block.create_var(name='h1', shape=[4, 5], dtype='float32')
    block.create_var(name='h2', shape=[4, 5], dtype='float32')
    block.append_op('mul', {'X': ['x'], 'Y': ['w1']}, {'Out': ['h1']},
                    {'x_num_col_dims': 1, 'y_num_col_dims': 1})
    # rebind x in place (scale writes the same name)
    block.append_op('scale', {'X': ['x']}, {'Out': ['x']}, {'scale': 2.0})
    block.append_op('mul', {'X': ['x'], 'Y': ['w2']}, {'Out': ['h2']},
                    {'x_num_col_dims': 1, 'y_num_col_dims': 1})
    scope = fluid.core.Scope()
    rng = np.random.RandomState(0)
    scope.set('w1', rng.randn(6, 5).astype(np.float32))
    scope.set('w2', rng.randn(6, 5).astype(np.float32))
    calib = quant.CalibrationResult()
    calib.observe('x', rng.randn(4, 6))
    qprog, rep = passes.quantize_program(main, calib, scope,
                                         fetch_names=['h1', 'h2'])
    assert rep.details['quantized_ops'] == 2
    q_ops = [op for op in qprog.global_block().ops
             if op.type == 'quantize_int8']
    assert len(q_ops) == 2              # one per x BINDING, not per var
    assert len({op.outputs['Out'][0] for op in q_ops}) == 2


def test_quantize_shared_activation_quantized_once():
    """Two consumers of the SAME binding share one quantize op."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[4, 6], dtype='float32',
                              append_batch_size=False)
        w1 = fluid.layers.create_parameter([6, 5], 'float32', name='wa')
        w2 = fluid.layers.create_parameter([6, 5], 'float32', name='wb')
        h1 = fluid.layers.mul(x, w1)
        h2 = fluid.layers.mul(x, w2)
    scope = fluid.core.Scope()
    rng = np.random.RandomState(0)
    scope.set('wa', rng.randn(6, 5).astype(np.float32))
    scope.set('wb', rng.randn(6, 5).astype(np.float32))
    calib = quant.CalibrationResult()
    calib.observe('x', rng.randn(4, 6))
    qprog, rep = passes.quantize_program(
        main, calib, scope, fetch_names=[h1.name, h2.name])
    assert rep.details['quantized_ops'] == 2
    q_ops = [op for op in qprog.global_block().ops
             if op.type == 'quantize_int8']
    assert len(q_ops) == 1


def test_quantize_shared_weight_quantized_once():
    """One weight feeding TWO quantizable consumers is quantized (and
    byte-counted) once; both int8 ops reference the same var pair."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[4, 6], dtype='float32',
                              append_batch_size=False)
        y = fluid.layers.data(name='y', shape=[4, 6], dtype='float32',
                              append_batch_size=False)
        w = fluid.layers.create_parameter([6, 5], 'float32', name='wt')
        h1 = fluid.layers.mul(x, w)
        h2 = fluid.layers.mul(y, w)
    scope = fluid.core.Scope()
    rng = np.random.RandomState(0)
    w_val = rng.randn(6, 5).astype(np.float32)
    scope.set('wt', w_val)
    calib = quant.CalibrationResult()
    calib.observe('x', rng.randn(4, 6))
    calib.observe('y', rng.randn(4, 6))
    qprog, rep = passes.quantize_program(
        main, calib, scope, fetch_names=[h1.name, h2.name])
    assert rep.details['quantized_ops'] == 2
    assert rep.details['weight_bytes_before'] == w_val.nbytes  # once
    muls = [op for op in qprog.global_block().ops
            if op.type == 'mul_int8']
    assert len({op.inputs['Y'][0] for op in muls}) == 1
    assert len({op.inputs['Scale'][0] for op in muls}) == 1


def test_reexport_without_quantize_removes_stale_tier(tiered_artifact,
                                                     tmp_path):
    """A quantize=None re-export into a dir carrying an int8 tier must
    not leave the STALE quantized model servable."""
    from paddle_tpu.inference import (Config, create_predictor,
                                      export_compiled, CompiledPredictor)
    adir, calib = tiered_artifact
    mdir = os.path.join(os.path.dirname(adir), 'model')
    pred = create_predictor(Config(mdir))
    re_dir = str(tmp_path / 're')
    x = calib[0]['img']
    export_compiled(pred, [x], re_dir, batch_sizes=[1, 4],
                    quantize='int8', calibration=calib)
    assert os.path.isdir(os.path.join(re_dir, 'int8'))
    with pytest.warns(RuntimeWarning, match='stale int8 tier'):
        export_compiled(pred, [x], re_dir, batch_sizes=[1, 4])
    assert not os.path.isdir(os.path.join(re_dir, 'int8'))
    with open(os.path.join(re_dir, 'signature.json')) as f:
        assert 'tiers' not in json.load(f)
    with pytest.raises(ValueError, match='has no .* tier'):
        CompiledPredictor(re_dir, tier='int8')


def test_compile_cache_quant_tag():
    from paddle_tpu.core import compile_cache as cc
    main, logits, scope, exe, calib, _ = _calibrated(n_batches=1)
    assert cc.quant_tag('executor_run', main) == 'executor_run'
    qprog, _ = passes.quantize_program(main, calib, scope,
                                       fetch_names=[logits.name])
    assert cc.quant_tag('executor_run', qprog) == 'executor_run-int8'


# ---------------------------------------------------------------------------
# the artifact tier
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def tiered_artifact(tmp_path_factory):
    """One small artifact with both tiers, buckets [1, 4]."""
    from paddle_tpu.inference import (Config, create_predictor,
                                      export_compiled)
    d = tmp_path_factory.mktemp('quant_art')
    main, startup = fluid.Program(), fluid.Program()
    prev_m = fluid.switch_main_program(main)
    prev_s = fluid.switch_startup_program(startup)
    try:
        img = fluid.layers.data(name='img', shape=[3, 16, 16],
                                dtype='float32')
        c = fluid.layers.conv2d(img, 8, 3, padding=1, act='relu')
        fc = fluid.layers.fc(c, 16, act='relu')
        logits = fluid.layers.fc(fc, 10, act='softmax')
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        mdir = str(d / 'model')
        adir = str(d / 'artifact')
        fluid.io.save_inference_model(mdir, ['img'], [logits], exe, main)
        pred = create_predictor(Config(mdir))
        rng = np.random.RandomState(0)
        calib = [{'img': rng.randn(4, 3, 16, 16).astype(np.float32)}
                 for _ in range(2)]
        export_compiled(pred, [calib[0]['img']], adir, batch_sizes=[1, 4],
                        quantize='int8', calibration=calib)
    finally:
        fluid.switch_main_program(prev_m)
        fluid.switch_startup_program(prev_s)
    return adir, calib


def test_tier_layout_and_signature(tiered_artifact):
    adir, _ = tiered_artifact
    assert os.path.isdir(os.path.join(adir, 'int8', 'bucket_00001'))
    assert os.path.isdir(os.path.join(adir, 'int8', 'bucket_00004'))
    with open(os.path.join(adir, 'signature.json')) as f:
        top = json.load(f)
    assert top['tiers'] == ['bf16', 'int8']
    q = top['quantization']
    assert q['quantized_ops'] > 0 and q['act_scales']
    for e in q['float_ops']:
        assert e['reason'] in quant.REASON_CODES
    with open(os.path.join(adir, 'int8', 'signature.json')) as f:
        tier_sig = json.load(f)
    assert tier_sig['tier'] == 'int8'
    assert tier_sig['buckets'] == [1, 4]


def test_tier_loading_and_parity(tiered_artifact):
    from paddle_tpu.inference import CompiledPredictor
    adir, calib = tiered_artifact
    p_b = CompiledPredictor(adir)
    p_q = CompiledPredictor(adir, tier='int8')
    assert (p_b.tier, p_q.tier) == ('bf16', 'int8')
    ob = np.concatenate([p_b.run([c['img']])[0] for c in calib])
    oq = np.concatenate([p_q.run([c['img']])[0] for c in calib])
    # top-1 parity wherever the float tier's own top-2 margin exceeds
    # what int8 moves a probability by here (a random 10-class model has
    # near-ties: under jax 0.9.0's random stream one row is 0.2205 vs
    # 0.2209 and int8 flips it), and the tiers stay close
    err = float(np.abs(ob - oq).max())
    assert err < 0.02
    top2 = np.sort(ob, axis=1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * err
    assert clear.sum() >= len(ob) // 2
    assert (ob.argmax(1) == oq.argmax(1))[clear].all()
    with pytest.raises(ValueError, match='has no .* tier'):
        CompiledPredictor(adir, tier='fp8')
    # env preference degrades silently when the tier is absent (a bucket
    # dir inside the int8 tree has no further int8/ subdir)
    os.environ['PTPU_SERVE_TIER'] = 'int8'
    try:
        p_env = CompiledPredictor(adir)
        assert p_env.tier == 'int8'
        p_bucket = CompiledPredictor(
            os.path.join(adir, 'int8', 'bucket_00004'))
        assert p_bucket.tier == 'int8'
    finally:
        del os.environ['PTPU_SERVE_TIER']


def test_batching_predictor_int8_tier_and_report(tiered_artifact):
    from paddle_tpu.inference import BatchingPredictor
    from paddle_tpu import profiler
    adir, calib = tiered_artifact
    b = BatchingPredictor(adir, tier='int8', batch_timeout_ms=1.0)
    try:
        b.warmup()
        assert b.tier == 'int8'
        out = b.run([calib[0]['img'][:1]])
        assert out[0].shape == (1, 10)
        snap = b.stats.snapshot()
        assert snap['tier'] == 'int8'
        rep = profiler.serving_report()
        src = next(v for k, v in rep.items() if k.startswith('serving:'))
        assert src['tier'] == 'int8'
    finally:
        b.close()


def test_warm_int8_replica_zero_compiles(tiered_artifact, tmp_path):
    adir, calib = tiered_artifact
    in_npz = str(tmp_path / 'in.npz')
    np.savez(in_npz, img=calib[0]['img'])
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'quant_serve_worker.py')
    out = subprocess.run([sys.executable, worker, adir, in_npz, 'int8'],
                         capture_output=True, text=True, timeout=300)
    assert 'QUANT_OK' in out.stdout, out.stdout + out.stderr
    payload = json.loads(next(
        l for l in out.stdout.splitlines()
        if l.startswith('QUANT '))[len('QUANT '):])
    assert payload['compiles'] == 0
    assert payload['tier'] == 'int8'


def test_export_quantize_requires_calibration(tiered_artifact):
    from paddle_tpu.inference import (Config, create_predictor,
                                      export_compiled)
    adir, _ = tiered_artifact
    mdir = os.path.join(os.path.dirname(adir), 'model')
    pred = create_predictor(Config(mdir))
    x = np.zeros((2, 3, 16, 16), np.float32)
    with pytest.raises(ValueError, match='calibration'):
        export_compiled(pred, [x], adir + '_x', quantize='int8')
    with pytest.raises(ValueError, match="quantize must be"):
        export_compiled(pred, [x], adir + '_y', quantize='fp8',
                        calibration=[{'img': x}])


# ---------------------------------------------------------------------------
# the int8 paged KV cache
# ---------------------------------------------------------------------------
def _decode_spec(kv, slots, scope):
    from models.transformer import build_decode_spec
    with fluid.scope_guard(scope):
        spec = build_decode_spec(vocab=41, d_model=16, n_head=2,
                                 n_layer=2, d_ff=32, max_slots=slots,
                                 max_cache_len=24, chunk_sizes=(4,),
                                 block_size=4, eos_id=1,
                                 kv_cache_dtype=kv)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'], scope=scope)
    return spec


def test_int8_kv_cache_fixed_hbm_and_transcripts(tmp_path):
    from paddle_tpu.inference import DecodingPredictor, export_decode
    fp_scope, q_scope = fluid.core.Scope(), fluid.core.Scope()
    fp_spec = _decode_spec('float32', 2, fp_scope)
    q_spec = _decode_spec('int8', 4, q_scope)     # 2x slots
    assert set(q_spec['cache_vars']) >= {'kv_ks_0', 'kv_vs_0'}
    cache_names = set(q_spec['cache_vars'])
    for n in q_scope.local_var_names():
        if n not in cache_names and fp_scope.get(n) is not None:
            q_scope.set(n, fp_scope.get(n))

    def serve(spec, scope, art):
        with fluid.scope_guard(scope):
            export_decode(spec, art, scope=scope)
        with open(os.path.join(art, 'decode_signature.json')) as f:
            sig = json.load(f)
        pred = DecodingPredictor(art)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(2, 41, int(rng.randint(2, 5)))
                   for _ in range(6)]
        outs = [pred.generate(p, max_new_tokens=8) for p in prompts]
        snap = pred.stats.snapshot()
        pred.close()
        return outs, sig, snap

    fp_out, fp_sig, fp_snap = serve(fp_spec, fp_scope,
                                    str(tmp_path / 'fp'))
    q_out, q_sig, q_snap = serve(q_spec, q_scope, str(tmp_path / 'q'))
    # 2x slots at LOWER cache bytes: the fixed-HBM doubling
    assert q_sig['max_slots'] == 2 * fp_sig['max_slots']
    assert q_sig['cache_bytes'] < fp_sig['cache_bytes']
    assert q_sig['kv_cache_dtype'] == 'int8'
    assert fp_sig['kv_cache_dtype'] == 'float32'
    assert (fp_snap['tier'], q_snap['tier']) == ('bf16', 'int8')
    # transcripts track the fp reference within tolerance
    match = np.mean([
        np.mean(np.asarray(a[:min(len(a), len(b))])
                == np.asarray(b[:min(len(a), len(b))]))
        for a, b in zip(fp_out, q_out)])
    assert match >= 0.85, 'int8-KV transcripts diverged: %.3f' % match


def test_export_decode_kv_dtype_mismatch(tmp_path):
    from paddle_tpu.inference import export_decode
    scope = fluid.core.Scope()
    spec = _decode_spec('float32', 2, scope)
    with pytest.raises(ValueError, match='kv_cache_dtype'):
        export_decode(spec, str(tmp_path / 'a'), scope=scope,
                      kv_cache_dtype='int8')


def test_kv_quant_ops_roundtrip():
    """Write-then-attend through the quantized kernels tracks the fp
    kernels within the per-page quantization step, and stale garbage in
    masked rows stays exactly invisible."""
    import jax.numpy as jnp
    from paddle_tpu.core.registry import get

    class Ctx:
        def __init__(self, **a):
            self.attrs = a

        def attr(self, n, d=None):
            return self.attrs.get(n, d)

    rng = np.random.RandomState(0)
    S, BS, MAXB, D = 3, 4, 2, 8            # slot s owns blocks 1+2s, 2+2s
    NB = 1 + S * MAXB
    table = (1 + MAXB * np.arange(S)[:, None]
             + np.arange(MAXB)[None, :]).astype(np.int32)
    kv = rng.randn(S, D).astype(np.float32)
    pos = np.full((S, 1), 2, np.int32)
    cache = np.zeros((NB, BS, D), np.int8)
    cscale = np.ones((NB, BS), np.float32)
    out = get('kv_block_write_quant').lower(Ctx(), {
        'Cache': [jnp.asarray(cache)], 'Scale': [jnp.asarray(cscale)],
        'KV': [jnp.asarray(kv)], 'Pos': [jnp.asarray(pos)],
        'BlockTable': [jnp.asarray(table)]})
    c2, s2 = np.asarray(out['Out'][0]), np.asarray(out['OutScale'][0])
    deq = c2[table[:, 0], 2, :].astype(np.float32) \
        * s2[table[:, 0], 2, None]
    assert np.abs(deq - kv).max() <= np.abs(kv).max() / 127.0 * 0.51
    # attention: garbage in rows > pos (the rest of each slot's first
    # page, all of its second, the trash block) must not perturb the
    # result
    q = rng.randn(S, D).astype(np.float32)
    kc = c2.copy()
    kc[table[:, 0], 3:, :] = 77
    kc[table[:, 1]] = 77
    kc[0] = 77
    args = lambda k: {'Q': [jnp.asarray(q)], 'KCache': [jnp.asarray(k)],
                      'KScale': [jnp.asarray(s2)],
                      'VCache': [jnp.asarray(c2)],
                      'VScale': [jnp.asarray(s2)],
                      'Pos': [jnp.asarray(pos)],
                      'BlockTable': [jnp.asarray(table)]}
    att = get('kv_block_attention_quant')
    o1 = np.asarray(att.lower(Ctx(n_head=2), args(c2))['Out'][0])
    o2 = np.asarray(att.lower(Ctx(n_head=2), args(kc))['Out'][0])
    assert np.array_equal(o1, o2)
