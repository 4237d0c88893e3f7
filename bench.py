"""Benchmark: training throughput on one TPU chip.

Methodology mirrors the reference's benchmark/fluid/fluid_benchmark.py
(synthetic data, steady-state samples/sec after warmup; fluid_benchmark.py:139
prints every metric it measures — so does this harness: one JSON line per
metric, and a failed metric emits an {"metric", "error"} line instead of
sinking the process).

Harness contract:
  * Every cell builds on `Executor()` — jax's default backend, which
    JAX_PLATFORMS selects from outside — and every metric line names the
    `platform` and `device_kind` of the device it ran on. A cpu line is a
    smoke of the harness, never a device number, and carries no `mfu`.
  * EVERY benchmark runs inside a per-metric try/except, ONCE: a failure
    becomes an {"metric", "error"} line, the remaining metrics still run,
    and main() exits non-zero if any line is an error.
  * The headline (ResNet-50) RUNS FIRST, and its result line is printed
    immediately (insurance against a later hard crash) and re-printed LAST
    so the driver's last-JSON-line parse still sees the headline.
  * Every metric line is COMPACT standalone JSON under LINE_BYTE_BUDGET
    bytes (baseline derivations and caveat prose live in BENCH_NOTES.md,
    keyed by metric), and an all-metrics summary line prints immediately
    before the headline re-print — a tail-capped artifact still carries
    every metric's number.

Dual timing (ISSUE 3): next to each dispatch-inclusive number, every
train and infer metric reports `device_ms_per_step` — measured through
ONE K-step `run_steps` / K-batch `run_batches` device program via the
two-point slope (T(K) - T(K/2)) / (K - K/2), so the fixed per-dispatch
cost cancels instead of polluting the number. PTPU_BENCH_DEVICE_TIME=0
disables; PTPU_BENCH_DEVICE_K overrides the per-bench K.

Baselines (vs_baseline derivations, see BASELINE.md and BENCH_NOTES.md):
  * resnet: 84.08 img/s — the only committed reference training number
    (2S Xeon 6148 + MKL-DNN, bs=256, benchmark/IntelOptimizedPaddle.md:45).
  * transformer / bert: FLOPs-equalized from the same committed Xeon run.
  * ctr: the SAME DeepFM measured on the benchmark host's CPU
    (tools/measure_ctr_baseline.py, value recorded in BASELINE.md).

Training runs in bf16 mixed precision (contrib.mixed_precision) — the
TPU-native default.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

BASELINE_RESNET_IMG_S = 84.08  # ResNet-50 train, IntelOptimizedPaddle.md:45

# CTR denominator: the repo's own DeepFM on the benchmark host's CPU —
# median of 4 committed runs of tools/measure_ctr_baseline.py (BASELINE.md;
# the reference commits no CTR number and FLOPs proxies are meaningless
# for embedding-bound work)
BASELINE_CTR_CPU_SAMPLES_S = 8740.0

# Peak dense bf16 FLOP/s per chip, keyed on jax device_kind EXACTLY (a v5e
# reports 'TPU v5 lite'); a TPU kind missing here is an error, not a guess.
PEAK_FLOPS = {
    'TPU v2': 45e12,
    'TPU v3': 123e12,
    'TPU v4': 275e12,
    'TPU v5': 459e12,
    'TPU v5p': 459e12,
    'TPU v5 lite': 197e12,
    'TPU v5e': 197e12,
    'TPU v6 lite': 918e12,
    'TPU v6e': 918e12,
}

# Analytic FLOPs per training sample (fwd 2*MACs, training = 3x fwd):
# ResNet-50 @224: 4.089e9 MACs forward (conv+fc, standard count).
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.089e9

# Measured training FLOP/s of the committed reference Xeon ResNet run —
# the denominator for FLOPs-equalized baselines (module docstring).
XEON_TRAIN_FLOPS = BASELINE_RESNET_IMG_S * RESNET50_TRAIN_FLOPS_PER_IMG

# device of the newest _device() executor: run_metric stamps its platform
# and device_kind on the metric line
_ran_on = {'device': None}


def _peak_flops(dev):
    """Peak bf16 FLOP/s of the device the step ran on; None on cpu (a cpu
    line carries no mfu); an unknown accelerator kind raises."""
    if dev.platform == 'cpu':
        return None
    if dev.device_kind not in PEAK_FLOPS:
        raise KeyError('no peak FLOP/s on record for device_kind %r — add '
                       'it to PEAK_FLOPS with its source' % dev.device_kind)
    return PEAK_FLOPS[dev.device_kind]


# every metric line must parse standalone under this byte budget (a
# tail-capped artifact must keep whole lines — prose lives in
# BENCH_NOTES.md, never in the line); sized for the fattest real line
# plus its platform/device_kind stamp
LINE_BYTE_BUDGET = 480


def _line(metric, value, unit, vs_baseline, **extra):
    line = {'metric': metric, 'value': round(value, 2), 'unit': unit,
            'vs_baseline': round(vs_baseline, 2)}
    line.update(extra)
    return line


def _print_line(line):
    print(json.dumps(line, separators=(',', ':')), flush=True)


def _summary_line(lines):
    """One compact all-metrics JSON line: {metric: [value, vs_baseline]}
    (or "error"). Printed immediately before the headline re-print so a
    tail-byte-capped artifact still carries every metric's number."""
    return {'summary': {
        l.get('metric', '?'): ('error' if 'error' in l
                               else [l.get('value'), l.get('vs_baseline')])
        for l in lines}}


def _pass_ops(program, fetch):
    """[op count before, after] the optimization pass pipeline
    (paddle_tpu/passes: verify, constant_fold, dead_op_elimination,
    fuse_activation) rooted at this bench's fetch target — so pass
    effectiveness rides in the perf trajectory next to throughput.
    None when the pipeline declines (never fails the metric)."""
    try:
        from paddle_tpu import passes
        name = fetch if isinstance(fetch, str) else fetch.name
        before = sum(len(b.ops) for b in program.blocks)
        opt, _ = passes.apply_optimization_pipeline(program,
                                                    fetch_names=[name])
        return [before, sum(len(b.ops) for b in opt.blocks)]
    except Exception:
        return None


def _static_fields(program, fetch, batch=None):
    """pass_ops + peak_bytes_est for one train metric: the pipeline op
    counts above plus the dataflow analyzer's static peak-memory
    estimate at this bench's batch (passes/dataflow.py — pure
    shape/dtype math, no runtime cost; omitted if analysis declines)."""
    fields = {'pass_ops': _pass_ops(program, fetch)}
    try:
        from paddle_tpu.passes import dataflow
        name = fetch if isinstance(fetch, str) else fetch.name
        dfa = dataflow.analyze_program(program, fetch_names=[name])
        est = dfa.peak_memory(batch=batch or 1, top=0)
        fields['peak_bytes_est'] = int(est.peak_bytes)
        if dfa.remat_interiors()[0]:
            remat = dfa.peak_memory(batch=batch or 1, top=0,
                                    remat_aware=True)
            fields['remat_segments'] = int(remat.remat_segments)
            fields['peak_bytes_remat'] = int(remat.peak_bytes)
    except Exception:
        pass
    return fields


def _memory_fields(program, feed, fetch, exe, scope=None):
    """Measured HLO memory column (PTPU_BENCH_MEMORY=1): XLA's
    buffer-assignment temp/peak bytes for this bench's compiled step via
    Executor.compiled_memory_stats — the number the recompute pass
    (ISSUE 18) actually moves. Opt-in: the extra lower+compile is cached
    but not free; omitted (and never fatal) otherwise."""
    if os.environ.get('PTPU_BENCH_MEMORY', '0') != '1':
        return {}
    try:
        from paddle_tpu.executor import compiled_memory_stats
        stats = compiled_memory_stats(program, feed=feed,
                                      fetch_list=[fetch], scope=scope,
                                      exe=exe)
        if not stats:
            return {}
        return {'hlo_temp_bytes': int(stats['temp_bytes']),
                'hlo_peak_bytes': int(stats['peak_bytes'])}
    except Exception:
        return {}


def _cc_stats():
    try:
        from paddle_tpu.core import compile_cache as cc
        return cc.stats()
    except Exception:
        return None


def _compile_fields(before, after):
    """compile_s_cold / compile_s_warm for one metric (ISSUE 5): cold =
    seconds spent tracing+XLA-compiling this round (persistent-cache
    misses, or raw XLA compile time when the cache is off); warm = seconds
    spent deserializing warm-started executables. The next BENCH round
    reads the pair as the warm-start trajectory."""
    if not before or not after:
        return {}
    fields = {}
    if after['misses'] > before['misses']:
        fields['compile_s_cold'] = round(
            after['compile_s'] - before['compile_s'], 2)
    elif after['xla_compile_s'] > before['xla_compile_s']:
        fields['compile_s_cold'] = round(
            after['xla_compile_s'] - before['xla_compile_s'], 2)
    hits = (after['exec_hits'] + after['hlo_hits']
            - before['exec_hits'] - before['hlo_hits'])
    if hits:
        fields['compile_s_warm'] = round(
            after['hit_load_s'] - before['hit_load_s'], 3)
    return fields


def run_metric(name, fn):
    """Run one benchmark ONCE, isolated: returns the metric line dict, or
    an error line dict carrying the metric name and the error string
    (never raises, never retries — main() turns an error line into a
    non-zero exit). Success lines additionally carry the platform and
    device_kind of the device the cell ran on (its _device() executor's;
    a host-only cell says cpu), compile_s_cold/compile_s_warm (the
    warm-start trajectory, _compile_fields), and `mfu` only when there is
    a peak to divide by."""
    before = _cc_stats()
    _ran_on['device'] = None
    try:
        line = fn()
    except Exception as e:  # per-metric isolation: nothing may escape
        return {'metric': name, 'error': str(e)[:300]}
    if isinstance(line, dict) and 'error' not in line:
        dev = _ran_on['device']
        line.setdefault('platform', dev.platform if dev else 'cpu')
        line.setdefault('device_kind', dev.device_kind if dev else 'cpu')
        if line.get('mfu') is None:
            line.pop('mfu', None)
        line.update(_compile_fields(before, _cc_stats()))
    return line


def _timed_steps(exe, program, feed, loss, steps, warmup=4):
    """Warmup (compile) + `steps` timed runs; async dispatch pipelines the
    loop with ONE host sync at the end. Returns elapsed seconds."""
    for _ in range(warmup):
        l, = exe.run(program=program, feed=feed, fetch_list=[loss],
                     return_numpy=False)
    np.asarray(l)  # block on compile + warmup
    t0 = time.perf_counter()
    for _ in range(steps):
        l, = exe.run(program=program, feed=feed, fetch_list=[loss],
                     return_numpy=False)
    _ = float(np.asarray(l).reshape(-1)[0])  # sync
    return time.perf_counter() - t0


def _device():
    """An executor on jax's default backend (a chip run's TPU; the cpu
    under JAX_PLATFORMS=cpu, where the lines then say so) and its device."""
    import paddle_tpu as fluid
    exe = fluid.Executor()
    _ran_on['device'] = exe._device
    return exe, exe._device


def _timed_multi_steps(exe, program, feed, loss, dispatches, k, warmup=2):
    """Warmup + `dispatches` timed run_steps dispatches (K steps each,
    'final' fetch thinning), one host sync at the end — the multi-step
    counterpart of _timed_steps. Returns elapsed seconds."""
    for _ in range(warmup):
        out = exe.run_steps(program=program, feed=feed, fetch_list=[loss],
                            steps=k, return_numpy=False)
    np.asarray(out[0])  # block on compile + warmup
    t0 = time.perf_counter()
    for _ in range(dispatches):
        out = exe.run_steps(program=program, feed=feed, fetch_list=[loss],
                            steps=k, return_numpy=False)
    _ = float(np.asarray(out[0]).reshape(-1)[0])  # sync
    return time.perf_counter() - t0


def _stack_k(feed, k):
    """Tile a single-step feed into a K-group for run_steps (the shapes
    are what is benched; contents repeat): dense device arrays stack on
    device; a host LoDTensor — or a (values, offsets) TUPLE, run()'s LoD
    pair form — is ONE per-step value and repeats as a K-list (run_steps
    stacks static-lod groups itself); only a python list is taken as an
    already-built K-group."""
    import jax.numpy as jnp
    out = {}
    for n, v in feed.items():
        if isinstance(v, list):
            out[n] = list(v)
        elif hasattr(v, 'lod') or isinstance(v, tuple):
            out[n] = [v] * k
        else:
            out[n] = jnp.stack([v] * k)
    return out


def _device_time_enabled():
    return os.environ.get('PTPU_BENCH_DEVICE_TIME', '1') != '0'


def _device_k(default):
    return int(os.environ.get('PTPU_BENCH_DEVICE_K', str(default)))


def _device_ms_scan(exe, program, feed, fetch, k, reps=3, scope=None):
    """Measured DEVICE time per scanned unit (train step or inference
    batch): T(k) and T(k/2) are each ONE run_steps dispatch timed with a
    host sync, with the stacked K-group staged OUTSIDE the timed region —
    so both the fixed per-dispatch cost and the K-proportional staging
    cost cancel in the slope
    (T(k) - T(k/2)) / (k - k/2). Caveat: LoD feeds ride as K-lists that
    run_steps stacks INSIDE the timed region (it accepts no pre-stacked
    LoD group), so OCR's device number carries the per-group host lod
    staging — µs-scale offset arrays against ~ms steps, and the dominant
    jitter term (the dispatch floor) still cancels.
    Returns (ms_per_unit, k), raw: a NON-POSITIVE slope means host noise
    swamped the A/B and _attach_device_time marks it invalid rather than
    publishing a fake 0. `fetch` is a name or a list of names."""
    k = max(2, int(k))
    k2 = max(1, k // 2)
    fetches = list(fetch) if isinstance(fetch, (list, tuple)) else [fetch]

    def timed(kk):
        group = _stack_k(feed, kk)  # staged once, reused every rep
        out = exe.run_steps(program=program, feed=group,
                            fetch_list=fetches, steps=kk, scope=scope,
                            return_numpy=False)
        np.asarray(out[0])  # block on compile + warmup
        best = float('inf')
        for _ in range(reps):
            t0 = time.perf_counter()
            out = exe.run_steps(program=program, feed=group,
                                fetch_list=fetches, steps=kk, scope=scope,
                                return_numpy=False)
            for o in out:  # sync EVERY fetch — dropping one would let
                np.asarray(o)  # XLA dead-code-eliminate its compute
            best = min(best, time.perf_counter() - t0)
        return best

    tk, tk2 = timed(k), timed(k2)
    return (tk - tk2) / (k - k2) * 1e3, k


def _device_ms_infer(pred, batch_feed, k, reps=3):
    """Device time per inference batch: the same staged two-point slope,
    driven through the Predictor's scanned bulk machinery
    (Executor.run_steps — exactly what run_batches wraps) against the
    predictor's own scope, fetching ALL outputs as run() does.
    Returns (ms, k)."""
    feed = (dict(zip(pred._feed_names, batch_feed))
            if isinstance(batch_feed, (list, tuple)) else dict(batch_feed))
    fetches = [v.name for v in pred._fetch_vars if v is not None]
    return _device_ms_scan(pred._exe, pred._program, feed, fetches, k,
                           reps=reps, scope=pred._scope)


def _attach_device_time(line, measure):
    """Attach device_ms_per_step/device_k under an isolation guard: a
    device-time failure (e.g. an op XLA cannot scan on this backend) must
    never cost the dispatch-inclusive metric it rides on. A non-positive
    slope is recorded as a miss, not published as a real 0-ms number."""
    if not _device_time_enabled():
        return line
    try:
        ms, k = measure()
        if ms <= 0:
            line['device_ms_per_step'] = None
            line['device_error'] = 'non-positive slope: host noise'
        else:
            line['device_ms_per_step'] = round(ms, 3)
            line['device_k'] = k
    except Exception as e:  # keep the metric; record the miss compactly
        line['device_ms_per_step'] = None
        # 60-char cap keeps even the fattest line under LINE_BYTE_BUDGET
        # (the full error belongs in logs, not the artifact line)
        line['device_error'] = str(e)[:60]
    return line


def _bench_image_train(metric, build, batch, steps, flops_per_img,
                       baseline_img_s, baseline_ref, use_bf16=True,
                       warmup=4, class_dim=1000, device_k=4):
    """Shared image-classifier train bench: synthetic data staged on device
    ONCE (the reference benchmark's synthetic mode, benchmark/fluid/args.py
    --use_reader_op=false path) so steady-state throughput measures the
    train step, not the host-to-device transfer."""
    import paddle_tpu as fluid
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images, label, loss, acc = build()
    if use_bf16:
        fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)
    import jax
    import jax.numpy as jnp
    xs = jax.device_put(
        jnp.asarray(np.random.randn(batch, 3, 224, 224), jnp.float32), dev)
    lab = jax.device_put(
        jnp.asarray(np.random.randint(0, class_dim, (batch, 1)), jnp.int32),
        dev)
    feed = {'data': xs, 'label': lab}

    dt = _timed_steps(exe, main_p, feed, loss, steps, warmup=warmup)
    img_s = batch * steps / dt
    peak = _peak_flops(dev)
    mfu = (img_s * flops_per_img / peak) if peak else None
    line = _line(metric, img_s, 'img/s', img_s / baseline_img_s,
                 mfu=round(mfu, 4) if mfu is not None else None,
                 dtype='bf16' if use_bf16 else 'fp32', batch=batch,
                 baseline_ref=baseline_ref,
                 **_static_fields(main_p, loss, batch))
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(device_k)))


def bench_resnet():
    from models.resnet import build_train_net
    batch = int(os.environ.get('PTPU_BENCH_BATCH', '256'))
    steps = int(os.environ.get('PTPU_BENCH_STEPS', '30'))
    use_bf16 = os.environ.get('PTPU_BENCH_DTYPE', 'bf16') == 'bf16'
    # MLPerf-style space-to-depth stem (models/resnet.py _s2d_stem);
    # PTPU_BENCH_S2D=0 benches the classic 7x7 stem
    s2d = os.environ.get('PTPU_BENCH_S2D', '1') != '0'
    return _bench_image_train(
        'resnet50_train_img_s_per_chip',
        lambda: build_train_net(dshape=(3, 224, 224), class_dim=1000,
                                depth=50, imagenet=True, lr=0.1,
                                s2d_stem=s2d),
        batch, steps, RESNET50_TRAIN_FLOPS_PER_IMG, BASELINE_RESNET_IMG_S,
        'xeon6148', use_bf16=use_bf16)


def bench_transformer():
    import paddle_tpu as fluid
    from models.transformer import build_transformer_train

    batch = int(os.environ.get('PTPU_BENCH_TRANS_BATCH', '64'))
    seq_len = int(os.environ.get('PTPU_BENCH_TRANS_SEQ', '256'))
    steps = int(os.environ.get('PTPU_BENCH_TRANS_STEPS', '20'))
    # ablation knobs (PERF_NOTES.md dropout-tax section); remat:
    # ''=off, 'layers'=per-layer checkpoints, 'auto'=pass-chosen cuts
    dropout = float(os.environ.get('PTPU_BENCH_TRANS_DROPOUT', '0.1'))
    ad_env = os.environ.get('PTPU_BENCH_TRANS_ATTN_DROPOUT', '')
    attn_dropout = float(ad_env) if ad_env else None
    remat = os.environ.get('PTPU_BENCH_TRANS_REMAT', '')
    cps = {'': None, 'layers': True, 'auto': 'auto'}.get(remat, None)

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        feeds, loss, flops_per_tok = build_transformer_train(
            src_vocab=32000, trg_vocab=32000, max_len=seq_len,
            d_model=512, d_ff=2048, n_head=8, n_layer=6,
            dropout=dropout, attn_dropout=attn_dropout,
            checkpoints=cps)
    fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)

    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    feed = {}
    for name, shape, dtype in feeds:
        full = (batch,) + tuple(shape)
        if dtype == 'int64':
            arr = rng.randint(1, 31999, full).astype(np.int32)
        else:
            arr = rng.randn(*full).astype(np.float32)
        feed[name] = jax.device_put(jnp.asarray(arr), dev)

    dt = _timed_steps(exe, main_p, feed, loss, steps, warmup=3)
    tok_s = batch * seq_len * steps / dt
    peak = _peak_flops(dev)
    mfu = (tok_s * flops_per_tok / peak) if peak else None
    # FLOPs-equalized Xeon baseline (module docstring): same FLOP/s as the
    # committed ResNet Xeon run, spent on this model's per-token cost.
    base_tok_s = XEON_TRAIN_FLOPS / flops_per_tok
    line = _line('transformer_base_tokens_s_per_chip', tok_s, 'tokens/s',
                 tok_s / base_tok_s,
                 mfu=round(mfu, 4) if mfu is not None else None, dtype='bf16',
                 batch=batch, seq_len=seq_len, baseline_ref='flops_eq_xeon',
                 **_static_fields(main_p, loss, batch))
    line.update(_memory_fields(main_p, feed, loss, exe))
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(8)))


def bench_bert():
    import paddle_tpu as fluid
    from models.bert import build_bert_pretrain

    batch = int(os.environ.get('PTPU_BENCH_BERT_BATCH', '64'))
    seq_len = int(os.environ.get('PTPU_BENCH_BERT_SEQ', '128'))
    steps = int(os.environ.get('PTPU_BENCH_BERT_STEPS', '20'))
    k_merge = int(os.environ.get('PTPU_BENCH_BERT_GA', '2'))
    # remat ablation knob: ''=off, 'layers'=per-layer, 'auto'=pass-chosen
    remat = os.environ.get('PTPU_BENCH_BERT_REMAT', '')
    cps = {'': None, 'layers': True, 'auto': 'auto'}.get(remat, None)

    vocab, d_model, d_ff, n_head, n_layer = 30522, 768, 3072, 12, 12
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        feeds, loss = build_bert_pretrain(
            vocab=vocab, max_len=seq_len, d_model=d_model, d_ff=d_ff,
            n_head=n_head, n_layer=n_layer, checkpoints=cps)
    fluid.contrib.mixed_precision.enable_bf16(main_p)
    if k_merge > 1:
        fluid.contrib.gradient_merge.enable(k_merge, main_p)

    exe, dev = _device()
    exe.run(startup_p)

    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    feed = {}
    for name, shape, dtype in feeds:
        full = (batch,) + tuple(shape)
        if dtype == 'int64':
            hi = vocab if name == 'tok_ids' else (
                2 if name == 'seg_ids' else vocab)
            feed[name] = jax.device_put(jnp.asarray(
                rng.randint(0, hi, full).astype(np.int32)), dev)
        else:  # mlm_weights: ~15% masked positions
            feed[name] = jax.device_put(jnp.asarray(
                (rng.rand(*full) < 0.15).astype(np.float32)), dev)

    dt = _timed_steps(exe, main_p, feed, loss, steps, warmup=3)
    tok_s = batch * seq_len * steps / dt
    # analytic train FLOPs per token (fwd 2*MACs, train = 3x): per encoder
    # layer 4d^2 proj + 2*d*dff ffn + 2*S*d attention scores; MLM head
    # d^2 transform + d*V projection over every position (models/bert.py)
    macs_per_tok = (n_layer * (4 * d_model ** 2 + 2 * d_model * d_ff
                               + 2 * seq_len * d_model)
                    + d_model ** 2 + d_model * vocab)
    flops_per_tok = 3 * 2 * macs_per_tok
    peak = _peak_flops(dev)
    mfu = (tok_s * flops_per_tok / peak) if peak else None
    base_tok_s = XEON_TRAIN_FLOPS / flops_per_tok
    line = _line('bert_mlm_tokens_s_per_chip', tok_s, 'tokens/s',
                 tok_s / base_tok_s,
                 mfu=round(mfu, 4) if mfu is not None else None, dtype='bf16',
                 batch=batch, seq_len=seq_len, grad_merge_k=k_merge,
                 baseline_ref='flops_eq_xeon',
                 **_static_fields(main_p, loss, batch))
    line.update(_memory_fields(main_p, feed, loss, exe))
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(8)))


def bench_vgg():
    """VGG-19 train vs the committed reference number: 30.44 img/s on 2S
    Xeon 6148 + MKL-DNN, bs=256 (benchmark/IntelOptimizedPaddle.md:35).
    VGG-19 fwd MACs @224 ~= 19.6e9 (standard count), train = 3x fwd."""
    from models.vgg import build_train_net
    return _bench_image_train(
        'vgg19_train_img_s_per_chip',
        lambda: build_train_net(depth=19),
        int(os.environ.get('PTPU_BENCH_VGG_BATCH', '128')),
        int(os.environ.get('PTPU_BENCH_VGG_STEPS', '20')),
        3 * 2 * 19.6e9, 30.44, 'xeon6148', warmup=3)


def bench_googlenet():
    """GoogLeNet (Inception v1) train vs the committed reference number:
    269.50 img/s on 2S Xeon 6148 + MKL-DNN, bs=256
    (benchmark/IntelOptimizedPaddle.md:55)."""
    from models.googlenet import build_train_net, GOOGLENET_FWD_MACS
    return _bench_image_train(
        'googlenet_train_img_s_per_chip',
        lambda: build_train_net(),
        int(os.environ.get('PTPU_BENCH_GOOGLENET_BATCH', '256')),
        int(os.environ.get('PTPU_BENCH_GOOGLENET_STEPS', '20')),
        3 * 2 * GOOGLENET_FWD_MACS, 269.50, 'xeon6148', warmup=3)


def bench_googlenet_infer():
    """GoogLeNet INFERENCE vs the committed reference number: 600.94 img/s
    on 2S Xeon 6148 + MKL-DNN, bs=16 (IntelOptimizedPaddle.md:97)."""
    from models.googlenet import googlenet
    return _bench_image_infer(
        'googlenet_infer_img_s_per_chip',
        lambda images: googlenet(images, class_dim=1000, is_train=False),
        'GINFER', 600.94, 'xeon6148')


def bench_alexnet():
    """AlexNet train vs the committed reference numbers: 626.53 img/s on
    2S Xeon 6148 (IntelOptimizedPaddle.md:65); the K40m number is
    602 ms/batch at bs=256 ~= 425 img/s (benchmark/README.md:37).
    AlexNet fwd ~0.77 GMACs incl. the 58.6M-param fc head, train = 3x."""
    from models.alexnet import build_train_net
    return _bench_image_train(
        'alexnet_train_img_s_per_chip', build_train_net,
        int(os.environ.get('PTPU_BENCH_ALEX_BATCH', '256')),
        int(os.environ.get('PTPU_BENCH_ALEX_STEPS', '30')),
        3 * 2 * 0.77e9, 626.53, 'xeon6148', warmup=3)


def _bench_image_infer(metric, build_logits, env_prefix, baseline_img_s,
                       baseline_ref):
    """Shared image-classifier INFERENCE bench: Predictor path (load ->
    prune -> jit), input staged on device ONCE, steps dispatched async
    with a single final sync, so a per-call host sync is not what gets
    benched. The dispatch-inclusive number rides next to a measured
    device number: run_batches(K) scans K batches in ONE dispatch and the
    two-point slope cancels the per-dispatch cost."""
    import tempfile
    import paddle_tpu as fluid
    from paddle_tpu.inference import Config, create_predictor

    batch = int(os.environ.get('PTPU_BENCH_%s_BATCH' % env_prefix, '16'))
    steps = int(os.environ.get('PTPU_BENCH_%s_STEPS' % env_prefix, '50'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images = fluid.layers.data(name='data', shape=[3, 224, 224],
                                   dtype='float32')
        logits = build_logits(images)
    exe, dev = _device()
    exe.run(startup_p)
    with tempfile.TemporaryDirectory() as d:
        fluid.io.save_inference_model(d, ['data'], [logits], exe, main_p)
        pred = create_predictor(Config(d))
    import jax
    import jax.numpy as jnp
    x = jax.device_put(
        jnp.asarray(np.random.randn(batch, 3, 224, 224), jnp.float32), dev)
    pred.warmup([x])
    t0 = time.perf_counter()
    for _ in range(steps):
        out, = pred.run([x], return_numpy=False)
    _ = np.asarray(out)  # one sync
    dt = time.perf_counter() - t0
    img_s = batch * steps / dt
    line = _line(metric, img_s, 'img/s', img_s / baseline_img_s,
                 batch=batch, baseline_ref=baseline_ref)

    def measure():
        ms, k = _device_ms_infer(pred, [x], _device_k(8))
        if ms > 0:
            line['device_img_s'] = round(batch / ms * 1e3, 2)
        return ms, k
    return _attach_device_time(line, measure)


def _bench_image_serving(metric, build_logits, env_prefix, baseline_img_s,
                         baseline_ref, dshape=(3, 224, 224)):
    """Dynamic-batched SERVING bench: a Poisson arrival stream of small
    requests drives inference.BatchingPredictor over a multi-bucket
    artifact. This is the scenario the per-call benches cannot measure:
    sequential small-batch dispatch pays the full per-dispatch cost on
    every request, while
    the batcher coalesces concurrent requests into one dispatch and
    double-buffers the next batch's host work under the current batch's
    execution. Reports served img/s plus p50/p95/p99 request latency.

    Env knobs (PTPU_BENCH_<prefix>_*): BUCKETS, REQS, REQ_BATCH,
    TIMEOUT_MS, RATE (req/s, or 'auto' = 80% of measured capacity)."""
    import tempfile
    import paddle_tpu as fluid
    from paddle_tpu.inference import (Config, create_predictor,
                                      export_compiled, BatchingPredictor)

    buckets = sorted({int(t) for t in os.environ.get(
        'PTPU_BENCH_%s_BUCKETS' % env_prefix, '1,8,32,128').split(',')})
    n_req = int(os.environ.get('PTPU_BENCH_%s_REQS' % env_prefix, '256'))
    req_bs = int(os.environ.get('PTPU_BENCH_%s_REQ_BATCH' % env_prefix, '1'))
    timeout_ms = float(os.environ.get(
        'PTPU_BENCH_%s_TIMEOUT_MS' % env_prefix, '5'))
    rate_env = os.environ.get('PTPU_BENCH_%s_RATE' % env_prefix, 'auto')

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images = fluid.layers.data(name='data', shape=list(dshape),
                                   dtype='float32')
        logits = build_logits(images)
    exe, dev = _device()
    exe.run(startup_p)
    with tempfile.TemporaryDirectory() as d:
        mdir = os.path.join(d, 'model')
        adir = os.path.join(d, 'artifact')
        fluid.io.save_inference_model(mdir, ['data'], [logits], exe, main_p)
        pred = create_predictor(Config(mdir))
        big = max(buckets)
        sample = np.random.RandomState(0).randn(
            big, *dshape).astype(np.float32)
        export_compiled(pred, [sample], adir, batch_sizes=buckets)

        batcher = BatchingPredictor(adir, batch_timeout_ms=timeout_ms)
        try:
            batcher.warmup()
            # capacity calibration: steady-state full-bucket dispatch rate
            t0 = time.perf_counter()
            cal_steps = 5
            for _ in range(cal_steps):
                batcher.run([sample])
            cap_img_s = big * cal_steps / (time.perf_counter() - t0)
            rate = (0.8 * cap_img_s / req_bs if rate_env == 'auto'
                    else float(rate_env))
            batcher.stats.reset()  # report the Poisson run, not calibration

            x1 = sample[:req_bs]
            arrivals = np.cumsum(
                np.random.RandomState(1).exponential(1.0 / rate, n_req))
            futs = []
            t0 = time.perf_counter()
            for i in range(n_req):
                delay = t0 + arrivals[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                futs.append(batcher.submit([x1]))
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            snap = batcher.stats.snapshot()
        finally:
            batcher.close()
    img_s = n_req * req_bs / wall
    return _line(metric, img_s, 'img/s', img_s / baseline_img_s,
                 batch=req_bs, buckets=buckets,
                 offered_req_s=round(rate, 1),
                 capacity_img_s=round(cap_img_s, 1),
                 occupancy=snap['occupancy'], p50_ms=snap['p50_ms'],
                 p95_ms=snap['p95_ms'], p99_ms=snap['p99_ms'],
                 baseline_ref=baseline_ref)


def bench_resnet_serving():
    """ResNet-50 dynamic-batched serving vs the same committed Xeon bs16
    number as resnet_infer (IntelOptimizedPaddle.md:87) — the scenario
    ISSUE 1 targets: coalescing Poisson-arriving bs-1 requests amortizes
    the per-dispatch cost sequential small-batch serving pays in full."""
    from models.resnet import resnet_imagenet
    return _bench_image_serving(
        'resnet50_serving_img_s_per_chip',
        lambda images: resnet_imagenet(images, class_dim=1000, depth=50,
                                       is_train=False),
        'SERVE', 217.69, 'xeon6148')


def bench_decode_serving():
    """Continuous in-flight DECODE serving (ISSUE 8): a Poisson arrival
    stream of autoregressive generate requests drives
    inference.DecodingPredictor over the two-program paged-KV artifact —
    the scenario the north star names (token-streaming generative decode
    for many concurrent users). The A/B inside the line is the point:
    sequential (one-request-at-a-time) decode pays the full fixed-shape
    [max_slots] step cost per token of ONE request, while iteration-level
    scheduling packs every occupied slot into the same dispatch. Reports
    continuous tokens/s, the sequential baseline, slot occupancy, and
    p50/p99 time-to-first-token + inter-token latency under the offered
    Poisson load.

    Env knobs (PTPU_BENCH_DECODE_*): REQS, MAX_NEW, SLOTS, RATE_X
    (offered load as a multiple of sequential capacity), DMODEL, LAYERS,
    BLOCK (the block-paged pool's block_size, default 16 — chunked
    prefill + prefix sharing; the metric line carries the block-cache
    gauges).
    """
    import tempfile
    import paddle_tpu as fluid
    from models.transformer import build_decode_spec
    from paddle_tpu.inference import DecodingPredictor, export_decode

    n_req = int(os.environ.get('PTPU_BENCH_DECODE_REQS', '64'))
    max_new = int(os.environ.get('PTPU_BENCH_DECODE_MAX_NEW', '24'))
    slots = int(os.environ.get('PTPU_BENCH_DECODE_SLOTS', '8'))
    rate_x = float(os.environ.get('PTPU_BENCH_DECODE_RATE_X', '8'))
    d_model = int(os.environ.get('PTPU_BENCH_DECODE_DMODEL', '64'))
    n_layer = int(os.environ.get('PTPU_BENCH_DECODE_LAYERS', '2'))
    block = int(os.environ.get('PTPU_BENCH_DECODE_BLOCK', '16'))
    vocab, buckets, cache = 512, (8, 16), 64

    scope = fluid.core.Scope()
    with tempfile.TemporaryDirectory() as d, fluid.scope_guard(scope):
        art = os.path.join(d, 'decode_art')
        spec = build_decode_spec(vocab=vocab, d_model=d_model, n_head=4,
                                 n_layer=n_layer, d_ff=4 * d_model,
                                 max_slots=slots, max_cache_len=cache,
                                 chunk_sizes=buckets, eos_id=1,
                                 block_size=block)
        exe, _ = _device()
        exe.run(spec['startup'], scope=scope)
        export_decode(spec, art, scope=scope)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(2, vocab, int(rng.randint(4, max(buckets))))
                   for _ in range(n_req)]
        pred = DecodingPredictor(art)
        try:
            pred.warmup()
            t0 = time.perf_counter()
            seq = [pred.generate(p, max_new_tokens=max_new)
                   for p in prompts]
            seq_s = time.perf_counter() - t0
            seq_tok_s = sum(len(t) for t in seq) / seq_s
            pred.stats.reset()
            if block:
                # the sequential arm registered every prompt's prefix;
                # without this the Poisson arm re-serves the SAME
                # prompts against a warm prefix cache and vs_baseline
                # conflates batching with reuse the baseline never got
                pred.block_manager.evict_all_prefixes()
                pred.block_manager.reset_counters()
            # offered rate derives from the MEASURED request rate, not
            # tokens/max_new: early-eos requests are cheaper than
            # max_new tokens, and a token-derived rate under-offers and
            # idles the slots (decode_serve_smoke.py calibration note)
            rate = rate_x * n_req / seq_s
            arrivals = np.cumsum(np.random.RandomState(1).exponential(
                1.0 / rate, n_req))
            streams = []
            t0 = time.perf_counter()
            for i, p in enumerate(prompts):
                delay = t0 + arrivals[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                streams.append(pred.submit(p, max_new_tokens=max_new))
            con = [s.result(600) for s in streams]
            wall = time.perf_counter() - t0
            snap = pred.stats.snapshot()
        finally:
            pred.close()
    if con != seq:
        raise RuntimeError('continuous decode transcripts diverged from '
                           'sequential (bit-identity contract)')
    tok_s = sum(len(t) for t in con) / wall
    extra = {}
    if block:
        extra = {'block_size': block,
                 'blocks_peak': snap['blocks_peak'],
                 'prefix_hit_rate': round(snap['prefix_hit_rate'], 3),
                 'cow_blocks': snap['cow_blocks'],
                 'chunk_slices': snap['chunk_slices']}
    return _line('decode_serving_tok_s_per_chip', tok_s, 'tok/s',
                 tok_s / seq_tok_s, seq_tok_s=round(seq_tok_s, 1),
                 slots=slots, max_new=max_new,
                 offered_req_s=round(rate, 1),
                 occupancy=snap['occupancy'],
                 ttft_p50_ms=snap['ttft_p50_ms'],
                 ttft_p99_ms=snap['ttft_p99_ms'],
                 itl_p50_ms=snap['itl_p50_ms'],
                 itl_p99_ms=snap['itl_p99_ms'],
                 baseline_ref='sequential_decode_self', **extra)


def bench_resnet_serving_int8():
    """ResNet-50 QUANTIZED serving tier vs the bf16 tier, SAME session
    (ISSUE 11): one export writes both tiers (calibrated int8 weights +
    activations, dequant fused), then each tier's device time per
    largest-bucket batch is measured through the scanned bulk dispatch
    (two-point slope, the device-time discipline — the per-dispatch cost
    cancels). vs_baseline IS the tier ratio (bf16_ms / int8_ms): on TPU
    the int8 MXU path is the HBM-traffic win the ROADMAP names; on the
    CPU proxy the int8 tier computes the same quantized values in f32
    (ops/quant_ops.py platform split), so the ratio there reads ~1.0 by
    design and parity is the signal. top1_parity: fraction of
    calibration rows whose argmax matches between the tiers.

    Env knobs (PTPU_BENCH_QSERVE_*): BUCKETS, K (slope batches),
    CALIB_BATCHES."""
    import tempfile
    import paddle_tpu as fluid
    from models.resnet import resnet_imagenet
    from paddle_tpu.inference import (Config, create_predictor,
                                      export_compiled, CompiledPredictor)

    buckets = sorted({int(t) for t in os.environ.get(
        'PTPU_BENCH_QSERVE_BUCKETS', '1,8,32').split(',')})
    k = max(2, int(os.environ.get('PTPU_BENCH_QSERVE_K', '8')))
    n_calib = int(os.environ.get('PTPU_BENCH_QSERVE_CALIB_BATCHES', '2'))
    dshape = (3, 224, 224)

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images = fluid.layers.data(name='data', shape=list(dshape),
                                   dtype='float32')
        logits = resnet_imagenet(images, class_dim=1000, depth=50,
                                 is_train=False)
    exe, _ = _device()
    exe.run(startup_p)
    big = max(buckets)
    rng = np.random.RandomState(0)
    calib = [{'data': rng.randn(big, *dshape).astype(np.float32)}
             for _ in range(n_calib)]
    with tempfile.TemporaryDirectory() as d:
        mdir = os.path.join(d, 'model')
        adir = os.path.join(d, 'artifact')
        fluid.io.save_inference_model(mdir, ['data'], [logits], exe,
                                      main_p)
        pred = create_predictor(Config(mdir))
        export_compiled(pred, [calib[0]['data']], adir,
                        batch_sizes=buckets, quantize='int8',
                        calibration=calib)
        with open(os.path.join(adir, 'signature.json')) as f:
            qmeta = json.load(f)['quantization']

        def tier_slope_ms(tier):
            p = CompiledPredictor(adir, tier=tier)
            batches = [[c['data']] for c in
                       (calib * ((k // n_calib) + 1))[:k]]
            p.run_batches(batches[:1])  # warm (compile/AOT load)

            def wall(n):
                t0 = time.perf_counter()
                p.run_batches(batches[:n], group=n)
                return time.perf_counter() - t0
            t_half, t_full = wall(max(1, k // 2)), wall(k)
            return (t_full - t_half) / (k - max(1, k // 2)) * 1e3, p

        bf16_ms, p_b = tier_slope_ms('bf16')
        int8_ms, p_q = tier_slope_ms('int8')
        agree = total = 0
        for c in calib:
            ob = p_b.run([c['data']])[0]
            oq = p_q.run([c['data']])[0]
            agree += int((ob.argmax(1) == oq.argmax(1)).sum())
            total += ob.shape[0]
    img_s = big / int8_ms * 1e3 if int8_ms > 0 else 0.0
    ratio = bf16_ms / int8_ms if int8_ms > 0 else 0.0
    return _line('resnet50_serving_int8_img_s_per_chip', img_s, 'img/s',
                 ratio, batch=big, buckets=buckets,
                 bf16_ms=round(bf16_ms, 3), int8_ms=round(int8_ms, 3),
                 top1_parity=round(agree / max(total, 1), 4),
                 quantized_ops=qmeta['quantized_ops'],
                 float_ops=len(qmeta['float_ops']),
                 baseline_ref='bf16_tier_self')


def bench_decode_serving_int8():
    """Continuous decode over the INT8 paged KV cache vs the fp cache at
    FIXED cache HBM, same session, shared weights (ISSUE 11): the int8
    tier's pages cost ~(1+4/D)/2 the bytes, so the same budget holds 2x
    max_slots — under saturating load the doubled occupancy is a direct
    tokens/s win (each fixed-cost step serves twice the streams).
    vs_baseline = int8 tok/s / fp tok/s at equal cache bytes;
    transcript_match reports the greedy token agreement against the
    fp-KV reference (quantization perturbs logits within the per-page
    step — the stated tolerance).

    Env knobs (PTPU_BENCH_QDECODE_*): SLOTS (fp tier; int8 gets 2x),
    REQS, MAX_NEW, DMODEL, LAYERS."""
    import tempfile
    import paddle_tpu as fluid
    from models.transformer import build_decode_spec
    from paddle_tpu.inference import DecodingPredictor, export_decode

    slots = int(os.environ.get('PTPU_BENCH_QDECODE_SLOTS', '4'))
    n_req = int(os.environ.get('PTPU_BENCH_QDECODE_REQS', '32'))
    max_new = int(os.environ.get('PTPU_BENCH_QDECODE_MAX_NEW', '16'))
    d_model = int(os.environ.get('PTPU_BENCH_QDECODE_DMODEL', '64'))
    n_layer = int(os.environ.get('PTPU_BENCH_QDECODE_LAYERS', '2'))
    vocab, buckets, cache = 512, (8, 16), 64

    def build(kv, s):
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            spec = build_decode_spec(
                vocab=vocab, d_model=d_model, n_head=4, n_layer=n_layer,
                d_ff=4 * d_model, max_slots=s, max_cache_len=cache,
                chunk_sizes=buckets, block_size=16, eos_id=1,
                kv_cache_dtype=kv)
            exe, _ = _device()
            exe.run(spec['startup'], scope=scope)
        return spec, scope

    fp_spec, fp_scope = build('float32', slots)
    q_spec, q_scope = build('int8', 2 * slots)
    cache_names = set(q_spec['cache_vars'])
    for n in q_scope.local_var_names():   # shared weights: honest parity
        if n not in cache_names and fp_scope.get(n) is not None:
            q_scope.set(n, fp_scope.get(n))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, vocab, int(rng.randint(4, max(buckets))))
               for _ in range(n_req)]

    def serve(spec, scope, art):
        with fluid.scope_guard(scope):
            export_decode(spec, art, scope=scope)
        with open(os.path.join(art, 'decode_signature.json')) as f:
            sig = json.load(f)
        pred = DecodingPredictor(art)
        try:
            pred.warmup()
            t0 = time.perf_counter()   # saturating: submit everything
            streams = [pred.submit(p, max_new_tokens=max_new)
                       for p in prompts]
            outs = [s.result(600) for s in streams]
            wall = time.perf_counter() - t0
            snap = pred.stats.snapshot()
        finally:
            pred.close()
        tok_s = sum(len(t) for t in outs) / wall
        return outs, tok_s, snap, sig['cache_bytes']

    with tempfile.TemporaryDirectory() as d:
        fp_out, fp_tok_s, fp_snap, fp_bytes = serve(
            fp_spec, fp_scope, os.path.join(d, 'fp'))
        q_out, q_tok_s, q_snap, q_bytes = serve(
            q_spec, q_scope, os.path.join(d, 'int8'))
    match = float(np.mean([
        np.mean(np.asarray(a[:min(len(a), len(b))])
                == np.asarray(b[:min(len(a), len(b))]))
        for a, b in zip(fp_out, q_out)]))
    return _line('decode_serving_int8_tok_s_per_chip', q_tok_s, 'tok/s',
                 q_tok_s / fp_tok_s if fp_tok_s else 0.0,
                 fp_tok_s=round(fp_tok_s, 1), slots_fp=slots,
                 slots_int8=2 * slots, cache_bytes_fp=fp_bytes,
                 cache_bytes_int8=q_bytes,
                 transcript_match=round(match, 4),
                 occupancy=q_snap['occupancy'], max_new=max_new,
                 itl_p50_ms=q_snap['itl_p50_ms'],
                 baseline_ref='fp_kv_fixed_hbm_self')


def bench_resnet_infer():
    """ResNet-50 INFERENCE vs the committed reference number: 217.69 img/s
    on 2S Xeon 6148 + MKL-DNN, bs=16 (benchmark/IntelOptimizedPaddle.md:87)."""
    from models.resnet import resnet_imagenet
    return _bench_image_infer(
        'resnet50_infer_img_s_per_chip',
        lambda images: resnet_imagenet(images, class_dim=1000, depth=50,
                                       is_train=False),
        'INFER', 217.69, 'xeon6148')


def bench_ocr():
    """CRNN+CTC OCR training (BASELINE.md north star #4: the LoDTensor
    var-len path end-to-end). Labels are variable-length LoD; one compiled
    program serves every batch via traced offsets."""
    import paddle_tpu as fluid
    from models.crnn import build_crnn_train

    batch = int(os.environ.get('PTPU_BENCH_OCR_BATCH', '64'))
    steps = int(os.environ.get('PTPU_BENCH_OCR_STEPS', '20'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images, label, avg_cost, decoded, edit = build_crnn_train(
            num_classes=95, img_h=32, img_w=96, rnn_hidden=96)
    fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)

    rng = np.random.RandomState(0)
    imgs = rng.randn(batch, 1, 32, 96).astype(np.float32)
    lens = rng.randint(3, 12, batch)
    toks = rng.randint(0, 95, int(lens.sum())).astype(np.int32)
    lbl = fluid.create_lod_tensor(toks.reshape(-1, 1), [list(lens)])
    feed = {'pixel': imgs, 'label': lbl}

    dt = _timed_steps(exe, main_p, feed, avg_cost, steps, warmup=3)
    line = _line('ocr_crnn_img_s_per_chip', batch * steps / dt, 'img/s',
                 1.0, dtype='bf16', batch=batch, baseline_ref='self',
                 **_static_fields(main_p, avg_cost, batch))
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, avg_cost, _device_k(8)))


def bench_smallnet():
    """SmallNet (cifar-quick) vs the committed row: 33.113 ms/batch at
    bs256 on a K40m (benchmark/README.md:58). Reported in the baseline's
    unit (ms/batch, lower is better); vs_baseline = baseline/measured."""
    import paddle_tpu as fluid
    from models.smallnet import build_train_net

    batch = int(os.environ.get('PTPU_BENCH_SMALLNET_BATCH', '256'))
    steps = int(os.environ.get('PTPU_BENCH_SMALLNET_STEPS', '50'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images, label, loss, acc = build_train_net()
    fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)
    import jax
    import jax.numpy as jnp
    xs = jax.device_put(
        jnp.asarray(np.random.randn(batch, 3, 32, 32), jnp.float32), dev)
    lab = jax.device_put(
        jnp.asarray(np.random.randint(0, 10, (batch, 1)), jnp.int32), dev)
    feed = {'data': xs, 'label': lab}

    dt = _timed_steps(exe, main_p, feed, loss, steps, warmup=4)
    ms_batch = dt / steps * 1000.0
    base_ms = 33.113 * batch / 256.0
    line = _line('smallnet_cifar_ms_batch', ms_batch, 'ms/batch',
                 base_ms / ms_batch, dtype='bf16', batch=batch,
                 baseline_ref='k40m', **_static_fields(main_p, loss, batch))
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(16)))


def bench_stacked_lstm():
    """Stacked-LSTM text classification vs the committed RNN benchmark row
    (benchmark/README.md:119: 2 LSTM layers + fc, hidden 256, batch 64,
    seq 100, dict 30000 -> 83 ms/batch on a K40m). Reported in the
    baseline's own unit (ms/batch, lower is better); vs_baseline is
    baseline_ms / measured_ms so >1 still means faster."""
    import paddle_tpu as fluid
    from models.stacked_lstm import build_stacked_lstm_train

    batch = int(os.environ.get('PTPU_BENCH_LSTM_BATCH', '64'))
    steps = int(os.environ.get('PTPU_BENCH_LSTM_STEPS', '30'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        ids, label, loss, flops_per_batch = build_stacked_lstm_train(batch)
    fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)

    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    feed = {'ids': jax.device_put(jnp.asarray(
                rng.randint(1, 30000, (batch, 100)).astype(np.int32)), dev),
            'label': jax.device_put(jnp.asarray(
                rng.randint(0, 2, (batch, 1)).astype(np.int32)), dev)}

    dt = _timed_steps(exe, main_p, feed, loss, steps, warmup=3)
    ms_batch = dt / steps * 1000.0
    peak = _peak_flops(dev)
    mfu = (flops_per_batch * steps / dt / peak) if peak else None
    # the committed row is per-batch at batch=64; scale the denominator
    # so an env-overridden batch still compares per-sample throughput
    base_ms = 83.0 * batch / 64.0
    line = _line('stacked_lstm_text_cls_ms_batch', ms_batch, 'ms/batch',
                 base_ms / ms_batch,
                 mfu=round(mfu, 4) if mfu is not None else None,
                 dtype='bf16', batch=batch, baseline_ref='k40m',
                 **_static_fields(main_p, loss, batch))
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(8)))


def bench_smallnet_multistep():
    """SmallNet with K steps per dispatch (ISSUE 2 headline scenario):
    the smallnet step carries little compute against the fixed
    per-dispatch cost, so ms/batch is dispatch-bound and run_steps(K)
    divides that cost by K. Same-session
    A/B: the single-step path is measured first and reported alongside.
    CPU caveat (PERF_NOTES round 6): XLA:CPU runs CONV bodies inside
    lax.scan ~10x slower than at top level, so this metric is only
    meaningful on the accelerator; the CPU dispatch-overhead proxy is
    scripts/multi_step_smoke.py's fc model."""
    import paddle_tpu as fluid
    from models.smallnet import build_train_net

    batch = int(os.environ.get('PTPU_BENCH_SMALLNET_BATCH', '256'))
    k = int(os.environ.get('PTPU_BENCH_SMALLNET_K', '16'))
    dispatches = int(os.environ.get('PTPU_BENCH_SMALLNET_DISPATCHES', '8'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images, label, loss, acc = build_train_net()
    fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)
    import jax
    import jax.numpy as jnp
    feed = {'data': jax.device_put(jnp.asarray(
                np.random.randn(batch, 3, 32, 32), jnp.float32), dev),
            'label': jax.device_put(jnp.asarray(
                np.random.randint(0, 10, (batch, 1)), jnp.int32), dev)}

    dt1 = _timed_steps(exe, main_p, feed, loss, 30, warmup=4)
    single_ms = dt1 / 30 * 1000.0
    dt = _timed_multi_steps(exe, main_p, _stack_k(feed, k), loss,
                            dispatches, k)
    ms_batch = dt / (dispatches * k) * 1000.0
    base_ms = 33.113 * batch / 256.0
    line = _line('smallnet_cifar_multistep_ms_batch', ms_batch, 'ms/batch',
                 base_ms / ms_batch, dtype='bf16', batch=batch,
                 steps_per_dispatch=k,
                 single_step_ms_batch=round(single_ms, 2),
                 speedup_vs_single=round(single_ms / ms_batch, 2),
                 baseline_ref='k40m')
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(k)))


def bench_stacked_lstm_multistep():
    """Stacked-LSTM with K steps per dispatch — the second dispatch-bound
    training metric. Matmul-dominated, so unlike smallnet the CPU scan
    body is not penalized and the A/B is meaningful on both platforms."""
    import paddle_tpu as fluid
    from models.stacked_lstm import build_stacked_lstm_train

    batch = int(os.environ.get('PTPU_BENCH_LSTM_BATCH', '64'))
    k = int(os.environ.get('PTPU_BENCH_LSTM_K', '8'))
    dispatches = int(os.environ.get('PTPU_BENCH_LSTM_DISPATCHES', '6'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        ids, label, loss, flops_per_batch = build_stacked_lstm_train(batch)
    fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    feed = {'ids': jax.device_put(jnp.asarray(
                rng.randint(1, 30000, (batch, 100)).astype(np.int32)), dev),
            'label': jax.device_put(jnp.asarray(
                rng.randint(0, 2, (batch, 1)).astype(np.int32)), dev)}

    dt1 = _timed_steps(exe, main_p, feed, loss, 20, warmup=3)
    single_ms = dt1 / 20 * 1000.0
    dt = _timed_multi_steps(exe, main_p, _stack_k(feed, k), loss,
                            dispatches, k)
    ms_batch = dt / (dispatches * k) * 1000.0
    base_ms = 83.0 * batch / 64.0
    line = _line('stacked_lstm_multistep_ms_batch', ms_batch, 'ms/batch',
                 base_ms / ms_batch, dtype='bf16', batch=batch,
                 steps_per_dispatch=k,
                 single_step_ms_batch=round(single_ms, 2),
                 speedup_vs_single=round(single_ms / ms_batch, 2),
                 baseline_ref='k40m')
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(k)))


def bench_ocr_multistep():
    """CRNN+CTC OCR with K steps per dispatch: the LoD-label path through
    run_steps (labels stack in STATIC-lod form — CRNN's decode ops need
    host offsets, so every step in a group shares one lod pattern). The
    same-session single-step A/B is the comparison that means
    something."""
    import paddle_tpu as fluid
    from models.crnn import build_crnn_train

    batch = int(os.environ.get('PTPU_BENCH_OCR_BATCH', '64'))
    k = int(os.environ.get('PTPU_BENCH_OCR_K', '8'))
    dispatches = int(os.environ.get('PTPU_BENCH_OCR_DISPATCHES', '6'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images, label, avg_cost, decoded, edit = build_crnn_train(
            num_classes=95, img_h=32, img_w=96, rnn_hidden=96)
    fluid.contrib.mixed_precision.enable_bf16(main_p)

    exe, dev = _device()
    exe.run(startup_p)
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    imgs = jax.device_put(jnp.asarray(
        rng.randn(batch, 1, 32, 96), jnp.float32), dev)
    lens = rng.randint(3, 12, batch)
    toks = rng.randint(0, 95, int(lens.sum())).astype(np.int32)
    lbl = fluid.create_lod_tensor(toks.reshape(-1, 1), [list(lens)])
    feed = {'pixel': imgs, 'label': lbl}

    dt1 = _timed_steps(exe, main_p, feed, avg_cost, 20, warmup=3)
    single_ms = dt1 / 20 * 1000.0
    # LoD labels cannot pre-stack into one array: run_steps stacks the K
    # per-step LoDTensors. CRNN's block contains host-lod ops
    # (ctc_greedy_decoder / edit_distance: output shapes depend on lod
    # CONTENT), so its groups must share one lod pattern and stack in
    # STATIC form — varying patterns would route to traced-offset
    # stacking, which this program cannot trace (same constraint as
    # single-step run()). The traced-stack path is exercised by
    # tests/test_multi_step.py's varying-pattern test instead.
    multi_feed = {'pixel': jnp.stack([imgs] * k), 'label': [lbl] * k}
    dt = _timed_multi_steps(exe, main_p, multi_feed, avg_cost,
                            dispatches, k)
    img_s = batch * dispatches * k / dt
    single_img_s = batch / (single_ms / 1000.0)
    line = _line('ocr_crnn_multistep_img_s_per_chip', img_s, 'img/s',
                 1.0, dtype='bf16', batch=batch, steps_per_dispatch=k,
                 single_step_img_s=round(single_img_s, 2),
                 speedup_vs_single=round(img_s / single_img_s, 2),
                 baseline_ref='self')
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, avg_cost, _device_k(k)))


def bench_data_plane():
    """Feeder saturation (ISSUE 9 acceptance): serial vs pooled decode
    throughput on the synthetic image pipeline (dataset/synthetic.py —
    zlib+numpy decode plus a modeled remote-fetch latency), SAME shards
    and SAME decode fn in both arms, delivery bit-identical (digest
    compared). value = pooled samples/s; vs_baseline = pooled/serial,
    the >=3x acceptance ratio. Host-only: no device work — this measures
    the data plane that has to hit ~320k img/s for a v5p-128 ResNet pod
    (ROADMAP item 5). Scale PTPU_BENCH_DP_WORKERS to host cores."""
    import hashlib
    import tempfile
    from paddle_tpu.dataset import synthetic
    from paddle_tpu.reader.sharded import ShardedFileReader

    shards = int(os.environ.get('PTPU_BENCH_DP_SHARDS', '4'))
    per = int(os.environ.get('PTPU_BENCH_DP_SAMPLES', '256'))
    workers = int(os.environ.get('PTPU_BENCH_DP_WORKERS',
                                 str(max(8, os.cpu_count() or 8))))
    mode = os.environ.get('PTPU_BENCH_DP_MODE', 'thread')
    lat_ms = float(os.environ.get('PTPU_BENCH_DP_LATENCY_MS', '3.0'))

    tmp = tempfile.mkdtemp(prefix='ptpu_bench_dp_')
    files = synthetic.write_shards(tmp, num_shards=shards,
                                   samples_per_shard=per, seed=11)
    decode = synthetic.make_decode_fn(latency_s=lat_ms * 1e-3)

    def drain(it):
        h = hashlib.sha256()
        n = 0
        t0 = time.perf_counter()
        for img, label in it:
            h.update(img.tobytes())
            h.update(label.tobytes())
            n += 1
        return h.hexdigest(), n / (time.perf_counter() - t0)

    d_serial, r_serial = drain(decode(r)
                               for r in ShardedFileReader(files).records())
    pooled = ShardedFileReader(files).pooled(decode, num_workers=workers,
                                             mode=mode)
    d_pooled, r_pooled = drain(pooled())
    stats = pooled.feeder_stats()
    return _line('data_plane_samples_s', r_pooled, 'samples/s',
                 r_pooled / r_serial,
                 serial_samples_s=round(r_serial, 1), workers=workers,
                 mode=mode, latency_ms=lat_ms,
                 occupancy=round(stats['occupancy'], 2),
                 bit_identical=bool(d_serial == d_pooled))


def bench_fleet_serving():
    """Serving-fleet control plane (ISSUE 12): the SAME 5x Poisson load
    swing (low -> 5x surge -> low, rates calibrated to one replica's
    measured capacity) offered to (a) a pinned single decode replica
    and (b) a pinned N-replica fleet of subprocess replicas.
    value = the fleet's p99 TTFT over the swing (ms, lower is
    better); vs_baseline = single-replica p99 TTFT / fleet p99 TTFT —
    the tail-latency cut the fleet buys at the same offered load (the
    single replica queues the surge; the fleet absorbs it). Fleet and
    single tokens/s ride along as fields, with the caveat that on a
    core-starved CI host the arrival generator itself slows under the
    fleet's worker processes, so wall-clock token rates under-report
    the fleet (PERF_NOTES round 15). The fleet arm runs N pre-warmed replicas (the
    steady-state the autoscaler converges to; REACTIVE scale-out under
    the same swing is exercised end-to-end by scripts/fleet_smoke.py —
    on a CPU-starved host a mid-surge spin-up steals cycles from
    serving, so the bench pins the arms instead of racing them). Decode
    steps are dispatch-floor-bound, so replica processes scale even on
    a small CI host (compute-bound fleets need cores >= replicas).

    Env knobs: PTPU_BENCH_FLEET_{REQS,MAX_NEW,REPLICAS}."""
    import tempfile
    import paddle_tpu as fluid
    from models.transformer import build_decode_spec
    from paddle_tpu.inference import FleetRouter, export_decode

    max_replicas = int(os.environ.get('PTPU_BENCH_FLEET_REPLICAS', '3'))
    surge_n = int(os.environ.get('PTPU_BENCH_FLEET_REQS', '120'))
    max_new = int(os.environ.get('PTPU_BENCH_FLEET_MAX_NEW', '96'))

    tmp = tempfile.mkdtemp(prefix='ptpu_bench_fleet_')
    art = os.path.join(tmp, 'decode_art')
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(vocab=211, d_model=48, n_head=4,
                                 n_layer=2, d_ff=96, max_slots=4,
                                 max_cache_len=max_new + 10,
                                 chunk_sizes=(4, 8), block_size=16,
                                 eos_id=1)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'])
        export_decode(spec, art, scope=scope)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, 211, rng.randint(2, 9))
               for _ in range(200)]

    def offer_swing(router, base_hz):
        futs = []
        arr = np.random.RandomState(1)
        for n, hz in ((surge_n // 4, base_hz), (surge_n, base_hz * 5),
                      (surge_n // 4, base_hz)):
            for k in range(n):
                futs.append(router.submit(prompts[k % len(prompts)],
                                          max_new_tokens=max_new))
                time.sleep(arr.exponential(1.0 / hz))
        return futs

    def run_arm(n_replicas, base_hz=None):
        router = FleetRouter(art, replicas=n_replicas, platform='cpu')
        try:
            if base_hz is None:
                # capacity calibration, SINGLE arm only: both arms offer
                # the same swing, derived from one replica's capacity
                t0 = time.perf_counter()
                cal = [router.submit(prompts[k], max_new_tokens=max_new)
                       for k in range(16)]
                for f in cal:
                    f.result(300)
                cap_hz = 16.0 / (time.perf_counter() - t0)
                base_hz = min(0.4 * cap_hz, 30.0)
                # the closed-loop burst queues hard on a 4-slot
                # replica: drop its high-TTFT samples so the reported
                # percentiles cover ONLY the swing both arms share
                router.stats.reset()
            t0 = time.perf_counter()
            futs = offer_swing(router, base_hz)
            toks = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            snap = router.fleet_snapshot()
            n_tok = sum(len(t) for t in toks)
            return {'tok_s': n_tok / wall, 'base_hz': base_hz,
                    'ttft_p50_ms': snap['ttft_p50_ms'],
                    'ttft_p99_ms': snap['ttft_p99_ms'],
                    'p99_ms': snap['p99_ms'],
                    'failed': snap['failed']}
        finally:
            router.close()

    single = run_arm(1)
    fleet = run_arm(max_replicas, base_hz=single['base_hz'])
    return _line('fleet_serving_ttft_p99_ms', fleet['ttft_p99_ms'],
                 'ms', (single['ttft_p99_ms'] / fleet['ttft_p99_ms'])
                 if fleet['ttft_p99_ms'] else 1.0,
                 max_replicas=max_replicas,
                 single_ttft_p99_ms=single['ttft_p99_ms'],
                 ttft_p50_ms=fleet['ttft_p50_ms'],
                 single_ttft_p50_ms=single['ttft_p50_ms'],
                 tok_s=round(fleet['tok_s'], 1),
                 single_tok_s=round(single['tok_s'], 1),
                 offered_req_s=round(single['base_hz'] * 5, 1),
                 dropped=fleet['failed'] + single['failed'],
                 baseline_ref='self_1replica_same_swing',
                 # the bench parent holds the chip (one process per
                 # chip), so the replicas are cpu processes by
                 # construction — and the line says so
                 platform='cpu', device_kind='cpu')


def bench_ctr():
    import paddle_tpu as fluid
    from models.deepfm import build_deepfm_train

    batch = int(os.environ.get('PTPU_BENCH_CTR_BATCH', '4096'))
    # steps are high because the step itself is short: dispatch jitter
    # dominates short runs
    steps = int(os.environ.get('PTPU_BENCH_CTR_STEPS', '100'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        feeds, loss = build_deepfm_train()

    exe, dev = _device()
    exe.run(startup_p)

    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    feed = {}
    for name, shape, dtype, vocab in feeds:
        full = (batch,) + tuple(shape)
        if dtype.startswith('int'):
            arr = rng.randint(0, vocab, full).astype(np.int32)
        elif vocab == 2:  # binary click label
            arr = (rng.rand(*full) < 0.5).astype(np.float32)
        else:
            arr = rng.randn(*full).astype(np.float32)
        feed[name] = jax.device_put(jnp.asarray(arr), dev)

    dt = _timed_steps(exe, main_p, feed, loss, steps, warmup=3)
    samples_s = batch * steps / dt
    # analytic dense-tower MACs/sample (models/deepfm.py defaults:
    # concat 26*16+13=429 -> 400 -> 400 -> 400 -> 1, + dense fc 13->1);
    # embedding gathers carry ~0 MXU FLOPs, so the honest MFU is tiny —
    # this workload measures the sparse/gather path, not the MXU
    macs = 429 * 400 + 400 * 400 + 400 * 400 + 400 + 13
    flops_per_sample = 3 * 2 * macs
    peak = _peak_flops(dev)
    mfu = (samples_s * flops_per_sample / peak) if peak else None
    if batch == 4096:  # the committed CPU denominator's batch
        vs = round(samples_s / BASELINE_CTR_CPU_SAMPLES_S, 2)
        base = 'cpu_deepfm@4096'
    else:  # embedding-gather throughput is batch-sensitive: a ratio
        # against the bs-4096 CPU number would be apples-to-oranges
        vs = 1.0
        base = 'self'
    line = _line(
        'ctr_deepfm_samples_s_per_chip', samples_s, 'samples/s', vs,
        mfu=round(mfu, 6) if mfu is not None else None, batch=batch,
        baseline_ref=base, **_static_fields(main_p, loss, batch))
    return _attach_device_time(line, lambda: _device_ms_scan(
        exe, main_p, feed, loss, _device_k(8)))


# ---------------------------------------------------------------------------
# ablation mode (ISSUE 16): PTPU_BENCH_ABLATE=googlenet|lstm runs the
# pass-on/off arms in ONE session with the same two-point-slope device
# timing as every other metric and emits a PERF_NOTES-ready markdown
# table next to the per-arm JSON lines. The on/off switch is structural
# (different pass pipeline / program attr), not an env flip, so both
# arms share the session, the compile cache, and the init snapshot.
# ---------------------------------------------------------------------------
def _emit_ablation_table(title, headers, rows):
    print('\nABLATION ' + title, flush=True)
    print('| ' + ' | '.join(headers) + ' |')
    print('|' + '|'.join('---' for _ in headers) + '|')
    for r in rows:
        print('| ' + ' | '.join(str(c) for c in r) + ' |')
    print('', flush=True)


def _snap_scope(scope):
    return {k: np.asarray(v) for k, v in scope._vars.items()
            if v is not None}


def _arm_scope(snap):
    import paddle_tpu as fluid
    sc = fluid.core.Scope()
    for k, v in snap.items():
        sc.set(k, v)
    return sc


def bench_ablate_googlenet():
    """GoogLeNet horizontal_fuse A/B: train and inference programs run
    through the SAME pass pipeline with and without horizontal_fuse (the
    only varying arm ingredient), same weights, same feed, same session.
    Per arm: dispatch-inclusive ms/step, device ms/step (two-point
    slope), derived img/s, and max|Δloss| vs the base arm (parity)."""
    import paddle_tpu as fluid
    from paddle_tpu import passes
    from models.googlenet import build_train_net, googlenet, \
        GOOGLENET_FWD_MACS

    batch = int(os.environ.get('PTPU_BENCH_ABLATE_BATCH', '8'))
    side = int(os.environ.get('PTPU_BENCH_ABLATE_SIDE', '224'))
    steps = int(os.environ.get('PTPU_BENCH_ABLATE_STEPS', '6'))
    k = _device_k(int(os.environ.get('PTPU_BENCH_ABLATE_K', '4')))
    reps = int(os.environ.get('PTPU_BENCH_ABLATE_REPS', '2'))
    use_bf16 = os.environ.get('PTPU_BENCH_DTYPE', 'bf16') == 'bf16'

    exe, dev = _device()
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    xs = jax.device_put(jnp.asarray(
        rng.randn(batch, 3, side, side).astype(np.float32)), dev)
    lab = jax.device_put(jnp.asarray(
        rng.randint(0, 1000, (batch, 1)).astype(np.int32)), dev)

    base_pl = [p for p in passes.OPTIMIZATION_PIPELINE
               if p != 'horizontal_fuse']
    infer_base_pl = [p for p in passes.INFERENCE_PIPELINE
                     if p != 'horizontal_fuse']

    # -- train program (one build, one init snapshot for every arm) --------
    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = 11
    with fluid.program_guard(main_p, startup_p):
        images, label, loss, acc = build_train_net(
            dshape=(3, side, side), class_dim=1000)
    if use_bf16:
        fluid.contrib.mixed_precision.enable_bf16(main_p)
    scope0 = fluid.core.Scope()
    with fluid.scope_guard(scope0):
        exe.run(startup_p)
    snap = _snap_scope(scope0)
    feed = {'data': xs, 'label': lab}

    # -- inference program (same weights via the shared snapshot) ----------
    infer_p, infer_sp = fluid.Program(), fluid.Program()
    infer_p.random_seed = infer_sp.random_seed = 11
    with fluid.program_guard(infer_p, infer_sp):
        iimages = fluid.layers.data(name='data', shape=[3, side, side],
                                    dtype='float32')
        logits = googlenet(iimages, class_dim=1000, is_train=False)
    scope_i = fluid.core.Scope()
    with fluid.scope_guard(scope_i):
        exe.run(infer_sp)
    snap_i = _snap_scope(scope_i)

    def train_arm(name, pipeline):
        prog, reports = passes.PassManager(pipeline).apply(
            main_p, fetch_names=[loss.name])
        hf = next((r for r in reports if r.name == 'horizontal_fuse'), None)
        sc = _arm_scope(snap)
        with fluid.scope_guard(sc):
            l0 = float(np.asarray(exe.run(
                prog, feed=feed, fetch_list=[loss.name])[0]).reshape(-1)[0])
        sc = _arm_scope(snap)
        with fluid.scope_guard(sc):
            dt = _timed_steps(exe, prog, feed, loss, steps, warmup=2)
            dev_ms, dev_k = _device_ms_scan(exe, prog, feed, loss, k,
                                            reps=reps, scope=sc)
        return {'arm': name, 'mode': 'train', 'batch': batch,
                'convs_fused': hf.details.get('convs_fused')
                if hf is not None else 0,
                'loss0': l0,
                'ms_step': round(dt / steps * 1e3, 2),
                'device_ms_step': round(dev_ms, 2) if dev_ms > 0 else None,
                'device_k': dev_k}

    def infer_arm(name, pipeline):
        prog, reports = passes.PassManager(pipeline).apply(
            infer_p, fetch_names=[logits.name])
        hf = next((r for r in reports if r.name == 'horizontal_fuse'), None)
        sc = _arm_scope(snap_i)
        with fluid.scope_guard(sc):
            out0 = np.asarray(exe.run(prog, feed={'data': xs},
                                      fetch_list=[logits.name])[0])
            t0 = time.perf_counter()
            for _ in range(steps):
                o = exe.run(prog, feed={'data': xs},
                            fetch_list=[logits.name], return_numpy=False)
            np.asarray(o[0])
            dt = time.perf_counter() - t0
            dev_ms, dev_k = _device_ms_scan(exe, prog, {'data': xs},
                                            logits.name, k, reps=reps,
                                            scope=sc)
        return {'arm': name, 'mode': 'infer', 'batch': batch,
                'convs_fused': hf.details.get('convs_fused')
                if hf is not None else 0,
                'out0': out0,
                'ms_step': round(dt / steps * 1e3, 2),
                'device_ms_step': round(dev_ms, 2) if dev_ms > 0 else None,
                'device_k': dev_k}

    arms = [train_arm('train_base', base_pl),
            train_arm('train_hfuse', list(passes.OPTIMIZATION_PIPELINE)),
            infer_arm('infer_base', infer_base_pl),
            infer_arm('infer_hfuse', list(passes.INFERENCE_PIPELINE))]

    # parity vs each mode's base arm (same snapshot, same feed, same rng
    # stream -> bit-level comparable)
    arms[1]['parity_dloss'] = abs(arms[1]['loss0'] - arms[0]['loss0'])
    arms[3]['parity_dlogits'] = float(
        np.max(np.abs(arms[3].pop('out0') - arms[2].pop('out0'))))
    rows = []
    for a in arms:
        base = arms[0] if a['mode'] == 'train' else arms[2]
        for key in ('ms_step', 'device_ms_step'):
            a['img_s' if key == 'ms_step' else 'device_img_s'] = (
                round(batch / a[key] * 1e3, 1) if a.get(key) else None)
        a['speedup_vs_base'] = (
            round(base['device_ms_step'] / a['device_ms_step'], 3)
            if a.get('device_ms_step') and base.get('device_ms_step')
            else None)
        line = {'metric': 'ablate_googlenet_' + a['arm']}
        line.update({k: v for k, v in a.items() if k not in ('out0',)})
        line.pop('loss0', None)
        _print_line(line)
        rows.append([a['arm'], batch, a['convs_fused'], a['ms_step'],
                     a['device_ms_step'], a['device_img_s'],
                     a['speedup_vs_base'],
                     a.get('parity_dloss', a.get('parity_dlogits', '-'))])
    _emit_ablation_table(
        'googlenet horizontal_fuse (side=%d, %s)'
        % (side, 'bf16' if use_bf16 else 'fp32'),
        ['arm', 'batch', 'convs_fused', 'ms/step', 'device ms/step',
         'device img/s', 'speedup vs base', 'parity |d|'], rows)
    return arms


def bench_ablate_lstm():
    """Stacked-LSTM fused-scan ablation over the three axes VERDICT r5
    item 4 asked for: fuse_layers off/on x batch 64->512 x run_steps K.
    Each (batch, fuse) arm is its own program build (fuse_layers is
    program structure); single-step dispatch ms, K-step dispatch ms, and
    the device slope ride in every row."""
    import paddle_tpu as fluid
    from models.stacked_lstm import build_stacked_lstm_train

    batches = [int(b) for b in os.environ.get(
        'PTPU_BENCH_ABLATE_BATCHES', '64,512').split(',') if b.strip()]
    kk = int(os.environ.get('PTPU_BENCH_LSTM_K', '8'))
    steps = int(os.environ.get('PTPU_BENCH_ABLATE_STEPS', '6'))
    dispatches = int(os.environ.get('PTPU_BENCH_LSTM_DISPATCHES', '3'))
    reps = int(os.environ.get('PTPU_BENCH_ABLATE_REPS', '2'))
    use_bf16 = os.environ.get('PTPU_BENCH_DTYPE', 'bf16') == 'bf16'

    exe, dev = _device()
    import jax
    import jax.numpy as jnp

    def arm(batch, fuse):
        main_p, startup_p = fluid.Program(), fluid.Program()
        main_p.random_seed = startup_p.random_seed = 11
        with fluid.program_guard(main_p, startup_p):
            ids, label, loss, flops = build_stacked_lstm_train(
                batch, fuse_layers=fuse)
        if use_bf16:
            fluid.contrib.mixed_precision.enable_bf16(main_p)
        scope = fluid.core.Scope()
        rng = np.random.RandomState(0)
        feed = {'ids': jax.device_put(jnp.asarray(
                    rng.randint(1, 30000, (batch, 100)).astype(np.int32)),
                    dev),
                'label': jax.device_put(jnp.asarray(
                    rng.randint(0, 2, (batch, 1)).astype(np.int32)), dev)}
        with fluid.scope_guard(scope):
            exe.run(startup_p)
            l0 = float(np.asarray(exe.run(
                main_p, feed=feed,
                fetch_list=[loss.name])[0]).reshape(-1)[0])
            dt1 = _timed_steps(exe, main_p, feed, loss, steps, warmup=2)
            dtk = _timed_multi_steps(exe, main_p, _stack_k(feed, kk), loss,
                                     dispatches, kk, warmup=1)
            dev_ms, dev_k = _device_ms_scan(exe, main_p, feed, loss, kk,
                                            reps=reps, scope=scope)
        return {'arm': 'b%d_%s' % (batch, 'fused' if fuse else 'perlayer'),
                'batch': batch, 'fuse_layers': fuse, 'loss0': l0,
                'ms_batch': round(dt1 / steps * 1e3, 2),
                'ms_batch_k%d' % kk: round(dtk / (dispatches * kk) * 1e3, 2),
                'device_ms_batch': round(dev_ms, 2) if dev_ms > 0 else None,
                'device_k': dev_k}

    arms = []
    for batch in batches:
        for fuse in (False, True):
            arms.append(arm(batch, fuse))
    rows = []
    for a in arms:
        base = next(b for b in arms
                    if b['batch'] == a['batch'] and not b['fuse_layers'])
        a['parity_dloss'] = abs(a['loss0'] - base['loss0'])
        a['speedup_vs_perlayer'] = (
            round(base['device_ms_batch'] / a['device_ms_batch'], 3)
            if a.get('device_ms_batch') and base.get('device_ms_batch')
            else None)
        line = {'metric': 'ablate_lstm_' + a['arm']}
        line.update(a)
        line.pop('loss0', None)
        _print_line(line)
        kcol = 'ms_batch_k%d' % kk
        rows.append([a['arm'], a['batch'],
                     'on' if a['fuse_layers'] else 'off', a['ms_batch'],
                     a[kcol], a['device_ms_batch'],
                     a['speedup_vs_perlayer'],
                     '%.3g' % a['parity_dloss']])
    _emit_ablation_table(
        'stacked_lstm fuse_layers (seq=100, hidden=256, %s)'
        % ('bf16' if use_bf16 else 'fp32'),
        ['arm', 'batch', 'fuse', 'ms/batch', 'ms/batch K=%d' % kk,
         'device ms/batch', 'speedup vs per-layer', 'parity |dloss|'],
        rows)
    return arms


_ABLATIONS = {'googlenet': bench_ablate_googlenet,
              'lstm': bench_ablate_lstm}


BENCHES = [
    ('resnet50_train_img_s_per_chip', bench_resnet),     # headline: FIRST
    ('transformer_base_tokens_s_per_chip', bench_transformer),
    ('bert_mlm_tokens_s_per_chip', bench_bert),
    ('ctr_deepfm_samples_s_per_chip', bench_ctr),
    ('ocr_crnn_img_s_per_chip', bench_ocr),
    ('vgg19_train_img_s_per_chip', bench_vgg),
    ('alexnet_train_img_s_per_chip', bench_alexnet),
    ('resnet50_infer_img_s_per_chip', bench_resnet_infer),
    ('resnet50_serving_img_s_per_chip', bench_resnet_serving),
    ('decode_serving_tok_s_per_chip', bench_decode_serving),
    # quantized serving tiers (ISSUE 11): same-session bf16 A/B rides in
    # each line (vs_baseline = the tier ratio) plus top-1 parity /
    # transcript agreement against the float reference
    ('resnet50_serving_int8_img_s_per_chip', bench_resnet_serving_int8),
    ('decode_serving_int8_tok_s_per_chip', bench_decode_serving_int8),
    ('stacked_lstm_text_cls_ms_batch', bench_stacked_lstm),
    ('googlenet_train_img_s_per_chip', bench_googlenet),
    ('googlenet_infer_img_s_per_chip', bench_googlenet_infer),
    ('smallnet_cifar_ms_batch', bench_smallnet),
    # multi-step dispatch variants (ISSUE 2): K steps per device program,
    # same-session single-step A/B in each line
    ('smallnet_cifar_multistep_ms_batch', bench_smallnet_multistep),
    ('stacked_lstm_multistep_ms_batch', bench_stacked_lstm_multistep),
    ('ocr_crnn_multistep_img_s_per_chip', bench_ocr_multistep),
    # data-plane feeder saturation (ISSUE 9): host-side serial-vs-pooled
    # A/B; vs_baseline is the pooled/serial ratio (>=3x acceptance)
    ('data_plane_samples_s', bench_data_plane),
    # serving-fleet control plane (ISSUE 12): 1-replica vs N-replica
    # FleetRouter under the SAME Poisson swing; value = fleet p99 TTFT
    # (ms, lower better), vs_baseline = single p99 / fleet p99 (the
    # tail-latency cut)
    ('fleet_serving_ttft_p99_ms', bench_fleet_serving),
]

# PTPU_BENCH_ONLY token -> metric-name prefix; indices derive from BENCHES
# so inserting/reordering entries can't silently select the wrong bench
_SHORT_PREFIX = {
    'resnet': 'resnet50_train', 'transformer': 'transformer',
    'bert': 'bert', 'ctr': 'ctr', 'ocr': 'ocr', 'vgg': 'vgg',
    'alexnet': 'alexnet', 'infer': 'resnet50_infer',
    'serving': 'resnet50_serving_img',
    'decode': 'decode_serving_tok',
    'qserving': 'resnet50_serving_int8',
    'qdecode': 'decode_serving_int8',
    'lstm': 'stacked_lstm_text', 'googlenet': 'googlenet_train',
    'ginfer': 'googlenet_infer', 'smallnet': 'smallnet_cifar_ms',
    'smallnet_k': 'smallnet_cifar_multistep',
    'lstm_k': 'stacked_lstm_multistep', 'ocr_k': 'ocr_crnn_multistep',
    'data_plane': 'data_plane',
    'fleet': 'fleet_serving',
}
_SHORT = {tok: next(i for i, (n, _) in enumerate(BENCHES)
                    if n.startswith(pref))
          for tok, pref in _SHORT_PREFIX.items()}


def main(benches=None):
    """Run benchmarks; exit non-zero if any metric line is an error. The
    headline runs first; its line is printed immediately (insurance) and
    re-printed last (the driver parses the final JSON line as the
    headline)."""
    # persistent compile cache ON by default for bench runs (placed by
    # JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout path —
    # core/compile_cache.py): round N+1 measures the warm-start trajectory
    # of the executables round N persisted, and compile_s_cold/warm on
    # every metric line records it. An EXPLICIT env opt-out
    # (PTPU_COMPILE_CACHE=0/off/...) wins — the knob's own semantics
    # (compile_cache.enabled()) decide, bench only flips the default for
    # the unset case
    from paddle_tpu.core import compile_cache as _cc
    if os.environ.get('PTPU_COMPILE_CACHE') is None or _cc.enabled():
        _cc.enable()
    failed = False
    ablate = os.environ.get('PTPU_BENCH_ABLATE', '')
    if ablate:
        # ablation mode replaces the suite: every requested model's
        # on/off arms run in this one session and emit a PERF_NOTES-ready
        # table; unknown tokens are reported, never silently skipped
        for tok in (t.strip() for t in ablate.split(',') if t.strip()):
            fn = _ABLATIONS.get(tok)
            if fn is None:
                _print_line({'metric': 'ablate_' + tok,
                             'error': 'unknown PTPU_BENCH_ABLATE token'})
                failed = True
                continue
            line = run_metric('ablate_' + tok, fn)
            if isinstance(line, dict) and 'error' in line:
                _print_line(line)
                failed = True
        return 1 if failed else 0
    if benches is None:
        benches = BENCHES
        only = os.environ.get('PTPU_BENCH_ONLY', '')
        if only and only != 'all':
            tokens = [t.strip() for t in only.split(',') if t.strip()]
            unknown = [t for t in tokens if t not in _SHORT]
            for t in unknown:
                _print_line({'metric': t,
                             'error': 'unknown PTPU_BENCH_ONLY token'})
                failed = True
            keep = {_SHORT[t] for t in tokens if t in _SHORT}
            # run only what was recognized; a pure-typo selection runs
            # nothing rather than burning TPU time on the full suite
            benches = [b for i, b in enumerate(BENCHES) if i in keep]
    headline_line = None
    results = []
    for i, (name, fn) in enumerate(benches):
        line = run_metric(name, fn)
        _print_line(line)
        results.append(line)
        failed = failed or 'error' in line
        if i == 0:
            headline_line = line
    if headline_line is not None and len(benches) > 1:
        # the all-metrics summary rides immediately before the headline
        # re-print: a tail-byte-capped artifact keeps every metric's
        # number even when the per-metric lines above are cut
        _print_line(_summary_line(results))
        # headline (success OR error) is the last JSON line — the driver
        # parses the final line, and mislabeling a secondary metric as the
        # headline would be worse than an explicit headline error
        _print_line(headline_line)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
