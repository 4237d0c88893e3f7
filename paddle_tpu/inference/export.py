"""Compiled-artifact export: serve without the Python tracer.

The reference ships a non-Python deployment path — a C++ API over a saved
program (inference/api/paddle_api.h:1 PaddlePredictor,
api/analysis_predictor.cc:359 CreatePaddlePredictor) and a C++ trainer demo
(train/demo_trainer.cc:1). The TPU-native equivalent of "deploy without the
framework" is an ahead-of-time compiled XLA artifact: the program is traced
ONCE here and the result is serialized with `jax.export` (StableHLO +
calling convention). The loader (serve.py, decoding.py) needs only jax +
numpy — it never imports the Program IR, the op registry, or the tracer.

Where the parameters live differs by kind of artifact. `export_compiled`
(the stateless inference path) still bakes them into its one module as
constants. `export_decode` and `export_train_step` hold nothing as a
constant: a decode artifact's programs all take the SAME parameter list as
their first, undonated argument, loaded once from the artifact's one
weights file (decode_weights.bin, mapped by the signature's 'params'), and
a train artifact threads parameters and optimizer state input -> output.

Artifact layout (out_dir/):
  module.jaxexport   serialized jax.export artifact (StableHLO, params baked)
  signature.json     {"feeds": [{name, shape, dtype}...], "fetches": [...]}

Shapes are fixed at export (XLA compiles static shapes); export one artifact
per served batch shape, as with any AOT deployment. With
`batch_sizes=[1, 8, 32, ...]` ONE artifact dir carries several compiled
batch buckets (dense feeds only): each bucket is a complete standard
artifact under bucket_<n>/, and the top level mirrors the LARGEST bucket
plus a "buckets" signature key — so CompiledPredictor(out_dir) keeps
working unchanged while batching.BatchingPredictor picks the smallest
bucket that fits each coalesced batch.
"""
from __future__ import annotations

import json
import os
import re
import shutil

import numpy as np

# the artifact layout contract lives in serve.py (the loader); export
# writes exactly what serve reads
from .serve import (_SIGNATURE, _MODULE, _BUCKET_DIR, _TIER_INT8,
                    _TRAIN_SIGNATURE, _TRAIN_MODULE, _TRAIN_STATE0,
                    _DECODE_WEIGHTS, _AOT_SIDECAR, span as _span, _aot_platform, _precompile_infer_dir,
                    _precompile_train_dir)


def _should_precompile(precompile):
    """Export-time AOT sidecars default ON (PTPU_EXPORT_PRECOMPILE=0 opts
    out): the exporting host pays one XLA compile per bucket so every
    serving replica that loads the artifact pays none."""
    if precompile is not None:
        return bool(precompile)
    return os.environ.get('PTPU_EXPORT_PRECOMPILE', '1') not in ('0',
                                                                 'false')


def _try_precompile(out_dir, train=False):
    """Best-effort sidecar write: a backend without executable
    serialization must never fail the export itself."""
    import warnings
    try:
        if train:
            _precompile_train_dir(out_dir)
        else:
            _precompile_infer_dir(out_dir)
    except Exception as e:
        warnings.warn(
            'export: could not precompile a warm-start sidecar for %s '
            '(%s: %s); the artifact still serves through the normal '
            'compile path' % (out_dir, type(e).__name__, e),
            RuntimeWarning)


def _normalize_lod_sample(name, value, lod_level):
    """Normalize a LoD feed sample to (data ndarray, [int32 offsets per
    level]). Accepts a LoDArray/LoDTensor or a (values, lod) pair where
    lod is nested offsets (or a flat list for one level)."""
    from ..core.lod import LoDArray
    if isinstance(value, LoDArray):
        data = np.asarray(value.data)
        offs = [np.asarray(value.off_t(i)) for i in range(value.nlevels)]
    elif isinstance(value, tuple) and len(value) == 2:
        data, lod = value
        data = np.asarray(data)
        if isinstance(lod, np.ndarray):
            lod = [lod] if lod.ndim == 1 else list(lod)
        elif len(lod) and np.isscalar(lod[0]):
            lod = [lod]
        offs = [np.asarray(l) for l in lod]
    else:
        raise ValueError(
            "feed %r has lod_level=%d: pass a LoDTensor "
            "(fluid.create_lod_tensor) or a (values, offsets) pair"
            % (name, lod_level))
    if len(offs) != lod_level:
        raise ValueError("feed %r: expected %d lod level(s), got %d"
                         % (name, lod_level, len(offs)))
    return data, [o.astype(np.int32).reshape(-1) for o in offs]


def export_compiled(predictor, sample_inputs, out_dir, batch_sizes=None,
                    precompile=None, quantize=None, calibration=None,
                    quantize_mode='abs_max', calibration_q=99.9):
    """Export `predictor`'s program as a tracer-free compiled artifact.

    sample_inputs: list (feed order) or dict of arrays fixing shapes and
    dtypes. LoD feeds take a LoDTensor or (values, offsets) pair; they
    export in TRACED-lod form (core/lod.py), so the artifact carries the
    offsets as runtime inputs and one export serves every batch of the
    same BUCKET shape (rows, nseq) — export one artifact per bucket, the
    same discipline the Executor's lod-generic cache uses. LoD fetches
    come back as (values, offsets...) with the levels recorded in
    signature.json (the reference's PaddleTensor.lod contract,
    inference/api/paddle_api.h:1).

    batch_sizes: optional list of batch buckets (e.g. [1, 8, 32, 128]) for
    a MULTI-BUCKET artifact (dense feeds only): the program is exported
    once per bucket into out_dir/bucket_<n>/, the top level mirrors the
    largest bucket (backward-compatible with CompiledPredictor), and the
    top signature records the bucket list for batching.BatchingPredictor.

    precompile: write AOT warm-start sidecars (serve.py _AOT_SIDECAR) per
    bucket for the exporting host's platform, so loaders skip the
    first-request XLA compile. Default: on (PTPU_EXPORT_PRECOMPILE=0
    opts out); other platforms prewarm with `tools/cache_ctl.py prewarm`.

    quantize='int8' (ISSUE 11): ALSO write a post-training-quantized
    bucket tier under out_dir/int8/ — a complete artifact tree (same
    buckets, own AOT sidecars) whose program went through
    passes/quantize.py: calibrated per-tensor activation quant +
    per-channel int8 weights, dequant fused into consumers. `calibration`
    is required: a list of representative feed batches (dicts, or lists
    in feed order) swept through the executor to observe activation
    ranges; `quantize_mode` picks the observer ('abs_max'|'percentile',
    percentile at `calibration_q`). The tier signature carries the full
    calibration metadata INCLUDING every op left in float with its
    machine-checkable reason code; the top-level signature records
    'tiers' so loaders can pick per artifact
    (CompiledPredictor/BatchingPredictor `tier='int8'`). The bf16 tier
    is byte-identical to a quantize=None export.

    Returns out_dir. Load with inference/serve.py (no framework imports).
    """
    feed_names = list(predictor._feed_names)
    if isinstance(sample_inputs, (list, tuple)):
        sample = dict(zip(feed_names, sample_inputs))
    else:
        sample = dict(sample_inputs)
    missing = [n for n in feed_names if n not in sample]
    if missing:
        raise ValueError("sample_inputs missing feeds: %r" % missing)
    program = _optimize_for_export(predictor)
    sizes = None
    if batch_sizes is not None:
        sizes = sorted({int(b) for b in batch_sizes})
        if not sizes or sizes[0] < 1:
            raise ValueError("batch_sizes must be positive ints, got %r"
                             % (batch_sizes,))
        for name in feed_names:
            v = program.global_block().var(name)
            if int(getattr(v, 'lod_level', 0) or 0):
                raise ValueError(
                    "multi-bucket export serves dense feeds only; feed %r "
                    "carries lod — export one artifact per lod bucket "
                    "instead (the Executor's bucket_rows discipline)"
                    % name)
    quant_meta = None
    if quantize is not None:
        if quantize != 'int8':
            raise ValueError("quantize must be None or 'int8', got %r"
                             % (quantize,))
        qprogram, quant_meta = _quantize_for_export(
            predictor, calibration, quantize_mode, calibration_q)
    _export_tier(predictor, program, sample, out_dir, sizes, precompile)
    if quantize is None:
        # a re-export WITHOUT quantize must not leave a previous export's
        # int8 tier behind: resolve_tier would serve the STALE quantized
        # weights against the fresh bf16 artifact with no error. A
        # signature-less partial tier (interrupted export) is dead
        # weight either way — remove it too.
        stale = os.path.join(out_dir, _TIER_INT8)
        if os.path.isdir(stale):
            import warnings
            warnings.warn(
                'export_compiled: removing stale int8 tier %s (this '
                "export did not request quantize='int8')" % stale,
                RuntimeWarning)
            shutil.rmtree(stale)
        return out_dir
    tier_sig = {'tier': 'int8', 'quantization': quant_meta}
    _export_tier(predictor, qprogram, sample,
                 os.path.join(out_dir, _TIER_INT8), sizes, precompile,
                 extra_sig=tier_sig)
    # record the tier inventory + calibration audit at the top level so
    # a loader (or a fleet operator) discovers the quantized tier without
    # probing subdirectories
    sig_path = os.path.join(out_dir, _SIGNATURE)
    with open(sig_path) as f:
        sig = json.load(f)
    sig['tiers'] = ['bf16', 'int8']
    sig['quantization'] = quant_meta
    with open(sig_path, 'w') as f:
        json.dump(sig, f, indent=1)
    return out_dir


def _export_tier(predictor, program, sample, out_dir, sizes,
                 precompile, extra_sig=None):
    """Write one complete artifact tree for `program`: single artifact
    when `sizes` is None, else the multi-bucket tree (bucket_<n>/ per
    size, top level mirroring the LARGEST bucket, top signature carrying
    the bucket list)."""
    feed_names = list(predictor._feed_names)
    if sizes is None:
        return _export_single(predictor, sample, out_dir, program=program,
                              precompile=precompile, extra_sig=extra_sig)
    arrs = {n: np.asarray(sample[n]) for n in feed_names}
    flat = [n for n, a in arrs.items() if a.ndim < 1]
    if flat:
        raise ValueError("feeds %r have no batch dimension to bucket on"
                         % flat)
    lead = {a.shape[0] for a in arrs.values()}
    if len(lead) != 1:
        raise ValueError(
            "multi-bucket export needs one uniform leading batch dim; "
            "sample feeds disagree: %s" % sorted(lead))
    os.makedirs(out_dir, exist_ok=True)
    for b in sizes:
        # np.resize tiles the sample rows up/down to the bucket — only
        # shapes and dtypes matter for the export trace
        resized = {n: np.resize(a, (b,) + a.shape[1:])
                   for n, a in arrs.items()}
        _export_single(predictor, resized,
                       os.path.join(out_dir, _BUCKET_DIR % b),
                       program=program, precompile=precompile,
                       extra_sig=extra_sig)
    # top level mirrors the LARGEST bucket so CompiledPredictor(out_dir)
    # keeps working unchanged on a multi-bucket dir
    top = os.path.join(out_dir, _BUCKET_DIR % sizes[-1])
    top_module = os.path.join(out_dir, _MODULE)
    if os.path.exists(top_module):
        os.remove(top_module)
    try:  # params are baked in: the module can be ~100MB — link, not copy
        os.link(os.path.join(top, _MODULE), top_module)
    except OSError:  # cross-device or no-hardlink filesystem
        shutil.copyfile(os.path.join(top, _MODULE), top_module)
    # the largest bucket's AOT sidecar serves the mirrored top module too
    # (same module bytes; the sidecar validates by content hash)
    side = _AOT_SIDECAR % _aot_platform()
    if os.path.exists(os.path.join(top, side)):
        top_side = os.path.join(out_dir, side)
        if os.path.exists(top_side):
            os.remove(top_side)
        try:
            os.link(os.path.join(top, side), top_side)
        except OSError:
            shutil.copyfile(os.path.join(top, side), top_side)
    with open(os.path.join(top, _SIGNATURE)) as f:
        sig = json.load(f)
    sig['buckets'] = sizes
    with open(os.path.join(out_dir, _SIGNATURE), 'w') as f:
        json.dump(sig, f, indent=1)
    return out_dir


def _quantize_for_export(predictor, calibration, mode, q):
    """Calibrate + quantize the predictor's program for the int8 tier.
    Returns (optimized quantized program, signature metadata). The sweep
    runs through the predictor's OWN executor and scope (the 'existing
    executor' calibration path, PAPER.md §6); the quantized program then
    goes through the standard inference pass pipeline, so constant
    folding/DCE/act-fusion apply to the int8 form exactly as to the
    float one."""
    from .. import passes
    if not calibration:
        raise ValueError(
            "quantize='int8' requires calibration=[feed batches...]: a "
            "representative sweep is what defines the activation scales "
            "(passes/quantize.calibrate_program)")
    feed_names = list(predictor._feed_names)
    fetch_names = [v.name for v in predictor._fetch_vars if v is not None]
    batches = []
    for b in calibration:
        batches.append(dict(zip(feed_names, b))
                       if isinstance(b, (list, tuple)) else dict(b))
    calib = passes.calibrate_program(
        predictor._program, batches, predictor._exe,
        scope=predictor._scope, q=q)
    qprog, report = passes.quantize_program(
        predictor._program, calib, predictor._scope, mode=mode,
        fetch_names=fetch_names, feed_names=feed_names)
    try:
        qprog, _ = passes.apply_inference_pipeline(
            qprog, fetch_names=fetch_names, feed_names=feed_names)
    except passes.ProgramVerifyError:
        raise
    except Exception as e:
        import warnings
        warnings.warn(
            "int8 tier optimization pipeline failed (%s: %s); exporting "
            "the unoptimized quantized program"
            % (type(e).__name__, e), RuntimeWarning)
    d = report.details
    meta = {'method': 'post_training_int8', 'mode': d['mode'],
            'percentile_q': float(q), 'calibration_batches': len(batches),
            'quantized_ops': d['quantized_ops'],
            'float_ops': d['float_ops'],
            'float_op_reasons': d['float_op_reasons'],
            'act_scales': d['act_scales'],
            'weight_bytes_before': d['weight_bytes_before'],
            'weight_bytes_after': d['weight_bytes_after']}
    return qprog, meta


def _decode_mesh(axes, platform=None):
    """Build the compile mesh for a sharded decode export. Delegates to
    the load-time reconstruction in decoding.py — ONE copy of the
    device-ordering rule, so an exported artifact can never place
    differently at serve time."""
    from . import decoding as _decoding
    return _decoding._decode_mesh(axes, platform)


def _mesh_tag(platform, axes):
    """Mesh-tagged AOT sidecar key: aot_<platform>_<axes>.jaxexec (e.g.
    aot_cpu_mp2.jaxexec) — a sharded executable must never load into an
    unsharded serve (or a different mesh shape), so the tag carries the
    axis layout next to the platform."""
    return '%s_%s' % (platform, ''.join(
        '%s%d' % (a, int(axes[a])) for a in sorted(axes)))


def _decode_shard_ctx(spec, state_names, params, param_args, platform=None):
    """Resolve the spec's mesh annotations into concrete NamedShardings:
    returns None for unsharded specs, else {mesh, rep, state_ns (aligned
    with state_names), param_specs ({name: partition spec} of the
    parameter ARGUMENTS), param_ns (the same as NamedShardings, aligned
    with param_args; a pack is replicated), constrain ({name:
    NamedSharding} applied inside the trace), axes, tag}. A parameter
    whose annotated axis does not divide its dimension cannot be an
    argument in that sharding (jit refuses an uneven argument): it enters
    replicated and is constrained inside the program, where GSPMD pads."""
    axes = spec.get('mesh_axes')
    if not axes:
        return None
    from jax.sharding import NamedSharding, PartitionSpec
    from .decoding import _arg_name, _state_shardings_ns
    mesh = _decode_mesh(axes, platform)
    rep, state_ns = _state_shardings_ns(
        mesh, spec.get('state_shardings'), state_names)
    param_specs, constrain = {}, {}
    for n, ps in (spec.get('param_shardings') or {}).items():
        if n not in params:
            continue
        even = all(a is None or dim % int(axes[a]) == 0
                   for dim, a in zip(np.shape(params[n]), ps))
        if even:
            param_specs[n] = tuple(ps)
        else:
            constrain[n] = NamedSharding(mesh, PartitionSpec(*ps))
    _, param_ns = _state_shardings_ns(
        mesh, param_specs, [_arg_name(a) for a in param_args])
    plat = np.asarray(mesh.devices).reshape(-1)[0].platform
    return {'mesh': mesh, 'rep': rep, 'state_ns': state_ns,
            'param_specs': param_specs, 'param_ns': param_ns,
            'constrain': constrain, 'axes': dict(axes),
            'platform': plat, 'tag': _mesh_tag(plat, axes)}


def _param_args(params, sharded):
    """How the weights become program arguments, as lists of names: every
    rank-1 parameter (norm weights, biases) of one dtype that no mesh
    sharding names rides in ONE 1-D argument, its members end to end —
    a dispatch pays for each argument buffer it passes (about 1.3 us
    each on the v5e's host, PERF.md PR 26), a model has as many such
    vectors as matrices, and a slice of a small vector costs nothing on
    the device. Every other parameter is an argument of its own: a slice
    of a stacked matrix would be copied out at every step."""
    packs = {}
    for n in sorted(params):
        if np.ndim(params[n]) == 1 and n not in sharded:
            packs.setdefault(np.dtype(params[n].dtype).name, []).append(n)
    args = [names for _, names in sorted(packs.items()) if len(names) > 1]
    packed = {n for names in args for n in names}
    return args + [[n] for n in sorted(params) if n not in packed]


def export_decode(spec, out_dir, scope=None, precompile=None,
                  kv_cache_dtype=None):
    """Export a continuous-decode serving artifact (ISSUE 8) over the
    block-paged KV cache (ISSUE 13).

    `spec` is the dict a decode model builder produces (e.g.
    models/transformer.build_decode_spec):

      startup      Program that initializes every shared parameter and
                   zeroes the KV cache state — run it in `scope` BEFORE
                   exporting.
      step         {'program', 'feeds', 'samples', 'fetches'}: the
                   decode-step program. Feeds must be named exactly
                   'tokens' [max_slots, 1] int64, 'pos' [max_slots, 1]
                   int32 and 'block_tables' [max_slots, max_blocks]
                   int32; 'fetches' names ONE var, the per-slot float32
                   logits [max_slots, vocab].
      chunk        {chunk_size: {...}}: one chunked-prefill program per
                   chunk size. Feeds must be named 'chunk_ids'
                   [1, chunk] int64, 'start' [1, 1] int32, 'chunk_len'
                   [1, 1] int32, 'block_table' [1, max_blocks] int32;
                   'fetches' names the last-real-position logits
                   [1, vocab].
      chunk_rows   (optional) {..., 'size': C, 'rows': R}: the LARGEST
                   chunk once more with a leading row dimension — the
                   same feeds at [R, C] / [R, 1] / [R, max_blocks],
                   logits [R, vocab] — so that the slices R DIFFERENT
                   admitting requests have due in one scheduler tick go
                   to the device in one dispatch. A builder adds it
                   where the shapes give one (models/decode_spec.py
                   `build`: at most 512 prompt tokens and 4 rows
                   a dispatch, the chunk attention over the gathered
                   view — as many K/V heads as query heads, no window —
                   with the rows' scores in its budget: chunks (32, 128)
                   -> 128 x 4, a largest chunk of 512 -> none).
      cache_vars   persistable KV-cache state vars present in every
                   program: the pool ([num_blocks, block_size, ...]),
                   addressed through the block tables fed at dispatch
                   time.
      cache_kind   (optional) what a pool's row is where it is not a K
                   or a V row: 'latent' (ONE pool a layer whose row is key
                   and value both; models/decode_spec.py). Written into
                   the signature's block entry; the loader's stats report
                   the pools' bytes under it.
      block_size / num_blocks / max_blocks_per_slot /
      max_slots / max_cache_len / eos_id / vocab.
      window       (optional) {'length', 'num_blocks', 'cache_vars'}:
                   the cache vars of sliding-window layers, which attend
                   the last `length` positions only. They form a pool of
                   their own ([num_blocks, block_size, ...], at least
                   full capacity: max_slots x kv_blocks.
                   window_blocks_per_slot + the trash block) addressed
                   through a SECOND table the scheduler keeps per
                   request and from which it drops what the window has
                   passed: the step then also feeds 'window_tables'
                   [max_slots, max_blocks] and a chunk 'window_table'
                   [1, max_blocks]. Every other cache var is a full
                   layer's. Such a spec has no verify program, and the
                   loader refuses beams and prefix reuse on it by name.
      shared_pools (optional) {reader layer: owner layer}: layers that
                   keep no cache and attend the owner's pools
                   (models/decode_spec.py); written into the signature,
                   where DecodeStats reads how many readers there are.
                   Not beside a verify program or a mesh.
      recurrent    (optional) {'cache_vars'}: the state vars of RECURRENT
                   layers (a linear-attention layer's rule state and
                   convolution tail: models/decode_spec.py), which are no
                   pool — [max_slots, ...], one row a SLOT, no block, no
                   table. The step's row r is slot r and touches its
                   state only where its table is not the idle row's; a
                   chunk then also feeds 'state_slot' [1, 1] int32, the
                   slot its row prefills (outside [0, max_slots): nobody,
                   nothing is written). No block pair copies them. Such a
                   spec has no verify and no row program, and the loader
                   refuses beams and prefix reuse on it by name.

    Every program is traced ONCE as fn(params, state, feeds) ->
    (fetches, new_state). The exported program returns TWO fetches
    (signature 'fetches': ['ids', <the logits var>]): fetch 0 is `ids`,
    int32, the argmax of the logits over the vocabulary — [max_slots]
    for the step, [max_slots, K+1] for verify, [1] for a chunk ([R] for
    the row program) — and
    fetch 1 is the float32 logits the spec names, untouched.
    The argmax is appended HERE, at the one place every program of every
    model is traced (_export_decode_program), over the same float32
    values and with np.argmax's tie rule (the lowest index), so the
    scheduler copies max_slots x 4 bytes a step and chooses no token on
    the host; it copies the logits only for a dispatch in which a beam
    row is live. `params` is every persistable any program
    reads, in one sorted list that is the same for all of them,
    UNDONATED: no module holds a weight as a constant, the artifact
    holds one copy of the weights (decode_weights.bin: raw bytes, mapped
    by the signature's 'params' entries {name, shape, dtype, offset,
    nbytes}) and the loader one set of device buffers that step, chunk
    and verify share. The cache state threads through as
    donated inputs/outputs. The artifact also carries a BLOCKCOPY
    program (up to max_slots (dst, src) block pairs copy per dispatch —
    beam copy-on-write moves diverged BLOCKS; a beam's history move
    itself is a table permutation on the host), a ZEROS program (the
    cache state born on the device: XLA-owned buffers, the pool held
    once, no host copy) and per-program AOT warm-start sidecars, the
    model's programs compiled WITH state donation (the paged cache updates in place; the loader
    passes only XLA-owned buffers, the executor's round-10 ownership
    discipline). Every program's state ends in the IDS ROW, [max_slots]
    int32 and no program variable: the step writes its ids there and
    takes a row's input token from it where the `tokens` feed is
    negative, a chunk takes a `slot` feed beside the spec's and writes
    its id into that entry where the slice is its prompt's last
    (_export_decode_program) — the scheduler dispatches a step before it
    has read the one before. The signature is version 6: weights as
    arguments (since 4), `ids` as fetch 0 beside the logits (5), the ids
    row (6); the loader refuses an older artifact by name.

    Artifact layout (out_dir/):
      decode_signature.json   shapes, chunk sizes, params and state
                              specs, the pool's geometry, eos/vocab
      decode_weights.bin      the one copy of the weights
      decode_step/            module.jaxexport (+ aot_<platform>.jaxexec)
      decode_zeros/           the state's birth
      prefill_chunk_<C>/      one per chunk size
      prefill_chunk_<C>x<R>/  the row program, where the spec has one:
                              `slot` [R, 1], ids [R], logits [R, V];
                              listed under the signature's 'chunk_rows'
                              (an artifact without it serves one slice a
                              dispatch, as every artifact did)
      decode_blockcopy/       block-pair copy program (CoW)

    kv_cache_dtype='int8' (ISSUE 11): assert-and-record that the spec
    was built with the quantized paged cache (build_decode_spec's
    kv_cache_dtype) — the int8 pages + per-position f32 scales thread
    through as state like any other cache var, halving cache HBM so the
    same budget serves ~2x max_slots. The signature records the dtype
    and the per-state byte accounting for capacity planning.

    Specs annotated for tensor-model sharding (build_decode_spec
    mp_shard=k) trace every program over the composed mesh: the
    parameter ARGUMENTS are pinned to their annotated shardings (one
    whose axis does not divide its dimension enters replicated and is
    constrained inside the program), the loader places each weight in
    its recorded sharding, the KV block pool threads through as
    mp-sharded donated state (round-13 output-sharding pinning keeps
    the step a sharding-stable loop), and AOT sidecars are MESH-TAGGED
    (aot_<platform>_mp<k>.jaxexec). The signature records the mesh so
    DecodingPredictor rebuilds it at load; serving needs prod(axes)
    devices. Sharded artifacts are single-platform (the exporting
    backend).

    Speculative-decode specs (ISSUE 17, build_decode_spec(draft_k=K))
    export a THIRD program, decode_verify/: [max_slots, K+1] token and
    position rows score in one dispatch over the same donated cache
    state, with its own AOT warm-start sidecar. The signature gains an
    optional 'verify' block ({feeds, fetches, draft_k}); an artifact
    without one serves without speculative decode.

    Load with inference/decoding.py DecodingPredictor (framework-free).
    Returns out_dir.
    """
    import jax
    from .. import global_scope
    from . import decoding as _decoding

    spec_kv = spec.get('kv_cache_dtype', 'float32')
    if kv_cache_dtype is not None and kv_cache_dtype != spec_kv:
        raise ValueError(
            "export_decode(kv_cache_dtype=%r) but the spec was built "
            "with kv_cache_dtype=%r — rebuild the decode spec with the "
            "requested cache dtype (build_decode_spec(kv_cache_dtype=...))"
            % (kv_cache_dtype, spec_kv))
    if spec.get('shared_pools') and (spec.get('verify') is not None
                                     or spec.get('mesh_axes')
                                     or spec_kv == 'int8'):
        raise ValueError('a spec with a shared pool has no verify program, '
                         'no mesh and no int8 pool: their ops and '
                         'partitions take a layer\'s own pools')
    scope = scope if scope is not None else global_scope()
    state_names = list(spec['cache_vars'])
    state_specs = []
    for n in state_names:
        val = scope.get(n)
        if val is None:
            raise ValueError(
                "cache var %r has no value in the scope — run the spec's "
                "startup program before export_decode" % n)
        # shape and dtype only: the pool itself never leaves the device
        state_specs.append(jax.ShapeDtypeStruct(np.shape(val), val.dtype))
    step = spec['step']
    window = spec.get('window')
    step_want = ['block_tables', 'pos', 'tokens']
    chunk_want = ['block_table', 'chunk_ids', 'chunk_len', 'start']
    if window is not None:
        _check_window(spec, state_names, state_specs)
        step_want = sorted(step_want + ['window_tables'])
        chunk_want = sorted(chunk_want + ['window_table'])
    recurrent = spec.get('recurrent')
    if recurrent is not None:
        _check_recurrent(spec, state_names, state_specs)
        chunk_want = sorted(chunk_want + ['state_slot'])
    if sorted(step['feeds']) != step_want:
        raise ValueError("decode-step feeds must be %r, got %r"
                         % (step_want, step['feeds']))
    # every program of the artifact, by its directory
    entries = {_decoding._STEP_DIR: step}
    roles = {_decoding._STEP_DIR: 'step'}
    verify = spec.get('verify')
    if verify is not None:
        # ISSUE 17: third program — same feed NAMES as the step (the
        # verify tick is a step with R = draft_k + 1 rows per slot)
        if sorted(verify['feeds']) != step_want:
            raise ValueError("decode-verify feeds must be %r, got %r"
                             % (step_want, verify['feeds']))
        entries[_decoding._VERIFY_DIR] = verify
        roles[_decoding._VERIFY_DIR] = 'verify'
    chunks = sorted(int(c) for c in spec['chunk'])
    if not chunks:
        raise ValueError("export_decode needs at least one chunk size")
    for C in chunks:
        p = spec['chunk'][C]
        if sorted(p['feeds']) != chunk_want:
            raise ValueError("chunk feeds must be %r, got %r"
                             % (chunk_want, p['feeds']))
        entries[_decoding._CHUNK_DIR % C] = p
        roles[_decoding._CHUNK_DIR % C] = 'chunk'
    rows = spec.get('chunk_rows')
    if rows is not None:
        # the one chunk program with a row dimension: its largest chunk
        # at [R, C], the same feed names
        if sorted(rows['feeds']) != chunk_want \
                or int(rows['size']) != chunks[-1]:
            raise ValueError(
                "spec['chunk_rows'] must be the largest chunk (%d) with "
                'feeds %r, got size %r, feeds %r'
                % (chunks[-1], chunk_want, rows['size'], rows['feeds']))
        rows_dir = _decoding._CHUNK_ROWS_DIR % (int(rows['size']),
                                                int(rows['rows']))
        entries[rows_dir] = rows
        roles[rows_dir] = 'chunk'
    programs = {d: _optimize_decode_program(e, state_names)
                for d, e in entries.items()}
    # the ONE parameter list every program takes, in this order
    params = _decode_params(programs.values(), state_names, scope)
    param_args = _param_args(params, set(spec.get('param_shardings') or ()))
    # (the ids row, named by no sharding, is replicated on a mesh)
    shard = _decode_shard_ctx(spec, state_names + [_IDS_ROW], params,
                              param_args)
    os.makedirs(out_dir, exist_ok=True)
    param_sig = _write_decode_weights(
        os.path.join(out_dir, _DECODE_WEIGHTS), param_args, params)
    param_specs = _decoding.param_arg_specs(param_sig, param_args)
    del params
    # the ids row rides LAST in every program's state: no program
    # variable, copied by no block pair
    uncopied = set((window or {}).get('cache_vars', ())) | set(
        (recurrent or {}).get('cache_vars', ()))
    copied = [n not in uncopied for n in state_names] + [False]
    state_names = state_names + [_IDS_ROW]
    state_specs = state_specs + [jax.ShapeDtypeStruct(
        (int(spec['max_slots']),), np.int32)]

    sigs = {}
    for d, e in entries.items():
        with _span('export/program', program=d, params=len(param_sig)):
            sigs[d] = _export_decode_program(
                e, programs[d], param_args, param_specs, state_names,
                state_specs, os.path.join(out_dir, d), shard=shard,
                role=roles[d])
    _export_decode_blockcopy(
        state_specs, int(spec['max_slots']),
        os.path.join(out_dir, _decoding._BLOCKCOPY_DIR), shard=shard,
        copied=copied)
    _export_decode_zeros(state_specs,
                         os.path.join(out_dir, _decoding._ZEROS_DIR),
                         shard=shard)

    state_bytes = [int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in state_specs]
    # version 4: the weights are ARGUMENTS of every program (one
    # decode_weights.bin, listed under 'params'); up to version 3 each
    # program's module held them as constants. Version 5: every program
    # returns the argmax ids as fetch 0 beside its logits. Version 6: the
    # state's last entry is the ids row. 'layout' is
    # what the loader refuses an artifact without (the slot tier's)
    sig = {'version': _decoding._SIG_VERSION, 'kind': 'decode',
           'layout': 'block',
           'max_slots': int(spec['max_slots']),
           'max_cache_len': int(spec['max_cache_len']),
           'eos_id': int(spec['eos_id']), 'vocab': int(spec['vocab']),
           'kv_cache_dtype': spec_kv,
           # fixed-HBM capacity planning: what the paged cache state
           # costs per replica (int8 tier: int8 pages + f32 page scales)
           'cache_bytes': int(sum(state_bytes)),
           'state': [{'name': n, 'shape': list(s.shape),
                      'dtype': s.dtype.name}
                     for n, s in zip(state_names, state_specs)],
           'params': param_sig,
           'param_args': param_args,
           'weight_bytes': int(sum(e['nbytes'] for e in param_sig)),
           'step': sigs[_decoding._STEP_DIR],
           'block': {'block_size': int(spec['block_size']),
                     'num_blocks': int(spec['num_blocks']),
                     'max_blocks_per_slot':
                         int(spec['max_blocks_per_slot'])},
           'chunk_buckets': chunks,
           'chunk': {str(C): sigs[_decoding._CHUNK_DIR % C]
                     for C in chunks}}
    if rows is not None:
        # a key of its own: a loader from before the row program reads
        # 'chunk_buckets' / 'chunk' and serves one slice a dispatch
        sig['chunk_rows'] = dict(sigs[rows_dir], size=int(rows['size']),
                                 rows=int(rows['rows']))
    if verify is not None:
        sig['verify'] = dict(sigs[_decoding._VERIFY_DIR],
                             draft_k=int(spec['draft_k']))
    if spec.get('cache_kind'):
        # what a pool's row IS, where it is not a K or a V row: 'latent'
        sig['block']['cache_kind'] = str(spec['cache_kind'])
    if window is not None:
        sig['block']['window'] = {
            'length': int(window['length']),
            'num_blocks': int(window['num_blocks']),
            'cache_vars': list(window['cache_vars'])}
    if recurrent is not None:
        sig['block']['recurrent'] = {
            'cache_vars': list(recurrent['cache_vars'])}
    if spec.get('shared_pools'):
        # layers that keep nothing and attend another layer's pools
        # ({reader: owner}): the state list above holds the owner's once
        sig['block']['shared_pools'] = {
            str(i): int(o) for i, o in sorted(spec['shared_pools'].items())}
    if shard is not None:
        sig['mesh'] = {'axes': {a: int(n) for a, n in
                                shard['axes'].items()},
                       'platform': shard['platform'],
                       'tag': shard['tag'],
                       'state_shardings':
                           {n: list(ps) for n, ps in
                            (spec.get('state_shardings') or {}).items()},
                       'param_shardings':
                           {n: list(ps) for n, ps in
                            shard['param_specs'].items()}}
    with open(os.path.join(out_dir, _decoding._DECODE_SIGNATURE), 'w') as f:
        json.dump(sig, f, indent=1)
    if _should_precompile(precompile):
        import warnings
        try:
            _decoding.precompile_decode_artifact(out_dir)
        except Exception as e:
            warnings.warn(
                'export_decode: could not precompile warm-start sidecars '
                'for %s (%s: %s); the artifact still serves through the '
                'normal compile path' % (out_dir, type(e).__name__, e),
                RuntimeWarning)
    return out_dir


def _shard_trace_ctx(shard):
    """Trace-time context for a sharded decode export: the spec's
    sharding_hint ops resolve against the mesh via the round-13
    trace_mesh_scope machinery. Null context when unsharded."""
    import contextlib
    if shard is None:
        return contextlib.nullcontext()
    from ..parallel.mesh import trace_mesh_scope
    return trace_mesh_scope(shard['mesh'])


def _export_serialize(fn, in_specs, out_dir, shard=None, in_shardings=None,
                      out_shardings=None):
    """jit + jax.export one decode program and write its module. An
    unsharded program exports cross-platform (cpu+tpu); a sharded one is
    single-platform (the mesh's) with its arguments and results pinned
    to `in_shardings` / `out_shardings` — the state input AND output to
    its annotated shardings, the round-13 fixed-point discipline that
    keeps the step a sharding-stable loop under the AOT warm path."""
    import jax
    from jax import export as jexport
    # the program's name in a device trace (jit_decode_step,
    # jit_prefill_chunk_32, ...): its artifact directory's, unpadded
    fn.__name__ = fn.__qualname__ = re.sub(
        r'_0+(?=\d)', '_', os.path.basename(os.path.normpath(out_dir)))
    if shard is None:
        jitted = jax.jit(fn)
        platforms = ['cpu', 'tpu']
    else:
        jitted = jax.jit(fn, in_shardings=in_shardings,
                         out_shardings=out_shardings)
        platforms = [shard['platform']]
    with _shard_trace_ctx(shard):
        exp = jexport.export(jitted, platforms=platforms)(*in_specs)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, _MODULE), 'wb') as f:
        f.write(exp.serialize())


def _optimize_decode_program(entry, state_names):
    """One decode program through the inference pass pipeline; the
    program as built when the pipeline fails on it."""
    from .. import passes
    try:
        # liveness roots include the cache state: its in-place writes are
        # program outputs even though they are not fetched
        program, _ = passes.apply_inference_pipeline(
            entry['program'],
            fetch_names=list(entry['fetches']) + list(state_names),
            feed_names=list(entry['feeds']))
        return program
    except passes.ProgramVerifyError:
        raise
    except Exception as e:
        import warnings
        warnings.warn(
            "export_decode optimization pipeline failed (%s: %s); "
            "exporting the unoptimized program" % (type(e).__name__, e),
            RuntimeWarning)
        return entry['program']


def _decode_params(programs, state_names, scope):
    """{name: scope value} of every persistable that any of `programs`
    reads and that is not cache state: the artifact's weights."""
    from ..core.lod import LoDArray
    state_set = set(state_names)
    params = {}
    for program in programs:
        for v in program.list_vars():
            if (v.persistable and v.name not in state_set
                    and v.name not in params):
                val = scope.get(v.name)
                if val is not None:
                    params[v.name] = (val.data if isinstance(val, LoDArray)
                                      else val)
    return params


def _write_decode_weights(path, param_args, params):
    """The artifact's ONE copy of the weights: each argument's bytes in
    `param_args` order, each argument starting on a 64-byte boundary and
    a pack's members end to end inside it. Returns the signature's
    'params' list ({name, shape, dtype, offset, nbytes}), which with
    'param_args' is all the loader needs to map the file."""
    out = []
    off = 0
    with open(path, 'wb') as f:
        for names in param_args:
            pad = -off % 64
            f.write(b'\0' * pad)
            off += pad
            for n in names:
                a = np.ascontiguousarray(np.asarray(params[n]))
                f.write(a.tobytes())
                out.append({'name': n, 'shape': list(a.shape),
                            'dtype': a.dtype.name, 'offset': off,
                            'nbytes': int(a.nbytes)})
                off += a.nbytes
    return out


# the last entry of every decode program's state: [max_slots] int32, the
# id each slot's row chose last (see _export_decode_program)
_IDS_ROW = 'decode_ids_row'


def _export_decode_program(entry, program, param_args, param_specs,
                           state_names, state_specs, out_dir, shard=None,
                           role='step'):
    """Trace one decode program as fn(params, state, feeds) -> (fetches,
    new_state) — export_train_step's state-threading convention minus
    the rng (decode programs draw no randomness) — and serialize it.
    `params` is the artifact's whole weight list as `param_args` groups
    it (_param_args: the small vectors packed, every other parameter by
    itself), the same for every program (one that reads only some
    ignores the rest), UNDONATED: the loader holds one set of device buffers and
    hands it to step, chunk and verify alike, and no module holds a
    weight as a constant. `state` threads through donated.
    With `shard` (_decode_shard_ctx), the trace runs over the composed
    mesh: the parameter ARGUMENTS are pinned to their annotated
    shardings (so the weights genuinely partition across the mesh), the
    KV state threads through mp-sharded input->output (fixed-point
    pinned), and feeds/fetches stay replicated (the host scheduler sees
    full arrays). The program's fetches are `ids` — int32, the argmax
    over the last axis of the logits (the spec's first fetch), lowest
    index on ties — and then what the spec fetches: the one site where
    greedy token selection enters every program of every model.

    The same site hands each row's token from dispatch to dispatch ON THE
    DEVICE. The state's last entry (_IDS_ROW, [max_slots] int32; no
    program variable, replicated on a mesh) holds the id each slot chose
    last, and `role` says what a program does with it. The 'step' writes
    its ids there and, in front of the embedding, takes a row's input
    token from it wherever the `tokens` feed holds a NEGATIVE value —
    the feed's sign is the per-row mask: a token >= 0 is one only the
    host knows (a beam's, an accepted draft's, a caller's own). A
    'chunk' takes one feed more, `slot` [1, 1] int32, and writes the id
    its last real position chose into that slot's entry — the slice is
    its prompt's last and the request decodes from the next step without
    the host having seen its first token — or nothing where `slot` is
    negative; the row program's `slot` is [R, 1] and the write a scatter
    over the rows whose slot is not negative. 'verify' threads the row
    through (its rows' tokens are the host's).

    Returns the program's signature entries: its 'feeds' (a chunk's
    with `slot`), its 'fetches' by name,
    and under 'attention' the body each of its kv_*attention* ops holds
    where the module is compiled for a TPU, by op type and counted
    ({'kv_block_attention': {'kernel': 6}}) — 'kernel' is the paged
    Pallas kernel, which only kv_block_attention has and reports to its
    Tracer as it lowers (ops/decode_ops.py); every other body, and
    every body on another platform, is the 'jnp' expression over the
    gathered view; a chunk program's entry also says how its
    kv_block_chunk_write ops write ({'pages': 18} | 'rows': ISSUE 54). A
    program with routed layers also has 'experts': the
    body of each moe_topk_ffn's grouped matmuls where the module is
    compiled for a TPU ({'moe_topk_ffn': {'grouped_kernel': 10}} — the
    Pallas weight-streaming kernel, ops/pallas_grouped_matmul.py; or
    'ragged_dot', which is also what every other platform runs)."""
    import jax
    import jax.numpy as jnp
    from ..core.lowering import Tracer

    feed_names = list(entry['feeds'])
    fetch_names = list(entry['fetches'])
    samples = {n: np.asarray(entry['samples'][n]) for n in feed_names}
    n_rows = 0      # of a chunk program: 1, or the row program's R
    if role == 'chunk':
        feed_names.append('slot')     # fed to fn, no program variable
        n_rows = samples['chunk_ids'].shape[0]
        samples['slot'] = np.full((n_rows, 1), -1, np.int32)
    rng = jax.random.key(0)  # decode programs draw no randomness
    constrain = shard['constrain'] if shard is not None else {}
    sizes = {v.name: int(np.prod(v.shape)) for v in program.list_vars()
             if v.persistable}

    def fn(param_list, state_list, feed_list):
        tracer = Tracer(program, rng)
        for names, arg in zip(param_args, param_list):
            if len(names) == 1:
                tracer.env[names[0]] = arg
                continue
            off = 0
            for n in names:     # a pack: its members end to end
                size = sizes[n]
                tracer.env[n] = jax.lax.slice(arg, (off,), (off + size,))
                off += size
        for n, ns in constrain.items():
            tracer.env[n] = jax.lax.with_sharding_constraint(
                tracer.env[n], ns)
        feeds = dict(zip(feed_names, feed_list))
        row = state_list[-1]
        if role == 'step':
            with jax.named_scope('device_tokens'):
                host = feeds['tokens']
                feeds['tokens'] = jnp.where(
                    host < 0, row[:, None].astype(host.dtype), host)
        slot = feeds.pop('slot', None)
        tracer.env.update(dict(zip(state_names[:-1], state_list)))
        tracer.env.update(feeds)
        tracer.run_block(program.global_block())
        lowered[:] = tracer.lowered_bodies
        fetched = [tracer.env[n] for n in fetch_names]
        with jax.named_scope('greedy_ids'):
            ids = jnp.argmax(fetched[0], axis=-1).astype(jnp.int32)
            if role == 'step':
                row = ids
            elif n_rows == 1:
                row = jnp.where(jnp.arange(row.shape[0]) == slot[0, 0],
                                ids[0], row)
            elif n_rows:
                # a scatter over the rows whose slot >= 0: the others
                # (a slice inside a prompt, a pad row) aim
                # past the row's end and are dropped
                at = jnp.where(slot[:, 0] >= 0, slot[:, 0], row.shape[0])
                row = row.at[at].set(ids, mode='drop')
        return ([ids] + fetched,
                [tracer.env[n] for n in state_names[:-1]] + [row])

    lowered = []     # (op type, body) as the export's trace lowered them
    feed_specs = [jax.ShapeDtypeStruct(samples[n].shape, samples[n].dtype)
                  for n in feed_names]
    in_sh = out_sh = None
    if shard is not None:
        in_sh = (list(shard['param_ns']), list(shard['state_ns']),
                 [shard['rep']] * len(feed_names))
        out_sh = ([shard['rep']] * (1 + len(fetch_names)),
                  list(shard['state_ns']))
    _export_serialize(fn, (param_specs, state_specs, feed_specs), out_dir,
                      shard=shard, in_shardings=in_sh, out_shardings=out_sh)
    # ops that chose a body said so as they lowered; the other
    # kv_*attention* ops have the one jnp body
    reported = {op_type for op_type, _ in lowered}
    attention, experts = {}, {}
    for op_type, body in lowered + [
            (op.type, 'jnp') for op in program.global_block().ops
            if re.fullmatch(r'kv_\w*attention\w*', op.type)
            and op.type not in reported]:
        by_body = (experts if op_type == 'moe_topk_ffn'
                   else attention).setdefault(op_type, {})
        by_body[body] = by_body.get(body, 0) + 1
    signature = {'feeds': [{'name': n, 'shape': list(samples[n].shape),
                            'dtype': samples[n].dtype.name}
                           for n in feed_names],
                 'fetches': ['ids'] + fetch_names,
                 'attention': attention}
    if experts:
        signature['experts'] = experts
    return signature


def _export_decode_zeros(state_specs, out_dir, shard=None):
    """Serialize the program that GIVES BIRTH to the cache state: one
    zero array per cache var, made on the device. The loader's state
    therefore starts life as XLA-owned buffers — the only kind that may
    reach a donated reloaded executable — without a host copy of the
    pool and without ever holding the pool twice. Its one argument, an
    int32 [1] it does not read, is there to say WHERE: a call without
    arguments has no devices to take a sharded program's mesh from."""
    import jax
    import jax.numpy as jnp

    def fn(where):
        return [jnp.zeros(s.shape, s.dtype) for s in state_specs]

    _export_serialize(fn, (jax.ShapeDtypeStruct((1,), np.int32),), out_dir,
                      shard=shard,
                      in_shardings=shard and (shard['rep'],),
                      out_shardings=shard and list(shard['state_ns']))


def _check_window(spec, state_names, state_specs):
    """A spec with window layers: its window vars are cache vars of one
    pool shape, the pool holds every slot's worst case (so the scheduler
    never has to shed for it), and nothing the window tables do not
    support rides along."""
    from .kv_blocks import window_blocks_per_slot
    window = spec['window']
    names = list(window['cache_vars'])
    unknown = [n for n in names if n not in state_names]
    if unknown or not names:
        raise ValueError("spec['window']['cache_vars'] must name cache "
                         "vars, got %r" % (names,))
    if spec.get('verify') is not None:
        raise ValueError('a spec with window layers has no speculative '
                         'verify program: kv_block_verify_* know no '
                         'window')
    nbw = int(window['num_blocks'])
    for n in names:
        shape = state_specs[state_names.index(n)].shape
        if shape[0] != nbw or shape[1] != int(spec['block_size']):
            raise ValueError(
                'window cache var %r is %r, not [%d, %d, ...]'
                % (n, tuple(shape), nbw, int(spec['block_size'])))
    need = int(spec['max_slots']) * window_blocks_per_slot(
        window['length'], max(int(c) for c in spec['chunk']),
        spec['block_size']) + 1
    if nbw < need:
        raise ValueError(
            'window pool of %d blocks is under full capacity (%d): the '
            'scheduler does not shed for the window layers' % (nbw, need))


def _check_recurrent(spec, state_names, state_specs):
    """A spec with recurrent layers: their state vars are cache vars with
    one row a slot, and nothing that would need a state forked, rolled
    back or shared rides along."""
    names = list(spec['recurrent']['cache_vars'])
    unknown = [n for n in names if n not in state_names]
    if unknown or not names:
        raise ValueError("spec['recurrent']['cache_vars'] must name cache "
                         "vars, got %r" % (names,))
    if spec.get('verify') is not None:
        raise ValueError('a spec with recurrent layers has no speculative '
                         'verify program: a rejected draft would have to '
                         'roll the state back')
    if spec.get('chunk_rows') is not None:
        raise ValueError('a spec with recurrent layers has no row program')
    if set(names) & set((spec.get('window') or {}).get('cache_vars', ())):
        raise ValueError('a cache var is recurrent or a window layer\'s')
    for n in names:
        shape = state_specs[state_names.index(n)].shape
        if shape[0] != int(spec['max_slots']):
            raise ValueError('recurrent state %r is %r, not [max_slots = '
                             '%d, ...]' % (n, tuple(shape),
                                           int(spec['max_slots'])))


def _export_decode_blockcopy(state_specs, max_pairs, out_dir, copied,
                             shard=None):
    """Serialize the block-copy program: up to `max_pairs` (dst, src)
    PHYSICAL-BLOCK pairs copy per dispatch —
    new_state[i] = state[i].at[dst].set(state[i][src]) for every pool
    var that `copied` marks: all but the window layers', whose blocks
    are never shared (the pairs index the full layers' pool), and a
    recurrent layer's per-slot states, which have no blocks. This is
    beam copy-on-write's device half: the scheduler copies only the
    DIVERGED partial tail blocks of a reordered beam group (and
    pads unused pairs with (0, 0) — a trash-to-trash self-copy), so
    the bytes a reorder moves scale with diverged blocks. Donated at
    load (in-place on the live pool)."""
    import jax

    def fn(state_list, dst, src):
        return [s.at[dst].set(s[src]) if copy else s
                for s, copy in zip(state_list, copied)]

    idx_spec = jax.ShapeDtypeStruct((max_pairs,), np.int32)
    state_ns = shard and list(shard['state_ns'])
    _export_serialize(fn, (state_specs, idx_spec, idx_spec), out_dir,
                      shard=shard,
                      in_shardings=shard and (state_ns, shard['rep'],
                                              shard['rep']),
                      out_shardings=state_ns)


def _optimize_for_export(predictor):
    """Run the optimization pass pipeline (paddle_tpu/passes/) on the
    predictor's program before lowering: constant chains fold, dead
    branches drop, activations fuse into their producers — the exported
    StableHLO traces the optimized graph. Falls back to the raw program
    if the pipeline declines (export must never fail on an optimizer
    bug); strict-verify errors (PTPU_STRICT_VERIFY=1) propagate."""
    from .. import passes
    program = predictor._program
    try:
        program, _ = passes.apply_inference_pipeline(
            program,
            fetch_names=[v.name for v in predictor._fetch_vars
                         if v is not None],
            feed_names=list(predictor._feed_names))
    except passes.ProgramVerifyError:
        raise
    except Exception as e:
        import warnings
        warnings.warn(
            "export optimization pipeline failed (%s: %s); exporting the "
            "unoptimized program" % (type(e).__name__, e), RuntimeWarning)
        program = predictor._program
    return program


def _peak_bytes_est(program, feed_names, fetch_names, feed_sig):
    """Static peak-memory estimate of one export bucket, from the
    dataflow analyzer at the bucket's batch (the sample's leading dim).
    None when estimation declines — the signature must never fail an
    export over an analysis bug."""
    try:
        from ..passes import dataflow as _dataflow
        # the bucket batch = the largest leading dim across the feeds (a
        # rank-1 auxiliary feed like im_shape must not win over the real
        # batched inputs)
        batch = 1
        for e in feed_sig:
            shp = e.get('shape') or ()
            if shp:
                batch = max(batch, int(shp[0]))
        dfa = _dataflow.analyze_program(program, feed_names=feed_names,
                                        fetch_names=fetch_names)
        return int(dfa.peak_memory(batch=batch).peak_bytes)
    except Exception:
        return None


def _export_single(predictor, sample, out_dir, program=None,
                   precompile=None, extra_sig=None):
    """One fixed-shape export (the original export_compiled body);
    `sample` is a {feed name: value} dict covering every feed;
    `extra_sig` entries merge into signature.json (the quantized tier's
    tier/calibration metadata)."""
    import jax
    from jax import export as jexport
    from ..core.lowering import Tracer
    from ..core.lod import LoDArray

    if program is None:
        program = _optimize_for_export(predictor)
    feed_names = list(predictor._feed_names)
    fetch_names = [v.name for v in predictor._fetch_vars]

    # flat calling convention: per feed, data then one int32 offsets array
    # per lod level (traced mode — offsets are runtime data)
    feed_plan = []           # (name, lod_levels)
    flat_specs = []
    feed_sig = []
    for name in feed_names:
        v = program.global_block().var(name)
        ll = int(getattr(v, 'lod_level', 0) or 0)
        if ll:
            data, offs = _normalize_lod_sample(name, sample[name], ll)
            flat_specs.append(jax.ShapeDtypeStruct(data.shape, data.dtype))
            flat_specs.extend(jax.ShapeDtypeStruct(o.shape, np.int32)
                              for o in offs)
            feed_sig.append({'name': name, 'shape': list(data.shape),
                             'dtype': data.dtype.name, 'lod_levels': ll,
                             'lod_sizes': [int(o.shape[0]) for o in offs]})
        else:
            arr = np.asarray(sample[name])
            flat_specs.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
            feed_sig.append({'name': name, 'shape': list(arr.shape),
                             'dtype': arr.dtype.name})
        feed_plan.append((name, ll))

    # parameters / BN stats become baked-in constants
    state = {}
    for v in program.list_vars():
        if v.persistable:
            val = predictor._scope.get(v.name)
            if val is not None:
                state[v.name] = val.data if isinstance(val, LoDArray) else val
    rng = jax.random.key(0)  # inference programs draw no randomness

    def run_env(*flat):
        it = iter(flat)
        tracer = Tracer(program, rng)
        tracer.env.update(state)
        for name, ll in feed_plan:
            data = next(it)
            if ll:
                tracer.env[name] = LoDArray.traced(
                    data, [next(it) for _ in range(ll)])
            else:
                tracer.env[name] = data
        tracer.run_block(program.global_block())
        return tuple(tracer.env[n] for n in fetch_names)

    # the export trace below records which fetches are LoD, with how many
    # levels, and their shapes (serve.py uses fetch shapes to pre-flag
    # row-count-dependent fetches when padding partial dense batches) —
    # the output flattening must be plain arrays (the serving process has
    # no LoDArray class to unflatten into)
    fetch_levels = []
    fetch_shapes = []

    def fn(*flat):
        outs = run_env(*flat)
        del fetch_levels[:]
        del fetch_shapes[:]
        flat_out = []
        for o in outs:
            if isinstance(o, LoDArray):
                fetch_levels.append(o.nlevels)
                fetch_shapes.append(list(o.data.shape))
                flat_out.append(o.data)
                flat_out.extend(o.off_t(i) for i in range(o.nlevels))
            else:
                fetch_levels.append(0)
                fetch_shapes.append(list(np.shape(o)))
                flat_out.append(o)
        return tuple(flat_out)

    # multi-platform artifact: serves on TPU or CPU hosts. Numerics follow
    # the executing platform's matmul precision (MXU bf16-input on TPU,
    # full f32 on CPU) — the same contract the Executor has.
    exp = jexport.export(jax.jit(fn), platforms=['cpu', 'tpu'])(*flat_specs)

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, _MODULE), 'wb') as f:
        f.write(exp.serialize())
    fetch_sig = [{'name': n, 'lod_levels': ll, 'shape': shp}
                 for n, ll, shp in zip(fetch_names, fetch_levels,
                                       fetch_shapes)]
    sig = {'version': 3, 'feeds': feed_sig, 'fetches': fetch_sig}
    est = _peak_bytes_est(program, feed_names, fetch_names, feed_sig)
    if est is not None:
        # static peak-bytes at THIS bucket's batch (passes/dataflow.py):
        # capacity planning reads it per bucket_<n>/signature.json before
        # ever loading the module
        sig['peak_bytes_est'] = est
    if extra_sig:
        sig.update(extra_sig)
    with open(os.path.join(out_dir, _SIGNATURE), 'w') as f:
        json.dump(sig, f, indent=1)
    if _should_precompile(precompile):
        _try_precompile(out_dir)
    return out_dir


def export_train_step(program, sample_inputs, fetch_list, out_dir,
                      scope=None, seed=None, precompile=None):
    """Export a full TRAIN step as a tracer-free compiled artifact.

    The reference can train from a saved program with no Python
    (train/demo_trainer.cc:1, train/test_train_recognize_digits.cc:1); the
    TPU-native counterpart is this: the train step (forward + backward +
    optimizer update) is traced ONCE, with parameters AND optimizer state
    as pytree inputs -> outputs — nothing baked — plus an rng input, and
    serialized with jax.export. The loader (serve.py CompiledTrainer) runs
    steps from numpy state in a process that imports only json/numpy/jax.

    program: the built train program (optimizer already applied).
    sample_inputs: dict name -> array fixing feed shapes/dtypes.
    fetch_list: Variables/names to fetch each step (put the loss here).
    scope: initialized scope (run the startup program first); its
      persistable values become the artifact's initial state
      (train_state0.npz) and define the state signature.
    seed: rng seed recorded in the artifact (default program.random_seed).
      The loader reproduces the Executor's per-step stream:
      fold_in(key(seed, impl), step).

    Artifact files: train_module.jaxexport, train_signature.json,
    train_state0.npz. Returns out_dir.
    """
    import jax
    from jax import export as jexport
    from ..core.lowering import Tracer
    from ..core import amp
    from ..core import config as _config
    from ..core.lod import LoDArray
    from ..executor import _program_analysis
    from ..framework import Variable
    from .. import global_scope

    if int(getattr(program, '_grad_accum_k', 1) or 1) > 1:
        raise ValueError(
            "export_train_step does not support gradient-merge programs; "
            "export the k=1 form and accumulate in the serving loop")
    scope = scope if scope is not None else global_scope()
    sample = dict(sample_inputs)
    feed_names = sorted(sample)
    fetch_names = [f.name if isinstance(f, Variable) else str(f)
                   for f in fetch_list]
    for name in feed_names:
        v = program.global_block()._find_var_recursive(name)
        if v is not None and getattr(v, 'lod_level', 0):
            raise ValueError(
                "export_train_step serves dense tensors only; feed %r is "
                "a LoD tensor" % name)
    for name in fetch_names:
        v = program.global_block()._find_var_recursive(name)
        if v is not None and getattr(v, 'lod_level', 0):
            raise ValueError(
                "export_train_step fetches must be dense; %r carries lod "
                "(the framework-free trainer has no LoD output "
                "convention) — fetch the loss or a dense metric" % name)

    persist, persist_written = _program_analysis(program)
    state = {}
    for name in persist:
        val = scope.get(name)
        if val is not None:
            state[name] = np.asarray(
                val.data if isinstance(val, LoDArray) else val)
    extra = sorted(set(persist_written) - set(state))
    if extra:
        raise ValueError(
            "train-step state %r is written by the program but absent "
            "from the scope — run the startup program before export so "
            "every optimizer slot is materialized" % (extra,))
    state_names = sorted(state)

    amp_on = bool(getattr(program, '_amp_bf16', False))
    rng_impl = _config.rng_impl()
    if seed is None:
        # mirror the Executor's fallback exactly (executor.py run()):
        # an unseeded program under the deterministic flag uses 1234567,
        # otherwise per-process entropy — so in-process bit-match always
        # holds; cross-process an unseeded stream is random by intent
        seed = int(program.random_seed or 0)
        if not seed:
            from ..executor import _process_entropy
            seed = (1234567 if _config.get_flag('deterministic')
                    else _process_entropy())

    def train_step(state_list, feed_list, rng_raw):
        rng = jax.random.wrap_key_data(rng_raw, impl=rng_impl)
        with amp.scope(amp_on):
            tracer = Tracer(program, rng)
            tracer.env.update(dict(zip(state_names, state_list)))
            tracer.env.update(dict(zip(feed_names, feed_list)))
            tracer.run_block(program.global_block())
            fetches = [tracer.env[n] for n in fetch_names]
            new_state = [tracer.env[n] for n in state_names]
        return fetches, new_state

    state_specs = [jax.ShapeDtypeStruct(state[n].shape, state[n].dtype)
                   for n in state_names]
    feed_specs = [jax.ShapeDtypeStruct(np.shape(sample[n]),
                                       np.asarray(sample[n]).dtype)
                  for n in feed_names]
    key_data = jax.random.key_data(jax.random.key(0, impl=rng_impl))
    rng_spec = jax.ShapeDtypeStruct(key_data.shape, key_data.dtype)
    exp = jexport.export(jax.jit(train_step), platforms=['cpu', 'tpu'])(
        state_specs, feed_specs, rng_spec)

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, _TRAIN_MODULE), 'wb') as f:
        f.write(exp.serialize())
    sig = {'version': 1,
           'feeds': [{'name': n, 'shape': list(np.shape(sample[n])),
                      'dtype': np.asarray(sample[n]).dtype.name}
                     for n in feed_names],
           'fetches': fetch_names,
           'state': [{'name': n, 'shape': list(state[n].shape),
                      'dtype': state[n].dtype.name} for n in state_names],
           'rng': {'impl': rng_impl, 'seed': int(seed),
                   'key_shape': list(key_data.shape),
                   'key_dtype': key_data.dtype.name}}
    with open(os.path.join(out_dir, _TRAIN_SIGNATURE), 'w') as f:
        json.dump(sig, f, indent=1)
    np.savez(os.path.join(out_dir, _TRAIN_STATE0),
             **{n: state[n] for n in state_names})
    if _should_precompile(precompile):
        _try_precompile(out_dir, train=True)
    return out_dir
