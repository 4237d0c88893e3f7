"""Tracer-free serving of a compiled inference artifact.

Counterpart to export.py — the deployment half of the reference's
non-Python serving story (inference/api/paddle_api.h:1): load a
`jax.export` artifact + signature and run it. This module imports ONLY
json/numpy/jax — no Program IR, no op registry, no tracer — so a serving
process carries none of the framework. It is also runnable as a script:

    python -m paddle_tpu.inference.serve ARTIFACT_DIR IN.npz OUT.npz

(or `python paddle_tpu/inference/serve.py ...` to avoid importing the
package __init__ entirely; the test exercises that path and asserts the
framework modules never load).

Bulk offline/eval inference: `CompiledPredictor.run_batches(batches)`
scans the exported module over K pre-staged batches in ONE device
dispatch (`serve.py loop ...` from the CLI) — the inference mirror of
the Executor's multi-step training dispatch.
"""
import contextlib
import itertools
import json
import os
import sys
import threading
import time
import warnings

import numpy as np

_SOURCE_SEQ = itertools.count()  # unique profiler source names per process


def _maybe_profiler():
    """paddle_tpu.profiler, but ONLY if the framework is already imported —
    importing it from here would drag the framework into a tracer-free
    serving process (canonical copy; batching.py reuses it)."""
    if sys.modules.get('paddle_tpu') is None:
        return None
    try:
        from paddle_tpu import profiler
        return profiler
    except Exception:
        return None


def span(name, **stats):
    """The program's one span primitive (paddle_tpu.profiler.span) for the
    framework-free serving modules: with the framework loaded it IS that
    class (so profiler.profiler() reports the serving spans too); without
    it, the bare jax.profiler.TraceAnnotation underneath. Either way the
    span lands in whatever jax profiler trace is running, on the device
    trace's clock, and is inert when none is."""
    prof = sys.modules.get('paddle_tpu.profiler')
    if prof is not None:
        return prof.span(name, **stats)
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **stats)


def tracing():
    """Whether a jax profiler trace is running: a stat that costs more
    than a name at hand is worked out only then."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation.is_enabled()


# python's cyclic collector, seen from the program: a collection runs on
# whichever thread's allocation tripped it and holds the GIL from start to
# stop, so EVERY python thread of the process stands still for it
_gc_lock = threading.Lock()
_gc_s = [0.0]           # seconds collecting, ever
_gc_open = [0.0, None]  # the running collection's start and its span


def _gc_event(phase, info):
    if phase == 'start':
        sp = None
        if tracing():
            sp = span('py/gc', generation=info['generation'])
            sp.__enter__()
        _gc_open[:] = time.perf_counter(), sp
        return
    t0, sp = _gc_open
    if sp is not None:
        sp.__exit__(None, None, None)
    if t0:
        _gc_s[0] += time.perf_counter() - t0
    _gc_open[:] = 0.0, None


def install_gc_hook():
    """Put ONE `gc.callbacks` hook into the process, however often it is
    asked for (the first DecodingPredictor asks): it times every
    collection for `gc_seconds()` and, while a jax profiler trace runs,
    opens a 'py/gc' span (`generation`) from the collection's start to
    its stop on the thread that collects. It tunes nothing."""
    import gc
    with _gc_lock:
        if _gc_event not in gc.callbacks:
            gc.callbacks.append(_gc_event)


def gc_seconds():
    """Seconds python's collector ran since install_gc_hook():
    process-wide, on any thread."""
    return _gc_s[0]


def _np_threefry_fold(seed, step):
    """fold_in(key(seed), step) raw key data with numpy only — the
    Threefry-2x32 core, bit-identical to jax's (the same math as
    executor.py's _np_threefry_key_group, duplicated because this module
    must import only json/numpy/jax and also run by file path). Used when
    no cpu backend is registered (JAX_PLATFORMS=tpu): eager key math on
    the accelerator would cost tiny dispatches per step."""
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    seed = int(seed)
    import jax
    with np.errstate(over='ignore'):
        # mirror jax's seed canonicalization: with x64 disabled (the
        # default) an int seed becomes int32, so the upper word is zero
        k0 = (np.uint32((seed >> 32) & 0xFFFFFFFF)
              if jax.config.jax_enable_x64 else np.uint32(0))
        k1 = np.uint32(seed & 0xFFFFFFFF)
        ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
        x0 = np.uint32(0) + ks[0]
        x1 = np.uint32(step) + ks[1]
        for i in range(5):
            for r in rot[i % 2]:
                x0 = x0 + x1
                x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
                x1 = x0 ^ x1
            x0 = x0 + ks[(i + 1) % 3]
            x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return np.stack([x0, x1])


_SIGNATURE = 'signature.json'
_MODULE = 'module.jaxexport'
_BUCKET_DIR = 'bucket_%05d'  # per-bucket subdir of a multi-bucket artifact
# quantized artifact tier (ISSUE 11): export_compiled(quantize='int8')
# writes a COMPLETE second artifact tree under <artifact>/int8/ — same
# buckets, own AOT sidecars, calibration metadata in its signature —
# next to the default ('bf16') tier at the top level
_TIER_INT8 = 'int8'
_TRAIN_SIGNATURE = 'train_signature.json'
_TRAIN_MODULE = 'train_module.jaxexport'
_TRAIN_STATE0 = 'train_state0.npz'
# export_decode's ONE copy of the weights: raw bytes, mapped through the
# decode signature's 'params' list (decoding.py loads it once per replica)
_DECODE_WEIGHTS = 'decode_weights.bin'
# AOT warm-start sidecars (ISSUE 5): the module's XLA executable,
# serialized per platform next to the module it was compiled from —
# loading one skips BOTH the StableHLO deserialize-compile and the trace,
# so a fresh serving replica answers its first request without paying
# cold-start compile latency. Written by export (default), or after the
# fact by `tools/cache_ctl.py prewarm ARTIFACT`.
_AOT_SIDECAR = 'aot_%s.jaxexec'              # % platform
_TRAIN_AOT_SIDECAR = 'aot_train_%s.jaxexec'  # % platform


def _module_sha(module_bytes):
    import hashlib
    return hashlib.sha256(module_bytes).hexdigest()


def resolve_tier(artifact_dir, tier=None, signature=_SIGNATURE):
    """Resolve a serving-tier request to the artifact directory to load.

    `tier` (or env PTPU_SERVE_TIER): 'bf16' (default) serves the top
    level; 'int8' serves the quantized tier subdir. An EXPLICIT tier
    argument on an artifact without that tier raises; the env preference
    degrades silently to the default tier so one fleet-wide setting can
    cover mixed artifact generations (and per-bucket loads inside an
    already-resolved tier). `signature` names the file a valid tier dir
    must carry — continuous-decode artifacts resolve against
    decode_signature.json (DecodingPredictor(tier=), same contract)."""
    req = tier or os.environ.get('PTPU_SERVE_TIER')
    if not req or req == 'bf16':
        return artifact_dir
    sub = os.path.join(artifact_dir, req)
    # a tier dir counts only with its signature: a partial/interrupted
    # export must surface the designed "has no tier" error, not a raw
    # FileNotFoundError from deep inside the loader
    if os.path.isdir(sub) and os.path.exists(os.path.join(sub,
                                                          signature)):
        return sub
    if tier:
        tiers = ['bf16']
        try:
            with open(os.path.join(artifact_dir, signature)) as f:
                tiers = json.load(f).get('tiers', ['bf16'])
        except Exception:
            pass
        raise ValueError(
            "artifact %s has no %r tier (tiers: %s) — export with "
            "export_compiled(..., quantize='int8') (or export_decode "
            "the quantized spec into <artifact>/%s) to add one"
            % (artifact_dir, req, tiers, req))
    return artifact_dir


def _aot_platform(device=None):
    """The platform an AOT sidecar is keyed on: the pinned device's, else
    the process's default jax backend."""
    if device is not None:
        return device.platform
    import jax
    return jax.default_backend()


@contextlib.contextmanager
def _fresh_compile(platform):
    """Context: compile for `platform` with jax's persistent compilation
    cache DISABLED where that is needed. An XLA:CPU executable the
    persistent cache satisfied re-serializes into a blob other processes
    cannot run ('Function ... not found' at dispatch) — so anything
    destined for _pack_executable on cpu (AOT sidecars, tier-1 cache
    entries) must come from a genuinely fresh compile. The defect is
    XLA:CPU's alone: on a TPU v5e (libtpu 0.0.34) a cache-satisfied
    executable serialized, reloaded in a third process and ran (PERF.md,
    PR 21), so other platforms compile through the cache.
    jax latches cache-enablement once per process (is_cache_used caches
    its verdict), so the latch is reset around the scope too."""
    if platform != 'cpu':
        yield
        return
    import jax
    from jax._src import compilation_cache as _jcc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    _jcc.reset_cache()
    try:
        yield
    finally:
        jax.config.update('jax_enable_compilation_cache', old)
        _jcc.reset_cache()


def _pack_executable(compiled):
    """Serialize a compiled executable together with what _load_executable
    needs to put it back where it was compiled for: the platform and the
    ids of its devices, in assignment order (one for a single-device
    program, the mesh's flat device list for a sharded one)."""
    from jax.experimental.serialize_executable import serialize
    payload, in_tree, out_tree = serialize(compiled)
    devs = list(compiled._executable._unloaded_executable.device_list)
    return {'payload': payload, 'in_tree': in_tree, 'out_tree': out_tree,
            'platform': devs[0].platform,
            'device_ids': [d.id for d in devs]}


def _named_call(exp):
    """`exp.call` under the exported function's own name: a jit of it is
    then 'jit_<fun_name>' in the compiled module and in a device trace's
    'XLA Modules' line (train_step, decode_step, prefill_chunk_32, ...),
    where a jit of the bound method reads 'jit_call' for every program."""
    def call(*args):
        return exp.call(*args)
    call.__name__ = call.__qualname__ = exp.fun_name
    return call


def _load_executable(packed):
    """THE one loader for serialized executables (AOT sidecars here,
    tier-1 entries in core/compile_cache.py): hands jax the client and the
    devices the executable was compiled for. Without them
    deserialize_and_load loads onto EVERY device of the default backend
    and a one-device program then demands one shard per device. Raises
    when the platform or a recorded device is absent from this process."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load
    by_id = {d.id: d for d in jax.devices(packed['platform'])}
    missing = [i for i in packed['device_ids'] if i not in by_id]
    if missing:
        raise ValueError(
            'executable was compiled for %s device(s) %s; this process '
            'has %s' % (packed['platform'], missing, sorted(by_id)))
    devices = [by_id[i] for i in packed['device_ids']]
    return deserialize_and_load(
        packed['payload'], packed['in_tree'], packed['out_tree'],
        backend=devices[0].client, execution_devices=devices)


def _save_aot(path, compiled, module_sha):
    """Serialize a compiled executable as a warm-start sidecar (atomic
    tmp+rename; pickle of the packed executable + validation facts)."""
    import pickle
    import jax
    import jaxlib
    blob = pickle.dumps(dict(_pack_executable(compiled), v=2,
                             jax=jax.__version__,
                             jaxlib=jaxlib.__version__, sha=module_sha))
    tmp = '%s.tmp-%d' % (path, os.getpid())
    with open(tmp, 'wb') as f:
        f.write(blob)
    os.replace(tmp, path)
    return path


def _load_aot(path, module_sha):
    """Deserialize a warm-start sidecar; None when absent. A stale or
    corrupt sidecar warns LOUDLY and is ignored (the module still serves
    through the normal compile path — never silently, never fatally)."""
    if not os.path.exists(path):
        return None
    import pickle
    import jax
    import jaxlib
    try:
        with span('load/read') as sp:
            with open(path, 'rb') as f:
                blob = f.read()
            sp.set_metadata(bytes=len(blob))
        with span('load/deserialize', bytes=len(blob)):
            d = pickle.loads(blob)
            del blob
            if d.get('sha') != module_sha:
                raise ValueError(
                    'sidecar was compiled from a different module')
            if (d.get('jax'), d.get('jaxlib')) != (jax.__version__,
                                                   jaxlib.__version__):
                raise ValueError(
                    'sidecar built with jax %s / jaxlib %s, process runs '
                    '%s/%s' % (d.get('jax'), d.get('jaxlib'),
                               jax.__version__, jaxlib.__version__))
            return _load_executable(d)
    except Exception as e:
        warnings.warn('AOT sidecar %s unusable (%s: %s) — falling back to '
                      'compiling the module; re-run `cache_ctl.py prewarm` '
                      'to refresh it' % (path, type(e).__name__, e),
                      RuntimeWarning)
        return None


def _infer_flat_specs(sig):
    """The module's flat arg specs from signature.json: per feed, data then
    one int32 offsets array per lod level (export.py's flat convention)."""
    import jax
    specs = []
    for e in sig['feeds']:
        specs.append(jax.ShapeDtypeStruct(tuple(e['shape']),
                                          np.dtype(e['dtype'])))
        if int(e.get('lod_levels', 0)):
            for n in e['lod_sizes']:
                specs.append(jax.ShapeDtypeStruct((int(n),), np.int32))
    return specs


def _precompile_infer_dir(d, platform=None):
    """AOT-compile the inference module in artifact dir `d` for this
    process's platform and write the sidecar. Returns the sidecar path."""
    import jax
    from jax import export as jexport
    with open(os.path.join(d, _MODULE), 'rb') as f:
        module_bytes = f.read()
    with open(os.path.join(d, _SIGNATURE)) as f:
        sig = json.load(f)
    plat = platform or _aot_platform()
    dev = jax.devices(plat)[0]
    exp = jexport.deserialize(module_bytes)
    with jax.default_device(dev), _fresh_compile(plat):
        compiled = jax.jit(_named_call(exp)).lower(
            *_infer_flat_specs(sig)).compile()
    return _save_aot(os.path.join(d, _AOT_SIDECAR % plat), compiled,
                     _module_sha(module_bytes))


def _precompile_train_dir(d, platform=None):
    """AOT-compile the train-step module in artifact dir `d` (sidecar per
    platform), mirroring CompiledTrainer.step's calling convention."""
    import jax
    from jax import export as jexport
    with open(os.path.join(d, _TRAIN_MODULE), 'rb') as f:
        module_bytes = f.read()
    with open(os.path.join(d, _TRAIN_SIGNATURE)) as f:
        sig = json.load(f)
    plat = platform or _aot_platform()
    dev = jax.devices(plat)[0]
    state_specs = [jax.ShapeDtypeStruct(tuple(e['shape']),
                                        np.dtype(e['dtype']))
                   for e in sig['state']]
    feed_specs = [jax.ShapeDtypeStruct(tuple(e['shape']),
                                       np.dtype(e['dtype']))
                  for e in sig['feeds']]
    rng_spec = jax.ShapeDtypeStruct(tuple(sig['rng']['key_shape']),
                                    np.dtype(sig['rng']['key_dtype']))
    exp = jexport.deserialize(module_bytes)
    with jax.default_device(dev), _fresh_compile(plat):
        compiled = jax.jit(_named_call(exp)).lower(
            state_specs, feed_specs, rng_spec).compile()
    return _save_aot(os.path.join(d, _TRAIN_AOT_SIDECAR % plat), compiled,
                     _module_sha(module_bytes))


def _decoding_module():
    """Sibling decoding.py (the continuous-decode tier), importable both
    as a package module and by file path (this module's own contract)."""
    try:
        from . import decoding
    except ImportError:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import decoding
    return decoding


def precompile_artifact(artifact_dir, platform=None):
    """Prewarm a serving artifact: AOT-compile EVERY bucket's module (and
    the train module when present) for this process's platform, writing
    warm-start sidecars — a replica that loads the artifact afterwards
    performs zero traces and zero XLA compiles before its first answer.
    Continuous-decode artifacts (export_decode's, decode_signature.json)
    prewarm every program they hold: each chunked-prefill size, the
    decode-step, verify, block-copy and zeros programs. The engine behind
    `tools/cache_ctl.py prewarm`. Returns the sidecar paths written."""
    import shutil
    written = []
    plat = platform or _aot_platform()
    decoding = _decoding_module()
    if os.path.exists(os.path.join(artifact_dir,
                                   decoding._DECODE_SIGNATURE)):
        written.extend(decoding.precompile_decode_artifact(
            artifact_dir, platform=plat))
    sig_p = os.path.join(artifact_dir, _SIGNATURE)
    if os.path.exists(sig_p):
        with open(sig_p) as f:
            buckets = json.load(f).get('buckets')
        if buckets:
            for b in buckets:
                written.append(_precompile_infer_dir(
                    os.path.join(artifact_dir, _BUCKET_DIR % int(b)),
                    platform=plat))
            # the top level mirrors (hardlinks) the LARGEST bucket's
            # module, so its sidecar is byte-for-byte reusable — link,
            # don't recompile
            src = written[-1]
            top = os.path.join(artifact_dir, _AOT_SIDECAR % plat)
            if os.path.exists(top):
                os.remove(top)
            try:
                os.link(src, top)
            except OSError:
                shutil.copyfile(src, top)
            written.append(top)
        else:
            written.append(_precompile_infer_dir(artifact_dir,
                                                 platform=plat))
    if os.path.exists(os.path.join(artifact_dir, _TRAIN_MODULE)):
        written.append(_precompile_train_dir(artifact_dir, platform=plat))
    # quantized artifact tier (ISSUE 11): a complete bucket tree under
    # int8/ prewarms exactly like the top level, so warm int8 replicas
    # answer with zero compiles too
    tier_dir = os.path.join(artifact_dir, _TIER_INT8)
    if os.path.isdir(tier_dir) and os.path.exists(
            os.path.join(tier_dir, _SIGNATURE)):
        written.extend(precompile_artifact(tier_dir, platform=plat))
    return written


def _split_lod_value(name, value, levels):
    """A LoD feed arrives as (values, lod) — lod nested offsets, or flat
    for one level — or any object with .data/.off_t (duck-typed LoDArray,
    so in-framework callers can pass LoDTensors without this module
    importing the framework)."""
    if hasattr(value, 'off_t') and hasattr(value, 'data'):
        return (np.asarray(value.data),
                [np.asarray(value.off_t(i)) for i in range(levels)])
    if isinstance(value, tuple) and len(value) == 2:
        data, lod = value
        if isinstance(lod, np.ndarray):
            lod = [lod] if lod.ndim == 1 else list(lod)
        elif len(lod) and np.isscalar(lod[0]):
            lod = [lod]
        return np.asarray(data), [np.asarray(l) for l in lod]
    raise ValueError(
        "feed %r carries %d lod level(s): pass a (values, offsets) pair"
        % (name, levels))


def _build_args(sig_feeds, feed_names, inputs, allow_pad=False):
    """Normalize list-or-dict inputs against the artifact signature:
    feed-order list, dtype cast, fixed-shape check; LoD feeds contribute
    their data plus one int32 offsets array per level. Shared by
    CompiledPredictor.run and CompiledTrainer.step.

    With allow_pad, a PARTIAL dense batch — every dense feed arriving with
    the same rows r below the artifact's (uniform) leading batch dim B —
    is zero-padded up to B, the dense analog of the LoD bucket_rows
    padding below. Returns (args, pad) where pad is None or (rows, B) so
    the caller can slice batch-led fetches back to r (and error loudly on
    row-count-dependent fetches)."""
    if isinstance(inputs, (list, tuple)):
        if len(inputs) != len(feed_names):
            raise ValueError("artifact expects %d inputs (%s), got %d"
                             % (len(feed_names), feed_names, len(inputs)))
        feed = dict(zip(feed_names, inputs))
    else:
        feed = dict(inputs)
    missing = [e['name'] for e in sig_feeds if e['name'] not in feed]
    if missing:
        raise ValueError("missing feeds: %r (artifact expects %s)"
                         % (missing, feed_names))
    pad = None
    dense_arrs = {}
    if allow_pad:
        dense = [(e, np.asarray(feed[e['name']],
                                dtype=np.dtype(e['dtype'])))
                 for e in sig_feeds if not int(e.get('lod_levels', 0))]
        dense_arrs = {e['name']: a for e, a in dense}
        if dense and all(
                e['shape'] and a.ndim == len(e['shape'])
                and list(a.shape[1:]) == e['shape'][1:] for e, a in dense):
            expect = {int(e['shape'][0]) for e, _ in dense}
            got = {int(a.shape[0]) for _, a in dense}
            if len(expect) == 1 and len(got) == 1:
                bucket, rows = expect.pop(), got.pop()
                if 0 < rows < bucket:
                    pad = (rows, bucket)
    args = []
    for e in sig_feeds:
        levels = int(e.get('lod_levels', 0))
        value = feed[e['name']]
        if levels:
            data, offs = _split_lod_value(e['name'], value, levels)
            if len(offs) != levels:
                raise ValueError("feed %r: expected %d lod level(s), got %d"
                                 % (e['name'], levels, len(offs)))
            data = np.asarray(data, dtype=np.dtype(e['dtype']))
            rows = data.shape[0]
            bucket_rows = e['shape'][0]
            if rows < bucket_rows \
                    and list(data.shape[1:]) == e['shape'][1:]:
                # pad up to the bucket capacity (the executor's
                # bucket_rows discipline, core/lod.py create_lod_array)
                fill = np.zeros((bucket_rows - rows,) + data.shape[1:],
                                data.dtype)
                data = np.concatenate([data, fill], axis=0)
            if list(data.shape) != e['shape']:
                raise ValueError(
                    "feed %r: expected bucket shape %s, got %s"
                    % (e['name'], e['shape'], list(data.shape)))
            args.append(data)
            for i, (o, want) in enumerate(zip(offs, e['lod_sizes'])):
                o = np.asarray(o, np.int32).reshape(-1)
                if o.shape[0] != want:
                    raise ValueError(
                        "feed %r lod level %d: artifact bucket has %d "
                        "offsets (nseq=%d), got %d"
                        % (e['name'], i, want, want - 1, o.shape[0]))
                args.append(o)
            continue
        arr = dense_arrs.get(e['name'])
        if arr is None:
            arr = np.asarray(value, dtype=np.dtype(e['dtype']))
        if pad is not None and arr.shape[0] == pad[0]:
            arr = np.concatenate(
                [arr, np.zeros((pad[1] - pad[0],) + arr.shape[1:],
                               arr.dtype)], axis=0)
        if list(arr.shape) != e['shape']:
            raise ValueError(
                "feed %r: expected shape %s (artifacts are compiled for "
                "fixed shapes), got %s"
                % (e['name'], e['shape'], list(arr.shape)))
        args.append(arr)
    return args, pad


def _fetch_entries(sig):
    """Fetch signature entries across artifact versions: v1 stored plain
    names (dense-only), v2 stores {name, lod_levels}."""
    return [{'name': f, 'lod_levels': 0} if isinstance(f, str) else f
            for f in sig['fetches']]


def _structure_outputs(sig, flat):
    """Group the module's flat outputs per the fetch signature: dense
    fetches yield an array, LoD fetches a (values, [offsets...]) pair."""
    flat = list(flat)
    out, i = [], 0
    for e in _fetch_entries(sig):
        levels = int(e.get('lod_levels', 0))
        data = np.asarray(flat[i])
        i += 1
        if levels:
            offs = [np.asarray(flat[i + k]) for k in range(levels)]
            i += levels
            out.append((data, offs))
        else:
            out.append(data)
    return out


class CompiledPredictor(object):
    """PaddlePredictor-shaped API over an exported artifact.

    `platform` pins execution, e.g. 'cpu' or 'tpu'; default is the
    process's default jax backend."""

    def __init__(self, artifact_dir, platform=None, tier=None):
        import jax
        artifact_dir = resolve_tier(artifact_dir, tier)
        with open(os.path.join(artifact_dir, _SIGNATURE)) as f:
            self._sig = json.load(f)
        # the tier actually LOADED, from the artifact's own signature
        # (the request may have resolved through env/default)
        self.tier = self._sig.get('tier', 'bf16')
        with open(os.path.join(artifact_dir, _MODULE), 'rb') as f:
            module_bytes = f.read()
        # the StableHLO module deserializes LAZILY: a warm replica that
        # loads an AOT sidecar never parses it at all (cold-start cost is
        # the sidecar deserialize alone)
        self._module_bytes = module_bytes
        self._exported_cached = None
        self._feed_names = [e['name'] for e in self._sig['feeds']]
        self._device = jax.devices(platform)[0] if platform else None
        # AOT warm start: a precompiled sidecar for this platform skips
        # the first-request XLA compile entirely (PTPU_ARTIFACT_AOT=0
        # opts out; a stale sidecar warns and falls back)
        self._aot = None
        if os.environ.get('PTPU_ARTIFACT_AOT', '1') not in ('0', 'false'):
            self._aot = _load_aot(
                os.path.join(artifact_dir,
                             _AOT_SIDECAR % _aot_platform(self._device)),
                _module_sha(module_bytes))
        # bulk-inference loop state (run_batches): one jitted scan over the
        # exported module; XLA caches one executable per group size
        self._loop = None
        self._bulk = {'dispatches': 0, 'batches': 0, 'tail_flushes': 0,
                      'stage_s': 0.0, 'dispatch_s': 0.0, 'total_s': 0.0}
        self._prof_name = None
        self._artifact_dir = artifact_dir

    @property
    def _exported(self):
        if self._exported_cached is None:
            from jax import export as jexport
            self._exported_cached = jexport.deserialize(self._module_bytes)
        return self._exported_cached

    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return [e['name'] for e in _fetch_entries(self._sig)]

    def drain(self):
        """Fleet scale-in hook (inference/fleet.py): CompiledPredictor
        is synchronous — it holds no queue and no in-flight work beyond
        the caller's own run(), so draining is a no-op. BatchingPredictor
        and DecodingPredictor override this with real drains."""
        return self

    def _call_flat(self, args):
        """Dispatch the exported module on the pinned device; returns the
        FLAT device outputs without a host sync (async serving loops —
        e.g. batching.BatchingPredictor — sync once at delivery). With a
        warm-start sidecar loaded, this calls the deserialized executable
        directly — no trace, no compile, same flat convention."""
        fn = self._aot if self._aot is not None else self._exported.call
        if self._device is not None:
            import jax
            with jax.default_device(self._device):
                return fn(*args)
        return fn(*args)

    def run(self, inputs, pad_partial=True):
        """inputs: list (feed order) or dict name -> array; LoD feeds as
        (values, offsets) pairs. Returns a list with a numpy array per
        dense fetch and a (values, [offsets...]) pair per LoD fetch.

        A PARTIAL dense batch (every dense feed with the same rows r below
        the compiled batch dim B) is zero-padded up to B and batch-led
        fetches are sliced back to r; fetches whose leading dim is NOT the
        batch (e.g. a batch reduction — their value depends on the padded
        row count) error loudly, flagged ahead of dispatch when the
        signature records fetch shapes (v3 exports) and at delivery
        otherwise. Caveat: a shape-preserving CROSS-ROW op (rows coupled
        but the fetch stays batch-led, e.g. x - mean(x, axis=0)) is
        undetectable from shapes — such programs would fold the zero rows
        into every result; pass pad_partial=False to restore the strict
        fixed-shape rejection."""
        args, pad = _build_args(self._sig['feeds'], self._feed_names,
                                inputs, allow_pad=pad_partial)
        if pad is not None:
            self._check_pad_fetches(pad)
        outs = _structure_outputs(self._sig, self._call_flat(args))
        if pad is None:
            return outs
        return self._slice_pad(outs, pad)

    def _check_pad_fetches(self, pad):
        """Pre-dispatch rejection of row-count-dependent fetches when the
        signature records fetch shapes (v3 exports)."""
        for e in _fetch_entries(self._sig):
            shape = e.get('shape')
            if int(e.get('lod_levels', 0)) or (
                    shape is not None
                    and (not shape or int(shape[0]) != pad[1])):
                raise ValueError(
                    "feed rows were padded %d->%d but fetch %r (shape "
                    "%s in the signature) is not batch-aligned — its "
                    "value would depend on the padded rows; run with "
                    "the exact compiled batch" % (pad + (e['name'],
                                                         shape)))

    def _slice_pad(self, outs, pad):
        """Slice batch-led fetches of a padded partial batch back to the
        caller's rows; delivery-time guard for v2 signatures."""
        rows, bucket = pad
        sliced = []
        for e, o in zip(_fetch_entries(self._sig), outs):
            if isinstance(o, tuple) or o.ndim < 1 or o.shape[0] != bucket:
                raise ValueError(
                    "feed rows were padded %d->%d but fetch %r has shape "
                    "%s — not batch-aligned, its value depends on the "
                    "padded row count (e.g. a batch reduction); run with "
                    "the exact compiled batch"
                    % (rows, bucket, e['name'],
                       'lod' if isinstance(o, tuple) else list(o.shape)))
            sliced.append(o[:rows])
        return sliced

    # -- bulk inference: one dispatch, K batches ---------------------------
    def _loop_jit(self):
        """jit of a lax.scan over the exported module: each scanned step is
        the exact per-batch program `run()` dispatches, so per-batch
        results are bit-identical through the same bucket. Every stacked
        input is donated — the buffers are staged copies this class owns
        (run_batches never hands a caller-visible array to the jit), so
        XLA may reuse them for the scan's intermediates. One jitted fn
        serves every group size: jit compiles one executable per leading
        dim, which is exactly the multi-bucket tail discipline."""
        if self._loop is None:
            import jax
            exported = self._exported
            nargs = sum(1 + int(e.get('lod_levels', 0))
                        for e in self._sig['feeds'])

            def loop(*stacked):
                def body(carry, xs):
                    return carry, tuple(exported.call(*xs))
                _, ys = jax.lax.scan(body, (), stacked)
                return ys
            self._loop = jax.jit(loop,
                                 donate_argnums=tuple(range(nargs)))
        return self._loop

    def _register_bulk_source(self):
        if self._prof_name is not None:
            return
        prof = _maybe_profiler()
        if prof is None or not hasattr(prof, 'register_infer_source'):
            return
        name = 'bulk_infer:%s#%d' % (
            os.path.basename(os.path.normpath(self._artifact_dir)),
            next(_SOURCE_SEQ))
        # weakref, the Executor's discipline: a predictor dropped by its
        # owner must not stay pinned (module + per-group executables) in
        # the profiler registry forever
        import weakref
        ref = weakref.ref(self)

        def snap():
            pred = ref()
            if pred is None:
                prof.unregister_infer_source(name)
                raise ReferenceError('predictor collected')
            return pred.bulk_stats()
        prof.register_infer_source(name, snap)
        self._prof_name = name

    def bulk_stats(self):
        """Bulk-inference counters (profiler.infer_report contract):
        dispatches, batches, batches_per_dispatch, tail_flushes,
        host_stall_ms (staging: stacking + device transfer), occupancy
        (device-call share of run_batches wall time)."""
        st = self._bulk
        d = max(st['dispatches'], 1)
        return {'dispatches': st['dispatches'], 'batches': st['batches'],
                'batches_per_dispatch': st['batches'] / d,
                'tail_flushes': st['tail_flushes'],
                'host_stall_ms': st['stage_s'] * 1e3,
                'occupancy': (st['dispatch_s'] / st['total_s']
                              if st['total_s'] else 0.0)}

    def run_batches(self, batches, group=None, pad_partial=True):
        """Bulk offline/eval inference: ONE device dispatch runs a
        lax.scan over K pre-staged input batches, amortizing the fixed
        per-dispatch cost across all K. Per-batch results are
        bit-identical to K sequential `run()` calls through the same
        bucket (matmul models exactly;
        XLA:CPU rounds conv scan bodies to ~1e-6, PERF_NOTES.md).

        batches: list of K per-batch inputs, each a list (feed order) or
        dict exactly as `run()` takes — LoD feeds as (values, offsets)
        pairs ride the scan as stacked runtime data, dense partial
        batches pad per-batch under `pad_partial` (run()'s discipline).

        group: dispatch at most `group` batches per compiled loop;
        the tail chunk (m < group) flushes through a smaller compiled
        group, the multi-bucket discipline of prefetch_to_device.
        Default: all K in one dispatch.

        Returns a list of K per-batch fetch lists (run()'s structure)."""
        t_all = time.perf_counter()
        batches = list(batches)
        if not batches:
            return []
        k = len(batches)
        g = k if group is None else int(group)
        if g < 1:
            raise ValueError("run_batches: group must be >= 1, got %d" % g)
        st = self._bulk
        t0 = time.perf_counter()
        flat, pads = [], []
        for b in batches:
            args, pad = _build_args(self._sig['feeds'], self._feed_names,
                                    b, allow_pad=pad_partial)
            if pad is not None:
                self._check_pad_fetches(pad)
            flat.append(args)
            pads.append(pad)
        st['stage_s'] += time.perf_counter() - t0
        loop = self._loop_jit()
        try:
            return self._run_chunks(loop, flat, pads, k, g)
        finally:
            # total accrues even when a chunk raises mid-call: dispatched
            # chunks' stage/dispatch seconds were already committed, and
            # occupancy (dispatch_s / total_s) must stay <= 1
            st['total_s'] += time.perf_counter() - t_all
            self._register_bulk_source()

    def _run_chunks(self, loop, flat, pads, k, g):
        import jax
        st = self._bulk
        results = []
        for off in range(0, k, g):
            chunk = flat[off:off + g]
            m = len(chunk)
            t0 = time.perf_counter()
            # np.stack materializes fresh host buffers (even for device-
            # array inputs), so the donated arrays below are ours alone
            stacked = [np.stack([c[j] for c in chunk])
                       for j in range(len(chunk[0]))]
            if self._device is not None:
                stacked = [jax.device_put(a, self._device) for a in stacked]
            else:
                stacked = [jax.device_put(a) for a in stacked]
            for a in stacked:
                a.block_until_ready()
            t1 = time.perf_counter()
            with warnings.catch_warnings():
                # backends without donation support (XLA:CPU) warn per
                # compile; the fallback is a copy, not a correctness issue
                warnings.filterwarnings(
                    'ignore', message='Some donated buffers were not usable')
                if self._device is not None:
                    with jax.default_device(self._device):
                        ys = loop(*stacked)
                else:
                    ys = loop(*stacked)
                ys = [np.asarray(y) for y in ys]  # ONE sync per dispatch
            t2 = time.perf_counter()
            st['dispatches'] += 1
            st['batches'] += m
            if m < g and off > 0:
                # a genuine tail: full chunks preceded this smaller one —
                # a single sub-group call (k < group) compiles only its
                # own size and is not a tail flush
                st['tail_flushes'] += 1
            st['stage_s'] += t1 - t0
            st['dispatch_s'] += t2 - t1
            for i in range(m):
                outs = _structure_outputs(self._sig, [y[i] for y in ys])
                pad = pads[off + i]
                results.append(outs if pad is None
                               else self._slice_pad(outs, pad))
        return results


def load_compiled(artifact_dir, tier=None):
    return CompiledPredictor(artifact_dir, tier=tier)


class CompiledTrainer(object):
    """Tracer-free TRAINING from an export_train_step artifact — the
    deployment-side counterpart of the reference's C++ trainer
    (train/demo_trainer.cc:1). Parameters and optimizer state flow
    through each call as arrays (nothing baked); this class carries them
    between steps and reproduces the Executor's per-step rng stream
    (fold_in(key(seed, impl), step)), so losses bit-match in-framework
    training. Imports only json/numpy/jax."""

    def __init__(self, artifact_dir, platform=None, seed=None):
        import jax
        with open(os.path.join(artifact_dir, _TRAIN_SIGNATURE)) as f:
            self._sig = json.load(f)
        with open(os.path.join(artifact_dir, _TRAIN_MODULE), 'rb') as f:
            module_bytes = f.read()
        # lazy, as in CompiledPredictor: an AOT-warm trainer never parses
        # the StableHLO module
        self._module_bytes = module_bytes
        self._exported_cached = None
        self._state_names = [e['name'] for e in self._sig['state']]
        with np.load(os.path.join(artifact_dir, _TRAIN_STATE0)) as z:
            self._state = [z[n] for n in self._state_names]
        self._feed_names = [e['name'] for e in self._sig['feeds']]
        self._seed = int(self._sig['rng']['seed'] if seed is None else seed)
        self._impl = self._sig['rng']['impl']
        self._step_count = 0
        self._device = jax.devices(platform)[0] if platform else None
        self._aot = None
        if os.environ.get('PTPU_ARTIFACT_AOT', '1') not in ('0', 'false'):
            self._aot = _load_aot(
                os.path.join(artifact_dir, _TRAIN_AOT_SIDECAR
                             % _aot_platform(self._device)),
                _module_sha(module_bytes))

    @property
    def _exported(self):
        if self._exported_cached is None:
            from jax import export as jexport
            self._exported_cached = jexport.deserialize(self._module_bytes)
        return self._exported_cached

    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._sig['fetches'])

    @property
    def state(self):
        """Current state as {name: numpy array} (a checkpoint)."""
        return {n: np.asarray(v)
                for n, v in zip(self._state_names, self._state)}

    def _rng(self):
        # derived on the host cpu backend when one is registered: eager
        # key math on the accelerator costs tiny dispatches per step (the
        # Executor does the same).
        # Under JAX_PLATFORMS=tpu the cpu platform is absent (ADVICE r5
        # item 3): threefry keys derive numpy-side (bit-identical,
        # dispatch-free); other impls fall back to the default device —
        # derivation is deterministic math, same stream either way.
        import contextlib
        import jax
        try:
            dev_ctx = jax.default_device(
                jax.local_devices(backend='cpu')[0])
        except RuntimeError:
            if self._impl == 'threefry2x32':
                return _np_threefry_fold(self._seed, self._step_count)
            dev_ctx = contextlib.nullcontext()
        with dev_ctx:
            key = jax.random.key(self._seed, impl=self._impl)
            return np.asarray(jax.random.key_data(
                jax.random.fold_in(key, self._step_count)))

    def step(self, inputs):
        """Run one train step. inputs: list (feed order) or dict.
        Advances the carried state and rng; returns numpy fetches.
        Strict shapes: a train step never pads (padded rows would corrupt
        the loss and every batch statistic)."""
        args, _ = _build_args(self._sig['feeds'], self._feed_names, inputs)
        fn = self._aot if self._aot is not None else self._exported.call

        def call():
            return fn(self._state, args, self._rng())
        if self._device is not None:
            import jax
            with jax.default_device(self._device):
                fetches, new_state = call()
        else:
            fetches, new_state = call()
        self._state = new_state
        self._step_count += 1
        return [np.asarray(f) for f in fetches]

    def save_state(self, path):
        """Checkpoint the carried state plus the step counter (so a
        resumed trainer continues the exact rng stream); same npz tensor
        format as the artifact's train_state0.npz."""
        np.savez(path, __step_count__=np.int64(self._step_count),
                 **self.state)

    def load_state(self, path):
        with np.load(path) as z:
            missing = [n for n in self._state_names if n not in z.files]
            if missing:
                raise ValueError("checkpoint missing state vars: %r"
                                 % missing)
            self._state = [z[n] for n in self._state_names]
            # a checkpoint without a counter (e.g. train_state0.npz) means
            # "restart from step 0" — keeping the old counter would
            # silently shift the rng stream off the bit-match trajectory
            self._step_count = (int(z['__step_count__'])
                                if '__step_count__' in z.files else 0)


def load_trainer(artifact_dir, platform=None, seed=None):
    return CompiledTrainer(artifact_dir, platform=platform, seed=seed)


def _bench_cli(argv):
    # serve.py bench ARTIFACT_DIR IN.npz N_REQUESTS [TIMEOUT_MS]
    # replays IN.npz N times through the dynamic batcher and prints
    # throughput + latency percentiles, with a sequential
    # one-request-per-run reference — serving perf measurable without a
    # benchmark harness.
    if len(argv) not in (5, 6):
        print("usage: serve.py bench ARTIFACT_DIR IN.npz N_REQUESTS "
              "[TIMEOUT_MS]", file=sys.stderr)
        return 2
    artifact_dir, in_path, n = argv[2], argv[3], int(argv[4])
    timeout_ms = float(argv[5]) if len(argv) == 6 else 5.0
    try:
        from . import batching
    except ImportError:  # run by file path: batching.py sits alongside
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import batching
    with np.load(in_path) as z:
        feed = {k: z[k] for k in z.files}
    rows = int(next(iter(feed.values())).shape[0])

    batcher = batching.BatchingPredictor(artifact_dir,
                                         batch_timeout_ms=timeout_ms)
    batcher.warmup()
    # sequential reference: the old serving path, one run() per request
    # (pads each request up to the compiled batch)
    seq = CompiledPredictor(artifact_dir)
    k = min(n, 8)
    seq.run(feed)  # warm
    t0 = time.perf_counter()
    for _ in range(k):
        seq.run(feed)
    seq_req_s = k / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    futs = [batcher.submit(feed) for _ in range(n)]
    for f in futs:
        f.result()
    wall = time.perf_counter() - t0
    snap = batcher.stats.snapshot()
    batcher.close()
    req_s = n / wall
    print("buckets=%s requests=%d rows/request=%d" %
          (batcher.buckets, n, rows))
    print("batched:    %10.1f req/s  %10.1f rows/s  (%d batches, "
          "occupancy %.2f)" % (req_s, req_s * rows, snap['batches'],
                               snap['occupancy']))
    print("sequential: %10.1f req/s  %10.1f rows/s  (CompiledPredictor."
          "run per request)" % (seq_req_s, seq_req_s * rows))
    print("latency ms: p50=%.2f p95=%.2f p99=%.2f" %
          (snap['p50_ms'], snap['p95_ms'], snap['p99_ms']))
    print(json.dumps({'req_s': round(req_s, 2),
                      'rows_s': round(req_s * rows, 2),
                      'seq_req_s': round(seq_req_s, 2),
                      'speedup': round(req_s / seq_req_s, 2),
                      'occupancy': snap['occupancy'],
                      'p50_ms': snap['p50_ms'], 'p95_ms': snap['p95_ms'],
                      'p99_ms': snap['p99_ms']}))
    return 0


def _feed_from_npz(sig_feeds, raw, index=None):
    """Rebuild one feed dict from npz arrays ('<name>' plus
    '<name>.lod<i>' offsets for LoD feeds); with `index`, slice batch
    `index` out of arrays stacked over a leading K axis."""
    feed = {}
    for e in sig_feeds:
        n, levels = e['name'], int(e.get('lod_levels', 0))
        pick = (lambda a: a[index]) if index is not None else (lambda a: a)
        if levels:
            feed[n] = (pick(raw[n]), [pick(raw['%s.lod%d' % (n, i)])
                                      for i in range(levels)])
        else:
            feed[n] = pick(raw[n])
    return feed


def _loop_cli(argv):
    # serve.py loop ARTIFACT_DIR IN.npz OUT.npz [GROUP]
    # IN.npz arrays carry a leading K batch axis (LoD feeds as '<name>'
    # [K, rows, ...] plus '<name>.lod<i>' [K, n] offsets); all K batches
    # run through run_batches — ONE compiled dispatch per group — and
    # OUT.npz holds each fetch stacked over the same K axis.
    if len(argv) not in (5, 6):
        print("usage: serve.py loop ARTIFACT_DIR IN.npz OUT.npz [GROUP]",
              file=sys.stderr)
        return 2
    artifact_dir, in_path, out_path = argv[2:5]
    group = int(argv[5]) if len(argv) == 6 else None
    pred = CompiledPredictor(artifact_dir)
    with np.load(in_path) as data:
        raw = {k: data[k] for k in data.files}
    k = int(next(iter(raw.values())).shape[0])
    batches = [_feed_from_npz(pred._sig['feeds'], raw, index=i)
               for i in range(k)]
    results = pred.run_batches(batches, group=group)
    save = {}
    for j, n in enumerate(pred.get_output_names()):
        outs = [r[j] for r in results]
        if isinstance(outs[0], tuple):
            save[n] = np.stack([o[0] for o in outs])
            for i in range(len(outs[0][1])):
                save['%s.lod%d' % (n, i)] = np.stack([o[1][i]
                                                      for o in outs])
        else:
            save[n] = np.stack(outs)
    np.savez(out_path, **save)
    return 0


def _pop_flag(argv, name):
    """Extract `--NAME VALUE` (or `--NAME=VALUE`) from argv anywhere;
    returns (value or None, argv without the flag) — the positional CLIs
    here stay positional, flags ride on top."""
    out, value, it = [], None, iter(argv)
    for a in it:
        if a == '--%s' % name:
            value = next(it, None)
            if value is None:
                raise SystemExit('--%s needs a value' % name)
        elif a.startswith('--%s=' % name):
            value = a.split('=', 1)[1]
        else:
            out.append(a)
    return value, out


def _decode_cli(argv):
    # serve.py decode ARTIFACT_DIR PROMPTS.npz OUT.npz [MAX_NEW [BEAM]]
    #          [--tier T]
    # PROMPTS.npz: 'prompts' [N, L] int64 (0-padded) + optional 'lens'
    # [N]. Greedy (default) writes OUT.npz 'tokens' [N, max_new] padded
    # with -1 after each transcript plus 'n_tokens' [N]; with BEAM, the
    # best hypothesis per request plus 'scores' [N]. Every request runs
    # through the continuous-batching scheduler — submit all, then wait.
    # --tier serves an explicit artifact tier (e.g. the quantized-KV
    # decode tier under <artifact>/int8/) with the same
    # explicit-missing-tier-raises contract as BatchingPredictor(tier=);
    # without it, PTPU_SERVE_TIER applies as a silent preference.
    tier, argv = _pop_flag(argv, 'tier')
    if len(argv) not in (5, 6, 7):
        print("usage: serve.py decode ARTIFACT_DIR PROMPTS.npz OUT.npz "
              "[MAX_NEW [BEAM]] [--tier T]", file=sys.stderr)
        return 2
    artifact_dir, in_path, out_path = argv[2:5]
    max_new = int(argv[5]) if len(argv) >= 6 else 32
    beam = int(argv[6]) if len(argv) == 7 else None
    decoding = _decoding_module()
    with np.load(in_path) as z:
        prompts = np.asarray(z['prompts'], np.int64)
        lens = (np.asarray(z['lens'], np.int64) if 'lens' in z.files
                else np.full(prompts.shape[0], prompts.shape[1], np.int64))
    with decoding.DecodingPredictor(artifact_dir, tier=tier) as pred:
        streams = [pred.submit(prompts[i, :lens[i]], max_new_tokens=max_new,
                               beam=beam) for i in range(prompts.shape[0])]
        results = [s.result() for s in streams]
        snap = pred.stats.snapshot()
    toks = np.full((len(results), max_new), -1, np.int64)
    n_tok = np.zeros(len(results), np.int64)
    scores = np.zeros(len(results), np.float64)
    for i, r in enumerate(results):
        ids = r[0][0] if beam else np.asarray(r, np.int64)
        if beam:
            scores[i] = r[1][0]
        n_tok[i] = len(ids)
        toks[i, :len(ids)] = ids
    save = {'tokens': toks, 'n_tokens': n_tok}
    if beam:
        save['scores'] = scores
    np.savez(out_path, **save)
    print(json.dumps({'requests': len(results),
                      'tier': snap.get('tier', 'bf16'),
                      'tokens': int(snap['tokens']),
                      'tokens_s': snap['tokens_s'],
                      'occupancy': snap['occupancy'],
                      'ttft_p50_ms': snap['ttft_p50_ms'],
                      'ttft_p99_ms': snap['ttft_p99_ms']}))
    return 0


def _fleet_cli(argv):
    # serve.py fleet ARTIFACT_DIR IN.npz N_REQUESTS [REPLICAS]
    #          [--tier T] [--kind K]
    # Spin up a replica fleet (subprocess workers over the fleet.py
    # frame protocol), replay IN.npz N times through FleetRouter.submit
    # with least-outstanding-work routing, and print fleet throughput,
    # latency percentiles and the per-replica table as JSON — serving-
    # fleet perf measurable without a benchmark harness.
    # Batching/compiled artifacts: IN.npz holds one request's feed
    # arrays. Decode artifacts: the decode-CLI convention — 'prompts'
    # [N, L] int64 (0-padded) + optional 'lens' [N]; requests cycle
    # through the prompt rows.
    tier, argv = _pop_flag(argv, 'tier')
    kind, argv = _pop_flag(argv, 'kind')
    if len(argv) not in (5, 6):
        print("usage: serve.py fleet ARTIFACT_DIR IN.npz N_REQUESTS "
              "[REPLICAS] [--tier T] [--kind K]", file=sys.stderr)
        return 2
    artifact_dir, in_path, n = argv[2], argv[3], int(argv[4])
    replicas = int(argv[5]) if len(argv) == 6 else 2
    try:
        from . import fleet as _fleet
    except ImportError:  # run by file path: fleet.py sits alongside
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import fleet as _fleet
    with np.load(in_path) as z:
        raw = {k: z[k] for k in z.files}
    with _fleet.FleetRouter(artifact_dir, replicas=replicas,
                            kind=kind or 'auto', tier=tier) as router:
        if router.kind == 'decoding':
            prompts = np.asarray(raw['prompts'], np.int64)
            lens = (np.asarray(raw['lens'], np.int64)
                    if 'lens' in raw else np.full(
                        prompts.shape[0], prompts.shape[1], np.int64))
            requests = [prompts[i % prompts.shape[0],
                                :lens[i % prompts.shape[0]]]
                        for i in range(n)]
        else:
            requests = [raw] * n
        t0 = time.perf_counter()
        futs = [router.submit(r) for r in requests]
        for f in futs:
            f.result(600)
        wall = time.perf_counter() - t0
        snap = router.fleet_snapshot()
    out = {'requests': n, 'replicas': replicas,
           'req_s': round(n / wall, 2), 'tier': snap['tier'],
           'p50_ms': snap['p50_ms'], 'p99_ms': snap['p99_ms'],
           'rerouted': snap['rerouted'], 'failed': snap['failed'],
           'per_replica': {rid: {'requests': s['requests'],
                                 'occupancy': s['occupancy'],
                                 'spinup_s': s['spinup_s'],
                                 'compiles': s['compiles']}
                           for rid, s in snap['replicas'].items()}}
    print(json.dumps(out))
    return 0


def _gateway_cli(argv):
    # serve.py gateway ARTIFACT_DIR [PORT] [--host H] [--replicas N]
    #          [--tier T] [--kind K] [--tenants TENANTS.json]
    #          [--max-queue N] [--max-inflight N]
    # Serve a replica fleet over HTTP (ISSUE 19): spin up REPLICAS
    # workers behind a FleetRouter, front them with gateway.Gateway,
    # print one {'url': ...} JSON line (flushed — callers poll it),
    # and serve until SIGTERM/SIGINT or an authenticated POST
    # /admin/drain. Shutdown is the graceful-drain contract: stop
    # admitting, finish every in-flight request/stream, close the
    # fleet, exit 0. TENANTS.json: {api_key: {tenant, rate, burst,
    # max_inflight, admin}}; omitted = open/anonymous serving.
    host, argv = _pop_flag(argv, 'host')
    tier, argv = _pop_flag(argv, 'tier')
    kind, argv = _pop_flag(argv, 'kind')
    tenants_path, argv = _pop_flag(argv, 'tenants')
    replicas, argv = _pop_flag(argv, 'replicas')
    max_queue, argv = _pop_flag(argv, 'max-queue')
    max_inflight, argv = _pop_flag(argv, 'max-inflight')
    if len(argv) not in (3, 4):
        print("usage: serve.py gateway ARTIFACT_DIR [PORT] [--host H] "
              "[--replicas N] [--tier T] [--kind K] "
              "[--tenants TENANTS.json] [--max-queue N] "
              "[--max-inflight N]", file=sys.stderr)
        return 2
    artifact_dir = argv[2]
    port = int(argv[3]) if len(argv) == 4 else 0
    try:
        from . import fleet as _fleet
        from . import gateway as _gateway
    except ImportError:  # run by file path: siblings sit alongside
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import fleet as _fleet
        import gateway as _gateway
    import signal
    import threading
    tenants = (_gateway.tenants_from_json(tenants_path)
               if tenants_path else None)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    with _fleet.FleetRouter(
            artifact_dir, replicas=int(replicas) if replicas else 2,
            kind=kind or 'auto', tier=tier,
            max_queue=int(max_queue) if max_queue else None) as router:
        gw = _gateway.Gateway(
            router, host=host or '127.0.0.1', port=port,
            tenants=tenants,
            max_inflight=int(max_inflight) if max_inflight else None)
        gw.start()
        print(json.dumps({'url': gw.url, 'pid': os.getpid(),
                          'kind': router.kind}), flush=True)
        try:
            while not stop.is_set() \
                    and not gw.drain_requested.is_set():
                if stop.wait(0.2):
                    break
            # SIGTERM/drain: stop admitting, finish in-flight streams
            # (the fleet drain path closes the router after us), exit 0
            gw.drain()
        finally:
            gw.close()
    return 0


def main(argv):
    if len(argv) >= 2 and argv[1] == 'bench':
        return _bench_cli(argv)
    if len(argv) >= 2 and argv[1] == 'loop':
        return _loop_cli(argv)
    if len(argv) >= 2 and argv[1] == 'decode':
        return _decode_cli(argv)
    if len(argv) >= 2 and argv[1] == 'fleet':
        return _fleet_cli(argv)
    if len(argv) >= 2 and argv[1] == 'gateway':
        return _gateway_cli(argv)
    if len(argv) >= 2 and argv[1] == 'train':
        # serve.py train ARTIFACT_DIR FEEDS.npz OUT.npz STEPS [CKPT.npz]
        # runs STEPS train steps on the (fixed) feeds; OUT.npz holds each
        # fetch stacked over steps; CKPT.npz (optional) the final state.
        if len(argv) not in (6, 7):
            print("usage: serve.py train ARTIFACT_DIR FEEDS.npz OUT.npz "
                  "STEPS [CKPT.npz]", file=sys.stderr)
            return 2
        artifact_dir, in_path, out_path, steps = argv[2:6]
        trainer = CompiledTrainer(artifact_dir)
        with np.load(in_path) as data:
            feed = {k: data[k] for k in data.files}
        per_step = [trainer.step(feed) for _ in range(int(steps))]
        np.savez(out_path, **{
            n: np.stack([s[i] for s in per_step])
            for i, n in enumerate(trainer.get_output_names())})
        if len(argv) == 7:
            trainer.save_state(argv[6])
        return 0
    if len(argv) != 4:
        print("usage: serve.py ARTIFACT_DIR IN.npz OUT.npz\n"
              "       serve.py loop ARTIFACT_DIR IN.npz OUT.npz [GROUP]\n"
              "       serve.py train ARTIFACT_DIR FEEDS.npz OUT.npz STEPS "
              "[CKPT.npz]\n"
              "       serve.py bench ARTIFACT_DIR IN.npz N_REQUESTS "
              "[TIMEOUT_MS]\n"
              "       serve.py decode ARTIFACT_DIR PROMPTS.npz OUT.npz "
              "[MAX_NEW [BEAM]] [--tier T]\n"
              "       serve.py fleet ARTIFACT_DIR IN.npz N_REQUESTS "
              "[REPLICAS] [--tier T] [--kind K]\n"
              "       serve.py gateway ARTIFACT_DIR [PORT] [--host H] "
              "[--replicas N] [--tier T] [--kind K] "
              "[--tenants TENANTS.json]", file=sys.stderr)
        return 2
    artifact_dir, in_path, out_path = argv[1:]
    pred = CompiledPredictor(artifact_dir)
    with np.load(in_path) as data:
        raw = {k: data[k] for k in data.files}
    # LoD feeds ride npz as '<name>' plus '<name>.lod<i>' offset arrays
    feed = _feed_from_npz(pred._sig['feeds'], raw)
    outs = pred.run(feed)
    save = {}
    for n, o in zip(pred.get_output_names(), outs):
        if isinstance(o, tuple):
            save[n] = o[0]
            for i, off in enumerate(o[1]):
                save['%s.lod%d' % (n, i)] = off
        else:
            save[n] = o
    np.savez(out_path, **save)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv))
