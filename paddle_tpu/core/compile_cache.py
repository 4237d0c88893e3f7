"""Persistent compile cache + AOT warm-start (ISSUE 5).

Every cache in the repo used to be in-memory and per-process — a fresh
process paid full trace + XLA compile for every (program, bucket, mesh)
even when the identical executable was built seconds earlier in the
previous run. This module is the on-disk, cross-process tier the ROADMAP's
serving story needs (autoscaled replicas, elastic-restarted trainers):
the same problem JAX's persistent compilation cache and TF's tfcompile/AOT
path solve upstream, specialized to the Program/Executor contract.

Three tiers, tried in order:

  1. **Executable tier** (`<key>.exec`): the XLA executable serialized via
     `jax.experimental.serialize_executable` — a warm hit skips BOTH the
     Python trace and the XLA compile (zero compiles, the AOT warm start).
  2. **StableHLO tier** (`<key>.hlo`): the `jax.export` serialization of
     the traced function — a warm hit skips the (often dominant) Python
     re-trace and still XLA-compiles. This tier also survives jaxlib
     upgrades that invalidate tier 1 (export has its own compatibility
     window).
  3. **JAX persistent compilation cache** underneath: enabled for the
     whole process when this cache is enabled, so even compiles that
     bypass this module (utility jits, the bulk-infer scan) warm-start at
     the XLA level.

Placement comes from OUTSIDE: with ``JAX_COMPILATION_CACHE_DIR`` set, that
directory is the root — tier 3 is the directory itself (jax reads the
variable; nothing here re-points it), tiers 1-2 live in its ``entries/``
subdirectory, and the cache is on. Unset, the root is the fixed
``<checkout>/.compile_cache`` (tier 3 in its ``xla/``) — never $HOME, a
temp name, a pid or a time: the path is part of jax's cache key, and a
sealed machine keeps nothing outside the checkout.

Content-addressed keys: sha256 over (serialized program desc, feed/fetch
signatures, arg avals + shardings, amp/mesh/K, rng impl + dropout bits,
jax + jaxlib versions, backend/topology, XLA_FLAGS). Anything that changes
the compiled numerics changes the key — a miss is always safe, a false hit
never happens.

Knobs: ``PTPU_COMPILE_CACHE=1`` enables (also implied by setting
``JAX_COMPILATION_CACHE_DIR``), ``PTPU_COMPILE_CACHE_MAX_MB`` bounds what
this module wrote (LRU by last-use mtime, default 512; an externally
placed tier 3 is jax's own to bound, ``jax_compilation_cache_max_size``).
Programmatic: ``enable(dir)`` / ``disable()``.

Discipline: flock-guarded writes/eviction (the elastic-journal pattern,
reader/elastic.py), atomic tmp+rename entry files, and LOUD fallback —
a corrupt or stale entry warns, is deleted, and recompiles; it never
fails the run and never silently serves garbage.

Numerics contract: within the cached world, cold and warm runs are
bit-identical — the cold path executes the very executable it persists,
and a StableHLO-tier recompile of the same module on the same
backend/version reproduces the same binary. (The cold *cached* path
compiles through ``jax.export``, which may differ in the last bit from
the uncached `jax.jit` path on some backends — the cache is opt-in
per process, never mixed mid-stream.)
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
import warnings

import numpy as np

from ..profiler import span as _span

try:
    import fcntl
except ImportError:          # non-POSIX: no advisory locking available
    fcntl = None

_SCHEMA = 1                  # bump to invalidate every entry wholesale

_override_enabled = None     # enable()/disable() beat the env
_override_dir = None
_override_max_mb = None

_stats = {
    'exec_hits': 0,          # tier-1 hits (zero trace, zero compile)
    'hlo_hits': 0,           # tier-2 hits (zero trace, one compile)
    'misses': 0,
    'compiles': 0,           # XLA compiles performed BY this cache layer
    'compile_s': 0.0,        # seconds spent tracing+compiling on miss
    'hit_load_s': 0.0,       # seconds spent deserializing on hit
    'bytes_read': 0,
    'bytes_written': 0,
    'corrupt': 0,            # entries dropped by the loud-fallback path
    'evicted': 0,
    # raw jax-wide counters (monitoring listener): every backend compile
    # in the process, and how many were served by the persistent XLA
    # cache (tier 3) — net real compiles = xla_compiles - xla_pcache_hits
    'xla_compiles': 0,
    'xla_compile_s': 0.0,
    'xla_pcache_hits': 0,
}
_stats_lock = threading.Lock()
_listener_on = False
_prof_registered = False
_dir_ready = set()


# -- knobs -------------------------------------------------------------------

_JAX_DIR_ENV = 'JAX_COMPILATION_CACHE_DIR'
# <checkout>/.compile_cache: this file is <checkout>/paddle_tpu/core/...
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.compile_cache')


def enabled():
    """Cache on? enable()/disable() override > PTPU_COMPILE_CACHE env >
    implied-on when JAX_COMPILATION_CACHE_DIR is set."""
    if _override_enabled is not None:
        return _override_enabled
    v = os.environ.get('PTPU_COMPILE_CACHE')
    if v is not None:
        return v not in ('0', 'false', 'off', '')
    return bool(os.environ.get(_JAX_DIR_ENV))


def cache_dir():
    """The cache root: enable(dir=) > $JAX_COMPILATION_CACHE_DIR > the
    fixed in-checkout path."""
    if _override_dir is not None:
        return _override_dir
    return os.environ.get(_JAX_DIR_ENV) or _DEFAULT_DIR


def max_mb():
    if _override_max_mb is not None:
        return _override_max_mb
    try:
        return float(os.environ.get('PTPU_COMPILE_CACHE_MAX_MB', '512'))
    except ValueError:
        return 512.0


def enable(dir=None, max_mb=None):
    """Turn the cache on for this process (beats the env knobs)."""
    global _override_enabled, _override_dir, _override_max_mb
    _override_enabled = True
    if dir is not None:
        _override_dir = dir
    if max_mb is not None:
        _override_max_mb = float(max_mb)
    _ensure_ready()


def disable():
    global _override_enabled
    _override_enabled = False


def _entries_dir():
    return os.path.join(cache_dir(), 'entries')


def _ensure_ready():
    """Create the cache dir, hook the jax persistent cache underneath
    (tier 3), and start the compile-event listener + profiler source."""
    d = cache_dir()
    if d not in _dir_ready:
        os.makedirs(_entries_dir(), exist_ok=True)
        _enable_jax_pcache()
        _dir_ready.add(d)
    _ensure_listener()
    _register_profiler_source()


_pcache_dir_set = None   # the xla dir THIS module configured (if any)


def _enable_jax_pcache():
    """Tier 3: JAX's own persistent compilation cache. Placed from
    outside by JAX_COMPILATION_CACHE_DIR (jax reads it; this module sets
    no other directory then). Otherwise point it at <root>/xla: set it
    when unset, RE-point it when a later enable(dir=...) moves the cache
    and the current value is one this module set — tier-3 traffic would
    else keep landing in the old dir, invisible to stats/prune."""
    global _pcache_dir_set
    import jax
    # cache everything: tiny executor steps matter here, and the default
    # min-entry/min-time thresholds would skip them
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    xla_dir = _xla_dir()
    if xla_dir is None:
        return
    cur = jax.config.jax_compilation_cache_dir
    if cur is None or (cur == _pcache_dir_set and cur != xla_dir):
        jax.config.update('jax_compilation_cache_dir', xla_dir)
        _pcache_dir_set = xla_dir


# -- compile-event counter (profiler register_compile_source feed) -----------

def _ensure_listener():
    """Count every XLA backend compile in the process via jax.monitoring.
    `/jax/core/compile/backend_compile_duration` fires even when the
    persistent XLA cache serves the compile, so the net real-compile
    count is xla_compiles - xla_pcache_hits."""
    global _listener_on
    if _listener_on:
        return
    _listener_on = True
    from jax import monitoring

    def _dur(event, secs, **kw):
        if event == '/jax/core/compile/backend_compile_duration':
            with _stats_lock:
                _stats['xla_compiles'] += 1
                _stats['xla_compile_s'] += secs

    def _ev(event, **kw):
        if event == '/jax/compilation_cache/cache_hits':
            with _stats_lock:
                _stats['xla_pcache_hits'] += 1

    monitoring.register_event_duration_secs_listener(_dur)
    monitoring.register_event_listener(_ev)


def _register_profiler_source():
    global _prof_registered
    if _prof_registered:
        return
    _prof_registered = True
    try:
        from .. import profiler
        profiler.register_compile_source('compile_cache', stats)
    except Exception:
        pass


def stats():
    """Snapshot of the cache counters (profiler compile_report contract).
    `xla_compiles_net` is the number of REAL backend compiles the process
    performed — zero on a fully warm run."""
    with _stats_lock:
        s = dict(_stats)
    s['xla_compiles_net'] = s['xla_compiles'] - s['xla_pcache_hits']
    return s


def reset_stats():
    with _stats_lock:
        for k in _stats:
            _stats[k] = 0 if not isinstance(_stats[k], float) else 0.0


# -- fingerprints ------------------------------------------------------------

def _canon(obj):
    """Canonical byte form for key hashing: dict/set order-stable, numpy
    content-hashed (repr truncates big arrays — a collision source)."""
    if isinstance(obj, dict):
        return '{%s}' % ','.join(
            '%s:%s' % (_canon(k), _canon(v)) for k, v in sorted(obj.items()))
    if isinstance(obj, (set, frozenset)):
        return '{%s}' % ','.join(sorted(_canon(x) for x in obj))
    if isinstance(obj, (list, tuple)):
        return '(%s)' % ','.join(_canon(x) for x in obj)
    if isinstance(obj, np.ndarray):
        return 'nd[%s;%s;%s]' % (obj.shape, obj.dtype,
                                 hashlib.sha256(
                                     np.ascontiguousarray(obj).tobytes()
                                 ).hexdigest())
    if isinstance(obj, (np.generic,)):
        return 'ns[%s;%r]' % (obj.dtype, obj.item())
    return repr(obj)


def quant_tag(tag, program):
    """Entry tag for `program`: '<tag>-int8' when it carries quantized
    ops (passes/quantize.py output), else `tag` unchanged. The int8 ops
    already distinguish the FINGERPRINT (they are part of the serialized
    desc); the tag split makes the quantized tier VISIBLE in the
    `cache_ctl.py stats` per-tag breakdown so a replica owner can audit
    that warm int8 programs are cached alongside the bf16 ones."""
    try:
        for b in program.blocks:
            for op in b.ops:
                if op.type.endswith('_int8'):
                    return tag + '-int8'
    except Exception:
        pass
    return tag


def program_fingerprint(program):
    """Stable content hash of the serialized program desc: blocks, ops
    (type, slots, attrs — including the per-op uid that seeds op-local
    rng streams), and var metadata. Cross-process stable, unlike the
    executor's (uid, build_epoch) in-memory key; memoized per build
    epoch on the program."""
    cached = program.__dict__.get('_ptpu_fingerprint')
    if cached is not None and cached[0] == program._build_epoch:
        return cached[1]
    h = hashlib.sha256()
    for b in program.blocks:
        h.update(('B%d<%d' % (b.idx, b.parent_idx)).encode())
        for name in sorted(b.vars):
            v = b.vars[name]
            h.update(_canon((
                'V', name, tuple(getattr(v, 'shape', ()) or ()),
                str(getattr(v, 'dtype', '')),
                bool(getattr(v, 'persistable', False)),
                int(getattr(v, 'lod_level', 0) or 0),
                bool(getattr(v, 'stop_gradient', False)),
                getattr(v, 'sharding_spec', None))).encode())
        for op in b.ops:
            h.update(_canon((
                'O', op.type, sorted(op.inputs.items()),
                sorted(op.outputs.items()),
                sorted(op.attrs.items()))).encode())
    fp = h.hexdigest()
    program.__dict__['_ptpu_fingerprint'] = (program._build_epoch, fp)
    return fp


def _versions():
    import jax
    import jaxlib
    return (jax.__version__, jaxlib.__version__)


def env_fingerprint(device=None, mesh=None):
    """Everything about the process that can change the compiled binary:
    jax/jaxlib versions, backend platform + device kind, topology
    (device/process counts; the mesh axes when compiling for one), and
    XLA_FLAGS (it carries codegen knobs and the virtual device count)."""
    import jax
    parts = [('schema', _SCHEMA), ('ver', _versions()),
             ('xla_flags', os.environ.get('XLA_FLAGS', ''))]
    if mesh is not None:
        devs = np.asarray(mesh.devices).reshape(-1)
        parts.append(('mesh', tuple(mesh.shape.items()),
                      tuple(sorted({d.device_kind for d in devs})),
                      len(devs),
                      len({d.process_index for d in devs})))
    else:
        d = device
        if d is None:
            d = jax.devices()[0]
        parts.append(('dev', d.platform, d.device_kind))
    try:
        parts.append(('nproc', jax.process_count()))
    except RuntimeError:
        parts.append(('nproc', 1))
    return tuple(parts)


def args_signature(args):
    """Aval + sharding signature of a concrete arg pytree — the same
    information jit keys its own C++ cache on."""
    import jax
    leaves, treedef = jax.tree.flatten(args)
    sig = []
    for x in leaves:
        srd = getattr(x, 'sharding', None)
        sig.append((tuple(getattr(x, 'shape', ()) or ()),
                    str(getattr(x, 'dtype', type(x).__name__)),
                    str(srd) if srd is not None else ''))
    return (str(treedef), tuple(sig))


def entry_key(parts):
    """Content-addressed entry name: sha256 over the canonical parts."""
    return hashlib.sha256(_canon(parts).encode()).hexdigest()


# -- on-disk entries ---------------------------------------------------------

def _paths(key):
    base = os.path.join(_entries_dir(), key)
    return base + '.exec', base + '.hlo', base + '.json'


class _flocked(object):
    """Exclusive flock on entries/.lock around writes/eviction — the
    elastic-journal discipline (reader/elastic.py): concurrent replicas
    warming one shared cache dir must not interleave eviction with a
    half-written entry. Filesystems without flock degrade to unlocked
    (atomic tmp+rename still keeps readers safe)."""

    def __init__(self):
        self._f = None

    def __enter__(self):
        if fcntl is None:
            return self
        try:
            self._f = open(os.path.join(_entries_dir(), '.lock'), 'a+')
            fcntl.flock(self._f, fcntl.LOCK_EX)
        except OSError:
            if self._f is not None:
                self._f.close()
            self._f = None
        return self

    def __exit__(self, *exc):
        if self._f is not None:
            try:
                fcntl.flock(self._f, fcntl.LOCK_UN)
            except OSError:
                pass
            self._f.close()


def _atomic_write(path, data):
    tmp = '%s.tmp-%d' % (path, os.getpid())
    with open(tmp, 'wb') as f:
        f.write(data)
    os.replace(tmp, path)
    return len(data)


def _drop_entry(key, reason=None):
    """Delete an entry's files; with `reason`, this is the loud-fallback
    path (corrupt/stale entry — warn, drop, recompile)."""
    if reason is not None:
        warnings.warn('compile cache entry %s...: %s — dropping it and '
                      'recompiling' % (key[:12], reason), RuntimeWarning)
        with _stats_lock:
            _stats['corrupt'] += 1
    for p in _paths(key):
        try:
            os.remove(p)
        except OSError:
            pass


def _touch(key):
    now = time.time()
    for p in _paths(key):
        try:
            os.utime(p, (now, now))
        except OSError:
            pass


def load(key, donate_argnums=()):
    """Load an entry: tier-1 executable (zero compile), else tier-2
    StableHLO (compiles, skips re-trace). None on miss. Corrupt entries
    drop loudly and return None. `donate_argnums`: the caller's
    certified donation plan — the tier-2 recompile applies it (a fresh
    bookkept jit, so it is safe where a reloaded tier-1 alias is not),
    keeping the warm-path copy recovery alive across jaxlib bumps."""
    exec_p, hlo_p, _meta_p = _paths(key)
    t0 = time.perf_counter()
    if os.path.exists(exec_p):
        try:
            from ..inference import serve as _serve
            with _span('compile/load_exec', key=key[:12]):
                with open(exec_p, 'rb') as f:
                    blob = f.read()
                # the one loader shared with the AOT sidecars: onto the
                # client and devices the entry was compiled for
                fn = _serve._load_executable(pickle.loads(blob))
            with _stats_lock:
                _stats['exec_hits'] += 1
                _stats['bytes_read'] += len(blob)
                _stats['hit_load_s'] += time.perf_counter() - t0
            _touch(key)
            return fn
        except Exception as e:
            # e.g. a jaxlib bump: the executable format is not stable
            # across versions even though the key matched a hash race —
            # drop tier 1, fall through to tier 2
            _drop_entry_file(exec_p)
            warnings.warn('compile cache entry %s...: executable tier '
                          'unusable (%s: %s) — falling back to the '
                          'StableHLO tier' % (key[:12], type(e).__name__,
                                              e), RuntimeWarning)
            with _stats_lock:
                _stats['corrupt'] += 1
    if os.path.exists(hlo_p):
        try:
            import jax
            from jax import export as jexport
            with open(hlo_p, 'rb') as f:
                blob = f.read()
            from ..inference.serve import _named_call
            exp = jexport.deserialize(blob)
            fn = jax.jit(_named_call(exp),
                         donate_argnums=tuple(donate_argnums or ()))
            with _stats_lock:
                _stats['hlo_hits'] += 1
                _stats['bytes_read'] += len(blob)
                _stats['hit_load_s'] += time.perf_counter() - t0
            _touch(key)
            return fn
        except Exception as e:
            _drop_entry(key, 'StableHLO tier unusable (%s: %s)'
                        % (type(e).__name__, e))
    return None


def _drop_entry_file(path):
    try:
        os.remove(path)
    except OSError:
        pass


def store(key, compiled=None, exported_bytes=None, tag='program',
          donated=False):
    """Persist an entry (either tier may be absent) and LRU-evict over
    budget. Write failures warn and are non-fatal — the cache never
    breaks the run."""
    wrote = 0
    exec_p, hlo_p, meta_p = _paths(key)
    try:
        with _flocked():
            if compiled is not None:
                try:
                    from ..inference import serve as _serve
                    wrote += _atomic_write(
                        exec_p,
                        pickle.dumps(_serve._pack_executable(compiled)))
                except Exception as e:
                    # backend without executable serialization: tier-2 only
                    warnings.warn('compile cache: executable tier '
                                  'unavailable (%s: %s); storing StableHLO '
                                  'only' % (type(e).__name__, e),
                                  RuntimeWarning)
            if exported_bytes is not None:
                wrote += _atomic_write(hlo_p, exported_bytes)
            if wrote:
                meta = {'tag': tag, 'created': time.time(),
                        'ver': list(_versions()), 'schema': _SCHEMA,
                        'donated': bool(donated)}
                wrote += _atomic_write(
                    meta_p, json.dumps(meta).encode())
                with _stats_lock:
                    _stats['bytes_written'] += wrote
                _evict_over_budget(keep=key)
    except Exception as e:
        warnings.warn('compile cache: store failed (%s: %s)'
                      % (type(e).__name__, e), RuntimeWarning)
    return wrote


def _entry_index():
    """{key: (bytes, last_use_mtime)} over the entries dir."""
    idx = {}
    d = _entries_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return idx
    for n in names:
        stem, dot, ext = n.rpartition('.')
        if ext not in ('exec', 'hlo', 'json') or not stem:
            continue
        try:
            st = os.stat(os.path.join(d, n))
        except OSError:
            continue
        b, m = idx.get(stem, (0, 0.0))
        idx[stem] = (b + st.st_size, max(m, st.st_mtime))
    return idx


def _xla_dir():
    """The tier-3 dir this module places and bounds, <root>/xla — or None
    when JAX_COMPILATION_CACHE_DIR places tier 3 (at the root itself):
    that directory is jax's own to fill and bound."""
    if _override_dir is None and os.environ.get(_JAX_DIR_ENV):
        return None
    return os.path.join(cache_dir(), 'xla')


def _xla_index():
    """{path: (bytes, mtime)} over the tier-3 dir when this module placed
    it — those bytes count against the SAME budget (the MAX_MB claim must
    hold for everything the module put on disk, not just entries/)."""
    idx = {}
    d = _xla_dir()
    if d is None:
        return idx
    try:
        names = os.listdir(d)
    except OSError:
        return idx
    for n in names:
        p = os.path.join(d, n)
        try:
            st = os.stat(p)
        except OSError:
            continue
        if os.path.isfile(p):
            idx[p] = (st.st_size, st.st_mtime)
    return idx


def _sweep_stale_tmp(max_age_s=3600.0):
    """Remove *.tmp-<pid> orphans a killed process left behind (the
    elastic-restart scenario): invisible to the entry index, so without
    this sweep they would accumulate unbounded. Age-gated so an in-flight
    write in another process is never torn."""
    n = 0
    cutoff = time.time() - max_age_s
    for d in filter(None, (_entries_dir(), _xla_dir())):
        try:
            names = os.listdir(d)
        except OSError:
            continue
        for name in names:
            if '.tmp-' not in name:
                continue
            p = os.path.join(d, name)
            try:
                if os.path.isfile(p) and os.stat(p).st_mtime < cutoff:
                    os.remove(p)
                    n += 1
            except OSError:
                pass
    return n


def _evict_over_budget(keep=None, budget_mb=None):
    """LRU eviction by last-use mtime (reads _touch their entry) down to
    the byte budget, across entries/ AND the tier-3 xla dir. Caller holds
    the flock."""
    budget = (max_mb() if budget_mb is None else float(budget_mb)) * 2**20
    _sweep_stale_tmp()
    idx = _entry_index()
    xla = _xla_index()
    total = sum(b for b, _ in idx.values()) + sum(b for b, _ in xla.values())
    if total <= budget:
        return 0
    items = [(m, 'entry', k, b) for k, (b, m) in idx.items()] \
        + [(m, 'xla', p, b) for p, (b, m) in xla.items()]
    n = 0
    for m, kind, ident, b in sorted(items):
        if total <= budget:
            break
        if kind == 'entry':
            if ident == keep:
                continue
            _drop_entry(ident)
        else:
            try:
                os.remove(ident)
            except OSError:
                continue
        total -= b
        n += 1
    with _stats_lock:
        _stats['evicted'] += n
    return n


def prune(budget_mb=None, clear=False):
    """CLI/maintenance eviction: down to `budget_mb` (default: the
    configured budget), or everything — entries, tier-3 xla files, and
    stale tmp orphans — with clear=True. Returns items removed."""
    _ensure_ready()
    with _flocked():
        if clear:
            n = _sweep_stale_tmp(max_age_s=0.0)
            idx = _entry_index()
            for key in idx:
                _drop_entry(key)
            for p in _xla_index():
                try:
                    os.remove(p)
                    n += 1
                except OSError:
                    pass
            with _stats_lock:
                _stats['evicted'] += len(idx) + n
            return len(idx) + n
        return _evict_over_budget(budget_mb=budget_mb)


def disk_stats():
    """On-disk view (tools/cache_ctl.py stats): entry count, bytes (split
    entries vs tier-3 xla), per-tag breakdown, oldest/newest last use."""
    _ensure_ready()
    idx = _entry_index()
    tags = {}
    for key in idx:
        meta_p = _paths(key)[2]
        tag = '?'
        try:
            with open(meta_p) as f:
                tag = json.load(f).get('tag', '?')
        except (OSError, ValueError):
            pass
        tags[tag] = tags.get(tag, 0) + 1
    mts = [m for _, m in idx.values()]
    ebytes = sum(b for b, _ in idx.values())
    xbytes = sum(b for b, _ in _xla_index().values())
    return {'dir': cache_dir(), 'entries': len(idx),
            'bytes': ebytes, 'xla_bytes': xbytes,
            'total_bytes': ebytes + xbytes,
            'max_mb': max_mb(), 'tags': tags,
            'oldest_use': min(mts) if mts else None,
            'newest_use': max(mts) if mts else None}


# -- the main entry: AOT-or-jit ----------------------------------------------

def aot_or_jit(jitted, args, key_parts, tag='program', fun=None,
               device=None, mesh=None, use_export=None,
               donate_argnums=None):
    """Warm-start for the avals of `args`, or compile-and-persist.

    Returns a callable with jitted's calling convention:
      * cache disabled -> `jitted` unchanged (the zero-risk path);
      * tier-1 hit     -> the deserialized executable (NO trace, NO
                          compile);
      * tier-2 hit     -> jit of the deserialized StableHLO (no re-trace,
                          one compile — which tier 3 may itself absorb);
      * miss           -> traces ONCE through jax.export, compiles, stores
                          both tiers, and returns the compiled executable
                          (so the cold run executes the exact binary the
                          warm run will load — bit-identity by
                          construction).

    `key_parts` must carry every trace-time input that is not visible in
    the arg avals (program fingerprint, fetch names, amp/K/rng flags);
    avals/shardings and the env fingerprint are appended here.

    DONATION: by default cached executables compile WITHOUT input
    donation, from `fun` (the raw step callable) when given. A
    serialized-then-reloaded executable keeps its XLA input/output
    aliasing but jax's buffer bookkeeping no longer knows the args were
    donated — the computation then scribbles over buffers the caller
    still holds (measured: nondeterministic fetches / NaN on the
    composed mesh programs). Correctness beats the one extra state copy
    — UNLESS the caller proves safety: pass `donate_argnums` only with
    a dataflow donation certificate (passes/dataflow.certify_donation)
    showing no caller-visible buffer aliases the donated args. Donated
    and undonated entries never collide (the donation plan is part of
    the entry key), the meta records `donated` for doctor/cache_ctl
    visibility, and a donated compile that fails falls back to the
    undonated path loudly.

    `use_export`: whether the miss path serializes through jax.export
    (both tiers) or direct-compiles (tier 1 only). Default: export for
    single-device programs, direct for mesh programs — jax.export does
    not faithfully round-trip every manual-collective pattern the
    composed mesh programs use (shard_map pipelines), and a wrong-answer
    cache would be worse than no cache.
    """
    if not enabled():
        return jitted
    _ensure_ready()
    import jax
    if use_export is None:
        use_export = mesh is None
    donate = tuple(donate_argnums or ())
    if donate and mesh is not None:
        donate = ()  # round-8 NaN cliff: mesh programs never donate
    key = entry_key((tag, key_parts, args_signature(args),
                     env_fingerprint(device=device, mesh=mesh),
                     ('donate', donate)))
    fn = load(key, donate_argnums=donate)
    if fn is not None:
        return fn
    with _stats_lock:
        _stats['misses'] += 1
    t0 = time.perf_counter()
    # the undonated jit the cached tier-2 module exports from (docstring);
    # tier 1 compiles WITH certified donation so the serialized
    # executable carries the state aliasing (warm runs skip the copy)
    cache_jit = jax.jit(fun) if fun is not None else jitted
    exported_bytes = None
    compiled = None
    donated = False
    # fresh_compile: the executable below goes to store()'s tier 1 via
    # serialize_executable — on cpu a tier-3-satisfied compile would
    # serialize into a blob no other process can run
    from ..inference.serve import _fresh_compile, _named_call
    if mesh is not None:
        platform = mesh.devices.flat[0].platform
    else:
        platform = (device or jax.devices()[0]).platform
    if use_export:
        try:
            from jax import export as jexport
            # for the platform compiled FOR: export's default is the
            # process's default backend, which a cpu executor on a TPU
            # host is not on
            exp = jexport.export(cache_jit, platforms=[platform])(*args)
            exported_bytes = exp.serialize()
            with _fresh_compile(platform), \
                    _span('compile/xla', key=key[:12]):
                compiled, donated = _compile_maybe_donated(
                    jax, _named_call(exp), donate, args)
        except Exception:
            exported_bytes = None
            compiled = None
    if compiled is None:
        # programs jax.export cannot carry (host callbacks, exotic
        # shardings): direct AOT compile — tier 1 only
        try:
            with _fresh_compile(platform), \
                    _span('compile/xla', key=key[:12]):
                if donate and fun is not None:
                    compiled, donated = _compile_maybe_donated(
                        jax, fun, donate, args)
                else:
                    compiled = cache_jit.lower(*args).compile()
        except TypeError:
            # a backend/jit wrapper without .lower: give up on caching
            return jitted
    with _stats_lock:
        _stats['compiles'] += 1
        _stats['compile_s'] += time.perf_counter() - t0
    # `donated` is the OUTCOME, not the request: a donated compile that
    # fell back stores donated=False so doctor/cache_ctl/smoke guards
    # never report a recovery that did not happen
    store(key, compiled=compiled, exported_bytes=exported_bytes, tag=tag,
          donated=donated)
    return compiled


def _compile_maybe_donated(jax, fn, donate, args):
    """AOT-compile `fn`, donating `donate` argnums when certified;
    returns (compiled, donated_outcome). A donated compile that fails
    warns and falls back to undonated (the copy tax returns,
    correctness never leaves)."""
    if donate:
        try:
            return (jax.jit(fn, donate_argnums=donate).lower(
                *args).compile(), True)
        except Exception as e:
            warnings.warn(
                'compile cache: donated compile failed (%s: %s) — '
                'falling back to the undonated executable (one extra '
                'state copy per step)' % (type(e).__name__, e),
                RuntimeWarning)
    return jax.jit(fn).lower(*args).compile(), False


# -- shared in-memory LRU helper ---------------------------------------------

class LRUCache(object):
    """Tiny insertion/access-ordered LRU (dict preserves order; move-to-end
    on hit) — the in-memory sibling of the on-disk eviction above, shared
    with CompiledProgram._opt_cache (parallel/compiler.py)."""

    def __init__(self, maxsize):
        self.maxsize = int(maxsize)
        self._d = {}

    def get(self, key, default=None):
        if key not in self._d:
            return default
        val = self._d.pop(key)
        self._d[key] = val
        return val

    def put(self, key, val):
        self._d.pop(key, None)
        self._d[key] = val
        while len(self._d) > self.maxsize:
            self._d.pop(next(iter(self._d)))

    def filter_inplace(self, keep):
        """Drop entries whose key fails `keep(key)` (epoch turnover)."""
        for k in [k for k in self._d if not keep(k)]:
            del self._d[k]

    def __len__(self):
        return len(self._d)

    def __contains__(self, key):
        return key in self._d
