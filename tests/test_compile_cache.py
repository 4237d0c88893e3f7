"""Persistent compile cache + AOT warm-start (core/compile_cache.py,
ISSUE 5): cross-process warm-start bit-identity, fingerprint-miss safety
(changed program / jax version / mesh must MISS, never falsely hit),
corrupt-entry loud fallback, LRU eviction, the shared LRU helper behind
CompiledProgram._opt_cache, and the cache_ctl CLI surface.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache as cc

WORKER = os.path.join(os.path.dirname(__file__), 'compile_cache_worker.py')


@pytest.fixture(autouse=True)
def _cache_off_after():
    """Tests toggle the process-wide cache overrides; every test leaves
    them cleared — and un-points the tier-3 jax persistent cache when we
    set it — so the rest of the suite runs cache-off as before."""
    yield
    cc._override_enabled = None
    cc._override_dir = None
    cc._override_max_mb = None
    if cc._pcache_dir_set is not None:
        import jax
        jax.config.update('jax_compilation_cache_dir', None)
        cc._pcache_dir_set = None
        cc._dir_ready.clear()


def _run_worker(cache_dir, out_path):
    p = subprocess.run([sys.executable, WORKER, cache_dir, out_path],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, "worker failed:\n%s\n%s" % (p.stdout, p.stderr)
    assert 'CC_OK' in p.stdout, p.stdout
    line = [l for l in p.stdout.splitlines()
            if l.startswith('CC_STATS ')][0]
    return json.loads(line[len('CC_STATS '):])


def test_cross_process_warm_start_bit_identity(tmp_path):
    """The acceptance bar: a fresh process re-running the same program
    performs ZERO XLA compiles for the cached entries (startup program,
    train step, K-step group) and its fetches are byte-identical."""
    cache = str(tmp_path / 'cache')
    cold = _run_worker(cache, str(tmp_path / 'cold.npz'))
    warm = _run_worker(cache, str(tmp_path / 'warm.npz'))

    assert cold['misses'] >= 3          # startup + run step + steps group
    assert cold['compiles'] == cold['misses']
    assert warm['misses'] == 0
    assert warm['compiles'] == 0
    assert warm['exec_hits'] == cold['misses']
    # zero REAL XLA compiles anywhere in the warm process: executable-tier
    # hits skip XLA entirely, and any stray utility jit is absorbed by the
    # jax persistent cache underneath (net = raw - pcache hits)
    assert warm['xla_compiles_net'] == 0, warm

    with np.load(str(tmp_path / 'cold.npz')) as a, \
            np.load(str(tmp_path / 'warm.npz')) as b:
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            assert a[k].tobytes() == b[k].tobytes(), \
                "fetch %r differs cold vs warm" % k


def _tiny_program(extra_op=False):
    # unique_name.guard: rebuilding the same model code must produce the
    # same var names, hence the same program desc fingerprint
    with fluid.unique_name.guard():
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            x = fluid.layers.data(name='x', shape=[4], dtype='float32')
            h = fluid.layers.fc(x, size=3)
            if extra_op:
                h = fluid.layers.relu(h)
    return prog


def test_program_fingerprint_stable_and_content_sensitive():
    # two builds of the SAME model code fingerprint identically (that is
    # what makes the cache cross-process): uid/epoch must not leak in
    fp1 = cc.program_fingerprint(_tiny_program())
    fp2 = cc.program_fingerprint(_tiny_program())
    assert fp1 == fp2
    # any op change is a different program desc
    assert cc.program_fingerprint(_tiny_program(extra_op=True)) != fp1


def test_program_fingerprint_tracks_mutation():
    prog = _tiny_program()
    fp1 = cc.program_fingerprint(prog)
    assert cc.program_fingerprint(prog) == fp1  # memoized per epoch
    with fluid.program_guard(prog, fluid.Program()):
        fluid.layers.data(name='z', shape=[2], dtype='float32')
    assert cc.program_fingerprint(prog) != fp1


def test_entry_key_misses_on_jax_version_change(monkeypatch):
    parts = ('step', 'abc', ('loss',))
    k1 = cc.entry_key((parts, cc.env_fingerprint()))
    monkeypatch.setattr(cc, '_versions',
                        lambda: ('99.99.99', '99.99.98'))
    k2 = cc.entry_key((parts, cc.env_fingerprint()))
    assert k1 != k2


def test_entry_key_misses_on_mesh_change():
    import jax
    from jax.sharding import Mesh
    devs = jax.devices('cpu')
    assert len(devs) >= 4
    m2 = Mesh(np.asarray(devs[:2]).reshape(2), ('dp',))
    m4 = Mesh(np.asarray(devs[:4]).reshape(2, 2), ('dp', 'mp'))
    parts = ('step', 'abc', ('loss',))
    k2 = cc.entry_key((parts, cc.env_fingerprint(mesh=m2)))
    k4 = cc.entry_key((parts, cc.env_fingerprint(mesh=m4)))
    kd = cc.entry_key((parts, cc.env_fingerprint(device=devs[0])))
    assert len({k2, k4, kd}) == 3


def test_entry_key_misses_on_program_change():
    env = cc.env_fingerprint()
    k1 = cc.entry_key((('step', cc.program_fingerprint(_tiny_program())),
                       env))
    k2 = cc.entry_key((('step', cc.program_fingerprint(
        _tiny_program(extra_op=True))), env))
    assert k1 != k2


def test_canon_hashes_ndarray_content():
    a = cc._canon(np.arange(1000, dtype=np.float32))
    b = cc._canon(np.arange(1000, dtype=np.float32) + 1)
    assert a != b  # repr() would truncate both to '...' and collide


def test_corrupt_entry_warns_and_recompiles(tmp_path):
    cc.enable(dir=str(tmp_path / 'c'))

    def run_once():
        # fresh build of the SAME model code: same fingerprint (warm
        # path), fresh uid/step counters (identical rng, so results are
        # comparable bit-for-bit)
        with fluid.unique_name.guard():
            prog, startup = fluid.Program(), fluid.Program()
            prog.random_seed = startup.random_seed = 5
            with fluid.program_guard(prog, startup):
                x = fluid.layers.data(name='x', shape=[4],
                                      dtype='float32')
                out = fluid.layers.fc(x, size=3, act='relu')
        scope = fluid.core.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            return exe.run(prog, feed={'x': np.ones((2, 4), np.float32)},
                           fetch_list=[out])[0]

    want = run_once()
    entries = os.path.join(str(tmp_path / 'c'), 'entries')
    names = [n for n in os.listdir(entries) if not n.endswith('.json')]
    assert names
    for n in names:  # torn/garbage writes in BOTH tiers
        with open(os.path.join(entries, n), 'wb') as f:
            f.write(b'garbage')
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        got = run_once()   # re-resolves through the corrupted entries
    assert any('compile cache' in str(x.message) for x in w), \
        "corrupt entry must fall back LOUDLY"
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_disk_lru_eviction(tmp_path):
    cc.enable(dir=str(tmp_path / 'c'), max_mb=0.02)   # ~20 KB budget
    for i in range(8):
        cc.store('k%064d' % i, exported_bytes=b'x' * 8192, tag='t')
    st = cc.disk_stats()
    assert st['bytes'] <= 0.02 * 2**20
    assert st['entries'] < 8
    assert cc.stats()['evicted'] > 0


def test_prune_clear(tmp_path):
    cc.enable(dir=str(tmp_path / 'c'))
    cc.store('k' * 64, exported_bytes=b'y' * 128, tag='t')
    assert cc.disk_stats()['entries'] == 1
    assert cc.prune(clear=True) == 1
    assert cc.disk_stats()['entries'] == 0


def test_cache_root_follows_jax_env_else_fixed_in_checkout(monkeypatch,
                                                          tmp_path):
    """The cache is placed from outside: $JAX_COMPILATION_CACHE_DIR is the
    root (jax's tier the directory itself — this module never re-points
    it — ours its entries/ subdirectory, and the cache is on); unset, the
    root is the fixed <checkout>/.compile_cache."""
    import jax
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    monkeypatch.delenv('PTPU_COMPILE_CACHE', raising=False)
    assert cc.cache_dir() == os.path.join(repo, '.compile_cache')
    assert cc._xla_dir() == os.path.join(repo, '.compile_cache', 'xla')
    assert not cc.enabled()
    root = str(tmp_path / 'placed')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', root)
    assert cc.enabled() and cc.cache_dir() == root
    assert cc._entries_dir() == os.path.join(root, 'entries')
    assert cc._xla_dir() is None          # jax's own to place and bound
    before = jax.config.jax_compilation_cache_dir
    try:
        cc._ensure_ready()
        assert jax.config.jax_compilation_cache_dir == before
        assert os.path.isdir(os.path.join(root, 'entries'))
    finally:
        cc._dir_ready.clear()


def test_tier1_entry_reloads_on_the_device_it_was_compiled_for(tmp_path):
    """A tier-1 entry compiled for device k of the 8 virtual devices
    reloads (zero compiles) and runs on device k — through the loader the
    AOT sidecars share (serve._load_executable)."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices('cpu')[3]
    cc.enable(dir=str(tmp_path / 'c'))

    def f(x):
        return x * 2 + 1
    x = jax.device_put(jnp.arange(4.0), dev)
    cold = cc.aot_or_jit(jax.jit(f), (x,), ('t',), device=dev)
    want = np.asarray(cold(x))
    cc.reset_stats()
    warm = cc.aot_or_jit(jax.jit(f), (x,), ('t',), device=dev)
    st = cc.stats()
    assert (st['exec_hits'], st['misses'], st['corrupt']) == (1, 0, 0)
    out = warm(x)
    assert out.devices() == {dev}
    np.testing.assert_array_equal(np.asarray(out), want)


def test_opt_cache_lru_capped():
    from paddle_tpu.parallel.compiler import CompiledProgram, _OPT_CACHE_MAX
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        outs = [fluid.layers.fc(x, size=2) for _ in range(12)]
    cp = CompiledProgram(prog)
    for o in outs:   # 12 distinct fetch sets > the cap
        cp._optimized_program([o.name])
    assert len(cp._opt_cache) <= _OPT_CACHE_MAX
    # most-recent fetch set still hits
    assert cp._opt_cache.get(
        (prog._uid, prog._build_epoch, (outs[-1].name,))) is not None


def test_lru_helper_semantics():
    lru = cc.LRUCache(2)
    lru.put('a', 1)
    lru.put('b', 2)
    assert lru.get('a') == 1        # refresh 'a'
    lru.put('c', 3)                 # evicts 'b', the LRU entry
    assert 'b' not in lru and 'a' in lru and 'c' in lru
    lru.filter_inplace(lambda k: k == 'c')
    assert len(lru) == 1 and 'c' in lru


def test_cache_ctl_cli(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'cache_ctl', os.path.join(os.path.dirname(__file__), '..',
                                  'tools', 'cache_ctl.py'))
    ctl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ctl)
    d = str(tmp_path / 'c')
    cc.enable(dir=d)
    cc.store('k' * 64, exported_bytes=b'z' * 64, tag='t')
    assert ctl.main(['stats', '--dir', d, '--json']) == 0
    assert ctl.main(['prune', '--dir', d, '--all']) == 0
    assert ctl.main([]) == 2                          # no subcommand
    assert ctl.main(['prewarm', str(tmp_path / 'nope')]) == 2
    assert ctl.main(['prewarm', str(tmp_path)]) == 2  # no module inside
