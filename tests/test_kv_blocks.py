"""Block-granular KV-cache management (ISSUE 13): BlockManager edge
cases — refcount-to-zero frees, copy-on-write ownership, prefix-hash
collision safety, LRU eviction under pressure — plus the served block
tier: what the parent's block artifacts served (greedy, beam, chunked
prefill, int8 pages), the float tier's logits against the plain
reference's full forward pass, CoW under beam divergence at block
boundaries, and prefix sharing's capacity effect."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, export_decode
from paddle_tpu.inference.kv_blocks import (BlockManager,
                                            BlockPoolExhausted,
                                            TRASH_BLOCK, WindowTable,
                                            window_blocks_per_slot)

VOCAB, SLOTS, CACHE = 41, 4, 64


# -- allocator units ---------------------------------------------------------

def test_capacity_excludes_trash_block():
    m = BlockManager(num_blocks=8, block_size=4)
    assert m.capacity() == 7
    assert m.free_blocks() == 7
    got = m.alloc(7)
    assert TRASH_BLOCK not in got
    assert sorted(got) == list(range(1, 8))
    with pytest.raises(ValueError):
        BlockManager(num_blocks=1, block_size=4)


def test_refcount_to_zero_frees():
    m = BlockManager(num_blocks=6, block_size=2)
    blocks = m.alloc(3)
    m.incref(blocks)                      # share (beam fork)
    m.decref(blocks)
    assert m.free_blocks() == 2           # still referenced once
    assert m.in_use() == 3
    m.decref(blocks)                      # refcount-to-zero
    assert m.free_blocks() == 5
    assert m.in_use() == 0
    st = m.stats()
    assert st['allocs'] == 3 and st['frees'] == 3
    # freed blocks are allocatable again
    assert sorted(m.alloc(5)) == sorted(range(1, 6))


def test_double_free_and_foreign_incref_raise():
    m = BlockManager(num_blocks=4, block_size=2)
    b = m.alloc(1)
    m.decref(b)
    with pytest.raises(RuntimeError, match='double free'):
        m.decref(b)
    with pytest.raises(RuntimeError, match='unallocated'):
        m.incref(b)
    # trash block refs are ignored, never counted
    m.incref([TRASH_BLOCK])
    m.decref([TRASH_BLOCK])
    assert m.refcount(TRASH_BLOCK) == 0


def test_writable_is_sole_ownership():
    m = BlockManager(num_blocks=4, block_size=2)
    b = m.alloc(1)[0]
    assert m.writable(b)
    m.incref([b])                         # shared: fork / prefix hit
    assert not m.writable(b)              # must copy-on-write
    m.decref([b])
    assert m.writable(b)
    assert not m.writable(TRASH_BLOCK)    # trash is never writable


def test_alloc_all_or_nothing_when_pinned():
    m = BlockManager(num_blocks=4, block_size=2)
    m.alloc(2)
    with pytest.raises(BlockPoolExhausted):
        m.alloc(2)                        # only 1 free, nothing evictable
    assert m.free_blocks() == 1           # failed alloc leaked nothing
    assert m.alloc(1)


def test_prefix_register_match_and_refcounts():
    m = BlockManager(num_blocks=16, block_size=4)
    tokens = list(range(100, 111))        # 11 tokens = 2 full blocks + 3
    blocks = m.alloc(3)
    m.register_prefix(tokens, blocks)     # publishes 1- and 2-block entries
    assert m.prefix_entries() == 2
    # full prompt released: prefix refs keep the FULL blocks alive
    m.decref(blocks)
    assert m.in_use() == 2                # tail block freed, 2 pinned
    shared, covered = m.match_prefix(tokens)
    assert covered == 8 and shared == blocks[:2]
    st = m.stats()
    assert st['prefix_hits'] == 1 and st['prefix_tokens_reused'] == 8
    # shorter prompt sharing only the first block hits the 1-block entry
    shared1, covered1 = m.match_prefix(tokens[:4] + [7, 8])
    assert covered1 == 4 and shared1 == blocks[:1]
    # a prompt the cache covers ENTIRELY still leaves its last token
    # uncovered: the admitting request must compute first-token logits
    sh, cov = m.match_prefix(tokens[:8])
    assert cov == 4 and sh == blocks[:1]
    m.decref(shared + shared1 + sh)
    assert m.in_use() == 2


def test_prefix_hash_collision_never_aliases():
    # force EVERY key onto one bucket: a colliding entry whose tokens
    # differ must be a miss, never an alias onto foreign blocks
    m = BlockManager(num_blocks=16, block_size=2,
                     hash_fn=lambda b: 'same')
    a = m.alloc(2)
    m.register_prefix([1, 2, 3, 4], a)
    b = m.alloc(2)
    m.register_prefix([9, 8, 7, 6], b)
    sh_a, cov_a = m.match_prefix([1, 2, 3, 4, 5])
    sh_b, cov_b = m.match_prefix([9, 8, 7, 6, 5])
    assert (sh_a, cov_a) == (a, 4)
    assert (sh_b, cov_b) == (b, 4)
    miss, cov = m.match_prefix([2, 1, 8, 9, 5])
    assert (miss, cov) == ([], 0)
    assert m.stats()['prefix_misses'] == 1


def test_lru_eviction_under_pressure():
    m = BlockManager(num_blocks=9, block_size=2)
    a, b = m.alloc(2), m.alloc(2)
    m.register_prefix([1, 2, 3, 4], a)
    m.register_prefix([5, 6, 7, 8], b)
    m.decref(a)
    m.decref(b)                           # both live only via the cache
    assert m.in_use() == 4 and m.free_blocks() == 4
    m.match_prefix([1, 2, 3, 4, 0])       # touch a: b becomes LRU
    m.decref(a)                           # drop the match's refs again
    got = m.alloc(6)                      # needs eviction to cover
    assert len(got) == 6
    st = m.stats()
    assert st['evictions'] >= 1
    # a (recently used) survived where possible; b evicted first
    sh, cov = m.match_prefix([5, 6, 7, 8, 0])
    assert (sh, cov) == ([], 0)


def test_reserve_preflight_contract():
    m = BlockManager(num_blocks=6, block_size=2)
    a = m.alloc(2)
    m.register_prefix([1, 2, 3, 4], a)
    m.decref(a)                           # evictable
    assert m.reserve(5)                   # evicts the prefix entry
    for _ in range(5):
        m.alloc(1)                        # cannot fail after reserve
    assert not m.reserve(1)               # fully pinned now
    m.alloc(1) if m.free_blocks() else None
    with pytest.raises(BlockPoolExhausted):
        m.alloc(1)


def test_evict_all_and_stats_keys():
    m = BlockManager(num_blocks=8, block_size=2)
    a = m.alloc(2)
    m.register_prefix([1, 2, 3, 4], a)
    m.decref(a)
    m.evict_all_prefixes()
    assert m.prefix_entries() == 0 and m.in_use() == 0
    st = m.stats()
    for k in ('num_blocks', 'block_size', 'blocks_in_use', 'blocks_peak',
              'blocks_free', 'allocs', 'frees', 'prefix_entries',
              'prefix_hits', 'prefix_misses', 'prefix_hit_rate',
              'prefix_tokens_reused', 'evictions'):
        assert k in st, k


# -- the window layers' pool (ISSUE 30) ---------------------------------------

@pytest.mark.parametrize('window,chunk,bs,plen,new', [
    (128, 512, 16, 6000, 300), (128, 512, 16, 24, 200), (16, 16, 8, 70, 40),
    (128, 128, 16, 127, 20), (100, 48, 16, 1000, 150)])
def test_window_table_holds_its_window_and_nothing_below(window, chunk, bs,
                                                         plen, new):
    """A request prefilled in slices and then decoded, as the scheduler
    drives it: before every dispatch the table names the blocks of every
    position a query of that dispatch attends, and never more than
    ceil((window + C) / BS) + 1 blocks; whatever it gave back is free for
    another request at once."""
    bound = window_blocks_per_slot(window, chunk, bs)
    assert bound == -(-(window + chunk) // bs) + 1
    m = BlockManager(8, bs, window=(2 * bound + 1, window))
    t, other = WindowTable(), WindowTable()
    seen_peak = 0

    def dispatch(first, end):
        nonlocal seen_peak
        m.window_advance(t, first - window + 1, end)
        lo = max(first - window + 1, 0) // bs
        assert t.first <= lo and t.first + len(t.blocks) == (end - 1) // bs + 1
        assert len(t.blocks) <= bound
        assert TRASH_BLOCK not in t.blocks
        assert len(set(t.blocks) | set(other.blocks)) \
            == len(t.blocks) + len(other.blocks)
        seen_peak = max(seen_peak, len(t.blocks))
        row = np.zeros(4096, np.int32)
        t.fill(row)
        assert list(row[t.first:t.first + len(t.blocks)]) == t.blocks
        assert not row[:t.first].any()

    start = 0
    while start < plen:
        take = min(chunk, plen - start)
        dispatch(start, start + take)
        start += take
        # a second request lives in what is free meanwhile
        m.window_advance(other, start - window + 1, start + 1)
    for p in range(plen, plen + new):
        dispatch(p, p + 1)
    if plen >= chunk + window:
        assert seen_peak >= bound - 1           # the bound is reached
    st = m.stats()
    assert st['window_blocks_in_use'] == len(t.blocks) + len(other.blocks)
    assert st['window_blocks_peak'] <= 2 * bound
    m.window_free(t)
    m.window_free(other)
    st = m.stats()
    assert st['window_blocks_in_use'] == 0
    assert st['window_blocks_released'] > 0 or plen + new <= window
    # the full layers' pool never moved
    assert st['blocks_in_use'] == 0 and st['allocs'] == 0


def test_window_pool_is_apart_from_the_full_pool_and_refuses_prefixes():
    m = BlockManager(6, 4, window=(4, 8))
    full = m.alloc(5)
    t = WindowTable()
    m.window_advance(t, 0, 12)                   # 3 blocks: the whole pool
    assert m.stats()['window_blocks_in_use'] == 3
    assert m.stats()['blocks_in_use'] == 5
    with pytest.raises(BlockPoolExhausted, match='window pool'):
        m.window_advance(WindowTable(), 0, 1)
    with pytest.raises(ValueError, match='prefix reuse is refused'):
        m.match_prefix(list(range(20)))
    with pytest.raises(ValueError, match='prefix reuse is refused'):
        m.register_prefix(list(range(20)), full)
    m.reset_counters()
    assert m.stats()['window_blocks_peak'] == 3
    assert 'window_blocks_in_use' not in BlockManager(6, 4).stats()


def test_doomed_alloc_does_not_wipe_prefix_cache():
    """An over-capacity alloc whose shortfall eviction CANNOT cover
    (every prefix entry's blocks also table-pinned) must fail without
    evicting anything: wiping the cache would trade the prefix-sharing
    capacity win for zero freed blocks."""
    m = BlockManager(num_blocks=6, block_size=2)
    a = m.alloc(3)
    m.register_prefix([1, 2, 3, 4, 5, 6], a)   # entries share pinned blocks
    m.alloc(2)                                 # pool now fully pinned
    with pytest.raises(BlockPoolExhausted):
        m.alloc(1)
    assert m.prefix_entries() == 3             # cache survived the miss
    assert not m.reserve(1)
    assert m.prefix_entries() == 3
    m.decref(a)   # table gone: entries alone hold the prefix blocks
    got, cov = m.match_prefix([1, 2, 3, 4, 5, 6, 7])
    assert cov == 6 and got == a


# -- served block tier -------------------------------------------------------

_MODEL = dict(vocab=VOCAB, d_model=16, n_head=2, n_layer=2, d_ff=32)


def _build(tmp, weights=None, **kw):
    """Export one artifact; `weights` (a dict) receives the scope's
    parameters by name, for the plain reference."""
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(
            max_slots=SLOTS, max_cache_len=CACHE, eos_id=1,
            **dict(_MODEL, **kw))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'])
        if weights is not None:
            weights.update((n, np.asarray(scope.get(n)))
                           for n in scope.local_var_names()
                           if n not in spec['cache_vars'])
        export_decode(spec, tmp, scope=scope)
    return tmp


@pytest.fixture(scope='module')
def arts(tmp_path_factory):
    """The f32 and the int8 tier of the same tiny LM, and the f32
    tier's weights by name (the plain reference's input)."""
    t = tmp_path_factory.mktemp('kvblocks')
    weights = {}
    return {
        'block': _build(str(t / 'block'), weights=weights,
                        chunk_sizes=(4, 8), block_size=4),
        'block8': _build(str(t / 'block8'), chunk_sizes=(4, 8),
                         block_size=4, kv_cache_dtype='int8'),
        'weights': weights,
    }


def _prompts(seed, n, lo=2):
    rng = np.random.RandomState(seed)
    return [rng.randint(lo, VOCAB, int(rng.randint(2, 9)))
            for _ in range(n)]


# What the PARENT commit's block artifacts (PR 27, the last tree with a
# slot tier, where each of these equalled the slot tier bit for bit)
# served for the requests below: greedy transcripts, and per beam request
# (ids [beam, n], scores [beam]).
_PARENT_GREEDY = [[40, 40, 40, 40, 40, 40, 40, 40, 40, 40],
                  [29, 29, 17, 16, 16, 40, 16, 40, 16, 16],
                  [23, 40, 23, 23, 16, 40, 40, 40, 40, 23],
                  [40, 40, 40, 23, 40, 40, 40, 40, 40, 40],
                  [29, 29, 29, 29, 29, 29, 29, 29, 29, 29],
                  [29, 16, 16, 16, 16, 16, 16, 16, 16, 16],
                  [16, 16, 16, 16, 16, 16, 16, 16, 16, 16],
                  [17, 40, 40, 40, 40, 40, 23, 40, 40, 40]]
_PARENT_BEAM = [([[40, 40, 40, 40, 40, 40, 40, 40],
                  [10, 40, 40, 40, 40, 40, 40, 40],
                  [40, 40, 40, 40, 40, 23, 40, 40]],
                 [-12.456114752830457, -13.189863539932261, -13.579984728514328]),
                ([[1, 1, 1, 1, 1, 1, 1, 1],
                  [16, 40, 16, 16, 16, 16, 40, 23],
                  [16, 40, 16, 16, 16, 16, 40, 16]],
                 [-2.932869733489811, -17.69957593421799, -17.77937570552689]),
                ([[23, 40, 23, 23, 16, 40, 40, 40],
                  [23, 40, 23, 16, 40, 23, 40, 40],
                  [23, 40, 23, 16, 40, 40, 40, 40]],
                 [-17.11012598352386, -17.181376874652884, -17.47598545129889])]
_PARENT_COW = [([[16, 16, 16, 16, 16, 16, 16, 16, 16, 16],
                 [16, 16, 16, 16, 16, 16, 16, 16, 16, 23],
                 [16, 16, 16, 16, 16, 16, 16, 16, 23, 16]],
                [-20.296402014762137, -20.41649977114794, -20.572828420454236]),
               ([[40, 23, 40, 23, 40, 40, 40, 40, 23, 23],
                 [40, 23, 40, 23, 40, 40, 40, 23, 11, 26],
                 [40, 40, 23, 40, 40, 40, 40, 23, 11, 26]],
                [-20.952905869109905, -20.985735012385927, -20.993592360285092])]
_PARENT_INT8_GREEDY = [[20, 20, 20, 20, 20, 20, 20, 20, 20, 20],
                       [31, 23, 18, 18, 31, 31, 31, 31, 31, 31],
                       [20, 20, 20, 20, 20, 20, 20, 20, 20, 20],
                       [27, 18, 20, 21, 31, 31, 31, 31, 20, 20],
                       [18, 31, 23, 7, 21, 31, 20, 20, 20, 20],
                       [7, 31, 20, 20, 20, 20, 20, 20, 20, 20]]
_PARENT_INT8_BEAM = [([[20, 20, 20, 20, 20, 20, 20, 20],
                       [31, 20, 20, 20, 20, 20, 20, 20],
                       [20, 20, 20, 20, 20, 20, 20, 31]],
                      [-17.066645254289973, -17.794964544832048, -17.975224365389217])]


def _assert_beams(got, want):
    assert len(got) == len(want)
    for (ids, scores), (want_ids, want_scores) in zip(got, want):
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(scores, want_scores)


def test_block_artifact_layout(arts):
    from paddle_tpu.inference import decoding
    with open(os.path.join(arts['block'],
                           decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    assert sig['layout'] == 'block'
    blk = sig['block']
    assert blk['block_size'] == 4
    assert blk['max_blocks_per_slot'] == CACHE // 4
    assert blk['num_blocks'] == SLOTS * (CACHE // 4) + 1
    for e in sig['state'][:-1]:
        assert e['shape'][:2] == [blk['num_blocks'], 4]
    # the last entry is no pool: each slot's last id (version 6)
    assert sig['state'][-1]['shape'] == [SLOTS]
    for d in ([decoding._STEP_DIR, decoding._ZEROS_DIR,
               decoding._BLOCKCOPY_DIR] +
              [decoding._CHUNK_DIR % c for c in sig['chunk_buckets']]):
        assert os.path.exists(os.path.join(arts['block'], d,
                                           'module.jaxexport'))
        assert os.path.exists(os.path.join(arts['block'], d,
                                           'aot_cpu.jaxexec'))


# -- one cache layout --------------------------------------------------------

def test_op_registry_holds_the_block_ops_alone():
    """12 = {fp, quant} x {step, chunk, verify} x {write, attention}, and
    no op or layer of the slot tier."""
    from paddle_tpu.core import registry
    kv = sorted(n for n in registry.registered_ops() if n.startswith('kv_'))
    assert len(kv) == 12 and all(n.startswith('kv_block_') for n in kv)
    assert not [n for n in dir(fluid.layers) if n.startswith('kv_cache_')]


def test_builder_default_is_a_pool_of_16_row_pages():
    from models.transformer import build_decode_spec
    with fluid.scope_guard(fluid.core.Scope()), fluid.unique_name.guard():
        spec = build_decode_spec()
    assert spec['block_size'] == 16 and 'layout' not in spec
    maxb = spec['max_blocks_per_slot']
    assert maxb == -(-spec['max_cache_len'] // 16)
    assert spec['num_blocks'] == spec['max_slots'] * maxb + 1
    assert sorted(spec['chunk']) == [8, 16] and 'prefill' not in spec
    pool = spec['step']['program'].global_block().var('kv_k_0')
    assert list(pool.shape)[:2] == [spec['num_blocks'], 16]
    with pytest.raises(TypeError):
        build_decode_spec(prompt_buckets=(8, 16))


@pytest.mark.parametrize('layout', [None, 'slot'])
def test_a_slot_artifact_is_refused_by_name(arts, tmp_path, layout):
    """A signature without 'layout' is what the slot tier wrote."""
    import shutil
    from paddle_tpu.inference import decoding
    art = str(tmp_path / 'slot')
    shutil.copytree(arts['block'], art)
    path = os.path.join(art, decoding._DECODE_SIGNATURE)
    with open(path) as f:
        sig = json.load(f)
    if layout is None:
        del sig['layout']
    else:
        sig['layout'] = layout
    with open(path, 'w') as f:
        json.dump(sig, f)
    with pytest.raises(ValueError, match='slot-layout artifacts are no '
                                         'longer served; export again'):
        DecodingPredictor(art)
    with pytest.raises(ValueError, match='no longer served'):
        decoding.precompile_decode_artifact(art)


def test_no_reorder_program_and_a_parents_artifact_still_loads(arts,
                                                               tmp_path):
    """The block scheduler never dispatched decode_reorder/: an artifact
    holds none, and one the parent exported — the same programs plus that
    directory — loads (the directory ignored) and serves the same
    transcripts and beams."""
    import shutil
    assert not os.path.exists(os.path.join(arts['block'], 'decode_reorder'))
    assert sorted(d for d in os.listdir(arts['block'])
                  if os.path.isdir(os.path.join(arts['block'], d))) == [
        'decode_blockcopy', 'decode_step', 'decode_zeros',
        'prefill_chunk_00004', 'prefill_chunk_00008',
        'prefill_chunk_00008x4']
    old = str(tmp_path / 'parent')
    shutil.copytree(arts['block'], old)
    shutil.copytree(os.path.join(old, 'decode_blockcopy'),
                    os.path.join(old, 'decode_reorder'))
    prompts = _prompts(31, 8)
    with DecodingPredictor(old) as pb:
        assert not hasattr(pb, '_reorder_mod')
        g = [pb.generate(p, max_new_tokens=10) for p in prompts]
        b = [pb.generate(p, max_new_tokens=8, beam=3) for p in prompts[:3]]
    assert g == _PARENT_GREEDY
    _assert_beams(b, _PARENT_BEAM)


def test_block_greedy_and_beam_are_the_parents(arts):
    prompts = _prompts(31, 8)
    with DecodingPredictor(arts['block']) as pb:
        g = [pb.generate(p, max_new_tokens=10) for p in prompts]
        b = [pb.generate(p, max_new_tokens=8, beam=3)
             for p in prompts[:3]]
        snap = pb.stats.snapshot()
    assert g == _PARENT_GREEDY
    _assert_beams(b, _PARENT_BEAM)
    # beam history moves were table permutations + block CoW — and the
    # copies dispatched blocks
    assert snap['cow_blocks'] > 0
    assert snap['blockcopies'] <= snap['cow_blocks']


def test_block_logits_match_the_plain_reference(arts):
    """An independent reference, which a second cache layout never was:
    chunked prefill (prompts longer than the largest chunk among them)
    and paged decode give the LOGITS of benchmark/reference/decoder_lm.py's
    full forward pass over the same tokens, to float32 summation order."""
    from benchmark.reference import decoder_lm
    from paddle_tpu.testing.decode_logits import served_logits
    rng = np.random.RandomState(38)
    prompts = [rng.randint(2, VOCAB, n) for n in (3, 8, 13, 23)]
    n_new = 10
    with DecodingPredictor(arts['block']) as pb:
        tokens, logits = served_logits(pb, prompts, n_new)
    for prompt, toks, rows in zip(prompts, tokens, logits):
        seq = np.concatenate([prompt, toks[:-1]])
        ref = np.asarray(decoder_lm.logits(
            arts['weights'], seq, n_head=_MODEL['n_head'],
            n_layer=_MODEL['n_layer']))[len(prompt) - 1:]
        assert ref.shape == rows.shape == (n_new, VOCAB)
        np.testing.assert_allclose(rows, ref, rtol=0, atol=2e-5)


def test_block_int8_pages_serve_the_parents_transcripts(arts):
    """int8 KV pages compose with block paging (round-14 x ISSUE 13):
    per-page scales ride the pool and, with a COLD prefix cache,
    transcripts AND beam scores are exactly the parent's (the chunk op
    attends the current chunk's fresh f32 rows — attend f32, store
    int8). Once prefix sharing engages, a hit
    attends the covered span via its int8 pages where a cold prefill
    recomputes it at f32: token ids stay identical, scores track within
    the quantization step — the (vLLM-standard) int8 prefix-cache
    boundary."""
    prompts = _prompts(32, 6)
    ref, (b_ref,) = _PARENT_INT8_GREEDY, _PARENT_INT8_BEAM
    with DecodingPredictor(arts['block8']) as pb:
        assert pb.stats.tier == 'int8'
        b_cold = pb.generate(prompts[0], max_new_tokens=8, beam=3)
        got = [pb.generate(p, max_new_tokens=10) for p in prompts]
        b_warm = pb.generate(prompts[0], max_new_tokens=8, beam=3)
        warm_snap = pb.stats.snapshot()
    assert got == ref
    np.testing.assert_array_equal(b_ref[0], b_cold[0])
    np.testing.assert_array_equal(b_ref[1], b_cold[1])
    # warm (prefix-hit) serve: same tokens, scores within quant step
    assert warm_snap['prefix_hits'] > 0
    np.testing.assert_array_equal(b_ref[0], b_warm[0])
    np.testing.assert_allclose(b_ref[1], b_warm[1], atol=0.05)
    with open(os.path.join(arts['block8'],
                           'decode_signature.json')) as f:
        sig = json.load(f)
    dt = {e['name']: e['dtype'] for e in sig['state']}
    assert dt['kv_k_0'] == 'int8' and dt['kv_ks_0'] == 'float32'


def test_beam_divergence_cow_at_block_boundary(arts):
    """Force beam CoW exactly where it is subtle: a prompt whose length
    is a multiple of block_size (the fork point is a BLOCK BOUNDARY, so
    the first divergent write extends into a fresh block — no copy) and
    one mid-block (the shared partial tail must CoW). Both must serve
    what the parent's block artifact served, bit for bit."""
    rng = np.random.RandomState(33)
    at_boundary = rng.randint(2, VOCAB, 8)    # 8 % 4 == 0
    mid_block = rng.randint(2, VOCAB, 6)      # 6 % 4 != 0
    with DecodingPredictor(arts['block']) as pb:
        got = [pb.generate(p, max_new_tokens=10, beam=3)
               for p in (at_boundary, mid_block)]
        snap = pb.stats.snapshot()
    _assert_beams(got, _PARENT_COW)
    assert snap['cow_blocks'] > 0


def test_prefix_sharing_skips_compute_and_storage(arts):
    """Two requests with the same prompt: the second hits the prefix
    cache — fewer chunk slices (covered span skips prefill compute) and
    shared full blocks (storage) — with an identical transcript."""
    rng = np.random.RandomState(34)
    prompt = rng.randint(2, VOCAB, 9)          # 2 full blocks + 1
    with DecodingPredictor(arts['block']) as pb:
        a = pb.generate(prompt, max_new_tokens=10)
        s1 = pb.stats.snapshot()
        b = pb.generate(prompt, max_new_tokens=10)
        s2 = pb.stats.snapshot()
    assert a == b
    assert s2['prefix_hits'] == s1['prefix_hits'] + 1
    assert s2['prefix_tokens_reused'] == s1['prefix_tokens_reused'] + 8
    # the covered 8 tokens (2 blocks) admitted without chunk dispatches:
    # request 1 took 2 slices (8 + 1 tokens), request 2 only 1
    assert (s2['chunk_slices'] - s1['chunk_slices']
            < s1['chunk_slices'])


def test_chunked_prefill_admits_beyond_largest_chunk(arts):
    """A prompt longer than the largest chunk size admits in slices —
    the ceiling is the cache length, and a prompt past THAT is refused
    by name. Greedy decode is deterministic, so serving the same prompt
    twice across chunk boundaries must agree."""
    rng = np.random.RandomState(35)
    long_prompt = rng.randint(2, VOCAB, 23)    # > max chunk (8)
    with DecodingPredictor(arts['block']) as pb:
        one = pb.generate(long_prompt, max_new_tokens=12)
        s = pb.stats.snapshot()
        two = pb.generate(long_prompt, max_new_tokens=12)
        with pytest.raises(ValueError, match='exceeds max_cache_len'):
            pb.generate(rng.randint(2, VOCAB, CACHE + 1), max_new_tokens=4)
    assert one == two
    assert s['chunk_slices'] >= 3              # 23 tokens over 8-chunks


# -- the tick's order (ISSUE 29): the step first, the slices behind it,
# one read of the device a tick — the same tokens whatever tick a row
# joins in -------------------------------------------------------------

# What the PARENT commit (PR 28, aa11165) served for _mixed_prompts() from
# draft_k=3 artifacts of the three pools: six greedy transcripts and the
# beam-3 hypotheses and scores of the 13-token prompt — alone or together,
# the parent served the same.
_PARENT_TOGETHER = {
    'f32': {
        'greedy': [[19, 29, 29, 29, 29, 29, 29, 17, 16, 16],
                   [40, 40, 40, 40, 40, 40, 40, 40, 40, 40],
                   [16, 16, 16, 16, 16, 16, 16, 16, 23, 16],
                   [40, 40, 40, 23, 17, 9, 23, 40, 23, 23],
                   [40, 16, 40, 16, 40, 23, 40, 23, 16, 16],
                   [16, 16, 16, 16, 16, 16, 40, 23, 16, 23]],
        'beam': ([[16, 16, 16, 16, 23, 16, 16, 16],
                  [16, 16, 16, 16, 40, 23, 16, 16],
                  [16, 16, 16, 16, 40, 16, 16, 16]],
                 [-16.60764288641112,
                  -16.704687913167376,
                  -16.80076966118413])},
    'bf16': {
        'greedy': [[19, 29, 29, 29, 29, 29, 29, 17, 16, 16],
                   [40, 40, 40, 40, 40, 40, 40, 40, 40, 40],
                   [16, 16, 16, 16, 16, 16, 16, 16, 23, 16],
                   [40, 40, 40, 23, 17, 9, 23, 40, 23, 23],
                   [40, 16, 40, 16, 40, 23, 40, 23, 16, 16],
                   [16, 16, 16, 16, 16, 16, 40, 23, 16, 23]],
        'beam': ([[16, 16, 16, 16, 23, 16, 16, 16],
                  [16, 16, 16, 16, 40, 23, 16, 16],
                  [16, 16, 16, 16, 40, 16, 16, 16]],
                 [-16.60848130747962,
                  -16.703123644847444,
                  -16.799922178149544])},
    'int8': {
        'greedy': [[31, 31, 31, 18, 20, 7, 14, 20, 20, 20],
                   [31, 31, 31, 31, 31, 38, 21, 31, 31, 31],
                   [31, 31, 31, 31, 31, 31, 31, 31, 31, 31],
                   [31, 31, 31, 31, 31, 31, 31, 31, 31, 31],
                   [20, 20, 20, 20, 20, 20, 20, 20, 20, 20],
                   [31, 20, 20, 20, 31, 31, 20, 20, 20, 20]],
        'beam': ([[0, 31, 31, 31, 31, 31, 31, 31],
                  [0, 31, 39, 31, 31, 31, 31, 31],
                  [0, 31, 31, 31, 31, 31, 18, 0]],
                 [-19.928895083176876,
                  -20.055627483755494,
                  -20.150544523072558])},
}
_POOLS = {'f32': {}, 'bf16': {'kv_cache_dtype': 'bfloat16'},
          'int8': {'kv_cache_dtype': 'int8'}}


def _mixed_prompts():
    """3 to 23 tokens over chunks of 4 and 8: one to three slices each."""
    rng = np.random.RandomState(39)
    return [rng.randint(2, VOCAB, n) for n in (3, 8, 13, 23, 5, 11)]


@pytest.fixture(scope='module')
def k3_arts(tmp_path_factory):
    t = tmp_path_factory.mktemp('kvblocks_k3')
    made = {}

    def get(pool):
        if pool not in made:
            made[pool] = _build(str(t / pool), chunk_sizes=(4, 8),
                                block_size=4, draft_k=3, **_POOLS[pool])
        return made[pool]
    return get


@pytest.mark.parametrize('mode', ['together', 'prefix_hit', 'draft'])
@pytest.mark.parametrize('pool', ['f32', 'bf16', 'int8'])
def test_served_together_is_what_the_parent_served(k3_arts, pool, mode):
    """Seven requests over four slots, prompts of one to three slices,
    a beam among the greedy rows: every transcript and beam score is the
    parent's, bit for bit — served cold, again over the prefix cache the
    first serve filled, and with the n-gram drafter's verify ticks in
    front of the step. (An int8 prefix hit attends the covered span
    through its quantized pages: ids as the parent's, scores within the
    quantization step, as test_block_int8_pages_... pins.)"""
    prompts = _mixed_prompts()
    want = _PARENT_TOGETHER[pool]
    kw = {'draft': 'ngram'} if mode == 'draft' else {}

    def serve(pred):
        streams = [pred.submit(p, max_new_tokens=10) for p in prompts[:3]]
        beam = pred.submit(prompts[2], max_new_tokens=8, beam=3)
        streams += [pred.submit(p, max_new_tokens=10) for p in prompts[3:]]
        return [s.result(120) for s in streams], beam.result(120)

    with DecodingPredictor(k3_arts(pool), **kw) as pred:
        greedy, beam = serve(pred)
        if mode == 'prefix_hit':
            cold = pred.stats.snapshot()
            greedy, beam = serve(pred)
        snap = pred.stats.snapshot()
    assert greedy == want['greedy']
    np.testing.assert_array_equal(beam[0], want['beam'][0])
    if mode == 'prefix_hit':
        assert snap['prefix_hits'] > cold['prefix_hits']
        assert (snap['chunk_slices'] - cold['chunk_slices']
                < cold['chunk_slices'])
    if mode == 'prefix_hit' and pool == 'int8':
        np.testing.assert_allclose(beam[1], want['beam'][1], atol=0.05)
    else:
        np.testing.assert_array_equal(beam[1], want['beam'][1])
    if mode == 'draft':
        assert snap['verify_steps'] > 0
    # a slice is read iff it was its prompt's last
    assert snap['slice_reads'] == snap['requests'] == \
        (14 if mode == 'prefix_hit' else 7)
    assert snap['chunk_slices'] >= snap['slice_reads']


def test_served_together_is_the_plain_references_argmax(arts):
    """The same mixed prompts through the scheduler, all in flight at
    once: every served token is the argmax of
    benchmark/reference/decoder_lm.py's full forward pass over the tokens
    before it, wherever the reference's top two are further apart than
    float32 summation order can move them."""
    from benchmark.reference import decoder_lm
    prompts = _mixed_prompts()
    with DecodingPredictor(arts['block']) as pb:
        streams = [pb.submit(p, max_new_tokens=10) for p in prompts]
        served = [s.result(120) for s in streams]
    compared = 0
    for prompt, toks in zip(prompts, served):
        seq = np.concatenate([prompt, toks[:-1]])
        ref = np.asarray(decoder_lm.logits(
            arts['weights'], seq, n_head=_MODEL['n_head'],
            n_layer=_MODEL['n_layer']))[len(prompt) - 1:]
        top = np.sort(ref, axis=-1)
        for row, margin, tok in zip(ref, top[:, -1] - top[:, -2], toks):
            if margin > 1e-4:
                compared += 1
                assert tok == int(np.argmax(row))
    assert compared >= 40


def test_slice_reads_counts_the_prompts_last_slices(arts):
    """chunk_slices counts every slice dispatched, slice_reads the ones
    whose id the host waited for and copied: one a prompt. reset() zeroes
    both."""
    prompts = _mixed_prompts()
    # chunks (4, 8): 3 -> 1 slice, 8 -> 1, 13 -> 2, 23 -> 3, 5 -> 1, 11 -> 2
    with DecodingPredictor(arts['block']) as pb:
        for p in prompts:
            pb.generate(p, max_new_tokens=3)
        snap = pb.stats.snapshot()
        pb.stats.reset()
        zero = pb.stats.snapshot()
    assert snap['chunk_slices'] == 10 and snap['prefills'] == 10
    assert snap['slice_reads'] == 6
    assert zero['chunk_slices'] == zero['slice_reads'] == 0


def test_mp_sharded_decode_transcripts_match_single_chip(arts,
                                                         tmp_path):
    """ISSUE 13 acceptance: the 2-chip mp-sharded decode artifact's
    TOKEN TRANSCRIPTS (greedy and beam ids) are bit-identical to the
    single-chip artifact's. The replicate-hint discipline keeps every
    contraction full-width (no partial-sum all-reduces), so logits
    agree to within local-fusion ulps — accumulated float beam scores
    may differ in the last ~1e-6 (the standard the sharded serving
    systems hold); ids must not."""
    mp2 = _build(str(tmp_path / 'mp2'), chunk_sizes=(4, 8),
                 block_size=4, mp_shard=2)
    with open(os.path.join(mp2, 'decode_signature.json')) as f:
        sig = json.load(f)
    assert sig['mesh']['axes'] == {'mp': 2}
    assert sig['mesh']['tag'] == 'cpu_mp2'
    # mesh-tagged sidecars: a sharded executable can never load into an
    # unsharded serve (or another mesh shape)
    from paddle_tpu.inference import decoding
    for d in (decoding._STEP_DIR, decoding._ZEROS_DIR,
              decoding._BLOCKCOPY_DIR):
        assert os.path.exists(os.path.join(mp2, d,
                                           'aot_cpu_mp2.jaxexec'))
        assert not os.path.exists(os.path.join(mp2, d,
                                               'aot_cpu.jaxexec'))
    prompts = _prompts(36, 6)
    with DecodingPredictor(arts['block']) as p1:
        g1 = [p1.generate(p, max_new_tokens=10) for p in prompts]
        b1 = [p1.generate(p, max_new_tokens=8, beam=3)
              for p in prompts[:2]]
    with DecodingPredictor(mp2) as p2:
        assert p2.mesh_tag == 'cpu_mp2'
        g2 = [p2.generate(p, max_new_tokens=10) for p in prompts]
        b2 = [p2.generate(p, max_new_tokens=8, beam=3)
              for p in prompts[:2]]
    assert g1 == g2
    for (i1, s1), (i2, s2) in zip(b1, b2):
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_allclose(s1, s2, atol=1e-4)


def test_mp_sharded_warm_replica_zero_compiles(arts, tmp_path):
    """A FRESH process loading the prewarmed mp-sharded artifact serves
    greedy + beam with ZERO XLA compiles (mesh-tagged AOT sidecars),
    and its transcripts equal the single-chip artifact served the same
    way — the full ISSUE 13 sharded-serve acceptance bar."""
    import subprocess
    import sys as _sys
    mp2 = _build(str(tmp_path / 'mp2w'), chunk_sizes=(4, 8),
                 block_size=4, mp_shard=2)
    here = os.path.dirname(os.path.abspath(__file__))
    outs = []
    for art in (arts['block'], mp2):
        env = dict(os.environ)
        env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
        env['JAX_PLATFORMS'] = 'cpu'
        p = subprocess.run(
            [_sys.executable, os.path.join(here,
                                           'decode_serve_worker.py'),
             art, '5', '4', '8'],
            capture_output=True, text=True, env=env, timeout=600)
        assert 'DECODE_OK' in p.stdout, p.stdout + p.stderr
        line = [ln for ln in p.stdout.splitlines()
                if ln.startswith('DECODE ')][0]
        outs.append(json.loads(line[len('DECODE '):]))
    single, sharded = outs
    assert sharded['compiles'] == 0
    assert sharded['greedy'] == single['greedy']
    assert sharded['beam_ids'] == single['beam_ids']


def test_chunk_pad_overflow_lands_in_trash_block():
    """A near-max-length prompt whose FINAL chunk slice runs past
    max_cache_len (take < size with a FULL block table) must scatter
    its pad rows into the trash block: gather clamping would resolve
    their overflowing positions to the LAST table column — a real
    block when the table is full — and pad garbage would overwrite
    prompt K/V written in the same dispatch. The transcript through a
    big chunk (pad rows overflow) and a small chunk (none do) must
    agree."""
    import tempfile
    t = tempfile.mkdtemp()
    big = _build(os.path.join(t, 'big'), block_size=8, chunk_sizes=(48,))
    small = _build(os.path.join(t, 'small'), block_size=8,
                   chunk_sizes=(8,))
    rng = np.random.RandomState(36)
    prompt = rng.randint(2, VOCAB, CACHE - 1)  # 63 tokens: table full
    with DecodingPredictor(small) as ps:
        ref = ps.generate(prompt, max_new_tokens=1)
    with DecodingPredictor(big) as pb:
        # final slice: start=48, take=15, size=48 -> pad positions
        # 64..95 overflow the 8-column table
        assert pb.generate(prompt, max_new_tokens=1) == ref


def test_waiting_request_rematches_published_prefix():
    """A request whose FIRST admission attempt misses the prefix cache
    (its twin ahead of it is still prefilling) and then stalls on
    blocks must RE-match once it can admit: the twin published the
    shared prefix while it waited. A cached miss holds no refs, so
    only a cached HIT may pin across attempts."""
    import tempfile
    art = _build(tempfile.mkdtemp() + '/rematch', chunk_sizes=(4, 8),
                 block_size=4, num_blocks=5)   # 4 usable blocks
    rng = np.random.RandomState(37)
    prompt = rng.randint(2, VOCAB, 12)         # 3 blocks at admission
    with DecodingPredictor(art) as pb:
        # A admits (3 blocks + 1 decode extension = the whole pool):
        # B's first attempt MISSES the prefix cache and stalls on
        # blocks; A publishes at prefill end and frees at finish —
        # B must then admit on the re-matched HIT (2 shared + 1 fresh)
        sa = pb.submit(prompt, max_new_tokens=4)
        sb = pb.submit(prompt, max_new_tokens=4)
        a = sa.result(120)
        b = sb.result(120)
        snap = pb.stats.snapshot()
    assert a == b
    assert snap['prefix_hits'] >= 1


def test_pool_exhaustion_sheds_loudly(arts):
    """A pool too small for the offered prompts sheds the unservable
    request with ServerOverloaded instead of deadlocking."""
    from paddle_tpu.inference import ServerOverloaded
    import tempfile
    small = _build(tempfile.mkdtemp() + '/tiny', chunk_sizes=(4, 8),
                   block_size=4, num_blocks=3)  # 2 usable blocks
    with DecodingPredictor(small) as pb:
        ok = pb.generate(np.asarray([3, 4, 5]), max_new_tokens=4)
        assert len(ok) == 4
        with pytest.raises(ServerOverloaded, match='block pool'):
            # needs 4 blocks (12 tokens + new): can never fit
            pb.submit(np.asarray(range(2, 14)),
                      max_new_tokens=4).result(60)
