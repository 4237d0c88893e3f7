"""Speculative decoding (ISSUE 17): greedy bit-identity of
draft-and-verify decode vs plain decode across the f32 / bf16 / int8
KV tiers, mixed draft/no-draft/beam ticks, rejected-tail cache
invisibility and block rollback, EOS/max_new truncation inside the
draft window, acceptance stats, drafter units, and fresh-subprocess
warm start with zero XLA compiles over the verify sidecar."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import (DecodingPredictor, DraftModelDrafter,
                                  NgramDrafter, export_decode)
from paddle_tpu.inference.kv_blocks import BlockManager

from decode_feed_check import watch_feed

VOCAB, SLOTS, CACHE, K = 37, 4, 64, 4


def _build(tmp, **kw):
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(
            vocab=VOCAB, d_model=16, n_head=2, n_layer=2, d_ff=32,
            max_slots=SLOTS, max_cache_len=CACHE, eos_id=1, **kw)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'])
        export_decode(spec, tmp, scope=scope)
    return tmp


@pytest.fixture(scope='module')
def arts(tmp_path_factory):
    """draft_k=K artifacts of the same tiny LM across the KV tiers
    (bfloat16 at two page sizes: 16 rows is what the TPU's paged kernel
    reads), plus one verify-less artifact for the negative tests."""
    t = tmp_path_factory.mktemp('spec')
    return {
        'block': _build(str(t / 'block'), chunk_sizes=(4, 8),
                        block_size=4, draft_k=K),
        'block_bf16': _build(str(t / 'block_bf16'), chunk_sizes=(4, 8),
                             block_size=4, kv_cache_dtype='bfloat16',
                             draft_k=K),
        'block_bf16_p16': _build(str(t / 'block_bf16_p16'),
                                 chunk_sizes=(4, 8), block_size=16,
                                 kv_cache_dtype='bfloat16', draft_k=K),
        'block8': _build(str(t / 'block8'), chunk_sizes=(4, 8),
                         block_size=4, kv_cache_dtype='int8', draft_k=K),
        'plain': _build(str(t / 'plain'), chunk_sizes=(4,), block_size=4),
    }


def _prompts(seed, n):
    """Alternating self-repetitive (the n-gram drafter fires) and
    random (no draft — the slot rides the plain step) prompts."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        if i % 2 == 0:
            pat = rng.randint(2, VOCAB, 2)
            plen = int(rng.randint(4, 9))
            out.append(np.tile(pat, plen)[:plen])
        else:
            out.append(rng.randint(2, VOCAB, int(rng.randint(2, 9))))
    return out


class _ScriptedDrafter(object):
    """Proposes a fixed token sequence regardless of context — the
    zero/low-acceptance adversary for rejection-path tests."""

    def __init__(self, toks):
        self._toks = [int(t) for t in toks]

    def draft(self, tokens, k):
        return self._toks[:k]


class _OracleDrafter(object):
    """Proposes the known-true continuation of a transcript recorded
    from a plain run — deterministic full acceptance."""

    def __init__(self):
        self.full = {}

    def remember(self, prompt, out):
        key = tuple(int(t) for t in prompt)
        self.full[key] = list(key) + [int(t) for t in out]

    def draft(self, tokens, k):
        toks = [int(t) for t in tokens]
        for full in self.full.values():
            if full[:len(toks)] == toks:
                return full[len(toks):len(toks) + k]
        return []


# -- artifact layout ---------------------------------------------------------

def test_verify_artifact_layout(arts):
    from paddle_tpu.inference import decoding
    for name in ('block', 'block_bf16', 'block_bf16_p16', 'block8'):
        with open(os.path.join(arts[name],
                               decoding._DECODE_SIGNATURE)) as f:
            sig = json.load(f)
        assert sig['version'] == decoding._SIG_VERSION == 6
        ver = sig['verify']
        assert ver['draft_k'] == K
        assert (sorted(e['name'] for e in ver['feeds']) ==
                sorted(e['name'] for e in sig['step']['feeds']))
        d = os.path.join(arts[name], decoding._VERIFY_DIR)
        assert os.path.exists(os.path.join(d, 'module.jaxexport'))
        # export-time AOT warm-start sidecar, same as the step program
        assert os.path.exists(os.path.join(d, 'aot_cpu.jaxexec'))
    with open(os.path.join(arts['plain'],
                           decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    assert 'verify' not in sig
    assert not os.path.exists(os.path.join(arts['plain'],
                                           decoding._VERIFY_DIR))


# -- greedy bit-identity -----------------------------------------------------

@pytest.mark.parametrize('name', ['block', 'block_bf16',
                                  'block_bf16_p16', 'block8'])
def test_spec_bit_identity_all_tiers(arts, name):
    """The ISSUE 17 bar: speculative greedy transcripts are
    BIT-IDENTICAL to plain decode on every KV tier, with real
    acceptance happening (not vacuous all-rejected runs)."""
    prompts = _prompts(17, 6)
    with DecodingPredictor(arts[name]) as pp:
        want = [pp.generate(p, max_new_tokens=10) for p in prompts]
    with DecodingPredictor(arts[name], draft='ngram') as ps:
        ps.stats.reset()
        streams = [ps.submit(p, max_new_tokens=10) for p in prompts]
        got = [s.result(120) for s in streams]
        snap = ps.stats.snapshot()
    assert got == want
    assert snap['verify_steps'] > 0 and snap['drafted'] > 0


def test_mixed_draft_nodraft_and_beam_tick(arts):
    """Drafted slots ride the verify program, undrafted slots the plain
    step, and a beam request (never drafted) decodes alongside — all in
    the same scheduler loop, all bit-identical to plain serving."""
    prompts = _prompts(23, 8)
    with DecodingPredictor(arts['block']) as pp:
        want = [pp.generate(p, max_new_tokens=10) for p in prompts]
        want_ids, want_scores = pp.generate(prompts[1],
                                            max_new_tokens=8, beam=3)
    with DecodingPredictor(arts['block'], draft='ngram') as ps:
        ps.stats.reset()
        # the plain step's feed is kept between ticks (ISSUE 35): under
        # a drafter every row is the host's to write — its token, the
        # position a verify tick moved, the table it trimmed, this
        # tick's drafted rows idle — through the same arrays, and each
        # feed equals a rebuild from the requests
        watch = watch_feed(ps)
        streams = [ps.submit(p, max_new_tokens=10) for p in prompts]
        got = [s.result(120) for s in streams]
        ids, scores = ps.generate(prompts[1], max_new_tokens=8, beam=3)
        snap = ps.stats.snapshot()
    assert got == want
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(scores, want_scores)
    assert snap['drafted'] > 0
    assert watch.steps > 0
    assert snap['feed_rows_touched'] == snap['feed_rows_live'] == watch.live


# -- rejection path ----------------------------------------------------------

@pytest.mark.parametrize('name', ['block', 'block8'])
def test_rejected_tail_invisible_and_rolled_back(arts, name):
    """An adversarial drafter forces rejections every tick: the
    speculatively written KV past the accepted frontier must never be
    attended (transcripts stay bit-identical), and the blocks grown for
    the rejected tail must roll back to the pool (no leak)."""
    prompts = _prompts(29, 5)
    with DecodingPredictor(arts[name]) as pp:
        want = [pp.generate(p, max_new_tokens=12) for p in prompts]
    with DecodingPredictor(arts[name],
                           draft=_ScriptedDrafter([2, 3, 4, 2])) as ps:
        ps.stats.reset()
        got = [ps.generate(p, max_new_tokens=12) for p in prompts]
        # same prompts again: prefix-cache reuse over rolled-back
        # tables must still match
        again = [ps.generate(p, max_new_tokens=12) for p in prompts]
        snap = ps.stats.snapshot()
        bm = ps.block_manager
        bm.evict_all_prefixes()
        assert bm.in_use() == 0, 'speculative blocks leaked'
    assert got == want and again == want
    assert snap['drafted'] > 0
    assert snap['accepted'] < snap['drafted'], \
        'adversarial drafter was never rejected — vacuous test'


def test_truncation_inside_draft_window(arts):
    """max_new_tokens smaller than the draft window: emission must stop
    exactly where plain decode stops, never overshooting on accepted
    draft tokens."""
    prompts = _prompts(31, 6)
    with DecodingPredictor(arts['block']) as pp, \
            DecodingPredictor(arts['block'], draft='ngram') as ps:
        for max_new in (1, 2, 3):
            want = [pp.generate(p, max_new_tokens=max_new)
                    for p in prompts]
            got = [ps.generate(p, max_new_tokens=max_new)
                   for p in prompts]
            assert got == want
            assert all(len(g) <= max_new for g in got)


def test_eos_semantics_match_plain(arts):
    """EOS truncation is host-side (`g == eos` breaks the acceptance
    walk): re-point the predictor's eos at a token the tiny model
    actually emits, then spec — including an oracle drafter that
    PROPOSES the EOS mid-window — must stop exactly where plain does."""
    prompts = _prompts(43, 8)
    with DecodingPredictor(arts['block']) as pp:
        base = [pp.generate(p, max_new_tokens=12) for p in prompts]
    toks = [t for w in base for t in w]
    eos = max(set(toks), key=toks.count)
    with DecodingPredictor(arts['block']) as pp:
        pp._eos = eos
        want = [pp.generate(p, max_new_tokens=12) for p in prompts]
    assert any(len(w) < 12 and w[-1] == eos for w in want), \
        'eos never fired early — vacuous test'
    oracle = _OracleDrafter()
    for p, w in zip(prompts, want):
        oracle.remember(p, w)
    for drafter in ('ngram', oracle):
        with DecodingPredictor(arts['block'], draft=drafter) as ps:
            ps._eos = eos
            got = [ps.generate(p, max_new_tokens=12) for p in prompts]
        assert got == want


# -- stats -------------------------------------------------------------------

def test_acceptance_stats(arts):
    oracle = _OracleDrafter()
    prompts = _prompts(37, 4)
    with DecodingPredictor(arts['block']) as pp:
        pp.stats.reset()
        want = [pp.generate(p, max_new_tokens=10) for p in prompts]
        plain_snap = pp.stats.snapshot()
        for p, w in zip(prompts, want):
            oracle.remember(p, w)
    # plain serving: ratios identically 1.0, no drafting counted
    assert plain_snap['drafted'] == 0 and plain_snap['accepted'] == 0
    assert plain_snap['acc_rate'] == 1.0
    assert plain_snap['tokens_per_dispatch'] == 1.0
    with DecodingPredictor(arts['block'], draft=oracle) as ps:
        ps.stats.reset()
        got = [ps.generate(p, max_new_tokens=10) for p in prompts]
        snap = ps.stats.snapshot()
    assert got == want
    assert snap['verify_steps'] > 0
    assert 0 < snap['accepted'] <= snap['drafted']
    assert snap['acc_rate'] == round(snap['accepted'] / snap['drafted'],
                                     4)
    if all(1 not in w for w in want):
        # an oracle drafter accepts everything it proposes (an EOS
        # inside the window legitimately truncates acceptance)
        assert snap['acc_rate'] == 1.0
    assert snap['tokens_per_dispatch'] > 1.0


def test_serving_report_spec_columns(arts, capsys):
    from paddle_tpu import profiler
    with DecodingPredictor(arts['block'], draft='ngram') as ps:
        ps.generate(np.tile([5, 9], 4), max_new_tokens=8)
        out = profiler.serving_report()
        name = [k for k in out if k.startswith('decode:')]
        assert name, out
        snap = out[name[0]]
    for key in ('acc_rate', 'tokens_per_dispatch', 'verify_steps'):
        assert key in snap
    text = capsys.readouterr().out
    assert 'acc' in text and 'tok/d' in text


# -- token delivery ----------------------------------------------------------

def test_tokenstream_batches_coalesce(arts):
    """A verify tick that accepts tokens delivers them as ONE batch on
    the stream; plain decode delivers singletons."""
    oracle = _OracleDrafter()
    prompt = np.asarray([3, 4, 5, 6], np.int64)
    with DecodingPredictor(arts['block']) as pp:
        want = pp.generate(prompt, max_new_tokens=10)
        st = pp.submit(prompt, max_new_tokens=10)
        plain_batches = list(st.batches())
    oracle.remember(prompt, want)
    assert all(len(b) == 1 for b in plain_batches)
    assert [t for b in plain_batches for t in b] == want
    with DecodingPredictor(arts['block'], draft=oracle) as ps:
        st = ps.submit(prompt, max_new_tokens=10)
        batches = list(st.batches())
    assert [t for b in batches for t in b] == want
    assert any(len(b) > 1 for b in batches), \
        'oracle-drafted decode never coalesced a delivery'


# -- drafters ----------------------------------------------------------------

def test_ngram_drafter_unit():
    d = NgramDrafter()
    # longest suffix wins; continuation follows the matched site
    assert d.draft([5, 6, 7, 5, 6], 3) == [7, 5, 6]
    # the MOST RECENT earlier occurrence predicts (8, not 9)
    assert d.draft([1, 2, 9, 1, 2, 8, 1, 2], 1) == [8]
    # 1-gram fallback by default...
    assert d.draft([1, 2, 3, 1], 2) == [2, 3]
    # proposals extend periodically past the transcript's end
    assert d.draft([5, 6, 5, 6], 4) == [5, 6, 5, 6]
    # ...suppressed by min_ngram
    assert NgramDrafter(min_ngram=2).draft([1, 2, 3, 1], 2) == []
    # no repetition, degenerate inputs -> no proposal
    assert d.draft([1, 2, 3, 4], 3) == []
    assert d.draft([7], 3) == []
    assert d.draft([5, 6, 7, 5, 6], 0) == []
    with pytest.raises(ValueError):
        NgramDrafter(min_ngram=0)
    with pytest.raises(ValueError):
        NgramDrafter(max_ngram=2, min_ngram=3)


def test_draft_model_drafter(arts):
    """A draft artifact (here: the target itself — proposals match the
    target argmax, so acceptance is high) plugged in as the drafter."""
    prompts = _prompts(41, 4)
    with DecodingPredictor(arts['block']) as pp:
        want = [pp.generate(p, max_new_tokens=8) for p in prompts]
    with DecodingPredictor(arts['block']) as dp, \
            DecodingPredictor(arts['block'],
                              draft=DraftModelDrafter(dp)) as ps:
        ps.stats.reset()
        got = [ps.generate(p, max_new_tokens=8) for p in prompts]
        snap = ps.stats.snapshot()
    assert got == want
    assert snap['accepted'] > 0
    with pytest.raises(ValueError):
        DraftModelDrafter(object())


def test_draft_validation(arts):
    with pytest.raises(ValueError):
        DecodingPredictor(arts['plain'], draft='ngram')
    for bad_k in (0, K + 1):
        with pytest.raises(ValueError):
            DecodingPredictor(arts['block'], draft='ngram',
                              draft_k=bad_k)
    # draft_k below the artifact's K narrows the window
    with DecodingPredictor(arts['block'], draft='ngram',
                           draft_k=2) as ps:
        out = ps.generate(np.tile([5, 9], 4), max_new_tokens=8)
    with DecodingPredictor(arts['block']) as pp:
        assert pp.generate(np.tile([5, 9], 4), max_new_tokens=8) == out


# -- allocator unit ----------------------------------------------------------

def test_blockmanager_rollback_unit():
    m = BlockManager(num_blocks=9, block_size=4)
    table = m.alloc(4)
    assert m.in_use() == 4
    # 9 tokens span 3 blocks: one speculative tail block returns
    assert m.rollback(table, 9) == 1
    assert len(table) == 3 and m.in_use() == 3
    # nothing past the keep point -> no-op
    assert m.rollback(table, 12) == 0
    assert m.rollback(table, 0) == 3
    assert table == [] and m.in_use() == 0


# -- warm start --------------------------------------------------------------

def test_warm_fresh_subprocess_zero_compiles(arts, tmp_path):
    """cache_ctl prewarm learns the verify program: strip every AOT
    sidecar from a copy, prewarm via the CLI, then a fresh speculative
    serving process must perform ZERO XLA compiles and match the
    in-process transcripts."""
    art = str(tmp_path / 'art')
    shutil.copytree(arts['block'], art)
    stripped = 0
    for root, _dirs, files in os.walk(art):
        for f in files:
            if f.startswith('aot_') and f.endswith('.jaxexec'):
                os.remove(os.path.join(root, f))
                stripped += 1
    assert stripped > 0
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, os.path.join(repo, 'tools', 'cache_ctl.py'),
         'prewarm', art], capture_output=True, text=True, env=env,
        timeout=600)
    assert out.returncode == 0, out.stderr
    from paddle_tpu.inference import decoding
    assert os.path.exists(os.path.join(art, decoding._VERIFY_DIR,
                                       'aot_cpu.jaxexec'))
    worker = os.path.join(os.path.dirname(__file__),
                          'spec_decode_worker.py')
    out = subprocess.run(
        [sys.executable, worker, art, '23', '4', '8'],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    assert 'SPEC_OK' in out.stdout
    payload = json.loads(
        [l for l in out.stdout.splitlines()
         if l.startswith('SPEC ')][0][len('SPEC '):])
    assert payload['compiles'] == 0, payload
    assert payload['verify_steps'] > 0 and payload['drafted'] > 0
    # replicate the worker's prompts in-process and compare transcripts
    rng = np.random.RandomState(23)
    prompts = []
    for _ in range(4):
        pat = rng.randint(2, VOCAB, 2)
        plen = int(rng.randint(4, 9))
        prompts.append(np.tile(pat, plen)[:plen])
    with DecodingPredictor(arts['block'], draft='ngram') as ps:
        want = [ps.submit(p, max_new_tokens=8) for p in prompts]
        want = [s.result(120) for s in want]
    assert payload['greedy'] == want
