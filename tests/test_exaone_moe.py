"""K-EXAONE decode serving on the cpu at a toy size (5 layers L L L G L,
hidden 64, 4 query / 2 K/V heads of 16, window 16, a dense leading layer,
16 routed experts top-4 of which 4 are held from offset 4, a shared
expert, vocab 128), seeded weights: prefill in slices then paged decode
through the two block tables against the plain reference's full-forward
LOGITS (benchmark/reference/exaone_moe.py); the same comparison against
the reference with one equation changed, which has to fail; what the
window layers hold and give back while the full layer keeps everything;
and what an artifact with window layers refuses by name."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, decoding, export_decode
from paddle_tpu.inference.kv_blocks import window_blocks_per_slot
from paddle_tpu.testing.decode_logits import served_logits
from benchmark.reference import exaone_moe as ref
from models.exaone_moe import build_decode_spec, layer_types

from decode_feed_check import watch_feed

TOY = dict(vocab=128, d_model=64, n_head=4, n_kv_head=2, d_head=16,
           n_layer=5, window=16, d_dense=96, n_expert=16, n_held=4,
           expert_offset=4, d_expert=32, top_k=4, max_slots=4,
           max_cache_len=96, block_size=8, chunk_sizes=(8, 16))
REF = dict(n_head=4, n_kv_head=2, n_layer=5, types=layer_types(5),
           window=16, first_dense=1, top_k=4, expert_offset=4)
# shorter than the window; longer than it; a whole slice past it; several
# slices of both sizes with the window crossing slice and page edges
PROMPTS = (3, 21, 40, 70)
N_NEW = 12


def _export(tmp, dtype='float32', seed=3, **over):
    art = str(tmp)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(weights_dtype=dtype, kv_cache_dtype=dtype,
                                 **dict(TOY, **over))
        spec['startup'].random_seed = seed
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        weights = {n: np.asarray(scope.get(n))
                   for n in scope.local_var_names()
                   if n not in spec['cache_vars']}
        export_decode(spec, art, scope=scope, precompile=False)
    return art, weights


def _prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(2, TOY['vocab'], n) for n in PROMPTS]


@pytest.fixture(scope='module')
def served(tmp_path_factory):
    """The float32 toy artifact, its weights, and the logits its programs
    gave for PROMPTS through the predictor's own dispatch."""
    from paddle_tpu.ops import decode_ops
    with pytest.MonkeyPatch.context() as mp:    # several key blocks a slot
        mp.setattr(decode_ops, '_CHUNK_KEY_BLOCK', 16)
        art, w = _export(tmp_path_factory.mktemp('exaone') / 'art')
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, _prompts(), N_NEW)
    return art, w, tokens, logits


def _row_errors(w, tokens, logits, **over):
    """Per compared row, the largest |reference logit - served logit|."""
    errs = []
    for p, t, lg in zip(_prompts(), tokens, logits):
        seq = np.zeros(TOY['max_cache_len'], np.int64)   # one traced shape;
        n = len(p) + len(t) - 1                          # causal: the pad
        seq[:n] = np.concatenate([p, t[:-1]])            # cannot reach back
        want = np.asarray(ref.logits(w, seq, **dict(REF, **over)))
        want = want[len(p) - 1:n]
        assert want.shape == lg.shape
        errs.append(np.abs(want - lg).max(axis=-1))
    return np.concatenate(errs)


# float32 weights and pools differ from the reference by summation order
# alone (measured 5e-7 on logits of standard deviation 0.16): 2e-6
F32_TOL = 2e-6


def test_slices_then_paged_decode_match_reference_logits(served):
    _, w, tokens, logits = served
    assert _row_errors(w, tokens, logits).max() <= F32_TOL


def _whole_view_attention(q, kview, vview, start, n_head, n_kv, window):
    """Chunk attention written out over the whole view in float64: row i
    at position start + i attends j <= start + i, inside the window."""
    c, t = q.shape[1], kview.shape[0]
    dh = kview.shape[1] // n_kv
    qh = q.astype(np.float64).reshape(c, n_kv, n_head // n_kv, dh)
    kh = kview.astype(np.float64).reshape(t, n_kv, dh)
    vh = vview.astype(np.float64).reshape(t, n_kv, dh)
    sc = np.einsum('ckgd,tkd->ckgt', qh, kh) * dh ** -0.5
    i, j = start + np.arange(c)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    sc = np.where(seen[:, None, None, :], sc, -np.inf)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum('ckgt,tkd->ckgd', w, vh).reshape(1, c, n_head * dh)


def _chunk_op(attrs, q, kc, vc, start, table, said=None):
    """kv_block_chunk_attention as a program lowers it; `said`: the list
    its Tracer keeps of the bodies ops took."""
    import types
    from paddle_tpu.ops import decode_ops
    ctx = types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))
    if said is not None:
        ctx.tracer = types.SimpleNamespace(lowered_bodies=said)
    return np.asarray(decode_ops._kv_block_chunk_attention(ctx, {
        'Q': [jnp.asarray(q)], 'KCache': [jnp.asarray(kc)],
        'VCache': [jnp.asarray(vc)],
        'Start': [jnp.full((1, 1), start, jnp.int32)],
        'BlockTable': [jnp.asarray(table)[None, :]]})['Out'][0])


@pytest.mark.parametrize('window', [0, 16])
@pytest.mark.parametrize('start', [0, 5, 24, 40])
def test_blocked_chunk_attention_is_the_whole_view_body(start, window,
                                                        monkeypatch):
    """Grouped heads (and a window) send the chunk op to the body that
    reads the cache a block of positions at a time — here 16 — under an
    online softmax, from the first block any row attends: against the
    attention written out over the whole view, with NaN in every page
    the window has passed (block 0, where the table points for them)."""
    from paddle_tpu.ops import decode_ops
    monkeypatch.setattr(decode_ops, '_CHUNK_KEY_BLOCK', 16)
    rng = np.random.RandomState(start + window)
    bs, maxb, c, n_head, n_kv, dh = 8, 10, 16, 4, 2, 16
    kc = rng.randn(maxb + 1, bs, n_kv * dh).astype(np.float32)
    vc = rng.randn(maxb + 1, bs, n_kv * dh).astype(np.float32)
    q = rng.randn(1, c, n_head * dh).astype(np.float32)
    table = np.arange(1, maxb + 1, dtype=np.int32)
    want = _whole_view_attention(
        q, kc[1:].reshape(-1, n_kv * dh), vc[1:].reshape(-1, n_kv * dh),
        start, n_head, n_kv, window)
    if window:
        table[:max(start - window + 1, 0) // bs] = 0
        kc[0] = vc[0] = np.nan
    got = _chunk_op({'n_head': n_head, 'n_kv_head': n_kv, 'window': window},
                    q, kc, vc, start, table)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize('budget', ['fits', 'too_large'])
def test_chunk_attention_chooses_its_body_from_the_scores_size(
        budget, monkeypatch):
    """As many K/V heads as query heads and no window: the gathered view
    under one softmax while its [C, n_head, T'] float32 scores fit the
    budget, the blocked body once they do not — the same function."""
    from paddle_tpu.ops import decode_ops
    calls = []
    blocked = decode_ops._chunk_attention_blocked
    monkeypatch.setattr(decode_ops, '_chunk_attention_blocked',
                        lambda *a: calls.append(1) or blocked(*a))
    monkeypatch.setattr(decode_ops, '_CHUNK_KEY_BLOCK', 16)
    rng = np.random.RandomState(7)
    bs, maxb, c, n_head, dh, start = 8, 10, 16, 4, 16, 29
    scores = 4 * c * n_head * maxb * bs
    monkeypatch.setattr(decode_ops, '_CHUNK_SCORES_BYTES',
                        scores if budget == 'fits' else scores - 1)
    kc = rng.randn(maxb + 1, bs, n_head * dh).astype(np.float32)
    vc = rng.randn(maxb + 1, bs, n_head * dh).astype(np.float32)
    q = rng.randn(1, c, n_head * dh).astype(np.float32)
    table = np.arange(1, maxb + 1, dtype=np.int32)
    said = []
    got = _chunk_op({'n_head': n_head}, q, kc, vc, start, table, said)
    assert len(calls) == (budget == 'too_large')
    # ... and says which to its Tracer, one entry an op
    assert said == [('kv_block_chunk_attention',
                     'blocked' if calls else 'gathered')]
    want = _whole_view_attention(
        q, kc[1:].reshape(-1, n_head * dh), vc[1:].reshape(-1, n_head * dh),
        start, n_head, n_head, 0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize('start,chunk_len', [
    (0, 32), (32, 32), (64, 32),        # on a key block's edge
    (8, 32), (40, 32), (56, 32),        # inside a key block, inside a page
    (32, 9), (80, 1), (0, 20), (48, 25),    # a prompt's last slice
], ids=lambda v: str(v))
def test_both_chunk_bodies_are_one_function_at_ungrouped_heads(
        start, chunk_len, monkeypatch):
    """OLMoE's head shape — as many K/V heads as query heads, heads of
    128, a bfloat16 pool — on the body its chunk_512 takes since ISSUE 56
    (scores past the budget): float32 'highest' over a PERMUTED table,
    `start` on and off a key block's edge (32 positions here: four pages
    of 8), and a last slice of chunk_len < C whose pad rows reach past the
    slot's allocated span, where the table names the trash block and the
    trash block holds other slots' finite garbage — the real rows against
    the attention written out over the whole view in float64, and against
    the gathered-view body at float32's own tolerance."""
    from paddle_tpu.ops import decode_ops
    monkeypatch.setattr(decode_ops, '_CHUNK_KEY_BLOCK', 32)
    rng = np.random.RandomState(1000 * start + chunk_len)
    bs, maxb, c, n_head, dh = 8, 16, 32, 4, 128
    d = n_head * dh
    written = start + chunk_len                 # positions the slot holds
    own = -(-written // bs)                     # pages allocated to it
    table = np.zeros(maxb, np.int32)            # past them: the trash block
    table[:own] = 1 + rng.permutation(3 * maxb)[:own]
    kc = jnp.asarray(rng.randn(3 * maxb + 1, bs, d), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(3 * maxb + 1, bs, d), jnp.bfloat16)
    q = rng.randn(1, c, d).astype(np.float32)
    kview, vview = (np.asarray(pool, np.float32)[table].reshape(-1, d)
                    for pool in (kc, vc))
    want = _whole_view_attention(q, kview, vview, start, n_head, n_head, 0)
    attrs = {'n_head': n_head}
    said = []
    gathered = _chunk_op(attrs, q, kc, vc, start, table, said)
    monkeypatch.setattr(decode_ops, '_CHUNK_SCORES_BYTES',
                        4 * c * n_head * maxb * bs - 1)
    blocked = _chunk_op(attrs, q, kc, vc, start, table, said)
    assert [body for _, body in said] == ['gathered', 'blocked']
    assert blocked.dtype == np.float32 and np.isfinite(blocked).all()
    # a real row attends the slot's own pages only: rows past chunk_len
    # are pad, and nobody reads them
    np.testing.assert_allclose(blocked[:, :chunk_len], want[:, :chunk_len],
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(gathered[:, :chunk_len],
                               want[:, :chunk_len], rtol=2e-5, atol=2e-6)


def test_quantized_chunk_attention_refuses_grouped_heads_by_name():
    import types
    from paddle_tpu.ops import decode_ops
    attrs = {'n_head': 4, 'n_kv_head': 2}
    ctx = types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))
    with pytest.raises(NotImplementedError, match='grouped K/V heads'):
        decode_ops._chunk_attention_body(
            ctx, jnp.zeros((1, 8, 64)), jnp.zeros((16, 32)),
            jnp.zeros((16, 32)), jnp.zeros((1, 1), jnp.int32), 32)


def test_bfloat16_is_what_the_stated_precision_costs(tmp_path):
    """bfloat16 weights and pools: every matmul rounds its activation and
    K/V are rounded once — measured 2.3e-3 at the median row and 5.1e-3
    at the ninth decile, bounded at about twice that. The largest row
    (0.115 here) is a router flip: a near tie between the fourth and
    fifth score falls the other way and a held expert's whole term comes
    or goes, in either precision; it bounds nothing. And float32's
    tolerance refuses it: the stated precision is what ran."""
    art, w = _export(tmp_path / 'art', 'bfloat16')
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, _prompts(), N_NEW)
    errs = _row_errors(w, tokens, logits)
    assert np.median(errs) <= 5e-3, np.median(errs)
    assert np.quantile(errs, 0.9) <= 1.2e-2, np.quantile(errs, 0.9)
    assert np.median(errs) > 100 * F32_TOL


def _whole_projection_norm(x, w, heads, eps):
    """QK-norm over the whole projection (OLMoE's), not per head."""
    return ref.rms_norm(x, jnp.tile(w, heads), eps)


def _wrong_group(x, w, n_head, n_kv_head, eps, theta, window, rotary):
    """Query head h reading K/V head h % n_kv_head, not h // group."""
    t = x.shape[0]
    g = n_head // n_kv_head
    dh = w['k_w'].shape[1] // n_kv_head
    perm = (np.arange(n_head).reshape(n_kv_head, g).T.reshape(-1)[:, None]
            * dh + np.arange(dh)).reshape(-1)
    return _ATTENTION(x, dict(w, q_w=w['q_w'][:, perm],
                              o_w=w['o_w'][perm]),
                      n_head, n_kv_head, eps, theta, window, rotary)


_ATTENTION = ref.attention


@pytest.mark.parametrize('what', [
    'router_bias', 'scaling', 'renormalisation', 'per_head_norm',
    'window_bound', 'kv_group', 'rotary_on_full_layers', 'shared_expert'])
def test_a_reference_with_one_equation_changed_fails(served, monkeypatch,
                                                     what):
    """The comparison is tight enough to tell: against the reference
    without the selection bias, the scaling, the renormalisation, the
    per-head norm, the window's lower bound, the right K/V group, the
    full layers' missing rotation or the shared expert, the served
    logits are off by far more than the tolerance."""
    _, w, tokens, logits = served
    over = {}
    if what == 'router_bias':
        w = {k: (np.zeros_like(v) if k.endswith('router_bias') else v)
             for k, v in w.items()}
    elif what == 'scaling':
        over = {'scaling': 1.0}
    elif what == 'renormalisation':
        over = {'norm_topk_prob': False}
    elif what == 'per_head_norm':
        monkeypatch.setattr(ref, 'head_norm', _whole_projection_norm)
    elif what == 'window_bound':
        over = {'window': 10 ** 6}
    elif what == 'kv_group':
        monkeypatch.setattr(ref, 'attention', _wrong_group)
    elif what == 'rotary_on_full_layers':
        real = ref._attend
        monkeypatch.setattr(
            ref, '_attend', lambda x, lw, **kw: real(
                x, lw, **dict(kw, rotary=True)))
    elif what == 'shared_expert':
        w = {k: v for k, v in w.items() if '_shared_' not in k}
    inner = what in ('per_head_norm', 'kv_group')
    if inner:                   # the jitted parts were traced unpatched
        jax.clear_caches()
    try:
        errs = _row_errors(w, tokens, logits, **over)
    finally:
        monkeypatch.undo()
        if inner:
            jax.clear_caches()
    assert np.median(errs) > 100 * F32_TOL, (what, np.median(errs))


# -- the two kinds of layer ----------------------------------------------

@pytest.mark.parametrize('prompt', [1, 3])
def test_the_other_side_of_a_near_tie_is_that_row_routed_the_other_way(
        served, monkeypatch, prompt):
    """logits(either_way=...) carries, for a row and a held expert near
    the choice's edge, the row computed again with that expert on the
    other side: the same as a whole pass whose router is forced that way
    at that position of that layer alone (the row attends the sequence
    below it, which the force does not touch) — in sliding and full
    layers, behind several slices, with the pad behind the row."""
    _, w, tokens, _ = served
    seq = np.zeros(TOY['max_cache_len'], np.int64)
    n = len(_prompts()[prompt]) + N_NEW - 1
    seq[:n] = np.concatenate([_prompts()[prompt], tokens[prompt][:-1]])
    rows = np.arange(n - N_NEW, n)
    plain, gap = (np.asarray(a) for a in ref.logits(
        w, seq, routing_gaps=True, **REF))
    _, alt = ref.logits(w, seq, either_way=(
        rows, float(np.median(gap[rows])), 64), **REF)
    assert len(alt['row']) >= N_NEW // 2 and not alt['overflow']
    assert len(set(alt['layer'].tolist())) > 1
    plain_ffn = ref.feed_forward
    for k in range(0, len(alt['row']), max(len(alt['row']) // 6, 1)):
        row, layer, expert = (int(alt[key][k])
                              for key in ('row', 'layer', 'expert'))

        def forced(h, weights, i, *a, **kw):
            if i == layer:
                chosen = np.asarray(ref.routing_distances(
                    h, weights['l%d_moe_router' % i],
                    weights['l%d_moe_router_bias' % i], REF['top_k'],
                    REF['expert_offset'], TOY['n_held'])[1])
                force = np.zeros((h.shape[0], TOY['n_expert']), np.float32)
                force[row, expert] = (
                    -np.inf if chosen[row, expert - REF['expert_offset']]
                    else np.inf)
                kw['force'] = jnp.asarray(force)
            return plain_ffn(h, weights, i, *a, **kw)

        monkeypatch.setattr(ref, 'feed_forward', forced)
        whole = np.asarray(ref.logits(w, seq, **REF))
        monkeypatch.setattr(ref, 'feed_forward', plain_ffn)
        np.testing.assert_allclose(alt['logits'][k], whole[row], rtol=0,
                                   atol=F32_TOL)
        np.testing.assert_allclose(whole[:row], plain[:row], rtol=0,
                                   atol=F32_TOL)
        assert np.abs(whole[row] - plain[row]).max() > 100 * F32_TOL


def test_window_layers_give_blocks_back_and_full_layers_keep_theirs(served):
    """Through the scheduler: a window layer never holds more than
    ceil((window + C) / BS) + 1 blocks of a request, the full layer's
    table grows with the request, every token is the logits path's, and
    at the end both pools are empty."""
    art, _, tokens, _ = served
    bound = window_blocks_per_slot(TOY['window'], max(TOY['chunk_sizes']),
                                   TOY['block_size'])
    assert bound == -(-(16 + 16) // 8) + 1 == 5
    with DecodingPredictor(art) as pred:
        held, full = [], []
        advance = pred._blocks.window_advance

        def watched(table, lo, hi):
            out = advance(table, lo, hi)
            held.append(len(table.blocks))
            return out
        pred._blocks.window_advance = watched
        streams = [pred.submit(p, max_new_tokens=N_NEW) for p in _prompts()]
        got = [list(s.result(120)) for s in streams]
        snap = pred.stats.snapshot()
        stats = pred.block_manager.stats()
    assert got == tokens
    assert max(held) <= bound and max(held) >= 4
    # the full layer keeps every position: all four prompts' blocks at
    # once (they prefill together), and more as they decode
    prompts_alone = sum(-(-n // TOY['block_size']) for n in PROMPTS)
    assert snap['blocks_peak'] > prompts_alone == 18
    assert snap['window_blocks_peak'] <= len(PROMPTS) * bound
    assert snap['window_blocks_peak'] < snap['blocks_peak']
    assert snap['window_blocks_released'] > 0
    assert stats['window_blocks_in_use'] == 0 and stats['blocks_in_use'] == 0
    assert stats['window_num_blocks'] == TOY['max_slots'] * bound


def test_a_released_block_serves_the_next_request_unchanged(served):
    """More requests than slots, so that requests run in blocks others
    gave back, beside requests still running: each transcript is the one
    the request gets when served alone."""
    art, _, _, _ = served
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, TOY['vocab'], n)
               for n in (33, 5, 60, 18, 47, 26, 70, 9, 52)]
    with DecodingPredictor(art) as pred:
        alone = [list(pred.generate(p, max_new_tokens=10, timeout=120))
                 for p in prompts]
        streams = [pred.submit(p, max_new_tokens=10) for p in prompts]
        together = [list(s.result(120)) for s in streams]
        assert pred.stats.snapshot()['window_blocks_released'] > 20
    assert together == alone


def test_kept_feed_on_window_layers_is_the_rebuilt_feed(served):
    """The step's feed is kept between ticks (ISSUE 35), the window
    layers' tables with it: nine requests over 4 slots — prompts shorter
    and longer than the window, slots let again, rows that end by
    max_new, one cancelled mid-decode — and at every step tokens, pos,
    block_tables and window_tables equal what a rebuild from the requests
    gives, a row re-written only in its first step, where its position
    opens a page and where its window leaves one behind; both pools end
    empty."""
    art, _, _, _ = served
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, TOY['vocab'], n)
               for n in (33, 5, 60, 18, 47, 26, 70, 9, 52)]
    with DecodingPredictor(art) as pred:
        alone = [list(pred.generate(p, max_new_tokens=21, timeout=120))
                 for p in prompts]
        pred.stats.reset()
        watch = watch_feed(pred)
        run_tick = pred._run_tick

        def tick(waiting):      # on the scheduler's own thread
            for req in pred._active_requests():
                if req.prompt.size == 18 and req.produced >= 4:
                    req.stream.cancel()
            run_tick(waiting)
        pred._run_tick = tick
        streams = [pred.submit(p, max_new_tokens=21) for p in prompts]
        together = []
        for s in streams:
            try:
                together.append(list(s.result(120)))
            except RuntimeError as e:
                together.append(str(e))
        snap = pred.stats.snapshot()
        stats = pred.block_manager.stats()
        assert not pred._feed_live.any()
        assert (pred._feed_wtables == pred._trash).all()
    assert together[3] == 'request cancelled'
    assert together[:3] + together[4:] == alone[:3] + alone[4:]
    assert snap['feed_rows_live'] == watch.live > 100
    # pages of 8 rows, a window of 16: two events in eight positions,
    # and each row's first step
    assert snap['feed_rows_touched'] == watch.events
    assert 0.25 * watch.live <= watch.events < 0.4 * watch.live
    assert stats['window_blocks_in_use'] == 0 and stats['blocks_in_use'] == 0


def test_signature_and_feeds_name_the_window_pool(served):
    art, _, _, _ = served
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    win = sig['block']['window']
    assert win['length'] == 16 and win['num_blocks'] == 4 * 5 + 1
    # layers 0, 1, 2 and 4 slide; layer 3 is the full one
    assert win['cache_vars'] == ['kv_%s_%d' % (kv, i) for i in (0, 1, 2, 4)
                                 for kv in 'kv']
    shapes = {e['name']: e['shape'] for e in sig['state']}
    assert shapes['kv_k_3'] == [4 * 12 + 1, 8, 32]
    assert shapes['kv_k_0'] == [21, 8, 32]
    assert [e['name'] for e in sig['step']['feeds']] == [
        'tokens', 'pos', 'block_tables', 'window_tables']
    assert [e['name'] for e in sig['chunk']['16']['feeds']] == [
        'chunk_ids', 'start', 'chunk_len', 'block_table', 'window_table',
        'slot']


def test_window_artifact_refuses_beams_and_prefix_reuse_by_name(served):
    art, _, _, _ = served
    with DecodingPredictor(art) as pred:
        with pytest.raises(ValueError, match='window layers'):
            pred.submit([2, 3, 4], beam=2).result(10)
        with pytest.raises(ValueError, match='prefix reuse is refused'):
            pred.block_manager.match_prefix([2] * 40)
        with pytest.raises(ValueError, match='prefix reuse is refused'):
            pred.block_manager.register_prefix([2] * 40, [1, 2])
        prompt = np.arange(2, 42)
        a = list(pred.generate(prompt, max_new_tokens=4, timeout=60))
        b = list(pred.generate(prompt, max_new_tokens=4, timeout=60))
        assert a == b and pred.stats.snapshot()['prefix_hits'] == 0


def test_export_refuses_a_window_pool_under_full_capacity():
    from paddle_tpu.inference import export
    with fluid.unique_name.guard():
        spec = build_decode_spec(**TOY)
    names = list(spec['cache_vars'])
    shapes = {v.name: v.shape
              for v in spec['step']['program'].list_vars()}
    specs = [jax.ShapeDtypeStruct(tuple(shapes[n]), jnp.bfloat16)
             for n in names]
    export._check_window(spec, names, specs)              # as built: fine
    spec['window']['num_blocks'] -= 1
    with pytest.raises(ValueError, match='window cache var'):
        export._check_window(spec, names, specs)
    specs = [jax.ShapeDtypeStruct((s.shape[0] - 1,) + s.shape[1:], s.dtype)
             if n in spec['window']['cache_vars'] else s
             for n, s in zip(names, specs)]
    with pytest.raises(ValueError, match='under full capacity'):
        export._check_window(spec, names, specs)
    with pytest.raises(ValueError, match='no speculative verify'):
        export._check_window(dict(spec, verify={}), names, specs)


def test_shared_expert_lowers_under_its_name_scope():
    """fluid.name_scope('shared_expert') reaches the lowered ops' names,
    which is what a device trace shows."""
    from jax import export as jexport
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(**TOY)
    ops = spec['step']['program'].global_block().ops
    scoped = [op for op in ops
              if op.attrs.get('op_namescope') == 'shared_expert']
    assert sorted({op.type for op in scoped}) == ['elementwise_add', 'mul',
                                                  'swiglu']
    assert len([op for op in scoped if op.type == 'mul']) == 3 * 4


def test_warmup_and_a_poisoned_trash_block(served):
    """warmup() feeds both tables; and with the window layers' trash
    block poisoned (NaN: what a page the window passed may hold),
    prompts longer than the window still give the same tokens — the
    bodies zero the V rows no query attends, as the kernel does."""
    art = served[0]
    with DecodingPredictor(art) as pred:
        pred.warmup()
        clean = [list(pred.generate(p, max_new_tokens=6, timeout=120))
                 for p in _prompts()[2:]]
        names = [e['name'] for e in pred._sig['state']]
        for n in pred._sig['block']['window']['cache_vars']:
            if n.startswith('kv_v_'):
                i = names.index(n)
                pred._state[i] = pred._state[i].at[0].set(jnp.nan)
        poisoned = [list(pred.generate(p, max_new_tokens=6, timeout=120))
                    for p in _prompts()[2:]]
    assert poisoned == clean
