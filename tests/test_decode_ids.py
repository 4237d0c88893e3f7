"""Greedy token selection inside the decode programs (signature version 5):
every token-emitting program returns `ids`, the int32 argmax of its logits,
as fetch 0 beside the float32 logits, and the scheduler copies the ids — the
logits only for a dispatch in which a beam row is live. Since version 6 the
programs also hand each slot's last id from dispatch to dispatch in their
state (the ids row), so that the scheduler dispatches a step before it has
read the one before: section (e).

On any platform the ids equal np.argmax of the logits fetch of the SAME
dispatch, bit for bit, lowest index on ties."""
import json
import os
import shutil

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, decoding, export_decode
from paddle_tpu.testing.decode_logits import served_logits

VOCAB, SLOTS, K = 97, 4, 3
_OLMOE = dict(vocab=128, d_model=64, n_head=4, n_layer=2, n_expert=8,
              d_expert=32, top_k=2, max_slots=SLOTS, max_cache_len=64,
              block_size=8, chunk_sizes=(8, 16))
# the output projection of each builder: [d_model, vocab]
_HEAD = {'block': 'out_w', 'block_int8': 'out_w', 'olmoe': 'lm_head_w'}


def _tie_head(w):
    """A head under which EVERY logits row has two equal maxima away from
    an all-equal row: columns 0 and 1 hold +v, columns 2 and 3 hold -v,
    every other column zero. h.v > 0 ties columns {0, 1}, h.v < 0 ties
    {2, 3} (both pairs sit in one vector lane of the matmul), h.v == 0
    ties every column."""
    v = np.asarray(w[:, 5], np.float32)
    out = np.zeros(w.shape, np.float32)
    out[:, 0] = out[:, 1] = v
    out[:, 2] = out[:, 3] = -v
    return out.astype(w.dtype)


def _export(tmp, name, tie=False):
    art = str(tmp / (name + ('_tie' if tie else '')))
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        if name == 'olmoe':
            from models.olmoe import build_decode_spec
            spec = build_decode_spec(weights_dtype='float32',
                                     kv_cache_dtype='float32', **_OLMOE)
        else:
            from models.transformer import build_decode_spec
            spec = build_decode_spec(
                vocab=VOCAB, d_model=32, n_head=4, n_layer=2, d_ff=64,
                max_slots=SLOTS, max_cache_len=48, eos_id=1,
                chunk_sizes=(8, 16), draft_k=K, block_size=4,
                kv_cache_dtype='int8' if name == 'block_int8'
                else 'float32')
        spec['startup'].random_seed = 11
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        if tie:
            head = np.asarray(scope.get(_HEAD[name]))
            scope.set(_HEAD[name], _tie_head(head))
        export_decode(spec, art, scope=scope)
    return art


@pytest.fixture(scope='module')
def arts(tmp_path_factory):
    tmp = tmp_path_factory.mktemp('ids')
    made = {}

    def get(name, tie=False):
        if (name, tie) not in made:
            made[name, tie] = _export(tmp, name, tie)
        return made[name, tie]
    return get


def _prompts(vocab):
    rng = np.random.RandomState(5)
    return [rng.randint(2, vocab, n) for n in (3, 7, 13, 16, 5, 9)]


# -- (a) ids == argmax of the same dispatch's logits -------------------------

def _dispatches(pred, program):
    """[(ids, logits)] of a few dispatches of `program` made through the
    predictor's own dispatch functions, logits asked for: three prompts
    prefilled into slots 0..2, then the program under test."""
    S, vocab = pred.max_slots, pred._vocab
    prompts = _prompts(vocab)[:3]
    out = {'chunk': [], 'step': [], 'verify': []}
    maxb = pred._maxb
    tables = np.full((S, maxb), pred._trash, np.int32)
    for i in range(len(prompts)):
        tables[i] = 1 + i * maxb + np.arange(maxb)
    last = []
    for i, prompt in enumerate(prompts):
        start = 0
        while start < len(prompt):
            left = len(prompt) - start
            size = next((c for c in pred._chunks if c >= left),
                        pred._chunks[-1])
            take = min(size, left)
            ids = np.zeros((1, size), np.int64)
            ids[0, :take] = prompt[start:start + take]
            tok, row = decoding._one_row(*pred._to_host(
                pred._dispatch_chunk(size, ids, start, take,
                                     tables[i:i + 1], logits=True)))
            out['chunk'].append((np.asarray([tok], np.int32), row[None]))
            start += take
        last.append(tok)
    if program == 'verify':
        R = K + 1
        tok = np.zeros((S, R), np.int64)
        pos = np.full((S, R), pred._maxb * pred._bs, np.int32)
        for i, prompt in enumerate(prompts):
            tok[i] = [last[i], 7, 9, 11]
            pos[i] = len(prompt) + np.arange(R)
        out['verify'].append(pred._to_host(
            pred._dispatch_verify(tok, pos, tables, logits=True)))
    elif program == 'step':
        for j in range(3):
            tok = np.zeros((S, 1), np.int64)
            pos = np.zeros((S, 1), np.int32)
            for i, prompt in enumerate(prompts):
                tok[i, 0] = last[i]
                pos[i, 0] = len(prompt) + j
            ids, logits = pred._to_host(
                pred._dispatch_step(tok, pos, tables, logits=True))
            out['step'].append((ids, logits))
            last = ids.tolist()
    return out[program]


@pytest.mark.parametrize('tie', [False, True], ids=['seeded', 'tied'])
@pytest.mark.parametrize('name,program', [
    ('block', 'step'), ('block', 'chunk'), ('block', 'verify'),
    ('block_int8', 'step'), ('block_int8', 'chunk'),
    ('block_int8', 'verify'),
    ('olmoe', 'step'), ('olmoe', 'chunk')])
def test_ids_are_the_argmax_of_the_same_dispatch(arts, name, program, tie):
    with DecodingPredictor(arts(name, tie)) as pred:
        got = _dispatches(pred, program)
        assert pred.stats.snapshot()['logits_fetches'] >= len(got)
    assert got
    for ids, logits in got:
        assert ids.dtype == np.int32 and logits.dtype == np.float32
        assert ids.shape == logits.shape[:-1]
        assert np.array_equal(ids, np.argmax(logits, axis=-1))
        if tie:
            # every row holds its maximum at least twice, and the lowest
            # index won
            rows = logits.reshape(-1, logits.shape[-1])
            assert ((rows == rows.max(-1, keepdims=True)).sum(-1) >= 2).all()
            assert set(ids.reshape(-1).tolist()) <= {0, 2}


@pytest.mark.parametrize('name', ['block', 'block_int8', 'olmoe'])
def test_signature_names_both_fetches(arts, name):
    with open(os.path.join(arts(name), decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    assert sig['version'] == decoding._SIG_VERSION == 6
    entries = [sig['step']] + list(sig['chunk'].values()) \
        + ([sig['verify']] if 'verify' in sig else [])
    assert len(entries) >= 3
    for e in entries:
        assert len(e['fetches']) == 2 and e['fetches'][0] == 'ids'


# -- (b) what the scheduler copies -------------------------------------------

class _Copies(object):
    """Every (program, fetch, bytes) the scheduler's one copy site moved:
    the stats of its decode/d2h spans, read where they are made."""

    def __init__(self, monkeypatch):
        self.seen = []
        real = decoding._span

        def span(name, **stats):
            if name == 'decode/d2h':
                self.seen.append((stats['program'], stats['fetch'],
                                  stats['bytes']))
            return real(name, **stats)
        monkeypatch.setattr(decoding, '_span', span)


@pytest.mark.parametrize('name', ['block', 'block_int8', 'olmoe'])
def test_greedy_serving_copies_ids_only(arts, name, monkeypatch):
    copies = _Copies(monkeypatch)
    with DecodingPredictor(arts(name)) as pred:
        streams = [pred.submit(p, max_new_tokens=6)
                   for p in _prompts(pred._vocab)]
        assert all(len(s.result(120)) >= 1 for s in streams)
        snap, rows = pred.stats.snapshot(), pred._rows
    assert snap['logits_fetches'] == 0 and snap['steps'] > 0
    assert {f for _, f, _ in copies.seen} == {'ids'}
    assert {b for p, _, b in copies.seen if p == 'step'} == {SLOTS * 4}
    # a slice's copy is its one id; a call of the row program (the float
    # pools' artifacts hold one: six prompts at once ride it) copies R
    assert rows == (1 if name == 'block_int8' else 4)
    assert {4 * rows} <= {b for p, _, b in copies.seen if p != 'step'} \
        <= {4, 4 * rows}


# what the parent commit served for these requests on this spec: 'block' at
# PR 26 (host argmax over the copied logits), 'block_int8' at PR 27 (its
# block artifact; nothing checked the quantized programs' ids before)
_PARENT_GREEDY = {
    'block': [[80, 80, 80, 81, 54, 81, 54, 80],
              [88, 60, 83, 81, 88, 60, 81, 88]],
    'block_int8': [[25, 42, 42, 42, 23, 42, 23, 42], [7, 91, 91, 1]]}
_PARENT_BEAM_IDS = {
    'block': [[54, 81, 88, 60, 81, 81, 81, 81],
              [54, 81, 88, 60, 83, 81, 81, 81]],
    'block_int8': [[96, 52, 2, 2, 2, 2, 2, 2],
                   [96, 7, 2, 2, 2, 2, 2, 2]]}
_PARENT_BEAM_SCORES = {
    'block': [-25.31855396037914, -25.322833602904396],
    'block_int8': [-24.096582432523846, -24.30078494080831]}
_PARENT_SPEC = {
    'block': [[81, 88, 23, 54, 82, 65, 81, 81, 88, 54, 62, 88],
              [81, 88, 54, 81, 81, 54, 81, 88, 54, 81, 88, 60]],
    'block_int8': [[2, 2, 2, 2, 37, 51, 2, 2, 2, 37, 2, 2],
                   [2, 52, 2, 37, 51, 2, 96, 96, 96, 96, 96, 96]]}


@pytest.mark.parametrize('name', ['block', 'block_int8'])
def test_a_live_beam_fetches_logits_and_serves_the_parents_beam(
        arts, name, monkeypatch):
    copies = _Copies(monkeypatch)
    prompts = _prompts(VOCAB)
    with DecodingPredictor(arts(name)) as pred:
        g1 = pred.submit(prompts[0], max_new_tokens=8)
        beam = pred.submit(prompts[2], max_new_tokens=8, beam=2)
        g2 = pred.submit(prompts[1], max_new_tokens=8)
        ids, scores = beam.result(120)
        greedy = [[int(t) for t in s.result(120)] for s in (g1, g2)]
        snap = pred.stats.snapshot()
    assert np.asarray(ids).tolist() == _PARENT_BEAM_IDS[name]
    assert [float(x) for x in scores] == _PARENT_BEAM_SCORES[name]
    assert greedy == _PARENT_GREEDY[name]
    # the beam's last prompt slice — a call of its bucket's one-row
    # program, whoever admits beside it — and every step it rode copied
    # logits (ids beside them); the greedy requests' prompts copied ids
    logits = [(p, b) for p, f, b in copies.seen if f == 'logits']
    assert snap['logits_fetches'] == len(logits) == 8
    assert {b for p, b in logits if p == 'step'} == {SLOTS * (VOCAB + 1) * 4}
    assert {b for p, b in logits if p != 'step'} == {(VOCAB + 1) * 4}
    assert len(logits) < len(copies.seen)


@pytest.mark.parametrize('name', ['block', 'block_int8'])
def test_speculative_serving_reads_the_verify_ids(arts, name, monkeypatch):
    copies = _Copies(monkeypatch)
    prompts = _prompts(VOCAB)
    with DecodingPredictor(arts(name), draft='ngram') as pred:
        got = [[int(t) for t in
                pred.submit(p, max_new_tokens=12).result(120)]
               for p in (np.tile(prompts[1][:4], 4), prompts[3])]
        snap = pred.stats.snapshot()
    assert got == _PARENT_SPEC[name]
    assert snap['verify_steps'] > 0 and snap['logits_fetches'] == 0
    assert {(f, b) for p, f, b in copies.seen if p == 'verify'} \
        == {('ids', SLOTS * (K + 1) * 4)}


# -- (c) an older artifact is refused by name --------------------------------

def test_a_version_4_artifact_is_refused_by_name(arts, tmp_path):
    art = str(tmp_path / 'v4')
    shutil.copytree(arts('block'), art)
    path = os.path.join(art, decoding._DECODE_SIGNATURE)
    with open(path) as f:
        sig = json.load(f)
    sig['version'] = 4
    with open(path, 'w') as f:
        json.dump(sig, f)
    with pytest.raises(ValueError, match='logits alone.*export_decode'):
        DecodingPredictor(art)
    with pytest.raises(ValueError, match='export it again'):
        decoding.precompile_decode_artifact(art)


# -- (d) the logits are still there for who asks -----------------------------

@pytest.mark.parametrize('name', ['block', 'olmoe'])
def test_served_logits_still_returns_rows(arts, name):
    n_new = 5
    with DecodingPredictor(arts(name)) as pred:
        vocab = pred._vocab
        prompts = _prompts(vocab)[:3]
        tokens, logits = served_logits(pred, prompts, n_new)
        assert pred.stats.snapshot()['logits_fetches'] == 0    # reset after
        served = [[int(t) for t in
                   pred.submit(p, max_new_tokens=n_new).result(120)]
                  for p in prompts]
    for toks, rows in zip(tokens, logits):
        assert rows.shape == (n_new, vocab) and rows.dtype == np.float32
        assert toks == np.argmax(rows, -1).tolist()
    # the scheduler, reading ids, serves the tokens those rows choose
    # (until eos ends a stream early)
    for toks, got in zip(tokens, served):
        assert got == toks[:len(got)]


# -- (e) the ids row: a row's token handed on, on the device -----------------

def _row(pred):
    return np.asarray(pred._state[-1]).tolist()


@pytest.mark.parametrize('name', ['block', 'block_int8', 'olmoe'])
def test_a_slice_writes_its_slot_and_the_step_takes_it_from_there(arts, name):
    """A chunk dispatched with `slot` writes the id its last real
    position chose into that entry of the state's last array and no
    other (a negative slot: nothing); a step fed -1 takes that entry as
    the row's token — the same logits, bit for bit, as the step fed the
    token by the host — and leaves its own ids there."""
    with DecodingPredictor(arts(name)) as pred:
        S, maxb = pred.max_slots, pred._maxb
        prompts = _prompts(pred._vocab)[:3]
        tables = np.full((S, maxb), pred._trash, np.int32)
        first = []
        for i, prompt in enumerate(prompts):
            tables[i] = 1 + i * maxb + np.arange(maxb)
            size = pred._chunks[-1]
            ids = np.zeros((1, size), np.int64)
            ids[0, :len(prompt)] = prompt
            before = _row(pred)
            # not the prompt's last slice as far as the program knows
            tok, _ = decoding._one_row(*pred._to_host(pred._dispatch_chunk(
                size, ids, 0, len(prompt), tables[i:i + 1])))
            assert _row(pred) == before
            again, _ = decoding._one_row(*pred._to_host(pred._dispatch_chunk(
                size, ids, 0, len(prompt), tables[i:i + 1], slot=i)))
            assert again == tok
            assert _row(pred) == before[:i] + [tok] + before[i + 1:]
            first.append(tok)
        pos = np.zeros((S, 1), np.int32)
        host = np.zeros((S, 1), np.int64)
        for i, prompt in enumerate(prompts):
            pos[i, 0] = len(prompt)
            host[i, 0] = first[i]
        want = pred._to_host(pred._dispatch_step(host, pos, tables,
                                                 logits=True))
        assert _row(pred) == want[0].tolist()
        # put the slices' ids back (the step above overwrote them), then
        # let the device supply rows 0..2 and the host row 3
        for i, prompt in enumerate(prompts):
            ids = np.zeros((1, pred._chunks[-1]), np.int64)
            ids[0, :len(prompt)] = prompt
            pred._dispatch_chunk(pred._chunks[-1], ids, 0, len(prompt),
                                 tables[i:i + 1], read=False, slot=i)
        dev = np.full((S, 1), -1, np.int64)
        dev[3, 0] = 0
        got = pred._to_host(pred._dispatch_step(dev, pos, tables,
                                                logits=True))
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        # the next step, fed nothing by the host, continues from the ids
        # the last one left in the row
        nxt_host = pred._to_host(pred._dispatch_step(
            np.asarray(want[0], np.int64)[:, None], pos + 1, tables,
            logits=True))
        for i, prompt in enumerate(prompts):    # rewind the row
            pred._state[-1] = pred._state[-1].at[i].set(int(want[0][i]))
        nxt_dev = pred._to_host(pred._dispatch_step(
            np.full((S, 1), -1, np.int64), pos + 1, tables, logits=True))
        assert np.array_equal(nxt_dev[1][:3], nxt_host[1][:3])


def test_verify_and_blockcopy_thread_the_row_through(arts):
    with DecodingPredictor(arts('block')) as pred:
        S = pred.max_slots
        pred._state[-1] = pred._state[-1].at[:].set(
            np.arange(5, 5 + S, dtype=np.int32))
        tables = np.full((S, pred._maxb), pred._trash, np.int32)
        R = K + 1
        pred._to_host(pred._dispatch_verify(
            np.zeros((S, R), np.int64),
            np.full((S, R), pred._maxb * pred._bs, np.int32), tables))
        pred._dispatch_blockcopy([])
        assert _row(pred) == list(range(5, 5 + S))
        pred._reset_state()
        assert _row(pred) == [0] * S


class _StepEvents(object):
    """'D' for each dispatch of the step program and 'H' for each copy of
    a step's ids to the host, in the order the scheduler made them."""

    def __init__(self, monkeypatch):
        self.seen = []
        real = decoding._span

        def span(name, **stats):
            if stats.get('program') == 'step' and name in (
                    'decode/dispatch', 'decode/d2h'):
                self.seen.append('D' if name == 'decode/dispatch' else 'H')
            return real(name, **stats)
        monkeypatch.setattr(decoding, '_span', span)


@pytest.mark.parametrize('name', ['block', 'olmoe'])
def test_step_k_plus_1_is_dispatched_before_step_k_is_read(arts, name,
                                                            monkeypatch):
    events = _StepEvents(monkeypatch)
    with DecodingPredictor(arts(name)) as pred:
        pred._eos = -1          # run to max_new: 1 token of the slice + 5
        toks = pred.submit(_prompts(pred._vocab)[1],
                           max_new_tokens=6).result(120)
        snap = pred.stats.snapshot()
    assert len(toks) == 6
    # dispatch(1) dispatch(2) read(1) dispatch(3) read(2) ... read(5)
    assert ''.join(events.seen) == 'D' + 'DH' * 4 + 'H'
    assert snap['steps'] == snap['steps_ahead'] == 5
    assert snap['wasted_rows'] == 0


@pytest.mark.parametrize('how', ['beam', 'drafter'])
def test_a_beam_row_or_a_drafter_takes_the_settled_order(arts, how,
                                                         monkeypatch):
    """Where the host must see a step's result before it can build the
    next feed, every step is read before the next is dispatched."""
    events = _StepEvents(monkeypatch)
    prompts = _prompts(VOCAB)
    kw = {'draft': 'ngram'} if how == 'drafter' else {}
    with DecodingPredictor(arts('block'), **kw) as pred:
        if how == 'beam':
            pred.submit(prompts[2], max_new_tokens=8, beam=2).result(120)
        else:
            for p in (np.tile(prompts[1][:4], 4), prompts[3]):
                pred.submit(p, max_new_tokens=12).result(120)
        snap = pred.stats.snapshot()
        assert pred._unread is None
    assert snap['steps'] > 0 and snap['steps_ahead'] == 0
    assert snap['wasted_rows'] == 0
    assert ''.join(events.seen) == 'DH' * snap['steps']


# -- (f) the chunk program's row dimension: every R = 1 program is what it
# was, and which spec holds a row program follows from its shapes ------------

# sha256 (16 hex digits) of the location-free StableHLO text of every module
# the parent of PR 39 exported for these three specs: a chunk op with a
# leading dimension of 1 lowers to the expression it always had
_PARENT_STABLEHLO = {
    'transformer_base_lm/decode_blockcopy': '981e930e2bdfa797',
    'transformer_base_lm/decode_step': '4a90334ca35afd6e',
    'transformer_base_lm/decode_zeros': 'a07c8904ffbd40c2',
    'transformer_base_lm/prefill_chunk_00008': '5c076e84cced5158',
    'transformer_base_lm/prefill_chunk_00016': '1754ec5d1c3d3932',
    'olmoe_1b_7b/decode_blockcopy': 'ccd86492049dc682',
    'olmoe_1b_7b/decode_step': '4790b3c3458b86db',
    'olmoe_1b_7b/decode_zeros': 'd1f3ab8996686fba',
    'olmoe_1b_7b/prefill_chunk_00008': 'd1b14246625acd6b',
    'olmoe_1b_7b/prefill_chunk_00016': 'dbf53268fad550ac',
    'k_exaone_236b_a23b/decode_blockcopy': '149381b1d6f6401a',
    'k_exaone_236b_a23b/decode_step': '48308d8960781fb1',
    'k_exaone_236b_a23b/decode_zeros': '8650ecbf8a90380f',
    'k_exaone_236b_a23b/prefill_chunk_00008': 'f0e3486c93c5f369',
    'k_exaone_236b_a23b/prefill_chunk_00016': '2cf6d1530b0afb36'}
# the one module each spec gained: its largest chunk at four rows
_ROW_MODULES = {'transformer_base_lm': ['prefill_chunk_00016x4'],
                'olmoe_1b_7b': ['prefill_chunk_00016x4'],
                'k_exaone_236b_a23b': []}
# what models.transformer.build_decode_spec alone offers, one build each
_VARIANTS = {'bfloat16': dict(kv_cache_dtype='bfloat16'),
             'int8': dict(kv_cache_dtype='int8'),
             'draft_k2': dict(draft_k=2), 'mp2': dict(mp_shard=2)}
_MODULE = {'k_exaone_236b_a23b': 'exaone_moe',
           'joyai_llm_flash': 'joyai_llm_flash',
           'qwen3_next_80b_a3b': 'qwen3_next',
           'phi4_mini_flash_reasoning': 'phi4_flash'}
# recorded on the parent of PR 45 (260716d), before models/transformer.py
# moved onto DecodeSpecBuilder: transformer_base_lm's row module and every
# module of its bfloat16, int8, draft_k=2 (the verify module with it) and
# mp_shard=2 builds, and what the builder's other models export that the
# table above does not hold
_PARENT_STABLEHLO_45 = {
    'joyai_llm_flash/decode_blockcopy': '842426bc95f02e01',
    'joyai_llm_flash/decode_step': 'ce202f3e29000790',
    'joyai_llm_flash/decode_zeros': '91b42ee05ce347c2',
    'joyai_llm_flash/prefill_chunk_00008': '713044675ef21ea5',
    'joyai_llm_flash/prefill_chunk_00016': '3bea14a129ad6576',
    'olmoe_1b_7b/prefill_chunk_00016x4': '75839f03abba323d',
    'qwen3_next_80b_a3b/decode_blockcopy': '9f5f587c18554e27',
    'qwen3_next_80b_a3b/decode_step': '2a88114c1cffb19a',
    'qwen3_next_80b_a3b/decode_zeros': '0543b1fdf5d768e7',
    'qwen3_next_80b_a3b/prefill_chunk_00008': '71023751d0fdbf2d',
    'qwen3_next_80b_a3b/prefill_chunk_00016': '24c1d3952e41e2a5',
    'transformer_base_lm+bfloat16/decode_blockcopy':
        '1d8ac103f86c5154',
    'transformer_base_lm+bfloat16/decode_step': '26abcc27884b9fdc',
    'transformer_base_lm+bfloat16/decode_zeros': '5a67c68311d3b990',
    'transformer_base_lm+bfloat16/prefill_chunk_00008':
        'ab8a299bcb6a9bc5',
    'transformer_base_lm+bfloat16/prefill_chunk_00016':
        '39b41c724900b813',
    'transformer_base_lm+bfloat16/prefill_chunk_00016x4':
        'a35d3990161b6a2f',
    'transformer_base_lm+draft_k2/decode_blockcopy':
        '981e930e2bdfa797',
    'transformer_base_lm+draft_k2/decode_step': '4a90334ca35afd6e',
    'transformer_base_lm+draft_k2/decode_verify': 'af25300c9df06327',
    'transformer_base_lm+draft_k2/decode_zeros': 'a07c8904ffbd40c2',
    'transformer_base_lm+draft_k2/prefill_chunk_00008':
        '5c076e84cced5158',
    'transformer_base_lm+draft_k2/prefill_chunk_00016':
        '1754ec5d1c3d3932',
    'transformer_base_lm+draft_k2/prefill_chunk_00016x4':
        '910aed065ac1e53a',
    'transformer_base_lm+int8/decode_blockcopy': '400f09046012f2fc',
    'transformer_base_lm+int8/decode_step': '638d3437d48e83a4',
    'transformer_base_lm+int8/decode_zeros': 'baf0611b912571e8',
    'transformer_base_lm+int8/prefill_chunk_00008': 'c0b2dd3386558a34',
    'transformer_base_lm+int8/prefill_chunk_00016': 'aa617c76f5b9851c',
    'transformer_base_lm+mp2/decode_blockcopy': '7b9b29bd740602df',
    'transformer_base_lm+mp2/decode_step': '5117d2eda729b0ef',
    'transformer_base_lm+mp2/decode_zeros': '20e8fd6f13a2791f',
    'transformer_base_lm+mp2/prefill_chunk_00008': 'dc470a83baff6e5d',
    'transformer_base_lm+mp2/prefill_chunk_00016': '8484674f0344955f',
    'transformer_base_lm+mp2/prefill_chunk_00016x4':
        'ccc0b66f27b26b73',
    'transformer_base_lm/prefill_chunk_00016x4': '910aed065ac1e53a'}
# and, for each of the five decode models and each variant, what the startup
# program leaves in the scope at random_seed 11 (name, shape, dtype, bytes
# of every variable) and the artifact's signature (programs, feeds,
# fetches, parameters, state): (weights, signature)
_PARENT_WEIGHTS_AND_SIGNATURE = {
    'joyai_llm_flash':
        ('1188206486089f03', 'b53382801d07ad12'),
    'k_exaone_236b_a23b':
        ('62a712e6740b3df1', '3b8838cd6ce04ec7'),
    'olmoe_1b_7b':
        ('96e3d61335d27c56', 'd1a707e1d7aa678f'),
    'qwen3_next_80b_a3b':
        ('ff48f602a5acd0c9', '286b0c291897c5b3'),
    'transformer_base_lm':
        ('f84f62cd8c337808', '5ac0247650a955e4'),
    'transformer_base_lm+bfloat16':
        ('399a39d6d0c69f6d', 'd5ffe821734b0c45'),
    'transformer_base_lm+draft_k2':
        ('f84f62cd8c337808', 'aae7f43ee8588903'),
    'transformer_base_lm+int8':
        ('33c7095112af711c', '297665ebb96396e7'),
    'transformer_base_lm+mp2':
        ('f84f62cd8c337808', 'c6f8ff8d26558ef2')}


def _rehearsal_spec(config, **kw):
    """The decode configurations at toy widths, the rehearsal's chunks
    (8, 16) unless `kw` says otherwise; 'config+variant' is
    transformer_base_lm with one of `_VARIANTS`' arguments."""
    config, _, variant = config.partition('+')
    if config == 'transformer_base_lm':
        from models.transformer import build_decode_spec
        args = dict(vocab=97, d_model=32, n_head=4, n_layer=2, d_ff=64,
                    max_slots=4, max_cache_len=48, eos_id=1,
                    chunk_sizes=(8, 16), block_size=4)
        args.update(_VARIANTS[variant] if variant else {})
    elif config == 'olmoe_1b_7b':
        from models.olmoe import build_decode_spec
        args = dict(_OLMOE)
    else:
        import importlib
        build_decode_spec = importlib.import_module(
            'models.' + _MODULE[config]).build_decode_spec
        args = {}
    args.update(kw)
    return build_decode_spec(**args)


def _location_free(text):
    """StableHLO text without its `loc(...)` marks: they name export.py's
    lines, which move with every edit of that file."""
    out = []
    for line in text.splitlines():
        if line.startswith('#loc'):
            continue
        while ' loc(' in line:
            i = line.index(' loc(')
            depth, j = 0, i + 4
            while True:
                depth += {'(': 1, ')': -1}.get(line[j], 0)
                j += 1
                if depth == 0:
                    break
            line = line[:i] + line[j:]
        out.append(line)
    return '\n'.join(out)


def _digest(*parts):
    import hashlib
    h = hashlib.sha256()
    for part in parts:
        h.update(part if isinstance(part, bytes) else str(part).encode())
    return h.hexdigest()[:16]


@pytest.fixture(scope='module')
def exported(tmp_path_factory):
    """One spec's export, made once a configuration: {'modules': {module
    directory: hash of its text}, 'weights': digest of the scope the
    startup program left, 'signature': digest of the artifact's
    signature}."""
    from jax import export as jexport
    tmp = tmp_path_factory.mktemp('stablehlo')
    made = {}

    def get(config):
        if config not in made:
            art = str(tmp / config)
            scope = fluid.core.Scope()
            with fluid.scope_guard(scope), fluid.unique_name.guard():
                spec = _rehearsal_spec(config)
                spec['startup'].random_seed = 11
                fluid.Executor(fluid.CPUPlace()).run(spec['startup'],
                                                     scope=scope)
                weights = []
                for name in sorted(scope.local_var_names()):
                    a = np.asarray(scope.get(name))
                    weights += [name, a.shape, a.dtype.name, a.tobytes()]
                export_decode(spec, art, scope=scope, precompile=False)
            modules = {}
            for d in sorted(os.listdir(art)):
                path = os.path.join(art, d, 'module.jaxexport')
                if os.path.exists(path):
                    with open(path, 'rb') as f:
                        text = jexport.deserialize(f.read()).mlir_module()
                    modules[d] = _digest(_location_free(text))
            with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
                sig = json.load(f)
            # since PR 48 gated_delta_chunk says which body it lowered:
            # the digests were recorded without that entry, so it is
            # taken out here and held to its own test
            said = {size: entry['attention'].pop('gated_delta_chunk', None)
                    for size, entry in sig['chunk'].items()}
            # ... and since PR 54 kv_block_chunk_write: the same
            chunks = dict(sig['chunk'], **({'rows': sig['chunk_rows']}
                                           if 'chunk_rows' in sig else {}))
            wrote = {size: entry['attention'].pop('kv_block_chunk_write',
                                                  None)
                     for size, entry in chunks.items()}
            # ... and since PR 56 kv_block_chunk_attention says
            # 'gathered' | 'blocked' where the digests hold 'jnp', the
            # word export_decode gave an op that said nothing: put back
            # for the digest, and held to its own test
            attended = {}
            for size, entry in chunks.items():
                body = entry['attention'].get('kv_block_chunk_attention')
                attended[size] = body
                if body is not None:
                    entry['attention']['kv_block_chunk_attention'] = {
                        'jnp': sum(body.values())}
            made[config] = {
                'modules': modules, 'weights': _digest(*weights),
                'signature': _digest(json.dumps(sig, sort_keys=True)),
                'chunk_rule': said, 'chunk_write': wrote,
                'chunk_attention': attended}
        return made[config]
    return get


@pytest.fixture(scope='module')
def stablehlo(exported):
    return lambda config: exported(config)['modules']


# recorded in PR 47, which brought models/phi4_flash.py (the builder's
# shared_pools / no_cache / last_only_from arguments with it: every table
# above was recorded WITHOUT them and holds with them): that model's modules
# at its toy widths, for the PRs after it
_STABLEHLO_47 = {
    'phi4_mini_flash_reasoning/decode_blockcopy': 'b1b733455362e885',
    'phi4_mini_flash_reasoning/decode_step': 'd098e911b96ee945',
    'phi4_mini_flash_reasoning/decode_zeros': '8ef474a32769d8d4',
    'phi4_mini_flash_reasoning/prefill_chunk_00008': '5a8223a29dbb3664',
    'phi4_mini_flash_reasoning/prefill_chunk_00016': '56bfbb3268e413bf'}
# recorded in PR 54, which made kv_block_chunk_write write a chunk of whole
# pages a page at a time behind a branch on start % BS (ops/decode_ops.py):
# every chunk module that holds the op — all but the int8 pool's, whose
# _quant form kept its body — is another text, and these take the place of
# their entries in the three tables above; the step, blockcopy and zeros
# modules are the parents' still
_STABLEHLO_54 = {
    'joyai_llm_flash/prefill_chunk_00008': '6c8b984035e0ba8e',
    'joyai_llm_flash/prefill_chunk_00016': 'f4694b9c0591101a',
    'k_exaone_236b_a23b/prefill_chunk_00008': '126ad49c6156f6ed',
    'k_exaone_236b_a23b/prefill_chunk_00016': '1c0f8b3d7f756d4b',
    'olmoe_1b_7b/prefill_chunk_00008': 'b74f7621ed3d87ff',
    'olmoe_1b_7b/prefill_chunk_00016': '0c69ccdc56ab451a',
    'olmoe_1b_7b/prefill_chunk_00016x4': '6b0be1bb5da776d2',
    'phi4_mini_flash_reasoning/prefill_chunk_00008': 'e578b8a81cc7d857',
    'phi4_mini_flash_reasoning/prefill_chunk_00016': '463246618f050bff',
    'qwen3_next_80b_a3b/prefill_chunk_00008': '998643bfdff3b679',
    'qwen3_next_80b_a3b/prefill_chunk_00016': '4aee1c42c323f0e8',
    'transformer_base_lm+bfloat16/prefill_chunk_00008': 'e8b88ec4727a55d6',
    'transformer_base_lm+bfloat16/prefill_chunk_00016': '2a235b441a0d0c38',
    'transformer_base_lm+bfloat16/prefill_chunk_00016x4': 'a97d5a51b10d036f',
    'transformer_base_lm+draft_k2/prefill_chunk_00008': 'd398a376f8da1138',
    'transformer_base_lm+draft_k2/prefill_chunk_00016': '2ca9f799d0074412',
    'transformer_base_lm+draft_k2/prefill_chunk_00016x4': 'b69dcd330a841bb2',
    'transformer_base_lm+mp2/prefill_chunk_00008': 'b185f84d030c0ac0',
    'transformer_base_lm+mp2/prefill_chunk_00016': '35e2d51661e93c71',
    'transformer_base_lm+mp2/prefill_chunk_00016x4': '18ef9c3ed76dc94f',
    'transformer_base_lm/prefill_chunk_00008': 'd398a376f8da1138',
    'transformer_base_lm/prefill_chunk_00016': '2ca9f799d0074412',
    'transformer_base_lm/prefill_chunk_00016x4': 'b69dcd330a841bb2'}
_PINNED = {**_PARENT_STABLEHLO, **_PARENT_STABLEHLO_45, **_STABLEHLO_47,
           **_STABLEHLO_54}


@pytest.mark.parametrize('module', sorted(_PARENT_STABLEHLO)
                         + sorted(_PARENT_STABLEHLO_45)
                         + sorted(_STABLEHLO_47))
def test_every_one_row_program_is_the_parents_stablehlo(stablehlo, module):
    config, d = module.split('/')
    assert stablehlo(config)[d] == _PINNED[module]


@pytest.mark.parametrize('config', sorted(_PARENT_WEIGHTS_AND_SIGNATURE))
def test_startup_weights_and_signature_are_the_parents(exported, config):
    """The same variables under the same names drawn from the same seed,
    and an artifact that declares the same programs, feeds, fetches,
    parameters and state: to the scheduler, the parent's."""
    got = exported(config)
    assert (got['weights'], got['signature']) \
        == _PARENT_WEIGHTS_AND_SIGNATURE[config]


@pytest.mark.parametrize('config', sorted(_PARENT_WEIGHTS_AND_SIGNATURE))
def test_only_the_chunked_delta_rule_says_a_body_of_its_own(exported,
                                                            config):
    """What PR 48 added to a signature: each chunk program of the one model
    that lowers gated_delta_chunk names the body it lowered — at the toy
    widths the kernel refuses, the parent's expression (its StableHLO is
    pinned above) — and no other model's signature gained a word."""
    said = exported(config)['chunk_rule']
    want = {'jnp': 3} if config == 'qwen3_next_80b_a3b' else None
    assert said == {'8': want, '16': want}


@pytest.mark.parametrize('config', sorted(_PARENT_WEIGHTS_AND_SIGNATURE))
def test_every_chunk_program_says_how_it_writes_its_pages(exported, config):
    """What PR 54 added to a signature: each chunk program — the row
    program too — names the body its kv_block_chunk_write ops lowered,
    one entry a K pool, a V pool or a latent pool of a caching layer. The
    toy chunks (8, 16) are whole pages of every toy block size, so it is
    'pages'; the int8 pool's _quant form reports nothing."""
    wrote = exported(config)['chunk_write']
    pools = {'joyai_llm_flash': 3, 'k_exaone_236b_a23b': 10,
             'olmoe_1b_7b': 4, 'qwen3_next_80b_a3b': 2}.get(config, 4)
    want = None if config.endswith('+int8') else {'pages': pools}
    sizes = {'8', '16'} | ({'rows'} if config.split('+')[0] in (
        'transformer_base_lm', 'olmoe_1b_7b') and want else set())
    assert wrote == dict.fromkeys(sizes, want)


@pytest.mark.parametrize('config', sorted(_PARENT_WEIGHTS_AND_SIGNATURE))
def test_every_chunk_program_says_which_body_its_attention_took(exported,
                                                                config):
    """What PR 56 added to a signature: each chunk program — the row
    program too — names the body its kv_block_chunk_attention ops
    lowered, one entry a caching layer: the gathered view where heads are
    ungrouped, nothing is windowed and the scores are inside the budget
    (transformer_base_lm and olmoe at these toy sizes, all three of their
    programs), the blocked body under grouped heads, a window or a latent
    pool; the int8 pool's _quant form has the one body and says nothing."""
    took = exported(config)['chunk_attention']
    base = config.split('+')[0]
    layers = {'joyai_llm_flash': 3, 'k_exaone_236b_a23b': 5,
              'qwen3_next_80b_a3b': 1}.get(config, 2)
    want = (None if config.endswith('+int8') else
            {'gathered' if base in ('transformer_base_lm', 'olmoe_1b_7b')
             else 'blocked': layers})
    sizes = {'8', '16'} | ({'rows'} if base in (
        'transformer_base_lm', 'olmoe_1b_7b') and want else set())
    assert took == dict.fromkeys(sizes, want)


def test_a_chunk_whose_scores_pass_the_budget_is_exported_blocked(tmp_path):
    """An OLMoE-shaped spec (ungrouped heads, toy widths) over a view long
    enough that its LARGEST chunk's scores pass _CHUNK_SCORES_BYTES —
    4 x 512 x 4 heads x 8,704 positions = 68 MiB — as olmoe_1b_7b's
    chunk_512 does at published widths: that program's signature entry
    (what DecodingPredictor.attention_bodies reads) says 'blocked', one
    an attention layer, the smaller chunks' (4 and 17 MiB) 'gathered',
    and such a spec holds no row program (its largest chunk is a whole
    dispatch)."""
    args = dict(_OLMOE, max_slots=1, max_cache_len=8704, block_size=16,
                chunk_sizes=(32, 128, 512))
    art = str(tmp_path / 'art')
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        from models.olmoe import build_decode_spec
        spec = build_decode_spec(**args)
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        export_decode(spec, art, scope=scope, precompile=False)
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    assert 'chunk_rows' not in sig
    n_layer = args['n_layer']
    took = {size: entry['attention']['kv_block_chunk_attention']
            for size, entry in sig['chunk'].items()}
    assert took == {'32': {'gathered': n_layer}, '128': {'gathered': n_layer},
                    '512': {'blocked': n_layer}}


@pytest.mark.parametrize('config', sorted(_ROW_MODULES))
def test_a_spec_gains_its_row_program_and_nothing_else(stablehlo, config):
    mine = {m.split('/')[1] for m in _PARENT_STABLEHLO
            if m.startswith(config + '/')}
    assert sorted(set(stablehlo(config)) - mine) == _ROW_MODULES[config]


@pytest.mark.parametrize('config,kw,want', [
    ('transformer_base_lm', dict(chunk_sizes=(32, 128), max_cache_len=256,
                                 block_size=16), (128, 4)),
    ('transformer_base_lm', {}, (16, 4)),
    ('transformer_base_lm', dict(kv_cache_dtype='int8'), None),
    ('olmoe_1b_7b', dict(chunk_sizes=(32, 128, 512), max_cache_len=512),
     None),
    ('olmoe_1b_7b', {}, (16, 4)),
    ('k_exaone_236b_a23b', dict(chunk_sizes=(128, 512), max_cache_len=512),
     None),
    ('k_exaone_236b_a23b', {}, None),
], ids=['base_32_128', 'base_8_16', 'base_int8', 'olmoe_published',
        'olmoe_8_16', 'exaone_published', 'exaone_8_16'])
def test_which_spec_holds_a_row_program_follows_from_its_shapes(config, kw,
                                                                want):
    """At most 512 prompt tokens and four rows a dispatch, over the
    gathered view only: transformer_base_lm's chunks (32, 128) give
    128 x 4, the MoE configurations' published chunks (largest 512)
    none, the rehearsal's (8, 16) 16 x 4 — so the routed layer sees
    [R, C, D] in tier-1 — and grouped heads, a window or the int8 pool
    none at any size. The row program's feeds are the chunk's with R
    rows."""
    with fluid.unique_name.guard():
        spec = _rehearsal_spec(config, **kw)
    rows = spec.get('chunk_rows')
    if want is None:
        assert rows is None
        return
    assert (rows['size'], rows['rows']) == want
    assert rows['size'] == max(spec['chunk'])
    one = spec['chunk'][rows['size']]
    assert rows['feeds'] == one['feeds']
    for name in one['feeds']:
        assert rows['samples'][name].shape \
            == (want[1],) + one['samples'][name].shape[1:]


@pytest.mark.parametrize('asked', [dict(kv_cache_dtype='int8'),
                                   dict(draft_k=2)], ids=['int8', 'draft_k'])
@pytest.mark.parametrize('beside,kind', [
    (dict(window_layers=(0,), window=8), 'window layers'),
    (dict(v_width=16), 'latent pool'),
    (dict(recurrent={1: {'state': ((2, 4), 'float32')}}),
     'recurrent layers')], ids=['window', 'latent', 'recurrent'])
def test_the_builder_refuses_by_name_what_its_ops_do_not_take(asked, beside,
                                                              kind):
    """The int8 pool's and the verify program's ops attend a full layer's
    K and V pools and nothing else: beside another kind of layer memory
    the spec fails where it is built, the layer kind in the message, and
    not at export (or at the first dispatch)."""
    from models.decode_spec import DecodeSpecBuilder
    args = dict(vocab=64, d_model=32, kv_width=32, n_layer=2, max_slots=2,
                max_cache_len=32, block_size=8, chunk_sizes=(8,),
                num_blocks=None, eos_id=1, kv_cache_dtype='float32')
    DecodeSpecBuilder(**dict(args, **beside))       # alone: fine
    DecodeSpecBuilder(**dict(args, **asked))
    what = 'int8' if 'kv_cache_dtype' in asked else 'draft_k=2'
    with pytest.raises(ValueError, match='%s.*%s' % (what, kind)):
        DecodeSpecBuilder(**dict(args, **beside, **asked))
