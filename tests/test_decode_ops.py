"""CTC / CRF / edit distance / chunk eval / beam search
(reference coverage model: test_warpctc_op.py, test_edit_distance_op.py,
test_linear_chain_crf_op.py, test_crf_decoding_op.py, test_chunk_eval_op.py,
test_beam_search_op.py, book test_machine_translation.py decode path,
CRNN-CTC OCR model).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.lod import create_lod_array


def _lod(data, lens):
    return create_lod_array(np.asarray(data), recursive_seq_lens=[list(lens)])


def _run(fetch, feed=None, startup=True):
    exe = fluid.Executor(fluid.CPUPlace())
    if startup:
        exe.run(fluid.default_startup_program())
    return exe.run(feed=feed or {}, fetch_list=fetch)


# ---------------------------------------------------------------------------
# CTC
# ---------------------------------------------------------------------------

def test_warpctc_loss_positive_and_differentiable():
    layers = fluid.layers
    C = 6  # classes incl. blank 0
    logits = fluid.layers.data(name='lg', shape=[C], dtype='float32',
                               lod_level=1)
    label = fluid.layers.data(name='lb', shape=[1], dtype='int64', lod_level=1)
    loss = layers.warpctc(input=logits, label=label, blank=0)
    avg = layers.mean(loss)
    fluid.backward.append_backward(avg)

    rng = np.random.RandomState(0)
    t_lens, l_lens = [5, 7], [2, 3]
    lg = _lod(rng.randn(sum(t_lens), C).astype(np.float32), t_lens)
    lb = _lod(rng.randint(1, C, (sum(l_lens), 1)).astype(np.int64), l_lens)
    out, = _run([loss], feed={'lg': lg, 'lb': lb}, startup=False)
    assert out.shape == (2, 1)
    assert (out > 0).all()


def test_ctc_pipeline_trains_ocr_style():
    """OCR CRNN+CTC milestone: conv features → gru → ctc loss decreases,
    greedy decode + edit distance run end-to-end."""
    layers = fluid.layers
    C = 5   # 4 symbols + blank
    T = 8
    feat = layers.data(name='f', shape=[16], dtype='float32', lod_level=1)
    label = layers.data(name='y', shape=[1], dtype='int64', lod_level=1)
    h = layers.fc(input=feat, size=32, act='relu')
    logits = layers.fc(input=h, size=C)
    loss = layers.mean(layers.warpctc(input=logits, label=label, blank=0))
    fluid.optimizer.Adam(learning_rate=0.02).minimize(loss)

    decoded = layers.ctc_greedy_decoder(layers.softmax(logits), blank=0)
    dist, seq_num = layers.edit_distance(decoded, label, normalized=False)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    t_lens = [T, T]
    l_lens = [3, 2]
    feats = rng.randn(sum(t_lens), 16).astype(np.float32)
    labs = rng.randint(1, C, (sum(l_lens), 1)).astype(np.int64)
    feed = {'f': _lod(feats, t_lens), 'y': _lod(labs, l_lens)}
    losses = [float(exe.run(feed=feed, fetch_list=[loss])[0][0])
              for _ in range(60)]
    assert losses[-1] < 0.5 * losses[0], losses[::12]
    d, n = exe.run(feed=feed, fetch_list=[dist, seq_num])
    assert n[0] == 2
    # after fitting two fixed sequences the greedy decode should be close
    assert d.sum() <= 2.0, d


def test_edit_distance_known_values():
    layers = fluid.layers
    hyp = layers.data(name='h', shape=[1], dtype='int64', lod_level=1)
    ref = layers.data(name='r', shape=[1], dtype='int64', lod_level=1)
    dist, _ = layers.edit_distance(hyp, ref, normalized=False)
    # "kitten"->"sitting" famous distance 3 (mapped to ints), plus equal pair
    k = [1, 2, 3, 3, 4, 5]          # kitten
    s = [6, 2, 3, 3, 2, 5, 7]       # sitting
    h_data = np.array(k + [1, 2], np.int64).reshape(-1, 1)
    r_data = np.array(s + [1, 2], np.int64).reshape(-1, 1)
    out, = _run([dist], feed={'h': _lod(h_data, [6, 2]),
                              'r': _lod(r_data, [7, 2])}, startup=False)
    np.testing.assert_allclose(out.reshape(-1), [3.0, 0.0])


def test_edit_distance_with_neg_padding():
    """-1 padding (greedy decoder convention) is ignored."""
    layers = fluid.layers
    hyp = layers.data(name='h', shape=[1], dtype='int64', lod_level=1)
    ref = layers.data(name='r', shape=[1], dtype='int64', lod_level=1)
    dist, _ = layers.edit_distance(hyp, ref, normalized=False)
    h_data = np.array([1, 2, -1, -1], np.int64).reshape(-1, 1)
    r_data = np.array([1, 2, 3], np.int64).reshape(-1, 1)
    out, = _run([dist], feed={'h': _lod(h_data, [4]),
                              'r': _lod(r_data, [3])}, startup=False)
    assert out[0, 0] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# CRF
# ---------------------------------------------------------------------------

def _brute_force_crf_nll(E, w, y):
    """Enumerate all paths for one sequence: -log p(y)."""
    import itertools
    start, end, A = w[0], w[1], w[2:]
    T, D = E.shape

    def score(path):
        s = start[path[0]] + E[0, path[0]]
        for t in range(1, T):
            s += A[path[t - 1], path[t]] + E[t, path[t]]
        return s + end[path[-1]]

    logZ = np.logaddexp.reduce(
        [score(p) for p in itertools.product(range(D), repeat=T)])
    return logZ - score(y)


def test_linear_chain_crf_matches_brute_force():
    layers = fluid.layers
    D = 3
    em = layers.data(name='e', shape=[D], dtype='float32', lod_level=1)
    lb = layers.data(name='l', shape=[1], dtype='int64', lod_level=1)
    nll = layers.linear_chain_crf(
        input=em, label=lb,
        param_attr=fluid.ParamAttr(name='crfw_test'))

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(2)
    lens = [4, 2]
    E = rng.randn(sum(lens), D).astype(np.float32)
    y = rng.randint(0, D, (sum(lens), 1)).astype(np.int64)
    out, = exe.run(feed={'e': _lod(E, lens), 'l': _lod(y, lens)},
                   fetch_list=[nll])
    w = np.asarray(fluid.global_scope().get('crfw_test'))
    exp0 = _brute_force_crf_nll(E[:4], w, y[:4, 0])
    exp1 = _brute_force_crf_nll(E[4:], w, y[4:, 0])
    np.testing.assert_allclose(out.reshape(-1), [exp0, exp1], rtol=1e-4)


def test_crf_train_and_decode():
    """label_semantic_roles-style slice: crf loss decreases; decoding with
    label yields the 0/1 correctness vector feeding chunk_eval."""
    layers = fluid.layers
    D = 4
    feat = layers.data(name='x', shape=[8], dtype='float32', lod_level=1)
    lb = layers.data(name='l', shape=[1], dtype='int64', lod_level=1)
    em = layers.fc(input=feat, size=D)
    nll = layers.linear_chain_crf(input=em, label=lb,
                                  param_attr=fluid.ParamAttr(name='crfw'))
    loss = layers.mean(nll)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    path = layers.crf_decoding(input=em,
                               param_attr=fluid.ParamAttr(name='crfw'))
    correct = layers.crf_decoding(input=em, label=lb,
                                  param_attr=fluid.ParamAttr(name='crfw'))

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(3)
    lens = [5, 3]
    X = rng.randn(sum(lens), 8).astype(np.float32)
    y = rng.randint(0, D, (sum(lens), 1)).astype(np.int64)
    feed = {'x': _lod(X, lens), 'l': _lod(y, lens)}
    losses = [float(exe.run(feed=feed, fetch_list=[loss])[0][0])
              for _ in range(60)]
    assert losses[-1] < losses[0]
    p, c = exe.run(feed=feed, fetch_list=[path, correct])
    assert p.shape == (sum(lens), 1)
    assert set(np.unique(c)) <= {0, 1}
    # after fitting, viterbi should recover the training labels
    assert c.mean() > 0.8


def test_chunk_eval_iob():
    layers = fluid.layers
    inf = layers.data(name='i', shape=[1], dtype='int64', lod_level=1)
    lab = layers.data(name='l', shape=[1], dtype='int64', lod_level=1)
    prec, rec, f1, n_inf, n_lab, n_cor = layers.chunk_eval(
        input=inf, label=lab, chunk_scheme='IOB', num_chunk_types=2)
    # tags: B-0=0 I-0=1 B-1=2 I-1=3; seq: [B0 I0 B1 I1 B0]
    gold = np.array([0, 1, 2, 3, 0], np.int64).reshape(-1, 1)
    # prediction: first chunk right, second wrong type, third right
    pred = np.array([0, 1, 0, 1, 0], np.int64).reshape(-1, 1)
    outs = _run([prec, rec, f1, n_inf, n_lab, n_cor],
                feed={'i': _lod(pred, [5]), 'l': _lod(gold, [5])},
                startup=False)
    assert outs[3][0] == 3 and outs[4][0] == 3
    assert outs[5][0] == 2
    assert outs[0][0] == pytest.approx(2 / 3)
    assert outs[1][0] == pytest.approx(2 / 3)


def test_chunk_eval_iob_other_tag():
    """O tags (value num_chunk_types * num_tag_types) are not chunks
    (ref chunk_eval_op.h:145 other_chunk_type) — the canonical NER case."""
    layers = fluid.layers
    inf = layers.data(name='io', shape=[1], dtype='int64', lod_level=1)
    lab = layers.data(name='lo', shape=[1], dtype='int64', lod_level=1)
    prec, rec, f1, n_inf, n_lab, n_cor = layers.chunk_eval(
        input=inf, label=lab, chunk_scheme='IOB', num_chunk_types=2)
    # tags: B-0=0 I-0=1 B-1=2 I-1=3 O=4; gold: [B0 I0 O O B1]
    gold = np.array([0, 1, 4, 4, 2], np.int64).reshape(-1, 1)
    # prediction: first chunk right; predicts O where gold has B1
    pred = np.array([0, 1, 4, 4, 4], np.int64).reshape(-1, 1)
    outs = _run([prec, rec, f1, n_inf, n_lab, n_cor],
                feed={'io': _lod(pred, [5]), 'lo': _lod(gold, [5])},
                startup=False)
    # O runs must not inflate the chunk counters
    assert outs[3][0] == 1   # inferred chunks: just [B0 I0]
    assert outs[4][0] == 2   # label chunks: [B0 I0], [B1]
    assert outs[5][0] == 1
    assert outs[0][0] == pytest.approx(1.0)
    assert outs[1][0] == pytest.approx(0.5)


def test_chunk_eval_plain_other_tag():
    layers = fluid.layers
    inf = layers.data(name='ip', shape=[1], dtype='int64', lod_level=1)
    lab = layers.data(name='lp', shape=[1], dtype='int64', lod_level=1)
    prec, rec, f1, n_inf, n_lab, n_cor = layers.chunk_eval(
        input=inf, label=lab, chunk_scheme='plain', num_chunk_types=2)
    # plain scheme: tag == chunk type, tag 2 (num_chunk_types) is Other
    gold = np.array([0, 0, 2, 1], np.int64).reshape(-1, 1)
    pred = np.array([0, 0, 2, 2], np.int64).reshape(-1, 1)
    outs = _run([prec, rec, f1, n_inf, n_lab, n_cor],
                feed={'ip': _lod(pred, [4]), 'lp': _lod(gold, [4])},
                startup=False)
    assert outs[3][0] == 1   # [0,0] only — the 2-run is Other
    assert outs[4][0] == 2   # [0,0] and [1]
    assert outs[5][0] == 1


def _oracle_chunks(tags, scheme, num_chunk_types):
    """Independent chunk extractor (a forward state machine, not the
    op's boundary predicates): {(start, end, chunk_type)} spans per the
    reference tag semantics — B begins, E ends, S is a singleton, I
    continues a same-type chunk or opens one when none is open."""
    ntt = 4 if scheme == 'IOBES' else 2
    roles = {'IOB': 'BI', 'IOE': 'IE', 'IOBES': 'BIES'}[scheme]
    chunks, state = [], [None, None]   # [start index, chunk type]

    def close(end):
        if state[0] is not None:
            chunks.append((state[0], end, state[1]))
        state[0] = state[1] = None

    for i, t in enumerate(tags):
        ct, role = t // ntt, roles[t % ntt]
        if ct == num_chunk_types:      # the Other tag: never a chunk
            close(i - 1)
            continue
        if role == 'S':
            close(i - 1)
            chunks.append((i, i, ct))
            continue
        if role == 'B':
            close(i - 1)
            state[:] = [i, ct]
            continue
        if state[0] is None or state[1] != ct:   # I/E with no open chunk
            close(i - 1)
            state[:] = [i, ct]
        if role == 'E':
            close(i)
    close(len(tags) - 1)
    return set(chunks)


@pytest.mark.parametrize('scheme', ['IOB', 'IOE', 'IOBES'])
def test_chunk_eval_schemes_vs_oracle(scheme):
    """Randomized numeric check of every positional scheme against the
    pure-python span extractor: chunk counts and correct-chunk counts
    must match exactly, per sequence boundaries (lod)."""
    layers = fluid.layers
    nct = 3
    ntt = 4 if scheme == 'IOBES' else 2
    inf = layers.data(name='i_' + scheme, shape=[1], dtype='int64',
                      lod_level=1)
    lab = layers.data(name='l_' + scheme, shape=[1], dtype='int64',
                      lod_level=1)
    prec, rec, f1, n_inf, n_lab, n_cor = layers.chunk_eval(
        input=inf, label=lab, chunk_scheme=scheme, num_chunk_types=nct)
    rng = np.random.RandomState(hash(scheme) % 2 ** 31)
    lens = [7, 5, 9]
    # tag vocabulary includes the Other tag (value nct * ntt)
    gold = rng.randint(0, nct * ntt + 1, (sum(lens), 1)).astype(np.int64)
    pred = rng.randint(0, nct * ntt + 1, (sum(lens), 1)).astype(np.int64)
    outs = _run([prec, rec, f1, n_inf, n_lab, n_cor],
                feed={'i_' + scheme: _lod(pred, lens),
                      'l_' + scheme: _lod(gold, lens)},
                startup=False)
    want_inf = want_lab = want_cor = 0
    off = 0
    for L in lens:
        pc = _oracle_chunks(pred[off:off + L, 0], scheme, nct)
        gc = _oracle_chunks(gold[off:off + L, 0], scheme, nct)
        want_inf += len(pc)
        want_lab += len(gc)
        want_cor += len(pc & gc)
        off += L
    assert outs[3][0] == want_inf
    assert outs[4][0] == want_lab
    assert outs[5][0] == want_cor
    assert outs[0][0] == pytest.approx(
        want_cor / want_inf if want_inf else 0.0)
    assert outs[1][0] == pytest.approx(
        want_cor / want_lab if want_lab else 0.0)


def test_chunk_eval_ioe_iobes_exact():
    """Hand-checked IOE and IOBES cases (ref chunk_eval_op.h tag tables:
    IOE I=0 E=1; IOBES B=0 I=1 E=2 S=3)."""
    layers = fluid.layers
    inf = layers.data(name='ix', shape=[1], dtype='int64', lod_level=1)
    lab = layers.data(name='lx', shape=[1], dtype='int64', lod_level=1)
    # IOE, 2 types: I-0=0 E-0=1 I-1=2 E-1=3 O=4
    outs_ioe = layers.chunk_eval(input=inf, label=lab, chunk_scheme='IOE',
                                 num_chunk_types=2)
    # gold: [I0 E0 | I1 E1 | O]  → chunks (0,1,t0), (2,3,t1)
    gold = np.array([0, 1, 2, 3, 4], np.int64).reshape(-1, 1)
    # pred: [I0 E0 | E1 | O O]   → chunks (0,1,t0), (2,2,t1)
    pred = np.array([0, 1, 3, 4, 4], np.int64).reshape(-1, 1)
    outs = _run(list(outs_ioe), feed={'ix': _lod(pred, [5]),
                                      'lx': _lod(gold, [5])},
                startup=False)
    assert outs[3][0] == 2 and outs[4][0] == 2 and outs[5][0] == 1

    inf2 = layers.data(name='iy', shape=[1], dtype='int64', lod_level=1)
    lab2 = layers.data(name='ly', shape=[1], dtype='int64', lod_level=1)
    # IOBES, 1 type: B=0 I=1 E=2 S=3 O=4
    outs_iobes = layers.chunk_eval(input=inf2, label=lab2,
                                   chunk_scheme='IOBES', num_chunk_types=1)
    # gold: [B I E | S | O] → chunks (0,2), (3,3)
    gold2 = np.array([0, 1, 2, 3, 4], np.int64).reshape(-1, 1)
    # pred: [B I E | O | S] → chunks (0,2), (4,4)
    pred2 = np.array([0, 1, 2, 4, 3], np.int64).reshape(-1, 1)
    outs2 = _run(list(outs_iobes),
                 feed={'ix': _lod(pred, [5]), 'lx': _lod(gold, [5]),
                       'iy': _lod(pred2, [5]), 'ly': _lod(gold2, [5])},
                 startup=False)
    assert outs2[3][0] == 2 and outs2[4][0] == 2 and outs2[5][0] == 1


# ---------------------------------------------------------------------------
# beam search
# ---------------------------------------------------------------------------

def test_beam_search_step_selects_topk():
    layers = fluid.layers
    K, C = 2, 3   # beam 2, 3 candidates/beam; one source sentence
    pre_ids = layers.data(name='pi', shape=[K, 1], dtype='int64',
                          append_batch_size=False)
    pre_scores = layers.data(name='ps', shape=[K, 1], dtype='float32',
                             append_batch_size=False)
    ids = layers.data(name='ids', shape=[K, C], dtype='int64',
                      append_batch_size=False)
    scores = layers.data(name='sc', shape=[K, C], dtype='float32',
                         append_batch_size=False)
    sel_ids, sel_scores, parent = layers.beam_search(
        pre_ids, pre_scores, ids, scores, beam_size=K, end_id=0,
        return_parent_idx=True)
    feed = {
        'pi': np.array([[5], [6]], np.int64),
        'ps': np.array([[0.1], [0.2]], np.float32),
        'ids': np.array([[11, 12, 13], [21, 22, 23]], np.int64),
        'sc': np.array([[0.9, 0.5, 0.1], [0.8, 0.7, 0.2]], np.float32),
    }
    si, ss, pa = _run([sel_ids, sel_scores, parent], feed=feed, startup=False)
    np.testing.assert_array_equal(si.reshape(-1), [11, 21])
    np.testing.assert_allclose(ss.reshape(-1), [0.9, 0.8])
    np.testing.assert_array_equal(pa.reshape(-1), [0, 1])


def test_beam_search_frozen_finished_beam():
    layers = fluid.layers
    K, C = 2, 2
    pre_ids = layers.data(name='pi', shape=[K, 1], dtype='int64',
                          append_batch_size=False)
    pre_scores = layers.data(name='ps', shape=[K, 1], dtype='float32',
                             append_batch_size=False)
    ids = layers.data(name='ids', shape=[K, C], dtype='int64',
                      append_batch_size=False)
    scores = layers.data(name='sc', shape=[K, C], dtype='float32',
                         append_batch_size=False)
    sel_ids, sel_scores = layers.beam_search(
        pre_ids, pre_scores, ids, scores, beam_size=K, end_id=0)
    feed = {
        'pi': np.array([[0], [6]], np.int64),      # beam 0 finished
        'ps': np.array([[2.0], [0.2]], np.float32),
        'ids': np.array([[11, 12], [21, 22]], np.int64),
        'sc': np.array([[9.0, 8.0], [1.0, 0.5]], np.float32),
    }
    si, ss = _run([sel_ids, sel_scores], feed=feed, startup=False)
    # finished beam contributes ONLY (end_id, 2.0); its 9.0/8.0 are ignored
    assert 0 in si.reshape(-1)
    assert 2.0 in ss.reshape(-1)
    assert 9.0 not in ss.reshape(-1)


def test_beam_search_decode_backtrace():
    """While-loop greedy-beam NMT decode: 2 beams over a toy 4-token vocab,
    decode 3 steps, backtrace must follow parent pointers."""
    layers = fluid.layers
    K, V, T = 2, 4, 3
    # logits per step are fed as data for determinism: [T, K, V]
    step_scores = layers.data(name='sc', shape=[T, K, V], dtype='float32',
                              append_batch_size=False)

    i = layers.fill_constant([1], 'int64', 0)
    limit = layers.fill_constant([1], 'int64', T)
    init_ids = layers.fill_constant([K, 1], 'int64', 1)     # <s>
    init_scores = layers.fill_constant([K, 1], 'float32', 0.0)
    ids_arr = layers.array_write(init_ids, i)
    scores_arr = layers.array_write(init_scores, i)
    parents_arr = layers.array_write(
        layers.fill_constant([K], 'int32', 0), i)
    layers.increment(i, 1)
    cond = layers.less_than(i, limit)
    w = layers.While(cond)
    with w.block():
        t = layers.elementwise_sub(i, layers.fill_constant([1], 'int64', 1))
        pre_ids = layers.array_read(ids_arr, t)
        pre_scores = layers.array_read(scores_arr, t)
        # this step's scores [K, V], accumulated onto the beam scores
        acc = layers.elementwise_add(
            layers.reshape(layers.gather(step_scores, t), [K, V]),
            pre_scores)
        sel_ids, sel_scores, parent = layers.beam_search(
            pre_ids, pre_scores, None, acc, beam_size=K, end_id=0,
            return_parent_idx=True)
        layers.array_write(sel_ids, i, array=ids_arr)
        layers.array_write(sel_scores, i, array=scores_arr)
        layers.array_write(parent, i, array=parents_arr)
        layers.increment(i, 1)
        layers.less_than(i, limit, cond=cond)
    sent_ids, sent_scores = layers.beam_search_decode(
        ids_arr, scores_arr, beam_size=K, end_id=0, parents=parents_arr)

    rng = np.random.RandomState(4)
    sc = rng.randn(T, K, V).astype(np.float32)
    out_ids, out_scores = _run([sent_ids, sent_scores],
                               feed={'sc': sc}, startup=False)
    ids_mat = out_ids.reshape(K, -1)
    scores_mat = out_scores.reshape(K, -1)
    assert ids_mat.shape[1] >= T
    assert ((ids_mat >= 0) & (ids_mat < V)).all()

    # numpy reference: fixed-K beam over the same scores. Loop iteration i
    # gathers sc[i-1], so step slots 1..T-1 consume sc[0..T-2].
    rows_hist = [[(1, 0)] * K]  # (token, parent) per step
    cur_scores = np.zeros(K)
    cur_ids = np.full(K, 1)
    for t in range(1, T):
        cand = cur_scores[:, None] + sc[t - 1]            # [K, V]
        for k in range(K):                                 # freeze finished
            if cur_ids[k] == 0:
                cand[k] = -1e9
                cand[k, 0] = cur_scores[k]
        flat = cand.reshape(-1)
        top = np.argsort(-flat, kind='stable')[:K]
        rows_hist.append([(int(i % V), int(i // V)) for i in top])
        cur_scores = flat[top]
        cur_ids = np.array([i % V for i in top])
    # backtrace numpy
    want = np.zeros((K, T), np.int64)
    for k in range(K):
        beam = k
        for t in range(T - 1, -1, -1):
            tok, par = rows_hist[t][beam]
            want[k, t] = tok
            beam = par
    # apply end-id freezing as the op does
    np.testing.assert_array_equal(ids_mat[:, :T], want)
    np.testing.assert_allclose(scores_mat[:, 0], cur_scores, rtol=1e-5)


# ---------------------------------------------------------------------------
# the chunked-prefill ops' ROW dimension: KV / Q [R, C, D], Start [R, 1],
# BlockTable [R, MAXB] — row r is one slot's chunk, through table row r
# ---------------------------------------------------------------------------

_NB, _BS, _D, _MAXB, _HEADS = 21, 4, 16, 5, 2


def _chunk_ops(attrs=None):
    import types
    from paddle_tpu.ops import decode_ops
    attrs = dict(attrs or {'n_head': _HEADS})
    ctx = types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))

    def write(cache, kv, start, tables):
        return decode_ops._kv_block_chunk_write(ctx, {
            'Cache': [cache], 'KV': [kv], 'Start': [start],
            'BlockTable': [tables]})['Out'][0]

    def attend(q, kc, vc, start, tables):
        return decode_ops._kv_block_chunk_attention(ctx, {
            'Q': [q], 'KCache': [kc], 'VCache': [vc], 'Start': [start],
            'BlockTable': [tables]})['Out'][0]
    return write, attend


def _rows_case(rng, rows, c, starts):
    """`rows` slots with tables of their own over one pool that already
    holds every slot's history, the last rows pad rows (the trash table,
    start 0)."""
    import jax.numpy as jnp
    pool = rng.randn(_NB, _BS, _D).astype(np.float32)
    tables = np.zeros((rows, _MAXB), np.int32)        # trash: block 0
    for r in range(len(starts)):
        tables[r] = 1 + r * _MAXB + np.arange(_MAXB)
    start = np.zeros((rows, 1), np.int32)
    start[:len(starts), 0] = starts
    kv = rng.randn(rows, c, _D).astype(np.float32)
    q = rng.randn(rows, c, _D).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (pool, kv, q, start, tables))


@pytest.mark.parametrize('rows,starts', [(4, [0, 3, 8, 5]), (4, [2, 9]),
                                         (3, [7]), (2, [0, 11])])
def test_chunk_ops_with_rows_are_the_one_row_ops_row_by_row(rows, starts):
    """kv_block_chunk_write / kv_block_chunk_attention at [R, C, D] equal
    R calls at [1, C, D], row by row: the same blocks written with the
    same rows, the same attention output to the bit. A pad row (the trash
    table) writes the trash block and nothing else."""
    write, attend = _chunk_ops()
    c = 6
    pool, kv, q, start, tables = _rows_case(np.random.RandomState(7), rows,
                                            c, starts)
    many = write(pool, kv, start, tables)
    one = pool
    for r in range(rows):
        one = write(one, kv[r:r + 1], start[r:r + 1], tables[r:r + 1])
    many, one = np.asarray(many), np.asarray(one)
    # block 0 is the trash block: every pad row lands there, in whatever
    # order; every other block is what the R one-row writes left
    np.testing.assert_array_equal(many[1:], one[1:])
    changed = np.flatnonzero((many != np.asarray(pool)).any(axis=(1, 2)))
    real = {int(b) for r in range(len(starts))
            for b in np.asarray(tables)[r, [int(starts[r] + i) // _BS
                                            for i in range(c)]]}
    assert set(changed) - {0} == real
    assert (0 in changed) == (len(starts) < rows)
    out = np.asarray(attend(q, many, many, start, tables))
    assert out.shape == (rows, c, _D)
    for r in range(len(starts)):
        np.testing.assert_array_equal(
            out[r:r + 1],
            np.asarray(attend(q[r:r + 1], many, many, start[r:r + 1],
                              tables[r:r + 1])))
    assert np.isfinite(out).all()


def _row_scatter(cache, kv, start, tables):
    """kv_block_chunk_write as it was before it wrote pages — one index
    pair a row — kept as the plain reference of the page write."""
    import jax.numpy as jnp
    from paddle_tpu.ops import decode_ops
    r, c = kv.shape[0], kv.shape[1]
    pos = start.reshape(r, 1).astype(jnp.int32) \
        + jnp.arange(c, dtype=jnp.int32)[None, :]
    bidx, boff = decode_ops._block_scatter_idx(
        jnp.repeat(tables, c, axis=0), pos.reshape(-1), cache.shape[1])
    return cache.at[bidx, boff].set(
        kv.reshape(r * c, -1).astype(cache.dtype))


def _slot_table(first_block, held):
    """One slot's table row: column j names block first_block + j where
    `held` has j, the trash block elsewhere (a column the window gave
    back, or one not allocated yet)."""
    row = np.zeros(_MAXB, np.int32)
    for j in held:
        row[j] = first_block + j
    return row


# (C, D, pool dtype, rows of the call, [(start, held table columns)] of its
# real rows — the rest are pad rows on the trash table)
_PAGE_WRITES = {
    'aligned': (8, _D, 'float32', 1, [(4, range(5))]),
    'aligned_at_zero': (8, _D, 'float32', 1, [(0, range(5))]),
    'inside_a_page': (8, _D, 'float32', 1, [(6, range(5))]),
    'last_row_of_a_page': (8, _D, 'float32', 1, [(3, range(5))]),
    'ends_with_the_table': (8, _D, 'float32', 1, [(12, range(5))]),
    'runs_past_the_table': (8, _D, 'float32', 1, [(16, range(5))]),
    'inside_a_page_past_the_table': (8, _D, 'float32', 1, [(14, range(5))]),
    'all_past_the_table': (8, _D, 'float32', 1, [(24, range(5))]),
    # a short chunk: the slot holds pages up to its prompt's end only, the
    # pad positions beyond land in its tail page and the trash block
    'short_chunk': (8, _D, 'float32', 1, [(4, range(2))]),
    'short_chunk_inside_a_page': (8, _D, 'float32', 1, [(5, range(3))]),
    'bfloat16': (8, _D, 'bfloat16', 1, [(8, range(5))]),
    'bfloat16_inside_a_page': (8, _D, 'bfloat16', 1, [(9, range(5))]),
    # a latent pool: ONE row a position, key and value both
    'latent_row': (16, 40, 'bfloat16', 1, [(4, range(5))]),
    'latent_row_inside_a_page': (4, 40, 'bfloat16', 1, [(7, range(5))]),
    # a window layer's table: the columns the window has passed were given
    # back and name the trash block
    'window_table': (8, _D, 'bfloat16', 1, [(8, [2, 3, 4])]),
    'window_table_starts_given_back': (8, _D, 'float32', 1,
                                       [(4, [2, 3, 4])]),
    'window_table_inside_a_page': (8, _D, 'float32', 1, [(6, [2, 3])]),
    'four_rows': (8, _D, 'float32', 4,
                  [(0, range(5)), (4, range(5)), (8, range(5)),
                   (12, range(5))]),
    'four_rows_inside_pages': (8, _D, 'bfloat16', 4,
                               [(1, range(5)), (6, range(3)),
                                (11, range(5)), (15, range(5))]),
    'four_rows_two_pad': (8, _D, 'float32', 4,
                          [(4, range(5)), (7, range(4))]),
    'three_rows_one_pad_latent': (4, 40, 'bfloat16', 3,
                                  [(16, range(5)), (2, range(1))]),
}


@pytest.mark.parametrize('case', sorted(_PAGE_WRITES))
def test_a_chunk_of_whole_pages_is_written_as_the_row_scatter_wrote_it(case):
    """C % BS == 0: kv_block_chunk_write reads the C / BS + 1 pages a row
    touches, lays the chunk over them and writes them back — and leaves
    every block but the trash block (0, never read) as the row scatter
    leaves it, to the bit, whatever `start`: page-aligned, inside a page,
    past the table's span. The blocks that changed are exactly the
    slot's own that hold a chunk position."""
    import jax.numpy as jnp
    c, d, dtype, rows, real = _PAGE_WRITES[case]
    assert c % _BS == 0
    rng = np.random.RandomState(len(case))
    pool = jnp.asarray(rng.randn(_NB, _BS, d).astype(np.float32)
                       ).astype(dtype)
    tables = np.zeros((rows, _MAXB), np.int32)
    start = np.zeros((rows, 1), np.int32)
    for r, (at, held) in enumerate(real):
        tables[r] = _slot_table(1 + r * _MAXB, held)
        start[r, 0] = at
    kv = jnp.asarray(rng.randn(rows, c, d).astype(np.float32))
    write, _ = _chunk_ops()
    got = np.asarray(write(pool, kv, jnp.asarray(start),
                           jnp.asarray(tables)))
    want = np.asarray(_row_scatter(pool, kv, jnp.asarray(start),
                                   jnp.asarray(tables)))
    assert got.dtype == want.dtype == np.asarray(pool).dtype
    np.testing.assert_array_equal(got[1:].view(np.uint8),
                                  want[1:].view(np.uint8))
    changed = np.flatnonzero(
        (got.view(np.uint8) != np.asarray(pool).view(np.uint8)
         ).any(axis=(1, 2)))
    own = {int(tables[r, (at + i) // _BS]) for r, (at, _) in enumerate(real)
           for i in range(c) if (at + i) // _BS < _MAXB}
    assert set(changed) - {0} == own - {0}


def _scatters(jaxpr):
    """(pool, index, update) shapes of every scatter of a jaxpr, those of
    its inner jaxprs (a jitted library function's) too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith('scatter'):
            out.append(tuple(v.aval.shape for v in eqn.invars))
        for v in eqn.params.values():
            if hasattr(v, 'jaxpr'):
                out += _scatters(v.jaxpr)
    return out


@pytest.mark.parametrize('rows,c,bs,d,dtype,body', [
    (1, 512, 16, 1280, 'bfloat16', 'pages'),    # a cell's largest chunk
    (1, 32, 16, 640, 'bfloat16', 'pages'),      # its smallest, latent rows
    (4, 128, 16, 512, 'float32', 'pages'),      # the row program
    (1, 6, 4, 16, 'float32', 'rows')])          # no cell's: C % BS != 0
def test_the_lowered_chunk_write_updates_a_page_an_index(rows, c, bs, d,
                                                         dtype, body):
    """What the lowering holds, from shapes alone: at C % BS == 0 a
    branch on start % BS whose aligned side is ONE scatter of R * C / BS
    index rows and page-shaped windows — not R * C index pairs, which is
    the other side and all a C of no whole pages has; the op names the
    body to its Tracer; and a program that donates the pool gets it back
    as its output buffer."""
    import re
    import types
    import jax
    from paddle_tpu.ops import decode_ops
    ctx = types.SimpleNamespace(
        attr=lambda n, default=None: default,
        tracer=types.SimpleNamespace(lowered_bodies=[]))

    def write(cache, kv, start, tables):
        return decode_ops._kv_block_chunk_write(ctx, {
            'Cache': [cache], 'KV': [kv], 'Start': [start],
            'BlockTable': [tables]})['Out'][0]

    nb, maxb = 3 * rows * (c // bs + 2), c // bs + 2
    args = (jax.ShapeDtypeStruct((nb, bs, d), dtype),
            jax.ShapeDtypeStruct((rows, c, d), 'float32'),
            jax.ShapeDtypeStruct((rows, 1), 'int32'),
            jax.ShapeDtypeStruct((rows, maxb), 'int32'))
    jaxpr = jax.make_jaxpr(write)(*args).jaxpr
    assert ctx.tracer.lowered_bodies == [('kv_block_chunk_write', body)]
    by_row = ((nb, bs, d), (rows * c, 2), (rows * c, d))
    conds = [e for e in jaxpr.eqns if e.primitive.name == 'cond']
    if body == 'pages':
        n = rows * c // bs
        unaligned, aligned = (_scatters(b.jaxpr)
                              for b in conds[0].params['branches'])
        # (a cond's branches are a tuple: _scatters(jaxpr) stays outside)
        assert len(conds) == 1 and not _scatters(jaxpr)
        assert aligned == [((nb, bs, d), (n, 1), (n, bs, d))]
        assert unaligned == [by_row]
    else:
        assert not conds and _scatters(jaxpr) == [by_row]
    # donated, the pool is the program's output buffer (that no copy of
    # it is made on the way is the TPU compiler's to show:
    # tests/test_paged_attention_kernel.py — XLA:CPU scatters a bfloat16
    # pool through float32 and copies out of a conditional)
    text = jax.jit(write, donate_argnums=0).lower(*args).compile().as_text()
    assert re.search(r'input_output_alias=\{ \{\}: \(0, \{\}', text)


def test_chunk_rows_exist_over_the_gathered_view_only(monkeypatch):
    """Grouped heads, a window or scores past the budget send ONE row to
    the blocked body; more rows are refused by name, as they are by the
    int8 pool's forms."""
    import jax.numpy as jnp
    from paddle_tpu.ops import decode_ops
    pool, kv, q, start, tables = _rows_case(np.random.RandomState(8), 2, 4,
                                            [0, 4])
    for attrs in ({'n_head': 4, 'n_kv_head': 2}, {'n_head': 2, 'window': 8}):
        _, attend = _chunk_ops(attrs)
        wide = jnp.concatenate([q, q], -1) if attrs['n_head'] == 4 else q
        with pytest.raises(NotImplementedError, match='gathered'):
            attend(wide, pool, pool, start, tables)
        assert attend(wide[:1], pool, pool, start[:1],
                      tables[:1]).shape == (1,) + wide.shape[1:]
    _, attend = _chunk_ops()
    # the budget counts the rows' scores together
    one_row = 4 * 4 * _HEADS * _MAXB * _BS
    monkeypatch.setattr(decode_ops, '_CHUNK_SCORES_BYTES', one_row)
    assert attend(q[:1], pool, pool, start[:1], tables[:1]).shape \
        == (1, 4, _D)
    with pytest.raises(NotImplementedError, match='R = 1'):
        attend(q, pool, pool, start, tables)
    with pytest.raises(NotImplementedError, match='one chunk row, got 2'):
        decode_ops._one_row_only('kv_block_chunk_write_quant', kv)


def _benchmark_chunk_shapes(config):
    """What a chunk attention of one of the benchmark's configurations is
    handed, read from benchmark/configs/<config>.json and not typed in:
    (chunk sizes ascending, n_head, n_kv_head, its window layers' window
    or 0, max_cache_len). A latent pool (kv_lora_rank) is ONE row a
    position that every head reads."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), '..', 'benchmark',
                        'configs', config + '.json')
    with open(path) as f:
        cfg = json.load(f)
    n_head = int(cfg.get('num_attention_heads', cfg.get('n_head')))
    n_kv = (1 if 'kv_lora_rank' in cfg
            else int(cfg.get('num_key_value_heads', n_head)))
    return (sorted(int(c) for c in cfg['chunk_sizes']), n_head, n_kv,
            int(cfg.get('sliding_window') or 0), int(cfg['max_cache_len']))


@pytest.mark.parametrize('config,chunk,rows,layer,mib,body', [
    # ungrouped heads, no window: the scores' size decides
    ('transformer_base_lm', 32, 1, 'full', 2, 'gathered'),
    ('transformer_base_lm', 128, 1, 'full', 8, 'gathered'),
    ('transformer_base_lm', 128, 4, 'full', 32, 'gathered'),  # the row program
    ('olmoe_1b_7b', 32, 1, 'full', 8, 'gathered'),
    ('olmoe_1b_7b', 128, 1, 'full', 32, 'gathered'),
    ('olmoe_1b_7b', 512, 1, 'full', 128, 'blocked'),
    # grouped heads, a window or a latent pool: blocked at any size
    ('k_exaone_236b_a23b', 128, 1, 'full', 384, 'blocked'),
    ('k_exaone_236b_a23b', 512, 1, 'full', 1536, 'blocked'),
    ('k_exaone_236b_a23b', 128, 1, 'window', 384, 'blocked'),
    ('k_exaone_236b_a23b', 512, 1, 'window', 1536, 'blocked'),
    ('joyai_llm_flash', 128, 1, 'full', 72, 'blocked'),
    ('joyai_llm_flash', 512, 1, 'full', 288, 'blocked'),
    ('qwen3_next_80b_a3b', 128, 1, 'full', 36, 'blocked'),
    ('qwen3_next_80b_a3b', 512, 1, 'full', 144, 'blocked'),
    ('phi4_mini_flash_reasoning', 128, 1, 'full', 90, 'blocked'),
    ('phi4_mini_flash_reasoning', 512, 1, 'full', 360, 'blocked'),
    ('phi4_mini_flash_reasoning', 512, 1, 'window', 360, 'blocked'),
    ('granite_4_0_h_micro', 128, 1, 'full', 72, 'blocked'),
    ('granite_4_0_h_micro', 512, 1, 'full', 288, 'blocked'),
])
def test_the_chunk_attentions_body_follows_from_the_benchmarks_shapes(
        config, chunk, rows, layer, mib, body):
    """_gathered_view_fits over (R, C, n_head, n_kv_head, window,
    view_len) at every chunk program the benchmark's configurations
    export: the gathered view while the scores are 64 MiB or fewer — the
    size above which it is the slower body (ISSUE 56's probe) — so
    olmoe's chunk_512 (128 MiB) is blocked and its smaller chunks and
    transformer_base_lm's three programs stay as they were; the other
    five configurations never reach the budget."""
    from paddle_tpu.ops import decode_ops
    chunks, n_head, n_kv, window, view_len = _benchmark_chunk_shapes(config)
    assert chunk in chunks
    if layer == 'window':
        assert window
    else:
        window = 0
    assert 4 * rows * chunk * n_head * view_len == mib << 20
    assert decode_ops._CHUNK_SCORES_BYTES == 64 << 20
    fits = decode_ops._gathered_view_fits(rows, chunk, n_head, n_kv, window,
                                          view_len)
    assert ('gathered' if fits else 'blocked') == body


@pytest.mark.parametrize('chunks,heads,want', [
    ((32, 128), (8, 8, 0), (128, 4)),       # transformer_base_lm
    # ... and the benchmark's own two ungrouped configurations, chunks,
    # heads and max_cache_len read from their files: the row program
    # exists on the gathered view alone, and transformer_base_lm's
    # 4 x 128 rows (32 MiB) are inside the budget of ISSUE 56
    ('transformer_base_lm', None, (128, 4)),
    ('olmoe_1b_7b', None, None),            # its largest chunk is 512: R = 1
    ((32, 128, 512), (16, 16, 0), None),    # olmoe_1b_7b as published
    ((128, 512), (64, 8, 128), None),       # k_exaone_236b_a23b
    ((8, 16), (4, 4, 0), (16, 4)),          # the rehearsal's chunks
    ((8, 16), (4, 2, 0), None),             # grouped heads
    ((8, 16), (4, 4, 16), None),            # a window
    ((256,), (8, 8, 0), (256, 2)),          # 512 tokens a dispatch
    ((300,), (8, 8, 0), None),
    ((64,), (8, 8, 0), (64, 4)),            # at most four rows
])
def test_the_row_programs_shape_follows_from_shapes(chunks, heads, want):
    from paddle_tpu.ops.decode_ops import chunk_row_program
    view_len = 2048
    if isinstance(chunks, str):
        chunks, n_head, n_kv, _, view_len = _benchmark_chunk_shapes(chunks)
        heads = (n_head, n_kv, 0)
    n_head, n_kv, window = heads
    ops = [('kv_block_chunk_attention', n_head, n_kv, window)] * 3
    assert chunk_row_program(chunks, ops, view_len) == want
    # one layer that cannot take rows, and none can: the int8 pool's form,
    # a window layer among full ones
    assert chunk_row_program(
        chunks, ops + [('kv_block_chunk_attention_quant',) + heads],
        2048) is None
    assert chunk_row_program(
        chunks, ops + [('kv_block_chunk_attention', n_head, n_kv, 128)],
        2048) is None
    assert chunk_row_program(chunks, [], 2048) is None


def test_the_rows_scores_stay_inside_the_budget(monkeypatch):
    """R x the [C, n_head, T'] float32 scores <= _CHUNK_SCORES_BYTES, or
    the spec holds no row program."""
    from paddle_tpu.ops import decode_ops
    ops = [('kv_block_chunk_attention', 8, 8, 0)]
    four = 4 * 4 * 128 * 8 * 2048
    monkeypatch.setattr(decode_ops, '_CHUNK_SCORES_BYTES', four)
    assert decode_ops.chunk_row_program((32, 128), ops, 2048) == (128, 4)
    monkeypatch.setattr(decode_ops, '_CHUNK_SCORES_BYTES', four - 1)
    assert decode_ops.chunk_row_program((32, 128), ops, 2048) is None
