"""The k_exaone_236b_a23b configuration and its cell: the file against the
catalog's config, the share it states, the traffic mix, the bytes a step,
its routed feed-forward and its attention need (against hand counts), the
two readers this cell brings (decode_attention_roofline, tick_window_ms) on
hand-made timelines and on a program that lacks what they read, and the
cell end to end under --rehearsal."""
import importlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, trace, traffic
from benchmark.configs import k_exaone_236b_a23b as model
from benchmark.layer_metrics import _xplane_meta

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, 'fixture_v5e.xplane.pb')
CELL = 'k_exaone_236b_a23b.longgen_closed'
MS = 1000000
# model-configs catalog, K-EXAONE-236B-A23B, `config`: the numbers and
# flags at its top level (its lists are compared whole below)
CATALOG = {"first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
           "hidden_size": 6144, "intermediate_size": 18432,
           "max_position_embeddings": 262144, "model_type": "exaone_moe",
           "moe_intermediate_size": 2048, "n_group": 1,
           "norm_topk_prob": True, "num_attention_heads": 64,
           "num_experts": 128, "num_experts_per_tok": 8,
           "num_hidden_layers": 48, "num_key_value_heads": 8,
           "num_nextn_predict_layers": 1, "num_shared_experts": 1,
           "rms_norm_eps": 1e-05, "routed_scaling_factor": 2.5,
           "scoring_func": "sigmoid", "sliding_window": 128,
           "sliding_window_pattern": "LLLG", "tie_word_embeddings": False,
           "topk_group": 1, "vocab_size": 153600}
REDUCED = ['num_hidden_layers', 'num_experts', 'vocab_size',
           'num_nextn_predict_layers']


def _cfg():
    return harness.load_json(os.path.join(ROOT, 'benchmark', 'configs',
                                          'k_exaone_236b_a23b.json'))


def test_every_width_is_the_catalogs_and_the_cuts_are_the_four_stated():
    cfg = _cfg()
    bench = harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    entry = {c['name']: c for c in bench['configs']}['k_exaone_236b_a23b']
    differs = sorted(k for k, v in CATALOG.items() if cfg[k] != v)
    assert differs == sorted(REDUCED) == sorted(entry['reduced'])
    assert list(cfg['reduced']) == REDUCED
    assert entry['source'] == cfg['source']
    # the nested groups are the catalog's, whole
    assert cfg['layer_types'] == (['sliding_attention'] * 3
                                  + ['full_attention']) * 12
    assert cfg['sliding_windows'] == [128, 128, 128, 0] * 12
    assert cfg['mlp_layer_types'] == ['dense'] + ['sparse'] * 47
    assert cfg['rope_parameters'] == {'rope_theta': 1000000,
                                      'rope_type': 'default'}
    # the floors: a whole period behind the dense layer, >= 8 experts,
    # >= an eighth of the vocabulary; the published counts beside the held
    assert cfg['num_hidden_layers'] == 1 + 4
    assert cfg['num_experts'] == 16 and cfg['num_experts_routed'] == 128
    assert cfg['vocab_size'] * 8 == cfg['published']['vocab_size'] == 153600
    assert cfg['published']['num_experts'] == 128
    assert '8 chips share each layer' in cfg['deployment']
    kinds = cfg['layer_types'][:cfg['num_hidden_layers']]
    assert kinds.count('full_attention') == 1 and kinds[3] == 'full_attention'
    for key in ('norm_placement', 'qk_norm', 'rotary', 'router',
                'router_bias', 'eos_id', 'chunk_sizes'):
        assert key in cfg['assumed']


def test_longgen_closed_lengths_fit_the_cache():
    mix = harness.load_json(os.path.join(ROOT, 'benchmark', 'traffic',
                                         'longgen_closed.json'))
    assert mix['runner'] == 'decode_closed' and mix['clients'] == 'max_slots'
    rng = traffic.rng_for(2 ** 31 + 5, 0)
    # ISSUE 30's lengths, prompts under its stated fallback cut of 4,096
    for key, lo, hi, med in (('prompt_len', 256, 4096, 2048),
                             ('output_len', 128, 4096, 1024)):
        xs = traffic.draw_lengths(mix[key], rng, 20000)
        assert xs.min() == lo and xs.max() == hi
        assert abs(np.median(xs) - med) <= 0.04 * med
    cfg = _cfg()
    assert mix['prompt_len']['max'] + mix['output_len']['max'] \
        <= cfg['max_cache_len']
    # every id the generator draws lies in the vocabulary slice held
    prompt, _ = next(traffic.closed_requests(mix, 7, 0,
                                             model.vocab_size(cfg)))
    assert prompt.min() >= 2 and prompt.max() < 19200


def test_byte_functions_against_hand_counts():
    cfg = _cfg()
    # 7.42 GB of weights: 5 x 113.2 M attention, the 339.7 M dense layer,
    # 4 x (0.8 M router + 17 x 37.75 M experts), 2 x 118 M vocabulary
    attn = 6144 * (8192 + 1024 + 1024) + 8192 * 6144
    assert model.attention_params(cfg) == attn == 113246208
    expert = 3 * 6144 * 2048
    held = (5 * attn + 3 * 6144 * 18432
            + 4 * (6144 * 128 + 17 * expert) + 2 * 6144 * 19200)
    assert model.weight_params(cfg) == held
    assert held * 2 == pytest.approx(7.42e9, rel=2e-3)
    # one cached position of one layer: K and V of 8 x 128 in bfloat16
    assert model.kv_row_bytes(cfg) == 4096
    # 64 rows x 8 of 128 experts: 16 * (1 - (15/16)^64) = 15.74 held ones
    assert model.expected_distinct_experts(cfg, 64) == pytest.approx(
        15.743, abs=1e-3)
    assert model.expected_distinct_experts(cfg, 1) == pytest.approx(1.0)
    assert model.moe_expert_bytes(cfg, 64) == pytest.approx(
        4 * 15.743 * expert * 2, rel=1e-4)               # 4.75 GB
    assert model.step_dense_bytes(cfg) == pytest.approx(2.36e9, rel=2e-3)
    # attention: the full layer reads every cached row, the four window
    # layers 128 rows a live request
    rows = 64 * 3300
    assert model.attention_bytes(cfg, rows, 64) == 4096 * (
        rows + 4 * 64 * 128)
    # a request shorter than the window is read whole
    assert model.attention_bytes(cfg, 64 * 50, 64) == 4096 * 5 * 64 * 50
    assert model.attention_bytes(cfg, 0, 0) == 0
    peaks = {'hbm_bytes_per_s': 819e9}
    floor = model.step_floor_seconds(cfg, peaks, rows)
    assert floor == pytest.approx(
        (model.step_dense_bytes(cfg) + model.moe_expert_bytes(cfg, 64)
         + model.attention_bytes(cfg, rows, 64)) / 819e9)
    assert floor == pytest.approx(9.9e-3, rel=1e-2)
    assert model.BOUND == 'memory'
    # one table for every layer would not fit: 5 x 64 x 12,288 x 4,096 B
    assert 5 * 64 * 12288 * 4096 > 16e9


# -- the two readers ---------------------------------------------------------

def _reader(name):
    return importlib.import_module('benchmark.layer_metrics.' + name).reduce


def _ctx(path='unused'):
    return types.SimpleNamespace(
        tracer=types.SimpleNamespace(path=path), cfg=_cfg(), model=model,
        peaks={'hbm_bytes_per_s': 819e9})


def _attention_run(monkeypatch, prov, live=64.0, rows=64 * 3300):
    """Two 16 ms dispatches of the step, each with 1 + 2 ms under
    kv_block_attention scopes, 9 ms of grouped matmuls and 4 ms of the
    rest, and a chunk program between them whose attention is the chunk
    op's, not the step's."""
    ops, mods = [], []
    for t0 in (0, 40):
        mods.append((t0 * MS, (t0 + 16) * MS, 'jit_decode_step(1)'))
        ops += [(t0 * MS, (t0 + 1) * MS, 'kernel.1'),
                ((t0 + 1) * MS, (t0 + 3) * MS, 'kernel.2'),
                ((t0 + 3) * MS, (t0 + 12) * MS, 'ragged.1'),
                ((t0 + 12) * MS, (t0 + 16) * MS, 'head.1')]
    mods.append((20 * MS, 32 * MS, 'jit_prefill_chunk_512(2)'))
    ops.append((20 * MS, 32 * MS, 'chunk_attn.1'))
    dev = trace.Device('/device:TPU:0', ops, mods)
    t = trace.Trace([dev], [], (0, 60 * MS))
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': prov})
    return {'trace': t, 'runner': None,
            'result': {'floor_arg': float(rows), 'counters_traced': {
                'steps': 2, 'chunk_slices': 1,
                'active_slot_steps': int(2 * live)}},
            'ctx': _ctx()}


_PROV = {
    'kernel.1': 'jit(decode_step)/kv_block_attention/kv_block_attention/'
                'kv_block_paged_attention',
    'kernel.2': 'jit(decode_step)/kv_block_attention/convert_element_type',
    'ragged.1': 'ragged-dot-none:',
    'head.1': 'jit(decode_step)/mul/dot_general:',
    'chunk_attn.1': 'jit(prefill_chunk_512)/kv_block_chunk_attention/while'}


def test_attention_roofline_on_a_hand_made_timeline(monkeypatch):
    run = _attention_run(monkeypatch, _PROV)
    floor = model.attention_bytes(run['ctx'].cfg, 64 * 3300, 64.0) / 819e9
    assert _reader('decode_attention_roofline')(run) == pytest.approx(
        100.0 * floor / 3e-3)
    assert 0 < _reader('decode_attention_roofline')(run) < 100
    # fewer cached rows need fewer bytes in the same time
    short = _attention_run(monkeypatch, _PROV, rows=64 * 500)
    assert _reader('decode_attention_roofline')(short) \
        < _reader('decode_attention_roofline')(run)


def test_attention_roofline_finds_nothing_where_there_is_nothing(monkeypatch):
    """The parent's program, another model's module, the recorded trace
    of a model without the scope, no trace path, no traced counters: the
    metric is left out of the line, nothing raises."""
    reduce = _reader('decode_attention_roofline')
    prov = {k: v.replace('kv_block_attention', 'fc')
            for k, v in _PROV.items()}
    assert reduce(_attention_run(monkeypatch, prov)) is None
    run = _attention_run(monkeypatch, _PROV)
    no_fn = dict(run, ctx=types.SimpleNamespace(
        tracer=run['ctx'].tracer, cfg=run['ctx'].cfg, peaks=run['ctx'].peaks,
        model=types.SimpleNamespace()))
    assert reduce(no_fn) is None
    assert reduce(dict(run, result={'floor_arg': 1.0})) is None
    monkeypatch.undo()
    t = trace.load(FIXTURE)
    fixture = {'trace': t, 'ctx': _ctx(FIXTURE),
               'result': {'floor_arg': 1.0, 'counters_traced': {
                   'steps': 1, 'chunk_slices': 0, 'active_slot_steps': 1}}}
    assert reduce(fixture) is None
    assert reduce(dict(fixture, ctx=_ctx(None))) is None


def _span_run(spans, steps=2, slices=1):
    host = [(s * MS, e * MS, name, 'python3') for s, e, name in spans]
    return {'trace': trace.Trace([], host, (0, 100 * MS)),
            'result': {'counters_traced': {'steps': steps,
                                           'chunk_slices': slices}},
            'ctx': _ctx(None)}


def test_tick_window_ms_on_a_synthetic_span_list():
    """Three window_release spans of 0.3, 0.3 and 0.6 ms over two steps
    and one slice: 0.4 ms a dispatch; the part of a span outside the
    traced window is not counted."""
    spans = [(0, 20, 'decode/tick'), (1, 4, 'decode/build_feed'),
             (1.0, 1.3, 'decode/window_release'),
             (8.0, 8.3, 'decode/window_release'),
             (30, 50, 'decode/tick'),
             (31.0, 31.6, 'decode/window_release'),
             (99.5, 100.5, 'decode/window_release')]
    run = _span_run(spans)
    assert _reader('tick_window_ms')(run) == pytest.approx((1.2 + 0.5) / 3)
    # the parent's program has no such span; an interval without dispatch
    parent = _span_run([s for s in spans
                        if s[2] != 'decode/window_release'])
    assert _reader('tick_window_ms')(parent) is None
    assert _reader('tick_window_ms')(_span_run(spans, 0, 0)) is None


def test_the_cell_is_filed_under_every_decode_metric_it_reports():
    bench = harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    entry = {w['name']: w for w in bench['workloads']}[CELL]
    assert entry == dict(entry, config='k_exaone_236b_a23b',
                         traffic='longgen_closed', chips=1)
    assert len(entry['why']) <= 200 and '8x' in entry['why']
    assert bench['workloads'][-1] == entry           # appended, at the end
    assert bench['configs'][-1]['name'] == 'k_exaone_236b_a23b'
    e2e = {m['name'] for m in bench['end_to_end']
           if 'workloads' not in m or CELL in m['workloads']}
    assert e2e == {'itl_p99_ms', 'setup_s'}
    per_layer = {m['name']: m for m in bench['per_layer']
                 if CELL in m.get('workloads', ())}
    assert set(per_layer) == {
        'decode_step_device_ms', 'decode_step_roofline', 'artifact_load_s',
        'tick_host_ms', 'tick_feed_ms', 'tick_d2h_ms', 'tick_advance_ms',
        'tick_admit_ms', 'step_d2h_bytes', 'idle_attributed_share',
        'decode_attention_device_share', 'moe_ffn_device_share',
        'moe_experts_roofline', 'decode_attention_roofline',
        'tick_window_ms'}
    assert [m['name'] for m in bench['per_layer'][-2:]] == [
        'decode_attention_roofline', 'tick_window_ms']
    for name in ('decode_attention_roofline', 'tick_window_ms'):
        assert per_layer[name]['workloads'] == [CELL]
    assert all(m['moves'] in e2e for m in per_layer.values())
    assert json.dumps(bench).count(CELL) == 1 + len(per_layer) + 1


def test_the_cell_runs_end_to_end_under_rehearsal():
    """The harness finds the configuration, the traffic mix and both new
    readers by name and runs the cell at toy sizes on the cpu: a clean
    window, transcripts the reference agrees with, every token served
    through the two block tables."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', CELL, '--seed', str(2 ** 31 + 30), '--seconds', '4',
         '--trace', '1', '--rehearsal'],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line['rehearsal'] is True and line['correct'] is False
    assert line['rehearsal_checks_passed'] is True, out.stdout[-3000:]
    assert line['failed'] == 0 and line['attempted'] > 0
    metrics = line['metrics']
    assert metrics['compiles_in_window']['value'] == 0
    assert metrics['tick_window_ms']['value'] > 0
    assert metrics['step_d2h_bytes']['value'] == 4 * 8      # ids alone


def test_routing_gap_is_a_distance_in_logits_on_a_hand_made_row():
    """Four experts, two chosen, experts 0 and 1 held. Row 0: expert 0 is
    chosen and leaves under the third best (expert 3), expert 1 is not
    and enters over the second best (expert 2); the nearer of the two
    decides. Row 1: the same order with every logit far up the sigmoid —
    scores a few 1e-5 apart, the biases decide, and no rounding of a
    logit changes that."""
    import jax.numpy as jnp
    from benchmark.reference import exaone_moe as ref
    z = np.array([[2.0, 0.0, 1.0, 0.5], [12.0, 10.0, 11.0, 10.5]],
                 np.float32)
    b = np.array([0.02, -0.02, 0.01, 0.0], np.float32)
    got = np.asarray(ref.routing_gap(jnp.asarray(z), jnp.eye(4), b,
                                     top_k=2, first=0, held=2))
    s = 1 / (1 + np.exp(-z.astype(np.float64)))
    c, slope = s + b, s * (1 - s)
    want = np.minimum((c[:, 0] - c[:, 3]) / (slope[:, 0] + slope[:, 3]),
                      (c[:, 2] - c[:, 1]) / (slope[:, 2] + slope[:, 1]))
    np.testing.assert_allclose(got, want, rtol=2e-3)
    assert got[0] < 0.6 and abs(s[1, 2] - s[1, 1]) < 1e-4 and got[1] > 100


def test_weakest_side_on_hand_made_rows():
    a = np.array([0.0, 3.0, 1.0, 2.5])        # best 1, margin 0.5
    b = np.array([0.0, 3.0, 2.8, 1.0])        # best 1, margin 0.2
    c = np.array([0.0, 1.0, 3.0, 2.0])        # best 2
    np.testing.assert_array_equal(model.weakest_side([a, b]), b)
    np.testing.assert_array_equal(model.weakest_side([b, a]), b)
    for got in (model.weakest_side([a, b, c]),
                model.weakest_side([a, b], undecided=True)):
        np.testing.assert_array_equal(got, [0.0, 2.5, 1.0, 2.5])


def test_reference_returns_the_weakest_side_of_every_near_tie(tmp_path):
    """On the rows a transcript check reads, reference_logits computes a
    position whose routing is a near tie on a held expert both ways and
    returns its weakest side, whatever id follows it; every other row
    comes back as computed; a leaf that np.savez turned into void bytes
    reads back as bfloat16."""
    import jax.numpy as jnp
    from benchmark.reference import exaone_moe as ref
    cfg = harness.overlay(_cfg(), _cfg()['rehearsal'])
    rng = np.random.RandomState(0)
    d, f, dense, v, e, held = 64, 32, 96, 128, 16, 4

    def bf(*shape):
        return np.asarray(jnp.asarray(rng.randn(*shape) * 0.05,
                                      jnp.bfloat16))
    w = {'embed_w': bf(v, d), 'lm_head_w': bf(d, v),
         'final_norm_w': np.ones(d, np.float32)}
    for i in range(5):
        p = 'l%d_' % i
        w.update({p + 'q_w': bf(d, 64), p + 'k_w': bf(d, 32),
                  p + 'v_w': bf(d, 32), p + 'o_w': bf(64, d),
                  p + 'q_norm_w': np.ones(16, np.float32),
                  p + 'k_norm_w': np.ones(16, np.float32),
                  p + 'post_attn_norm_w': np.ones(d, np.float32),
                  p + 'post_ff_norm_w': np.ones(d, np.float32)})
        if i == 0:
            w.update({p + 'ff_gate_w': bf(d, dense), p + 'ff_up_w':
                      bf(d, dense), p + 'ff_down_w': bf(dense, d)})
            continue
        w.update({p + 'moe_router': bf(d, e),
                  p + 'moe_router_bias': rng.randn(e).astype(np.float32)
                  * 0.01,
                  p + 'moe_gate': bf(held, d, f), p + 'moe_up': bf(held, d, f),
                  p + 'moe_down': bf(held, f, d),
                  p + 'shared_gate_w': bf(d, f), p + 'shared_up_w': bf(d, f),
                  p + 'shared_down_w': bf(f, d)})
    ids = np.zeros(48, np.int64)               # padded, as the runner pads
    ids[:40] = rng.randint(2, v, 40)
    kw = model._model_kw(cfg)
    plain, gap = (np.asarray(a) for a in ref.logits(w, ids,
                                                    routing_gaps=True, **kw))
    assert np.isfinite(gap).all() and (gap >= 0).all()
    new = int(cfg['verify']['max_new_tokens'])
    read = np.arange(39 - new, 39)             # in front of the last token
    eps = float(np.median(gap[read]))
    cfg = harness.overlay(cfg, {'verify': {'routing_gap_eps': eps}})
    got = np.asarray(model.reference_logits(cfg, w, ids))
    base, alt = ref.logits(w, ids, either_way=(read, eps, 128), **kw)
    base = np.asarray(base)       # the same pass, the carried rows behind
    np.testing.assert_allclose(base, plain, rtol=0, atol=5e-6)
    near = gap[read] <= eps
    assert sorted(set(alt['row'].tolist())) == read[near].tolist()
    assert 0 < near.sum() < len(read) and not alt['overflow']
    untouched = np.setdiff1d(np.arange(len(ids)), read[near])
    np.testing.assert_array_equal(got[untouched], base[untouched])
    for r in read[near]:
        sides = [base[r]] + list(alt['logits'][alt['row'] == r])
        np.testing.assert_array_equal(got[r], model.weakest_side(sides))
    # too many near ties to carry: the rows left over come back at margin 0
    _, few = ref.logits(w, ids, either_way=(read, eps, 1), **kw)
    assert len(few['row']) == 1 and few['overflow']
    # blind to what was served: another last id moves no row in front of it
    other = ids.copy()
    other[39] = 2 + (ids[39] - 1) % (v - 2)
    np.testing.assert_array_equal(
        np.asarray(model.reference_logits(cfg, w, other))[:39], got[:39])
    np.savez(tmp_path / 'w.npz', **w)
    back = dict(np.load(tmp_path / 'w.npz'))
    assert back['embed_w'].dtype.kind == 'V'
    np.testing.assert_array_equal(
        np.asarray(model.reference_logits(cfg, back, ids)), got)
