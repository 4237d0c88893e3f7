"""The granite_4_0_h_micro configuration and its cell: the file against the
catalog's config (nothing reduced), what it assumes, the traffic mix against
the cache, the parameters held (3.19 B: 6.38 GB of bfloat16) and the bytes a
step, its attention and its Mamba-2 layers' STATE need, the SSD chunk's
operations and bytes, against hand counts; the two readers this cell brings
(ssm_chunk_device_ms, ssm_chunk_roofline) and the accepted state-space
readers on hand-made timelines and on a program that lacks what they read;
the cell's entries in BENCHMARK.json BY NAME — what THIS PR filed, as a
subset: no count of cells or configurations is pinned — and the cell end to
end under --rehearsal, tracing off and on."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, trace, traffic
from benchmark.configs import granite_4_0_h_micro as model
from benchmark.layer_metrics import (_xplane_meta, decode_attention_roofline,
                                     ssm_chunk_device_ms, ssm_chunk_roofline,
                                     ssm_scan_device_share,
                                     ssm_scan_roofline)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = 'granite_4_0_h_micro'
CELL = CONFIG + '.reason_closed'
MS = 1000000
# model-configs catalog, granite-4.0-h-micro, `config`
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
# the accepted per-layer metrics this PR appended the cell to, by name
JOINED = {'decode_step_device_ms', 'decode_step_roofline', 'artifact_load_s',
          'tick_host_ms', 'tick_feed_ms', 'tick_d2h_ms', 'tick_advance_ms',
          'tick_admit_ms', 'step_d2h_bytes', 'idle_attributed_share',
          'decode_attention_device_share', 'decode_attention_roofline',
          'step_ahead_share', 'feed_touched_share', 'sched_offcpu_share',
          'tick_gc_share', 'tick_ms_p99', 'tick_ms_max',
          'slices_per_chunk_dispatch', 'prefill_slice_device_ms',
          'ssm_scan_device_share', 'ssm_scan_roofline',
          'slice_deferred_share', 'emit_gap_ms_p99',
          'gap_p99_prefill_tokens', 'gap_p99_wait_share',
          'slice_read_wait_ms', 'request_ttft_p95_ms'}
NEW = {'ssm_chunk_device_ms': 'ms', 'ssm_chunk_roofline': '%'}
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}


def _cfg():
    return harness.load_json(os.path.join(ROOT, 'benchmark', 'configs',
                                          CONFIG + '.json'))


def _bench():
    return harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))


def test_every_key_is_the_catalogs_and_nothing_is_reduced():
    cfg = _cfg()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    entry = {c['name']: c for c in _bench()['configs']}[CONFIG]
    assert entry['reduced'] == [] and cfg['reduced'] == {}
    assert entry['file'] == 'benchmark/configs/%s.json' % CONFIG
    assert entry['source'] == cfg['source'] and len(entry['why']) <= 200
    assert 'ONE chip holds the whole model' in cfg['deployment']
    for key in ('head_dim', 'mamba_sizes', 'time_step_limit', 'gated_norm',
                'mlp', 'positions', 'multipliers', 'padded_heads',
                'recurrent_state', 'chunked_prefill', 'seeds', 'embed_std',
                'eos_id', 'max_cache_len', 'max_slots', 'num_blocks'):
        assert key in cfg['assumed']
    assert (cfg['max_slots'], cfg['block_size'], cfg['max_cache_len'],
            cfg['chunk_sizes']) == (64, 16, 4608, [128, 512])
    assert cfg['kv_cache_dtype'] == cfg['weights_dtype'] == 'bfloat16'
    assert cfg['state_dtype'] == 'float32'
    assert (cfg['dt_range'], cfg['a_range']) == ([0.001, 0.1], [1.0, 16.0])
    # the tied table seeded apart, and the final norm so that the logits'
    # standard deviation stays ~1: final_norm_std x embed_std x sqrt(2,048)
    # over logits_scaling
    assert cfg['final_norm_std'] * cfg['embed_std'] * 2048 ** 0.5 / 8 \
        == pytest.approx(1.0, rel=0.01)


def test_the_layer_map_is_the_published_list():
    cfg = _cfg()
    attend = [i for i, t in enumerate(cfg['layer_types'])
              if t == 'attention']
    assert attend == [5, 15, 25, 35]
    assert (model._count(cfg, 'mamba'), model._count(cfg, 'attention')) \
        == (36, 4)
    from models.granite_hybrid import layer_types
    assert layer_types(40) == cfg['layer_types']
    w = model._widths(cfg)
    assert (w.dh, w.di, w.xbc, w.q) == (64, 4096, 4352, 256)


def test_reason_closed_lengths_fit_the_cache_and_one_slice():
    cfg = _cfg()
    mix = harness.load_json(os.path.join(ROOT, 'benchmark', 'traffic',
                                         'reason_closed.json'))
    assert mix['runner'] == 'decode_closed' and mix['clients'] == 'max_slots'
    assert mix['prompt_len']['max'] <= max(model.chunk_sizes(cfg)) == 512
    assert (mix['prompt_len']['max'] + mix['output_len']['max']
            <= cfg['max_cache_len'])
    v = cfg['verify']
    assert max(v['prompt_lens']) + v['max_new_tokens'] <= v['pad_to'] \
        <= cfg['max_cache_len']
    lens = v['prompt_lens']
    assert len(lens) == 48 <= cfg['max_slots'] and v['max_new_tokens'] == 48
    # one prompt of 3 slices and one of 8: the carried state and the
    # carried tail are inside the comparison
    deep = [n for n in lens if n > mix['prompt_len']['max']]
    assert deep == [1500, 4000]
    assert all(mix['prompt_len']['min'] <= n <= mix['prompt_len']['max']
               for n in lens if n not in deep)
    assert 0 < v['margin_eps'] and 'routing_gap_eps' not in v
    assert 'bfloat16' in v['tolerance_reason']
    # the generator under a seed past 32 bits: ids in the whole vocabulary
    reqs = traffic.closed_requests(mix, 2 ** 31 + 42, 63,
                                   model.vocab_size(cfg))
    drawn = [next(reqs) for _ in range(64)]
    assert all(160 <= len(p) <= 512 and 512 <= n <= 4096 for p, n in drawn)
    assert all(2 <= p.min() and p.max() < 100352 for p, _ in drawn)
    assert max(p.max() for p, _ in drawn) > 50000


def test_parameters_and_bytes_against_hand_counts():
    cfg = _cfg()
    table = 100352 * 2048
    assert model.mlp_params(cfg) == 2048 * 16384 + 8192 * 2048 + 2 * 2048 \
        == 50331648 + 4096
    # in_proj (to 4,096 | 4,352 | 64), conv + bias, A_log, D, dt_bias, the
    # gated norm, out_proj
    mamba = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048)
    assert model.mamba_params(cfg) == mamba == 25847232
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert model.attention_params(cfg) == attn == 10485760
    held = 36 * (mamba + 50335744) + 4 * (attn + 50335744) + table + 2048
    assert model.weight_params(cfg) == held == 3191396096
    assert model.step_weight_bytes(cfg) == 6382792192
    # K and V of 8 heads of 64 in bfloat16: 2,048 B a cached row a layer
    assert model.kv_row_bytes(cfg) == 2048
    rows = 64 * 1560
    assert model.attention_bytes(cfg, rows, 64) == 8192 * rows
    assert model.attention_bytes(cfg, rows, 64) == pytest.approx(0.82e9,
                                                                 rel=5e-3)
    assert model.attention_bytes(cfg, 0, 0) == 0
    # the state: [64, 64, 128] float32 + a tail of 3 x 4,352 float32
    assert model.state_slot_bytes(cfg) == 2097152 + 52224 == 2149376
    assert model.ssm_state_bytes(cfg, 1) == 36 * 2 * 2149376
    assert model.ssm_state_bytes(cfg, 64) == pytest.approx(9.90e9, rel=1e-3)
    floor = model.step_floor_seconds(cfg, PEAKS, rows)
    assert floor == pytest.approx(
        (6382792192 + 8192 * rows + model.ssm_state_bytes(cfg, 64)) / 819e9)
    assert floor == pytest.approx(20.9e-3, rel=5e-3)
    # the state is 58 % of a step's bytes
    assert model.ssm_state_bytes(cfg, 64) / (floor * 819e9) \
        == pytest.approx(0.58, abs=0.005)
    assert model.BOUND == 'memory'


def test_the_chips_resident_bytes_are_what_the_file_says():
    """13.75 GB of 17.18: the weights, 36 layers' states at 64 slots, four
    attention layers' pool."""
    cfg = _cfg()
    state = 36 * 64 * model.state_slot_bytes(cfg)
    pool = 4 * (64 * 288 + 1) * 16 * model.kv_row_bytes(cfg)
    assert (state, pool) == (4952162304, 2416050176)
    assert model.step_weight_bytes(cfg) + state + pool \
        == pytest.approx(13.75e9, rel=1e-3)


def test_the_ssd_chunks_operations_and_bytes_against_hand_counts():
    cfg = _cfg()
    # a sub-chunk of 256: C B^T once, then a head's (L * CB)(dX), C S_0 and
    # the state's update
    sub = 2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64
                                      + 4 * 256 * 128 * 64)
    assert sub == 1090519040
    assert model.ssd_chunk_flops(cfg, 512) == 36 * 2 * sub
    assert model.ssd_chunk_flops(cfg, 512) / 36 == pytest.approx(2.18e9,
                                                                rel=2e-3)
    assert model.ssd_chunk_flops(cfg, 256) == 36 * sub
    # 330 real tokens: one sub-chunk of 256 and one of 74, not two of 256
    rest = 2 * 74 * 74 * 128 + 64 * (2 * 74 * 74 * 64 + 4 * 74 * 128 * 64)
    assert model.ssd_chunk_flops(cfg, 330) == 36 * (sub + rest)
    assert model.ssd_chunk_flops(cfg, 330) < 0.6 * model.ssd_chunk_flops(
        cfg, 512)
    assert model.ssd_chunk_flops(cfg, 0) == 0
    # bytes: the state once read and written, x and y [tokens, 4096], B, C
    # [tokens, 128], dt [tokens, 64], float32
    assert model.ssd_chunk_bytes(cfg, 512) == 36 * (
        2 * 2097152 + 512 * (2 * 4096 + 2 * 128 + 64) * 4)
    assert model.ssd_chunk_bytes(cfg, 512) / 36 == pytest.approx(21.6e6,
                                                                rel=5e-3)
    assert model.ssd_chunk_bytes(cfg, 0) == 36 * 2 * 2097152
    # the bytes bound the chunk on this chip, at any length
    for tokens in (128, 330, 512):
        assert (model.ssd_chunk_bytes(cfg, tokens) / 819e9
                > model.ssd_chunk_flops(cfg, tokens) / 197e12)


# -- the readers ---------------------------------------------------------------
def _timeline(modules, ops=()):
    dev = trace.Device('/device:TPU:0', ops=list(ops), modules=list(modules))
    return trace.Trace(devices=[dev], host=[], window=(0, 200 * MS))


def _requests(prompt_lens, slices=None, t=1.0):
    rows = np.zeros(len(prompt_lens), dtype=[
        ('t_submit', 'f8'), ('t_first', 'f8'), ('prompt_len', 'i8'),
        ('prefix_covered', 'i8'), ('slices', 'i8')])
    rows['t_submit'], rows['t_first'] = t, t + 0.1
    rows['prompt_len'] = prompt_lens
    rows['slices'] = 1 if slices is None else slices
    return rows


def _run(tr, path='x', live=64, rows=64 * 1560, requests=None, mod=model):
    ctx = types.SimpleNamespace(model=mod, cfg=_cfg(),
                                tracer=types.SimpleNamespace(path=path),
                                peaks=PEAKS)
    log = None if requests is None else (
        lambda since=0.0: requests[requests['t_submit'] >= since])
    stats = types.SimpleNamespace(request_log=log) if log else \
        types.SimpleNamespace()
    runner = types.SimpleNamespace(served=types.SimpleNamespace(
        pred=types.SimpleNamespace(stats=stats)))
    return {'trace': tr, 'ctx': ctx, 'runner': runner,
            'result': {'floor_arg': rows, 't_open': 0.5, 'window_s': 45.0,
                       'counters_traced': {'steps': 10,
                                           'active_slot_steps': 10 * live}}}


NAMES = {
    'i': 'jit(decode_step)/state_space/in_proj/mul/dot_general',
    'c': 'jit(decode_step)/state_space/conv/causal_conv_step/mul',
    's': 'jit(decode_step)/state_space/selective_scan/ssd_step/multiply',
    'r': 'jit(decode_step)/state_space/selective_scan/ssd_step/reduce_sum',
    'n': 'jit(decode_step)/state_space/gated_norm/rms_norm/mul',
    'a': 'jit(decode_step)/full_attention/kv_block_attention/while',
    'f': 'jit(decode_step)/mul/dot_general',
    'C': 'jit(prefill_chunk_512)/state_space/selective_scan/ssd_chunk/'
         'dot_general',
    'E': 'jit(prefill_chunk_512)/state_space/selective_scan/ssd_chunk/exp',
    'V': 'jit(prefill_chunk_512)/state_space/conv/causal_conv_chunk/mul',
    'F': 'jit(prefill_chunk_512)/mul/dot_general',
    'S': 'jit(prefill_chunk_128)/state_space/selective_scan/ssd_chunk/'
         'dot_general'}


def _step(t0):
    cuts = [0, 2, 3, 12, 17, 18, 20, 28]
    return [((t0 + a) * MS, (t0 + b) * MS, n)
            for a, b, n in zip(cuts, cuts[1:], 'icsrnaf')]


def _slice(t0, name='jit_prefill_chunk_512(1)', scan=8):
    ops = [(t0 * MS, (t0 + scan) * MS, 'C'),
           ((t0 + scan) * MS, (t0 + scan + 2) * MS, 'E'),
           ((t0 + scan + 2) * MS, (t0 + scan + 3) * MS, 'V'),
           ((t0 + scan + 3) * MS, (t0 + 35) * MS, 'F')]
    return ops, (t0 * MS, (t0 + 35) * MS, name)


def test_the_readers_on_a_hand_made_timeline(monkeypatch):
    """Three steps of 28 ms — 2 in_proj, 1 the convolution, 9 + 5 the
    recurrence, 1 the gated norm, 2 attention, 8 the plain products — and
    two 512-slices of 35 ms — 8 + 2 under the chunk form, 1 the
    convolution — and a 128-slice no reader of the largest program
    counts."""
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': NAMES})
    a, ma = _slice(84)
    b, mb = _slice(120)
    small = [(160 * MS, 180 * MS, 'S')]
    ops = _step(0) + _step(28) + _step(56) + a + b + small
    mods = [(t * MS, (t + 28) * MS, 'jit_decode_step(4)')
            for t in (0, 28, 56)] + [
                ma, mb, (160 * MS, 181 * MS, 'jit_prefill_chunk_128(1)')]
    cfg = _cfg()
    # 64 requests of 330 tokens, one slice each
    run = _run(_timeline(mods, ops), requests=_requests([330] * 64))
    assert ssm_scan_device_share.reduce(run) == pytest.approx(100 * 14 / 28)
    state_floor = model.ssm_state_bytes(cfg, 64) / 819e9
    assert ssm_scan_roofline.reduce(run) == pytest.approx(
        100 * state_floor / 15e-3)
    assert ssm_scan_roofline.reduce(run) == pytest.approx(80.6, abs=0.1)
    assert decode_attention_roofline.reduce(run) == pytest.approx(
        100 * model.attention_bytes(cfg, 64 * 1560, 64) / 819e9 / 2e-3)
    # the chunk form: 10 ms under selective_scan, 11 with the convolution
    assert ssm_chunk_device_ms.reduce(run) == pytest.approx(11.0)
    floor = model.ssd_chunk_bytes(cfg, 330) / 819e9
    assert floor > model.ssd_chunk_flops(cfg, 330) / 197e12
    assert ssm_chunk_roofline.reduce(run) == pytest.approx(
        100 * floor / 10e-3)
    assert ssm_chunk_roofline.reduce(run) == pytest.approx(6.78, abs=0.01)
    # reckoned at the MEAN REAL tokens of a slice: full slices are dearer,
    # and a prompt of two slices counts its tokens over both
    full = _run(_timeline(mods, ops), requests=_requests([512] * 64))
    assert ssm_chunk_roofline.reduce(full) == pytest.approx(
        100 * model.ssd_chunk_bytes(cfg, 512) / 819e9 / 10e-3)
    two = _run(_timeline(mods, ops),
               requests=_requests([660] * 64, slices=2))
    assert ssm_chunk_roofline.reduce(two) == pytest.approx(
        ssm_chunk_roofline.reduce(run))
    # requests submitted before the window opened are not the window's
    early = _run(_timeline(mods, ops),
                 requests=np.concatenate([_requests([330] * 8),
                                          _requests([512] * 50, t=0.1)]))
    assert ssm_chunk_roofline.reduce(early) == pytest.approx(
        ssm_chunk_roofline.reduce(run))
    # a chunk form that is bound by its operations is read by them
    fast = dict(PEAKS, bf16_flops_per_s=1e12)
    run['ctx'].peaks = fast
    assert ssm_chunk_roofline.reduce(run) == pytest.approx(
        100 * model.ssd_chunk_flops(cfg, 330) / 1e12 / 10e-3)


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """A program without the scopes (the parent's, any other
    configuration's), a window without a slice, a program without a request
    log, a configuration without the functions, a run without a trace:
    None, never an error."""
    a, ma = _slice(0)
    tl = _timeline([ma], a)
    reqs = _requests([330] * 8)
    other = {k: v.replace('state_space', 'linear_attention')
             for k, v in NAMES.items()}
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': other})
    empty = trace.Trace(devices=[], host=[], window=(0, MS))
    for reader in (ssm_chunk_device_ms, ssm_chunk_roofline):
        assert reader.reduce(_run(tl, requests=reqs)) is None
        assert reader.reduce(_run(empty, requests=reqs)) is None
        assert reader.reduce(_run(None, requests=reqs)) is None
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': NAMES})
    assert ssm_chunk_device_ms.reduce(_run(tl, requests=reqs)) \
        == pytest.approx(11.0)
    assert ssm_chunk_roofline.reduce(_run(tl, requests=reqs)) > 0
    # no request log (the parent of the PR that added it), or no columns
    assert ssm_chunk_roofline.reduce(_run(tl)) is None
    bare = np.zeros(3, dtype=[('t_submit', 'f8'), ('t_first', 'f8')])
    bare['t_submit'] = 1.0
    assert ssm_chunk_roofline.reduce(_run(tl, requests=bare)) is None
    # a window that held steps only
    steps = _timeline([(0, 28 * MS, 'jit_decode_step(4)')], _step(0))
    assert ssm_chunk_device_ms.reduce(_run(steps, requests=reqs)) is None
    assert ssm_chunk_roofline.reduce(_run(steps, requests=reqs)) is None
    # a configuration whose module has neither function, or no chunk sizes
    lacking = types.SimpleNamespace(chunk_sizes=model.chunk_sizes)
    assert ssm_chunk_roofline.reduce(
        _run(tl, requests=reqs, mod=lacking)) is None
    assert ssm_chunk_device_ms.reduce(
        _run(tl, requests=reqs, mod=lacking)) == pytest.approx(11.0)
    nothing = types.SimpleNamespace()
    assert ssm_chunk_device_ms.reduce(
        _run(tl, requests=reqs, mod=nothing)) is None


def test_the_cell_is_filed_by_name_under_what_it_reports():
    """What THIS PR filed, as a subset of what the cell reports: a later PR
    that files one more metric, cell or configuration breaks nothing
    here."""
    bench = _bench()
    cells = {w['name']: w for w in bench['workloads']}
    assert cells[CELL] == dict(cells[CELL], config=CONFIG,
                               traffic='reason_closed', chips=1)
    assert len(cells[CELL]['why']) <= 200
    e2e = {m['name'] for m in bench['end_to_end']
           if 'workloads' not in m or CELL in m['workloads']}
    assert {'itl_p99_ms', 'setup_s'} <= e2e
    by_name = {m['name']: m for m in bench['per_layer']}
    filed = {n for n, m in by_name.items() if CELL in m.get('workloads', ())}
    assert JOINED | set(NEW) <= filed
    for name, unit in NEW.items():  # this PR's, born for this cell
        assert by_name[name]['workloads'][0] == CELL
        assert by_name[name]['layer'] == 'Op lowerings / kernels'
        assert by_name[name]['source'] == 'device_trace'
        assert by_name[name]['unit'] == unit
    for name in JOINED | set(NEW):
        assert by_name[name]['moves'] == (
            'setup_s' if name == 'artifact_load_s' else 'itl_p99_ms')
        assert os.path.exists(os.path.join(
            ROOT, 'benchmark', 'layer_metrics', name + '.py'))
    for name in JOINED:
        # appended: the accepted cells before it are as they were
        before = by_name[name]['workloads'][
            :by_name[name]['workloads'].index(CELL)]
        assert 'phi4_mini_flash_reasoning.reason_closed' in before
    assert not any(n.startswith(('moe_', 'linear_attention_', 'latent_',
                                 'shared_cache_'))
                   for n in filed)


@pytest.mark.parametrize('traced', [0, 1])
def test_the_cell_runs_end_to_end_under_rehearsal(traced):
    """The harness finds the configuration, the traffic mix and the new
    readers by name and runs the cell at toy sizes on the cpu: a clean
    window, transcripts the position-by-position reference agrees with,
    every token served through per-slot Mamba-2 states and one pool."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', CELL, '--seed', str(2 ** 31 + 42), '--seconds', '4',
         '--trace', str(traced), '--rehearsal'],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line['rehearsal'] is True and line['correct'] is False
    assert line['rehearsal_checks_passed'] is True, out.stdout[-3000:]
    assert line['failed'] == 0 and line['attempted'] > 0
    metrics = line['metrics']
    if not traced:
        assert {'itl_p99_ms', 'setup_s'} <= set(metrics)
        assert metrics['itl_p99_ms']['value'] > 0
        return
    assert metrics['compiles_in_window']['value'] == 0
    assert metrics['prefill_slice_device_ms']['value'] > 0
    assert metrics['slices_per_chunk_dispatch']['value'] == 1.0
    assert metrics['step_d2h_bytes']['value'] == 4 * 8      # ids alone
    # the cpu's trace names no scope: the scope readers are left out
    assert not (set(NEW) | {'ssm_scan_roofline'}) & set(metrics)
