"""The phi4_mini_flash_reasoning configuration and its cell: the file against
the catalog's config (nothing reduced), what it assumes, the traffic mix
against the cache, the parameters held (3.85 B: 7.70 GB of bfloat16) and the
bytes a step, its attention — ONE cache read by eight layers — and its Mamba
layers' STATE need, against hand counts; the three readers this cell brings
(ssm_scan_device_share, ssm_scan_roofline,
shared_cache_attention_device_share) on hand-made timelines and on a program
that lacks what they read; the accepted decode_attention_roofline through
the new attention_bytes; the cell's entries in BENCHMARK.json BY NAME; and
the cell end to end under --rehearsal."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import harness, trace, traffic
from benchmark.configs import phi4_mini_flash_reasoning as model
from benchmark.layer_metrics import (_xplane_meta, decode_attention_roofline,
                                     shared_cache_attention_device_share,
                                     ssm_scan_device_share,
                                     ssm_scan_roofline)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = 'phi4_mini_flash_reasoning'
CELL = CONFIG + '.reason_closed'
MS = 1000000
# model-configs catalog, Phi-4-mini-flash-reasoning, `config`
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
# the accepted per-layer metrics the cell is filed under, by name
SHARED = {'decode_step_device_ms', 'decode_step_roofline', 'artifact_load_s',
          'tick_host_ms', 'tick_feed_ms', 'tick_d2h_ms', 'tick_advance_ms',
          'tick_admit_ms', 'step_d2h_bytes', 'idle_attributed_share',
          'decode_attention_device_share', 'decode_attention_roofline',
          'step_ahead_share', 'feed_touched_share', 'sched_offcpu_share',
          'tick_gc_share', 'tick_ms_p99', 'tick_ms_max',
          'slices_per_chunk_dispatch', 'prefill_slice_device_ms'}
NEW = {'ssm_scan_device_share', 'ssm_scan_roofline',
       'shared_cache_attention_device_share'}


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
    # what the catalog does not carry, each said with its source
    assert (cfg['mamba_d_state'], cfg['mamba_d_conv'], cfg['mamba_expand'],
            cfg['mamba_dt_rank']) == (16, 4, 2, 160)
    for key in ('mamba_sizes', 'layer_map', 'positions', 'head_split',
                'lambda_init', 'biases', 'differential_attention',
                'one_pass_attention', 'cross_decoder_prefill',
                'recurrent_state', 'chunked_prefill', 'seeds', 'embed_std',
                'eos_id', 'max_cache_len', 'max_slots', 'num_blocks'):
        assert key in cfg['assumed']
    assert (cfg['max_slots'], cfg['block_size'], cfg['max_cache_len']) == (
        64, 16, 4608)
    assert cfg['kv_cache_dtype'] == cfg['weights_dtype'] == 'bfloat16'
    assert cfg['state_dtype'] == 'float32'
    # the tied table seeded apart, and the final norm so that the logits'
    # standard deviation stays ~1: final_norm_std x embed_std x sqrt(2,560)
    assert cfg['final_norm_std'] * cfg['embed_std'] * 2560 ** 0.5 \
        == pytest.approx(1.0, rel=0.01)


def test_the_layer_map_is_the_papers():
    from models.phi4_flash import CROSS, FULL, GMU, MAMBA, WINDOW
    types_ = model._types(_cfg())
    assert types_[:18] == [MAMBA, WINDOW] * 8 + [MAMBA, FULL]
    assert types_[18:] == [GMU, CROSS] * 7
    assert [model._count(_cfg(), t)
            for t in (MAMBA, WINDOW, FULL, GMU, CROSS)] == [9, 8, 1, 7, 7]


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
    # one prompt of 3 slices and one of 8: the carried state, the tail and
    # a window that has dropped rows are inside the comparison
    deep = [n for n in lens if n > mix['prompt_len']['max']]
    assert deep == [1500, 4000] and min(deep) > cfg['sliding_window']
    assert all(mix['prompt_len']['min'] <= n <= mix['prompt_len']['max']
               for n in lens if n not in deep)
    assert 0 < v['margin_eps'] and 'routing_gap_eps' not in v
    # the generator under a seed past 32 bits: ids in the whole vocabulary
    reqs = traffic.closed_requests(mix, 2 ** 31 + 42, 63,
                                   model.vocab_size(cfg))
    drawn = [next(reqs) for _ in range(64)]
    assert all(160 <= len(p) <= 512 and 512 <= n <= 4096 for p, n in drawn)
    assert all(2 <= p.min() and p.max() < 200064 for p, _ in drawn)
    assert max(p.max() for p, _ in drawn) > 100000


def test_parameters_and_bytes_against_hand_counts():
    cfg = _cfg()
    table = 200064 * 2560
    assert table == 512163840
    assert model.mlp_params(cfg) == 3 * 2560 * 10240 + 4 * 2560 == 78653440
    # W_in, conv + bias, W_x, W_dt + bias, A_log, D, W_out
    mamba = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 16 * 5120 + 5120 + 5120 * 2560)
    assert model.mamba_params(cfg) == mamba == 41241600
    # q, o (2,560 square), k, v (to 20 heads of 64), biases, lambdas, subln
    attn = 2 * (2560 * 2560 + 2560) + 2 * (2560 * 1280 + 1280) + 256 + 128
    assert model.attention_params(cfg) == attn == 19668864
    cross = 2 * (2560 * 2560 + 2560) + 256 + 128
    assert model.attention_params(cfg, cross=True) == cross == 13112704
    assert model.gmu_params(cfg) == 2 * 2560 * 5120 == 26214400
    held = (table + 32 * 78653440 + 9 * mamba + 9 * attn + 7 * 26214400
            + 7 * cross + 2 * 2560)
    assert model.weight_params(cfg) == held == 3852562944
    assert model.step_weight_bytes(cfg) == pytest.approx(7.70e9, rel=1e-3)
    # K and V of 20 heads of 64 in bfloat16
    assert model.kv_row_bytes(cfg) == 5120
    rows = 64 * 1600
    # layer 17's rows by 8 readers + 8 window layers' last 512 a slot
    assert model.attention_bytes(cfg, rows, 64) == 5120 * (
        8 * rows + 8 * 64 * 512)
    assert model.attention_bytes(cfg, rows, 64) == pytest.approx(5.54e9,
                                                                 rel=2e-3)
    # a row shorter than the window is read whole
    assert model.attention_bytes(cfg, 64 * 300, 64) == 5120 * 16 * 64 * 300
    assert model.attention_bytes(cfg, 0, 0) == 0
    # the state: [16, 5120] float32 + a tail of 3 x 5,120 float32
    assert model.state_slot_bytes(cfg) == 327680 + 61440 == 389120
    assert model.ssm_state_bytes(cfg, 64) == 2 * 64 * 9 * 389120
    assert model.ssm_state_bytes(cfg, 64) == pytest.approx(0.448e9, rel=2e-3)
    peaks = {'hbm_bytes_per_s': 819e9}
    floor = model.step_floor_seconds(cfg, peaks, rows)
    assert floor == pytest.approx(
        (model.step_weight_bytes(cfg) + model.attention_bytes(cfg, rows, 64)
         + model.ssm_state_bytes(cfg, 64)) / 819e9)
    assert floor == pytest.approx(16.7e-3, rel=0.01)
    # the cache and the state are 44 % of a step's bytes, the one shared
    # cache's eight readers alone 31 %
    need = floor * 819e9
    assert (model.attention_bytes(cfg, rows, 64)
            + model.ssm_state_bytes(cfg, 64)) / need == pytest.approx(
                0.44, abs=0.01)
    assert 5120 * 8 * rows / need == pytest.approx(0.31, abs=0.01)
    assert model.BOUND == 'memory'


def test_the_pools_resident_bytes_are_what_the_file_says():
    """12.2 GB of 16: the weights, one full layer's pool, eight window
    pools of 65 blocks a slot, nine layers' states."""
    from paddle_tpu.inference.kv_blocks import window_blocks_per_slot
    cfg = _cfg()
    block = cfg['block_size'] * model.kv_row_bytes(cfg)
    assert block == 81920
    assert window_blocks_per_slot(512, 512, 16) == 65
    full = (64 * 288 + 1) * block
    window = 8 * (64 * 65 + 1) * block
    state = 9 * 64 * model.state_slot_bytes(cfg)
    assert (full, window, state) == (1510031360, 2726952960, 224133120)
    resident = model.step_weight_bytes(cfg) + full + window + state
    assert resident == pytest.approx(12.17e9, rel=2e-3)
    # 128 slots would not fit the chip
    assert (model.step_weight_bytes(cfg) + (128 * 288 + 1) * block
            + 8 * (128 * 65 + 1) * block + 2 * state) > 16e9


def _timeline(modules, ops=()):
    dev = trace.Device('/device:TPU:0', ops=list(ops), modules=list(modules))
    return trace.Trace(devices=[dev], host=[], window=(0, 100 * MS))


def _run(tr, path=None, live=64, rows=64 * 1600):
    ctx = types.SimpleNamespace(model=model, cfg=_cfg(),
                                tracer=types.SimpleNamespace(path=path),
                                peaks={'hbm_bytes_per_s': 819e9})
    return {'trace': tr, 'ctx': ctx,
            'result': {'floor_arg': rows,
                       'counters_traced': {'steps': 10,
                                           'active_slot_steps': 10 * live}}}


NAMES = {
    'i': 'jit(decode_step)/state_space/in_proj/mul/dot_general',
    'c': 'jit(decode_step)/state_space/conv/causal_conv_step/mul',
    's': 'jit(decode_step)/state_space/selective_scan/selective_scan_step/'
         'exp',
    'r': 'jit(decode_step)/state_space/selective_scan/selective_scan_step/'
         'reduce_sum',
    'w': 'jit(decode_step)/kv_block_attention/while',
    'x': 'jit(decode_step)/cross_decoder/kv_block_attention/while',
    'd': 'jit(decode_step)/cross_decoder/differential/rms_norm/mul',
    'g': 'jit(decode_step)/gated_memory/mul/dot_general',
    'f': 'jit(decode_step)/mul/dot_general',
    'C': 'jit(prefill_chunk_512)/state_space/selective_scan/'
         'selective_scan_chunk/while'}


def test_the_three_readers_on_a_hand_made_timeline(monkeypatch):
    """Two steps of 20 ms — 1 in_proj, 1 the convolution, 1 + 1 the scan, 2
    the window layers' attention, 6 the cross-decoder's, 1 its norm, 1 the
    GMU, 6 the MLPs — and a 512-slice that no reader of the step counts:
    the scan's share is 2 of 20, the shared cache's 6 of 20, the state's
    roofline its bytes over the 3 ms the scan AND the convolution took."""
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': NAMES})

    def step(t0):
        cuts = [0, 1, 2, 3, 4, 6, 12, 13, 14, 20]
        return [((t0 + a) * MS, (t0 + b) * MS, n)
                for a, b, n in zip(cuts, cuts[1:], 'icsrwxdgf')]
    ops = step(0) + step(20) + [(40 * MS, 49 * MS, 'C')]
    mods = [(0, 20 * MS, 'jit_decode_step(4)'),
            (20 * MS, 40 * MS, 'jit_decode_step(4)'),
            (40 * MS, 62 * MS, 'jit_prefill_chunk_512(1)')]
    run = _run(_timeline(mods, ops), path='x')
    assert ssm_scan_device_share.reduce(run) == pytest.approx(10.0)
    assert shared_cache_attention_device_share.reduce(run) \
        == pytest.approx(30.0)
    floor = model.ssm_state_bytes(_cfg(), 64) / 819e9
    assert ssm_scan_roofline.reduce(run) == pytest.approx(100 * floor / 3e-3)
    assert ssm_scan_roofline.reduce(run) == pytest.approx(18.2, abs=0.1)
    # half the slots live: half the bytes over the same time
    half = _run(_timeline(mods, ops), path='x', live=32)
    assert ssm_scan_roofline.reduce(half) == pytest.approx(
        50 * floor / 3e-3)
    # the accepted reader takes BOTH attention scopes and the new bytes:
    # 5.54 GB over the 8 ms the window's and the cross-decoder's ops took
    want = 100 * model.attention_bytes(_cfg(), 64 * 1600, 64) / 819e9 / 8e-3
    assert decode_attention_roofline.reduce(run) == pytest.approx(want)
    assert want == pytest.approx(84.5, abs=0.2)


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """A program without the scopes (the parent's, any other
    configuration's), a trace without a device, a run without a trace:
    None, never an error."""
    other = {k: v.replace('state_space', 'linear_attention')
             .replace('cross_decoder', 'full_attention')
             for k, v in NAMES.items()}
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': other})
    step_only = _timeline([(1 * MS, 9 * MS, 'jit_decode_step(4)')],
                          [(1 * MS, 9 * MS, 's')])
    readers = (ssm_scan_device_share, ssm_scan_roofline,
               shared_cache_attention_device_share)
    empty = trace.Trace(devices=[], host=[], window=(0, MS))
    for reader in readers:
        assert reader.reduce(_run(step_only, path='x')) is None
        assert reader.reduce(_run(empty, path='x')) is None
        assert reader.reduce(_run(None)) is None
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': NAMES})
    assert ssm_scan_roofline.reduce(_run(step_only, path='x')) > 0
    # a configuration whose module has no ssm_state_bytes
    bare = types.SimpleNamespace(model=types.SimpleNamespace(), cfg={},
                                 tracer=types.SimpleNamespace(path='x'),
                                 peaks={'hbm_bytes_per_s': 819e9})
    assert ssm_scan_roofline.reduce({'trace': step_only, 'ctx': bare,
                                     'result': {}}) is None


def test_the_cell_is_filed_by_name_under_what_it_reports():
    bench = _bench()
    cells = {w['name']: w for w in bench['workloads']}
    assert cells[CELL] == dict(cells[CELL], config=CONFIG,
                               traffic='reason_closed', chips=1)
    assert len(cells[CELL]['why']) <= 200
    assert sum(w['chips'] == 4 for w in cells.values()) == 1
    assert len(bench['configs']) == 7 and len(cells) == 9
    # the third reasoning cell on one traffic file
    assert sorted(n for n, w in cells.items()
                  if w['traffic'] == 'reason_closed') == sorted([
                      'joyai_llm_flash.reason_closed',
                      'qwen3_next_80b_a3b.reason_closed', CELL])
    e2e = {m['name'] for m in bench['end_to_end']
           if 'workloads' not in m or CELL in m['workloads']}
    assert e2e == {'itl_p99_ms', 'setup_s'}
    by_name = {m['name']: m for m in bench['per_layer']}
    filed = {n for n, m in by_name.items() if CELL in m.get('workloads', ())}
    assert filed == SHARED | NEW
    for name in NEW:        # this PR's, for this cell alone, filed last
        assert by_name[name]['workloads'] == [CELL]
        assert by_name[name]['layer'] == 'Op lowerings / kernels'
        assert by_name[name]['source'] == 'device_trace'
        assert by_name[name]['unit'] == '%'
    assert [m['name'] for m in bench['per_layer'][-3:]] == [
        'ssm_scan_device_share', 'ssm_scan_roofline',
        'shared_cache_attention_device_share']
    for name in filed:
        assert by_name[name]['moves'] == (
            'setup_s' if name == 'artifact_load_s' else 'itl_p99_ms')
        assert os.path.exists(os.path.join(
            ROOT, 'benchmark', 'layer_metrics', name + '.py'))
        # appended: the accepted cells before it are as they were
        assert by_name[name]['workloads'][-1] == CELL
    for name in ('decode_step_roofline', 'decode_attention_roofline'):
        assert CELL in by_name[name]['workloads']
    assert not any(n.startswith(('moe_', 'linear_attention_', 'latent_'))
                   for n in filed)
    assert json.dumps(bench).count(CELL) == 1 + 1 + len(filed)


def test_the_cell_runs_end_to_end_under_rehearsal():
    """The harness finds the configuration, the traffic mix and the three
    new readers by name and runs the cell at toy sizes on the cpu: a clean
    window, transcripts the token-by-token reference agrees with, every
    token served through per-slot states and one shared pool."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', CELL, '--seed', str(2 ** 31 + 42), '--seconds', '4',
         '--trace', '1', '--rehearsal'],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line['rehearsal'] is True and line['correct'] is False
    assert line['rehearsal_checks_passed'] is True, out.stdout[-3000:]
    assert line['failed'] == 0 and line['attempted'] > 0
    metrics = line['metrics']
    assert metrics['compiles_in_window']['value'] == 0
    assert metrics['prefill_slice_device_ms']['value'] > 0
    assert metrics['slices_per_chunk_dispatch']['value'] == 1.0
    assert metrics['step_d2h_bytes']['value'] == 4 * 8      # ids alone
    # the cpu's trace names no scope: the three new metrics are left out
    assert not NEW & set(metrics)
