"""The routed FFN's grouped matmul kernel (ISSUE 41), on the CPU: the
kernel through Pallas interpret mode against lax.ragged_dot, the rule
that keeps ragged_dot, the platform switch in a cpu+tpu export, and the
gradient. (It is compiled for a described v5e at the cells' widths in
tests/test_paged_attention_kernel.py, the one file that loads the TPU's
compiler.)"""
import contextlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as fluid
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import pallas_grouped_matmul as pgm
from paddle_tpu.parallel.mesh import trace_mesh_scope

# (K, N, weight-tile budget): the three cells' classes at an eighth of
# their widths, each as its gate / up product and as its down product —
# joyai_llm_flash 2,048 x 768 and olmoe_1b_7b 2,048 x 1,024 (a matrix is
# one tile), k_exaone_236b_a23b 6,144 x 2,048 (several column tiles: the
# budget is cut as the widths are)
CLASSES = {
    'joyai_in': (256, 384, None), 'joyai_out': (384, 256, None),
    'olmoe_in': (256, 128, None), 'olmoe_out': (128, 256, None),
    'exaone_in': (768, 256, 768 * 128 * 2),
    'exaone_out': (256, 768, 256 * 256 * 2),
}
# (rows, sizes): what the sorted pairs can look like
LAYOUTS = {
    'ragged_with_empty_groups': (256, [3, 0, 5, 120, 0, 9, 0, 1]),
    'one_group_holds_every_row': (256, [0, 256, 0]),
    'no_group_holds_a_row': (128, [0, 0, 0, 0]),
    'rows_past_the_sizes': (384, [7, 30, 0, 11]),
    'a_row_count_no_tile_divides': (200, [10, 20, 30, 90, 50]),
    'a_group_over_three_tiles': (512, [100, 290, 2, 120]),
    'every_tile_full': (256, [128, 128]),
}


@contextlib.contextmanager
def _budget(nbytes):
    was = pgm._WEIGHT_TILE_BYTES
    if nbytes:
        pgm._WEIGHT_TILE_BYTES = nbytes
    try:
        yield
    finally:
        pgm._WEIGHT_TILE_BYTES = was


def _operands(m, k, n, sizes, seed=0):
    """rows, weights, sizes; the rows behind sum(sizes) hold NaN."""
    rng = np.random.RandomState(seed)
    rows = rng.randn(m, k).astype(np.float32)
    rows[int(np.sum(sizes)):] = np.nan
    w = rng.randn(len(sizes), k, n).astype(np.float32) * k ** -0.5
    return (jnp.asarray(rows, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16),
            jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize('layout', sorted(LAYOUTS))
@pytest.mark.parametrize('shape', sorted(CLASSES))
def test_kernel_equals_ragged_dot(shape, layout):
    k, n, budget = CLASSES[shape]
    m, sizes = LAYOUTS[layout]
    rows, w, sizes = _operands(m, k, n, sizes)
    held = int(sizes.sum())
    with _budget(budget):
        assert pgm.refuses(rows, w, sizes) is None
        if budget:
            assert pgm._column_tile(k, n, 2) < n
        got = np.asarray(pgm.grouped_matmul(rows, w, sizes, interpret=True))
    want = np.asarray(lax.ragged_dot(rows, w, sizes,
                                     preferred_element_type=jnp.float32))
    assert got.shape == (m, n) and got.dtype == np.float32
    # the NaN rows behind the sizes reach no held row
    assert np.isfinite(got[:held]).all()
    np.testing.assert_allclose(got[:held], want[:held], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_the_walk_reaches_every_group_and_every_held_row_once(layout):
    """(group, row tile) visits in row order: every group at least once
    — an empty one exactly once, so that the stream is every held
    expert's weights (the module says why) — every held row under
    exactly one visit's mask, a tile's visits consecutive."""
    m, sizes = LAYOUTS[layout]
    m += -m % pgm._ROW_TILE
    tm, n_tile, n_group = pgm._ROW_TILE, m // pgm._ROW_TILE, len(sizes)
    n_visit = n_tile + n_group - 1
    group, tile, start, end, visits = (np.asarray(a) for a in pgm._visits(
        jnp.asarray(sizes, jnp.int32), tm, n_tile, n_visit))
    assert group.shape == tile.shape == (n_visit,)
    assert n_group <= visits <= n_visit
    group, tile = group[:visits], tile[:visits]
    assert (np.diff(group) >= 0).all() and (np.diff(tile) >= 0).all()
    assert sorted(set(group)) == list(range(n_group))
    assert (0 <= tile).all() and (tile < n_tile).all()
    covered = np.zeros(m, int)
    for g, t in zip(group, tile):
        rows = np.arange(t * tm, (t + 1) * tm)
        covered[rows[(rows >= start[g]) & (rows < end[g])]] += 1
    held = int(np.sum(sizes))
    assert (covered[:held] == 1).all() and not covered[held:].any()
    for g, size in enumerate(sizes):
        if not size:
            assert (group == g).sum() == 1


def test_a_rows_result_does_not_depend_on_the_other_rows():
    """Group 1's rows, alone in their tile and then behind 130 rows of
    group 0 (another tile, another offset in it): the same bits."""
    k, n = 256, 128
    rng = np.random.RandomState(3)
    mine = jnp.asarray(rng.randn(20, k), jnp.bfloat16)
    others = jnp.asarray(rng.randn(130, k), jnp.bfloat16)
    w = jnp.asarray(rng.randn(2, k, n) * 0.06, jnp.bfloat16)
    pad = jnp.zeros((256 - 20, k), jnp.bfloat16)
    alone = pgm.grouped_matmul(jnp.concatenate([mine, pad]), w,
                               jnp.asarray([0, 20], jnp.int32),
                               interpret=True)[:20]
    crowded = pgm.grouped_matmul(
        jnp.concatenate([others, mine, pad[:106]]), w,
        jnp.asarray([130, 20], jnp.int32), interpret=True)[130:150]
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(crowded))


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


_SIZES = jax.ShapeDtypeStruct((4,), jnp.int32)


@pytest.mark.parametrize('rows,w,sizes,names', [
    (_bf16(64, 256), _bf16(4, 128, 256), _SIZES, 'not rows'),
    (_bf16(64, 128), _bf16(3, 128, 256), _SIZES, 'not rows'),
    (jax.ShapeDtypeStruct((64, 128), jnp.float32), _bf16(4, 128, 256),
     _SIZES, 'not bfloat16'),
    (_bf16(64, 128), jax.ShapeDtypeStruct((4, 128, 256), jnp.float32),
     _SIZES, 'not bfloat16'),
    (_bf16(64, 128), _bf16(4, 128, 256),
     jax.ShapeDtypeStruct((4,), jnp.float32), 'not integers'),
    (_bf16(64, 96), _bf16(4, 96, 256), _SIZES, 'no multiple of 128'),
    (_bf16(64, 128), _bf16(4, 128, 200), _SIZES, 'no multiple of 128'),
    (_bf16(64, 32768), _bf16(4, 32768, 128), _SIZES, 'too wide'),
])
def test_refuses_names_what_it_refuses(rows, w, sizes, names):
    assert names in pgm.refuses(rows, w, sizes)


def test_refuses_a_sharded_trace_and_takes_the_cells_shapes():
    for m, k, n, e in ((1024, 2048, 768, 32), (4096, 768, 2048, 32),
                       (256, 2048, 1024, 64), (4096, 1024, 2048, 64),
                       (512, 6144, 2048, 16), (4096, 2048, 6144, 16)):
        sizes = jax.ShapeDtypeStruct((e,), jnp.int32)
        assert pgm.refuses(_bf16(m, k), _bf16(e, k, n), sizes) is None
        with trace_mesh_scope(object()):
            assert pgm.refuses(_bf16(m, k), _bf16(e, k, n),
                               sizes) == 'a sharded trace'


# -- the op ------------------------------------------------------------------

def _layer(d, f, e, held, dtype, seed=5):
    rng = np.random.RandomState(seed)
    return {'RouterW': jnp.asarray(rng.randn(d, e) * 0.3, jnp.float32),
            'WGate': jnp.asarray(rng.randn(held, d, f) * d ** -0.5, dtype),
            'WUp': jnp.asarray(rng.randn(held, d, f) * d ** -0.5, dtype),
            'WDown': jnp.asarray(rng.randn(held, f, d) * f ** -0.5, dtype)}


def _op(weights, x, offset=0, k=2):
    """moe_topk_ffn's lowering under a Tracer's eyes: (Out, what the op
    told the Tracer)."""
    attrs = {'k': k, 'expert_offset': offset}
    ctx = types.SimpleNamespace(
        attr=lambda name, default=None: attrs.get(name, default),
        abstract=False, tracer=types.SimpleNamespace(lowered_bodies=[]))
    ins = dict({slot: [v] for slot, v in weights.items()}, X=[x])
    return moe_ops._moe_topk_ffn(ctx, ins)['Out'][0], \
        ctx.tracer.lowered_bodies


@pytest.mark.parametrize('d,f,dtype,body', [
    (128, 256, jnp.bfloat16, 'grouped_kernel'),
    (128, 256, jnp.float32, 'ragged_dot'),
    (128, 192, jnp.bfloat16, 'ragged_dot'),       # the down product's K
    (96, 128, jnp.bfloat16, 'ragged_dot'),
])
def test_lowered_bodies_records_one_entry_an_op(d, f, dtype, body):
    x = jnp.asarray(np.random.RandomState(1).randn(6, d), jnp.float32)
    _, told = _op(_layer(d, f, 8, 4, dtype), x, offset=2)
    assert told == [('moe_topk_ffn', body)]


def test_a_sharded_trace_keeps_ragged_dot():
    x = jnp.asarray(np.random.RandomState(1).randn(6, 128), jnp.float32)
    with trace_mesh_scope(object()):
        _, told = _op(_layer(128, 128, 8, 8, jnp.bfloat16), x)
    assert told == [('moe_topk_ffn', 'ragged_dot')]


def test_shape_inference_needs_no_tracer():
    from paddle_tpu.core.registry import ShapeCtx
    op = types.SimpleNamespace(attrs={'k': 2})
    weights = _layer(128, 128, 8, 8, jnp.bfloat16)
    ins = dict({slot: [v] for slot, v in weights.items()},
               X=[jnp.zeros((6, 128), jnp.float32)])
    out = jax.eval_shape(
        lambda: moe_ops._moe_topk_ffn(ShapeCtx(op, None), ins)['Out'][0])
    assert out.shape == (6, 128)


def test_a_cpu_tpu_export_holds_both_bodies_and_runs_on_the_cpu():
    """One exported module for both platforms: the TPU's body is the
    Mosaic kernel, the other lax.ragged_dot, and the cpu runs what it
    ran before there was a kernel."""
    from jax import export
    weights = _layer(128, 256, 8, 4, jnp.bfloat16)
    x = jnp.asarray(np.random.RandomState(2).randn(24, 128), jnp.float32)
    told = []

    def fn(weights, x):
        out, bodies = _op(weights, x, offset=2)
        told.extend(bodies)
        return out

    exported = export.export(jax.jit(fn), platforms=('cpu', 'tpu'))(
        weights, x)
    assert told == [('moe_topk_ffn', 'grouped_kernel')]
    text = exported.mlir_module()
    assert text.count('tpu_custom_call') == 3
    # off the TPU a ragged dot lowers to one masked product over
    # [groups, rows, K]
    assert len(re.findall(r'dot_general[^\n]*\(tensor<4x48x\d+xbf16>, '
                          r'tensor<4x\d+x\d+xbf16>\)', text)) == 3
    got = exported.call(weights, x)
    del told[:]
    with trace_mesh_scope(object()):        # the parent's expression
        want = jax.jit(lambda w, x: fn(w, x))(weights, x)   # traced anew
    assert told == [('moe_topk_ffn', 'ragged_dot')]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(got)).max() > 1e-3


def test_a_module_for_the_cpu_alone_holds_no_kernel():
    weights = _layer(128, 256, 8, 4, jnp.bfloat16)
    x = jnp.zeros((24, 128), jnp.float32)
    text = jax.jit(lambda w, x: _op(w, x, offset=2)[0]).lower(
        weights, x).as_text()
    assert 'tpu_custom_call' not in text and 'tensor<4x48x128xbf16>' in text


def test_the_gradient_is_ragged_dots():
    """d Out / d (X, WGate, WUp, WDown) through the platform switch (its
    custom_vjp) equals the parent's expression's, bit for bit."""
    weights = _layer(128, 256, 8, 4, jnp.bfloat16)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(24, 128), jnp.float32)
    cot = jnp.asarray(rng.randn(24, 128), jnp.float32)

    def loss(x, gate, up, down):
        w = dict(weights, WGate=gate, WUp=up, WDown=down)
        out, told = _op(w, x, offset=2)
        loss.told = told
        return jnp.sum(out * cot)

    args = (x, weights['WGate'], weights['WUp'], weights['WDown'])
    got = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
    assert loss.told == [('moe_topk_ffn', 'grouped_kernel')]
    with trace_mesh_scope(object()):
        want = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
    assert loss.told == [('moe_topk_ffn', 'ragged_dot')]
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.abs(np.asarray(
            w, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_moe_topk_ffn_trains(dtype):
    """The op under the Executor's backward pass, with float32 experts
    (ragged_dot is the one body) and with bfloat16 experts at widths the
    kernel takes (the platform switch and its gradient rule): the loss
    falls."""
    n, d, f, e = 32, 128, 128, 4
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[d], dtype='float32')
        y = fluid.layers.data(name='y', shape=[d], dtype='float32')
        out = fluid.layers.moe_topk_ffn(x, num_experts=e, d_ff=f, k=2,
                                       norm_topk_prob=True, dtype=dtype)
        loss = fluid.layers.mean(fluid.layers.square(out - y))
        fluid.optimizer.SGD(0.5).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(n, d).astype(np.float32),
            'y': rng.randn(n, d).astype(np.float32)}
    vals = [float(np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                     scope=scope)[0]).reshape(-1)[0])
            for _ in range(12)]
    assert np.isfinite(vals).all()
    assert vals[-1] < vals[0], (vals[0], vals[-1])


# -- a decode artifact ---------------------------------------------------------

WIDE = dict(vocab=128, d_model=128, n_head=4, n_layer=2, n_expert=8,
            d_expert=128, top_k=2, max_slots=4, max_cache_len=64,
            block_size=8, chunk_sizes=(8, 16))


def _olmoe_art(path):
    from models.olmoe import build_decode_spec
    from paddle_tpu.inference import export_decode
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(weights_dtype='bfloat16',
                                 kv_cache_dtype='bfloat16', **WIDE)
        spec['startup'].random_seed = 3
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        export_decode(spec, str(path), scope=scope, precompile=False)
    return str(path)


def test_a_decode_artifact_says_which_body_its_routed_layers_hold(
        tmp_path, monkeypatch):
    """OLMoE at widths the kernel takes (hidden and expert width 128,
    bfloat16 weights): every program's signature entry says
    'grouped_kernel' for its two routed layers — the body the module holds
    for a TPU — the predictor on the cpu reads them as 'ragged_dot', and
    serves the logits of an artifact exported with ragged_dot alone (the
    parent's programs), bit for bit."""
    import json
    import os
    from paddle_tpu.inference import DecodingPredictor, decoding
    from paddle_tpu.testing.decode_logits import served_logits
    art = _olmoe_art(tmp_path / 'art')
    monkeypatch.setattr(pgm, 'refuses', lambda *_: 'the test')
    plain = _olmoe_art(tmp_path / 'plain')
    monkeypatch.undo()

    def sig(art):
        with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
            return json.load(f)

    for art_, body in ((art, 'grouped_kernel'), (plain, 'ragged_dot')):
        held = {'moe_topk_ffn': {body: 2}}
        assert sig(art_)['step']['experts'] == held
        assert all(e['experts'] == held
                   for e in sig(art_)['chunk'].values())
        assert 'moe_topk_ffn' not in sig(art_)['step']['attention']
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, WIDE['vocab'], n) for n in (3, 21, 40, 17)]
    served = []
    for art_ in (art, plain):
        with DecodingPredictor(art_) as pred:
            assert pred.expert_bodies['step'] == {
                'moe_topk_ffn': {'ragged_dot': 2}}
            assert set(pred.expert_bodies) == set(pred.attention_bodies)
            served.append(served_logits(pred, prompts, 12))
    (tokens, logits), (tokens_, logits_) = served
    assert [list(t) for t in tokens] == [list(t) for t in tokens_]
    for got, want in zip(logits, logits_):
        np.testing.assert_array_equal(got, want)
