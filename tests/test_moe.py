"""Switch-MoE FFN (expert parallelism over the mesh 'ep' axis): numeric
parity vs a numpy reference, capacity-drop semantics, training, and
ep-sharded execution matching single-device outputs.

TPU-native extension (the reference has no MoE); GShard/Switch einsum
dispatch (ops/moe_ops.py) keeps every shape static so GSPMD inserts the
all-to-alls.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.compiler import CompiledProgram


def _np_switch_moe(x, gw, w1, w2, cap_factor=1.25):
    n, d = x.shape
    e = gw.shape[1]
    cap = max(1, int(np.ceil(n * cap_factor / e)))
    logits = x @ gw
    z = logits - logits.max(-1, keepdims=True)
    gates = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    idx = gates.argmax(-1)
    out = np.zeros_like(x)
    counts = np.zeros(e, np.int64)
    for i in range(n):
        ex = idx[i]
        if counts[ex] >= cap:
            counts[ex] += 1
            continue  # dropped token: zero output
        counts[ex] += 1
        h = np.maximum(x[i] @ w1[ex], 0.0)
        out[i] = (h @ w2[ex]) * gates[i, ex]
    return out


def _build(n_tok, d, e, f, cap=1.25, seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[d], dtype='float32')
        out, aux = fluid.layers.switch_moe_ffn(x, num_experts=e, d_ff=f,
                                               capacity_factor=cap)
    return main, startup, out, aux


def test_switch_moe_matches_numpy():
    n, d, e, f = 32, 8, 4, 16
    main, startup, out, aux = _build(n, d, e, f)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    from paddle_tpu.core.scope import global_scope
    params = main.global_block().all_parameters()
    gw, w1, w2 = [np.asarray(global_scope().get(p.name)) for p in params]
    rng = np.random.RandomState(0)
    x = rng.randn(n, d).astype(np.float32)
    got, aux_v = exe.run(main, feed={'x': x}, fetch_list=[out, aux])
    want = _np_switch_moe(x, gw, w1, w2)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert np.isfinite(float(np.asarray(aux_v).reshape(-1)[0]))


def test_capacity_drops_overflow_tokens():
    # capacity_factor so small every expert takes exactly 1 token
    n, d, e, f = 8, 4, 4, 8
    main, startup, out, aux = _build(n, d, e, f, cap=0.5)  # cap = 1
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    from paddle_tpu.core.scope import global_scope
    params = main.global_block().all_parameters()
    gw, w1, w2 = [np.asarray(global_scope().get(p.name)) for p in params]
    rng = np.random.RandomState(1)
    x = rng.randn(n, d).astype(np.float32)
    got, = exe.run(main, feed={'x': x}, fetch_list=[out])
    want = _np_switch_moe(x, gw, w1, w2, cap_factor=0.5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    # with 8 tokens / 4 experts / capacity 1, some rows MUST be dropped
    assert (np.abs(want).sum(axis=1) == 0).any()


def test_moe_trains_with_aux_loss():
    n, d, e, f = 16, 8, 4, 16
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[d], dtype='float32')
        y = fluid.layers.data(name='y', shape=[d], dtype='float32')
        out, aux = fluid.layers.switch_moe_ffn(x, num_experts=e, d_ff=f)
        mse = fluid.layers.mean(fluid.layers.square(out - y))
        loss = mse + 0.01 * aux
        fluid.optimizer.Adam(1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(n, d).astype(np.float32),
            'y': rng.randn(n, d).astype(np.float32)}
    vals = []
    for _ in range(25):
        l, = exe.run(main, feed=feed, fetch_list=[loss])
        vals.append(float(np.asarray(l).reshape(-1)[0]))
    assert np.isfinite(vals).all()
    assert vals[-1] < vals[0], (vals[0], vals[-1])


def test_expert_parallel_matches_single_device():
    n, d, e, f = 32, 8, 4, 16
    main, startup, out, aux = _build(n, d, e, f, seed=11)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(2)
    x = rng.randn(n, d).astype(np.float32)
    single, = exe.run(main, feed={'x': x}, fetch_list=[out])

    main2, startup2, out2, aux2 = _build(n, d, e, f, seed=11)
    mesh = make_mesh(axes={'dp': 2, 'ep': 4})
    prog = CompiledProgram(main2).with_data_parallel(mesh=mesh)
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(startup2)
    sharded, = exe2.run(prog, feed={'x': x}, fetch_list=[out2])
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               rtol=1e-4, atol=1e-4)


# -- moe_topk_ffn as one chip's share of an expert-parallel layer (ISSUE 30) --

def _topk_ffn(x, weights, k, held, offset, **attrs):
    """The op's lowering on host arrays: the experts [offset, offset +
    held) of `weights` (router and bias whole)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    ins = {'X': [jnp.asarray(x)], 'RouterW': [jnp.asarray(weights['router'])],
           'RouterBias': [jnp.asarray(weights['bias'])]}
    for slot, name in (('WGate', 'gate'), ('WUp', 'up'), ('WDown', 'down')):
        ins[slot] = [jnp.asarray(weights[name][offset:offset + held])]
    if attrs.pop('no_bias', False):
        del ins['RouterBias']
    attrs = dict({'k': k, 'scoring': 'sigmoid', 'norm_topk_prob': True,
                  'routed_scaling_factor': 2.5, 'expert_offset': offset},
                 **attrs)

    class Ctx(object):
        def attr(self, name, default=None):
            return attrs.get(name, default)
    return np.asarray(moe_ops._moe_topk_ffn(Ctx(), ins)['Out'][0])


def _share_weights(d=32, e=16, f=24, seed=11):
    rng = np.random.RandomState(seed)
    return {'router': rng.randn(d, e).astype(np.float32) * 0.4,
            'bias': rng.randn(e).astype(np.float32) * 0.3,
            'gate': rng.randn(e, d, f).astype(np.float32) * 0.2,
            'up': rng.randn(e, d, f).astype(np.float32) * 0.2,
            'down': rng.randn(e, f, d).astype(np.float32) * 0.2,
            'shared_gate': rng.randn(d, f).astype(np.float32) * 0.2,
            'shared_up': rng.randn(d, f).astype(np.float32) * 0.2,
            'shared_down': rng.randn(f, d).astype(np.float32) * 0.2}


def _uncut_layer(x, w, k, **over):
    """The reference's whole layer: every expert held, the shared expert
    once (benchmark/reference/exaone_moe.py feed_forward)."""
    import jax
    from benchmark.reference import exaone_moe as ref
    weights = {'l1_moe_router': w['router'], 'l1_moe_router_bias': w['bias'],
               'l1_moe_gate': w['gate'], 'l1_moe_up': w['up'],
               'l1_moe_down': w['down'], 'l1_shared_gate_w': w['shared_gate'],
               'l1_shared_up_w': w['shared_up'],
               'l1_shared_down_w': w['shared_down']}
    kw = dict(dict(first_dense=1, top_k=k, expert_offset=0, scaling=2.5,
                   norm_topk_prob=True), **over)
    with jax.default_matmul_precision('highest'):
        return np.asarray(ref.feed_forward(x, weights, 1, **kw))


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """THE SHARE TEST: 16 experts over 8 chips, 2 a chip. The routed
    parts the 8 offsets give, plus the shared expert counted once, are
    the uncut reference's whole layer — and one share alone is not."""
    import jax
    from benchmark.reference import exaone_moe as ref
    w = _share_weights()
    x = np.random.RandomState(12).randn(40, 32).astype(np.float32)
    with jax.default_matmul_precision('highest'):
        shared = np.asarray(ref.ffn(x, w['shared_gate'], w['shared_up'],
                                    w['shared_down']))
    parts = [_topk_ffn(x, w, 4, held=2, offset=2 * c) for c in range(8)]
    want = _uncut_layer(x, w, 4)
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-6)
    assert np.abs(parts[0] + shared - want).max() > 1e-2
    # a share's rows whose experts all live elsewhere are exactly zero
    import jax.numpy as jnp
    logits = x @ w['router']
    picked = np.argsort(-(1 / (1 + np.exp(-logits)) + w['bias']),
                        axis=-1, kind='stable')[:, :4]
    elsewhere = ~((picked >= 6) & (picked < 8)).any(-1)
    assert elsewhere.any() and not elsewhere.all()
    assert (parts[3][elsewhere] == 0).all()
    assert (np.abs(parts[3][~elsewhere]).max(-1) > 0).all()
    # every expert held at offset 0 is the whole routed part
    np.testing.assert_allclose(_topk_ffn(x, w, 4, held=16, offset=0),
                               sum(parts), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize('dropped', ['bias', 'scaling', 'renormalisation',
                                     'sigmoid'])
def test_a_router_without_one_of_its_parts_is_another_layer(dropped):
    """Each attribute is load-bearing: without the selection bias, the
    scaling, the renormalisation, or with softmax scores, the op no
    longer gives the reference's layer."""
    w = _share_weights()
    x = np.random.RandomState(12).randn(40, 32).astype(np.float32)
    over = {'bias': {'no_bias': True},
            'scaling': {'routed_scaling_factor': 1.0},
            'renormalisation': {'norm_topk_prob': False},
            'sigmoid': {'scoring': 'softmax'}}[dropped]
    want = _uncut_layer(x, w, 4, shared=False)
    np.testing.assert_allclose(_topk_ffn(x, w, 4, held=16, offset=0), want,
                               rtol=2e-5, atol=2e-6)
    got = _topk_ffn(x, w, 4, held=16, offset=0, **over)
    assert np.abs(got - want).max() > 1e-2


def test_the_bias_moves_the_choice_and_not_the_weights():
    """A bias that lifts expert 5 into every token's choice leaves the
    gates the chosen scores' own: the layer differs from the unbiased one
    by the swap alone, and equals the reference with the same bias."""
    w = _share_weights()
    w['bias'] = np.zeros(16, np.float32)
    w['bias'][5] = 10.0
    x = np.random.RandomState(13).randn(12, 32).astype(np.float32)
    np.testing.assert_allclose(_topk_ffn(x, w, 4, held=16, offset=0),
                               _uncut_layer(x, w, 4, shared=False),
                               rtol=2e-5, atol=2e-6)
    only5 = _topk_ffn(x, w, 4, held=1, offset=5)
    assert (np.abs(only5).max(-1) > 0).all()     # everyone routed to it
