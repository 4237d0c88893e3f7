"""A second train configuration, for the test that train_loop runs it
without being touched: a two-layer regression net, float32. It brings what
benchmark/README.md says a train configuration's module brings: build,
make_feed, reference, flops_per_sample, step_floor_seconds, BOUND."""
from __future__ import annotations

import numpy as np

BOUND = 'compute'
CFG = {
    'name': 'second_config', 'model': 'second_config', 'weights_seed': 5,
    'features': 12, 'hidden': 16, 'lr': 0.05,
    'verify': {'batch': 6, 'compare': {
        'loss': {'kind': 'abs', 'tol': 1e-4},
        'pred': {'kind': 'rel_l2', 'tol': 1e-4}}},
}
TRAFFIC = {'runner': 'train_loop', 'executor': 'single', 'batch_per_chip': 4,
           'group_steps': 2, 'groups_in_flight': 2, 'warmup_groups': 1,
           'trace_seconds': 1.0}


def build(cfg):
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = int(cfg['weights_seed'])
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[int(cfg['features'])],
                              dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(x, int(cfg['hidden']), act='tanh')
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(float(cfg['lr'])).minimize(loss)
    return main, startup, {'loss': loss, 'pred': pred}


def make_feed(cfg, batch, seed, shard=None):
    import jax
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {'x': jax.random.normal(k1, (batch, int(cfg['features']))),
            'y': jax.random.normal(k2, (batch, 1))}


def reference(cfg, weights, feed):
    h = np.tanh(feed['x'] @ weights['fc_0.w_0'] + weights['fc_0.b_0'])
    pred = h @ weights['fc_1.w_0'] + weights['fc_1.b_0']
    return {'pred': pred, 'loss': np.mean(np.square(pred - feed['y']))}


def flops_per_sample(cfg):
    return 6.0 * (int(cfg['features']) + 1) * int(cfg['hidden'])


def step_floor_seconds(cfg, peaks, batch_per_chip):
    return flops_per_sample(cfg) * batch_per_chip / peaks['bf16_flops_per_s']
