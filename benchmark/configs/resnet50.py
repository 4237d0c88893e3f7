"""resnet50: everything the train_loop runner asks a configuration for —
how the file becomes a program, what it is fed, what the plain reference
(benchmark/reference/resnet50.py) says the same feed gives, and what a
step has to cost at the least."""
from __future__ import annotations

BOUND = 'compute'     # which roofline bounds the step


def build(cfg):
    """(main program, startup program, {name: variable}) for cfg, through
    the repo's own builder and bf16 policy. 'loss' is what the loop
    fetches; every name configs/resnet50.json "verify.compare" lists is a
    key too."""
    import paddle_tpu as fluid
    from models.resnet import build_train_net
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = int(cfg['weights_seed'])
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, loss, _ = build_train_net(
            dshape=tuple(cfg['image_shape']), class_dim=int(cfg['class_dim']),
            depth=int(cfg['depth']), imagenet=True,
            s2d_stem=cfg['stem'] == 'space_to_depth', lr=float(cfg['lr']))
    if cfg['compute_dtype'] == 'bfloat16':
        fluid.contrib.mixed_precision.enable_bf16(main)
    block = main.global_block()
    xent, = [op for op in block.ops
             if op.type == 'softmax_with_cross_entropy']
    return main, startup, {'loss': loss,
                           'logits': block.var(xent.input('Logits')[0])}


def make_feed(cfg, batch, seed, shard=None):
    """{feed name: device array} for one batch: images (standard normal)
    and labels (uniform) made on the device in one jitted call from the
    seed. `shard(ndim)` gives the sharding a feed of that rank is made
    under (the runner passes the mesh's batch sharding on several chips,
    so that no step copies the feed); None = the default device."""
    import jax
    import jax.numpy as jnp
    shape = (batch,) + tuple(cfg['image_shape'])
    classes = int(cfg['class_dim'])
    shardings = (shard(len(shape)), shard(2)) if shard else None

    def make(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(k1, shape, jnp.float32),
                jax.random.randint(k2, (batch, 1), 0, classes, jnp.int32))
    img, lab = jax.jit(make, out_shardings=shardings)(jax.random.key(seed))
    return {'data': img, 'label': lab}


def reference(cfg, weights, feed):
    """{name: float32 array} the plain reference gives for this feed with
    these weights (both as host arrays), under the names build() fetches."""
    import numpy as np
    from ..reference import resnet50 as ref
    lg = ref.logits_f32(weights, feed['data'], depth=int(cfg['depth']),
                        s2d_stem=cfg['stem'] == 'space_to_depth')
    return {'logits': np.asarray(lg),
            'loss': np.asarray(ref.xent(lg, feed['label']))}


def flops_per_sample(cfg):
    """FLOPs one trained sample requires (forward + backward)."""
    return float(cfg['flops_per_sample'])


def step_floor_seconds(cfg, peaks, batch_per_chip):
    """The least time one chip could take for its share of a step."""
    return flops_per_sample(cfg) * batch_per_chip / peaks['bf16_flops_per_s']
