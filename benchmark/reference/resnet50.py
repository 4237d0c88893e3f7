"""Plain reference for the resnet50 configuration: forward pass and loss in
float32 jax.numpy at 'highest' matmul precision. No kernels, no passes, no
bf16, nothing of paddle_tpu: weights come in as a dict of arrays read from
the program's scope by name.

Follows He et al. 2015 (arXiv:1512.03385), Table 1, 50-layer, in the
variant the Fluid 1.2 benchmark trains (benchmark/fluid/models/resnet.py):
bottleneck blocks [3, 4, 6, 3], the stride of 2 in the 3x3 conv of each
later stage's first block, projection shortcuts where the shape changes,
batch norm after every conv, no conv bias.

Departures from the paper, each as the program under test has it:
  * space-to-depth stem for 224x224 inputs: the 7x7/2 conv over 3 channels
    is a 4x4/1 VALID conv over the 12 channels of the 2x2 space-to-depth of
    the image padded by 3 (models/resnet.py:_s2d_stem). Same receptive
    field family and output size; its own weights.
  * batch norm uses the BATCH's statistics (training mode), biased
    variance, epsilon 1e-5.
  * loss: mean softmax cross-entropy over the batch.

Tolerance against the bf16 program: see configs/resnet50.json "verify".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
_EPS = 1e-5
_HI = lax.Precision.HIGHEST


class _Names(object):
    """Parameter names in the order models/resnet.py creates its layers
    under fluid.unique_name.guard(): conv2d_<i>.w_0, batch_norm_<i>.w_0 /
    .b_0, fc_0.w_0 / .b_0."""

    def __init__(self, weights):
        self.w = weights
        self.i = 0

    def conv_bn(self):
        i = self.i
        self.i += 1
        return (self.w['conv2d_%d.w_0' % i],
                self.w['batch_norm_%d.w_0' % i],
                self.w['batch_norm_%d.b_0' % i])


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, jnp.asarray(w, jnp.float32), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'), precision=_HI)


def _bn(x, scale, bias):
    m = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=(0, 2, 3), keepdims=True)
    y = (x - m) * lax.rsqrt(v + _EPS)
    return (y * jnp.asarray(scale, jnp.float32).reshape(1, -1, 1, 1)
            + jnp.asarray(bias, jnp.float32).reshape(1, -1, 1, 1))


def _conv_bn(x, names, stride, pad, relu=True):
    w, s, b = names.conv_bn()
    y = _bn(_conv(x, w, stride, pad), s, b)
    return jax.nn.relu(y) if relu else y


def _bottleneck(x, names, width, stride):
    if x.shape[1] != width * 4 or stride != 1:
        short = _conv_bn(x, names, stride, 0, relu=False)
    else:
        short = x
    y = _conv_bn(x, names, 1, 0)
    y = _conv_bn(y, names, stride, 1)
    y = _conv_bn(y, names, 1, 0, relu=False)
    return jax.nn.relu(short + y)


def _space_to_depth(x):
    x = jnp.pad(x, ((0, 0), (0, 0), (3, 3), (3, 3)))
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // 2, 2, w // 2, 2).transpose(0, 1, 3, 5, 2, 4)
    return x.reshape(n, c * 4, h // 2, w // 2)


def logits(weights, images, depth=50, s2d_stem=True):
    names = _Names(weights)
    x = jnp.asarray(images, jnp.float32)
    if s2d_stem and x.shape[2] == 224 and x.shape[3] == 224:
        x = _conv_bn(_space_to_depth(x), names, 1, 0)
    else:
        x = _conv_bn(x, names, 2, 3)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, blocks in enumerate(_STAGES[depth]):
        for i in range(blocks):
            x = _bottleneck(x, names, 64 * 2 ** stage,
                            2 if i == 0 and stage != 0 else 1)
    x = jnp.mean(x, axis=(2, 3))
    return (jnp.dot(x, jnp.asarray(weights['fc_0.w_0'], jnp.float32),
                    precision=_HI)
            + jnp.asarray(weights['fc_0.b_0'], jnp.float32))


@functools.partial(jax.jit, static_argnames=('depth', 's2d_stem'))
def _logits_jit(weights, images, depth, s2d_stem):
    return logits(weights, images, depth=depth, s2d_stem=s2d_stem)


def logits_f32(weights, images, depth=50, s2d_stem=True):
    """[batch, classes] float32 logits at 'highest' matmul precision."""
    with jax.default_matmul_precision('highest'):
        return _logits_jit(weights, images, depth=depth, s2d_stem=s2d_stem)


@jax.jit
def _xent(lg, labels):
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=1)
    return -jnp.mean(picked)


def xent(lg, labels):
    """Mean softmax cross-entropy of the batch, float32."""
    return _xent(jnp.asarray(lg, jnp.float32), jnp.asarray(labels, jnp.int32))
