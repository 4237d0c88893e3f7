"""Math / elementwise / activation / reduction op lowerings.

Each op here replaces a C++/CUDA kernel pair from the reference
(paddle/fluid/operators/*_op.{cc,cu}, elementwise/, reduce_ops/,
activation_op.cc) with a single JAX lowering; XLA supplies both the TPU and
CPU kernels, the fusion the reference got from fused_* ops, and — via the
generic vjp path — the grad kernels.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core.registry import register
from ..core import amp


def X(ins, slot='X'):
    return ins[slot][0]


# ---------------------------------------------------------------------------
# elementwise binary ops with Paddle's axis-broadcast semantics
# (ref: operators/elementwise/elementwise_op_function.h)
# ---------------------------------------------------------------------------
def _bcast_y(x, y, axis):
    if x.ndim == y.ndim:
        return y
    if axis == -1:
        axis = x.ndim - y.ndim
    shape = [1] * axis + list(y.shape)
    shape += [1] * (x.ndim - len(shape))
    return y.reshape(shape)


def _elementwise(name, fn):
    @register(name)
    def _lower(ctx, ins, _fn=fn):
        x, y = ins['X'][0], ins['Y'][0]
        y = _bcast_y(x, y, ctx.attr('axis', -1))
        x, y = amp.unify(x, y)
        out = _fn(x, y)
        scale = ctx.attr('scale', None)  # fused scale (rare attr)
        if scale not in (None, 1.0):
            out = out * scale
        return {'Out': [out]}


_elementwise('elementwise_add', jnp.add)
_elementwise('elementwise_sub', jnp.subtract)
_elementwise('elementwise_mul', jnp.multiply)
_elementwise('elementwise_div', jnp.divide)
_elementwise('elementwise_max', jnp.maximum)
_elementwise('elementwise_min', jnp.minimum)
_elementwise('elementwise_pow', jnp.power)
_elementwise('elementwise_mod', jnp.mod)
_elementwise('elementwise_floordiv', jnp.floor_divide)


# ---------------------------------------------------------------------------
# activations (ref: operators/activation_op.cc — ~25 kernels)
# ---------------------------------------------------------------------------
def _unary(name, fn):
    @register(name)
    def _lower(ctx, ins, _fn=fn):
        return {'Out': [_fn(X(ins))]}


_unary('relu', jax.nn.relu)
_unary('sigmoid', jax.nn.sigmoid)
_unary('logsigmoid', jax.nn.log_sigmoid)
_unary('tanh', jnp.tanh)
_unary('tanh_shrink', lambda x: x - jnp.tanh(x))
_unary('exp', jnp.exp)
_unary('sqrt', jnp.sqrt)
_unary('rsqrt', jax.lax.rsqrt)
_unary('abs', jnp.abs)
_unary('ceil', jnp.ceil)
_unary('floor', jnp.floor)
_unary('cos', jnp.cos)
_unary('sin', jnp.sin)
_unary('round', jnp.round)
_unary('reciprocal', jnp.reciprocal)
_unary('square', jnp.square)
_unary('softplus', jax.nn.softplus)
_unary('softsign', jax.nn.soft_sign)
_unary('log', jnp.log)
_unary('gelu', jax.nn.gelu)
_unary('erf', jax.scipy.special.erf)
_unary('sign', jnp.sign)


@register('leaky_relu')
def _leaky_relu(ctx, ins):
    a = ctx.attr('alpha', 0.02)
    x = X(ins)
    return {'Out': [jnp.where(x >= 0, x, a * x)]}


@register('elu')
def _elu(ctx, ins):
    return {'Out': [jax.nn.elu(X(ins), alpha=ctx.attr('alpha', 1.0))]}


@register('relu6')
def _relu6(ctx, ins):
    t = ctx.attr('threshold', 6.0)
    return {'Out': [jnp.clip(X(ins), 0.0, t)]}


@register('brelu')
def _brelu(ctx, ins):
    return {'Out': [jnp.clip(X(ins), ctx.attr('t_min', 0.0),
                             ctx.attr('t_max', 24.0))]}


@register('soft_relu')
def _soft_relu(ctx, ins):
    t = ctx.attr('threshold', 40.0)
    x = jnp.clip(X(ins), -t, t)
    return {'Out': [jnp.log1p(jnp.exp(x))]}


@register('stanh')
def _stanh(ctx, ins):
    a = ctx.attr('scale_a', 2.0 / 3.0)
    b = ctx.attr('scale_b', 1.7159)
    return {'Out': [b * jnp.tanh(a * X(ins))]}


@register('hard_sigmoid')
def _hard_sigmoid(ctx, ins):
    s = ctx.attr('slope', 0.2)
    o = ctx.attr('offset', 0.5)
    return {'Out': [jnp.clip(s * X(ins) + o, 0.0, 1.0)]}


@register('hard_shrink')
def _hard_shrink(ctx, ins):
    t = ctx.attr('threshold', 0.5)
    x = X(ins)
    return {'Out': [jnp.where(jnp.abs(x) > t, x, 0.0)]}


@register('softshrink')
def _softshrink(ctx, ins):
    lam = ctx.attr('lambda', 0.5)
    x = X(ins)
    return {'Out': [jnp.where(x > lam, x - lam,
                              jnp.where(x < -lam, x + lam, 0.0))]}


@register('thresholded_relu')
def _thresholded_relu(ctx, ins):
    t = ctx.attr('threshold', 1.0)
    x = X(ins)
    return {'Out': [jnp.where(x > t, x, 0.0)]}


@register('swish')
def _swish(ctx, ins):
    b = ctx.attr('beta', 1.0)
    x = X(ins)
    return {'Out': [x * jax.nn.sigmoid(b * x)]}


@register('selu')
def _selu(ctx, ins):
    scale = ctx.attr('scale', 1.0507009873554805)
    alpha = ctx.attr('alpha', 1.6732632423543772)
    x = X(ins)
    return {'Out': [scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))]}


@register('prelu')
def _prelu(ctx, ins):
    x = X(ins)
    alpha = ins['Alpha'][0]
    mode = ctx.attr('mode', 'all')
    if mode == 'all':
        a = alpha.reshape(())
    elif mode == 'channel':
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:  # element
        a = alpha.reshape((1,) + x.shape[1:])
    return {'Out': [jnp.where(x > 0, x, a * x)]}


@register('pow')
def _pow(ctx, ins):
    return {'Out': [jnp.power(X(ins), ctx.attr('factor', 1.0))]}


@register('clip')
def _clip(ctx, ins):
    return {'Out': [jnp.clip(X(ins), ctx.attr('min'), ctx.attr('max'))]}


@register('clip_by_norm')
def _clip_by_norm(ctx, ins):
    x = X(ins)
    m = ctx.attr('max_norm')
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    return {'Out': [jnp.where(norm > m, x * (m / norm), x)]}


# ---------------------------------------------------------------------------
# matmul family (ref: operators/mul_op.cc, matmul_op.cc) — the MXU path.
# ---------------------------------------------------------------------------
def _flatten2(x, num_col_dims):
    lead = int(np.prod(x.shape[:num_col_dims])) if num_col_dims else 1
    return x.reshape(lead, -1)


@register('mul')
def _mul(ctx, ins):
    x, y = ins['X'][0], ins['Y'][0]
    xn = ctx.attr('x_num_col_dims', 1)
    yn = ctx.attr('y_num_col_dims', 1)
    x2 = _flatten2(x, xn)
    y2 = y.reshape(int(np.prod(y.shape[:yn])), -1)
    if x2.dtype == jnp.float32 and y2.dtype == jnp.bfloat16:
        # a weight STORED in bfloat16 under a float32 activation: the
        # MXU's native product (bf16 x bf16, float32 accumulation) on the
        # stored bytes, not an upcast copy of the weight
        x2 = x2.astype(jnp.bfloat16)
    out = amp.matmul(x2, y2, preferred_element_type=x.dtype)
    out_shape = x.shape[:xn] + y.shape[yn:]
    return {'Out': [out.reshape(out_shape)]}


@register('matmul')
def _matmul(ctx, ins):
    x, y = ins['X'][0], ins['Y'][0]
    tx, ty = ctx.attr('transpose_X', False), ctx.attr('transpose_Y', False)
    alpha = ctx.attr('alpha', 1.0)
    squeeze_out = []
    if x.ndim == 1:
        x = x[None, :]
        squeeze_out.append(-2)
    if y.ndim == 1:
        y = y[:, None]
        squeeze_out.append(-1)
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    if x.dtype == jnp.float32 and y.dtype == jnp.bfloat16:
        # mul's rule: a weight stored in bfloat16 under a float32
        # activation multiplies as the MXU does, on the stored bytes
        out = amp.matmul(x.astype(jnp.bfloat16), y,
                         preferred_element_type=jnp.float32)
    else:
        out = amp.matmul(x, y)
    if alpha != 1.0:
        out = out * alpha
    if squeeze_out:
        out = jnp.squeeze(out, axis=tuple(squeeze_out))
    return {'Out': [out]}


@register('bilinear_tensor_product')
def _bilinear_tensor_product(ctx, ins):
    x, y, w = ins['X'][0], ins['Y'][0], ins['Weight'][0]
    # w: [out, dx, dy]
    out = jnp.einsum('bi,oij,bj->bo', x, w, y)
    if ins.get('Bias') and ins['Bias'][0] is not None:
        out = out + ins['Bias'][0]
    return {'Out': [out]}


# ---------------------------------------------------------------------------
# reductions (ref: operators/reduce_ops/)
# ---------------------------------------------------------------------------
def _reduce(name, fn):
    @register(name)
    def _lower(ctx, ins, _fn=fn):
        x = X(ins)
        if ctx.attr('reduce_all', False):
            axes = None
        else:
            dims = ctx.attr('dim', [0])
            if isinstance(dims, int):
                dims = [dims]
            axes = tuple(d % x.ndim for d in dims)
        out = _fn(x, axis=axes, keepdims=ctx.attr('keep_dim', False))
        return {'Out': [out]}


_reduce('reduce_sum', jnp.sum)
_reduce('reduce_mean', jnp.mean)
_reduce('reduce_max', jnp.max)
_reduce('reduce_min', jnp.min)
_reduce('reduce_prod', jnp.prod)


@register('mean')
def _mean(ctx, ins):
    # reference mean_op emits a {1}-shaped tensor (mean_op.cc InferShape);
    # loss reductions accumulate in f32 even when activations flow bf16
    return {'Out': [jnp.mean(amp.promote_f32(X(ins))).reshape(1)]}


@register('scale')
def _scale(ctx, ins):
    x = X(ins)
    s = ctx.attr('scale', 1.0)
    b = ctx.attr('bias', 0.0)
    if 'ScaleTensor' in ins and ins['ScaleTensor'] and ins['ScaleTensor'][0] is not None:
        s = ins['ScaleTensor'][0]
    if ctx.attr('bias_after_scale', True):
        return {'Out': [x * s + b]}
    return {'Out': [(x + b) * s]}


@register('sum')
def _sum(ctx, ins):
    from ..core.selected_rows import SelectedRowsVal, concat_rows
    xs = [x for x in ins['X'] if x is not None]
    sparse = [x for x in xs if isinstance(x, SelectedRowsVal)]
    if sparse:
        # sparse grad accumulation (ref selected_rows_functor Add): all
        # sparse -> concatenated rows (addition for scatter consumers);
        # mixed -> densify the sparse parts
        if len(sparse) == len(xs):
            return {'Out': [concat_rows(xs)]}
        xs = [x.to_dense() if isinstance(x, SelectedRowsVal) else x
              for x in xs]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {'Out': [out]}


@register('cast')
def _cast(ctx, ins):
    from ..framework import runtime_dtype
    return {'Out': [X(ins).astype(runtime_dtype(ctx.attr('out_dtype')))]}


# ---------------------------------------------------------------------------
# softmax / losses (ref: operators/softmax_op.cc, cross_entropy_op.cc,
# softmax_with_cross_entropy_op.cc)
# ---------------------------------------------------------------------------
@register('softmax')
def _softmax(ctx, ins):
    x = X(ins)
    axis = ctx.attr('axis', -1)
    # exp/sum in f32 for bf16 activations, back to the compute dtype after
    return {'Out': [amp.restore(jax.nn.softmax(amp.promote_f32(x),
                                               axis=axis), x)]}


@register('log_softmax')
def _log_softmax(ctx, ins):
    x = X(ins)
    out = jax.nn.log_softmax(amp.promote_f32(x), axis=ctx.attr('axis', -1))
    return {'Out': [amp.restore(out, x)]}


@register('cross_entropy')
def _cross_entropy(ctx, ins):
    x = amp.promote_f32(X(ins))  # probabilities [N, C] (or [..., C])
    label = ins['Label'][0]
    logp = jnp.log(jnp.clip(x, 1e-20))
    if ctx.attr('soft_label', False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        ignore = ctx.attr('ignore_index', -100)
        lab = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        picked = jnp.take_along_axis(logp, lab[..., None].astype(jnp.int32),
                                     axis=-1)
        loss = -picked
        loss = jnp.where((lab == ignore)[..., None], 0.0, loss)
    return {'Y': [loss]}


@register('softmax_with_cross_entropy')
def _softmax_with_cross_entropy(ctx, ins):
    logits = amp.promote_f32(ins['Logits'][0])  # loss math stays f32
    label = ins['Label'][0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    if ctx.attr('soft_label', False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        ignore = ctx.attr('ignore_index', -100)
        lab = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        picked = jnp.take_along_axis(logp, lab[..., None].astype(jnp.int32),
                                     axis=-1)
        loss = jnp.where((lab == ignore)[..., None], 0.0, -picked)
    return {'Softmax': [amp.restore(jnp.exp(logp), ins['Logits'][0])],
            'Loss': [loss]}


@register('square_error_cost')
def _square_error_cost(ctx, ins):
    x, y = ins['X'][0], ins['Y'][0]
    return {'Out': [jnp.square(x - y)]}


@register('huber_loss')
def _huber_loss(ctx, ins):
    x, y = ins['X'][0], ins['Y'][0]
    d = ctx.attr('delta', 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= d, 0.5 * r * r, d * (a - 0.5 * d))
    return {'Out': [loss], 'Residual': [r]}


@register('hinge_loss')
def _hinge_loss(ctx, ins):
    """max(0, 1 - logits * (2*label - 1)) with {0,1} labels
    (ref: operators/hinge_loss_op.cc)."""
    x, y = ins['Logits'][0], ins['Labels'][0]
    return {'Loss': [jnp.maximum(0.0, 1.0 - x * (2.0 * y - 1.0))]}


@register('modified_huber_loss')
def _modified_huber_loss(ctx, ins):
    """z = x*(2y-1); loss = -4z for z<-1, (1-z)^2 for z<1, else 0
    (ref: operators/modified_huber_loss_op.cc)."""
    x, y = ins['X'][0], ins['Y'][0]
    z = x * (2.0 * y - 1.0)
    loss = jnp.where(z < -1.0, -4.0 * z,
                     jnp.square(jnp.maximum(0.0, 1.0 - z)))
    return {'Out': [loss.reshape(-1, 1)], 'IntermediateVal': [z]}


@register('squared_l2_distance')
def _squared_l2_distance(ctx, ins):
    """Row-wise ||x - y||^2; y may have one row broadcast over the batch
    (ref: operators/squared_l2_distance_op.cc)."""
    x, y = ins['X'][0], ins['Y'][0]
    x2 = x.reshape(x.shape[0], -1)
    y2 = y.reshape(y.shape[0], -1)
    sub = x2 - y2  # broadcasts when y has a single row
    return {'sub_result': [sub],
            'Out': [jnp.sum(jnp.square(sub), axis=1, keepdims=True)]}


@register('l1_norm')
def _l1_norm(ctx, ins):
    """Scalar sum of absolute values (ref: operators/l1_norm_op.cc)."""
    return {'Out': [jnp.sum(jnp.abs(X(ins))).reshape(1)]}


@register('smooth_l1_loss')
def _smooth_l1_loss(ctx, ins):
    x, y = ins['X'][0], ins['Y'][0]
    sigma = ctx.attr('sigma', 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ins.get('InsideWeight') and ins['InsideWeight'][0] is not None:
        diff = diff * ins['InsideWeight'][0]
    a = jnp.abs(diff)
    val = jnp.where(a < 1.0 / s2, 0.5 * s2 * diff * diff, a - 0.5 / s2)
    if ins.get('OutsideWeight') and ins['OutsideWeight'][0] is not None:
        val = val * ins['OutsideWeight'][0]
    loss = jnp.sum(val.reshape(val.shape[0], -1), axis=1, keepdims=True)
    return {'Out': [loss], 'Diff': [diff]}


@register('log_loss')
def _log_loss(ctx, ins):
    p = ins['Predicted'][0]
    y = ins['Labels'][0]
    eps = ctx.attr('epsilon', 1e-4)
    loss = -y * jnp.log(p + eps) - (1.0 - y) * jnp.log(1.0 - p + eps)
    return {'Loss': [loss]}


@register('sigmoid_cross_entropy_with_logits')
def _sce_logits(ctx, ins):
    x = X(ins)
    label = ins['Label'][0]
    ignore = ctx.attr('ignore_index', -100)
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    loss = jnp.where(label == ignore, 0.0, loss)
    if ctx.attr('normalize', False):
        cnt = jnp.maximum(jnp.sum(label != ignore), 1)
        loss = loss / cnt
    return {'Out': [loss]}


@register('bpr_loss')
def _bpr_loss(ctx, ins):
    x = X(ins)  # [N, C] logits/probs
    label = ins['Label'][0]
    lab = label.reshape(-1).astype(jnp.int32)
    pos = jnp.take_along_axis(x, lab[:, None], axis=1)
    # -log sigmoid(x_pos - x_neg) = log1p(exp(x_neg - x_pos)), averaged over
    # the negatives (ref bpr_loss_op.h:72 sums -log(1+exp(neg-pos)) and
    # negates/normalizes)
    diff = x - pos
    # exclude the positive column itself
    mask = jnp.ones_like(x, dtype=bool).at[jnp.arange(x.shape[0]), lab].set(False)
    loss = jnp.where(mask, jnp.log1p(jnp.exp(diff)), 0.0)
    loss = jnp.sum(loss, axis=1, keepdims=True) / (x.shape[1] - 1)
    return {'Y': [loss]}


@register('margin_rank_loss')
def _margin_rank_loss(ctx, ins):
    x1, x2, label = ins['X1'][0], ins['X2'][0], ins['Label'][0]
    m = ctx.attr('margin', 0.0)
    act = jnp.maximum(0.0, -label * (x1 - x2) + m)
    return {'Out': [act], 'Activated': [(act > 0).astype(x1.dtype)]}


@register('rank_loss')
def _rank_loss(ctx, ins):
    label = ins['Label'][0]
    left, right = ins['Left'][0], ins['Right'][0]
    d = left - right
    return {'Out': [jnp.log1p(jnp.exp(d)) - label * d]}


@register('cos_sim')
def _cos_sim(ctx, ins):
    x, y = ins['X'][0], ins['Y'][0]
    xn = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=1, keepdims=True))
    out = jnp.sum(x * y, axis=1, keepdims=True) / (xn * yn + 1e-12)
    return {'Out': [out], 'XNorm': [xn], 'YNorm': [yn]}


# ---------------------------------------------------------------------------
# logical / compare (ref: operators/controlflow/compare_op.cc, logical_op.cc)
# ---------------------------------------------------------------------------
def _compare(name, fn):
    @register(name, no_grad=True)
    def _lower(ctx, ins, _fn=fn):
        x, y = ins['X'][0], ins['Y'][0]
        y = _bcast_y(x, y, ctx.attr('axis', -1))
        return {'Out': [_fn(x, y)]}


_compare('less_than', jnp.less)
_compare('less_equal', jnp.less_equal)
_compare('greater_than', jnp.greater)
_compare('greater_equal', jnp.greater_equal)
_compare('equal', jnp.equal)
_compare('not_equal', jnp.not_equal)
_compare('logical_and', jnp.logical_and)
_compare('logical_or', jnp.logical_or)
_compare('logical_xor', jnp.logical_xor)


@register('logical_not', no_grad=True)
def _logical_not(ctx, ins):
    return {'Out': [jnp.logical_not(X(ins))]}


@register('isfinite', no_grad=True)
def _isfinite(ctx, ins):
    return {'Out': [jnp.all(jnp.isfinite(X(ins)))[None]]}


@register('squared_l2_norm', lod='none')
def _squared_l2_norm(ctx, ins):
    x = X(ins)
    return {'Out': [jnp.sum(jnp.square(x))]}


@register('global_norm_scale', no_grad=True, lod='none')
def _global_norm_scale(ctx, ins):
    norm = ins['Norm'][0]
    clip = ctx.attr('clip_norm')
    return {'Out': [jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))]}


@register('norm')
def _norm(ctx, ins):
    x = X(ins)
    axis = ctx.attr('axis', -1)
    eps = ctx.attr('epsilon', 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {'Out': [x / norm], 'Norm': [norm]}


@register('teacher_student_sigmoid_loss', diff_inputs=('X',))
def _teacher_student_sigmoid_loss(ctx, ins):
    """ref teacher_student_sigmoid_loss_op.h: BCE on the click bit plus BCE
    on the teacher score, encoded in one label:
      label < -1: clk=0, no teacher;  label in [-1,0): clk=1, no teacher;
      label in [0,1): clk=0, teacher=label;  label >= 1: clk=1,
      teacher=label-1."""
    x = X(ins).reshape(-1)
    lab = ins['Label'][0].reshape(-1)
    bce = lambda z: jnp.maximum(x, 0.0) - x * z + jnp.log1p(
        jnp.exp(-jnp.abs(x)))
    clk = jnp.where(lab < -1.0, 0.0,
                    jnp.where(lab < 0.0, 1.0,
                              jnp.where(lab < 1.0, 0.0, 1.0)))
    teacher = jnp.where(lab < 0.0, 0.0,
                        jnp.where(lab < 1.0, lab, lab - 1.0))
    has_teacher = lab >= 0.0
    loss = bce(clk) + jnp.where(has_teacher, bce(teacher), 0.0)
    return {'Y': [loss.reshape(-1, 1)]}
