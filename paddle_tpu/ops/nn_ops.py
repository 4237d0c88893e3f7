"""NN op lowerings: conv / pool / normalization / embedding / resize.

Replaces the reference's cuDNN-backed kernels (operators/conv_op.cc,
conv_cudnn_op.cu.cc, pool_op.cc, batch_norm_op.cc/cu, layer_norm_op.cc,
lookup_table_op.cc, interpolate_op.cc ...). Convs lower to
lax.conv_general_dilated in NCHW — XLA picks MXU-friendly internal layouts;
grads come from the generic vjp path (no conv_grad kernels needed).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core.registry import register
from ..core import amp
from .math_ops import X


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


# ---------------------------------------------------------------------------
# convolutions
# ---------------------------------------------------------------------------
@register('conv2d')
def _conv2d(ctx, ins):
    x, w = ins['Input'][0], ins['Filter'][0]
    strides = _pair(ctx.attr('strides', [1, 1]))
    pads = _pair(ctx.attr('paddings', [0, 0]))
    dils = _pair(ctx.attr('dilations', [1, 1]))
    groups = ctx.attr('groups', 1) or 1
    out = amp.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dils, feature_group_count=groups,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    return {'Output': [out]}


@register('depthwise_conv2d')
def _depthwise_conv2d(ctx, ins):
    return _conv2d(ctx, ins)


@register('conv3d')
def _conv3d(ctx, ins):
    x, w = ins['Input'][0], ins['Filter'][0]
    strides = _pair(ctx.attr('strides', [1, 1, 1]), 3)
    pads = _pair(ctx.attr('paddings', [0, 0, 0]), 3)
    dils = _pair(ctx.attr('dilations', [1, 1, 1]), 3)
    groups = ctx.attr('groups', 1) or 1
    out = amp.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p, p) for p in pads], rhs_dilation=dils,
        feature_group_count=groups,
        dimension_numbers=('NCDHW', 'OIDHW', 'NCDHW'))
    return {'Output': [out]}


def _conv_transpose(x, w, strides, pads, dils, groups, nd):
    # w: [C_in, C_out/groups, *k]; emulate grad-of-conv via lhs dilation
    k = w.shape[2:]
    if groups > 1:
        xs = jnp.split(x, groups, axis=1)
        ws = jnp.split(w, groups, axis=0)
        outs = [_conv_transpose(xi, wi, strides, pads, dils, 1, nd)
                for xi, wi in zip(xs, ws)]
        return jnp.concatenate(outs, axis=1)
    wt = jnp.swapaxes(w, 0, 1)  # [C_out, C_in, *k]
    wt = jnp.flip(wt, axis=tuple(range(2, 2 + nd)))
    dk = [(ki - 1) * di + 1 for ki, di in zip(k, dils)]  # dilated kernel size
    padding = [(dki - 1 - p, dki - 1 - p) for dki, p in zip(dk, pads)]
    dims = (('NCHW', 'OIHW', 'NCHW') if nd == 2
            else ('NCDHW', 'OIDHW', 'NCDHW'))
    return amp.conv_general_dilated(
        x, wt, window_strides=[1] * nd, padding=padding,
        lhs_dilation=strides, rhs_dilation=dils, dimension_numbers=dims)


@register('conv2d_transpose')
def _conv2d_transpose(ctx, ins):
    x, w = ins['Input'][0], ins['Filter'][0]
    out = _conv_transpose(x, w, _pair(ctx.attr('strides', [1, 1])),
                          _pair(ctx.attr('paddings', [0, 0])),
                          _pair(ctx.attr('dilations', [1, 1])),
                          ctx.attr('groups', 1) or 1, 2)
    return {'Output': [out]}


@register('conv3d_transpose')
def _conv3d_transpose(ctx, ins):
    x, w = ins['Input'][0], ins['Filter'][0]
    out = _conv_transpose(x, w, _pair(ctx.attr('strides', [1, 1, 1]), 3),
                          _pair(ctx.attr('paddings', [0, 0, 0]), 3),
                          _pair(ctx.attr('dilations', [1, 1, 1]), 3),
                          ctx.attr('groups', 1) or 1, 3)
    return {'Output': [out]}


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------
def ceil_mode_pads(spatial, ksize, strides, pads):
    """Per-spatial-dim (lo, hi) padding implementing pool ceil_mode: the
    high side grows so the last (partial) window is kept instead of
    dropped — output dims become ceil((in + 2p - k) / s) + 1. Shared by
    the graph lowering below and imperative.Pool2D."""
    out = []
    for i in range(len(ksize)):
        in_sz = spatial[i] + 2 * pads[i]
        rem = (in_sz - ksize[i]) % strides[i]
        out.append((pads[i],
                    pads[i] + (strides[i] - rem if rem else 0)))
    return out


def _pool(ctx, ins, nd):
    x = X(ins)
    ptype = ctx.attr('pooling_type', 'max')
    ksize = _pair(ctx.attr('ksize'), nd)
    strides = _pair(ctx.attr('strides', [1] * nd), nd)
    pads = _pair(ctx.attr('paddings', [0] * nd), nd)
    if ctx.attr('global_pooling', False):
        ksize = list(x.shape[2:])
        pads = [0] * nd
    if ctx.attr('adaptive', False):
        return {'Out': [_adaptive_pool(x, ksize, ptype, nd)]}
    window = (1, 1) + tuple(ksize)
    strides_full = (1, 1) + tuple(strides)
    pad_full = [(0, 0), (0, 0)] + [(p, p) for p in pads]
    if ctx.attr('ceil_mode', False):
        pad_full[2:] = ceil_mode_pads(x.shape[2:], ksize, strides, pads)
    if ptype == 'max':
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window,
                                    strides_full, pad_full)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_full,
                                  pad_full)
        # count windows' REAL elements when any padding exists — including
        # ceil_mode's high-side extension (pads alone misses it)
        if ctx.attr('exclusive', True) and any(lo or hi
                                               for lo, hi in pad_full[2:]):
            ones = jnp.ones(x.shape, x.dtype)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides_full, pad_full)
            # clamp: a window entirely inside padding (ceil_mode with
            # stride > kernel) counts 0 real elements — 0/0 would NaN;
            # clamped it yields the finite 0 the pre-ceil path produced
            out = s / jnp.maximum(cnt, 1.0)
        else:
            out = s / float(np.prod(ksize))
    return {'Out': [out]}


def _adaptive_pool(x, out_size, ptype, nd):
    # general adaptive pooling: per-dim bucket boundaries (static)
    spatial = x.shape[2:]
    red = jnp.max if ptype == 'max' else jnp.mean
    # reshape trick when evenly divisible, else explicit window slices
    if all(s % o == 0 for s, o in zip(spatial, out_size)):
        shape = [x.shape[0], x.shape[1]]
        axes = []
        for i, (s, o) in enumerate(zip(spatial, out_size)):
            shape += [o, s // o]
            axes.append(2 + 2 * i + 1)
        return red(x.reshape(shape), axis=tuple(axes))
    slices = []
    import itertools
    for idx in itertools.product(*[range(o) for o in out_size]):
        window = [slice(None), slice(None)]
        for i, o in enumerate(idx):
            s = spatial[i]
            start = (o * s) // out_size[i]
            end = -(-((o + 1) * s) // out_size[i])
            window.append(slice(start, end))
        slices.append(red(x[tuple(window)], axis=tuple(range(2, 2 + nd))))
    out = jnp.stack(slices, axis=-1)
    return out.reshape(x.shape[:2] + tuple(out_size))


@register('pool2d')
def _pool2d(ctx, ins):
    return _pool(ctx, ins, 2)


@register('pool3d')
def _pool3d(ctx, ins):
    return _pool(ctx, ins, 3)


@register('max_pool2d_with_index')
def _max_pool2d_with_index(ctx, ins):
    """Max pool returning values + argmax flat index within each input
    [H, W] plane (ref: operators/pool_with_index_op.cc, math/pooling.cc:625
    index = h * input_width + w; first max wins, matching jnp.argmax).

    TPU design: the kernel window is unrolled statically (kh*kw strided
    slices stacked on a trailing axis) so value-max and index-gather are
    one fused argmax — no data-dependent shapes."""
    x = X(ins)
    kh, kw = _pair(ctx.attr('ksize'))
    sh, sw = _pair(ctx.attr('strides', [1, 1]))
    ph, pw = _pair(ctx.attr('paddings', [0, 0]))
    if ctx.attr('global_pooling', False):
        # one argmax over the flattened plane — the windowed unroll below
        # would trace H*W slices for the same result
        n, c, h, w = x.shape
        flat = x.reshape(n, c, h * w)
        arg = jnp.argmax(flat, axis=-1)
        return {'Out': [jnp.max(flat, axis=-1).reshape(n, c, 1, 1)],
                'Mask': [arg.astype(jnp.int32).reshape(n, c, 1, 1)]}
    if ph >= kh or pw >= kw:
        raise ValueError(
            "max_pool2d_with_index: paddings must be smaller than ksize "
            "(got ksize=%r paddings=%r) — the reference constraint "
            "(pool_with_index_op.cc); a window lying entirely in padding "
            "has no valid argmax index" % ((kh, kw), (ph, pw)))
    n, c, h, w = x.shape
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                 constant_values=neg)
    vals, idxs, valid = [], [], []
    for i in range(kh):
        for j in range(kw):
            sl = jax.lax.slice(
                xp, (0, 0, i, j),
                (n, c, i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1),
                (1, 1, sh, sw))
            vals.append(sl)
            row = jnp.arange(oh) * sh + i - ph      # input-plane coords
            col = jnp.arange(ow) * sw + j - pw
            idxs.append(row[:, None] * w + col[None, :])
            valid.append((row[:, None] >= 0) & (row[:, None] < h)
                         & (col[None, :] >= 0) & (col[None, :] < w))
    stack_v = jnp.stack(vals, axis=-1)              # [N, C, OH, OW, K]
    stack_i = jnp.stack(idxs, axis=-1)              # [OH, OW, K]
    stack_m = jnp.broadcast_to(jnp.stack(valid, axis=-1), stack_v.shape)
    # padded slots must never win the argmax: a real value equal to
    # dtype-min would TIE the padding fill and an earlier padded slot
    # would emit its out-of-plane index — pick the first max that is
    # also a valid in-plane slot (every window has one: paddings < ksize)
    eff = jnp.where(stack_m, stack_v, neg)
    mx = jnp.max(eff, axis=-1, keepdims=True)
    score = (eff == mx) & stack_m
    # NaN window: eff == mx is all-False (NaN != NaN) — fall back to the
    # first VALID slot so the Mask stays in-plane while the NaN value
    # propagates through Out
    pick = jnp.where(score.any(axis=-1, keepdims=True), score, stack_m)
    arg = jnp.argmax(pick, axis=-1)
    mask = jnp.take_along_axis(
        jnp.broadcast_to(stack_i, stack_v.shape), arg[..., None],
        axis=-1)[..., 0]
    return {'Out': [mx[..., 0]], 'Mask': [mask.astype(jnp.int32)]}


@register('unpool')
def _unpool(ctx, ins):
    """Max unpooling: scatter X values to the Indices positions of each
    output plane, zeros elsewhere (ref: operators/unpool_op.cc:68
    OutputSize = (in - 1) * stride - 2 * padding + ksize,
    math/unpooling.cc scatter). One batched scatter — XLA lowers it to a
    single dynamic-update pass."""
    x, idx = ins['X'][0], ins['Indices'][0]
    kh, kw = _pair(ctx.attr('ksize'))
    sh, sw = _pair(ctx.attr('strides', [1, 1]))
    ph, pw = _pair(ctx.attr('paddings', [0, 0]))
    n, c, h, w = x.shape
    oh = (h - 1) * sh - 2 * ph + kh
    ow = (w - 1) * sw - 2 * pw + kw
    flat = jnp.zeros((n, c, oh * ow), x.dtype)
    b_ix = jnp.arange(n)[:, None, None]
    c_ix = jnp.arange(c)[None, :, None]
    out = flat.at[b_ix, c_ix, idx.reshape(n, c, -1).astype(jnp.int32)].set(
        x.reshape(n, c, -1), mode='drop')
    return {'Out': [out.reshape(n, c, oh, ow)]}


@register('spp')
def _spp(ctx, ins):
    """Spatial pyramid pooling: levels 2^0..2^(h-1) bins per side, each an
    exact-cover pool (kernel = ceil(dim/bins), asymmetric pad to
    kernel*bins), flattened [N, C*bins*bins] and concatenated
    (ref: operators/spp_op.h). Each level is one reduce_window — no
    per-bin loops."""
    x = X(ins)
    levels = int(ctx.attr('pyramid_height', 1))
    ptype = ctx.attr('pooling_type', 'max')
    n, c, h, w = x.shape
    outs = []
    for p in range(levels):
        bins = 2 ** p
        kh, kw = -(-h // bins), -(-w // bins)
        ph, pw = (kh * bins - h + 1) // 2, (kw * bins - w + 1) // 2
        pad = [(0, 0), (0, 0),
               (ph, max(0, kh * bins - h - ph)),
               (pw, max(0, kw * bins - w - pw))]
        window, strides = (1, 1, kh, kw), (1, 1, kh, kw)
        if ptype == 'max':
            lvl = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                        strides, pad)
        else:
            s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                      pad)
            cnt = jax.lax.reduce_window(jnp.ones(x.shape, x.dtype), 0.0,
                                        jax.lax.add, window, strides, pad)
            lvl = s / cnt  # exclusive counting, as the reference pools
        outs.append(lvl.reshape(n, c * bins * bins))
    return {'Out': [jnp.concatenate(outs, axis=1)]}


@register('conv_shift')
def _conv_shift(ctx, ins):
    """Circular convolution (NTM shift): Out[b,i] = sum_j X[b,(i+j-half)%M]
    * Y[b,j], N odd (ref: operators/conv_shift_op.cc). The N rotations are
    a static gather -> one batched contraction on the MXU."""
    x, y = ins['X'][0], ins['Y'][0]
    m, nk = x.shape[1], y.shape[1]
    offs = jnp.arange(nk) - (nk - 1) // 2
    idx = (jnp.arange(m)[None, :] + offs[:, None]) % m   # [N, M]
    return {'Out': [jnp.einsum('bnm,bn->bm', x[:, idx], y)]}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
@register('batch_norm')
def _batch_norm(ctx, ins):
    """Bandwidth-lean BN: stats accumulate in f32 THROUGH the reduction
    (the dtype convert fuses into the reduce — no f32 copy of a bf16 x is
    ever materialized), and the normalize runs as one FMA in the compute
    dtype (y = x*k + b with per-channel f32-derived k,b), so the big
    tensor is read once at storage width. Measured +2% e2e on ResNet-50
    v5e vs the promote-everything formulation (PERF_NOTES.md)."""
    x = X(ins)
    scale, bias = ins['Scale'][0], ins['Bias'][0]
    mean, var = ins['Mean'][0], ins['Variance'][0]
    eps = ctx.attr('epsilon', 1e-5)
    momentum = ctx.attr('momentum', 0.9)
    layout = ctx.attr('data_layout', 'NCHW')
    use_global = ctx.attr('use_global_stats', False) or ctx.is_test

    c_axis = 1 if layout == 'NCHW' else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if use_global:
        m, v = mean, var
        mean_out, var_out = mean, var
    else:
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=red_axes)
        v = jnp.mean(jnp.square(xf), axis=red_axes) - jnp.square(m)
        mean_out = momentum * mean + (1.0 - momentum) * m
        var_out = momentum * var + (1.0 - momentum) * v
    inv = jax.lax.rsqrt(v + eps)
    kvec = inv * scale
    bvec = bias - m * kvec

    # pre-folded FMA y = x*k + (bias - m*k). In bf16 this rounds x*k before
    # the mean cancels, adding ~|m|*2^-8 absolute error — but a bf16 x
    # ALREADY carries (|m|+sigma)*2^-8 quantization from the producing
    # conv, so the floor is unchanged in order; the centered (x-m)*k form
    # measured 2.5% slower e2e for no floor improvement (PERF_NOTES.md)
    y = (x * kvec.astype(x.dtype).reshape(bshape)
         + bvec.astype(x.dtype).reshape(bshape))
    return {'Y': [y], 'MeanOut': [mean_out],
            'VarianceOut': [var_out],
            'SavedMean': [m], 'SavedVariance': [inv]}


@register('layer_norm')
def _layer_norm(ctx, ins):
    x_in = X(ins)
    x = amp.promote_f32(x_in)
    eps = ctx.attr('epsilon', 1e-5)
    axis = ctx.attr('begin_norm_axis', 1)
    red = tuple(range(axis, x.ndim))
    m = jnp.mean(x, axis=red, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=red, keepdims=True)
    y = (x - m) * jax.lax.rsqrt(v + eps)
    norm_shape = x.shape[axis:]
    if ins.get('Scale') and ins['Scale'][0] is not None:
        y = y * ins['Scale'][0].reshape(norm_shape)
    if ins.get('Bias') and ins['Bias'][0] is not None:
        y = y + ins['Bias'][0].reshape(norm_shape)
    lead = int(np.prod(x.shape[:axis]))
    return {'Y': [amp.restore(y, x_in)], 'Mean': [m.reshape(lead)],
            'Variance': [v.reshape(lead)]}


@register('group_norm')
def _group_norm(ctx, ins):
    x_in = X(ins)  # NCHW
    x = amp.promote_f32(x_in)
    g = ctx.attr('groups')
    eps = ctx.attr('epsilon', 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    red = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axis=red, keepdims=True)
    v = jnp.mean(jnp.square(xg - m), axis=red, keepdims=True)
    y = ((xg - m) * jax.lax.rsqrt(v + eps)).reshape(x.shape)
    bshape = [1, c] + [1] * (x.ndim - 2)
    if ins.get('Scale') and ins['Scale'][0] is not None:
        y = y * ins['Scale'][0].reshape(bshape)
    if ins.get('Bias') and ins['Bias'][0] is not None:
        y = y + ins['Bias'][0].reshape(bshape)
    return {'Y': [amp.restore(y, x_in)], 'Mean': [m.reshape(n, g)],
            'Variance': [v.reshape(n, g)]}


@register('data_norm')
def _data_norm(ctx, ins):
    x = X(ins)
    bsum = ins['BatchSum'][0]
    bsize = ins['BatchSize'][0]
    bsquare = ins['BatchSquareSum'][0]
    means = bsum / bsize
    scales = jnp.sqrt(bsize / bsquare)
    y = (x - means) * scales
    return {'Y': [y], 'Means': [means], 'Scales': [scales]}


@register('lrn')
def _lrn(ctx, ins):
    x = X(ins)  # NCHW
    n_ = ctx.attr('n', 5)
    k = ctx.attr('k', 2.0)
    alpha = ctx.attr('alpha', 1e-4)
    beta = ctx.attr('beta', 0.75)
    sq = jnp.square(x)
    half = n_ // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n_))
    mid = k + alpha * acc
    return {'Out': [x / jnp.power(mid, beta)], 'MidOut': [mid]}


@register('l2_normalize')
def _l2_normalize(ctx, ins):
    x = X(ins)
    axis = ctx.attr('axis', -1)
    eps = ctx.attr('epsilon', 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True))
    return {'Out': [x / jnp.maximum(norm, eps)], 'Norm': [norm]}


@register('affine_channel')
def _affine_channel(ctx, ins):
    x = X(ins)
    layout = ctx.attr('data_layout', 'NCHW')
    c_axis = 1 if layout == 'NCHW' else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    return {'Out': [x * ins['Scale'][0].reshape(shape)
                    + ins['Bias'][0].reshape(shape)]}


# ---------------------------------------------------------------------------
# embedding (ref: operators/lookup_table_op.cc). is_sparse/remote prefetch
# collapse into dense gather; sharded tables ride the mesh (see parallel/).
# ---------------------------------------------------------------------------
@register('lookup_table')
def _lookup_table(ctx, ins):
    w = ins['W'][0]
    ids = ins['Ids'][0]
    flat = ids.reshape(-1).astype(jnp.int32)
    out = jnp.take(w, flat, axis=0)
    pad = ctx.attr('padding_idx', -1)
    if pad is not None and pad != -1:
        if pad < 0:
            pad += w.shape[0]
        out = jnp.where((flat == pad)[:, None], 0.0, out)
    shape = ids.shape
    if shape[-1] == 1:
        shape = shape[:-1]
    return {'Out': [out.reshape(shape + (w.shape[1],))]}


@register('embedding')
def _embedding(ctx, ins):
    return _lookup_table(ctx, ins)


@register('lookup_table_grad', no_grad=True, lod='aware')
def _lookup_table_grad(ctx, ins):
    """Explicit grad: with is_sparse the table gradient is a SelectedRows
    (rows = the batch's ids, values = output cotangent rows) instead of a
    dense [V, D] scatter — the SelectedRows path of the reference
    (lookup_table_op.cc W@GRAD as SelectedRows, selected_rows_functor.h).
    Dense fallback matches the generic vjp."""
    from ..core.selected_rows import SelectedRowsVal
    from ..core.lod import unwrap as _unw
    a = ctx.attrs
    w_name = a['_fwd_inputs']['W'][0]
    ids_name = a['_fwd_inputs']['Ids'][0]
    out_name = a['_fwd_outputs']['Out'][0]
    gname = a['_in_grad_map'].get(w_name, '')
    if not gname:
        return
    g_out_name = a['_out_grad_map'].get(out_name, '')
    w = _unw(ctx.env(w_name))
    ids = _unw(ctx.env(ids_name))
    flat = ids.reshape(-1).astype(jnp.int32)
    if not g_out_name or g_out_name not in ctx.tracer.env:
        gv = jnp.zeros((flat.shape[0], w.shape[1]), w.dtype)
    else:
        gv = _unw(ctx.env(g_out_name)).reshape(flat.shape[0], w.shape[1])
    pad = ctx.attr('padding_idx', -1)
    if pad is not None and pad != -1:
        if pad < 0:
            pad += w.shape[0]
        gv = jnp.where((flat == pad)[:, None], 0.0, gv)
    if ctx.attr('is_sparse', False):
        return {'IN@GRAD': [SelectedRowsVal(flat, gv, w.shape[0])]}
    dense = jnp.zeros_like(w).at[flat].add(gv, mode='drop')
    return {'IN@GRAD': [dense]}


@register('embedding_grad', no_grad=True, lod='aware')
def _embedding_grad(ctx, ins):
    return _lookup_table_grad(ctx, ins)


@register('merge_selected_rows', no_grad=True, lod='none')
def _merge_selected_rows(ctx, ins):
    from ..core.selected_rows import SelectedRowsVal
    x = X(ins)
    if not isinstance(x, SelectedRowsVal):
        raise TypeError("merge_selected_rows expects SelectedRows input "
                        "(a sparse embedding gradient), got %r" % (x,))
    return {'Out': [x.merged()]}


@register('get_tensor_from_selected_rows', no_grad=True, lod='none')
def _get_tensor_from_selected_rows(ctx, ins):
    from ..core.selected_rows import SelectedRowsVal
    x = X(ins)
    if not isinstance(x, SelectedRowsVal):
        raise TypeError("get_tensor_from_selected_rows expects SelectedRows "
                        "input, got %r" % (x,))
    return {'Out': [x.values]}


# ---------------------------------------------------------------------------
# image resize (ref: operators/interpolate_op.cc)
# ---------------------------------------------------------------------------
def _out_hw(ctx, ins, x):
    if ins.get('OutSize') and ins['OutSize'][0] is not None:
        sz = np.asarray(ins['OutSize'][0])
        return int(sz[0]), int(sz[1])
    oh, ow = ctx.attr('out_h', -1), ctx.attr('out_w', -1)
    scale = ctx.attr('scale', 0.0)
    if (oh <= 0 or ow <= 0) and scale > 0:
        oh = int(x.shape[2] * scale)
        ow = int(x.shape[3] * scale)
    return oh, ow


def _src_index(out_len, in_len, align_corners, align_mode):
    i = jnp.arange(out_len, dtype=jnp.float32)
    if align_corners and out_len > 1:
        return i * (in_len - 1) / (out_len - 1)
    ratio = in_len / out_len
    if align_mode == 0:
        return jnp.clip((i + 0.5) * ratio - 0.5, 0.0)
    return i * ratio


@register('bilinear_interp')
def _bilinear_interp(ctx, ins):
    x = X(ins)
    oh, ow = _out_hw(ctx, ins, x)
    ac = ctx.attr('align_corners', True)
    am = ctx.attr('align_mode', 1)
    h, w = x.shape[2], x.shape[3]
    fy = _src_index(oh, h, ac, am)
    fx = _src_index(ow, w, ac, am)
    y0 = jnp.floor(fy).astype(jnp.int32)
    x0 = jnp.floor(fx).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (fy - y0).reshape(1, 1, -1, 1)
    wx = (fx - x0).reshape(1, 1, 1, -1)
    g = lambda yi, xi: x[:, :, yi, :][:, :, :, xi]
    out = ((1 - wy) * (1 - wx) * g(y0, x0) + (1 - wy) * wx * g(y0, x1)
           + wy * (1 - wx) * g(y1, x0) + wy * wx * g(y1, x1))
    return {'Out': [out.astype(x.dtype)]}


@register('nearest_interp')
def _nearest_interp(ctx, ins):
    x = X(ins)
    oh, ow = _out_hw(ctx, ins, x)
    ac = ctx.attr('align_corners', True)
    h, w = x.shape[2], x.shape[3]
    fy = _src_index(oh, h, ac, 1)
    fx = _src_index(ow, w, ac, 1)
    yi = (jnp.round(fy) if ac else jnp.floor(fy)).astype(jnp.int32)
    xi = (jnp.round(fx) if ac else jnp.floor(fx)).astype(jnp.int32)
    yi = jnp.clip(yi, 0, h - 1)
    xi = jnp.clip(xi, 0, w - 1)
    return {'Out': [x[:, :, yi, :][:, :, :, xi]]}


@register('grid_sampler')
def _grid_sampler(ctx, ins):
    x = X(ins)           # [N, C, H, W]
    grid = ins['Grid'][0]  # [N, H', W', 2] in [-1, 1]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx, wy = gx - x0, gy - y0

    def gather(yi, xi):
        yi = jnp.clip(yi, 0, h - 1)
        xi = jnp.clip(xi, 0, w - 1)
        bidx = jnp.arange(n).reshape(n, 1, 1)
        return x[bidx, :, yi, xi]  # [N, H', W', C]

    out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[..., None]
           + gather(y0, x1) * (wx * (1 - wy))[..., None]
           + gather(y1, x0) * ((1 - wx) * wy)[..., None]
           + gather(y1, x1) * (wx * wy)[..., None])
    return {'Output': [jnp.moveaxis(out, -1, 1)]}


@register('affine_grid')
def _affine_grid(ctx, ins):
    theta = ins['Theta'][0]  # [N, 2, 3]
    if ins.get('OutputShape') and ins['OutputShape'][0] is not None:
        shape = [int(s) for s in np.asarray(ins['OutputShape'][0])]
    else:
        shape = ctx.attr('output_shape')
    n, c, h, w = shape
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing='ij')
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [H, W, 3]
    out = jnp.einsum('nij,hwj->nhwi', theta, base)
    return {'Output': [out]}


def _flash_policy(seq, causal):
    """Measured v5e auto-selection (fwd+bwd timings, /tmp-sweep recorded in
    PERF_NOTES.md): the Pallas kernel WINS for non-causal 512<=S<=1024
    (q256/k512 blocks, 13-27% faster than the XLA composition) and is
    mandatory above S>=4096 where [B,H,S,S] materialization hits the HBM
    wall; the causal path loses at every measured S on this chip, so only
    memory forces it. Returns (use_flash, block_q, block_kv)."""
    if seq % 128 != 0:
        return False, 0, 0

    def fit(pref):  # largest preferred block that DIVIDES seq — the kernel
        return next(b for b in (pref, 256, 128) if seq % b == 0)  # rejects
    if causal:                                  # non-divisors outright
        return seq >= 4096, fit(512), fit(256)
    if 512 <= seq <= 1024 or seq >= 4096:
        return True, fit(256), fit(512)
    return False, 0, 0


@register('fused_multihead_attention', diff_inputs=('Q', 'K', 'V'))
def _fused_multihead_attention(ctx, ins):
    """TPU-native fused attention (beyond reference parity: the reference
    composes scaled_dot_product_attention from matmul/softmax ops,
    nets.py). Where _flash_policy picks the Pallas flash kernel (measured
    to win, or memory-necessary), a program COMPILED for tpu runs it and
    every other platform the composition — lax.platform_dependent decides
    at lowering, so a cpu executor on a TPU host never meets the kernel.
    A kernel the policy picked and Pallas refuses raises; nothing gives
    way to the O(S^2) path. PTPU_FLASH_ATTN=0/1 forces.
    Q/K/V: [B, H, S, D]."""
    import os
    q, k, v = ins['Q'][0], ins['K'][0], ins['V'][0]
    causal = bool(ctx.attr('causal', False))
    scale = float(ctx.attr('scale', 1.0))
    if ctx.attr('sequence_parallel', False):
        from ..parallel.mesh import current_trace_mesh, SEQ_AXIS
        mesh = current_trace_mesh()
        if mesh is not None and mesh.shape.get(SEQ_AXIS, 1) > 1:
            from ..parallel.ring_attention import ring_attention
            return {'Out': [ring_attention(q, k, v, mesh, causal=causal,
                                           scale=scale)]}
        # no sp axis in the compile mesh: single-device semantics below
    want, bq, bkv = _flash_policy(q.shape[2], causal)
    force = os.environ.get('PTPU_FLASH_ATTN', '')
    if force == '1':
        seq = q.shape[2]
        want = seq % 128 == 0
        bq = next(b for b in (256, 128) if seq % b == 0) if want else 0
        bkv = next(b for b in (512, 256, 128) if seq % b == 0) if want else 0
    elif force == '0':
        want = False

    def compose(q, k, v):
        s = jnp.einsum('bhqd,bhkd->bhqk', q * scale, k)
        if causal:
            Sq, Sk = q.shape[2], k.shape[2]
            mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
            s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
        p = jax.nn.softmax(amp.promote_f32(s), axis=-1)
        p = amp.restore(p, s)
        return jnp.einsum('bhqk,bhkd->bhqd', p, v)

    if not want:
        return {'Out': [compose(q, k, v)]}

    def flash(q, k, v):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention, BlockSizes)
        bs = BlockSizes(
            block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bkv,
            block_k_dkv=bkv, block_q_dkv=bq,
            block_k_major_dq=bkv, block_k_dq=bkv, block_q_dq=bq)
        return flash_attention(q * scale, k, v, causal=causal,
                               block_sizes=bs)
    return {'Out': [jax.lax.platform_dependent(q, k, v, tpu=flash,
                                               default=compose)]}
