"""Int8 quantized-inference op lowerings (ISSUE 11 tentpole).

The reference lineage grew INT8 calibration in its inference transpiler
after Fluid 1.2 (PAPER.md §6: fake-quant calibration + a frozen int8
program); the TPU-native counterpart is this op family, emitted by
`passes/quantize.py` over calibrated inference programs:

  quantize_int8     f32 activation -> int8 at a CALIBRATED per-tensor
                    scale (round-to-nearest-even, symmetric [-127, 127])
  dequantize_int8   int8 -> f32 at a fixed scale (fetched quantized vars,
                    tests; the pass itself fuses dequant into consumers)
  mul_int8          int8 activation x int8 per-channel weight matmul,
                    dequant fused into the output epilogue
  conv2d_int8       int8 NCHW conv over per-output-channel int8 filters,
  (+ depthwise)     dequant fused into the output epilogue

Platform split (lax.platform_dependent, kept inside ONE multi-platform
exported module): on TPU the MXU executes the s8 x s8 -> s32 form
directly — int8 operands halve HBM traffic vs bf16 and double MXU
throughput on the memory-bound serving buckets. XLA:CPU has no fast s8
GEMM (the naive int8 dot measures ~10-100x slower than Eigen f32), so
the cpu/default branch computes the SAME quantized integer values in
f32 — int8 weight constants are folded to f32 by XLA at compile time,
making the CPU proxy a numerics-faithful reference for the TPU path
rather than a throughput simulation. Accumulation differs (exact int32
on TPU vs f32 on CPU); products can exceed f32's 2^24 exact-int range
for K > ~1500, a ~1e-7 relative effect dwarfed by the ~1e-2 quantization
step itself — the parity tolerance the quantize reports state.

All ops are serving-only (no_grad): quantization-aware TRAINING stays in
contrib/quantize.py (fake-quant with STE); this family is the post-
training inference form.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register

# symmetric signed-int8 grid: +-127 levels, -128 unused (the standard
# symmetric convention — keeps w and -w representable at equal error)
QMAX = 127.0

def _per_platform(args, tpu_fn, ref_fn):
    """tpu_fn on TPU, ref_fn elsewhere — one traced module carries both
    branches (multi-platform jax.export keeps platform_dependent)."""
    return lax.platform_dependent(*args, tpu=tpu_fn, default=ref_fn)


def quantize_array(x, scale):
    """round(x / scale) clipped to the symmetric int8 grid (`scale` may
    be a scalar or any broadcastable per-channel array). Shared by the
    runtime lowerings below AND passes/quantize.quantize_weight's
    host-side per-channel weight quantization — one rounding rule
    everywhere, or activation/weight parity would drift."""
    q = jnp.clip(jnp.round(x / scale), -QMAX, QMAX)
    return q.astype(jnp.int8)


@register('quantize_int8', no_grad=True, lod='none')
def _quantize_int8(ctx, ins):
    """Per-tensor symmetric activation quant at the calibrated scale
    (attr 'scale' > 0, fixed at pass time — no runtime statistics, so
    the op is a pure elementwise XLA fuses into its producer)."""
    x = ins['X'][0]
    scale = float(ctx.attr('scale'))
    return {'Out': [quantize_array(x, scale)]}


@register('dequantize_int8', no_grad=True, lod='none')
def _dequantize_int8(ctx, ins):
    x = ins['X'][0]
    scale = float(ctx.attr('scale'))
    return {'Out': [x.astype(jnp.float32) * scale]}


@register('mul_int8', no_grad=True, lod='none')
def _mul_int8(ctx, ins):
    """Quantized `mul`: X int8 (activation), Y int8 [K, N] (per-OUTPUT-
    channel quantized weight), Scale f32 [N] (per-channel weight scales).
    Dequant is fused into the epilogue: out = (x_q . w_q) * in_scale *
    w_scale[None, :] — one f32 multiply per output element, which XLA
    folds into the surrounding elementwise chain."""
    x, y = ins['X'][0], ins['Y'][0]
    w_scale = ins['Scale'][0]
    in_scale = float(ctx.attr('in_scale'))
    xn = ctx.attr('x_num_col_dims', 1)
    yn = ctx.attr('y_num_col_dims', 1)
    lead = int(np.prod(x.shape[:xn])) if xn else 1
    x2 = x.reshape(lead, -1)
    y2 = y.reshape(int(np.prod(y.shape[:yn])), -1)
    dims = (((1,), (0,)), ((), ()))

    def tpu_path(x2, y2):
        acc = lax.dot_general(x2, y2, dims,
                              preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32)

    def ref_path(x2, y2):
        return lax.dot_general(x2.astype(jnp.float32),
                               y2.astype(jnp.float32), dims)

    acc = _per_platform((x2, y2), tpu_path, ref_path)
    out = acc * (in_scale * w_scale.reshape(1, -1))
    out_shape = x.shape[:xn] + y.shape[yn:]
    return {'Out': [out.reshape(out_shape)]}


def _conv2d_int8_impl(ctx, ins):
    x, w = ins['Input'][0], ins['Filter'][0]
    w_scale = ins['Scale'][0]                      # [O]
    in_scale = float(ctx.attr('in_scale'))

    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    strides = _pair(ctx.attr('strides', [1, 1]))
    pads = _pair(ctx.attr('paddings', [0, 0]))
    dils = _pair(ctx.attr('dilations', [1, 1]))
    groups = ctx.attr('groups', 1) or 1
    kw = dict(window_strides=strides,
              padding=[(pads[0], pads[0]), (pads[1], pads[1])],
              rhs_dilation=dils, feature_group_count=groups,
              dimension_numbers=('NCHW', 'OIHW', 'NCHW'))

    def tpu_path(x, w):
        acc = lax.conv_general_dilated(
            x, w, preferred_element_type=jnp.int32, **kw)
        return acc.astype(jnp.float32)

    def ref_path(x, w):
        return lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32), **kw)

    acc = _per_platform((x, w), tpu_path, ref_path)
    out = acc * (in_scale * w_scale.reshape(1, -1, 1, 1))
    return {'Output': [out]}


@register('conv2d_int8', no_grad=True, lod='none')
def _conv2d_int8(ctx, ins):
    """Quantized conv2d: Input int8 NCHW, Filter int8 OIHW quantized per
    OUTPUT channel, Scale f32 [O]; dequant fused into the epilogue as
    with mul_int8."""
    return _conv2d_int8_impl(ctx, ins)


@register('depthwise_conv2d_int8', no_grad=True, lod='none')
def _depthwise_conv2d_int8(ctx, ins):
    return _conv2d_int8_impl(ctx, ins)
