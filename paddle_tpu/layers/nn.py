"""Neural-network layer functions (ref: python/paddle/fluid/layers/nn.py —
~190 functions, the model-building vocabulary).

Layers append ops to the default main program; parameters are created via
LayerHelper with init ops in the startup program. Signatures follow the
reference so user model code ports unchanged; `use_cudnn`-style knobs are
accepted and ignored (XLA owns kernels).
"""
from __future__ import annotations

import numpy as np

from ..framework import Variable
from ..layer_helper import LayerHelper
from ..initializer import NormalInitializer, ConstantInitializer
from ..param_attr import ParamAttr


def _single(v, n):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully-connected layer (ref nn.py fc): mul per input + sum + bias + act."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, pattr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_shape = [int(np.prod(input_shape[num_flatten_dims:])), size]
        w = helper.create_parameter(attr=pattr, shape=param_shape, dtype=dtype)
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul", inputs={"X": input_var, "Y": w},
            outputs={"Out": tmp},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": pre_bias}, attrs={})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype='float32'):
    """Embedding lookup (ref nn.py embedding / lookup_table_op.cc).
    is_sparse/is_distributed are accepted; sharding over a mesh axis is
    configured via paddle_tpu.parallel (the dist-lookup-table equivalent)."""
    helper = LayerHelper('embedding', param_attr=param_attr)
    w = helper.create_parameter(attr=helper.param_attr, shape=size,
                                dtype=dtype, is_bias=False)
    tmp = helper.create_variable_for_type_inference(dtype)
    padding_idx = (-1 if padding_idx is None else
                   padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op(
        type='lookup_table', inputs={'Ids': input, 'W': w},
        outputs={'Out': tmp},
        attrs={'is_sparse': is_sparse, 'is_distributed': is_distributed,
               'padding_idx': padding_idx})
    return tmp


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper('conv2d', param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    groups = groups or 1
    filter_size = _single(filter_size, 2)
    stride = _single(stride, 2)
    padding = _single(padding, 2)
    dilation = _single(dilation, 2)
    filter_shape = [num_filters, num_channels // groups] + filter_size
    filter_elem_num = int(np.prod(filter_shape[1:]))
    std = (2.0 / filter_elem_num) ** 0.5
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, std))
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='conv2d',
        inputs={'Input': input, 'Filter': w},
        outputs={'Output': pre_bias},
        attrs={'strides': stride, 'paddings': padding, 'dilations': dilation,
               'groups': groups, 'use_cudnn': use_cudnn})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper('conv3d', param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    groups = groups or 1
    filter_size = _single(filter_size, 3)
    filter_shape = [num_filters, num_channels // groups] + filter_size
    std = (2.0 / int(np.prod(filter_shape[1:]))) ** 0.5
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, std))
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='conv3d', inputs={'Input': input, 'Filter': w},
        outputs={'Output': pre_bias},
        attrs={'strides': _single(stride, 3), 'paddings': _single(padding, 3),
               'dilations': _single(dilation, 3), 'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper('conv2d_transpose', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    groups = groups or 1
    stride = _single(stride, 2)
    padding = _single(padding, 2)
    dilation = _single(dilation, 2)
    if filter_size is None:
        if output_size is None:
            raise ValueError("filter_size or output_size must be set")
        output_size = _single(output_size, 2)
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h_in - 1) * stride[0] + 2 * padding[0] - 1)
            // dilation[0] + 1,
            (output_size[1] - (w_in - 1) * stride[1] + 2 * padding[1] - 1)
            // dilation[1] + 1]
    else:
        filter_size = _single(filter_size, 2)
    filter_shape = [input.shape[1], num_filters // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='conv2d_transpose', inputs={'Input': input, 'Filter': w},
        outputs={'Output': pre_bias},
        attrs={'strides': stride, 'paddings': padding, 'dilations': dilation,
               'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper('conv3d_transpose', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    groups = groups or 1
    filter_size = _single(filter_size, 3)
    filter_shape = [input.shape[1], num_filters // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='conv3d_transpose', inputs={'Input': input, 'Filter': w},
        outputs={'Output': pre_bias},
        attrs={'strides': _single(stride, 3), 'paddings': _single(padding, 3),
               'dilations': _single(dilation, 3), 'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    helper = LayerHelper('pool2d', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='pool2d', inputs={'X': input}, outputs={'Out': out},
        attrs={'pooling_type': pool_type, 'ksize': _single(pool_size, 2),
               'global_pooling': global_pooling,
               'strides': _single(pool_stride, 2),
               'paddings': _single(pool_padding, 2),
               'ceil_mode': ceil_mode, 'exclusive': exclusive})
    return out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    helper = LayerHelper('pool3d', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='pool3d', inputs={'X': input}, outputs={'Out': out},
        attrs={'pooling_type': pool_type, 'ksize': _single(pool_size, 3),
               'global_pooling': global_pooling,
               'strides': _single(pool_stride, 3),
               'paddings': _single(pool_padding, 3),
               'ceil_mode': ceil_mode, 'exclusive': exclusive})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    helper = LayerHelper('adaptive_pool2d', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='pool2d', inputs={'X': input}, outputs={'Out': out},
        attrs={'pooling_type': pool_type, 'ksize': _single(pool_size, 2),
               'adaptive': True})
    return out


def adaptive_pool3d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    helper = LayerHelper('adaptive_pool3d', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='pool3d', inputs={'X': input}, outputs={'Out': out},
        attrs={'pooling_type': pool_type, 'ksize': _single(pool_size, 3),
               'adaptive': True})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-05,
               param_attr=None, bias_attr=None, data_layout='NCHW',
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None,
               do_model_average_for_mean_and_var=False,
               fuse_with_relu=False, use_global_stats=False):
    helper = LayerHelper('batch_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    c = input.shape[1] if data_layout == 'NCHW' else input.shape[-1]
    scale = helper.create_parameter(
        attr=helper.param_attr or ParamAttr(), shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(attr=helper.bias_attr or ParamAttr(),
                                   shape=[c], dtype=dtype, is_bias=True)
    mean = helper.create_or_get_global_variable(
        name=moving_mean_name or (helper.name + '.mean'),
        shape=[c], dtype=dtype, persistable=True)
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.create_or_get_global_variable(
        name=moving_variance_name or (helper.name + '.variance'),
        shape=[c], dtype=dtype, persistable=True)
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(dtype, True)
    saved_var = helper.create_variable_for_type_inference(dtype, True)
    out = input if in_place else helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='batch_norm',
        inputs={'X': input, 'Scale': scale, 'Bias': bias,
                'Mean': mean, 'Variance': variance},
        outputs={'Y': out, 'MeanOut': mean, 'VarianceOut': variance,
                 'SavedMean': saved_mean, 'SavedVariance': saved_var},
        attrs={'momentum': momentum, 'epsilon': epsilon, 'is_test': is_test,
               'data_layout': data_layout,
               'use_global_stats': use_global_stats})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-05, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper('layer_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    param_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {'X': input}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr, shape=param_shape, dtype=dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs['Scale'] = s
    if shift:
        b = helper.create_parameter(attr=helper.bias_attr, shape=param_shape,
                                    dtype=dtype, is_bias=True)
        inputs['Bias'] = b
    mean_out = helper.create_variable_for_type_inference(dtype, True)
    var_out = helper.create_variable_for_type_inference(dtype, True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='layer_norm', inputs=inputs,
        outputs={'Y': out, 'Mean': mean_out, 'Variance': var_out},
        attrs={'epsilon': epsilon, 'begin_norm_axis': begin_norm_axis})
    return helper.append_activation(out)


def group_norm(input, groups, epsilon=1e-05, param_attr=None, bias_attr=None,
               act=None, data_layout='NCHW', name=None):
    helper = LayerHelper('group_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    c = input.shape[1]
    inputs = {'X': input}
    if param_attr is not False:
        s = helper.create_parameter(
            attr=helper.param_attr, shape=[c], dtype=dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs['Scale'] = s
    if bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr, shape=[c],
                                    dtype=dtype, is_bias=True)
        inputs['Bias'] = b
    mean_out = helper.create_variable_for_type_inference(dtype, True)
    var_out = helper.create_variable_for_type_inference(dtype, True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='group_norm', inputs=inputs,
                     outputs={'Y': out, 'Mean': mean_out, 'Variance': var_out},
                     attrs={'epsilon': epsilon, 'groups': groups})
    return helper.append_activation(out)


def data_norm(input, act=None, epsilon=1e-05, param_attr=None,
              data_layout='NCHW', in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    helper = LayerHelper('data_norm', act=act, name=name)
    dtype = input.dtype
    c = input.shape[1]
    batch_size = helper.create_parameter(
        attr=ParamAttr(name=helper.name + '.batch_size', trainable=True),
        shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1e4))
    batch_sum = helper.create_parameter(
        attr=ParamAttr(name=helper.name + '.batch_sum', trainable=True),
        shape=[c], dtype=dtype, default_initializer=ConstantInitializer(0.0))
    batch_square = helper.create_parameter(
        attr=ParamAttr(name=helper.name + '.batch_square_sum', trainable=True),
        shape=[c], dtype=dtype, default_initializer=ConstantInitializer(1e4))
    means = helper.create_variable_for_type_inference(dtype, True)
    scales = helper.create_variable_for_type_inference(dtype, True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='data_norm',
        inputs={'X': input, 'BatchSize': batch_size, 'BatchSum': batch_sum,
                'BatchSquareSum': batch_square},
        outputs={'Y': out, 'Means': means, 'Scales': scales},
        attrs={'epsilon': epsilon})
    return helper.append_activation(out)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper('dropout', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        type='dropout', inputs={'X': x},
        outputs={'Out': out, 'Mask': mask},
        attrs={'dropout_prob': dropout_prob, 'is_test': is_test,
               'seed': seed if seed is not None else 0,
               'dropout_implementation': dropout_implementation})
    return out


def softmax(input, use_cudnn=True, name=None, axis=-1):
    helper = LayerHelper('softmax', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='softmax', inputs={'X': input},
                     outputs={'Out': out}, attrs={'axis': axis})
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper('cross_entropy')
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='cross_entropy', inputs={'X': input, 'Label': label},
        outputs={'Y': out},
        attrs={'soft_label': soft_label, 'ignore_index': ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False):
    helper = LayerHelper('softmax_with_cross_entropy')
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type='softmax_with_cross_entropy',
        inputs={'Logits': logits, 'Label': label},
        outputs={'Softmax': softmax_out, 'Loss': loss},
        attrs={'soft_label': soft_label, 'ignore_index': ignore_index})
    if return_softmax:
        return loss, softmax_out
    return loss


def square_error_cost(input, label):
    helper = LayerHelper('square_error_cost')
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='square_error_cost',
                     inputs={'X': input, 'Y': label}, outputs={'Out': out},
                     attrs={})
    return out


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False):
    helper = LayerHelper('sigmoid_cross_entropy_with_logits', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type='sigmoid_cross_entropy_with_logits',
        inputs={'X': x, 'Label': label}, outputs={'Out': out},
        attrs={'ignore_index': ignore_index, 'normalize': normalize})
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper('huber_loss')
    out = helper.create_variable_for_type_inference(input.dtype)
    residual = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(type='huber_loss', inputs={'X': input, 'Y': label},
                     outputs={'Out': out, 'Residual': residual},
                     attrs={'delta': delta})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper('smooth_l1_loss')
    out = helper.create_variable_for_type_inference(x.dtype)
    diff = helper.create_variable_for_type_inference(x.dtype, True)
    inputs = {'X': x, 'Y': y}
    if inside_weight is not None:
        inputs['InsideWeight'] = inside_weight
    if outside_weight is not None:
        inputs['OutsideWeight'] = outside_weight
    helper.append_op(type='smooth_l1_loss', inputs=inputs,
                     outputs={'Out': out, 'Diff': diff},
                     attrs={'sigma': sigma if sigma is not None else 1.0})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper('log_loss', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='log_loss',
                     inputs={'Predicted': input, 'Labels': label},
                     outputs={'Loss': out}, attrs={'epsilon': epsilon})
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper('bpr_loss', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='bpr_loss', inputs={'X': input, 'Label': label},
                     outputs={'Y': out}, attrs={})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper('margin_rank_loss', name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    act = helper.create_variable_for_type_inference(left.dtype, True)
    helper.append_op(type='margin_rank_loss',
                     inputs={'Label': label, 'X1': left, 'X2': right},
                     outputs={'Out': out, 'Activated': act},
                     attrs={'margin': margin})
    return out


def rank_loss(label, left, right, name=None):
    helper = LayerHelper('rank_loss', name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op(type='rank_loss',
                     inputs={'Label': label, 'Left': left, 'Right': right},
                     outputs={'Out': out}, attrs={})
    return out


def dice_loss(input, label, epsilon=0.00001):
    label = one_hot(label, depth=input.shape[-1])
    reduce_dim = list(range(1, len(input.shape)))
    inse = reduce_sum(input * label, dim=reduce_dim)
    dice_denominator = reduce_sum(input, dim=reduce_dim) + reduce_sum(
        label, dim=reduce_dim)
    dice_score = 1 - inse * 2 / (dice_denominator + epsilon)
    return reduce_mean(dice_score)


def mean_iou(input, label, num_classes):
    helper = LayerHelper('mean_iou')
    out_mean_iou = helper.create_variable_for_type_inference('float32')
    out_wrong = helper.create_variable_for_type_inference('float32')
    out_correct = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='mean_iou',
                     inputs={'Predictions': input, 'Labels': label},
                     outputs={'OutMeanIou': out_mean_iou,
                              'OutWrong': out_wrong,
                              'OutCorrect': out_correct},
                     attrs={'num_classes': num_classes})
    return out_mean_iou, out_wrong, out_correct


def relu(x, name=None):
    helper = LayerHelper('relu', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='relu', inputs={'X': x}, outputs={'Out': out},
                     attrs={})
    return out


def log(x, name=None):
    helper = LayerHelper('log', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='log', inputs={'X': x}, outputs={'Out': out},
                     attrs={})
    return out


def _simple_unary(op_type):
    def layer(x, name=None, **attrs):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(type=op_type, inputs={'X': x}, outputs={'Out': out},
                         attrs=attrs)
        return out
    layer.__name__ = op_type
    return layer


leaky_relu = _simple_unary('leaky_relu')
elu = _simple_unary('elu')
relu6 = _simple_unary('relu6')
brelu = _simple_unary('brelu')
soft_relu = _simple_unary('soft_relu')
stanh = _simple_unary('stanh')
hard_sigmoid = _simple_unary('hard_sigmoid')
swish = _simple_unary('swish')
selu = _simple_unary('selu')
maxout = _simple_unary('maxout')
space_to_depth = _simple_unary('space_to_depth')
shuffle_channel = _simple_unary('shuffle_channel')


def pow(x, factor=1.0, name=None):
    helper = LayerHelper('pow', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='pow', inputs={'X': x}, outputs={'Out': out},
                     attrs={'factor': factor})
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper('prelu', param_attr=param_attr, name=name)
    if mode not in ['all', 'channel', 'element']:
        raise ValueError('mode should be one of all, channel, element.')
    alpha_shape = [1]
    if mode == 'channel':
        alpha_shape = [1, x.shape[1], 1, 1]
    elif mode == 'element':
        alpha_shape = list(x.shape)
        alpha_shape[0] = 1
    alpha = helper.create_parameter(
        attr=helper.param_attr, shape=alpha_shape, dtype='float32',
        is_bias=False, default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='prelu', inputs={'X': x, 'Alpha': alpha},
                     outputs={'Out': out}, attrs={'mode': mode})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper('clip', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='clip', inputs={'X': x}, outputs={'Out': out},
                     attrs={'min': min, 'max': max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper('clip_by_norm', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='clip_by_norm', inputs={'X': x},
                     outputs={'Out': out}, attrs={'max_norm': max_norm})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper('l2_normalize', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(type='l2_normalize', inputs={'X': x},
                     outputs={'Out': out, 'Norm': norm},
                     attrs={'axis': 1 if axis is None else axis,
                            'epsilon': epsilon})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper('lrn', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(type='lrn', inputs={'X': input},
                     outputs={'Out': out, 'MidOut': mid},
                     attrs={'n': n, 'k': k, 'alpha': alpha, 'beta': beta})
    return out


def affine_channel(x, scale=None, bias=None, data_layout='NCHW', name=None):
    helper = LayerHelper('affine_channel', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='affine_channel',
                     inputs={'X': x, 'Scale': scale, 'Bias': bias},
                     outputs={'Out': out}, attrs={'data_layout': data_layout})
    return out


def affine_grid(theta, out_shape, name=None):
    helper = LayerHelper('affine_grid', name=name)
    out = helper.create_variable_for_type_inference(theta.dtype)
    inputs = {'Theta': theta}
    attrs = {}
    if isinstance(out_shape, Variable):
        inputs['OutputShape'] = out_shape
    else:
        attrs['output_shape'] = list(out_shape)
    helper.append_op(type='affine_grid', inputs=inputs,
                     outputs={'Output': out}, attrs=attrs)
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper('grid_sampler', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='grid_sampler', inputs={'X': x, 'Grid': grid},
                     outputs={'Output': out}, attrs={})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample='BILINEAR', actual_shape=None, align_corners=True,
                 align_mode=1):
    helper = LayerHelper('interpolate', name=name)
    op_type = {'BILINEAR': 'bilinear_interp',
               'NEAREST': 'nearest_interp'}[resample]
    out = helper.create_variable_for_type_inference(input.dtype)
    inputs = {'X': input}
    attrs = {'align_corners': align_corners, 'align_mode': align_mode,
             'out_h': -1, 'out_w': -1, 'scale': 0.0}
    if out_shape is not None:
        if isinstance(out_shape, Variable):
            inputs['OutSize'] = out_shape
        else:
            attrs['out_h'], attrs['out_w'] = int(out_shape[0]), int(out_shape[1])
    elif scale is not None:
        attrs['scale'] = float(scale)
    helper.append_op(type=op_type, inputs=inputs, outputs={'Out': out},
                     attrs=attrs)
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, 'BILINEAR',
                        actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, 'NEAREST',
                        actual_shape, align_corners)


def image_resize_short(input, out_short_len, resample='BILINEAR'):
    in_shape = input.shape
    hw = in_shape[2:4]
    short_idx = hw.index(min(hw))
    out_shape = list(hw)
    out_shape[short_idx] = out_short_len
    out_shape[1 - short_idx] = int(float(out_shape[1 - short_idx]) *
                                   (float(out_short_len) / float(hw[short_idx])) + 0.5)
    return image_resize(input=input, out_shape=out_shape, resample=resample)


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper('pad', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='pad', inputs={'X': x}, outputs={'Out': out},
                     attrs={'paddings': paddings, 'pad_value': pad_value})
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode='constant', pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper('pad2d', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='pad2d', inputs={'X': input}, outputs={'Out': out},
                     attrs={'paddings': paddings, 'mode': mode,
                            'pad_value': pad_value, 'data_format': data_format})
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper('pad_constant_like', name=name)
    out = helper.create_variable_for_type_inference(y.dtype)
    helper.append_op(type='pad_constant_like', inputs={'X': x, 'Y': y},
                     outputs={'Out': out}, attrs={'pad_value': pad_value})
    return out


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper('crop', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {'X': x}
    attrs = {}
    if isinstance(shape, Variable):
        inputs['Y'] = shape
        attrs['shape'] = list(shape.shape)
    else:
        attrs['shape'] = list(shape)
    if isinstance(offsets, Variable):
        inputs['Offsets'] = offsets
    else:
        attrs['offsets'] = list(offsets) if offsets else [0] * len(x.shape)
    helper.append_op(type='crop', inputs=inputs, outputs={'Out': out},
                     attrs=attrs)
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper('matmul', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type='matmul', inputs={'X': x, 'Y': y}, outputs={'Out': out},
        attrs={'transpose_X': transpose_x, 'transpose_Y': transpose_y,
               'alpha': float(alpha)})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper('mul', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type='mul', inputs={'X': x, 'Y': y}, outputs={'Out': out},
        attrs={'x_num_col_dims': x_num_col_dims,
               'y_num_col_dims': y_num_col_dims})
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None, param_attr=None,
                            bias_attr=None):
    helper = LayerHelper('bilinear_tensor_product', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype('x')
    param_shape = [size, x.shape[1], y.shape[1]]
    w = helper.create_parameter(attr=helper.param_attr, shape=param_shape,
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {'X': x, 'Y': y, 'Weight': w}
    if helper.bias_attr:
        bias_size = [1, size]
        bias = helper.create_parameter(attr=helper.bias_attr, shape=bias_size,
                                       dtype=dtype, is_bias=True)
        inputs['Bias'] = bias
    helper.append_op(type='bilinear_tensor_product', inputs=inputs,
                     outputs={'Out': out}, attrs={})
    return helper.append_activation(out)


def _elementwise_layer(op_type):
    def layer(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, act=act, name=name)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(type=op_type, inputs={'X': x, 'Y': y},
                         outputs={'Out': out}, attrs={'axis': axis})
        return helper.append_activation(out)
    layer.__name__ = op_type
    return layer


elementwise_add = _elementwise_layer('elementwise_add')
elementwise_sub = _elementwise_layer('elementwise_sub')
elementwise_mul = _elementwise_layer('elementwise_mul')
elementwise_div = _elementwise_layer('elementwise_div')
elementwise_max = _elementwise_layer('elementwise_max')
elementwise_min = _elementwise_layer('elementwise_min')
elementwise_pow = _elementwise_layer('elementwise_pow')
elementwise_mod = _elementwise_layer('elementwise_mod')
elementwise_floordiv = _elementwise_layer('elementwise_floordiv')


def _logical_layer(op_type, binary=True):
    def layer(x, y=None, out=None, name=None):
        helper = LayerHelper(op_type, name=name)
        if out is None:
            out = helper.create_variable_for_type_inference('bool')
        inputs = {'X': x}
        if binary:
            inputs['Y'] = y
        helper.append_op(type=op_type, inputs=inputs, outputs={'Out': out},
                         attrs={})
        return out
    layer.__name__ = op_type
    return layer


logical_and = _logical_layer('logical_and')
logical_or = _logical_layer('logical_or')
logical_xor = _logical_layer('logical_xor')


def logical_not(x, out=None, name=None):
    helper = LayerHelper('logical_not', name=name)
    if out is None:
        out = helper.create_variable_for_type_inference('bool')
    helper.append_op(type='logical_not', inputs={'X': x},
                     outputs={'Out': out}, attrs={})
    return out


def _reduce_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(input.dtype)
        if dim is not None and not isinstance(dim, (list, tuple)):
            dim = [dim]
        helper.append_op(
            type=op_type, inputs={'X': input}, outputs={'Out': out},
            attrs={'dim': dim if dim is not None else [0],
                   'keep_dim': keep_dim, 'reduce_all': dim is None})
        return out
    layer.__name__ = op_type
    return layer


reduce_sum = _reduce_layer('reduce_sum')
reduce_mean = _reduce_layer('reduce_mean')
reduce_max = _reduce_layer('reduce_max')
reduce_min = _reduce_layer('reduce_min')
reduce_prod = _reduce_layer('reduce_prod')


def mean(x, name=None):
    helper = LayerHelper('mean', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='mean', inputs={'X': x}, outputs={'Out': out},
                     attrs={})
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper('scale', act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type='scale', inputs={'X': x}, outputs={'Out': out},
        attrs={'scale': float(scale), 'bias': float(bias),
               'bias_after_scale': bias_after_scale})
    return helper.append_activation(out)


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper('reshape2', act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    x_shape = helper.create_variable_for_type_inference(x.dtype, True)
    inputs = {'X': x}
    if actual_shape is not None:
        inputs['Shape'] = actual_shape
    helper.append_op(type='reshape2', inputs=inputs,
                     outputs={'Out': out, 'XShape': x_shape},
                     attrs={'shape': list(shape)})
    return helper.append_activation(out)


def squeeze(input, axes, name=None):
    helper = LayerHelper('squeeze2', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    x_shape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(type='squeeze2', inputs={'X': input},
                     outputs={'Out': out, 'XShape': x_shape},
                     attrs={'axes': axes})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper('unsqueeze2', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    x_shape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(type='unsqueeze2', inputs={'X': input},
                     outputs={'Out': out, 'XShape': x_shape},
                     attrs={'axes': axes})
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper('transpose2', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    x_shape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(type='transpose2', inputs={'X': x},
                     outputs={'Out': out, 'XShape': x_shape},
                     attrs={'axis': list(perm)})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper('flatten2', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    x_shape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(type='flatten2', inputs={'X': x},
                     outputs={'Out': out, 'XShape': x_shape},
                     attrs={'axis': axis})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper('split', name=name)
    input_shape = input.shape
    dim = dim if dim >= 0 else dim + len(input_shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = 0
        sections = list(num_or_sections)
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(num or len(sections))]
    helper.append_op(type='split', inputs={'X': input}, outputs={'Out': outs},
                     attrs={'num': num, 'sections': sections, 'axis': dim})
    return outs


def slice(input, axes, starts, ends):
    helper = LayerHelper('slice')
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='slice', inputs={'Input': input},
                     outputs={'Out': out},
                     attrs={'axes': axes, 'starts': starts, 'ends': ends})
    return out


def shape(input):
    helper = LayerHelper('shape')
    out = helper.create_variable_for_type_inference('int32')
    helper.append_op(type='shape', inputs={'Input': input},
                     outputs={'Out': out}, attrs={})
    return out


def stack(x, axis=0):
    helper = LayerHelper('stack')
    if isinstance(x, Variable):
        x = [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(type='stack', inputs={'X': x}, outputs={'Y': out},
                     attrs={'axis': axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper('unstack')
    if num is None:
        num = x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype)
            for _ in range(num)]
    helper.append_op(type='unstack', inputs={'X': x}, outputs={'Y': outs},
                     attrs={'axis': axis, 'num': num})
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper('expand', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='expand', inputs={'X': x}, outputs={'Out': out},
                     attrs={'expand_times': list(expand_times)})
    return out


def gather(input, index):
    helper = LayerHelper('gather')
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='gather', inputs={'X': input, 'Index': index},
                     outputs={'Out': out}, attrs={})
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper('scatter', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='scatter',
                     inputs={'X': input, 'Ids': index, 'Updates': updates},
                     outputs={'Out': out}, attrs={'overwrite': overwrite})
    return out


def one_hot(input, depth):
    helper = LayerHelper('one_hot')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='one_hot', inputs={'X': input},
                     outputs={'Out': out}, attrs={'depth': depth})
    return out


def topk(input, k, name=None):
    helper = LayerHelper('top_k', name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='top_k', inputs={'X': input},
                     outputs={'Out': values, 'Indices': indices},
                     attrs={'k': k})
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def argmax(x, axis=0):
    helper = LayerHelper('arg_max')
    out = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='arg_max', inputs={'X': x}, outputs={'Out': out},
                     attrs={'axis': axis})
    out.stop_gradient = True
    return out


def argmin(x, axis=0):
    helper = LayerHelper('arg_min')
    out = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='arg_min', inputs={'X': x}, outputs={'Out': out},
                     attrs={'axis': axis})
    out.stop_gradient = True
    return out


def argsort(input, axis=-1, name=None):
    helper = LayerHelper('argsort', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    ids = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='argsort', inputs={'X': input},
                     outputs={'Out': out, 'Indices': ids},
                     attrs={'axis': axis})
    ids.stop_gradient = True
    return out, ids


def concat(input, axis=0, name=None):
    helper = LayerHelper('concat', name=name)
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(type='concat', inputs={'X': input},
                     outputs={'Out': out}, attrs={'axis': axis})
    return out


def cast(x, dtype):
    from ..framework import convert_dtype
    helper = LayerHelper('cast')
    out = helper.create_variable_for_type_inference(convert_dtype(dtype))
    helper.append_op(type='cast', inputs={'X': x}, outputs={'Out': out},
                     attrs={'in_dtype': x.dtype,
                            'out_dtype': convert_dtype(dtype)})
    return out


def multiplex(inputs, index):
    helper = LayerHelper('multiplex')
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(type='multiplex',
                     inputs={'X': inputs, 'Ids': index},
                     outputs={'Out': out}, attrs={})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper('label_smooth', name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {'X': label}
    if prior_dist is not None:
        inputs['PriorDist'] = prior_dist
    helper.append_op(type='label_smooth', inputs=inputs,
                     outputs={'Out': out}, attrs={'epsilon': float(epsilon)})
    return out


def cos_sim(X, Y):
    helper = LayerHelper('cos_sim')
    out = helper.create_variable_for_type_inference(X.dtype)
    xnorm = helper.create_variable_for_type_inference(X.dtype, True)
    ynorm = helper.create_variable_for_type_inference(X.dtype, True)
    helper.append_op(type='cos_sim', inputs={'X': X, 'Y': Y},
                     outputs={'Out': out, 'XNorm': xnorm, 'YNorm': ynorm},
                     attrs={})
    return out


def uniform_random_batch_size_like(input, shape, dtype='float32',
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    helper = LayerHelper('uniform_random_batch_size_like')
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='uniform_random_batch_size_like',
                     inputs={'Input': input}, outputs={'Out': out},
                     attrs={'shape': list(shape), 'input_dim_idx': input_dim_idx,
                            'output_dim_idx': output_dim_idx, 'min': min,
                            'max': max, 'seed': seed, 'dtype': dtype})
    out.stop_gradient = True
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype='float32'):
    helper = LayerHelper('gaussian_random')
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='gaussian_random', outputs={'Out': out},
                     attrs={'shape': list(shape), 'mean': mean, 'std': std,
                            'seed': seed, 'dtype': dtype})
    out.stop_gradient = True
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype='float32'):
    helper = LayerHelper('gaussian_random_batch_size_like')
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='gaussian_random_batch_size_like',
                     inputs={'Input': input}, outputs={'Out': out},
                     attrs={'shape': list(shape), 'input_dim_idx': input_dim_idx,
                            'output_dim_idx': output_dim_idx, 'mean': mean,
                            'std': std, 'seed': seed, 'dtype': dtype})
    out.stop_gradient = True
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype='float32'):
    helper = LayerHelper('sampling_id')
    out = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='sampling_id', inputs={'X': x},
                     outputs={'Out': out},
                     attrs={'min': min, 'max': max, 'seed': seed})
    out.stop_gradient = True
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper('random_crop')
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='random_crop', inputs={'X': x},
                     outputs={'Out': out},
                     attrs={'shape': list(shape),
                            'seed': seed if seed is not None else 0})
    return out


def relu_(x):
    return relu(x)


def add_position_encoding(input, alpha, beta, name=None):
    helper = LayerHelper('add_position_encoding', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='add_position_encoding', inputs={'X': input},
                     outputs={'Out': out},
                     attrs={'alpha': alpha, 'beta': beta})
    return out


def similarity_focus(input, axis, indexes, name=None):
    helper = LayerHelper('similarity_focus', name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='similarity_focus', inputs={'X': input},
                     outputs={'Out': out},
                     attrs={'axis': axis, 'indexes': indexes})
    return out


def hash(input, hash_size, num_hash=1, name=None):
    helper = LayerHelper('hash', name=name)
    out = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='hash', inputs={'X': input}, outputs={'Out': out},
                     attrs={'num_hash': num_hash, 'mod_by': hash_size})
    return out


def grid_sample(*a, **k):
    return grid_sampler(*a, **k)


# ---------------------------------------------------------------------------
# sequence decode / structured prediction layers
# (ref: layers/nn.py warpctc, ctc_greedy_decoder, edit_distance,
# linear_chain_crf, crf_decoding, chunk_eval, beam_search,
# beam_search_decode; op semantics in paddle_tpu/ops/decode_ops.py)
# ---------------------------------------------------------------------------

def warpctc(input, label, blank=0, norm_by_times=False, use_cudnn=False):
    helper = LayerHelper('warpctc')
    loss = helper.create_variable_for_type_inference(input.dtype)
    grad = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='warpctc', inputs={'Logits': input, 'Label': label},
        outputs={'Loss': loss, 'WarpCTCGrad': grad},
        attrs={'blank': blank, 'norm_by_times': norm_by_times})
    return loss


def ctc_greedy_decoder(input, blank, name=None):
    helper = LayerHelper('ctc_greedy_decoder', name=name)
    out = helper.create_variable_for_type_inference('int64')
    out.lod_level = 1
    helper.append_op(type='ctc_greedy_decoder', inputs={'Input': input},
                     outputs={'Output': out}, attrs={'blank': blank})
    out.stop_gradient = True
    return out


def edit_distance(input, label, normalized=True, ignored_tokens=None):
    helper = LayerHelper('edit_distance')
    out = helper.create_variable_for_type_inference('float32')
    seq_num = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='edit_distance',
                     inputs={'Hyps': input, 'Refs': label},
                     outputs={'Out': out, 'SequenceNum': seq_num},
                     attrs={'normalized': normalized,
                            'ignored_tokens': tuple(ignored_tokens or ())})
    out.stop_gradient = True
    seq_num.stop_gradient = True
    return out, seq_num


def linear_chain_crf(input, label, param_attr=None):
    helper = LayerHelper('linear_chain_crf', param_attr=param_attr)
    size = input.shape[1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype=input.dtype)
    ll = helper.create_variable_for_type_inference(input.dtype)
    alpha = helper.create_variable_for_type_inference(input.dtype)
    em_exps = helper.create_variable_for_type_inference(input.dtype)
    tr_exps = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='linear_chain_crf',
        inputs={'Emission': input, 'Transition': transition, 'Label': label},
        outputs={'LogLikelihood': ll, 'Alpha': alpha,
                 'EmissionExps': em_exps, 'TransitionExps': tr_exps})
    return ll


def crf_decoding(input, param_attr, label=None):
    helper = LayerHelper('crf_decoding', param_attr=param_attr)
    transition = helper.get_parameter(helper.param_attr.name)
    path = helper.create_variable_for_type_inference('int64')
    path.lod_level = 1
    inputs = {'Emission': input, 'Transition': transition}
    if label is not None:
        inputs['Label'] = label
    helper.append_op(type='crf_decoding', inputs=inputs,
                     outputs={'ViterbiPath': path})
    path.stop_gradient = True
    return path


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    helper = LayerHelper('chunk_eval')
    precision = helper.create_variable_for_type_inference('float32')
    recall = helper.create_variable_for_type_inference('float32')
    f1 = helper.create_variable_for_type_inference('float32')
    n_inf = helper.create_variable_for_type_inference('int64')
    n_lab = helper.create_variable_for_type_inference('int64')
    n_cor = helper.create_variable_for_type_inference('int64')
    for v in (precision, recall, f1, n_inf, n_lab, n_cor):
        v.stop_gradient = True
    helper.append_op(
        type='chunk_eval', inputs={'Inference': input, 'Label': label},
        outputs={'Precision': precision, 'Recall': recall, 'F1-Score': f1,
                 'NumInferChunks': n_inf, 'NumLabelChunks': n_lab,
                 'NumCorrectChunks': n_cor},
        attrs={'chunk_scheme': chunk_scheme,
               'num_chunk_types': num_chunk_types,
               'excluded_chunk_types': tuple(excluded_chunk_types or ())})
    return precision, recall, f1, n_inf, n_lab, n_cor


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id, level=0,
                name=None, return_parent_idx=False):
    """Fixed-width beam step: rows are [batch*beam_size]; finished beams
    (pre_id == end_id) propagate frozen. parent_idx (absolute parent row of
    each selected beam) is what the reference encodes in the output LoD —
    feed it to beam_search_decode."""
    helper = LayerHelper('beam_search', name=name)
    sel_ids = helper.create_variable_for_type_inference('int64')
    sel_scores = helper.create_variable_for_type_inference(pre_scores.dtype)
    parent_idx = helper.create_variable_for_type_inference('int32')
    inputs = {'pre_ids': pre_ids, 'pre_scores': pre_scores, 'scores': scores}
    if ids is not None:
        inputs['ids'] = ids
    helper.append_op(
        type='beam_search', inputs=inputs,
        outputs={'selected_ids': sel_ids, 'selected_scores': sel_scores,
                 'parent_idx': parent_idx},
        attrs={'level': level, 'beam_size': beam_size, 'end_id': end_id})
    for v in (sel_ids, sel_scores, parent_idx):
        v.stop_gradient = True
    if return_parent_idx:
        return sel_ids, sel_scores, parent_idx
    return sel_ids, sel_scores


def beam_search_decode(ids, scores, beam_size, end_id, name=None,
                       parents=None):
    """Backtrace per-step TensorArrays (ids, scores [, parents]) into full
    hypotheses. Output rows are padded with end_id after each hypothesis
    finishes (static shapes; the reference emits a data-dependent LoD)."""
    helper = LayerHelper('beam_search_decode', name=name)
    sent_ids = helper.create_variable_for_type_inference('int64')
    sent_scores = helper.create_variable_for_type_inference('float32')
    sent_ids.lod_level = 1
    sent_scores.lod_level = 1
    inputs = {'Ids': ids, 'Scores': scores}
    if parents is not None:
        inputs['Parents'] = parents
    helper.append_op(
        type='beam_search_decode', inputs=inputs,
        outputs={'SentenceIds': sent_ids, 'SentenceScores': sent_scores},
        attrs={'beam_size': beam_size, 'end_id': end_id})
    sent_ids.stop_gradient = True
    sent_scores.stop_gradient = True
    return sent_ids, sent_scores


# ---------------------------------------------------------------------------
# large-vocabulary losses + SelectedRows surface
# (ref: nn.py nce/hsigmoid, operators/nce_op.cc,
#  operators/hierarchical_sigmoid_op.cc, get_tensor_from_selected_rows_op.cc,
#  merge_selected_rows_op.cc)
# ---------------------------------------------------------------------------
_NCE_SAMPLERS = {'uniform': 0, 'log_uniform': 1, 'custom_dist': 2}


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None, name=None, sampler='uniform',
        custom_dist=None, seed=0, is_sparse=False):
    """Noise-contrastive estimation loss (ref nce_op.cc). Scores the true
    class(es) plus `num_neg_samples` sampled noise classes per example;
    with is_sparse the weight gradient is SelectedRows over sampled rows."""
    helper = LayerHelper('nce', param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    inputs = {'Input': input, 'Label': label, 'Weight': w}
    battr = helper.bias_attr
    if battr:
        b = helper.create_parameter(attr=battr,
                                    shape=[num_total_classes, 1],
                                    dtype=input.dtype, is_bias=True)
        inputs['Bias'] = b
    if sample_weight is not None:
        inputs['SampleWeight'] = sample_weight
    S = int(num_neg_samples) if num_neg_samples else 10
    attrs = {'num_total_classes': int(num_total_classes),
             'num_neg_samples': S, 'seed': seed,
             'sampler': _NCE_SAMPLERS[sampler], 'is_sparse': is_sparse}
    if sampler == 'custom_dist':
        # static probs become an XLA-constant CDF (ref CustomSampler's
        # host alias table, math/sampler.cc)
        if custom_dist is None:
            raise ValueError("nce sampler='custom_dist' requires "
                             "custom_dist (per-class probabilities)")
        if len(custom_dist) != int(num_total_classes):
            raise ValueError(
                "nce custom_dist must have num_total_classes=%d entries, "
                "got %d" % (num_total_classes, len(custom_dist)))
        attrs['custom_probs'] = [float(p) for p in custom_dist]
    cost = helper.create_variable_for_type_inference(input.dtype)
    sample_logits = helper.create_variable_for_type_inference(input.dtype)
    sample_labels = helper.create_variable_for_type_inference('int64')
    helper.append_op(
        type='nce', inputs=inputs,
        outputs={'Cost': cost, 'SampleLogits': sample_logits,
                 'SampleLabels': sample_labels},
        attrs=attrs)
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None, is_custom=False,
             is_sparse=False):
    """Hierarchical sigmoid over a complete binary class tree, or a
    user-supplied tree via path_table/path_code (ref
    hierarchical_sigmoid_op.cc, math/matrix_bit_code.h CustomCode).
    Cost is O(log2 C) (or path length) dots per example.

    Custom trees: path_table [N, L] holds each sample's leaf->root rows
    into W (-1 padding after the path ends), path_code [N, L] the target
    bit per node; num_classes is then the NON-LEAF node count (W rows),
    matching the reference's contract."""
    custom = is_custom or path_table is not None or path_code is not None
    if custom and (path_table is None or path_code is None):
        raise ValueError("hsigmoid custom trees need BOTH path_table and "
                         "path_code (ref layers.hsigmoid contract)")
    helper = LayerHelper('hierarchical_sigmoid', param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[-1]
    rows = int(num_classes) if custom else int(num_classes) - 1
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[rows, dim], dtype=input.dtype)
    inputs = {'X': input, 'Label': label, 'W': w}
    if custom:
        inputs['PathTable'] = path_table
        inputs['PathCode'] = path_code
    battr = helper.bias_attr
    if battr:
        b = helper.create_parameter(attr=battr, shape=[1, rows],
                                    dtype=input.dtype, is_bias=True)
        inputs['Bias'] = b
    out = helper.create_variable_for_type_inference(input.dtype)
    pre_out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='hierarchical_sigmoid', inputs=inputs,
        outputs={'Out': out, 'PreOut': pre_out},
        attrs={'num_classes': int(num_classes), 'is_sparse': is_sparse})
    return out


def merge_selected_rows(x, name=None):
    """Deduplicate a SelectedRows' rows, summing values
    (ref merge_selected_rows_op.cc)."""
    helper = LayerHelper('merge_selected_rows', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='merge_selected_rows', inputs={'X': x},
                     outputs={'Out': out})
    return out


def get_tensor_from_selected_rows(x, name=None):
    """The dense values tensor of a SelectedRows
    (ref get_tensor_from_selected_rows_op.cc)."""
    helper = LayerHelper('get_tensor_from_selected_rows', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='get_tensor_from_selected_rows', inputs={'X': x},
                     outputs={'Out': out})
    return out


# ---------------------------------------------------------------------------
# py_func (ref nn.py py_func / operators/py_func_op.cc): run arbitrary host
# python inside the graph
# ---------------------------------------------------------------------------
_PY_FUNC_REGISTRY = []


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-python op via jax.pure_callback: `func` receives numpy arrays
    and must return arrays matching `out`'s declared shape/dtype.
    backward_func receives (inputs + outputs + output grads) minus any
    vars listed in skip_vars_in_backward_input, and returns the input
    grads — reference py_func semantics (operators/py_func_op.cc)."""
    helper = LayerHelper('py_func')
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    skip = skip_vars_in_backward_input or []
    skip = skip if isinstance(skip, (list, tuple)) else [skip]
    skip_names = {v.name if hasattr(v, 'name') else v for v in skip}
    _PY_FUNC_REGISTRY.append((func, backward_func, skip_names))
    helper.append_op(
        type='py_func', inputs={'X': list(xs)},
        outputs={'Out': list(outs)},
        attrs={'func_id': len(_PY_FUNC_REGISTRY) - 1},
        infer_shape=False)
    return outs if isinstance(out, (list, tuple)) else outs[0]


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    """Distillation CTR loss (ref nn.py teacher_student_sigmoid_loss)."""
    helper = LayerHelper('teacher_student_sigmoid_loss')
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type='teacher_student_sigmoid_loss',
        inputs={'X': input, 'Label': label}, outputs={'Y': out},
        attrs={'soft_max_up_bound': soft_max_up_bound,
               'soft_max_lower_bound': soft_max_lower_bound},
        infer_shape=False)
    return out


def _suffixed_attr(param_attr, suffix):
    """Per-weight copy of a shared ParamAttr: create_parameter mutates
    attr.name in place, so reusing one attr would alias every weight of a
    multi-parameter layer to a single name."""
    import copy
    if param_attr is None:
        return None
    a = copy.deepcopy(param_attr)
    if getattr(a, 'name', None):
        a.name = a.name + suffix
    return a


def switch_moe_ffn(input, num_experts, d_ff, capacity_factor=1.25,
                   expert_axis='ep', param_attr=None, name=None):
    """Switch (top-1) mixture-of-experts FFN over the last dim of `input`
    (TPU-native extension; the reference has no MoE). Expert weights are
    sharded over the mesh `expert_axis` when one exists — GSPMD turns the
    einsum dispatch/combine into all-to-alls over ICI. Returns
    (out, aux_loss); add aux_loss (load-balancing, Switch eq. 4) to the
    training objective scaled by ~1e-2."""
    from ..parallel.api import shard_parameter
    helper = LayerHelper('switch_moe_ffn', name=name)
    d = int(input.shape[-1])
    dtype = input.dtype
    gate_w = helper.create_parameter(attr=_suffixed_attr(param_attr, '_gate'),
                                     shape=[d, num_experts], dtype=dtype)
    w1 = helper.create_parameter(attr=_suffixed_attr(param_attr, '_w1'),
                                 shape=[num_experts, d, d_ff], dtype=dtype)
    w2 = helper.create_parameter(attr=_suffixed_attr(param_attr, '_w2'),
                                 shape=[num_experts, d_ff, d], dtype=dtype)
    shard_parameter(w1, (expert_axis, None, None))
    shard_parameter(w2, (expert_axis, None, None))
    out = helper.create_variable_for_type_inference(dtype)
    aux = helper.create_variable_for_type_inference('float32')
    helper.append_op(
        type='switch_moe_ffn',
        inputs={'X': input, 'GateW': gate_w, 'W1': w1, 'W2': w2},
        outputs={'Out': out, 'AuxLoss': aux},
        attrs={'capacity_factor': capacity_factor}, infer_shape=False)
    out.shape = input.shape
    aux.shape = (1,)
    return out, aux


def rms_norm(input, epsilon=1e-5, param_attr=None, name=None):
    """Root-mean-square norm over the last axis: x * rsqrt(mean(x^2) +
    epsilon) * weight, computed and returned in float32. The weight [D]
    starts at one."""
    helper = LayerHelper('rms_norm', param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1])],
        dtype='float32', default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='rms_norm', inputs={'X': input, 'Scale': scale},
                     outputs={'Y': out}, attrs={'epsilon': float(epsilon)})
    return out


def rotary_embedding(input, pos, n_head, theta=10000.0, interleave=False,
                     rotary_dim=0):
    """Rotate-half rotary position embedding of `input` [..., n_head *
    d_head] at the FED positions `pos` (one per row of input's leading
    axes, any integer shape of that many elements): within each head,
    channel i of the first half pairs with channel i of the second and
    the pair turns by pos * theta^(-2i / d_head). With `interleave` the
    pairs are the neighbouring channels (2i, 2i + 1), turned in place.
    `rotary_dim` r > 0 (a partial rotary factor: r < d_head) turns only
    the FIRST r channels of each head, as a head of r channels would be
    turned, and passes the rest. float32 out."""
    helper = LayerHelper('rotary_embedding')
    out = helper.create_variable_for_type_inference('float32')
    attrs = {'n_head': int(n_head), 'theta': float(theta)}
    if interleave:      # an op without it is the one every program has held
        attrs['interleave'] = True
    if rotary_dim:
        attrs['rotary_dim'] = int(rotary_dim)
    helper.append_op(type='rotary_embedding',
                     inputs={'X': input, 'Pos': pos}, outputs={'Out': out},
                     attrs=attrs)
    return out


def swiglu(gate, up):
    """The gated FFN's activation: silu(gate) * up, elementwise."""
    helper = LayerHelper('swiglu')
    out = helper.create_variable_for_type_inference(gate.dtype)
    helper.append_op(type='swiglu', inputs={'Gate': gate, 'Up': up},
                     outputs={'Out': out}, attrs={})
    return out


def gated_rms_norm(input, gate, epsilon=1e-6, param_attr=None, name=None):
    """RMSNorm(input) * weight * silu(gate) over the last axis — the norm
    BEFORE the gate (a linear-attention layer's output norm) — computed
    and returned in float32. The weight [D] starts at one."""
    helper = LayerHelper('gated_rms_norm', param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1])],
        dtype='float32', default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='gated_rms_norm',
                     inputs={'X': input, 'Gate': gate, 'Scale': scale},
                     outputs={'Y': out}, attrs={'epsilon': float(epsilon)})
    return out


def _conv_inputs(input, weight, tail, bias, **rows):
    inputs = dict({'X': input, 'Weight': weight, 'Tail': tail}, **rows)
    if bias is not None:    # an op without it is the one qwen3_next holds
        inputs['Bias'] = bias
    return inputs


def causal_conv_step(input, weight, tail, block_table, bias=None):
    """One token a slot through a causal depthwise convolution and SiLU
    (ops/linear_attention_ops.py): `input` [max_slots, W], `weight` [K, W],
    `tail` [max_slots, K - 1, W] the slot's last K - 1 inputs, updated in
    place where the row is LIVE — its row of `block_table` does not start
    with the trash block; `bias` [W], where the convolution has one, is
    added before the SiLU. Returns (out [max_slots, W] float32, tail)."""
    helper = LayerHelper('causal_conv_step')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='causal_conv_step',
                     inputs=_conv_inputs(input, weight, tail, bias,
                                         BlockTable=block_table),
                     outputs={'Out': out, 'TailOut': tail}, attrs={})
    return out, tail


def causal_conv_chunk(input, weight, tail, start, chunk_len, state_slot,
                      bias=None):
    """C tokens a row through causal_conv_step's convolution: `input`
    [R, C, W] from the tail of slot `state_slot` [R, 1] (zero where
    `start` is 0: the request begins here), which is left holding the last
    K - 1 inputs before position `chunk_len`. A slot outside [0,
    max_slots) is nobody's: nothing is written. Returns (out [R, C, W]
    float32, tail)."""
    helper = LayerHelper('causal_conv_chunk')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='causal_conv_chunk',
                     inputs=_conv_inputs(input, weight, tail, bias,
                                         Start=start, ChunkLen=chunk_len,
                                         StateSlot=state_slot),
                     outputs={'Out': out, 'TailOut': tail}, attrs={})
    return out, tail


def _scan_inputs(x, dt, b, c, a_log, dt_bias, d, state, **rows):
    return dict({'X': x, 'Dt': dt, 'B': b, 'C': c, 'ALog': a_log,
                 'DtBias': dt_bias, 'D': d, 'State': state}, **rows)


def selective_scan_step(x, dt, b, c, a_log, dt_bias, d, state, block_table):
    """One token a slot through a Mamba layer's selective scan
    (ops/state_space_ops.py): x, dt [max_slots, channels] (the convolved
    input; the projected step size before `dt_bias`), b, c [max_slots,
    d_state], a_log [d_state, channels], dt_bias, d [channels], against the
    per-slot float32 `state` [max_slots, d_state, channels], updated in
    place where the row is LIVE (causal_conv_step's rule). Returns (out
    [max_slots, channels] float32 — h C + D x, before any gate — state)."""
    helper = LayerHelper('selective_scan_step')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='selective_scan_step',
                     inputs=_scan_inputs(x, dt, b, c, a_log, dt_bias, d,
                                         state, BlockTable=block_table),
                     outputs={'Out': out, 'StateOut': state}, attrs={})
    return out, state


def selective_scan_chunk(x, dt, b, c, a_log, dt_bias, d, state, start,
                         chunk_len, state_slot):
    """C tokens a row through selective_scan_step's recurrence, position
    after position, from the state of slot `state_slot` [R, 1] (zero where
    `start` is 0) to the state after `chunk_len` tokens, written back to
    that slot: x, dt [R, C, channels], b, c [R, C, d_state]. Returns (out
    [R, C, channels] float32, state)."""
    helper = LayerHelper('selective_scan_chunk')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='selective_scan_chunk',
                     inputs=_scan_inputs(x, dt, b, c, a_log, dt_bias, d,
                                         state, Start=start,
                                         ChunkLen=chunk_len,
                                         StateSlot=state_slot),
                     outputs={'Out': out, 'StateOut': state}, attrs={})
    return out, state


def ssd_step(x, dt, b, c, a_log, dt_bias, d, state, block_table, n_head):
    """One token a slot through a Mamba-2 (SSD) layer's recurrence
    (ops/state_space_ops.py): x [max_slots, n_head * d_head] (the convolved
    input), dt [max_slots, n_head] (the projected step size before
    `dt_bias`), b, c [max_slots, d_state] (shared by every head), a_log,
    dt_bias, d [n_head], against the per-slot float32 `state` [max_slots,
    n_head, d_head, d_state], updated in place where the row is LIVE
    (causal_conv_step's rule). Returns (out [max_slots, n_head * d_head]
    float32 — S C + D x, before the gate — state)."""
    helper = LayerHelper('ssd_step')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='ssd_step',
                     inputs=_scan_inputs(x, dt, b, c, a_log, dt_bias, d,
                                         state, BlockTable=block_table),
                     outputs={'Out': out, 'StateOut': state},
                     attrs={'n_head': int(n_head)})
    return out, state


def ssd_chunk(x, dt, b, c, a_log, dt_bias, d, state, start, chunk_len,
              state_slot, n_head, sub_chunk=256):
    """C tokens a row through ssd_step's recurrence in its dual (matrix)
    form, sub-chunks of `sub_chunk` positions, from the state of slot
    `state_slot` [R, 1] (zero where `start` is 0) to the state after
    `chunk_len` tokens, written back to that slot: x [R, C, n_head *
    d_head], dt [R, C, n_head], b, c [R, C, d_state]. Returns (out [R, C,
    n_head * d_head] float32, state)."""
    helper = LayerHelper('ssd_chunk')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='ssd_chunk',
                     inputs=_scan_inputs(x, dt, b, c, a_log, dt_bias, d,
                                         state, Start=start,
                                         ChunkLen=chunk_len,
                                         StateSlot=state_slot),
                     outputs={'Out': out, 'StateOut': state},
                     attrs={'n_head': int(n_head),
                            'sub_chunk': int(sub_chunk)})
    return out, state


def _delta_inputs(q, k, v, a, b, a_log, dt_bias, state):
    return {'Q': q, 'K': k, 'V': v, 'A': a, 'B': b, 'ALog': a_log,
            'DtBias': dt_bias, 'State': state}


def gated_delta_step(q, k, v, a, b, a_log, dt_bias, state, block_table,
                     n_key_head, n_value_head):
    """One token a slot through the gated delta rule (Gated DeltaNet,
    ops/linear_attention_ops.py): q, k [max_slots, n_key_head * d_k], v
    [max_slots, n_value_head * d_v], a, b [max_slots, n_value_head] (decay
    and write-strength projections; a_log, dt_bias [n_value_head]),
    against the per-slot float32 `state` [max_slots, n_value_head, d_k,
    d_v], updated in place where the row is LIVE (causal_conv_step's
    rule). q and k are L2-normalised per head inside the op. With a
    [max_slots, n_value_head * d_k] and dt_bias [n_value_head * d_k] the
    decay is PER KEY CHANNEL (Kimi Delta Attention: the state's rows
    decay apart; a_log stays one scalar a head). Returns
    (out [max_slots, n_value_head * d_v] float32, state)."""
    helper = LayerHelper('gated_delta_step')
    out = helper.create_variable_for_type_inference('float32')
    inputs = _delta_inputs(q, k, v, a, b, a_log, dt_bias, state)
    inputs['BlockTable'] = block_table
    helper.append_op(type='gated_delta_step', inputs=inputs,
                     outputs={'Out': out, 'StateOut': state},
                     attrs={'n_key_head': int(n_key_head),
                            'n_value_head': int(n_value_head)})
    return out, state


def gated_delta_chunk(q, k, v, a, b, a_log, dt_bias, state, start,
                      chunk_len, state_slot, n_key_head, n_value_head,
                      sub_chunk=64):
    """C tokens a row through the CHUNKED gated delta rule (sub-chunks of
    `sub_chunk` tokens), from the state of slot `state_slot` [R, 1] (zero
    where `start` is 0) to the state after `chunk_len` tokens, written
    back to that slot: q, k [R, C, n_key_head * d_k], v [R, C,
    n_value_head * d_v], a, b [R, C, n_value_head] (a [R, C,
    n_value_head * d_k]: gated_delta_step's per-channel decay, exact while
    `sub_chunk` tokens' summed log-decay stays under
    ops/linear_attention_ops.CHANNEL_DECAY_LIMIT). Returns (out [R, C,
    n_value_head * d_v] float32, state)."""
    helper = LayerHelper('gated_delta_chunk')
    out = helper.create_variable_for_type_inference('float32')
    inputs = _delta_inputs(q, k, v, a, b, a_log, dt_bias, state)
    inputs.update({'Start': start, 'ChunkLen': chunk_len,
                   'StateSlot': state_slot})
    helper.append_op(type='gated_delta_chunk', inputs=inputs,
                     outputs={'Out': out, 'StateOut': state},
                     attrs={'n_key_head': int(n_key_head),
                            'n_value_head': int(n_value_head),
                            'sub_chunk': int(sub_chunk)})
    return out, state


def moe_topk_ffn(input, num_experts, d_ff, k, norm_topk_prob=False,
                 dtype=None, param_attr=None, name=None,
                 scoring='softmax', router_bias=False,
                 routed_scaling_factor=1.0, num_held=None,
                 expert_offset=0):
    """Dropless top-k mixture of SwiGLU experts over the last axis of
    `input` (ops/moe_ops.py moe_topk_ffn): a float32 router
    [D, num_experts] scored by `scoring` ('softmax' | 'sigmoid'), each
    token's `k` best experts (weights renormalised only with
    norm_topk_prob, then times routed_scaling_factor), and per expert
    gate / up [D, d_ff] and down [d_ff, D] matrices stacked
    [num_held, ...]. `router_bias` (True, or a ParamAttr of its own)
    adds a float32 [num_experts] parameter that moves the CHOICE of
    experts only (a selection bias; the weights stay the scores).
    `num_held` (default: all) experts starting at `expert_offset` live
    in this op — one chip's share of an expert-parallel layer: it routes
    over all num_experts and returns its own experts' part. Every (token, held expert) pair is computed:
    there is no capacity. Weights are created in `dtype` (default:
    input's) under param_attr's name + '_router' / '_gate' / '_up' /
    '_down' (/ '_router_bias'); float32 out."""
    if scoring not in ('softmax', 'sigmoid'):
        raise ValueError("scoring must be 'softmax' or 'sigmoid', got %r"
                         % (scoring,))
    held = int(num_experts if num_held is None else num_held)
    if not 0 <= int(expert_offset) <= int(num_experts) - held:
        raise ValueError(
            'experts [%d, %d) are not among the %d the router scores'
            % (expert_offset, int(expert_offset) + held, num_experts))
    helper = LayerHelper('moe_topk_ffn', name=name)
    d = int(input.shape[-1])
    dtype = dtype or input.dtype
    shapes = {'RouterW': ('_router', [d, num_experts]),
              'WGate': ('_gate', [held, d, d_ff]),
              'WUp': ('_up', [held, d, d_ff]),
              'WDown': ('_down', [held, d_ff, d])}
    inputs = {'X': input}
    for slot, (suffix, shape) in shapes.items():
        inputs[slot] = helper.create_parameter(
            attr=_suffixed_attr(param_attr, suffix), shape=shape,
            dtype=dtype)
    if router_bias:
        inputs['RouterBias'] = helper.create_parameter(
            attr=(_suffixed_attr(param_attr, '_router_bias')
                  if router_bias is True else router_bias),
            shape=[num_experts], dtype='float32')
    out = helper.create_variable_for_type_inference('float32')
    helper.append_op(type='moe_topk_ffn', inputs=inputs,
                     outputs={'Out': out},
                     attrs={'k': int(k),
                            'norm_topk_prob': bool(norm_topk_prob),
                            'scoring': scoring,
                            'routed_scaling_factor':
                                float(routed_scaling_factor),
                            'expert_offset': int(expert_offset)})
    return out


def pipelined_ffn_stack(input, num_layers, d_ff, num_microbatches=0,
                        pipe_axis='pp', param_attr=None, name=None):
    """A stack of `num_layers` residual FFN layers (x + W2·relu(W1·x))
    with parameters stacked [L, ...] and sharded over the mesh `pipe_axis`
    (TPU-native extension). Under a mesh whose 'pp' axis equals
    num_layers, the stack runs as an SPMD GPipe (parallel/pipeline.py):
    each rank owns one layer, activations ride ICI, microbatches hide the
    bubble. Without a pp axis the same op runs the layers sequentially —
    identical math, so programs are portable across meshes."""
    from ..parallel.api import shard_parameter
    helper = LayerHelper('pipelined_ffn_stack', name=name)
    d = int(input.shape[-1])
    dtype = input.dtype
    w1 = helper.create_parameter(attr=_suffixed_attr(param_attr, '_w1'),
                                 shape=[num_layers, d, d_ff], dtype=dtype)
    b1 = helper.create_parameter(attr=_suffixed_attr(param_attr, '_b1'),
                                 shape=[num_layers, d_ff], dtype=dtype,
                                 is_bias=True)
    w2 = helper.create_parameter(attr=_suffixed_attr(param_attr, '_w2'),
                                 shape=[num_layers, d_ff, d], dtype=dtype)
    b2 = helper.create_parameter(attr=_suffixed_attr(param_attr, '_b2'),
                                 shape=[num_layers, d], dtype=dtype,
                                 is_bias=True)
    for p in (w1, b1, w2, b2):
        shard_parameter(p, (pipe_axis,) + (None,) * (len(p.shape) - 1))
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='pipelined_ffn_stack',
        inputs={'X': input, 'W1': w1, 'B1': b1, 'W2': w2, 'B2': b2},
        outputs={'Out': out},
        attrs={'num_microbatches': num_microbatches}, infer_shape=False)
    out.shape = input.shape
    return out


def sharding_hint(x, spec=()):
    """Constrain `x` to a GSPMD partition spec (mesh axis name per dim,
    None/'' to replicate a dim; empty spec = fully replicated) on the
    trace-time mesh. Identity when traced without a mesh — programs
    carrying hints stay valid single-chip programs. The mp-sharded
    decode spec places replicate hints at contraction boundaries so
    every reduction stays full-width (bit-identity with the single-chip
    artifact; ops/decode_ops.py sharding_hint)."""
    helper = LayerHelper('sharding_hint')
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='sharding_hint', inputs={'X': x},
                     outputs={'Out': out},
                     attrs={'spec': [a or '' for a in spec]},
                     infer_shape=False)
    out.shape = x.shape
    out.stop_gradient = x.stop_gradient
    return out


def kv_block_write(cache, kv, pos, block_table):
    """Block-paged continuous-decode primitive (ISSUE 13): write this
    step's K or V rows [max_slots, d] into the BLOCK pool `cache`
    [num_blocks, block_size, d] through each slot's row of
    `block_table` [max_slots, max_blocks] int32 at position `pos`.
    In-place on `cache` (output aliases the input var); returns it so
    downstream kv_block_attention reads the post-write binding."""
    helper = LayerHelper('kv_block_write')
    helper.append_op(type='kv_block_write',
                     inputs={'Cache': cache, 'KV': kv, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': cache}, attrs={})
    return cache


def _attention_attrs(n_head, n_kv_head, window, scale, v_width):
    attrs = {'n_head': int(n_head), 'n_kv_head': int(n_kv_head or n_head),
             'window': int(window), 'scale': float(scale or 0.0)}
    if v_width:     # an op without it is the one every program has held
        attrs['v_width'] = int(v_width)
    return attrs


def kv_block_attention(query, k_cache, v_cache, pos, block_table,
                       n_head, scale=None, n_kv_head=None, window=0,
                       v_width=0):
    """One-token-per-slot attention over the block-paged cache: `query`
    [max_slots, d] attends its own slot's logically-ordered block view
    (rows j <= pos) through `block_table`. Rows beyond get exactly-zero
    weight — foreign blocks and trash-block garbage can never perturb
    an active slot, and a slot's output depends only on its own pages
    and `pos` (the block form of the continuous-batching contract: a
    stream is bit-identical to serving the request alone). The op has
    two lowerings (ops/decode_ops.py): the masked-attention expression
    over the gathered view on every platform but a TPU; and,
    compiled for a TPU with a float32 / bfloat16 pool of whole-tile
    pages, a Pallas kernel that reads pages 0 .. pos // block_size only
    and rounds differently from the gathered body (float32 throughout).
    `n_kv_head` (default n_head) K/V heads: the cache is n_kv_head *
    d_head wide, `query` n_head * d_head, and query head h reads K/V
    head h // (n_head // n_kv_head). `window` w > 0 attends only the
    rows pos - w < j <= pos — the table then needs to name the slot's
    own blocks only from the one that holds pos - w + 1 on.
    `v_width` > 0: a head's VALUE is the first v_width channels of its K
    row — a LATENT pool, `k_cache` and `v_cache` the same variable,
    n_kv_head 1: every query head (d_head wide, the key up-projection
    folded into it) scores the whole row and sums that part of it; the
    output is n_head * v_width wide. On a TPU such a pool of whole-tile
    rows is read by a kernel of its own, once a page."""
    helper = LayerHelper('kv_block_attention')
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(type='kv_block_attention',
                     inputs={'Q': query, 'KCache': k_cache,
                             'VCache': v_cache, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': out},
                     attrs=_attention_attrs(n_head, n_kv_head, window,
                                            scale, v_width))
    out.stop_gradient = True
    return out


def kv_block_chunk_write(cache, kv, start, block_table):
    """Chunked-prefill write (ISSUE 13): one chunk's K or V rows
    [1, chunk, d] for absolute positions start..start+chunk-1 of ONE
    slot scatter into the block pool through the slot's `block_table`
    row [1, max_blocks]. The leading dimension may be R rows — `kv`
    [R, chunk, d], `start` [R, 1], `block_table` [R, max_blocks] — each
    row one slot's chunk through its own table row (a pad row: the
    trash table). In-place on `cache`."""
    helper = LayerHelper('kv_block_chunk_write')
    helper.append_op(type='kv_block_chunk_write',
                     inputs={'Cache': cache, 'KV': kv, 'Start': start,
                             'BlockTable': block_table},
                     outputs={'Out': cache}, attrs={})
    return cache


def kv_block_chunk_attention(query, k_cache, v_cache, start, block_table,
                             n_head, scale=None, n_kv_head=None, window=0,
                             v_width=0):
    """Chunked-prefill attention: chunk row i ([1, chunk, d] `query`)
    attends the slot's block view rows j <= start + i — causal within
    the chunk AND over every previously written position (earlier
    chunks, shared prefix blocks), which is what lets a prefix-cache
    hit skip recomputing the shared span. `n_kv_head` and `window` as
    kv_block_attention's. Where heads are grouped, a window is set or
    the [chunk, n_head, max_blocks * block_size] scores of the whole
    view would be too large to hold, the lowering reads the slot's pages
    a block of positions at a time under an online softmax instead.
    Over the gathered view the leading dimension may be R rows (`query`
    [R, chunk, d], `start` [R, 1], `block_table` [R, max_blocks]): row r
    is one slot's chunk, attended through table row r — the same
    function per row; the other body takes one row. `v_width` as
    kv_block_attention's (a latent pool: the paged body, absorbed like
    the step's)."""
    helper = LayerHelper('kv_block_chunk_attention')
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(type='kv_block_chunk_attention',
                     inputs={'Q': query, 'KCache': k_cache,
                             'VCache': v_cache, 'Start': start,
                             'BlockTable': block_table},
                     outputs={'Out': out},
                     attrs=_attention_attrs(n_head, n_kv_head, window,
                                            scale, v_width))
    out.stop_gradient = True
    return out


def kv_block_write_quant(cache, cache_scale, kv, pos, block_table):
    """kv_block_write over the INT8 block pool (block paging composed
    with the ISSUE 11 quantized cache): int8 pages [num_blocks,
    block_size, d] + one f32 scale per page position in `cache_scale`
    [num_blocks, block_size]. In-place on the pair; returns both
    post-write bindings."""
    helper = LayerHelper('kv_block_write_quant')
    helper.append_op(type='kv_block_write_quant',
                     inputs={'Cache': cache, 'Scale': cache_scale,
                             'KV': kv, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': cache, 'OutScale': cache_scale},
                     attrs={})
    return cache, cache_scale


def kv_block_attention_quant(query, k_cache, k_scale, v_cache, v_scale,
                             pos, block_table, n_head, scale=None):
    """kv_block_attention over the INT8 block pool: per-slot views
    dequantize (int8 page x its scale) inside the body — no f32 cache
    copy materializes."""
    helper = LayerHelper('kv_block_attention_quant')
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(type='kv_block_attention_quant',
                     inputs={'Q': query, 'KCache': k_cache,
                             'KScale': k_scale, 'VCache': v_cache,
                             'VScale': v_scale, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': out},
                     attrs={'n_head': int(n_head),
                            'scale': float(scale or 0.0)})
    out.stop_gradient = True
    return out


def kv_block_chunk_write_quant(cache, cache_scale, kv, start, block_table):
    """kv_block_chunk_write over the INT8 block pool: chunk rows
    quantize per page position and scatter through the slot's table.
    In-place on the (cache, scale) pair."""
    helper = LayerHelper('kv_block_chunk_write_quant')
    helper.append_op(type='kv_block_chunk_write_quant',
                     inputs={'Cache': cache, 'Scale': cache_scale,
                             'KV': kv, 'Start': start,
                             'BlockTable': block_table},
                     outputs={'Out': cache, 'OutScale': cache_scale},
                     attrs={})
    return cache, cache_scale


def kv_block_chunk_attention_quant(query, k_cache, k_scale, v_cache,
                                   v_scale, k, v, start, block_table,
                                   n_head, scale=None):
    """kv_block_chunk_attention over the INT8 block pool. `k`/`v` are
    the CURRENT chunk's fresh f32 projections (the arrays the write op
    quantized): they splice over the view's in-chunk span so the chunk
    attends itself at full precision — the slot tier's int8 prefill
    semantics, bit-identical for single-chunk prompts. Earlier chunks
    and shared prefix blocks dequantize from their int8 pages."""
    helper = LayerHelper('kv_block_chunk_attention_quant')
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(type='kv_block_chunk_attention_quant',
                     inputs={'Q': query, 'KCache': k_cache,
                             'KScale': k_scale, 'VCache': v_cache,
                             'VScale': v_scale, 'K': k, 'V': v,
                             'Start': start,
                             'BlockTable': block_table},
                     outputs={'Out': out},
                     attrs={'n_head': int(n_head),
                            'scale': float(scale or 0.0)})
    out.stop_gradient = True
    return out


def kv_block_verify_write(cache, kv, pos, block_table):
    """Speculative-decode verify write (ISSUE 17): R = draft_k + 1 K or
    V rows per slot [max_slots, R, d] at `pos` [max_slots, R] scatter
    through the slot's `block_table` row (broadcast over its R rows).
    Pad rows carry pos = max_blocks * block_size,
    which the scatter's span guard forces to the trash block — never a
    shared prefix block. In-place on `cache`."""
    helper = LayerHelper('kv_block_verify_write')
    helper.append_op(type='kv_block_verify_write',
                     inputs={'Cache': cache, 'KV': kv, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': cache}, attrs={})
    return cache


def kv_block_verify_attention(query, k_cache, v_cache, pos, block_table,
                              n_head, scale=None):
    """Verify attention over the block pool: `query` [max_slots, R, d],
    per-slot logical views gather through `block_table`, row i masks at
    j <= pos[s, i]. Foreign blocks and trash garbage get exactly-zero
    weight."""
    helper = LayerHelper('kv_block_verify_attention')
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(type='kv_block_verify_attention',
                     inputs={'Q': query, 'KCache': k_cache,
                             'VCache': v_cache, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': out},
                     attrs={'n_head': int(n_head),
                            'scale': float(scale or 0.0)})
    out.stop_gradient = True
    return out


def kv_block_verify_write_quant(cache, cache_scale, kv, pos, block_table):
    """kv_block_verify_write over the INT8 block pool: speculative rows
    quantize per page position and scatter with their scales through
    the broadcast tables. In-place on the pair."""
    helper = LayerHelper('kv_block_verify_write_quant')
    helper.append_op(type='kv_block_verify_write_quant',
                     inputs={'Cache': cache, 'Scale': cache_scale,
                             'KV': kv, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': cache, 'OutScale': cache_scale},
                     attrs={})
    return cache, cache_scale


def kv_block_verify_attention_quant(query, k_cache, k_scale, v_cache,
                                    v_scale, pos, block_table, n_head,
                                    scale=None):
    """kv_block_verify_attention over the INT8 block pool: per-slot
    views dequantize inside the body, then the fp verify expression."""
    helper = LayerHelper('kv_block_verify_attention_quant')
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(type='kv_block_verify_attention_quant',
                     inputs={'Q': query, 'KCache': k_cache,
                             'KScale': k_scale, 'VCache': v_cache,
                             'VScale': v_scale, 'Pos': pos,
                             'BlockTable': block_table},
                     outputs={'Out': out},
                     attrs={'n_head': int(n_head),
                            'scale': float(scale or 0.0)})
    out.stop_gradient = True
    return out


def fused_multihead_attention(q, k, v, causal=False, scale=1.0,
                              sequence_parallel=False, name=None):
    """Fused [B, H, S, D] attention: Pallas flash attention on TPU where
    measured to win, naive composition elsewhere (TPU-native extension;
    the reference composes attention in nets.scaled_dot_product_attention).
    With sequence_parallel=True and a mesh carrying an 'sp' axis, lowers
    to ring attention (parallel/ring_attention.py) — the sequence shards
    across devices and k/v blocks rotate over ICI, O(S·S/P) memory."""
    helper = LayerHelper('fused_multihead_attention', name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        type='fused_multihead_attention',
        inputs={'Q': q, 'K': k, 'V': v}, outputs={'Out': out},
        attrs={'causal': causal, 'scale': scale,
               'sequence_parallel': sequence_parallel}, infer_shape=False)
    out.shape = q.shape  # same [B, H, S, D] as the query
    return out
