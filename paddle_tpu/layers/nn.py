"""Neural-network layers DSL (reference: ``python/paddle/fluid/layers/nn.py``,
12.4k LoC / 172 functions — built here op-by-op on the TPU op registry)."""

import math

import numpy as np

from .. import unique_name
from ..framework import Variable
from ..layer_helper import LayerHelper
from ..initializer import (ConstantInitializer, Initializer,
                           NormalInitializer, UniformInitializer)
from ..param_attr import ParamAttr
from .. import core

__all__ = [
    "fc",
    "embedding",
    "conv2d",
    "depthwise_conv2d",
    "conv2d_transpose",
    "pool2d",
    "batch_norm",
    "layer_norm",
    "dropout",
    "softmax",
    "log_softmax",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "square_error_cost",
    "smooth_l1",
    "huber_loss",
    "label_smooth",
    "mean",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "matmul",
    "mul",
    "fused_dropout_add_ln",
    "fused_multihead_attention",
    "rms_norm",
    "rotary_embedding",
    "swiglu",
    "relu2",
    "causal_conv1d",
    "rms_norm_gated",
    "ssd_scan",
    "moe_route",
    "moe_experts",
    "moe_count_rows",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "reshape",
    "transpose",
    "concat",
    "split",
    "squeeze",
    "unsqueeze",
    "flatten",
    "stack",
    "unstack",
    "expand",
    "slice",
    "gather",
    "gather_nd",
    "scatter",
    "one_hot",
    "topk",
    "argmax",
    "argmin",
    "argsort",
    "shape",
    "clip",
    "clip_by_norm",
    "l2_normalize",
    "relu",
    "prelu",
    "leaky_relu",
    "pad",
    "pad2d",
    "image_resize",
    "resize_bilinear",
    "resize_nearest",
    "where",
    "cond_not_supported",
    "lod_reset",
    "group_norm",
    "cos_sim",
    "unsqueeze",
]


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully connected layer (reference nn.py fc): mul (+ sum over multiple
    inputs) + bias + activation.  Lowered as one jnp.matmul per input —
    MXU-shaped; bias/act fuse in XLA."""
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, p_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_shape = [
            int(np.prod([abs(d) for d in input_shape[num_flatten_dims:]]))
        ] + [size]
        w = helper.create_parameter(
            attr=p_attr, shape=param_shape, dtype=dtype, is_bias=False
        )
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="sum",
            inputs={"X": mul_results},
            outputs={"Out": [pre_bias]},
        )
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """Embedding lookup (reference nn.py embedding → lookup_table op).
    is_sparse selects the reference's SelectedRows grad path; on TPU the
    grad is always XLA scatter-add, so the flag is accepted and ignored.

    is_distributed=True row-shards the table over the mesh's data axis
    when the program runs under ``CompiledProgram.with_data_parallel`` —
    the TPU-native replacement for the reference's parameter-server
    distributed lookup table (``transpiler/distribute_transpiler.py:
    353-376``, ``operators/distributed/parameter_prefetch.cc``): GSPMD
    partitions the lookup/scatter-grad with the id exchange over ICI
    instead of RPC remote_prefetch, and the optimizer state shards with
    the table."""
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False
    )
    if is_distributed:
        w._is_distributed = True
    tmp = helper.create_variable_for_type_inference(dtype)
    padding_idx = (
        -1 if padding_idx is None
        else padding_idx if padding_idx >= 0
        else size[0] + padding_idx
    )
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [tmp]},
        attrs={
            "is_sparse": is_sparse,
            "is_distributed": is_distributed,
            "padding_idx": padding_idx,
        },
    )
    return tmp


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return [int(x) for x in v]
    return [int(v)] * n


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d", **locals())
    dtype = input.dtype
    groups = groups or 1
    num_channels = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    filter_shape = [num_filters, num_channels // groups] + filter_size
    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    std = (2.0 / fan_in) ** 0.5
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=NormalInitializer(0.0, std),
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    op_type = (
        "depthwise_conv2d"
        if groups == num_channels and num_filters % num_channels == 0
        else "conv2d"
    )
    helper.append_op(
        type=op_type,
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "data_format": data_format,
        },
    )
    # bias is per output CHANNEL: axis 1 for NCHW, last for NHWC (a
    # layout-blind axis-1 add would silently bias over H instead)
    if data_format == "NCHW":
        pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    else:
        nd = len(input.shape)
        pre_act = helper.append_bias_op(pre_bias, dim_start=nd - 1,
                                        dim_end=nd)
    return helper.append_activation(pre_act)


def depthwise_conv2d(input, num_filters, filter_size, **kwargs):
    groups = (input.shape[1]
              if kwargs.get("data_format", "NCHW") == "NCHW"
              else input.shape[-1])
    return conv2d(input, num_filters, filter_size, groups=groups,
                  **kwargs)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv2d_transpose", **locals())
    dtype = input.dtype
    groups = groups or 1
    num_channels = input.shape[1]
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    if filter_size is None:
        raise ValueError("filter_size required (output_size inference TBD)")
    filter_size = _pair(filter_size)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True,
           data_format="NCHW"):
    helper = LayerHelper("pool2d", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": _pair(pool_size),
            "strides": _pair(pool_stride),
            "paddings": _pair(pool_padding),
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
            "data_format": data_format,
        },
    )
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    helper = LayerHelper("batch_norm", **locals())
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    shape = [c]
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=shape, dtype="float32",
        default_initializer=ConstantInitializer(1.0),
    )
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=shape, dtype="float32", is_bias=True
    )
    from ..param_attr import ParamAttr

    mean = helper.create_parameter(
        attr=ParamAttr(name=moving_mean_name, trainable=False),
        shape=shape, dtype="float32",
        default_initializer=ConstantInitializer(0.0),
    )
    variance = helper.create_parameter(
        attr=ParamAttr(name=moving_variance_name, trainable=False),
        shape=shape, dtype="float32",
        default_initializer=ConstantInitializer(1.0),
    )
    mean.stop_gradient = True
    variance.stop_gradient = True

    out = helper.create_variable_for_type_inference(dtype)
    saved_mean = helper.create_variable_for_type_inference("float32", True)
    saved_var = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="batch_norm",
        inputs={
            "X": [input], "Scale": [scale], "Bias": [bias],
            "Mean": [mean], "Variance": [variance],
        },
        outputs={
            "Y": [out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_var],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", **locals())
    dtype = input.dtype
    input_shape = input.shape
    param_shape = [int(np.prod([abs(d) for d in input_shape[begin_norm_axis:]]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr, shape=param_shape, dtype="float32",
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype="float32",
            is_bias=True,
        )
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference("float32", True)
    var = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"begin_norm_axis": begin_norm_axis, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    """Group normalization (reference layers/nn.py:3487; kernel
    group_norm_op.cc) over the channel axis of an NCHW tensor."""
    if data_layout != "NCHW":
        raise ValueError("group_norm supports data_layout='NCHW' only, "
                         "got %r" % (data_layout,))
    helper = LayerHelper("group_norm", **locals())
    dtype = input.dtype
    c = int(input.shape[1])
    inputs = {"X": [input]}
    if param_attr is not False:
        scale = helper.create_parameter(
            attr=helper.param_attr, shape=[c], dtype="float32",
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [scale]
    if bias_attr is not False:
        bias = helper.create_parameter(
            attr=helper.bias_attr, shape=[c], dtype="float32", is_bias=True,
        )
        inputs["Bias"] = [bias]
    out = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference("float32", True)
    var = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        type="group_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"groups": int(groups), "epsilon": float(epsilon)},
    )
    return helper.append_activation(out)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference("uint8", True)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="softmax", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="log_softmax", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    helper = LayerHelper("softmax_with_cross_entropy", **locals())
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={
            "soft_label": soft_label,
            "ignore_index": ignore_index,
            "axis": axis,
        },
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]},
        outputs={"Out": [out]},
        attrs={"ignore_index": ignore_index, "normalize": normalize},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="square_error_cost",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out]},
    )
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss", **locals())
    diff = helper.create_variable_for_type_inference(x.dtype, True)
    loss = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        type="smooth_l1_loss",
        inputs=inputs,
        outputs={"Diff": [diff], "Out": [loss]},
        attrs={"sigma": sigma if sigma is not None else 1.0},
    )
    return loss


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss", **locals())
    residual = helper.create_variable_for_type_inference(input.dtype, True)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="huber_loss",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out], "Residual": [residual]},
        attrs={"delta": delta},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        type="label_smooth", inputs=inputs, outputs={"Out": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def _reduce(op_type, input, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if dim is not None and not isinstance(dim, (list, tuple)):
        dim = [dim]
    helper.append_op(
        type=op_type,
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "dim": dim if dim is not None else [0],
            "keep_dim": keep_dim,
            "reduce_all": dim is None,
        },
    )
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="matmul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={
            "transpose_X": transpose_x,
            "transpose_Y": transpose_y,
            "alpha": float(alpha),
        },
    )
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="mul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={
            "x_num_col_dims": x_num_col_dims,
            "y_num_col_dims": y_num_col_dims,
        },
    )
    return out


def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type=op_type,
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_pow", x, y, axis, act, name)


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        type="reshape2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"shape": [int(s) for s in shape]},
    )
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        type="transpose2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": list(perm)},
    )
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", **locals())
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(
        type="concat",
        inputs={"X": list(input)},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", **locals())
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        attrs = {"num": num, "axis": dim, "sections": []}
    else:
        num = len(num_or_sections)
        attrs = {"num": 0, "axis": dim,
                 "sections": [int(s) for s in num_or_sections]}
    outs = [
        helper.create_variable_for_type_inference(input.dtype)
        for _ in range(num)
    ]
    helper.append_op(
        type="split", inputs={"X": [input]}, outputs={"Out": outs},
        attrs=attrs,
    )
    return outs


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(
        type="squeeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op(
        type="unsqueeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        type="flatten2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": axis},
    )
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack", **locals())
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(
        type="stack", inputs={"X": list(x)}, outputs={"Y": [out]},
        attrs={"axis": axis},
    )
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack", **locals())
    if num is None:
        num = x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in range(num)]
    helper.append_op(
        type="unstack", inputs={"X": [x]}, outputs={"Y": outs},
        attrs={"axis": axis, "num": num},
    )
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="expand", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"expand_times": [int(t) for t in expand_times]},
    )
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="slice", inputs={"Input": [input]}, outputs={"Out": [out]},
        attrs={"axes": list(axes), "starts": [int(s) for s in starts],
               "ends": [int(e) for e in ends]},
    )
    return out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gather", inputs={"X": [input], "Index": [index]},
        outputs={"Out": [out]},
    )
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gather_nd", inputs={"X": [input], "Index": [index]},
        outputs={"Out": [out]},
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]},
        attrs={"overwrite": overwrite},
    )
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot", **locals())
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"depth": depth},
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", **locals())
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="top_k", inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": int(k)},
    )
    return values, indices


def argmax(x, axis=0):
    helper = LayerHelper("arg_max", **locals())
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="arg_max", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def argmin(x, axis=0):
    helper = LayerHelper("arg_min", **locals())
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="arg_min", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def argsort(input, axis=-1, name=None):
    helper = LayerHelper("argsort", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    ids = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="argsort", inputs={"X": [input]},
        outputs={"Out": [out], "Indices": [ids]},
        attrs={"axis": axis},
    )
    return out, ids


def shape(input):
    helper = LayerHelper("shape", **locals())
    out = helper.create_variable_for_type_inference("int32", True)
    helper.append_op(
        type="shape", inputs={"Input": [input]}, outputs={"Out": [out]}
    )
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"min": float(min), "max": float(max)},
    )
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="clip_by_norm", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"max_norm": float(max_norm)},
    )
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    from . import ops as _ops

    sq = elementwise_mul(x, x)
    s = reduce_sum(sq, dim=axis, keep_dim=True)
    norm = _ops.sqrt(elementwise_add(s, fill_like_scalar(s, epsilon)))
    return elementwise_div(x, norm)


def fill_like_scalar(ref, value):
    from .tensor import fill_constant

    return fill_constant([1], ref.dtype, value)


def relu(x, name=None):
    helper = LayerHelper("relu", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="relu", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", **locals())
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        attr=helper.param_attr, shape=alpha_shape, dtype="float32",
        is_bias=False, default_initializer=ConstantInitializer(0.25),
    )
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="prelu", inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]}, attrs={"mode": mode},
    )
    return out


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="leaky_relu", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"alpha": alpha},
    )
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="pad", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "pad_value": float(pad_value)},
    )
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "mode": mode,
               "pad_value": float(pad_value), "data_format": data_format},
    )
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1):
    """Resize NCHW images (reference layers/nn.py:7483; interpolate_op.cc).

    TPU redesign: the output H/W must be static Python ints (XLA static
    shapes) — tensor-valued `out_shape`/`actual_shape` are rejected with
    a targeted error instead of the reference's runtime OutSize input.
    """
    resample = str(resample).upper()
    if resample not in ("BILINEAR", "NEAREST"):
        raise ValueError(
            "image_resize resample must be 'BILINEAR' or 'NEAREST', got %r"
            % (resample,))
    if actual_shape is not None or isinstance(out_shape, Variable):
        raise ValueError(
            "image_resize on TPU needs a static out_shape (list/tuple of "
            "ints); tensor-valued out_shape/actual_shape would make the "
            "compiled shape dynamic")
    h, w = int(input.shape[2]), int(input.shape[3])
    if out_shape is not None:
        if len(out_shape) != 2:
            raise ValueError("out_shape must be [out_h, out_w]")
        oh, ow = int(out_shape[0]), int(out_shape[1])
    elif scale is not None:
        oh, ow = int(h * float(scale)), int(w * float(scale))
    else:
        raise ValueError("one of out_shape and scale must be set")
    helper = LayerHelper("image_resize", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="bilinear_interp" if resample == "BILINEAR" else "nearest_interp",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"out_h": oh, "out_w": ow,
               "align_corners": bool(align_corners),
               "align_mode": int(align_mode)},
    )
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    """reference layers/nn.py:7706."""
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    """reference layers/nn.py:7811."""
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        actual_shape, align_corners)


def where(condition, x=None, y=None):
    helper = LayerHelper("where", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="where",
        inputs={"Condition": [condition], "X": [x], "Y": [y]},
        outputs={"Out": [out]},
    )
    return out


def cond_not_supported(*a, **k):
    raise NotImplementedError


def lod_reset(x, y=None, target_lod=None):
    helper = LayerHelper("lod_reset", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    if y is not None:
        inputs["Y"] = [y]
    helper.append_op(
        type="lod_reset", inputs=inputs, outputs={"Out": [out]},
        attrs={"target_lod": target_lod or []},
    )
    return out


def cos_sim(X, Y):
    xn = l2_normalize(X, axis=-1)
    yn = l2_normalize(Y, axis=-1)
    return reduce_sum(elementwise_mul(xn, yn), dim=-1, keep_dim=True)


def fused_dropout_add_ln(x, residual, dropout_prob=0.0, epsilon=1e-5,
                         param_attr=None, bias_attr=None, name=None):
    """``layer_norm(residual + dropout(x))`` over the LAST axis as one
    fused op (Pallas kernel on TPU, XLA expression elsewhere) — the
    transformer encoder's inter-GEMM glue without the intermediate HBM
    round-trips.  Creates LN scale/bias parameters of shape [D] like
    ``layer_norm(begin_norm_axis=ndim-1)``."""
    helper = LayerHelper("fused_dropout_add_ln", **locals())
    d = x.shape[-1]
    # params match layer_norm's exactly (float32 + is_bias) so the two
    # graph forms stay checkpoint-compatible under the same names
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[d], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=[d], dtype="float32",
        is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="fused_dropout_add_ln",
        inputs={"X": [x], "Residual": [residual], "Scale": [scale],
                "Bias": [bias]},
        outputs={"Out": [out]},
        attrs={"dropout_prob": float(dropout_prob),
               "epsilon": float(epsilon)},
    )
    return out


def fused_multihead_attention(q, k, v, bias=None, causal=False, scale=None,
                              dropout_rate=0.0, window=None, name=None):
    """Fused multi-head attention over q: [B, H, T, Dh], k: [B, Hkv, T,
    Dh] and v: [B, Hkv, T, Dv] (Dv may differ from Dh and is the output's
    width; Hkv may divide H: query head h reads head ``h // (H // Hkv)``
    of k and v, which are never expanded to H heads); on TPU this
    is a single Pallas flash-attention kernel (O(T) memory), elsewhere XLA
    attention.  `scale` defaults to 1/sqrt(Dh).  `window` (with `causal`):
    a query sees only the last `window` keys up to its own, and the
    kernels visit only that band.  `bias` is an additive
    key bias ([B, Tk] or [B,1,1,Tk], e.g. a padding mask); no gradient flows to it.  dropout_rate applies
    attention-probability dropout INSIDE the kernel (train mode only) —
    the [B,H,T,T] mask never materializes in HBM."""
    helper = LayerHelper("fused_multihead_attention", **locals())
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["BiasQK"] = [bias]
    attrs = {"causal": bool(causal)}
    if dropout_rate:
        attrs["dropout_rate"] = float(dropout_rate)
    if scale is not None:
        attrs["scale"] = float(scale)
    if window is not None:
        attrs["window"] = int(window)
    helper.append_op(
        type="fused_multihead_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs=attrs,
    )
    return out


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """``x / sqrt(mean(x^2) + epsilon) * scale`` over the LAST axis, the
    statistics in float32; creates the float32 scale [D], ones."""
    helper = LayerHelper("rms_norm", **locals())
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[input.shape[-1]], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="rms_norm", inputs={"X": [input], "Scale": [scale]},
        outputs={"Y": [out]}, attrs={"epsilon": float(epsilon)})
    return out


def rotary_embedding(x, rotary_dim=None, offset=0, theta=10000.0,
                     interleaved=True, frequency_scale=None, magnitude=1.0,
                     name=None):
    """Rotary position embedding over x: [..., T, Dh], positions 0..T-1 on
    the last axis but one.  The ``rotary_dim`` features of a head from
    ``offset`` on are rotated (default: from ``offset`` to the end), the
    others pass through: a head that is part position-free, part rotary.
    ``interleaved`` pairs features (2i, 2i+1); else (i, i + half).  Pair
    i turns by ``t * theta^(-2i/rotary_dim) * frequency_scale[i]``
    (``frequency_scale``: ``rotary_dim / 2`` factors, for a table scaled
    by frequency: interpolation, NTK, YaRN), and cos and sin are both
    times ``magnitude``."""
    helper = LayerHelper("rotary_embedding", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    rot = int(rotary_dim or x.shape[-1] - offset)
    attrs = {"rotary_dim": rot, "offset": int(offset),
             "theta": float(theta), "interleaved": bool(interleaved)}
    if frequency_scale is not None:
        if len(frequency_scale) != rot // 2:
            raise ValueError("frequency_scale holds %d factors for %d pairs"
                             % (len(frequency_scale), rot // 2))
        attrs["frequency_scale"] = [float(f) for f in frequency_scale]
    if magnitude != 1.0:
        attrs["magnitude"] = float(magnitude)
    helper.append_op(
        type="rotary_embedding", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs=attrs)
    return out


def swiglu(x, y, name=None):
    """``silu(x) * y``: the gate of a SwiGLU feed-forward."""
    helper = LayerHelper("swiglu", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="swiglu", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def relu2(x, name=None):
    """``relu(x)^2``: the activation of a relu² feed-forward."""
    helper = LayerHelper("relu2", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="relu2", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def causal_conv1d(input, filter_size, param_attr=None, bias_attr=None,
                  name=None):
    """Depthwise causal convolution along time over input [B, T, C]:
    ``out[t, c] = sum_k w[c, k] x[t - K + 1 + k, c] + b[c]`` with ``K =
    filter_size``, x zero before the first position.  Creates the filter
    [C, K] and the bias [C] (``bias_attr=False``: none)."""
    helper = LayerHelper("causal_conv1d", **locals())
    channels = input.shape[-1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[channels, int(filter_size)],
                                dtype="float32")
    inputs = {"X": [input], "Filter": [w]}
    b = helper.create_parameter(attr=helper.bias_attr, shape=[channels],
                                dtype="float32", is_bias=True)
    if b is not None:
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="causal_conv1d", inputs=inputs,
                     outputs={"Out": [out]})
    return out


def rms_norm_gated(input, gate, epsilon=1e-6, param_attr=None,
                   group_size=None, name=None):
    """``g = input * silu(gate)``, then ``g / sqrt(mean(g^2) + epsilon) *
    scale`` in float32 (Mamba-2's gated norm): the mean over the whole
    LAST axis (one group), or over each run of ``group_size`` channels of
    it (groups of the mixer's width); creates the float32 scale [D],
    ones."""
    helper = LayerHelper("rms_norm_gated", **locals())
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[input.shape[-1]], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"epsilon": float(epsilon)}
    if group_size:
        attrs["group_size"] = int(group_size)
    helper.append_op(
        type="rms_norm_gated",
        inputs={"X": [input], "Z": [gate], "Scale": [scale]},
        outputs={"Y": [out]}, attrs=attrs)
    return out


class _SSDInitializer(Initializer):
    """Mamba-2's own start (``mamba_ssm``): ``a_log``: ``log A`` with ``A
    ~ U[1, 16]``; ``dt_bias``: the inverse softplus of ``Δ ~ logU[1e-3,
    1e-1]`` (at least 1e-4), so that ``softplus(dt_bias)`` is Δ."""

    def __init__(self, kind):
        self.kind = kind

    def __call__(self, var, block):
        def op(type, x, out=None, **attrs):
            out = out or block.create_var(
                name=unique_name.generate(var.name + ".init"),
                shape=var.shape, dtype=var.dtype)
            block.append_op(type=type, inputs={"X": [x]},
                            outputs={"Out": [out]}, attrs=attrs)
            return out

        first = block.create_var(
            name=unique_name.generate(var.name + ".init"), shape=var.shape,
            dtype=var.dtype)
        if self.kind == "a_log":
            UniformInitializer(1.0, 16.0)(first, block)
            return op("log", first, var)
        UniformInitializer(math.log(1e-3), math.log(1e-1))(first, block)
        dt = op("clip", op("exp", first), min=1e-4, max=1.0)
        # softplus^-1(dt) = dt + log(1 - exp(-dt))
        rest = op("log", op("scale", op("exp", op("scale", dt, scale=-1.0)),
                            scale=-1.0, bias=1.0))
        return block.append_op(type="elementwise_add",
                               inputs={"X": [dt], "Y": [rest]},
                               outputs={"Out": [var]}, attrs={"axis": -1})


def ssd_scan(x, dt, b, c, chunk_size=256, a_log_attr=None, d_attr=None,
             dt_bias_attr=None, name=None):
    """Mamba-2's state-space-dual scan.  x [B, T, H, P]; dt [B, T, H], the
    step before its bias and softplus; b, c [B, T, N], one group shared by
    every head, or [B, T, G, N], head h reading group ``h // (H / G)``.
    ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per
    head ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t b_t^T`` (S: P x N, S_0 = 0),
    ``y_t = S_t c_t + D x_t``; returns y [B, T, H, P].  Computed in chunks
    of ``chunk_size`` (on the TPU by the Pallas kernels of
    ``ops/pallas/ssd_scan.py``), the decays and the carried state in
    float32.  Creates ``A_log``, ``D`` (ones) and ``dt_bias`` [H], float32;
    ``A_log`` and ``dt_bias`` start as Mamba-2's do."""
    helper = LayerHelper("ssd_scan", **locals())
    heads = x.shape[2]

    def param(attr, suffix, init):
        attr = ParamAttr._to_attr(attr)
        if attr.name is None:
            attr.name = unique_name.generate(helper.name + "." + suffix)
        return helper.create_parameter(attr=attr, shape=[heads],
                                       dtype="float32",
                                       default_initializer=init)

    a_log = param(a_log_attr, "A_log", _SSDInitializer("a_log"))
    d = param(d_attr, "D", ConstantInitializer(1.0))
    dt_bias = param(dt_bias_attr, "dt_bias", _SSDInitializer("dt_bias"))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="ssd_scan",
        inputs={"X": [x], "Dt": [dt], "ALog": [a_log], "B": [b], "C": [c],
                "D": [d], "DtBias": [dt_bias]},
        outputs={"Y": [out]}, attrs={"chunk_size": int(chunk_size)})
    return out


def moe_route(input, num_experts, top_k, scale=1.0, norm_topk_prob=True,
              center_bias=False, keep_input=None, param_attr=None,
              bias_attr=None, score_func="sigmoid", name=None):
    """The router of a top-k expert layer, over all ``num_experts``:
    ``s = sigmoid(x W)`` in float32 (``score_func="softmax"``: the
    softmax over all the experts), the ``top_k`` largest of ``s + b``
    (of ``log s + b`` under ``"softmax"``) chosen, gates ``scale * s_i / sum_chosen s_j`` (the sum left out
    unless ``norm_topk_prob``).  Creates W [D, num_experts] and the
    correction bias b [num_experts], which takes no gradient.  Returns
    ``(index [N, top_k] int32, gate [N, top_k] float32)``, N the rows of
    ``input`` flattened to [N, D].

    ``center_bias``: in training the choice is made not with b but with
    minus each expert's mean score over the step's rows (under
    ``"softmax"``: minus the log-score that ``top_k / num_experts`` of
    the rows give it more than; what balances
    the load of a model that is not trained yet; ``parallel/moe.py``
    ``sigmoid_topk_route``), and a third value is returned, the bias
    used [num_experts]: assign it to b after ``optimizer.minimize`` (b's
    name is ``bias_attr``'s) and a test-mode program routes by the last
    step's.

    ``keep_input``: a name; the rows the router read are left in a
    persistable variable of that name, the very numbers (behind an
    optimization barrier), for a check that routes them again."""
    if score_func not in ("sigmoid", "softmax"):
        raise ValueError("moe_route has no score_func %r" % (score_func,))
    helper = LayerHelper("moe_route", **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[input.shape[-1], num_experts],
        dtype="float32")
    b = helper.create_parameter(
        attr=helper.bias_attr, shape=[num_experts], dtype="float32",
        is_bias=True)
    b.stop_gradient = True
    index = helper.create_variable_for_type_inference("int32", True)
    gate = helper.create_variable_for_type_inference("float32")
    used = helper.create_variable_for_type_inference("float32", True)
    outputs = {"Index": [index], "Gate": [gate], "BiasOut": [used]}
    if keep_input:
        kept = helper.create_or_get_global_variable(
            keep_input, shape=[-1, input.shape[-1]], dtype=input.dtype)
        kept.stop_gradient = True
        outputs["XOut"] = [kept]
    helper.append_op(
        type="moe_route",
        inputs={"X": [input], "Weight": [w], "Bias": [b]},
        outputs=outputs,
        attrs={"top_k": int(top_k), "scale": float(scale),
               "norm_topk_prob": bool(norm_topk_prob),
               "center_bias": bool(center_bias),
               "keep_input": bool(keep_input), "score_func": score_func})
    return (index, gate, used) if center_bias else (index, gate)


def moe_experts(input, index, gate, expert_width, experts_held,
                first_expert=0, param_attr=None, experts_total=None,
                activation="swiglu", name=None):
    """The part of a top-k expert layer that the ``experts_held`` experts
    from ``first_expert`` on give: ``sum_k gate[t,k] * E_index[t,k](x_t)``
    over the choices that name a held expert, of width ``expert_width``:
    by ``activation``, ``E(x) = W_down (silu(W_gate x) * W_up x)``
    (``"swiglu"``) or ``E(x) = W_down relu(W_up x)^2`` (``"relu2"``, no
    gate weights).  Dropless: every row routed
    here is computed, whatever the imbalance, by grouped products whose
    work follows those rows.  ``index`` and ``gate`` come from
    :func:`moe_route` and count over all the layer's experts, held or
    not.  Creates each held expert's own ``<name>.<e>.gate`` (SwiGLU
    only), ``.up`` [D, F] and ``.down`` [F, D], e counted from 0 over the
    experts held,
    ``<name>`` and the initializer from ``param_attr`` (as a checkpoint of
    such a model stores them, an expert a tensor; the lowering stacks
    them).  ``experts_total``: how many experts ``index`` counts over;
    where given, the loop that walks the routed rows takes blocks of one
    and a half times the held experts' even share of a step's choices
    (``parallel/moe.py`` ``block_rows``), else of 8,192 rows.  Returns
    ``(out, rows)``; ``rows`` [held] int32 are the rows
    each held expert was given (see :func:`moe_count_rows`)."""
    helper = LayerHelper("moe_experts", **locals())
    d = input.shape[-1]
    attr = ParamAttr._to_attr(param_attr)
    prefix = attr.name or helper.name

    def weights(part, shape):
        return [helper.create_parameter(
            attr=ParamAttr(name="%s.%d.%s" % (prefix, e, part),
                           initializer=attr.initializer,
                           learning_rate=attr.learning_rate,
                           regularizer=attr.regularizer,
                           trainable=attr.trainable),
            shape=shape, dtype="float32") for e in range(experts_held)]

    if activation not in ("swiglu", "relu2"):
        raise ValueError("moe_experts has no activation %r" % (activation,))
    out = helper.create_variable_for_type_inference(input.dtype)
    rows = helper.create_variable_for_type_inference("int32", True)
    inputs = {"X": [input], "Index": [index], "Gate": [gate]}
    attrs = {"first_expert": int(first_expert),
             "experts_total": int(experts_total or 0)}
    if activation == "swiglu":
        inputs["WGate"] = weights("gate", [d, expert_width])
    else:
        attrs["activation"] = activation
    inputs["WUp"] = weights("up", [d, expert_width])
    inputs["WDown"] = weights("down", [expert_width, d])
    helper.append_op(type="moe_experts", inputs=inputs,
                     outputs={"Out": [out], "Rows": [rows]}, attrs=attrs)
    return out, rows


def moe_count_rows(rows, index, layer, experts_total=None, name=None):
    """Keeps an expert layer's counters on the device: a persistable
    int32 vector ``<name>`` of the rows given to each held expert so far,
    then the rows possible (tokens * top_k), the rows dispatch moved (a
    whole block for each block the layer's loop ran) and the steps,
    updated inside the step with no host sync.  ``observability.runtime.
    publish_moe_counters`` reads them into the metrics registry under the
    label ``layer``.  ``experts_total`` as :func:`moe_experts` was given
    it (the block's size).  Call it outside any recompute region."""
    helper = LayerHelper("moe_count_rows", **locals())
    stats = helper.create_or_get_global_variable(
        name or "moe_rows.layer%s" % layer, shape=[rows.shape[0] + 3],
        dtype="int32")
    stats.stop_gradient = True
    helper.set_variable_initializer(stats, ConstantInitializer(0))
    helper.append_op(
        type="moe_count_rows",
        inputs={"Rows": [rows], "Index": [index], "Stats": [stats]},
        outputs={"StatsOut": [stats]},
        attrs={"layer": str(layer),
               "experts_total": int(experts_total or 0)})
    return stats


# Reference parity: the reference keeps all of these names in ONE
# layers/nn.py module, so `from paddle.fluid.layers.nn import X` works
# for every entry.  This repo splits the implementation across
# nn_extra/nn_extra2 for file size; re-exporting them here restores the
# single-module import surface (nn_extra* import nothing from this
# module, so the late import is cycle-free).
#
# CAUTION for future edits to THIS module: the star-imports below bind
# layer ops over the builtins `sum` and `hash` (reference nn exports
# both).  Globals resolve at CALL time, so code ANYWHERE in this module
# (before or after this point) must not call those builtins
# unqualified — use builtins.sum / builtins.hash.
from .nn_extra import *  # noqa: E402,F401,F403
from .nn_extra2 import *  # noqa: E402,F401,F403
from .nn_extra import __all__ as _extra_all
from .nn_extra2 import __all__ as _extra2_all

__all__ = list(__all__) + list(_extra_all) + list(_extra2_all)


def _reexport_reference_nn_names():
    """The reference nn.py also hosts the sequence/rnn/beam/unary-op
    layer names; pull EXACTLY the reference-nn names this repo homes
    elsewhere into this module so `from ...layers.nn import X` covers
    the full reference nn __all__ (169 names).  The list is curated —
    a blanket re-export of those modules' __all__ would also drag in
    names like `abs` that shadow builtins this module's own code uses."""
    import sys

    from . import beam, detection, ops, sequence

    # ONLY names absent after the nn_extra star-imports above (names
    # those already bind — selu, sum, rank, roi_pool, lstm, ... — are
    # deliberately not listed; the hasattr guard is belt-and-braces)
    wanted = [
        "sequence_pool", "sequence_softmax", "sequence_expand",
        "sequence_pad", "sequence_unpad", "sequence_first_step",
        "sequence_last_step", "sequence_slice", "sequence_mask",
        "sequence_enumerate", "sequence_concat", "sequence_reverse",
        "beam_search", "beam_search_decode",
        "dynamic_lstm", "dynamic_gru",
        "roi_align",
        "log", "pow", "scale", "sign", "elu", "relu6", "stanh",
        "hard_sigmoid", "swish", "brelu", "soft_relu",
        "logical_and", "logical_or", "logical_xor", "logical_not",
    ]
    here = sys.modules[__name__]
    for name in wanted:
        if hasattr(here, name):
            continue
        for mod in (beam, detection, ops, sequence):
            if hasattr(mod, name):
                setattr(here, name, getattr(mod, name))
                __all__.append(name)
                break


_reexport_reference_nn_names()
