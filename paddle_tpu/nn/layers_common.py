"""nn layer classes (2.0 surface).

Analog of python/paddle/nn/layer/{common,conv,norm,pooling,activation}.py.
Built on the dygraph Layer base + nn.functional.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from ..dygraph.layers import Layer
from ..dygraph.tensor import Tensor
from ..initializer import ConstantInitializer, XavierInitializer
from ..param_attr import ParamAttr
from . import functional as F


class Linear(Layer):
    def __init__(self, in_features: int, out_features: int,
                 weight_attr=None, bias_attr=None, name=None,
                 dtype="float32"):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=XavierInitializer())
        self.bias = self.create_parameter(
            [out_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.linear(x, self.weight, self.bias)


class Conv2D(Layer):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, weight_attr=None,
                 bias_attr=None, data_format="NCHW"):
        super().__init__()
        k = [kernel_size] * 2 if isinstance(kernel_size, int) \
            else list(kernel_size)
        self._stride = stride
        self._padding = padding
        self._dilation = dilation
        self._groups = groups
        self._data_format = data_format
        self.weight = self.create_parameter(
            [out_channels, in_channels // groups] + k, attr=weight_attr,
            default_initializer=XavierInitializer())
        self.bias = self.create_parameter([out_channels], attr=bias_attr,
                                          is_bias=True)

    def forward(self, x):
        return F.conv2d(x, self.weight, self.bias, self._stride,
                        self._padding, self._dilation, self._groups,
                        self._data_format)


class Conv2DTranspose(Layer):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        k = [kernel_size] * 2 if isinstance(kernel_size, int) \
            else list(kernel_size)
        self._stride, self._padding = stride, padding
        self._dilation, self._groups = dilation, groups
        self.weight = self.create_parameter(
            [in_channels, out_channels // groups] + k, attr=weight_attr,
            default_initializer=XavierInitializer())
        self.bias = self.create_parameter([out_channels], attr=bias_attr,
                                          is_bias=True)

    def forward(self, x):
        return F.conv2d_transpose(x, self.weight, self.bias, self._stride,
                                  self._padding, self._dilation, self._groups)


class MaxPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, ceil_mode=False):
        super().__init__()
        self._args = (kernel_size, stride, padding, ceil_mode)

    def forward(self, x):
        return F.max_pool2d(x, *self._args)


class AvgPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, ceil_mode=False,
                 exclusive=True):
        super().__init__()
        self._args = (kernel_size, stride, padding, ceil_mode, exclusive)

    def forward(self, x):
        return F.avg_pool2d(x, *self._args)


class AdaptiveAvgPool2D(Layer):
    def __init__(self, output_size):
        super().__init__()
        self._output_size = output_size

    def forward(self, x):
        return F.adaptive_avg_pool2d(x, self._output_size)


class BatchNorm2D(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW"):
        super().__init__()
        self._momentum, self._epsilon = momentum, epsilon
        self._data_format = data_format
        self.weight = self.create_parameter(
            [num_features], attr=weight_attr,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter([num_features], attr=bias_attr,
                                          is_bias=True)
        self.register_buffer("_mean",
                             Tensor(np.zeros(num_features, np.float32)))
        self.register_buffer("_variance",
                             Tensor(np.ones(num_features, np.float32)))

    def forward(self, x):
        return F.batch_norm(x, self._mean, self._variance, self.weight,
                            self.bias, training=self.training,
                            momentum=self._momentum, epsilon=self._epsilon,
                            data_format=self._data_format)


BatchNorm = BatchNorm2D
BatchNorm1D = BatchNorm2D
BatchNorm3D = BatchNorm2D


class SyncBatchNorm(BatchNorm2D):
    """Cross-replica batch norm (analog of reference
    sync_batch_norm_op.cu): batch statistics psum'd over the data-parallel
    mesh axis via the sync_batch_norm op, so autograd, eval mode, and
    running-stat updates all behave like BatchNorm. Outside a mesh the op
    degrades to local statistics."""

    def forward(self, x):
        from ..dygraph.tape import run_op
        outs = run_op(
            "sync_batch_norm",
            {"X": [x], "Scale": [self.weight], "Bias": [self.bias],
             "Mean": [self._mean], "Variance": [self._variance]},
            {"momentum": self._momentum, "epsilon": self._epsilon,
             "is_test": not self.training,
             "data_format": self._data_format})
        if self.training:
            self._mean.set_value(outs["MeanOut"][0].value)
            self._variance.set_value(outs["VarianceOut"][0].value)
        return outs["Y"][0]

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        """Recursively convert BatchNorm layers to SyncBatchNorm (2.0 API)."""
        if isinstance(layer, BatchNorm2D) and not isinstance(
                layer, SyncBatchNorm):
            new = cls(layer.weight.shape[0], layer._momentum,
                      layer._epsilon, data_format=layer._data_format)
            new.weight = layer.weight
            new.bias = layer.bias
            new._mean = layer._mean
            new._variance = layer._variance
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer.add_sublayer(name, cls.convert_sync_batchnorm(sub))
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        n = int(np.prod(normalized_shape))
        self.weight = self.create_parameter(
            [n], attr=weight_attr,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter([n], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight,
                            self.bias, self._epsilon)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [num_channels], attr=weight_attr,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter([num_channels], attr=bias_attr,
                                          is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self.weight, self.bias,
                            self._epsilon)


class Embedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, dtype="float32"):
        super().__init__(dtype=dtype)
        self._padding_idx = padding_idx
        from ..initializer import NormalInitializer
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=NormalInitializer(0.0, 1.0))
        if padding_idx is not None:
            import jax.numpy as jnp
            w = self.weight.value
            self.weight.set_value(w.at[padding_idx].set(0.0))

    def forward(self, x):
        return F.embedding(x, self.weight, self._padding_idx)


class Dropout(Layer):
    def __init__(self, p=0.5, mode="upscale_in_train"):
        super().__init__()
        self.p = p
        self.mode = mode

    def forward(self, x):
        return F.dropout(x, self.p, training=self.training, mode=self.mode)


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self._axes = (start_axis, stop_axis)

    def forward(self, x):
        return x.flatten(*self._axes)


def _act_layer(name, fn):
    class _Act(Layer):
        def __init__(self, *a, **kw):
            super().__init__()
            self._a, self._kw = a, kw

        def forward(self, x):
            return fn(x, *self._a, **self._kw)
    _Act.__name__ = name
    return _Act


ReLU = _act_layer("ReLU", F.relu)
ReLU6 = _act_layer("ReLU6", F.relu6)
GELU = _act_layer("GELU", F.gelu)
Sigmoid = _act_layer("Sigmoid", F.sigmoid)
Tanh = _act_layer("Tanh", F.tanh)
Softmax = _act_layer("Softmax", F.softmax)
LogSoftmax = _act_layer("LogSoftmax", F.log_softmax)
LeakyReLU = _act_layer("LeakyReLU", F.leaky_relu)
SiLU = _act_layer("SiLU", F.silu)
Swish = _act_layer("Swish", F.swish)
Hardswish = _act_layer("Hardswish", F.hardswish)
Hardsigmoid = _act_layer("Hardsigmoid", F.hardsigmoid)
ELU = _act_layer("ELU", F.elu)
Softplus = _act_layer("Softplus", F.softplus)


class CrossEntropyLoss(Layer):
    def __init__(self, soft_label=False, ignore_index=-100,
                 reduction="mean", axis=-1):
        super().__init__()
        self._args = (soft_label, ignore_index, reduction, axis)

    def forward(self, input, label):
        return F.cross_entropy(input, label, *self._args)


class MSELoss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self._reduction = reduction

    def forward(self, input, label):
        return F.mse_loss(input, label, self._reduction)


class L1Loss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self._reduction = reduction

    def forward(self, input, label):
        return F.l1_loss(input, label, self._reduction)


class BCEWithLogitsLoss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self._reduction = reduction

    def forward(self, logit, label):
        return F.binary_cross_entropy_with_logits(logit, label,
                                                  self._reduction)


class NLLLoss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self._reduction = reduction

    def forward(self, input, label):
        return F.nll_loss(input, label, self._reduction)


class KLDivLoss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self._reduction = reduction

    def forward(self, input, label):
        return F.kl_div(input, label, self._reduction)


class SmoothL1Loss(Layer):
    def __init__(self, reduction="mean", delta=1.0):
        super().__init__()
        self._args = (reduction, delta)

    def forward(self, input, label):
        return F.smooth_l1_loss(input, label, *self._args)


class Pad2D(Layer):
    def __init__(self, padding, mode="constant", value=0.0):
        super().__init__()
        self._padding = padding if isinstance(padding, (list, tuple)) \
            else [padding] * 4
        self._mode, self._value = mode, value

    def forward(self, x):
        return F.pad(x, self._padding, self._mode, self._value)


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest"):
        super().__init__()
        self._args = (size, scale_factor, mode)

    def forward(self, x):
        return F.interpolate(x, *self._args)
