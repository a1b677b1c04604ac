"""Core Gluon layers (parity: python/mxnet/gluon/nn/basic_layers.py)."""
from __future__ import annotations

from typing import Optional

from ... import base as _base
from ... import initializer as init_mod
from ...ndarray import NDArray
from .. import block as _block
from ..block import Block, HybridBlock
from ..parameter import Parameter

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "Embedding",
           "BatchNorm", "LayerNorm", "RMSNorm", "GroupNorm", "InstanceNorm",
           "Flatten",
           "Activation", "LeakyReLU", "PReLU", "ELU", "SELU", "GELU", "Swish",
           "SiLU", "Lambda", "HybridLambda", "Identity"]


class Sequential(Block):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x, *args):
        for b in self._children.values():
            x = b(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        children = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*children[key])
            return net
        return children[key]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x, *args):
        for b in self._children.values():
            x = b(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        children = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*children[key])
            return net
        return children[key]

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully connected: out = act(x·Wᵀ + b) (parity: nn.Dense).

    Weight layout (units, in_units) matches MXNet/FullyConnected so exported
    checkpoints interchange.  Lowered to one MXU matmul by XLA.
    """

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._flatten = flatten
        self._activation = activation
        self.weight = self.params.get(
            "weight", shape=(units, in_units), dtype=dtype,
            init=weight_initializer, allow_deferred_init=True)
        self.bias = self.params.get(
            "bias", shape=(units,), dtype=dtype, init=bias_initializer,
            allow_deferred_init=True) if use_bias else None

    def infer_shape(self, x, *args):
        in_units = x.size // x.shape[0] if self._flatten else x.shape[-1]
        self.weight._set_shape((self._units, in_units))
        if self.bias is not None:
            self.bias._set_shape((self._units,))

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, num_hidden=self._units,
                               no_bias=bias is None, flatten=self._flatten)
        if self._activation is not None:
            out = F.Activation(out, act_type=self._activation)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return (f"Dense({shape[1] if shape and len(shape) > 1 else None} -> "
                f"{self._units}, "
                f"{self._activation if self._activation else 'linear'})")


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if self._rate > 0:
            return F.Dropout(x, p=self._rate, axes=self._axes)
        return F.identity(x)

    def __repr__(self):
        return f"Dropout(p = {self._rate}, axes={self._axes})"


class Embedding(HybridBlock):
    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = sparse_grad
        self.weight = self.params.get(
            "weight", shape=(input_dim, output_dim), dtype=dtype,
            init=weight_initializer, allow_deferred_init=True,
            grad_stype="row_sparse" if sparse_grad else "default")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim,
                           sparse_grad=self._sparse_grad)

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim})"


class BatchNorm(HybridBlock):
    """Batch normalization with moving stats (parity: nn.BatchNorm).

    The moving averages are updated functionally: inside a hybridized trace
    the update becomes an extra XLA output written back by the CachedOp.
    """

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._momentum = momentum
        self._eps = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self.gamma = self.params.get(
            "gamma", shape=(in_channels,), init=gamma_initializer,
            allow_deferred_init=True,
            differentiable=scale)
        self.beta = self.params.get(
            "beta", shape=(in_channels,), init=beta_initializer,
            allow_deferred_init=True, differentiable=center)
        self.running_mean = self.params.get(
            "running_mean", shape=(in_channels,),
            init=running_mean_initializer, allow_deferred_init=True,
            differentiable=False)
        self.running_var = self.params.get(
            "running_var", shape=(in_channels,),
            init=running_variance_initializer, allow_deferred_init=True,
            differentiable=False)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p._set_shape((c,))

    def forward(self, x):
        from ...ndarray import ops
        gamma = self.gamma.data()
        beta = self.beta.data()
        rmean = self.running_mean.data()
        rvar = self.running_var.data()
        out, mean, var = ops.BatchNorm(
            x, gamma, beta, rmean, rvar, eps=self._eps,
            momentum=self._momentum, fix_gamma=not self._scale,
            use_global_stats=self._use_global_stats, axis=self._axis,
            _internal_stats=True)
        training = _base.is_training() and not self._use_global_stats
        if training:
            m = self._momentum
            from ... import autograd
            with autograd.pause():
                rmean._rebind(m * rmean.jax + (1 - m) * mean.detach().jax)
                rvar._rebind(m * rvar.jax + (1 - m) * var.detach().jax)
        return out

    def __repr__(self):
        return (f"BatchNorm(axis={self._axis}, eps={self._eps}, "
                f"momentum={self._momentum})")


class SyncBatchNorm(BatchNorm):
    """Cross-device BatchNorm: under pjit/shard_map the batch axis reduction
    becomes a cross-chip psum automatically when the data is sharded; eager
    single-process behavior equals BatchNorm."""

    def __init__(self, in_channels=0, num_devices=None, **kwargs):
        super().__init__(in_channels=in_channels, **kwargs)


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        self.gamma = self.params.get(
            "gamma", shape=(in_channels,), init=gamma_initializer,
            allow_deferred_init=True, differentiable=scale)
        self.beta = self.params.get(
            "beta", shape=(in_channels,), init=beta_initializer,
            allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        self.gamma._set_shape((c,))
        self.beta._set_shape((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._eps)

    def __repr__(self):
        return f"LayerNorm(axis={self._axis}, eps={self._eps})"


class RMSNorm(HybridBlock):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` over the last axis, computed
    in float32 (no centring).  ``unit_offset=True``: the gain is
    ``1 + gamma`` and ``gamma`` starts at zero."""

    def __init__(self, epsilon=1e-5, gamma_initializer=None,
                 in_channels=0, unit_offset=False, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        self._unit_offset = bool(unit_offset)
        self.gamma = self.params.get(
            "gamma", shape=(in_channels,), allow_deferred_init=True,
            init=gamma_initializer or ("zeros" if unit_offset else "ones"))

    def infer_shape(self, x, *args):
        self.gamma._set_shape((x.shape[-1],))

    def forward(self, x):
        from ...ndarray import ops
        return ops.RMSNorm(x, self.gamma.data(), eps=self._eps,
                           unit_offset=self._unit_offset)

    def __repr__(self):
        return f"RMSNorm(eps={self._eps}, unit_offset={self._unit_offset})"


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._num_groups = num_groups
        self._eps = epsilon
        self.gamma = self.params.get(
            "gamma", shape=(in_channels,), init=gamma_initializer,
            allow_deferred_init=True, differentiable=scale)
        self.beta = self.params.get(
            "beta", shape=(in_channels,), init=beta_initializer,
            allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x, *args):
        c = x.shape[1]
        self.gamma._set_shape((c,))
        self.beta._set_shape((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._eps)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        self.gamma = self.params.get(
            "gamma", shape=(in_channels,), init=gamma_initializer,
            allow_deferred_init=True, differentiable=scale)
        self.beta = self.params.get(
            "beta", shape=(in_channels,), init=beta_initializer,
            allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x, *args):
        c = x.shape[1]
        self.gamma._set_shape((c,))
        self.beta._set_shape((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, eps=self._eps)


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.flatten(x)

    def __repr__(self):
        return "Flatten"


class Activation(HybridBlock):
    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self._act_type = activation

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=init_mod.Constant(0.25),
                 in_channels=1, **kwargs):
        super().__init__(**kwargs)
        self.alpha = self.params.get("alpha", shape=(in_channels,),
                                     init=alpha_initializer)

    def hybrid_forward(self, F, x, alpha):
        return F.LeakyReLU(x, gamma=alpha, act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def __init__(self, approximation="erf", **kwargs):
        super().__init__(**kwargs)
        self._approx = approximation

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="gelu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x)


SiLU = Swish


class Lambda(Block):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            from ... import ndarray as nd
            function = getattr(nd, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            from ... import ndarray as nd
            fn = getattr(nd, function)
            function = lambda F, *args: getattr(F, fn.__name__)(*args)
        self._func = function

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)


class Identity(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.identity(x)
