"""Core layers.

The user-facing layer zoo, re-providing the reference's gserver layers
(gserver/layers/: FullyConnectedLayer, ConvBaseLayer + exconv/cudnn_conv variants,
BatchNormalizationLayer, embeddings via TableProjection, pooling layers, MixedLayer
projections) and the fluid layer builders (python/paddle/v2/fluid/layers.py: fc:18,
embedding:90, conv2d:638, batch_norm:765). Each layer is a Module: params are explicit,
__call__ is pure, XLA fuses the bias/activation into the matmul/conv.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..ops import activations as A
from ..ops import conv as conv_ops
from ..ops import norm as norm_ops
from ..ops import pallas_kernels as pk
from ..ops import pool as pool_ops
from ..ops.random import dropout as dropout_op
from . import initializer as I
from .module import Module


def _act(act: Union[None, str, Callable]):
    if act is None:
        return lambda x: x
    if callable(act):
        return act
    return A.get(act)


class Linear(Module):
    """Fully-connected layer (ref: gserver/layers/FullyConnectedLayer.cpp; fluid fc)."""

    def __init__(self, in_dim: int, out_dim: int, act: Union[None, str, Callable] = None,
                 bias: bool = True, w_init: Optional[I.Initializer] = None,
                 name: str = "fc"):
        super().__init__()
        self.in_dim, self.out_dim = in_dim, out_dim
        self.act = _act(act)
        self.use_bias = bias
        self.param("w", (in_dim, out_dim), w_init or I.xavier())
        if bias:
            self.param("b", (out_dim,), I.zeros)

    def __call__(self, params, x, **kw):
        x = x.reshape((x.shape[0], -1)) if x.ndim > 2 and x.shape[-1] != self.in_dim else x
        y = jnp.matmul(x, params["w"])
        if self.use_bias:
            y = y + params["b"]
        return self.act(y)


# gen-1 name
Fc = Linear


class Embedding(Module):
    """Lookup table (ref: gserver TableProjection/table_projection; fluid embedding:90;
    operators/lookup_table_op.cc — the sparse-grad path becomes SelectedRows-style
    updates in optimizer.sparse)."""

    def __init__(self, vocab_size: int, dim: int, padding_idx: Optional[int] = None,
                 w_init: Optional[I.Initializer] = None, dtype=jnp.float32):
        super().__init__()
        self.vocab_size, self.dim = vocab_size, dim
        self.padding_idx = padding_idx
        self.param("w", (vocab_size, dim), w_init or I.normal(0.0, 0.01),
                   dtype=dtype)

    def __call__(self, params, ids, **kw):
        out = jnp.take(params["w"], ids, axis=0)
        if self.padding_idx is not None:
            out = jnp.where((ids == self.padding_idx)[..., None], 0.0, out)
        return out


class Conv2D(Module):
    """2-D conv + bias + act, NHWC (ref: gserver/layers/ExpandConvLayer.cpp /
    CudnnConvLayer.cpp; fluid conv2d:638)."""

    def __init__(self, in_ch: int, out_ch: int, kernel: Union[int, Tuple[int, int]],
                 stride=1, padding=0, dilation=1, groups: int = 1,
                 act: Union[None, str, Callable] = None, bias: bool = True,
                 w_init: Optional[I.Initializer] = None):
        super().__init__()
        k = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
        self.kernel, self.in_ch = k, in_ch
        self.stride, self.padding, self.dilation, self.groups = stride, padding, dilation, groups
        self.act = _act(act)
        self.use_bias = bias
        self.param("w", k + (in_ch // groups, out_ch), w_init or I.msra())
        if bias:
            self.param("b", (out_ch,), I.zeros)

    def _is_stem7s2(self):
        # only shallow inputs (ImageNet's 3 channels): the rewrite exists
        # to deepen an MXU-starved contraction; with cin already deep it
        # just adds pad/reshape HBM traffic for nothing
        return (self.kernel == (7, 7) and self.stride in (2, (2, 2))
                and self.padding in (3, (3, 3))
                and self.dilation in (1, (1, 1))
                and self.groups == 1 and self.in_ch <= 4)

    def __call__(self, params, x, **kw):
        if self._is_stem7s2():
            # the classic ImageNet stem shape: routed through the exact
            # space-to-depth rewrite (ops/conv.py conv7s2) — a direct 7x7
            # over 3 channels is the measured MXU worst case
            # (docs/design/conv_mfu.md); same params, same math
            y = conv_ops.conv7s2(x, params["w"])
        else:
            y = conv_ops.conv2d(x, params["w"], stride=self.stride,
                                padding=self.padding, dilation=self.dilation,
                                groups=self.groups)
        if self.use_bias:
            y = y + params["b"]
        return self.act(y)


class Conv2DTranspose(Module):
    """ref: operators/conv_transpose_op.cc."""

    def __init__(self, in_ch: int, out_ch: int, kernel, stride=1, padding=0,
                 act=None, bias: bool = True):
        super().__init__()
        k = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
        self.stride, self.padding = stride, padding
        self.act = _act(act)
        self.use_bias = bias
        self.param("w", k + (in_ch, out_ch), I.msra())
        if bias:
            self.param("b", (out_ch,), I.zeros)

    def __call__(self, params, x, **kw):
        y = conv_ops.conv2d_transpose(x, params["w"], stride=self.stride,
                                      padding=self.padding)
        if self.use_bias:
            y = y + params["b"]
        return self.act(y)


class BatchNorm(Module):
    """Functional batch norm (ref: 3 BN impls in gserver + operators/batch_norm_op.cc).

    Running stats are non-trainable ``stat`` buffers (excluded from optimizer
    updates/decay). In train mode the updated stats are recorded into the
    ``mutable`` collector; merge them back with ``nn.apply_stat_updates``.
    """

    def __init__(self, channels: int, momentum: float = 0.9, eps: float = 1e-5,
                 act: Union[None, str, Callable] = None):
        super().__init__()
        self.momentum, self.eps = momentum, eps
        self.act = _act(act)
        self.param("gamma", (channels,), I.ones)
        self.param("beta", (channels,), I.zeros)
        self.stat("moving_mean", (channels,), I.zeros)
        self.stat("moving_var", (channels,), I.ones)

    def __call__(self, params, x, train: bool = False, mutable=None, **kw):
        y, nm, nv = norm_ops.batch_norm(
            x, params["gamma"], params["beta"], params["stats"]["moving_mean"],
            params["stats"]["moving_var"], train=train, momentum=self.momentum,
            eps=self.eps)
        if train:
            self.record_stats(mutable, {"moving_mean": nm, "moving_var": nv})
        return self.act(y)


class LayerNorm(Module):
    def __init__(self, dim: int, eps: float = 1e-5):
        super().__init__()
        self.eps = eps
        self.param("gamma", (dim,), I.ones)
        self.param("beta", (dim,), I.zeros)

    def __call__(self, params, x, **kw):
        return norm_ops.layer_norm(x, params["gamma"], params["beta"], self.eps)


class Dropout(Module):
    """ref: operators/dropout_op.cc; needs rng passed at call time."""

    def __init__(self, rate: float):
        super().__init__()
        self.rate = rate

    def __call__(self, params, x, train: bool = False, rng: Optional[jax.Array] = None, **kw):
        if not train or rng is None:
            return x
        return dropout_op(x, self.rate, rng, train=True)


class MaxPool2D(Module):
    def __init__(self, kernel, stride=None, padding=0):
        super().__init__()
        self.kernel, self.stride, self.padding = kernel, stride, padding

    def __call__(self, params, x, **kw):
        return pool_ops.max_pool2d(x, self.kernel, self.stride, self.padding)


class AvgPool2D(Module):
    def __init__(self, kernel, stride=None, padding=0):
        super().__init__()
        self.kernel, self.stride, self.padding = kernel, stride, padding

    def __call__(self, params, x, **kw):
        return pool_ops.avg_pool2d(x, self.kernel, self.stride, self.padding)


class RMSNorm(Module):
    """Root-mean-square norm, no centring and no bias (Zhang & Sennrich
    2019): the statistics and the scaling are computed in float32 whatever
    dtype comes in, and the result is returned in float32 — the caller casts
    it to its matmul operand dtype."""

    def __init__(self, dim: int, eps: float = 1e-6, dtype=jnp.float32):
        super().__init__()
        self.eps = eps
        self.param("gamma", (dim,), I.ones, dtype=dtype)

    def __call__(self, params, x, **kw):
        x = x.astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * params["gamma"].astype(
            jnp.float32)


class SwiGLU(Module):
    """Gated feed-forward ``(silu(x W_g) * x W_u) W_d`` without biases.
    Operands in the weights' dtype, every product accumulated in float32;
    returns float32."""

    def __init__(self, dim: int, width: int,
                 w_init: Optional[I.Initializer] = None, dtype=jnp.float32):
        super().__init__()
        init = w_init or I.normal(0.0, 0.02)
        self.param("w_gate", (dim, width), init, dtype=dtype)
        self.param("w_up", (dim, width), init, dtype=dtype)
        self.param("w_down", (width, dim), init, dtype=dtype)

    def __call__(self, params, x, **kw):
        dt = params["w_gate"].dtype
        x = x.astype(dt)
        g = jnp.dot(x, params["w_gate"], preferred_element_type=jnp.float32)
        u = jnp.dot(x, params["w_up"], preferred_element_type=jnp.float32)
        return jnp.dot((jax.nn.silu(g) * u).astype(dt), params["w_down"],
                       preferred_element_type=jnp.float32)


class ReluSquaredMLP(Module):
    """``down(relu(up(x)) ** 2)``: the ungated two-matrix feed-forward
    (``relu2``) of Nemotron-H's experts. Operands in the weights' dtype,
    float32 accumulation, the activation in float32."""

    def __init__(self, dim: int, hidden: int,
                 w_init: Optional[I.Initializer] = None, dtype=jnp.float32):
        super().__init__()
        init = w_init or I.normal(0.0, 0.02)
        self.param("w_up", (dim, hidden), init, dtype=dtype)
        self.param("w_down", (hidden, dim), init, dtype=dtype)

    def __call__(self, params, x, **kw):
        dt = params["w_up"].dtype
        u = jnp.dot(x.astype(dt), params["w_up"],
                    preferred_element_type=jnp.float32)
        return jnp.dot(jnp.square(jax.nn.relu(u)).astype(dt),
                       params["w_down"], preferred_element_type=jnp.float32)


class ShortConv(Module):
    """Gated short convolution (the ``conv`` operator of LFM2): ``[B | C |
    x] = u W_in``; ``z = B * x``; ``c_t = sum_j w[:, j] * z_{t - (taps - 1)
    + j}`` — depthwise, causal, no bias; ``y = (C * c) W_out``. Its state is
    the last ``taps - 1`` rows of ``z``, fixed in size whatever the context.

    Operands of the two projections in the weights' dtype with float32
    accumulation; ``z`` is rounded to that dtype where it is made (it is
    what the state holds, so a step from the state and a whole sequence see
    the same values); the gates and the taps' sum are float32."""

    def __init__(self, dim: int, taps: int = 3,
                 w_init: Optional[I.Initializer] = None, dtype=jnp.float32):
        super().__init__()
        self.dim, self.taps = dim, taps
        init = w_init or I.normal(0.0, 0.02)
        self.param("w_in", (dim, 3 * dim), init, dtype=dtype)
        # PyTorch's Conv1d default: uniform(+-1/sqrt(fan_in)), fan_in = taps
        self.param("w_conv", (dim, taps),
                   I.uniform(-taps ** -0.5, taps ** -0.5), dtype=dtype)
        self.param("w_out", (dim, dim), init, dtype=dtype)

    def _gates(self, params, u):
        dt = params["w_in"].dtype
        bcx = jnp.dot(u.astype(dt), params["w_in"],
                      preferred_element_type=jnp.float32)
        b, c, x = jnp.split(bcx, 3, axis=-1)
        return (b * x).astype(dt), c

    def _out(self, params, c_gate, taps_of):
        """``taps_of(j)``: the rows tap j multiplies, [..., dim]."""
        w = params["w_conv"].astype(jnp.float32)
        c = sum(w[:, j] * taps_of(j).astype(jnp.float32)
                for j in range(self.taps))
        dt = params["w_out"].dtype
        return jnp.dot((c_gate * c).astype(dt), params["w_out"],
                       preferred_element_type=jnp.float32)

    def __call__(self, params, u, tail=None, lengths=None, **kw):
        """u [B, T, dim] -> (y [B, T, dim] f32, new tail [B, taps - 1, dim]).
        ``tail``: the state the sequence continues from (zeros: it starts
        here); ``lengths`` [B]: each row's live length, where the new tail
        is taken (T when None)."""
        z, c_gate = self._gates(params, u)
        B, T, _ = z.shape
        keep = self.taps - 1
        if tail is None:
            tail = jnp.zeros((B, keep, self.dim), z.dtype)
        zz = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
        y = self._out(params, c_gate, lambda j: zz[:, j:j + T])
        n = jnp.full((B,), T, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        at = n[:, None] + jnp.arange(keep, dtype=jnp.int32)[None, :]
        return y, jnp.take_along_axis(zz, at[:, :, None], axis=1)

    def step(self, params, u, tail):
        """One position: u [B, dim], tail [B, taps - 1, dim] -> (y [B, dim]
        f32, the tail rolled by one)."""
        z, c_gate = self._gates(params, u)
        zz = jnp.concatenate([tail, z[:, None].astype(tail.dtype)], axis=1)
        return self._out(params, c_gate, lambda j: zz[:, j]), zz[:, 1:]


def _log_uniform_dt_bias(low: float, high: float, floor: float):
    """``dt_bias`` as the published Mamba-2 draws it: the inverse softplus
    of a step log-uniform in [low, high], floored at ``floor``."""
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        step = jnp.maximum(jnp.exp(u * (jnp.log(high) - jnp.log(low))
                                   + jnp.log(low)), floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return init


class Mamba2Mixer(Module):
    """The Mamba-2 mixer (Nemotron-H's ``M`` layer): ``[z | xBC | dt] = u
    W_in``; ``xBC = silu(conv1d(xBC))`` (depthwise, causal, ``taps`` taps,
    with bias); ``[x | B | C] = xBC`` (``heads x head_dim`` | ``groups x
    state`` | the same); ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(a_log)``, a head each. Head h of group ``h // (heads // groups)``
    keeps ``S`` [head_dim, state]: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    (x) B_t``, ``y_t = S_t C_t + D x_t``. Then ``y = RMSNorm_groups(y *
    silu(z))`` (the gate BEFORE the norm, ``groups`` groups, one gain over
    the whole width) and ``y W_out``.

    Its state, whatever the context: ``S`` of every head, float32, packed
    as ops/pallas_kernels.ssm_pack lays it out ([heads // 2, state, 2
    head_dim]), and the last ``taps - 1`` rows of ``xBC`` as they enter
    the convolution, in the weights' dtype (rounded where they are made, so
    a step from the state and a whole sequence see the same values). A
    sequence runs the chunked scan (pk.ssd_chunk_scan), a step the
    in-place update (pk.ssm_state_update). Products take operands in the
    weights' dtype with float32 accumulation; ``dt``, ``exp(dt A)``, the
    taps' sum, the gate, the norm and ``S`` are float32."""

    def __init__(self, dim: int, *, heads: int, head_dim: int, groups: int,
                 state: int, taps: int = 4, chunk: int = 128,
                 eps: float = 1e-5, dt_min: float = 1e-3,
                 dt_max: float = 0.1, dt_floor: float = 1e-4,
                 w_init: Optional[I.Initializer] = None, dtype=jnp.float32):
        super().__init__()
        self.heads, self.head_dim, self.groups = heads, head_dim, groups
        self.state, self.taps, self.chunk, self.eps = state, taps, chunk, eps
        self.inner = heads * head_dim
        self.conv_dim = self.inner + 2 * groups * state
        init = w_init or I.normal(0.0, 0.02)
        self.param("w_in", (dim, self.inner + self.conv_dim + heads), init,
                   dtype=dtype)
        # PyTorch's Conv1d default: uniform(+-1/sqrt(fan_in)), fan_in = taps
        bound = taps ** -0.5
        self.param("w_conv", (self.conv_dim, taps),
                   I.uniform(-bound, bound), dtype=dtype)
        self.param("b_conv", (self.conv_dim,), I.uniform(-bound, bound),
                   dtype=dtype)
        self.param("dt_bias", (heads,),
                   _log_uniform_dt_bias(dt_min, dt_max, dt_floor))
        self.param("a_log", (heads,), lambda key, shape, dtype=jnp.float32:
                   jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0)))
        self.param("d", (heads,), I.ones)
        self.param("norm_gamma", (self.inner,), I.ones, dtype=dtype)
        self.param("w_out", (self.inner, dim), init, dtype=dtype)

    def _project(self, params, u):
        dt_ = params["w_in"].dtype
        zxd = jnp.dot(u.astype(dt_), params["w_in"],
                      preferred_element_type=jnp.float32)
        z = zxd[..., :self.inner]
        xbc = zxd[..., self.inner:self.inner + self.conv_dim].astype(dt_)
        dt = jax.nn.softplus(zxd[..., self.inner + self.conv_dim:]
                             + params["dt_bias"].astype(jnp.float32))
        return z, xbc, dt

    def _conv(self, params, taps_of):
        """``taps_of(j)``: the rows tap j multiplies -> (x [..., heads,
        head_dim], B, C [..., groups, state]) f32, after the silu."""
        w = params["w_conv"].astype(jnp.float32)
        c = params["b_conv"].astype(jnp.float32) + sum(
            w[:, j] * taps_of(j).astype(jnp.float32)
            for j in range(self.taps))
        c = jax.nn.silu(c)
        lead, gn = c.shape[:-1], self.groups * self.state
        return (c[..., :self.inner].reshape(lead + (self.heads,
                                                    self.head_dim)),
                c[..., self.inner:self.inner + gn].reshape(
                    lead + (self.groups, self.state)),
                c[..., self.inner + gn:].reshape(
                    lead + (self.groups, self.state)))

    def _out(self, params, y, x, z):
        """(S C, x, z) -> the mixer's output: + D x, gate, grouped norm,
        ``W_out``."""
        y = y + params["d"].astype(jnp.float32)[:, None] * x
        lead = z.shape[:-1]
        y = y.reshape(lead + (self.inner,)) * jax.nn.silu(z)
        g = y.reshape(lead + (self.groups, self.inner // self.groups))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + self.eps)
        y = g.reshape(lead + (self.inner,)) \
            * params["norm_gamma"].astype(jnp.float32)
        dt_ = params["w_out"].dtype
        return jnp.dot(y.astype(dt_), params["w_out"],
                       preferred_element_type=jnp.float32)

    def __call__(self, params, u, lengths=None, *, route=None, **kw):
        """u [B, T, dim], a sequence from its start -> (y [B, T, dim] f32,
        S packed [B, heads // 2, state, 2 head_dim] f32, tail [B, taps -
        1, conv_dim]) — state and tail AT each row's own length
        (``lengths`` [B]; T when None), whatever the padding holds."""
        z, xbc, dt = self._project(params, u)
        B, T, _ = xbc.shape
        keep = self.taps - 1
        zz = jnp.concatenate(
            [jnp.zeros((B, keep, self.conv_dim), xbc.dtype), xbc], axis=1)
        x, bm, cm = self._conv(params, lambda j: zz[:, j:j + T])
        y, state = pk.ssd_chunk_scan(
            x, dt, -jnp.exp(params["a_log"].astype(jnp.float32)), bm, cm,
            lengths, chunk=self.chunk, dtype=params["w_in"].dtype,
            route=route)
        n = jnp.full((B,), T, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        at = n[:, None] + jnp.arange(keep, dtype=jnp.int32)[None, :]
        tail = jnp.take_along_axis(zz, at[:, :, None], axis=1)
        return self._out(params, y, x, z), state, tail

    def step(self, params, u, state, tail, live=None, *, route=None):
        """One position: u [B, dim], the slot's ``state`` and ``tail`` ->
        (y [B, dim] f32, the state after the position — in place on the
        kernel route, untouched where ``live`` [B] is False — and the
        tail rolled by one)."""
        z, xbc, dt = self._project(params, u)
        zz = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)],
                             axis=1)
        x, bm, cm = self._conv(params, lambda j: zz[:, j])
        y, state = pk.ssm_state_update(
            state, x, dt, -jnp.exp(params["a_log"].astype(jnp.float32)),
            bm, cm, live, route=route)
        return self._out(params, y, x, z), state, zz[:, 1:]
