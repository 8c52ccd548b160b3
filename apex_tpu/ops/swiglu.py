"""Fused bias + SwiGLU.

Reference: csrc/megatron/fused_bias_swiglu.cpp (fwd/bwd) — given
``y = x + bias`` with ``y = [y1 ‖ y2]`` split on the last dim,

    out = silu(y1) · y2,   silu(z) = z·sigmoid(z)

Backward (derived, matches fused_bias_swiglu.cu):
    dsilu(z) = sigmoid(z)·(1 + z·(1-sigmoid(z)))
    dy1 = g · y2 · dsilu(y1);  dy2 = g · silu(y1);  dbias = Σ dy

Elementwise throughout — XLA fuses it into the surrounding GEMMs; custom VJP
avoids saving silu activations (recomputes from x+bias like the reference).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops._pallas_utils import param_cotangent

__all__ = ["fused_bias_swiglu", "fused_bias_swiglu_paired", "bias_swiglu_ref"]


def _silu(z):
    return z * jax.nn.sigmoid(z)


def bias_swiglu_ref(x, bias=None):
    y = x.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y1, y2 = jnp.split(y, 2, axis=-1)
    return (_silu(y1) * y2).astype(x.dtype)


@jax.custom_vjp
def _bias_swiglu(x, bias):
    return bias_swiglu_ref(x, bias)


def _fwd(x, bias):
    return bias_swiglu_ref(x, bias), (x, bias)


def _bwd(res, g):
    x, bias = res
    y = x.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y1, y2 = jnp.split(y, 2, axis=-1)
    g32 = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(y1)
    dsilu = sig * (1.0 + y1 * (1.0 - sig))
    dy1 = g32 * y2 * dsilu
    dy2 = g32 * _silu(y1)
    dx = jnp.concatenate([dy1, dy2], axis=-1)
    dbias = None
    if bias is not None:
        reduce_axes = tuple(range(dx.ndim - 1))
        dbias = param_cotangent(
            jnp.sum(dx, axis=reduce_axes).astype(bias.dtype), bias)
    return dx.astype(x.dtype), dbias


_bias_swiglu.defvjp(_fwd, _bwd)


def fused_bias_swiglu(x: jax.Array, bias: Optional[jax.Array] = None):
    """SwiGLU over the (even) last dim of ``x + bias``
    (reference fused_bias_swiglu.cpp:9-10)."""
    if x.shape[-1] % 2 != 0:
        raise ValueError("fused_bias_swiglu needs an even last dimension")
    return _bias_swiglu(x, bias)


@jax.custom_vjp
def _bias_swiglu_paired(y, bias):
    yf = y.astype(jnp.float32)
    if bias is not None:
        yf = yf + bias.astype(jnp.float32)
    return (_silu(yf[..., 0, :]) * yf[..., 1, :]).astype(y.dtype)


def _paired_fwd(y, bias):
    return _bias_swiglu_paired(y, bias), (y, bias)


def _paired_bwd(res, g):
    y, bias = res
    yf = y.astype(jnp.float32)
    if bias is not None:
        yf = yf + bias.astype(jnp.float32)
    y1 = yf[..., 0, :]
    y2 = yf[..., 1, :]
    g32 = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(y1)
    dsilu = sig * (1.0 + y1 * (1.0 - sig))
    dy = jnp.stack([g32 * y2 * dsilu, g32 * _silu(y1)], axis=-2)
    dbias = None
    if bias is not None:
        reduce_axes = tuple(range(dy.ndim - bias.ndim))
        dbias = param_cotangent(
            jnp.sum(dy, axis=reduce_axes).astype(bias.dtype), bias)
    return dy.astype(y.dtype), dbias


_bias_swiglu_paired.defvjp(_paired_fwd, _paired_bwd)


def fused_bias_swiglu_paired(y: jax.Array,
                             bias: Optional[jax.Array] = None) -> jax.Array:
    """SwiGLU on the paired layout ``[..., 2, f]`` — gate at index 0, up at
    index 1 on the second-to-last dim.

    Tensor-parallel-safe variant of :func:`fused_bias_swiglu`: sharding the
    trailing ``f`` dim keeps each shard a (gate, up) pair, whereas sharding
    the concatenated ``[..., 2f]`` layout splits gate columns across ranks.
    Same math as the reference kernel (fused_bias_swiglu.cu), recompute-in-
    backward like the concat variant.
    """
    if y.shape[-2] != 2:
        raise ValueError("paired layout requires shape [..., 2, f]")
    return _bias_swiglu_paired(y, bias)
