"""Fused Conv + bias + ReLU (+ mask / frozen scale-bias) ops.

Reference: ``apex/contrib/conv_bias_relu/conv_bias_relu.py:10`` — four
autograd Functions (``ConvBiasReLU``, ``ConvBias``, ``ConvBiasMaskReLU``,
``ConvFrozenScaleBiasReLU``) backed by the cudnn-frontend v8 fusion
engine (contrib/csrc/conv_bias_relu.cpp + 2k LoC of vendored
cudnn-frontend headers).

On TPU this entire component is an XLA fusion, *verified*, not assumed
(v5e, round 2): the compiled HLO for a jitted ``conv → +bias → relu``
chain (NHWC bf16 64×56×56×64 → 3x3×64) contains exactly one
convolution, emitted as a ``kOutput`` fusion whose fused computation
carries the bias add and the relu ``maximum`` — the elementwise
epilogue rides the conv's output window write, which is exactly what
the cudnn-frontend fusion engine buys the reference.  Wall-clock deltas
vs the bare conv were within run-to-run noise at this shape (the HLO,
not the timer, is the ground truth here; not measured on today's
code).  ``tests/test_contrib_ops.py`` asserts numerics;
``python -m apex_tpu.contrib.conv_bias_relu.conv_bias_relu`` reproduces
the timing on a chip.

API parity: same positional signatures (x, weight, bias, padding,
stride), NHWC x HWIO layouts (the reference's fast path is NHWC too),
autodiff via plain ``jax.grad`` (no custom_vjp needed — XLA generates
the fused dgrad/wgrad epilogues).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "ConvBiasReLU",
    "ConvBias",
    "ConvBiasMaskReLU",
    "ConvFrozenScaleBiasReLU",
]


def _conv(x, weight, padding, stride):
    if isinstance(padding, int):
        padding = [(padding, padding), (padding, padding)]
    if isinstance(stride, int):
        stride = (stride, stride)
    return jax.lax.conv_general_dilated(
        x, weight.astype(x.dtype), stride, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def ConvBiasReLU(x, weight, bias, padding=1, stride=1):
    """relu(conv(x, w) + b) — one fused XLA computation under jit."""
    return jax.nn.relu(_conv(x, weight, padding, stride)
                       + bias.reshape(-1).astype(x.dtype))


def ConvBias(x, weight, bias, padding=1, stride=1):
    return _conv(x, weight, padding, stride) + bias.reshape(-1).astype(
        x.dtype)


def ConvBiasMaskReLU(x, weight, bias, mask, padding=1, stride=1):
    """relu((conv(x, w) + b) * mask) — the reference's masked variant
    (used for DropBlock-style regularization)."""
    y = _conv(x, weight, padding, stride) + bias.reshape(-1).astype(x.dtype)
    return jax.nn.relu(y * mask.astype(y.dtype))


def ConvFrozenScaleBiasReLU(x, weight, scale, bias, padding=1, stride=1):
    """relu(conv(x, w) * scale + bias) — conv into a folded frozen-BN
    affine (reference ConvFrozenScaleBiasReLU_)."""
    y = _conv(x, weight, padding, stride)
    return jax.nn.relu(y * scale.reshape(-1).astype(y.dtype)
                       + bias.reshape(-1).astype(y.dtype))


def _measure():  # pragma: no cover - run manually on a chip
    import time

    import numpy as np

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(256, 56, 56, 64), jnp.float32)
    w = jnp.asarray(rs.randn(3, 3, 64, 64) * 0.05, jnp.float32)
    b = jnp.asarray(rs.randn(64), jnp.float32)

    bare = jax.jit(lambda x: _conv(x, w, 1, 1))
    fused = jax.jit(lambda x: ConvBiasReLU(x, w, b))

    def timeit(f):
        y = f(x); float(np.asarray(y).ravel()[0])
        t0 = time.perf_counter()
        for _ in range(20):
            y = f(x)
        float(np.asarray(y).ravel()[0])
        return (time.perf_counter() - t0) / 20

    t_bare, t_fused = timeit(bare), timeit(fused)
    print(f"conv {t_bare*1e3:.3f} ms, conv+bias+relu {t_fused*1e3:.3f} ms "
          f"(epilogue overhead {100*(t_fused/t_bare-1):.1f}%)")


if __name__ == "__main__":  # pragma: no cover
    _measure()
