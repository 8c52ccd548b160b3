"""Fused LayerNorm/RMSNorm numerics.

Reference analog: tests/L0/run_fused_layer_norm/test_fused_layer_norm.py —
fused op vs torch composition, fwd + bwd, affine/plain, mixed dtype,
memory-efficient mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from apex_tpu.ops.layer_norm import (
    fused_layer_norm,
    fused_rms_norm,
    layer_norm_ref,
)


def _torch_ln(x, w, b, eps=1e-5):
    tx = torch.tensor(x, requires_grad=True)
    tw = torch.tensor(w, requires_grad=True) if w is not None else None
    tb = torch.tensor(b, requires_grad=True) if b is not None else None
    y = torch.nn.functional.layer_norm(
        tx, (x.shape[-1],), weight=tw, bias=tb, eps=eps
    )
    return tx, tw, tb, y


@pytest.mark.parametrize("affine", [True, False])
@pytest.mark.parametrize("shape", [(4, 8, 256), (3, 384)])
def test_layer_norm_matches_torch(affine, shape):
    rng = np.random.RandomState(0)
    x = rng.randn(*shape).astype(np.float32)
    w = rng.rand(shape[-1]).astype(np.float32) + 0.5 if affine else None
    b = rng.randn(shape[-1]).astype(np.float32) if affine else None

    y = fused_layer_norm(jnp.asarray(x), None if w is None else jnp.asarray(w),
                         None if b is None else jnp.asarray(b))
    tx, tw, tb, ty = _torch_ln(x, w, b)
    np.testing.assert_allclose(np.asarray(y), ty.detach().numpy(),
                               atol=1e-5, rtol=1e-5)

    # gradients
    dy = rng.randn(*shape).astype(np.float32)

    def f(x_, w_, b_):
        return jnp.sum(fused_layer_norm(x_, w_, b_) * jnp.asarray(dy))

    if affine:
        gx, gw, gb = jax.grad(f, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(b)
        )
    else:
        gx = jax.grad(f)(jnp.asarray(x), None, None)
    ty.backward(torch.tensor(dy))
    np.testing.assert_allclose(np.asarray(gx), tx.grad.numpy(),
                               atol=1e-4, rtol=1e-4)
    if affine:
        np.testing.assert_allclose(np.asarray(gw), tw.grad.numpy(),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(gb), tb.grad.numpy(),
                                   atol=1e-4, rtol=1e-4)


def test_rms_norm_matches_reference_formula():
    rng = np.random.RandomState(1)
    x = rng.randn(6, 256).astype(np.float32)
    w = (rng.rand(256) + 0.5).astype(np.float32)
    y = fused_rms_norm(jnp.asarray(x), jnp.asarray(w))
    expect = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(np.asarray(y), expect, atol=1e-5, rtol=1e-5)

    # grad vs numerical finite differences on a reduced function
    def f(w_):
        return jnp.sum(jnp.square(fused_rms_norm(jnp.asarray(x), w_)))

    g = jax.grad(f)(jnp.asarray(w))
    eps = 1e-3
    for i in [0, 100, 255]:
        wp, wm = w.copy(), w.copy()
        wp[i] += eps
        wm[i] -= eps
        num = (float(f(jnp.asarray(wp))) - float(f(jnp.asarray(wm)))) / (2 * eps)
        np.testing.assert_allclose(float(g[i]), num, rtol=2e-2, atol=1e-2)


def test_memory_efficient_matches_standard():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(5, 128).astype(np.float32))
    w = jnp.asarray((rng.rand(128) + 0.5).astype(np.float32))
    b = jnp.asarray(rng.randn(128).astype(np.float32))
    dy = jnp.asarray(rng.randn(5, 128).astype(np.float32))

    def loss(mem_eff):
        def f(x_, w_, b_):
            return jnp.sum(
                fused_layer_norm(x_, w_, b_, memory_efficient=mem_eff) * dy
            )
        return jax.grad(f, argnums=(0, 1, 2))(x, w, b)

    g_std = loss(False)
    g_mem = loss(True)
    for a, c in zip(g_std, g_mem):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-4, rtol=1e-4)


def test_mixed_dtype_bf16_input_fp32_params():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 256), jnp.bfloat16)
    w = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.float32)
    y = fused_layer_norm(x, w, b)
    assert y.dtype == jnp.bfloat16
    ref = layer_norm_ref(x.astype(jnp.float32), w, b)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ref), atol=2e-2
    )


def test_pallas_interpret_matches_ref(monkeypatch):
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(9, 256).astype(np.float32))  # odd rows → pad
    w = jnp.asarray((rng.rand(256) + 0.5).astype(np.float32))
    b = jnp.asarray(rng.randn(256).astype(np.float32))
    dy = jnp.asarray(rng.randn(9, 256).astype(np.float32))

    def f(x_, w_, b_):
        return jnp.sum(fused_layer_norm(x_, w_, b_) * dy)

    gx, gw, gb = jax.grad(f, argnums=(0, 1, 2))(x, w, b)
    y = fused_layer_norm(x, w, b)

    monkeypatch.delenv("APEX_TPU_PALLAS_INTERPRET")
    y_ref = fused_layer_norm(x, w, b)
    gx_r, gw_r, gb_r = jax.grad(f, argnums=(0, 1, 2))(x, w, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_r), atol=1e-4)


def test_pallas_bwd_kernel_matches_xla(monkeypatch):
    """The Pallas revisit backward runs wherever the Pallas forward is
    eligible (interpret mode here); outside interpret mode the CPU gets
    the XLA composition, the reference side."""
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(11)
    # >512 rows -> multiple grid blocks (_rows_block(256, 8) = 512): the
    # revisit accumulator must actually cross block boundaries, not
    # degenerate to the single-block case
    x = jnp.asarray(rng.randn(1040, 256).astype(np.float32))
    w = jnp.asarray((rng.rand(256) + 0.5).astype(np.float32))
    b = jnp.asarray(rng.randn(256).astype(np.float32))
    dy = jnp.asarray(rng.randn(1040, 256).astype(np.float32))

    def f(x_, w_, b_):
        return jnp.sum(fused_layer_norm(x_, w_, b_) * dy)

    gx, gw, gb = jax.grad(f, argnums=(0, 1, 2))(x, w, b)

    monkeypatch.delenv("APEX_TPU_PALLAS_INTERPRET")  # reference side
    gx_r, gw_r, gb_r = jax.grad(f, argnums=(0, 1, 2))(x, w, b)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_r), atol=1e-4)

    # RMS variant through the same kernel
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")

    def fr(x_, w_):
        return jnp.sum(fused_rms_norm(x_, w_) * dy)

    rx, rw = jax.grad(fr, argnums=(0, 1))(x, w)
    monkeypatch.delenv("APEX_TPU_PALLAS_INTERPRET")  # reference side
    rx_r, rw_r = jax.grad(fr, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(rx), np.asarray(rx_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(rw), np.asarray(rw_r), atol=1e-4)


def test_flax_modules():
    from apex_tpu.normalization import FusedLayerNorm, FusedRMSNorm

    x = jnp.ones((2, 64))
    ln = FusedLayerNorm(normalized_shape=64)
    params = ln.init(jax.random.PRNGKey(0), x)
    y = ln.apply(params, x)
    assert y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y), 0.0, atol=1e-5)

    rms = FusedRMSNorm(normalized_shape=64)
    params = rms.init(jax.random.PRNGKey(0), x)
    y = rms.apply(params, x)
    np.testing.assert_allclose(np.asarray(y), 1.0, atol=1e-3)
