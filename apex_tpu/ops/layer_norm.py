"""Fused LayerNorm / RMSNorm with custom VJP and Pallas TPU kernels.

Reference: csrc/layer_norm_cuda.cpp + layer_norm_cuda_kernel.cu (Welford
row reduction, 10 entry points: LN/RMS × affine/plain × fwd/bwd, mixed-dtype
"Megatron" variants, memory-efficient mode that saves the *output* instead of
the input and reconstructs x in backward), wrapped by
apex/normalization/fused_layer_norm.py.

TPU design: a row-parallel Pallas kernel — each grid step normalizes a
(block × hidden) tile held in VMEM; mean/rstd are saved as residuals. The
backward kernel recomputes x̂ and accumulates dγ/dβ across row blocks in a
revisited output tile (the TPU analog of the reference's two-pass part-grad
reduction). Falls back to a pure-XLA composition when the hidden size isn't
lane-aligned or we're off TPU (XLA fuses that composition well; the Pallas
path wins by keeping the row statistics in VMEM and fusing the affine
epilogue).

Norm semantics match torch.nn.functional.layer_norm /
the reference's RMSNorm (no mean subtraction, rsqrt(E[x²]+eps)).
Mixed-dtype: stats and affine math always run in fp32; output dtype equals
input dtype, params may be fp32 while inputs are bf16 (the Megatron
``MixedFused*`` contract, fused_layer_norm.py:553+).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import (
    interpret_forced,
    on_tpu,
    out_struct,
    pallas_ok,
    param_cotangent,
)

__all__ = [
    "fused_layer_norm",
    "fused_rms_norm",
    "layer_norm_ref",
    "rms_norm_ref",
]

_LANES = 128


def _rows_block(hidden: int, n_bufs: int) -> int:
    """Pick a row-block size that keeps ~n_bufs (block, hidden) fp32 tiles
    within a few MB of VMEM."""
    budget = 6 * 1024 * 1024 // n_bufs
    rows = max(8, budget // (hidden * 4))
    rows = 1 << (rows.bit_length() - 1)  # floor to pow2
    return min(512, rows)


# ----------------------------------------------------------------------------
# Pure-XLA reference implementations (always available; fp32 math).
# ----------------------------------------------------------------------------


def layer_norm_ref(x, weight=None, bias=None, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_ref(x, weight=None, eps=1e-5):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------------------------
# Pallas kernels. x is viewed as (rows, hidden).
# ----------------------------------------------------------------------------


def _ln_fwd_kernel(rms: bool, affine: bool, has_bias: bool, eps: float,
                   *refs):
    if affine:
        if has_bias:
            x_ref, w_ref, b_ref, y_ref, mu_ref, rs_ref = refs
        else:
            x_ref, w_ref, y_ref, mu_ref, rs_ref = refs
    else:
        x_ref, y_ref, mu_ref, rs_ref = refs
    x = x_ref[:].astype(jnp.float32)
    if rms:
        mu = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rs
    y = xhat
    if affine:
        y = y * w_ref[:].astype(jnp.float32)
        if has_bias:
            y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rs_ref[:] = rs


def _ln_bwd_kernel(rms: bool, affine: bool, has_bias: bool, *refs):
    """dx plus dγ/dβ accumulated into one revisited (1, hidden) tile.

    Per-block dγ/dβ rows for a trailing XLA sum are not an alternative:
    Mosaic rejects a (1, hidden) output block over a (n_blocks, hidden)
    array (the last two block dims must be (8k, 128k) or the array's).
    Against the XLA chain: not measured this round."""
    if affine:
        if has_bias:
            (dy_ref, x_ref, w_ref, mu_ref, rs_ref,
             dx_ref, dw_ref, db_ref) = refs
        else:
            dy_ref, x_ref, w_ref, mu_ref, rs_ref, dx_ref, dw_ref = refs
    else:
        dy_ref, x_ref, mu_ref, rs_ref, dx_ref = refs

    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    mu = mu_ref[:]
    rs = rs_ref[:]
    xhat = (x - mu) * rs
    if affine:
        wdy = dy * w_ref[:].astype(jnp.float32)
    else:
        wdy = dy
    h = x.shape[-1]
    c1 = jnp.sum(wdy, axis=-1, keepdims=True) / h
    c2 = jnp.sum(wdy * xhat, axis=-1, keepdims=True) / h
    if rms:
        dx = (wdy - xhat * c2) * rs
    else:
        dx = (wdy - c1 - xhat * c2) * rs
    dx_ref[:] = dx.astype(dx_ref.dtype)

    if affine:
        first = pl.program_id(0) == 0

        @pl.when(first)
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)
            if has_bias:
                db_ref[:] = jnp.zeros_like(db_ref)

        dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
        if has_bias:
            db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


def _pallas_ok(hidden: int, dtype) -> bool:
    """Whether the kernels (forward and backward alike) take this width
    and dtype; the XLA composition runs otherwise.  On a TPU, 16-bit
    inputs only (fp32 against XLA's fused chain: not measured this
    round); interpret mode keeps every dtype for test coverage."""
    if not pallas_ok(hidden, dtype):
        return False
    return interpret_forced() or dtype in (jnp.bfloat16, jnp.float16)


def _pad_rows(x2, br):
    from apex_tpu.ops._pallas_utils import pad_rows

    return pad_rows(x2, br)


def _ln_fwd_pallas(x2, weight, bias, eps, rms):
    from jax.experimental.pallas import tpu as pltpu

    hidden = x2.shape[1]
    affine = weight is not None
    has_bias = bias is not None
    n_bufs = 3 + (1 if affine else 0) + (1 if has_bias else 0)
    br = _rows_block(hidden, n_bufs)
    x2, rows = _pad_rows(x2, br)
    prows = x2.shape[0]
    grid = (prows // br,)
    row_tile = pl.BlockSpec((br, hidden), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    stat_tile = pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    param_tile = pl.BlockSpec((1, hidden), lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    in_specs = [row_tile]
    args = [x2]
    if affine:
        in_specs.append(param_tile)
        args.append(weight.reshape(1, hidden))
        if has_bias:
            in_specs.append(param_tile)
            args.append(bias.reshape(1, hidden))
    y, mu, rs = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, rms, affine, has_bias, eps),
        grid=grid,
        in_specs=in_specs,
        out_specs=(row_tile, stat_tile, stat_tile),
        out_shape=(
            out_struct((prows, hidden), x2.dtype, x2),
            out_struct((prows, 1), jnp.float32, x2),
            out_struct((prows, 1), jnp.float32, x2),
        ),
        interpret=not on_tpu(),
    )(*args)
    return y[:rows], mu[:rows], rs[:rows]


def _ln_bwd_pallas(dy2, x2, weight, mu, rs, rms, has_bias):
    from jax.experimental.pallas import tpu as pltpu

    hidden = x2.shape[1]
    affine = weight is not None
    n_bufs = 5 + (3 if affine else 0)
    br = _rows_block(hidden, n_bufs)
    dy2, rows = _pad_rows(dy2, br)
    x2, _ = _pad_rows(x2, br)
    mu, _ = _pad_rows(mu, br)
    # rs is zero-padded like everything else; padded rows are safe because
    # dy there is zero too (dx = 0·rs = 0, dγ/dβ partial sums get zeros)
    # and the per-row outputs are sliced off below.
    rs, _ = _pad_rows(rs, br)
    prows = x2.shape[0]
    grid = (prows // br,)
    row_tile = pl.BlockSpec((br, hidden), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    stat_tile = pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    param_tile = pl.BlockSpec((1, hidden), lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    acc_tile = pl.BlockSpec((1, hidden), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [row_tile, row_tile]
    args = [dy2, x2]
    if affine:
        in_specs.append(param_tile)
        args.append(weight.reshape(1, hidden))
    in_specs += [stat_tile, stat_tile]
    args += [mu, rs]

    acc_rows = 1
    out_specs = [row_tile]
    out_shape = [out_struct((prows, hidden), x2.dtype, x2)]
    if affine:
        out_specs.append(acc_tile)
        out_shape.append(out_struct((acc_rows, hidden), jnp.float32, x2))
        if has_bias:
            out_specs.append(acc_tile)
            out_shape.append(
                out_struct((acc_rows, hidden), jnp.float32, x2))

    outs = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, rms, affine, has_bias),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=not on_tpu(),
    )(*args)
    if not affine:
        dx = outs[0] if isinstance(outs, (tuple, list)) else outs
        return dx[:rows], None, None

    def red(t):
        return t.reshape(-1)

    if has_bias:
        dx, dw, db = outs
        return dx[:rows], red(dw), red(db)
    dx, dw = outs
    return dx[:rows], red(dw), None


# ----------------------------------------------------------------------------
# custom_vjp wrappers
# ----------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(x, weight, bias, eps, rms, memory_efficient):
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    hidden = x.shape[-1]
    if _pallas_ok(hidden, x.dtype):
        with jax.named_scope("layer_norm_fwd"):
            y, _, _ = _ln_fwd_pallas(
                x.reshape(rows, hidden), weight, bias, eps, rms
            )
        return y.reshape(x.shape)
    if rms:
        return rms_norm_ref(x, weight, eps)
    return layer_norm_ref(x, weight, bias, eps)


def _norm_fwd(x, weight, bias, eps, rms, memory_efficient):
    shape = x.shape
    hidden = shape[-1]
    rows = x.size // hidden
    x2 = x.reshape(rows, hidden)
    if _pallas_ok(hidden, x.dtype):
        with jax.named_scope("layer_norm_fwd"):
            y2, mu, rs = _ln_fwd_pallas(x2, weight, bias, eps, rms)
    else:
        x32 = x2.astype(jnp.float32)
        if rms:
            mu = jnp.zeros((rows, 1), jnp.float32)
            var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        else:
            mu = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        rs = jax.lax.rsqrt(var + eps)
        y32 = (x32 - mu) * rs
        if weight is not None:
            y32 = y32 * weight.astype(jnp.float32)
            if bias is not None:
                y32 = y32 + bias.astype(jnp.float32)
        y2 = y32.astype(x.dtype)
    # memory_efficient mode (reference layer_norm_cuda.cpp "mem eff" entry
    # points): save y instead of x; x is reconstructed in backward.
    saved_x = None if memory_efficient else x2
    saved_y = y2 if memory_efficient else None
    return y2.reshape(shape), (saved_x, saved_y, weight, bias, mu, rs, shape)


def _norm_bwd(eps, rms, memory_efficient, res, dy):
    saved_x, saved_y, weight, bias, mu, rs, shape = res
    hidden = shape[-1]
    rows = dy.size // hidden
    dy2 = dy.reshape(rows, hidden)
    if memory_efficient:
        # Reconstruct x̂ (and x) from y: y = x̂*w + b  ⇒  x̂ = (y - b)/w.
        y32 = saved_y.astype(jnp.float32)
        if weight is not None:
            w32 = weight.astype(jnp.float32)
            # guard zero gammas exactly like the reference's
            # clamp_by_magnitude (layer_norm_cuda_kernel.cu:540)
            w32 = jnp.sign(w32) * jnp.maximum(jnp.abs(w32), eps) + jnp.where(
                w32 == 0.0, eps, 0.0
            )
            if bias is not None:
                y32 = y32 - bias.astype(jnp.float32)
            xhat = y32 / w32
        else:
            xhat = y32
        x2 = (xhat / rs + mu).astype(dy.dtype)
    else:
        x2 = saved_x

    if _pallas_ok(hidden, x2.dtype):
        with jax.named_scope("layer_norm_bwd"):
            dx, dw, db = _ln_bwd_pallas(
                dy2, x2, weight, mu, rs, rms, bias is not None
            )
    else:
        dy32 = dy2.astype(jnp.float32)
        x32 = x2.astype(jnp.float32)
        xhat = (x32 - mu) * rs
        wdy = dy32 if weight is None else dy32 * weight.astype(jnp.float32)
        c1 = jnp.mean(wdy, axis=-1, keepdims=True)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        if rms:
            dx = (wdy - xhat * c2) * rs
        else:
            dx = (wdy - c1 - xhat * c2) * rs
        dx = dx.astype(dy.dtype)
        dw = jnp.sum(dy32 * xhat, axis=0) if weight is not None else None
        db = jnp.sum(dy32, axis=0) if bias is not None else None

    dxr = dx.reshape(shape)
    dwr = None if weight is None else param_cotangent(
        dw.astype(weight.dtype), weight)
    dbr = None if bias is None else param_cotangent(
        db.astype(bias.dtype), bias)
    return (dxr, dwr, dbr)


_norm.defvjp(_norm_fwd, _norm_bwd)


def fused_layer_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
    memory_efficient: bool = False,
) -> jax.Array:
    """LayerNorm over the last dimension (affine when weight/bias given).

    Equivalent surface to ``fused_layer_norm_cuda``'s forward entry points
    (csrc/layer_norm_cuda.cpp:446-458) + autograd
    (apex/normalization/fused_layer_norm.py:38+).
    """
    return _norm(x, weight, bias, eps, False, memory_efficient)


def fused_rms_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    eps: float = 1e-5,
    memory_efficient: bool = False,
) -> jax.Array:
    """RMSNorm over the last dimension (reference ``FusedRMSNorm``,
    fused_layer_norm.py:347+)."""
    return _norm(x, weight, None, eps, True, memory_efficient)
