"""Scaled (masked / causal / generic) softmax family.

Reference: csrc/megatron/scaled_masked_softmax.h warp-softmax templates bound
as four modules — ``scaled_softmax_cuda``, ``scaled_masked_softmax_cuda``,
``scaled_upper_triang_masked_softmax_cuda``,
``generic_scaled_masked_softmax_cuda`` (SURVEY.md §2.2) — wrapped by
``FusedScaleMaskSoftmax`` (apex/transformer/functional/fused_softmax.py).

Semantics preserved:
- input is multiplied by ``scale`` *before* the mask/softmax,
- ``mask`` is boolean with True = masked-out (filled with -10000.0 like the
  reference kernels), broadcastable against the input,
- the causal variant requires square (sq == sk) inputs
  (fused_softmax.py:214 assert),
- backward is ``(dy - Σ dy·y) · y · scale`` through a custom VJP (the
  reference saves softmax_results for backward; so do we).

On TPU the forward runs as a Pallas row kernel that fuses scale + mask +
stable softmax in one VMEM pass — the causal mask is generated from iota
inside the kernel, never materialized in HBM. Off-TPU (or lane-misaligned)
the pure-XLA composition is used; softmax math is fp32 throughout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import (
    on_tpu,
    out_struct,
    pad_rows,
    pallas_ok,
)

__all__ = [
    "scaled_softmax",
    "scaled_masked_softmax",
    "scaled_upper_triang_masked_softmax",
    "generic_scaled_masked_softmax",
]

_MASK_FILL = -10000.0


# --------------------------------------------------------------------------
# XLA reference paths (fp32 math).
# --------------------------------------------------------------------------


def _softmax_fwd_ref(x, scale, mask=None, causal=False):
    x32 = x.astype(jnp.float32) * scale
    if mask is not None:
        x32 = jnp.where(mask, _MASK_FILL, x32)
    if causal:
        sq, sk = x.shape[-2], x.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        x32 = jnp.where(col > row, _MASK_FILL, x32)
    y = jax.nn.softmax(x32, axis=-1)
    # Fully-masked rows emit zeros, matching the reference kernels'
    # scale_value=0 when a row's max is the mask fill
    # (scaled_masked_softmax.h:304, generic_scaled_masked_softmax.h:288).
    if mask is not None or causal:
        all_masked = jnp.max(x32, axis=-1, keepdims=True) <= _MASK_FILL
        y = jnp.where(all_masked, 0.0, y)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# Pallas forward kernels: x viewed as (rows, sk).
# --------------------------------------------------------------------------


def _softmax_kernel(scale, causal, sq, has_mask, *refs):
    if has_mask:
        x_ref, m_ref, y_ref = refs
    else:
        x_ref, y_ref = refs
    x = x_ref[:].astype(jnp.float32) * scale
    if has_mask:
        x = jnp.where(m_ref[:] != 0, _MASK_FILL, x)
    if causal:
        br, sk = x.shape
        base = pl.program_id(0) * br
        row_in_block = jax.lax.broadcasted_iota(jnp.int32, (br, sk), 0)
        q_pos = (base + row_in_block) % sq
        col = jax.lax.broadcasted_iota(jnp.int32, (br, sk), 1)
        x = jnp.where(col > q_pos, _MASK_FILL, x)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    y = e / jnp.sum(e, axis=-1, keepdims=True)
    if has_mask or causal:
        # fully-masked rows → zeros (reference scale_value=0 semantics)
        y = jnp.where(m <= _MASK_FILL, 0.0, y)
    y_ref[:] = y.astype(y_ref.dtype)


def _softmax_fwd_pallas(x, scale, mask, causal):
    from jax.experimental.pallas import tpu as pltpu

    shape = x.shape
    sk = shape[-1]
    sq = shape[-2]
    rows = x.size // sk
    # The causal q-position of a row is (global_row % sq) regardless of the
    # block size, so any row blocking works.
    br = max(8, min(512, (4 * 1024 * 1024 // 3) // (sk * 4)) // 8 * 8)
    x2, _ = pad_rows(x.reshape(rows, sk), br)
    padded_rows = x2.shape[0]
    grid = (padded_rows // br,)
    row_tile = pl.BlockSpec((br, sk), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [row_tile]
    args = [x2]
    if mask is not None:
        # dispatcher guarantees mask.shape == x.shape here (broadcast masks
        # take the XLA path, which reads them with broadcast strides)
        m2, _ = pad_rows(mask.reshape(rows, sk).astype(jnp.int32), br)
        in_specs.append(row_tile)
        args.append(m2)
    y = pl.pallas_call(
        functools.partial(
            _softmax_kernel, scale, causal, sq, mask is not None
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=row_tile,
        out_shape=out_struct((padded_rows, sk), x.dtype, x2),
        interpret=not on_tpu(),
    )(*args)
    return y[:rows].reshape(shape)


# --------------------------------------------------------------------------
# custom_vjp
# --------------------------------------------------------------------------


def _use_pallas(x, mask, causal):
    # Broadcast masks (e.g. (B,1,sq,sk) vs (B,H,sq,sk)) would have to be
    # materialized at full size in HBM for the kernel; XLA reads them with
    # broadcast strides instead, so route those to the reference path.
    if mask is not None and mask.shape != x.shape:
        return False
    # rows past 512 go to the XLA composition, which fuses with the
    # matmuls around it (the crossover: not measured this round)
    if x.shape[-1] > 512:
        return False
    return pallas_ok(x.shape[-1], x.dtype) and (
        not causal or x.shape[-2] == x.shape[-1]
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _scaled_softmax(x, mask, scale, causal):
    if _use_pallas(x, mask, causal):
        return _softmax_fwd_pallas(x, scale, mask, causal)
    return _softmax_fwd_ref(x, scale, mask, causal)


def _scaled_softmax_fwd(x, mask, scale, causal):
    # Under differentiation the XLA composition runs: its backward fuses
    # across the fwd/bwd boundary, where an opaque kernel in the middle
    # sends y through HBM twice (not measured this round).  The Pallas
    # row kernel is the primal (forward-only) path.
    y = _softmax_fwd_ref(x, scale, mask, causal)
    return y, y


def _scaled_softmax_bwd(scale, causal, y, dy):
    y32 = y.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    inner = dy32 - jnp.sum(dy32 * y32, axis=-1, keepdims=True)
    dx = (inner * y32 * scale).astype(dy.dtype)
    return (dx, None)


_scaled_softmax.defvjp(_scaled_softmax_fwd, _scaled_softmax_bwd)


def scaled_softmax(x: jax.Array, scale: float = 1.0) -> jax.Array:
    """softmax(x*scale) — reference ``scaled_softmax_cuda`` (seq-len ≤16k
    warp kernel; here any length)."""
    return _scaled_softmax(x, None, float(scale), False)


def scaled_masked_softmax(
    x: jax.Array, mask: Optional[jax.Array], scale: float = 1.0
) -> jax.Array:
    """softmax(mask_fill(x*scale)) — reference ``scaled_masked_softmax_cuda``.

    ``mask`` boolean, True = masked (filled with -10000), broadcastable
    (typically (B, 1, sq, sk) against (B, H, sq, sk))."""
    if mask is None:
        return scaled_softmax(x, scale)
    return _scaled_softmax(x, mask, float(scale), False)


def scaled_upper_triang_masked_softmax(
    x: jax.Array, scale: float = 1.0
) -> jax.Array:
    """Causal softmax — reference
    ``scaled_upper_triang_masked_softmax_cuda`` (requires sq == sk)."""
    if x.shape[-1] != x.shape[-2]:
        raise ValueError(
            "scaled_upper_triang_masked_softmax requires square inputs "
            f"(got {x.shape[-2]}x{x.shape[-1]}); use scaled_masked_softmax "
            "with an explicit mask for rectangular attention."
        )
    return _scaled_softmax(x, None, float(scale), True)


def generic_scaled_masked_softmax(
    x: jax.Array, mask: Optional[jax.Array], scale: float = 1.0
) -> jax.Array:
    """Arbitrary-broadcast masked softmax — reference
    ``generic_scaled_masked_softmax_cuda`` (no pow-2/seq-len limits)."""
    return scaled_masked_softmax(x, mask, scale)
