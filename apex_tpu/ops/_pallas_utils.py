"""Shared helpers for Pallas row-kernel wrappers."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.utils.registry import on_tpu

LANES = 128

__all__ = ["LANES", "pallas_ok", "pad_rows", "out_struct",
           "param_cotangent"]


def out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct for a pallas_call output, propagating the mesh-axis
    variance (vma) of ``like`` — required when the kernel runs inside a
    ``jax.shard_map`` with its default ``check_vma=True``."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def param_cotangent(ct, primal):
    """A hand-written ``custom_vjp`` backward's cotangent for ``primal``,
    typed like it: summed over the manual (``shard_map``) axes the
    cotangent varies on and the primal does not.  A parameter replicated
    over a data-parallel axis gets the SUM of its shards' contributions
    — what autodiff derives for an XLA composition (the transpose of the
    implicit ``pvary``) and a custom backward must do itself, or
    ``check_vma`` refuses the rule.  No-op outside ``shard_map`` and for
    ``None``."""
    if ct is None:
        return None
    extra = tuple(sorted(jax.typeof(ct).vma - jax.typeof(primal).vma))
    return jax.lax.psum(ct, extra) if extra else ct


def pallas_ok(op_name: str, last_dim: int, dtype) -> bool:
    """Common gate: on TPU (or forced interpret), lane-aligned last dim,
    supported dtype, and not disabled via APEX_TPU_DISABLE_<OP>=1."""
    if os.environ.get(f"APEX_TPU_DISABLE_{op_name.upper()}", "0") == "1":
        return False
    interp = os.environ.get("APEX_TPU_PALLAS_INTERPRET", "0") == "1"
    return (
        (on_tpu() or interp)
        and last_dim % LANES == 0
        and dtype in (jnp.float32, jnp.bfloat16, jnp.float16)
    )


def pad_rows(x2, block_rows: int):
    """Zero-pad dim 0 to a multiple of block_rows; returns (padded, rows).

    Padding rows are zeros: reductions over rows (dγ/dβ-style accumulators)
    see zero contributions, and per-row outputs are sliced off by callers.
    """
    rows = x2.shape[0]
    padded = pl.cdiv(rows, block_rows) * block_rows
    if padded == rows:
        return x2, rows
    pad_width = [(0, padded - rows)] + [(0, 0)] * (x2.ndim - 1)
    return jnp.pad(x2, pad_width), rows
