"""Shared helpers for the Pallas kernel wrappers, and the one place that
decides which implementation of an op runs.

The choice reads two things the program can observe: the platform
(:func:`on_tpu`) and whether the caller or the test harness asked for
interpret mode (:func:`interpret_forced`, the package's single read of
``APEX_TPU_PALLAS_INTERPRET``).  An op that has an XLA reference beside
its kernel takes a ``backend=`` argument and hands it to
:func:`resolve_backend`; a row kernel asks :func:`pallas_ok`.  Inside an
op only the static shape takes part (``flash_attention._bwd_plan``).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128

__all__ = ["LANES", "default_backend", "on_tpu", "interpret_forced",
           "resolve_backend", "pallas_ok", "pad_rows", "out_struct",
           "param_cotangent"]


@functools.lru_cache(maxsize=None)
def default_backend() -> str:
    """The active jax platform ('tpu', 'cpu', 'gpu')."""
    return jax.default_backend()


def on_tpu() -> bool:
    """Whether the active platform is a TPU."""
    return default_backend() == "tpu"


def interpret_forced() -> bool:
    """``APEX_TPU_PALLAS_INTERPRET=1``: take the kernel routes off the
    TPU, interpreted (the CPU test tier)."""
    return os.environ.get("APEX_TPU_PALLAS_INTERPRET", "0") == "1"


def resolve_backend(op: str, backend: Optional[str]) -> str:
    """``"kernel"`` or ``"reference"`` for ``op``.  ``None``/``"auto"``:
    the kernel on a TPU or in forced interpret mode, the XLA reference
    elsewhere; ``"kernel"``/``"reference"`` pin (how the parity tests
    reach each side)."""
    if backend in (None, "auto"):
        return "kernel" if (on_tpu() or interpret_forced()) else "reference"
    if backend not in ("kernel", "reference"):
        raise ValueError(
            f"{op}: backend={backend!r}, expected auto|kernel|reference")
    return backend


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


def pallas_ok(last_dim: int, dtype) -> bool:
    """A row kernel's gate: on TPU (or forced interpret), lane-aligned
    last dim, supported dtype."""
    return (
        (on_tpu() or interpret_forced())
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
