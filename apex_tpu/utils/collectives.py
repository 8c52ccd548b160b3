"""Collective helpers aware of SPMD autodiff semantics.

Under ``jax.shard_map`` with varying-axes tracking (jax ≥0.9), gradients
taken w.r.t. *replicated* (axis-invariant) parameters are ALREADY summed
over the mapped axis — the transpose of the implicit broadcast inserts the
psum. A DDP layer that blindly psums again double-counts (verified on the
8-device mesh: explicit psum after jax.grad yields 8× gradients).

These helpers consult ``jax.typeof(x).vma`` (the set of mesh axes a value
varies over) to apply a collective only when the value is still
shard-varying, and a plain division when SPMD-AD has pre-summed.

The ``collectives.*`` counters these helpers book are load-bearing
beyond dashboards: the Tier-B jaxpr auditor
(``apex_tpu/analysis/jaxpr_audit.py``, gated by the ``static_audit``
dryrun phase) diffs them against a census of the collective equations
that actually landed in each entry point's jaxpr — a collective
emitted around these wrappers shows up as accounting drift and fails
CI.  New comm paths must route through this module (or the
ring/compressed wrappers built on it), not bind ``jax.lax``
collectives directly.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from apex_tpu.observability import metrics as _telemetry

__all__ = [
    "is_varying",
    "grad_mean",
    "grad_sum",
    "flag_and",
    "flag_or",
    "match_vma",
    "pvary",
    "vma_of",
    "all_gather",
    "all_to_all",
    "ppermute",
    "psum_scatter",
]


def _note_collective(kind: str, x) -> None:
    """Count a collective about to be emitted: ``collectives.<kind>.calls``
    and ``collectives.<kind>.bytes`` (abstract shape x itemsize).

    Trace-time accounting — these helpers run while the enclosing
    jit/shard_map traces, so counts are per collective *emitted into
    the compiled program* (once per trace), not per executed step;
    host-callback-free by construction.  One enabled() check when
    telemetry is off.
    """
    reg = _telemetry.registry()
    if reg is None:
        return
    dtype = getattr(x, "dtype", None)
    nbytes = 0
    if dtype is not None:
        nbytes = int(math.prod(getattr(x, "shape", ()) or ())
                     ) * dtype.itemsize
    reg.counter(f"collectives.{kind}.calls").inc()
    reg.counter(f"collectives.{kind}.bytes").inc(nbytes)


def pvary(tree, axis_name: str):
    """Type values as varying over ``axis_name`` (jax≥0.9 vma typing).

    No-op for leaves already varying or outside a mapped context (used
    by the TP mappings and the pipeline scan carries, where the target
    is one known axis).  When the target is a *set* of axes derived from
    another value, use :func:`match_vma` + :func:`vma_of` instead.
    """

    def leaf(v):
        if is_varying(v, axis_name):
            return v
        try:
            return jax.lax.pcast(v, axis_name, to="varying")
        except NameError:
            # axis not bound (outside shard_map) — nothing to type
            return v

    return jax.tree_util.tree_map(leaf, tree)


def vma_of(x) -> tuple:
    """The manual axes ``x`` is typed as varying over (empty outside
    shard_map / for untyped tracers)."""
    return tuple(jax.typeof(x).vma)


def match_vma(tree, axes):
    """Promote every leaf to vary over each of ``axes`` it doesn't
    already — the one home for the pcast-to-varying dance when a target
    vma set is known (fresh constants entering a lax.switch/scan next to
    shard_map-varying operands, Pallas calls with mixed-vma inputs)."""
    axes = tuple(axes)
    if not axes:
        return tree

    def leaf(v):
        have = set(vma_of(v))
        missing = tuple(a for a in axes if a not in have)
        return jax.lax.pcast(v, missing, to="varying") if missing else v

    return jax.tree_util.tree_map(leaf, tree)


def is_varying(x, axis_name: str) -> bool:
    """True if ``x`` still differs across shards of ``axis_name``."""
    return axis_name in jax.typeof(x).vma


def grad_sum(tree: Any, axis_name: str) -> Any:
    """Sum grads over the axis (no-op when SPMD-AD already summed)."""

    def red(g):
        if not hasattr(g, "dtype") or not jnp.issubdtype(g.dtype, jnp.inexact):
            return g
        if is_varying(g, axis_name):
            _note_collective("psum", g)
            return jax.lax.psum(g, axis_name)
        return g

    return jax.tree_util.tree_map(red, tree)


def grad_mean(tree: Any, axis_name: str) -> Any:
    """Average grads over the axis, whether or not they were pre-summed."""
    n = jax.lax.axis_size(axis_name)

    def red(g):
        if not hasattr(g, "dtype") or not jnp.issubdtype(g.dtype, jnp.inexact):
            return g
        if is_varying(g, axis_name):
            _note_collective("pmean", g)
            return jax.lax.pmean(g, axis_name)
        return g / n

    return jax.tree_util.tree_map(red, tree)


def flag_and(flag, axis_name: str):
    """AND a boolean flag across shards (found-inf combining)."""
    if is_varying(flag, axis_name):
        _note_collective("pmin", flag)
        return jax.lax.pmin(flag.astype(jnp.int32), axis_name) > 0
    return flag


def flag_or(flag, axis_name: str):
    if is_varying(flag, axis_name):
        _note_collective("pmax", flag)
        return jax.lax.pmax(flag.astype(jnp.int32), axis_name) > 0
    return flag


# ---- counted pass-throughs for the non-psum collective family -------------
# The psum/pmean/pmin/pmax helpers above count themselves; everything the
# comm/ and ring paths emit (all_gather, all_to_all, ppermute,
# psum_scatter) was invisible to collectives.* until these wrappers.
# ``bytes`` counts what THIS rank puts on the wire per emitted collective:
# the full local operand (trace-time accounting, like _note_collective).


def all_gather(x, axis_name: str, *, axis: int = 0, tiled: bool = False):
    """Counted ``jax.lax.all_gather`` → ``collectives.all_gather.*``."""
    _note_collective("all_gather", x)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int, *,
               tiled: bool = False):
    """Counted ``jax.lax.all_to_all`` → ``collectives.all_to_all.*``."""
    _note_collective("all_to_all", x)
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis,
                              tiled=tiled)


def ppermute(x, axis_name: str, perm):
    """Counted ``jax.lax.ppermute`` → ``collectives.ppermute.*``."""
    _note_collective("ppermute", x)
    return jax.lax.ppermute(x, axis_name, perm)


def psum_scatter(x, axis_name: str, *, scatter_dimension: int = 0,
                 tiled: bool = False):
    """Counted ``jax.lax.psum_scatter`` → ``collectives.psum_scatter.*``."""
    _note_collective("psum_scatter", x)
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)
