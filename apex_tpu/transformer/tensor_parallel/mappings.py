"""The 8 tensor-parallel collective mappings.

Reference: apex/transformer/tensor_parallel/mappings.py:23-292 — autograd
Functions pairing a forward collective with its transpose in backward:

| mapping                                   | fwd             | bwd            |
|-------------------------------------------|-----------------|----------------|
| copy_to_tensor_model_parallel_region      | identity        | all-reduce     |
| reduce_from_tensor_model_parallel_region  | all-reduce      | identity       |
| scatter_to_tensor_model_parallel_region   | split last dim  | all-gather     |
| gather_from_tensor_model_parallel_region  | all-gather last | split          |
| scatter_to_sequence_parallel_region       | split first dim | all-gather     |
| gather_from_sequence_parallel_region      | all-gather first| reduce-scatter*|
| reduce_scatter_to_sequence_parallel_region| reduce-scatter  | all-gather     |
| (copy's sequence-parallel dual is the * case: to_model_parallel_region
|  =False makes the backward a plain split)                                |

Implemented over ``jax.lax`` collectives (custom VJPs where the table's
backward is not what autodiff derives), usable inside ``shard_map`` on
the 'tp' axis.  The varying-axes (vma) typing of ``shard_map`` is kept
consistent: identities that move a value into per-shard compute insert
``pvary``; reductions produce axis-invariant values; and a gathered
value is typed VARYING like ``jax.lax.all_gather``'s — every rank holds
the same bytes, but the type system cannot prove it, so a caller that
returns one through a replicated ``out_specs`` reduces it first
(``pmean`` over identical copies is the identity).  The two scatters
are plain ``split(pvary(x))``: autodiff's transpose of that — each
rank's cotangent placed at its rows, summed over ranks — IS the table's
all-gather, typed invariant like the input it is the cotangent of.

The two sequence-parallel mappings with a collective on *both* sides of
the table take an ``overlap_comm`` tri-state (explicit bool, or ``None``
to inherit ``ops.collective_matmul.overlap_scope``): when enabled, the
monolithic all-gather / reduce-scatter is decomposed into n−1
``ppermute`` ring hops (``ring_all_gather`` / ``ring_reduce_scatter``)
in the forward AND the backward, so the XLA scheduler can overlap each
hop with neighboring compute — and a mapping whose forward rides the
ring never falls back to a monolithic collective under grad.

(The GSPMD layer path — apex_tpu.transformer.tensor_parallel.layers — does
not call these; XLA inserts the same collectives from sharding annotations.
These exist for manual shard_map programming and 1:1 reference parity.)
"""

from __future__ import annotations

import functools

import jax

from apex_tpu.transformer.parallel_state import TP_AXIS

__all__ = [
    "copy_to_tensor_model_parallel_region",
    "reduce_from_tensor_model_parallel_region",
    "scatter_to_tensor_model_parallel_region",
    "gather_from_tensor_model_parallel_region",
    "scatter_to_sequence_parallel_region",
    "gather_from_sequence_parallel_region",
    "reduce_scatter_to_sequence_parallel_region",
]


from apex_tpu.utils.collectives import pvary as _pvary  # noqa: E402


def _split_along(x, dim, axis):
    """Local shard of x along ``dim`` for this tp rank
    (reference _split_along_last_dim :40 / _split_along_first_dim :55)."""
    n = jax.lax.axis_size(axis)
    rank = jax.lax.axis_index(axis)
    size = x.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(x, rank * size, size, axis=dim)


# ---- copy (f): identity fwd, allreduce bwd  (mappings.py:133) -------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tensor_model_parallel_region(x, axis=TP_AXIS):
    return _pvary(x, axis)


def _copy_fwd(x, axis):
    return _pvary(x, axis), None


def _copy_bwd(axis, _, g):
    return (jax.lax.psum(g, axis),)


copy_to_tensor_model_parallel_region.defvjp(_copy_fwd, _copy_bwd)


# ---- reduce (g): allreduce fwd, identity bwd  (mappings.py:152) -----------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tensor_model_parallel_region(x, axis=TP_AXIS):
    return jax.lax.psum(x, axis)


def _reduce_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _reduce_bwd(axis, _, g):
    return (_pvary(g, axis),)


reduce_from_tensor_model_parallel_region.defvjp(_reduce_fwd, _reduce_bwd)


# ---- scatter/gather along the LAST dim (mappings.py:170,196) --------------


def scatter_to_tensor_model_parallel_region(x, axis=TP_AXIS):
    return _split_along(_pvary(x, axis), -1, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def gather_from_tensor_model_parallel_region(x, axis=TP_AXIS):
    return jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


def _gather_fwd(x, axis):
    return jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True), None


def _gather_bwd(axis, _, g):
    return (_split_along(g, -1, axis),)


gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


# ---- sequence-parallel: FIRST dim (mappings.py:55,95,114,223,245) ---------


def scatter_to_sequence_parallel_region(x, axis=TP_AXIS):
    return _split_along(_pvary(x, axis), 0, axis)


def _seq_all_gather(x, axis, overlap_comm):
    """Dim-0 all-gather: monolithic (counted) or the n−1-hop ring form
    under ``overlap_comm`` (ops.collective_matmul.ring_all_gather)."""
    from apex_tpu.ops import collective_matmul as _cm

    if _cm.overlap_enabled(overlap_comm):
        return _cm.ring_all_gather(x, axis, dim=0)
    from apex_tpu.utils.collectives import all_gather

    return all_gather(x, axis, axis=0, tiled=True)


def _seq_reduce_scatter(x, axis, overlap_comm):
    """Dim-0 sum-scatter: monolithic (counted) or the rotating-
    accumulator ring form under ``overlap_comm``."""
    from apex_tpu.ops import collective_matmul as _cm

    if _cm.overlap_enabled(overlap_comm):
        return _cm.ring_reduce_scatter(x, axis, dim=0)
    from apex_tpu.utils.collectives import psum_scatter

    return psum_scatter(x, axis, scatter_dimension=0, tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def gather_from_sequence_parallel_region(
    x, to_model_parallel: bool = True, axis=TP_AXIS, overlap_comm=None
):
    """fwd: all-gather along dim 0. bwd: reduce-scatter when the gathered
    value feeds tensor-parallel compute (reference
    _GatherFromSequenceParallelRegion :223, to_model_parallel flag), else a
    plain split.  ``overlap_comm`` (tri-state; ``None`` inherits
    ``overlap_scope``) rides both directions on the ppermute ring."""
    return _seq_all_gather(x, axis, overlap_comm)


def _sp_gather_fwd(x, to_model_parallel, axis, overlap_comm):
    return _seq_all_gather(x, axis, overlap_comm), None


def _sp_gather_bwd(to_model_parallel, axis, overlap_comm, _, g):
    if to_model_parallel:
        return (_seq_reduce_scatter(g, axis, overlap_comm),)
    return (_split_along(g, 0, axis),)


gather_from_sequence_parallel_region.defvjp(_sp_gather_fwd, _sp_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _sp_reduce_scatter(x, axis, overlap_comm):
    return _seq_reduce_scatter(x, axis, overlap_comm)


def _sp_rs_fwd(x, axis, overlap_comm):
    return _seq_reduce_scatter(x, axis, overlap_comm), None


def _sp_rs_bwd(axis, overlap_comm, _, g):
    return (_seq_all_gather(g, axis, overlap_comm),)


_sp_reduce_scatter.defvjp(_sp_rs_fwd, _sp_rs_bwd)


def reduce_scatter_to_sequence_parallel_region(x, axis=TP_AXIS,
                                               overlap_comm=None):
    """fwd: sum-scatter along dim 0; bwd: all-gather.  ``overlap_comm``
    (tri-state) decomposes both into ppermute ring hops.  The input is
    per-rank partial sums, so it enters as varying: an axis-invariant
    ``x`` (every rank contributing the same value) is ``pvary``-ed
    here, outside the custom VJP, and its cotangent then sums over the
    ranks as autodiff's transpose of that cast."""
    return _sp_reduce_scatter(_pvary(x, axis), axis, overlap_comm)
