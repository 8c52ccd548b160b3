"""Ring collective-matmul — overlapped tensor-parallel collectives.

Megatron-style TP pays a *serialized* collective around every linear:
``gather_from_sequence_parallel_region`` → matmul in ColumnParallelLinear,
and matmul → ``reduce_scatter_to_sequence_parallel_region``/psum in
RowParallelLinear (reference apex/transformer/tensor_parallel/layers.py:429,
:613, mappings.py:223,:245).  While the monolithic collective runs, the MXU
idles; while the matmul runs, the ICI idles.

This module decomposes those pairs into ``ppermute`` ring steps so hop
``t+1``'s transfer is dataflow-independent of hop ``t``'s shard matmul —
XLA's latency-hiding scheduler then runs them concurrently (the classic
TPU "collective matmul"; the same ring structure as
``parallel/ring_attention.py``, applied to the dense TP hot path):

- :func:`all_gather_matmul` — ``all_gather(x) @ w`` as a ring: each hop's
  incoming activation shard is matmul'd immediately while the next shard
  is in flight.  Backward is :func:`matmul_reduce_scatter` for dx plus a
  ring re-gather of ``x`` for dw — no monolithic collective under grad.
- :func:`matmul_reduce_scatter` — ``reduce_scatter(x @ w)`` as a
  partial-product ring with a rotating accumulator: each hop computes only
  the output chunk the traveling accumulator is destined for.  Backward is
  one ring over the output cotangent producing dx chunks and dw together.
- :func:`matmul_all_reduce` — ``psum(x @ w)`` spelled as the ring
  reduce-scatter followed by an all-gather (same wire bytes as the
  monolithic all-reduce; the reduce-scatter half rides the ring overlapped
  with the partial-product matmuls).  Backward sums the output cotangent
  only if it arrives shard-varying (the dual of ``copy_to``'s pvary);
  an invariant cotangent keeps it communication-free like
  ``reduce_from_tensor_model_parallel_region``'s identity backward.
- :func:`ring_all_gather` / :func:`ring_reduce_scatter` — the bare ring
  decompositions (no fused matmul) the sequence-parallel mappings route
  through under ``overlap_comm``.

Rings are **bidirectional** for ≥3 shards: the forward-direction buffer
carries ⌈(n−1)/2⌉ hops and the backward buffer the rest, so both ICI
directions are busy and wall-clock latency halves while total hop count
stays n−1.

All functions run on *local shards inside* ``jax.shard_map`` (or pmap)
with ``axis_name`` bound.  Their outputs are typed like the monolithic
collective they decompose under ``shard_map``'s varying-axes checking:
a ring all-gather (and ``matmul_all_reduce``, which ends in one) returns
a value every rank holds identically but TYPED varying, exactly like
``jax.lax.all_gather`` — ``ppermute`` hops cannot prove replication, so
a caller returning it through a replicated ``out_specs`` reduces it
first (``pmean`` over identical copies is the identity).  The
``overlap_*``/``gspmd_*`` helpers wrap them in a shard_map island for use from GSPMD-annotated code (the pattern of
``transformer_lm._cp_core_attention``), returning ``None`` whenever the
ring path does not apply (no mesh, axis absent or size 1, indivisible
dims) so callers fall back to the monolithic path.

Trace-time telemetry (PR-1 registry): every ring loop counts
``collectives.ring.calls`` (+1), ``collectives.ring.hops`` (+n−1) and
``collectives.ring.bytes`` (+(n−1) × per-hop message bytes) — by
construction ``hops == (tp−1) × calls`` on a fixed-tp program, the
invariant the dryrun gate asserts.

The ring-only contract is additionally enforced structurally: the
``static_audit`` dryrun phase traces these paths under an active
:func:`overlap_scope` and walks the jaxpr
(``analysis/jaxpr_audit.py``) — any monolithic
``all_gather``/``psum``/``all_to_all`` equation inside the overlap
region fails CI, so a fallback path silently engaging under the scope
cannot ship.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.observability import metrics as _telemetry
from apex_tpu.utils.collectives import (
    match_vma,
    ppermute as _counted_ppermute,
    pvary as _pvary,
    vma_of,
)

__all__ = [
    "all_gather_matmul",
    "matmul_reduce_scatter",
    "matmul_all_reduce",
    "ring_all_gather",
    "ring_reduce_scatter",
    "overlap_scope",
    "overlap_enabled",
    "sequence_parallel_matmul",
    "gspmd_row_parallel_matmul",
]


# ---------------------------------------------------------------------------
# overlap_comm tri-state resolution
# ---------------------------------------------------------------------------

# Default for overlap_comm=None call sites; overlap_scope pushes overrides.
# amp.frontend.make_train_step(overlap_comm=...) traces the loss under a
# scope so TP contexts built with the tri-state default inherit the
# train-step's choice without re-plumbing every layer.
_SCOPE = [False]


@contextlib.contextmanager
def overlap_scope(enable: bool = True):
    """Set the default for ``overlap_comm=None`` call sites within the
    ``with`` block (trace-time: affects functions traced inside it)."""
    _SCOPE.append(bool(enable))
    try:
        yield
    finally:
        _SCOPE.pop()


def overlap_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve an ``overlap_comm`` tri-state: an explicit bool wins;
    ``None`` reads the innermost :func:`overlap_scope` (default off)."""
    return _SCOPE[-1] if flag is None else bool(flag)


# ---------------------------------------------------------------------------
# ring plumbing
# ---------------------------------------------------------------------------


def _note_ring(n: int, msg_nbytes: int) -> None:
    """Trace-time ring accounting: one call, n−1 hops, (n−1)·msg bytes."""
    reg = _telemetry.registry()
    if reg is None:
        return
    reg.counter("collectives.ring.calls").inc()
    reg.counter("collectives.ring.hops").inc(n - 1)
    reg.counter("collectives.ring.bytes").inc((n - 1) * int(msg_nbytes))


def _nbytes(x) -> int:
    return int(math.prod(x.shape or ())) * x.dtype.itemsize


def _perms(axis_name, n):
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


def _split_hops(n: int):
    """Bidirectional hop split: a fwd + b bwd hops, a+b = n−1, a ≥ b."""
    a = -(-(n - 1) // 2)
    return a, (n - 1) - a


def _zeros_like_vma(shape, dtype, *refs):
    axes = set()
    for r in refs:
        axes |= set(vma_of(r))
    return match_vma(jnp.zeros(shape, dtype), tuple(sorted(axes)))


def _mm(x, w):
    """x [..., k] @ w [k, p] with fp32 accumulation (fp32 output)."""
    return jax.lax.dot_general(
        x, w, dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _mm_grad_w(xc, gc):
    """dw [k, p] = Σ over every non-contracted dim of x [..., k] ⊗
    g [..., p] (fp32 accumulation)."""
    dims = tuple(range(xc.ndim - 1))
    return jax.lax.dot_general(
        xc, gc, dimension_numbers=((dims, dims), ((), ())),
        preferred_element_type=jnp.float32)


def _ring_visit(x, axis_name, visit):
    """Bidirectional all-gather ring over ``x``'s shards: call
    ``visit(src_rank, shard)`` once per rank's shard (``src_rank`` is a
    traced index; the local shard is visited first, at hop 0).  n−1 hops;
    hop t+1's ppermute depends only on the buffer, not on ``visit``'s
    consumption of it, so transfer t+1 overlaps compute t."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    x = _pvary(x, axis_name)
    visit(my, x)
    if n == 1:
        _note_ring(n, _nbytes(x))
        return
    fwd, bwd = _perms(axis_name, n)
    a, b = _split_hops(n)
    xf = x
    for t in range(1, a + 1):
        xf = _counted_ppermute(xf, axis_name, fwd)
        visit((my - t) % n, xf)
    xb = x
    for t in range(1, b + 1):
        xb = _counted_ppermute(xb, axis_name, bwd)
        visit((my + t) % n, xb)
    _note_ring(n, _nbytes(x))


def _ring_scatter_sum(axis_name, n, chunk_shape, dtype, part, *vma_refs):
    """Bidirectional reduce-scatter ring: ``part(d)`` computes this
    rank's fp32 contribution to destination chunk ``d`` (traced index);
    returns this rank's fully-summed chunk.  Two accumulators travel in
    opposite directions and meet at the destination after n−1 total
    hops; each hop's ``part`` for the next destination is independent of
    the in-flight accumulator, so compute overlaps transfer."""
    my = jax.lax.axis_index(axis_name)
    if n == 1:
        out = part(my)
        _note_ring(n, _nbytes(out))
        return out
    fwd, bwd = _perms(axis_name, n)
    a, b = _split_hops(n)
    acc_f = _zeros_like_vma(chunk_shape, dtype, *vma_refs)
    for t in range(a):
        acc_f = acc_f + part((my + a - t) % n)
        acc_f = _counted_ppermute(acc_f, axis_name, fwd)
    out = acc_f
    if b:
        acc_b = _zeros_like_vma(chunk_shape, dtype, *vma_refs)
        for t in range(b):
            acc_b = acc_b + part((my - b + t) % n)
            acc_b = _counted_ppermute(acc_b, axis_name, bwd)
        out = out + acc_b
    out = out + part(my)
    _note_ring(n, int(math.prod(chunk_shape)) * jnp.dtype(dtype).itemsize)
    return out


def _check_dims(x, w, dim, what):
    if w.ndim != 2:
        raise ValueError(f"{what}: w must be 2-D [k, p], got {w.shape}")
    if x.ndim < 2:
        raise ValueError(f"{what}: x must be at least 2-D, got {x.shape}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(
            f"{what}: contraction mismatch — x [..., {x.shape[-1]}] vs "
            f"w [{w.shape[0]}, ...]")
    if not (0 <= dim < x.ndim - 1):
        raise ValueError(
            f"{what}: ring dim {dim} must be a non-contracted dim of x "
            f"(ndim {x.ndim})")


# ---------------------------------------------------------------------------
# all_gather_matmul
# ---------------------------------------------------------------------------


def _agmm_impl(x, w, axis_name, gather_dim, out_dtype):
    n = jax.lax.axis_size(axis_name)
    m = x.shape[gather_dim]
    out_shape = (x.shape[:gather_dim] + (n * m,)
                 + x.shape[gather_dim + 1:-1] + (w.shape[1],))
    y = _zeros_like_vma(out_shape, jnp.float32, x, w)
    box = [y]

    def visit(src, shard):
        box[0] = jax.lax.dynamic_update_slice_in_dim(
            box[0], _mm(shard, w), src * m, axis=gather_dim)

    _ring_visit(x, axis_name, visit)
    return box[0].astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _agmm(x, w, axis_name, gather_dim):
    return _agmm_impl(x, w, axis_name, gather_dim,
                      jnp.result_type(x, w))


def _agmm_fwd(x, w, axis_name, gather_dim):
    return _agmm(x, w, axis_name, gather_dim), (x, w)


def _agmm_bwd(axis_name, gather_dim, res, g):
    x, w = res
    n = jax.lax.axis_size(axis_name)
    m = x.shape[gather_dim]
    # dx = reduce_scatter(g @ w^T) along gather_dim — the dual ring
    dx = _mmrs_impl(g, w.T.astype(g.dtype), axis_name, gather_dim,
                    x.dtype)
    # dw = gather(x)^T @ g: re-ring x, consuming each shard against its
    # rows of g the hop it lands (never materializing the gathered x)
    dw_box = [_zeros_like_vma(w.shape, jnp.float32, x, g)]

    def visit(src, shard):
        gc = jax.lax.dynamic_slice_in_dim(g, src * m, m, axis=gather_dim)
        dw_box[0] = dw_box[0] + _mm_grad_w(shard, gc)

    _ring_visit(x, axis_name, visit)
    return dx, dw_box[0].astype(w.dtype)


_agmm.defvjp(_agmm_fwd, _agmm_bwd)


def all_gather_matmul(x: jax.Array, w: jax.Array, axis_name: str, *,
                      gather_dim: int = 0) -> jax.Array:
    """``all_gather(x, dim=gather_dim) @ w`` as an overlapped ring.

    ``x`` is this rank's activation shard (sequence-parallel input of a
    column-parallel linear, [s/tp, ..., k]); ``w`` this rank's column
    shard [k, p/tp].  Each hop's incoming shard is matmul'd into its rows
    of the gathered output while the next transfer is in flight.  Output
    [s, ..., p/tp] in ``result_type(x, w)`` with fp32 accumulation.

    Backward: dx via :func:`matmul_reduce_scatter` (the transpose pair),
    dw via a ring re-gather of ``x`` — both n−1-hop rings, no monolithic
    collective under grad.  Call inside ``shard_map`` with ``axis_name``
    bound.
    """
    _check_dims(x, w, gather_dim, "all_gather_matmul")
    return _agmm(x, w, axis_name, gather_dim)


# ---------------------------------------------------------------------------
# matmul_reduce_scatter
# ---------------------------------------------------------------------------


def _mmrs_impl(x, w, axis_name, scatter_dim, out_dtype):
    n = jax.lax.axis_size(axis_name)
    M = x.shape[scatter_dim]
    if M % n:
        raise ValueError(
            f"matmul_reduce_scatter: dim {scatter_dim} of x ({M}) not "
            f"divisible by the '{axis_name}' axis size {n}")
    mc = M // n
    x = _pvary(x, axis_name)
    chunk_shape = (x.shape[:scatter_dim] + (mc,)
                   + x.shape[scatter_dim + 1:-1] + (w.shape[1],))

    def part(d):
        xc = jax.lax.dynamic_slice_in_dim(x, d * mc, mc, axis=scatter_dim)
        return _mm(xc, w)

    out = _ring_scatter_sum(axis_name, n, chunk_shape, jnp.float32, part,
                            x, w)
    return out.astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mmrs(x, w, axis_name, scatter_dim):
    return _mmrs_impl(x, w, axis_name, scatter_dim, jnp.result_type(x, w))


def _mmrs_fwd(x, w, axis_name, scatter_dim):
    return _mmrs(x, w, axis_name, scatter_dim), (x, w)


def _mmrs_bwd(axis_name, scatter_dim, res, g):
    """ONE ring over the scattered cotangent yields both grads: as chunk
    ``c`` of g lands, dx rows c (= g_c @ w^T) are written and x's rows c
    contribute x_c^T @ g_c to dw — the all-gather-matmul dual."""
    x, w = res
    mc = g.shape[scatter_dim]
    wT = w.T.astype(g.dtype)
    dx_box = [_zeros_like_vma(x.shape, jnp.float32, x, g)]
    dw_box = [_zeros_like_vma(w.shape, jnp.float32, x, g)]

    def visit(src, gc):
        dx_box[0] = jax.lax.dynamic_update_slice_in_dim(
            dx_box[0], _mm(gc, wT), src * mc, axis=scatter_dim)
        xc = jax.lax.dynamic_slice_in_dim(x, src * mc, mc,
                                          axis=scatter_dim)
        dw_box[0] = dw_box[0] + _mm_grad_w(xc, gc)

    _ring_visit(g, axis_name, visit)
    return dx_box[0].astype(x.dtype), dw_box[0].astype(w.dtype)


_mmrs.defvjp(_mmrs_fwd, _mmrs_bwd)


def matmul_reduce_scatter(x: jax.Array, w: jax.Array, axis_name: str, *,
                          scatter_dim: int = 0) -> jax.Array:
    """``reduce_scatter(x @ w, dim=scatter_dim)`` as an overlapped ring.

    ``x`` is this rank's full-length input with the contraction dim
    locally sharded ([s, ..., k/tp] of a row-parallel linear); ``w`` the
    row shard [k/tp, p].  A rotating accumulator visits every rank; each
    hop computes only the partial-product chunk the accumulator is
    destined for, so the next transfer overlaps the current chunk matmul.
    Output [s/tp, ..., p]: this rank's fully-summed chunk.

    Backward is a single ring over the output cotangent producing dx
    chunks and dw together (see :func:`all_gather_matmul` — the two are
    each other's transpose).  Call inside ``shard_map``.
    """
    _check_dims(x, w, scatter_dim, "matmul_reduce_scatter")
    return _mmrs(x, w, axis_name, scatter_dim)


# ---------------------------------------------------------------------------
# matmul_all_reduce
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mmar(x, w, axis_name, scatter_dim):
    from apex_tpu.utils.collectives import all_gather as _counted_ag

    y = _mmrs_impl(x, w, axis_name, scatter_dim, jnp.result_type(x, w))
    return _counted_ag(y, axis_name, axis=scatter_dim, tiled=True)


def _mmar_fwd(x, w, axis_name, scatter_dim):
    return _mmar(x, w, axis_name, scatter_dim), (x, w)


def _mmar_bwd(axis_name, scatter_dim, res, g):
    # The replicated-valued output is consumed per-shard, so a cotangent
    # that arrives still shard-varying is only this rank's contribution:
    # the true dy is the psum of the per-rank cotangents — the same sum
    # the monolithic path pays at copy_to's pvary transpose.  An
    # axis-invariant cotangent (already the total, e.g. an out_specs-
    # replicated consumer) skips it, keeping the backward
    # communication-free like reduce_from_tensor_model_parallel_region's
    # identity transpose; grad_sum makes exactly that distinction.
    from apex_tpu.utils.collectives import grad_sum

    x, w = res
    g = _pvary(grad_sum(g, axis_name), axis_name)
    dx = _mm(g, w.T.astype(g.dtype)).astype(x.dtype)
    dw = _mm_grad_w(x, g).astype(w.dtype)
    return dx, dw


_mmar.defvjp(_mmar_fwd, _mmar_bwd)


def matmul_all_reduce(x: jax.Array, w: jax.Array, axis_name: str, *,
                      scatter_dim: int = 0) -> jax.Array:
    """``psum(x @ w)`` as ring reduce-scatter + all-gather.

    Same wire bytes as the monolithic all-reduce, but the reduce-scatter
    half rides the ring overlapped with the partial-product matmul
    chunks.  ``scatter_dim`` names the dim the intermediate scatter
    tiles over (must be divisible by the axis size).  Backward psums the
    output cotangent only when it arrives shard-varying (the per-rank
    consumption of a replicated value — the same sum the monolithic
    path pays at ``copy_to_tensor_model_parallel_region``'s transpose);
    an axis-invariant cotangent is used as-is, communication-free.
    """
    _check_dims(x, w, scatter_dim, "matmul_all_reduce")
    return _mmar(x, w, axis_name, scatter_dim)


# ---------------------------------------------------------------------------
# bare ring collectives (the sequence-parallel mapping decompositions)
# ---------------------------------------------------------------------------


def ring_all_gather(x: jax.Array, axis_name: str, *,
                    dim: int = 0) -> jax.Array:
    """``all_gather(x, dim)`` decomposed into n−1 ``ppermute`` hops.

    Each hop's chunk is placed as it lands, so downstream consumers of
    early rows can start before the last hop arrives (the scheduler's
    hook for overlapping the gather with neighboring compute).  Plain
    jax autodiff transposes the ring into a ring (reversed ppermutes),
    so no custom VJP is needed.
    """
    m = x.shape[dim]
    n = jax.lax.axis_size(axis_name)
    out_shape = x.shape[:dim] + (n * m,) + x.shape[dim + 1:]
    box = [_zeros_like_vma(out_shape, x.dtype, x)]

    def visit(src, shard):
        box[0] = jax.lax.dynamic_update_slice_in_dim(
            box[0], shard, src * m, axis=dim)

    _ring_visit(x, axis_name, visit)
    return box[0]


def ring_reduce_scatter(x: jax.Array, axis_name: str, *,
                        dim: int = 0) -> jax.Array:
    """``psum_scatter(x, dim, tiled=True)`` decomposed into n−1
    ``ppermute`` hops with a rotating accumulator (sum semantics)."""
    n = jax.lax.axis_size(axis_name)
    M = x.shape[dim]
    if M % n:
        raise ValueError(
            f"ring_reduce_scatter: dim {dim} of x ({M}) not divisible "
            f"by the '{axis_name}' axis size {n}")
    mc = M // n
    x = _pvary(x, axis_name)
    chunk_shape = x.shape[:dim] + (mc,) + x.shape[dim + 1:]

    def part(d):
        return jax.lax.dynamic_slice_in_dim(x, d * mc, mc, axis=dim)

    return _ring_scatter_sum(axis_name, n, chunk_shape, x.dtype, part, x)


# ---------------------------------------------------------------------------
# GSPMD shard_map islands (the _cp_core_attention pattern)
# ---------------------------------------------------------------------------


def _abstract_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def _mesh_axis(mesh, axis_name):
    """Axis size when present on the mesh, else 0."""
    if axis_name is None or axis_name not in mesh.axis_names:
        return 0
    return int(mesh.shape[axis_name])


def sequence_parallel_matmul(x: jax.Array, w: jax.Array, *,
                             mode: str, axis_name: str = "tp",
                             dim: int = 0,
                             enable: Optional[bool] = None):
    """Shard_map island for the GSPMD Column/Row parallel flax layers.

    ``mode='gather'``: ``x`` sequence-sharded over ``axis_name`` at
    ``dim``, ``w`` column-sharded on its last dim → ring
    :func:`all_gather_matmul`; output carries the full sequence with the
    last dim still tp-sharded.  ``mode='scatter'``: ``x`` with its last
    dim tp-sharded, ``w`` row-sharded on dim 0 → ring
    :func:`matmul_reduce_scatter`; output sequence-scattered over
    ``axis_name`` at ``dim`` (constrain it afterwards to re-gather for
    non-sequence-parallel semantics — XLA then overlaps that all-gather
    with downstream compute).

    Returns ``None`` when the ring path does not apply (overlap
    disabled, no active mesh, axis absent or size 1, indivisible dims):
    the caller falls back to the monolithic collective.
    """
    if mode not in ("gather", "scatter"):
        raise ValueError(f"mode must be 'gather' or 'scatter', got {mode!r}")
    if not overlap_enabled(enable):
        return None
    mesh = _abstract_mesh()
    if mesh is None:
        return None
    n = _mesh_axis(mesh, axis_name)
    if n < 2:
        return None
    rest = [None] * (x.ndim - 1)
    if mode == "gather":
        if x.shape[dim] % n or w.shape[1] % n:
            return None
        x_spec = P(*([None] * dim + [axis_name] + rest[dim:]))
        w_spec = P(None, axis_name)
        out_spec = P(*([None] * (x.ndim - 1) + [axis_name]))
        fn = functools.partial(all_gather_matmul, axis_name=axis_name,
                               gather_dim=dim)
    elif mode == "scatter":
        if x.shape[dim] % n or x.shape[-1] % n or w.shape[0] % n:
            return None
        x_spec = P(*(rest + [axis_name]))
        w_spec = P(axis_name, None)
        out_spec = P(*([None] * dim + [axis_name]
                       + [None] * (x.ndim - 1 - dim)))
        fn = functools.partial(matmul_reduce_scatter, axis_name=axis_name,
                               scatter_dim=dim)
    f = jax.shard_map(fn, mesh=mesh, in_specs=(x_spec, w_spec),
                      out_specs=out_spec)
    return f(x, w)


def gspmd_row_parallel_matmul(x: jax.Array, w: jax.Array, *,
                              tp_axis: str = "tp",
                              batch_axis: str = "dp",
                              seq_axis: Optional[str] = None,
                              enable: Optional[bool] = None):
    """Overlapped row-parallel matmul for the GSPMD model forward.

    ``x`` [b, s, k] with k tp-sharded (attention/MLP output partials),
    ``w`` [k, h] row-sharded: the island runs the ring
    :func:`matmul_reduce_scatter` over ``tp_axis`` scattering the local
    sequence dim, and returns the output sequence-sharded over
    ``(seq_axis, tp_axis)`` — the caller's hidden-state constraint then
    re-gathers over tp lazily (overlappable), replacing the monolithic
    tp all-reduce XLA would otherwise serialize after the matmul.

    Returns ``None`` when inapplicable (overlap disabled, no mesh, tp
    absent/1, indivisible batch/seq/contraction dims) so callers fall
    back to the annotated monolithic path.
    """
    if not overlap_enabled(enable) or x.ndim != 3 or w.ndim != 2:
        return None
    mesh = _abstract_mesh()
    if mesh is None:
        return None
    tp = _mesh_axis(mesh, tp_axis)
    if tp < 2:
        return None
    dp = max(_mesh_axis(mesh, batch_axis), 1)
    sp = max(_mesh_axis(mesh, seq_axis), 1)
    b, s, k = x.shape
    if b % dp or s % (sp * tp) or k % tp or k != w.shape[0]:
        return None
    bspec = batch_axis if dp > 1 or batch_axis in mesh.axis_names else None
    sspec = seq_axis if (seq_axis and seq_axis in mesh.axis_names) else None
    seq_out = (sspec, tp_axis) if sspec else tp_axis
    f = jax.shard_map(
        functools.partial(matmul_reduce_scatter, axis_name=tp_axis,
                          scatter_dim=1),
        mesh=mesh,
        in_specs=(P(bspec, sspec, tp_axis), P(tp_axis, None)),
        out_specs=P(bspec, seq_out, None))
    return f(x, w)
