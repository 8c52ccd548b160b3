"""Grouped (segment) matmul — the ragged expert-FFN compute primitive.

Capacity-free MoE routing (transformer/moe.py ``routing='ragged'``) sorts
tokens by expert and hands each expert a *ragged* ``[tokens, k]`` segment;
the FFN is then ``out[r] = x[r] @ w[group(r)]`` with segment boundaries in
an offsets vector — no pad-to-capacity slots, no dropped tokens (the
megablocks formulation, arXiv:2211.15841, on TPU).

Two implementations behind one route (the flash/paged-attention pattern):

- **kernel** — Pallas kernels whose grid walks (group, row-block)
  intersection steps.  The per-step block/group ids, first-visit flags and
  the group offsets ride in SMEM via scalar prefetch, so the weight
  BlockSpec index map dereferences the right expert's slab per step and a
  row block shared by two experts is visited once per expert with row
  masks (a block whole inside one expert skips the mask).  Operands
  multiply in the dtype they come in (bf16 x bf16 on the MXU) with f32
  accumulation; rows, the contraction (past 4096) and the output columns
  are tiled, so a ``[2048, 3072]`` expert fits VMEM.  Only blocks that a
  span touches are visited: compute is proportional to the rows inside
  the window plus one partial block per boundary — never ``G·N·k·p`` and
  not ``N·k·p`` either when the window is a small part of a worst-case
  buffer; the steps past the real count keep their block indices, move
  nothing and multiply nothing.
- **reference** — the XLA segment-sum form: one masked matmul per group
  (``G`` dense matmuls), trivially correct and differentiable; the parity
  oracle and the CPU path.

``backend=None`` picks the kernel on TPU (or under
``APEX_TPU_PALLAS_INTERPRET=1``) and the reference elsewhere
(``_pallas_utils.resolve_backend``); ``"kernel"``/``"reference"`` pin.

``offsets`` may describe a *window*: ``offsets[0] > 0`` / ``offsets[-1] <
N`` leave the rows outside ``[offsets[0], offsets[-1])`` exactly zero in
the output (the expert-parallel ring path computes only its local experts'
window of a remote rank's token array this way; an expert layer that holds
some of the experts sorts their rows to the front of its buffer).  Offsets
may be traced values — all metadata is built with jnp and static shapes.

Backward, on the kernel route three kernels in all, named apart in a trace
(``gmm_fwd``, ``gmm_dx``, ``gmm_dw``): ``dx = g @ w[group]^T`` is the
forward kernel reading the slab as it lies (transposed in the dot, no
transposed copy), and ``dw[e] = x_seg(e)^T @ g_seg(e)`` a grouped kernel of
its own over the same walk, its steps grouped by expert, so that the weight
gradient too costs in proportion to the rows in the window.  On the
reference route both are masked XLA products over all rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import (
    on_tpu,
    out_struct,
    param_cotangent,
    resolve_backend,
)

__all__ = ["grouped_matmul", "grouped_matmul_quantized",
           "grouped_matmul_reference", "group_ids",
           "quantize_group_weights"]


def group_ids(offsets: jax.Array, n_rows: int, n_groups: int) -> jax.Array:
    """Group index per row: ``[n_rows]`` int32 in ``[0, n_groups]`` where
    rows outside the ``[offsets[0], offsets[-1])`` window get the
    sentinel ``n_groups`` (callers gather per-row biases through a
    zero-padded table so sentinel rows stay exactly zero)."""
    r = jnp.arange(n_rows, dtype=jnp.int32)
    off = offsets.astype(jnp.int32)
    g = jnp.searchsorted(off, r, side="right").astype(jnp.int32) - 1
    valid = (r >= off[0]) & (r < off[-1])
    return jnp.where(valid, jnp.clip(g, 0, n_groups - 1), n_groups)


def _check(x, w, offsets):
    if x.ndim != 2 or w.ndim != 3 or offsets.ndim != 1:
        raise ValueError(
            f"grouped_matmul: expected x [N, k], w [G, k, p], offsets "
            f"[G+1]; got {x.shape}, {w.shape}, {offsets.shape}")
    if w.shape[0] + 1 != offsets.shape[0]:
        raise ValueError(
            f"grouped_matmul: offsets length {offsets.shape[0]} != "
            f"G + 1 = {w.shape[0] + 1}")
    if x.shape[1] != w.shape[1]:
        raise ValueError(
            f"grouped_matmul: contraction mismatch — x [..., {x.shape[1]}]"
            f" vs w [., {w.shape[1]}, .]")


def grouped_matmul_reference(x: jax.Array, w: jax.Array,
                             offsets: jax.Array) -> jax.Array:
    """Segment-sum reference: ``out[r] = x[r] @ w[g]`` for rows in group
    ``g``'s ``[offsets[g], offsets[g+1])`` span, zero outside every
    span — one masked dense matmul per group."""
    _check(x, w, offsets)
    n = x.shape[0]
    off = offsets.astype(jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)
    out = jnp.zeros((n, w.shape[-1]), jnp.float32)
    for g in range(w.shape[0]):
        mask = ((rows >= off[g]) & (rows < off[g + 1]))[:, None]
        xg = jnp.where(mask, x.astype(jnp.float32), 0.0)
        out = out + jnp.where(
            mask,
            jax.lax.dot(xg, w[g].astype(jnp.float32),
                        preferred_element_type=jnp.float32),
            0.0)
    return out.astype(jnp.result_type(x, w))


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

_BLOCK_ROWS = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def _tile(dim: int, prefs) -> int:
    """The first of ``prefs`` that divides ``dim``, else the whole dim (a
    block equal to the array's extent is always legal)."""
    for t in prefs:
        if dim % t == 0:
            return t
    return dim


def _block_rows(n: int) -> int:
    if n >= _BLOCK_ROWS:
        return _BLOCK_ROWS
    if n >= 128:
        return 128
    return max(16, 16 * pl.cdiv(n, 16))


def _step_metadata(offsets, n_rows, n_groups, bm):
    """Static-shape walk of the (group, row-block) intersections, group by
    group: each group visits the ``bm``-row blocks its span
    ``[offsets[g], offsets[g+1])`` touches (an empty group visits one,
    fully masked, so that its weight gradient is written).  Blocks no
    span touches are never visited: the work is in proportion to the rows
    inside the window, not to ``n_rows``.  At most ``B + G`` real steps
    (``B = ceil(N/bm)``), the static bound the grid uses; trailing steps
    repeat the last real one and are skipped in the kernels.

    Returns ``(block, group, block_first, group_first, group_last,
    total)``; built from jnp so traced offsets work."""
    nb = pl.cdiv(n_rows, bm)
    n_steps = nb + n_groups
    off = offsets.astype(jnp.int32)
    start, end = off[:-1], off[1:]
    first_blk = jnp.clip(start // bm, 0, nb - 1)
    last_blk = jnp.clip(
        jnp.where(end > start, (end - 1) // bm, start // bm), 0, nb - 1)
    last_blk = jnp.maximum(last_blk, first_blk)
    per_group = last_blk - first_blk + 1                   # [G], >= 1
    cum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(per_group, dtype=jnp.int32)])
    total = jnp.minimum(cum[-1], n_steps)
    groups = jnp.arange(n_groups, dtype=jnp.int32)
    step_group = jnp.clip(
        jnp.repeat(groups, per_group, total_repeat_length=n_steps),
        0, n_groups - 1).astype(jnp.int32)
    within = jnp.arange(n_steps, dtype=jnp.int32) - cum[step_group]
    step_block = jnp.minimum(first_blk[step_group] + within,
                             last_blk[step_group]).astype(jnp.int32)
    one = jnp.ones(1, jnp.int32)
    block_first = jnp.concatenate(
        [one, (step_block[1:] != step_block[:-1]).astype(jnp.int32)])
    group_first = jnp.concatenate(
        [one, (step_group[1:] != step_group[:-1]).astype(jnp.int32)])
    steps = jnp.arange(n_steps, dtype=jnp.int32)
    group_last = jnp.concatenate(
        [(step_group[1:] != step_group[:-1]).astype(jnp.int32), one])
    group_last = jnp.where(steps == total - 1, 1, group_last)
    return (step_block, step_group, block_first, group_first, group_last,
            total.reshape(1))


def _live_rows(s, bm, n_rows, blk_ref, grp_ref, off_ref):
    """``(whole, live)`` of step ``s``: whether every row of its block
    belongs to its group (no mask is needed then), and the ``[bm, 1]`` mask
    of the rows that do."""
    g = grp_ref[s]
    first = blk_ref[s] * bm
    start, end = off_ref[g], jnp.minimum(off_ref[g + 1], n_rows)
    rows = first + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    return ((first >= start) & (first + bm <= end),
            (rows >= start) & (rows < end))


def _masked(live, x):
    return jnp.where(live, x, jnp.zeros_like(x))


def _gmm_kernel(bm, n_rows, n_k, transpose_rhs, quant, *refs):
    """Grid ``(p tiles, steps, k tiles)``; one step = one (group,
    row-block) intersection.  Consecutive steps on one row block share the
    f32 accumulator: the block's first visit overwrites, later visits (the
    next group's rows of a block two groups share) add.  Rows outside the
    step's group span are zeroed on the input side, so each row gets
    exactly its own expert's product; a block that lies whole inside its
    group skips the mask.  Operands multiply in the dtype they come in
    (bf16 x bf16 on the MXU), accumulated in f32.  Steps past the real
    count do nothing.

    ``quant`` (ISSUE 14): the expert slab is pre-quantized int8 and an
    extra ref carries its per-(k-block, column) scales; the slab
    dequantizes in VMEM right before the dot, so the HBM read of the
    weights is the int8 bytes (the k dimension is then one tile)."""
    if quant:
        (blk_ref, grp_ref, fst_ref, off_ref, nst_ref,
         x_ref, w_ref, s_ref, out_ref, acc) = refs
    else:
        (blk_ref, grp_ref, fst_ref, off_ref, nst_ref,
         x_ref, w_ref, out_ref, acc) = refs
        s_ref = None
    s = pl.program_id(1)
    kk = pl.program_id(2)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def step(x):
        w = w_ref[0]
        if quant:
            k, p = w.shape
            nkb = s_ref.shape[1]
            w = (w.astype(jnp.float32).reshape(nkb, k // nkb, p)
                 * s_ref[0][:, None, :]).reshape(k, p)
            x = x.astype(jnp.float32)
        part = jax.lax.dot_general(x, w, dims,
                                   preferred_element_type=jnp.float32)
        fresh = (fst_ref[s] == 1) & (kk == 0)

        @pl.when(fresh)
        def _init():
            acc[...] = part

        @pl.when(jnp.logical_not(fresh))
        def _accum():
            acc[...] = acc[...] + part

        @pl.when(kk == n_k - 1)
        def _store():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    real = s < nst_ref[0]
    whole, live = _live_rows(s, bm, n_rows, blk_ref, grp_ref, off_ref)

    @pl.when(real & whole)
    def _whole():
        step(x_ref[...])

    @pl.when(real & jnp.logical_not(whole))
    def _edge():
        step(_masked(live, x_ref[...]))


def _compiler_params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _gmm_pallas(x, w, offsets, interpret, scale=None,
                transpose_rhs=False):
    from jax.experimental.pallas import tpu as pltpu

    n, k = x.shape
    g_n = w.shape[0]
    p = w.shape[1] if transpose_rhs else w.shape[2]
    bm = _block_rows(n)
    # one k tile up to 4096 (the expert widths): x [bm, k] and one
    # [k, bn] slab tile, double-buffered, are 12 MB in bf16 at k = 2048
    bn = _tile(p, (1024, 512, 256, 128))
    bk = k if scale is not None or k <= 4096 else _tile(
        k, (2048, 1024, 512, 256, 128))
    n_k = k // bk
    blk, grp, fst, _, _, nst = _step_metadata(offsets, n, g_n, bm)
    n_steps = int(blk.shape[0])
    out_dtype = x.dtype if scale is not None else jnp.result_type(x, w)
    def k_tile(s, kk, nst):
        # steps past the real count stay on the last tile: an unchanged
        # block index moves nothing
        return jnp.where(s < nst[0], kk, n_k - 1)

    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, bn, bk),
            lambda j, s, kk, blk, grp, fst, off, nst:
            (grp[s], j, k_tile(s, kk, nst)))
    else:
        w_spec = pl.BlockSpec(
            (1, bk, bn),
            lambda j, s, kk, blk, grp, fst, off, nst:
            (grp[s], k_tile(s, kk, nst), j))
    in_specs = [
        pl.BlockSpec((bm, bk),
                     lambda j, s, kk, blk, grp, fst, off, nst:
                     (blk[s], k_tile(s, kk, nst))),
        w_spec,
    ]
    inputs = [x, w]
    if scale is not None:
        # the scale slab dereferences through the SAME per-step group
        # id, so the weight tile and its scales arrive together
        nkb = scale.shape[1]
        in_specs.append(pl.BlockSpec(
            (1, nkb, bn),
            lambda j, s, kk, blk, grp, fst, off, nst: (grp[s], 0, j)))
        inputs.append(scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(p // bn, n_steps, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (bm, bn),
            lambda j, s, kk, blk, grp, fst, off, nst: (blk[s], j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    off = offsets.astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, bm, n, n_k, transpose_rhs,
                          scale is not None),
        grid_spec=grid_spec,
        out_shape=out_struct((n, p), out_dtype, x),
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(blk, grp, fst, off, nst, *inputs)
    # blocks that no span touches were never written: rows outside the
    # window are zero by the contract, whatever the buffer held
    rows = jnp.arange(n, dtype=jnp.int32)
    inside = ((rows >= off[0]) & (rows < off[-1]))[:, None]
    return jnp.where(inside, out, jnp.zeros_like(out))


def _tgmm_kernel(bm, n_rows, *refs):
    """``dw[g] = x_seg(g)^T @ dy_seg(g)``: grid ``(k tiles, p tiles,
    steps)``.  The steps of one group are consecutive; its first step
    overwrites the f32 accumulator, its last writes the ``[bk, bp]`` tile
    of that group's gradient.  Rows of a shared block that belong to the
    other group are masked on both sides (a block whole inside its group
    skips the mask)."""
    (blk_ref, grp_ref, gfirst_ref, glast_ref, off_ref, nst_ref,
     x_ref, dy_ref, out_ref, acc) = refs
    s = pl.program_id(2)

    def step(x, dy):
        part = jax.lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(gfirst_ref[s] == 1)
        def _init():
            acc[...] = part

        @pl.when(gfirst_ref[s] == 0)
        def _accum():
            acc[...] = acc[...] + part

        @pl.when(glast_ref[s] == 1)
        def _store():
            out_ref[0] = acc[...].astype(out_ref.dtype)

    real = s < nst_ref[0]
    whole, live = _live_rows(s, bm, n_rows, blk_ref, grp_ref, off_ref)

    @pl.when(real & whole)
    def _whole():
        step(x_ref[...], dy_ref[...])

    @pl.when(real & jnp.logical_not(whole))
    def _edge():
        step(_masked(live, x_ref[...]), _masked(live, dy_ref[...]))


def _tgmm_pallas(x, dy, offsets, out_dtype, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, k = x.shape
    p = dy.shape[1]
    g_n = offsets.shape[0] - 1
    bm = _block_rows(n)
    bk = _tile(k, (1024, 512, 256, 128))
    bp = _tile(p, (1024, 512, 256, 128))
    blk, grp, _, gfirst, glast, nst = _step_metadata(offsets, n, g_n, bm)
    n_steps = int(blk.shape[0])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(k // bk, p // bp, n_steps),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, s, blk, grp, gf, gl, off, nst:
                         (blk[s], i)),
            pl.BlockSpec((bm, bp),
                         lambda i, j, s, blk, grp, gf, gl, off, nst:
                         (blk[s], j)),
        ],
        out_specs=pl.BlockSpec(
            (1, bk, bp),
            lambda i, j, s, blk, grp, gf, gl, off, nst: (grp[s], i, j)),
        scratch_shapes=[pltpu.VMEM((bk, bp), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, bm, n),
        grid_spec=grid_spec,
        out_shape=out_struct((g_n, k, p), out_dtype, x),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(blk, grp, gfirst, glast, offsets.astype(jnp.int32), nst, x, dy)


# ---------------------------------------------------------------------------
# routing + VJP
# ---------------------------------------------------------------------------


def _gmm_impl(x, w, offsets, backend, transpose_rhs=False):
    """``x @ w[g]`` per row, or ``x @ w[g]^T`` (the input gradient, which
    reads the expert slab as it lies instead of a transposed copy)."""
    p = w.shape[1] if transpose_rhs else w.shape[2]
    if x.shape[0] == 0:
        return jnp.zeros((0, p), jnp.result_type(x, w))
    if resolve_backend("grouped_matmul", backend) == "reference":
        return grouped_matmul_reference(
            x, w.swapaxes(1, 2) if transpose_rhs else w, offsets)
    with jax.named_scope("gmm_dx" if transpose_rhs else "gmm_fwd"):
        return _gmm_pallas(x, w, offsets, interpret=not on_tpu(),
                           transpose_rhs=transpose_rhs)


def _grouped_dw_reference(x, g, offsets):
    """``dw[e] = x_seg(e)^T @ g_seg(e)`` as masked segment outer products
    over all rows (fp32): ``G`` times the useful work, the parity oracle
    and the CPU path."""
    n = x.shape[0]
    off = offsets.astype(jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    parts = []
    for e in range(off.shape[0] - 1):
        mask = ((rows >= off[e]) & (rows < off[e + 1]))[:, None]
        parts.append(jax.lax.dot(
            jnp.where(mask, xf, 0.0).T, jnp.where(mask, gf, 0.0),
            preferred_element_type=jnp.float32))
    return jnp.stack(parts)


def _grouped_dw(x, g, offsets, out_dtype, backend):
    """The weight gradient: the grouped kernel (work in proportion to the
    rows inside the window) or the masked XLA reference, by the route."""
    if x.shape[0] == 0:
        return jnp.zeros((offsets.shape[0] - 1, x.shape[1], g.shape[1]),
                         out_dtype)
    if resolve_backend("grouped_matmul", backend) == "reference":
        return _grouped_dw_reference(x, g, offsets).astype(out_dtype)
    with jax.named_scope("gmm_dw"):
        return _tgmm_pallas(x, g.astype(x.dtype), offsets, out_dtype,
                            interpret=not on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, offsets, backend):
    return _gmm_impl(x, w, offsets, backend)


def _gmm_fwd(x, w, offsets, backend):
    return _gmm(x, w, offsets, backend), (x, w, offsets)


def _gmm_bwd(backend, res, g):
    x, w, offsets = res
    dx = _gmm_impl(g, w.astype(g.dtype), offsets, backend,
                   transpose_rhs=True).astype(x.dtype)
    dw = param_cotangent(
        _grouped_dw(x, g, offsets, w.dtype, backend), w)
    d_off = np.zeros(offsets.shape, jax.dtypes.float0)
    return dx, dw, d_off


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, offsets: jax.Array, *,
                   backend: Optional[str] = None) -> jax.Array:
    """``out[r] = x[r] @ w[g]`` for rows ``r`` in group ``g``'s span
    ``[offsets[g], offsets[g+1])``; rows outside every span (including
    outside a window — ``offsets[0] > 0`` / ``offsets[-1] < N``) come
    back exactly zero.

    ``x`` ``[N, k]`` sorted by group, ``w`` ``[G, k, p]`` stacked group
    weights, ``offsets`` ``[G+1]`` non-decreasing int (traced values
    fine).  fp32 accumulation, output in ``result_type(x, w)``.

    ``backend``: ``None`` routes automatically (Pallas kernel on TPU or
    under ``APEX_TPU_PALLAS_INTERPRET=1``; XLA segment-sum reference
    otherwise), ``"kernel"`` / ``"reference"`` pin a path — the parity
    suite compares the two.

    Differentiable: ``dx`` re-enters the routed primitive with the
    weights transposed (kernel backward stays a kernel), ``dw`` runs as
    masked segment outer products.
    """
    _check(x, w, offsets)
    return _gmm(x, w, offsets, backend)


# ---------------------------------------------------------------------------
# Weight-only int8 quantized slab path (ISSUE 14)
# ---------------------------------------------------------------------------


def quantize_group_weights(w, block: Optional[int] = None) -> dict:
    """Pre-quantize an expert weight slab ``[G, k, p]`` → ``{"wire":
    int8 [G, k, p], "scale": fp32 [G, k/kb, p]}`` — per-expert exactly
    :func:`~apex_tpu.ops.dense.quantize_weight` vmapped over the
    expert axis, so the dense and grouped slab forms share ONE
    quantization definition (one fp32 scale per (k-block, output
    column); the block is recoverable from the shapes, so the dict
    stays a pure array pytree)."""
    from apex_tpu.ops.dense import quantize_weight

    w = jnp.asarray(w)
    if w.ndim != 3:
        raise ValueError(
            f"quantize_group_weights expects [G, k, p] slabs, got "
            f"{w.shape}")
    return jax.vmap(lambda we: quantize_weight(we, block))(w)


def _check_group_slab(wire, scale) -> None:
    g_n, k, p = wire.shape
    if (scale.ndim != 3 or scale.shape[0] != g_n
            or scale.shape[2] != p or not scale.shape[1]
            or k % scale.shape[1]):
        raise ValueError(
            f"scale {scale.shape} does not tile slab {wire.shape}")


def _dequantize_group(wire, scale):
    from apex_tpu.ops.dense import dequantize_weight

    _check_group_slab(wire, scale)
    return jax.vmap(dequantize_weight)(wire, scale)


def _gmmq_impl(x, wire, scale, offsets, backend):
    if x.shape[0] == 0:
        return jnp.zeros((0, wire.shape[-1]), x.dtype)
    if resolve_backend("quantized grouped_matmul",
                       backend) == "reference":
        return grouped_matmul_reference(
            x, _dequantize_group(wire, scale), offsets).astype(x.dtype)
    return _gmm_pallas(x, wire, offsets, interpret=not on_tpu(),
                       scale=scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmmq(x, wire, scale, offsets, backend, x_dtype):
    return _gmmq_impl(x, wire, scale, offsets, backend)


def _gmmq_fwd(x, wire, scale, offsets, backend, x_dtype):
    return _gmmq(x, wire, scale, offsets, backend, x_dtype), (
        wire, scale, offsets)


def _gmmq_bwd(backend, x_dtype, res, g):
    # high-precision backward: dx runs the ROUTED float primitive over
    # the fp32-dequantized slab (transposed), so no requantization
    # error enters the cotangent; the frozen wire gets a float0
    # cotangent (int8) and the scales zeros — serving constants, the
    # same contract as ops/dense.quantize_weight
    wire, scale, offsets = res
    deq = _dequantize_group(wire, scale)
    dx = _gmm_impl(g.astype(jnp.float32), deq.swapaxes(1, 2), offsets,
                   backend).astype(x_dtype)
    d_off = np.zeros(offsets.shape, jax.dtypes.float0)
    return (dx, np.zeros(wire.shape, jax.dtypes.float0),
            jnp.zeros_like(scale), d_off)


_gmmq.defvjp(_gmmq_fwd, _gmmq_bwd)


def grouped_matmul_quantized(x: jax.Array, wire: jax.Array,
                             scale: jax.Array, offsets: jax.Array, *,
                             backend: Optional[str] = None) -> jax.Array:
    """:func:`grouped_matmul` off a pre-quantized expert slab
    (:func:`quantize_group_weights`): ``out[r] = x[r] @ deq(w[g])`` for
    rows in group ``g``'s span, rows outside every span exactly zero,
    output in ``x.dtype`` with fp32 accumulation.

    The kernel route extends the float grouped kernel: the per-step
    group index also dereferences the slab's scale rows, and each
    step's ``[k, p]`` expert tile dequantizes in VMEM before its dot —
    the HBM weight read per step is the int8 bytes, which is the
    decode-bandwidth win.  ``backend`` routes as in
    ``ops/dense.dense_quantized``; the XLA reference dequantizes
    the whole slab — the parity oracle.  Backward stays high-precision
    (``dx`` against fp32 dequantized weights; wire/scales frozen)."""
    if x.ndim != 2 or wire.ndim != 3 or offsets.ndim != 1:
        raise ValueError(
            f"grouped_matmul_quantized: expected x [N, k], wire "
            f"[G, k, p], offsets [G+1]; got {x.shape}, {wire.shape}, "
            f"{offsets.shape}")
    if wire.shape[0] + 1 != offsets.shape[0]:
        raise ValueError(
            f"grouped_matmul_quantized: offsets length "
            f"{offsets.shape[0]} != G + 1 = {wire.shape[0] + 1}")
    if x.shape[1] != wire.shape[1]:
        raise ValueError(
            f"grouped_matmul_quantized: contraction mismatch — x "
            f"[..., {x.shape[1]}] vs wire [., {wire.shape[1]}, .]")
    _check_group_slab(wire, scale)
    return _gmmq(x, wire, scale, offsets, backend,
                 jnp.dtype(x.dtype).name)
