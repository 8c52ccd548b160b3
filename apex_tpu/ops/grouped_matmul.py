"""Grouped (segment) matmul — the ragged expert-FFN compute primitive.

Capacity-free MoE routing (transformer/moe.py ``routing='ragged'``) sorts
tokens by expert and hands each expert a *ragged* ``[tokens, k]`` segment;
the FFN is then ``out[r] = x[r] @ w[group(r)]`` with segment boundaries in
an offsets vector — no pad-to-capacity slots, no dropped tokens (the
megablocks formulation, arXiv:2211.15841, on TPU).

Two implementations behind one route (the flash/paged-attention pattern):

- **kernel** — a Pallas kernel whose grid walks (row-block, group)
  intersection steps.  The per-step block/group ids, first-visit flags and
  the group offsets ride in SMEM via scalar prefetch, so the weight
  BlockSpec index map dereferences the right expert's ``[k, p]`` slab per
  step and a row block shared by two experts is visited once per expert
  with row masks — compute is proportional to ``N·k·p`` + one partial
  block per boundary, never ``G·N·k·p``.
- **reference** — the XLA segment-sum form: one masked matmul per group
  (``G`` dense matmuls), trivially correct and differentiable; the parity
  oracle and the CPU path.

``APEX_TPU_GROUPED_MATMUL=kernel|reference|auto`` overrides the route;
``auto`` picks the kernel on TPU (or under ``APEX_TPU_PALLAS_INTERPRET=1``)
and the reference elsewhere.

``offsets`` may describe a *window*: ``offsets[0] > 0`` / ``offsets[-1] <
N`` leave the rows outside ``[offsets[0], offsets[-1])`` exactly zero in
the output (the expert-parallel ring path computes only its local experts'
window of a remote rank's token array this way).  Offsets may be traced
values — all metadata is built with jnp and static shapes.

Backward: ``dx = grouped_matmul(g, w.swapaxes(1, 2), offsets)`` (the same
routed primitive — kernel backward stays a kernel) and ``dw[e] =
x_seg(e)^T @ g_seg(e)`` as masked segment outer products (XLA on both
routes; its access pattern is weight-stationary, not token-stationary, and
the G small ``[k, N]·[N, p]`` products fuse well).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import out_struct, param_cotangent
from apex_tpu.utils.registry import on_tpu

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
# Pallas kernel
# ---------------------------------------------------------------------------

_BLOCK_ROWS = 128


def _gmm_kernel(bm, n_rows, quant, *refs):
    """One grid step = one (row-block, group) intersection.  Consecutive
    steps share a row block (the f32 accumulator stays VMEM-resident);
    the first visit of a block overwrites, later visits add.  Rows
    outside the step's group span are zeroed *on the input side*, so a
    block straddling two groups gets each row exactly its own expert's
    product.

    ``quant`` (ISSUE 14): the expert slab is pre-quantized int8 and an
    extra ref carries its per-(k-block, column) scales (dereferenced by
    the same group index map) — the slab dequantizes in VMEM right
    before the dot, so the HBM read of the weights is the int8 bytes."""
    if quant:
        (blk_ref, grp_ref, fst_ref, off_ref, nst_ref,
         x_ref, w_ref, s_ref, out_ref, acc) = refs
    else:
        (blk_ref, grp_ref, fst_ref, off_ref, nst_ref,
         x_ref, w_ref, out_ref, acc) = refs
        s_ref = None
    s = pl.program_id(0)
    g = grp_ref[s]
    start = off_ref[g]
    end = off_ref[g + 1]
    rows = blk_ref[s] * bm + jax.lax.broadcasted_iota(
        jnp.int32, (bm, 1), 0)
    # padded trailing steps (s >= the actual intersection count) must
    # contribute nothing; their block id aliases the last real block
    live = (rows >= start) & (rows < end) & (rows < n_rows) \
        & (s < nst_ref[0])
    xm = jnp.where(live, x_ref[:].astype(jnp.float32), 0.0)
    w = w_ref[0].astype(jnp.float32)
    if quant:
        k, p = w.shape
        nkb = s_ref.shape[1]
        w = (w.reshape(nkb, k // nkb, p)
             * s_ref[0][:, None, :]).reshape(k, p)
    part = jax.lax.dot(xm, w, preferred_element_type=jnp.float32)

    @pl.when(fst_ref[s] == 1)
    def _init():
        acc[:] = part

    @pl.when(fst_ref[s] == 0)
    def _accum():
        acc[:] = acc[:] + part

    out_ref[:] = acc[:].astype(out_ref.dtype)


def _step_metadata(offsets, n_rows, n_groups, bm):
    """Static-shape (row-block, group) walk: for each of the
    ``B = ceil(N/bm)`` row blocks, one step per group intersecting it
    (≥ 1 — empty blocks get one masked step so every output block is
    initialized).  Total real steps ≤ B + G, the static bound the grid
    uses; trailing padding repeats the last block with a dead mask.
    Built entirely from jnp so traced offsets work."""
    nb = pl.cdiv(n_rows, bm)
    n_steps = nb + n_groups
    off = offsets.astype(jnp.int32)
    blocks = jnp.arange(nb, dtype=jnp.int32)

    def row_group(r):
        g = jnp.searchsorted(off, r, side="right").astype(jnp.int32) - 1
        return jnp.clip(g, 0, n_groups - 1)

    g_first = row_group(blocks * bm)
    g_last = row_group(jnp.minimum((blocks + 1) * bm - 1, n_rows - 1))
    per_block = g_last - g_first + 1                       # [B], >= 1
    cum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(per_block, dtype=jnp.int32)])
    total = cum[-1]
    step_block = jnp.clip(
        jnp.repeat(blocks, per_block, total_repeat_length=n_steps),
        0, nb - 1).astype(jnp.int32)
    within = jnp.arange(n_steps, dtype=jnp.int32) - cum[step_block]
    step_group = jnp.clip(g_first[step_block] + within,
                          0, n_groups - 1).astype(jnp.int32)
    first = jnp.concatenate([
        jnp.ones(1, jnp.int32),
        (step_block[1:] != step_block[:-1]).astype(jnp.int32)])
    return step_block, step_group, first, total.reshape(1)


def _gmm_pallas(x, w, offsets, interpret, scale=None):
    from jax.experimental.pallas import tpu as pltpu

    n, k = x.shape
    g_n, _, p = w.shape
    bm = _BLOCK_ROWS if n >= _BLOCK_ROWS else max(
        8, 8 * pl.cdiv(n, 8))
    blk, grp, fst, nst = _step_metadata(offsets, n, g_n, bm)
    n_steps = int(blk.shape[0])
    out_dtype = x.dtype if scale is not None else jnp.result_type(x, w)
    in_specs = [
        pl.BlockSpec((bm, k),
                     lambda s, blk, grp, fst, off, nst: (blk[s], 0)),
        pl.BlockSpec((1, k, p),
                     lambda s, blk, grp, fst, off, nst:
                     (grp[s], 0, 0)),
    ]
    inputs = [x, w]
    if scale is not None:
        # the scale slab dereferences through the SAME per-step group
        # id, so the weight tile and its scales arrive together
        nkb = scale.shape[1]
        in_specs.append(pl.BlockSpec(
            (1, nkb, p),
            lambda s, blk, grp, fst, off, nst: (grp[s], 0, 0)))
        inputs.append(scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (bm, p), lambda s, blk, grp, fst, off, nst: (blk[s], 0)),
        scratch_shapes=[pltpu.VMEM((bm, p), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, bm, n, scale is not None),
        grid_spec=grid_spec,
        out_shape=out_struct((n, p), out_dtype, x),
        interpret=interpret,
    )(blk, grp, fst, offsets.astype(jnp.int32), nst, *inputs)


# ---------------------------------------------------------------------------
# routing + VJP
# ---------------------------------------------------------------------------


def _route(backend: Optional[str]) -> str:
    if backend is None:
        backend = os.environ.get("APEX_TPU_GROUPED_MATMUL", "auto")
    if backend not in ("auto", "kernel", "reference"):
        raise ValueError(
            f"grouped_matmul backend={backend!r}: expected "
            "auto|kernel|reference")
    if backend == "auto":
        interp = os.environ.get("APEX_TPU_PALLAS_INTERPRET", "0") == "1"
        backend = "kernel" if (on_tpu() or interp) else "reference"
    return backend


def _gmm_impl(x, w, offsets, backend):
    if x.shape[0] == 0:
        return jnp.zeros((0, w.shape[-1]), jnp.result_type(x, w))
    if _route(backend) == "reference":
        return grouped_matmul_reference(x, w, offsets)
    return _gmm_pallas(x, w, offsets, interpret=not on_tpu())


def _grouped_dw(x, g, offsets):
    """``dw[e] = x_seg(e)^T @ g_seg(e)`` via masked segment outer
    products (fp32 accumulation); weight-stationary, shared by both
    routes."""
    n = x.shape[0]
    off = offsets.astype(jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    parts = []
    for e in range(off.shape[0] - 1):
        mask = ((rows >= off[e]) & (rows < off[e + 1]))[:, None]
        parts.append(jax.lax.dot(
            jnp.where(mask, xf, 0.0).T, gf,
            preferred_element_type=jnp.float32))
    return jnp.stack(parts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, offsets, backend):
    return _gmm_impl(x, w, offsets, backend)


def _gmm_fwd(x, w, offsets, backend):
    return _gmm(x, w, offsets, backend), (x, w, offsets)


def _gmm_bwd(backend, res, g):
    x, w, offsets = res
    dx = _gmm_impl(g, w.swapaxes(1, 2).astype(g.dtype), offsets,
                   backend).astype(x.dtype)
    dw = param_cotangent(_grouped_dw(x, g, offsets).astype(w.dtype), w)
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
    otherwise; ``APEX_TPU_GROUPED_MATMUL`` overrides), ``"kernel"`` /
    ``"reference"`` pin a path — the parity suite compares the two.

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
    from apex_tpu.ops.dense import route_quant_backend

    if x.shape[0] == 0:
        return jnp.zeros((0, wire.shape[-1]), x.dtype)
    if route_quant_backend(backend) == "reference":
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
    decode-bandwidth win.  ``APEX_TPU_QUANT_MATMUL`` routes (shared
    with ``ops/dense.dense_quantized``); the XLA reference dequantizes
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
