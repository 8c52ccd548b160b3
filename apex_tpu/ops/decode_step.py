"""Fused decode-layer step: rope + paged attention + output projection.

After PR 14/15 the serving tier schedules well, but the per-token step
itself is still inter-op bound: every decode layer launches rope → the
paged-attention kernel → the output projection as separate XLA ops with
HLO glue between them — exactly the residual cost "LLM Inference
Acceleration via Efficient Operation Fusion" (PAPERS.md) identifies.
This module fuses the three into ONE Pallas kernel with one VMEM
residency (ROADMAP item 4's kernel half):

- the query token's rotary embedding is applied in-kernel at the first
  block step (per-sequence angle rows ride a tiny ``[b, d2]`` input;
  the rotated query parks in a VMEM scratch reused by every block
  step), matching :func:`apex_tpu.ops.rope.fused_apply_rotary_pos_emb_
  ragged`'s partial-rotation NeoX math — including its round-trip to
  the compute dtype, so the fused path sees the bits the unfused path
  feeds its attention;
- attention over the paged KV pool runs the exact online-softmax loop
  of :mod:`apex_tpu.ops.paged_attention` — block table dereferenced by
  the BlockSpec index map via scalar prefetch (the fused-gather
  property), ragged skip of dead blocks, per-position tail mask, GQA/
  MQA head folding, and in-VMEM int8 dequantization of block-scaled
  pools (ISSUE 14's ``cache_wire="int8"``);
- the output projection (``ctx @ W_proj``) runs at the finalize step
  off the still-resident f32 accumulator — the context vector never
  round-trips through HBM between attention and projection.

``decode_layer_reference`` is the XLA composition (rope → :func:`~apex_
tpu.ops.paged_attention.ragged_paged_attention` → matmul), numerically
the exact op sequence ``models/generate._layer_decode_paged`` ran
before this op existed — the always-available fallback and the parity
oracle.  The route is ``_pallas_utils.resolve_backend``'s like
paged/grouped (kernel on TPU or under ``APEX_TPU_PALLAS_INTERPRET=1``),
and ``backend=`` pins a path.

VMEM budget note: the projection weight is held fully resident
(``nh·dh·h_out`` elements) next to one K/V block — the decode-layer
shapes this repo serves fit comfortably, but a multi-MB projection
slab should stay on the unfused path (quantized int8 weight slabs
already do: ``models/generate`` routes them to the reference
composition, where ``ops/dense.dense_quantized`` owns the tiling).

Layout contract (shared with :mod:`apex_tpu.ops.paged_attention`):
``q`` ``[b, num_heads, dh]`` PRE-rope, pools ``[num_blocks,
block_size, kv_groups, dh]``, ``block_tables`` ``[b, max_blocks]``
(entries ``>= num_blocks`` unmapped), ``lengths`` ``[b]`` live tokens
(query included), ``w_proj`` ``[num_heads·dh, h_out]`` float,
``rope_cos``/``rope_sin`` ``[b, d2]`` per-sequence angle rows (``None``
= no rotary, e.g. learned positions) → output ``[b, h_out]`` in
``q.dtype``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import LANES as _LANES
from apex_tpu.ops._pallas_utils import on_tpu, resolve_backend
from apex_tpu.ops.paged_attention import (
    _check_paged_shapes, ragged_paged_attention)
from apex_tpu.ops.rope import _rope

__all__ = ["fused_decode_layer", "decode_layer_reference",
           "route_decode_fused"]

_NEG_INF = -1e30


def _check_fused_shapes(q, w_proj, rope_cos, rope_sin):
    if isinstance(w_proj, dict):
        raise ValueError(
            "w_proj is a quantized weight slab; the fused decode layer "
            "takes plain float projection kernels only — route "
            "quantized projections through the reference composition "
            "(ops/dense.dense_quantized owns their tiling)")
    b, nh, dh = q.shape
    if w_proj.ndim != 2 or w_proj.shape[0] != nh * dh:
        raise ValueError(
            f"expected w_proj [num_heads*dh={nh * dh}, h_out], got "
            f"{w_proj.shape}")
    if (rope_cos is None) != (rope_sin is None):
        raise ValueError("pass rope_cos and rope_sin together or not "
                         "at all")
    if rope_cos is not None:
        d2 = rope_cos.shape[-1]
        if rope_cos.shape != (b, d2) or rope_sin.shape != (b, d2):
            raise ValueError(
                f"expected per-sequence rope rows [b={b}, d2], got cos "
                f"{rope_cos.shape} sin {rope_sin.shape}")
        if d2 > dh or d2 % 2:
            raise ValueError(
                f"rotary dim d2={d2} must be even and <= head dim "
                f"{dh}")


def route_decode_fused(backend: Optional[str]) -> str:
    """The fused-decode-layer route (``resolve_backend``).  Exposed so
    ``models/generate`` and the serving engine resolve it ONCE at the
    Python level and thread it through their jit static args: a jitted
    body traced in interpret mode must not be replayed outside it."""
    return resolve_backend("fused decode layer", backend)


def decode_layer_reference(q, k_pool, v_pool, block_tables, lengths,
                           w_proj, *, rope_cos=None, rope_sin=None,
                           scale: Optional[float] = None,
                           k_scale=None, v_scale=None,
                           attention_backend: Optional[str] = None):
    """XLA composition of the three fused stages — numerically the
    exact op sequence the unfused decode layer runs (rope's f32 math +
    dtype round-trip, :func:`ragged_paged_attention` with its own
    routing still honored via ``attention_backend``, then the plain
    ``ctx @ W.astype(dtype)`` matmul of ``ops/dense.quantized_matmul``'s
    float path).  The parity oracle and the always-available fallback."""
    _check_paged_shapes(q, k_pool, v_pool, block_tables, lengths,
                        k_scale, v_scale)
    _check_fused_shapes(q, w_proj, rope_cos, rope_sin)
    b = q.shape[0]
    if rope_cos is not None:
        # same math (and the same [b, s=1, h, d] shapes) as
        # fused_apply_rotary_pos_emb_ragged with the rows pre-gathered
        q = _rope(q[:, None],
                  rope_cos.astype(jnp.float32)[:, None, None, :],
                  rope_sin.astype(jnp.float32)[:, None, None, :])[:, 0]
    ctx = ragged_paged_attention(
        q, k_pool, v_pool, block_tables, lengths, scale=scale,
        backend=attention_backend, k_scale=k_scale, v_scale=v_scale)
    # the historical projection site: [b, 1, nh*dh] @ W in the compute
    # dtype (ops/dense.quantized_matmul's plain-array path)
    ctx_flat = ctx.astype(q.dtype).reshape(b, 1, -1)
    return (ctx_flat @ w_proj.astype(q.dtype))[:, 0]


# ---------------------------------------------------------------------------
# Fused Pallas kernel.
# ---------------------------------------------------------------------------


def _fused_kernel(scale, bs, g, rep, d2, quant, has_rope, *refs):
    """Grid (b, max_blocks), sequence-major like ``_paged_kernel``; one
    physical K/V block per step, online softmax across the block steps,
    plus two fused edges: the query ropes ONCE at ``j == 0`` (parked in
    a VMEM scratch every block step reuses) and the output projection
    runs at the last block step off the f32 accumulator — between rope
    and projection nothing leaves VMEM."""
    it = iter(refs)
    tbl_ref, len_ref = next(it), next(it)
    q_ref = next(it)
    cos_ref = sin_ref = None
    if has_rope:
        cos_ref, sin_ref = next(it), next(it)
    k_ref = next(it)
    ks_ref = next(it) if quant else None
    v_ref = next(it)
    vs_ref = next(it) if quant else None
    w_ref = next(it)
    o_ref = next(it)
    m_s, l_s, acc, qr = next(it), next(it), next(it), next(it)
    del it
    i, j = pl.program_id(0), pl.program_id(1)
    nh = g * rep
    dh = qr.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        q = q_ref[0].astype(jnp.float32)          # [nh, dh]
        if has_rope:
            cos = cos_ref[0]                      # [1, d2] (f32 input)
            sin = sin_ref[0]
            t32 = q[:, :d2]
            half = d2 // 2
            rot = jnp.concatenate([-t32[:, half:], t32[:, :half]],
                                  axis=-1)
            rq = t32 * cos + rot * sin
            if d2 < dh:
                rq = jnp.concatenate([rq, q[:, d2:]], axis=-1)
            # the unfused path rounds the roped query to the compute
            # dtype before attention casts it back up — replay that
            # round-trip so both paths score identical query bits
            q = rq.astype(o_ref.dtype).astype(jnp.float32)
        qr[:] = q

    length = len_ref[i]

    def _compute():
        q = qr[:]                                 # [nh, dh] f32
        k = k_ref[0].astype(jnp.float32)          # [bs, g, dh]
        if quant:
            k = k * ks_ref[0][..., None]
        qg = q.reshape(g, rep, dh)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale
        s = s.reshape(nh, bs)
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (nh, bs), 1)
        s = jnp.where(col < length, s, _NEG_INF)

        m_prev = m_s[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        alpha = jnp.where(m_new > _NEG_INF / 2, alpha, 0.0)
        l_s[:] = l_s[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)          # [bs, g, dh]
        if quant:
            v = v * vs_ref[0][..., None]
        pg = p.reshape(g, rep, bs)
        ctx = jax.lax.dot_general(
            pg, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)   # [g, rep, dh]
        acc[:] = acc[:] * alpha + ctx.reshape(nh, dh)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    pl.when(j * bs < length)(_compute)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        # replay the unfused path's dtype edges (ctx and W both pass
        # through the compute dtype at the historical matmul site)
        acc[:] = (acc[:] / safe_l).astype(o_ref.dtype).astype(jnp.float32)
        # the flat [1, nh*dh] @ [nh*dh, h_out] GEMM as one 2-D
        # [1, dh] @ [dh, h_out] dot per head, summed: Mosaic takes no
        # dot whose lhs lacks a non-contracting dim, and flattening the
        # [nh, dh] accumulator into one row would cross lane tiles
        out = jnp.zeros((1, o_ref.shape[-1]), jnp.float32)
        for h in range(nh):
            w = w_ref[h].astype(o_ref.dtype).astype(jnp.float32)
            out = out + jax.lax.dot(
                acc[h:h + 1, :], w, preferred_element_type=jnp.float32)
        o_ref[0] = out.astype(o_ref.dtype)


def _fused_pallas(q, k_pool, v_pool, block_tables, lengths, w_proj,
                  rope_cos, rope_sin, scale, interpret,
                  k_scale=None, v_scale=None):
    from jax.experimental.pallas import tpu as pltpu

    b, nh, dh = q.shape
    nb, bs, g, _ = k_pool.shape
    mb = block_tables.shape[1]
    rep = nh // g
    h_out = w_proj.shape[1]
    quant = k_scale is not None
    has_rope = rope_cos is not None
    d2 = rope_cos.shape[-1] if has_rope else 0
    # clamp unmapped sentinels once host-side: the index map runs for
    # EVERY grid step (skipped blocks included) and its DMA source must
    # stay in range — the in-kernel ragged skip / tail mask keeps the
    # clamped garbage out of the math
    tbl = jnp.minimum(block_tables.astype(jnp.int32), nb - 1)
    lens = lengths.astype(jnp.int32)

    kv_spec = pl.BlockSpec(
        (1, bs, g, dh),
        lambda i, j, tbl_ref, len_ref: (tbl_ref[i, j], 0, 0, 0))
    sc_spec = pl.BlockSpec(
        (1, bs, g),
        lambda i, j, tbl_ref, len_ref: (tbl_ref[i, j], 0, 0))
    # per-sequence rows (rope angles in, projected row out) ride a unit
    # MIDDLE axis ([b, 1, n]): Mosaic only takes a unit second-last
    # block dim when it equals the array's
    row_spec = pl.BlockSpec(
        (1, 1, d2), lambda i, j, tbl_ref, len_ref: (i, 0, 0))
    in_specs = [
        pl.BlockSpec((1, nh, dh),
                     lambda i, j, tbl_ref, len_ref: (i, 0, 0)),
    ]
    inputs = [q]
    if has_rope:
        in_specs.extend([row_spec, row_spec])
        inputs.extend([rope_cos.astype(jnp.float32)[:, None, :],
                       rope_sin.astype(jnp.float32)[:, None, :]])
    in_specs.append(kv_spec)
    inputs.append(k_pool)
    if quant:
        in_specs.append(sc_spec)
        inputs.append(k_scale)
    in_specs.append(kv_spec)
    inputs.append(v_pool)
    if quant:
        in_specs.append(sc_spec)
        inputs.append(v_scale)
    # the projection weight: one constant-index block — fetched once,
    # resident across the whole grid (the single-VMEM-residency claim)
    in_specs.append(pl.BlockSpec(
        (nh, dh, h_out), lambda i, j, tbl_ref, len_ref: (0, 0, 0)))
    inputs.append(w_proj.reshape(nh, dh, h_out))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, h_out), lambda i, j, tbl_ref, len_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, _LANES), jnp.float32),   # running max
            pltpu.VMEM((nh, _LANES), jnp.float32),   # running normalizer
            pltpu.VMEM((nh, dh), jnp.float32),       # output accumulator
            pltpu.VMEM((nh, dh), jnp.float32),       # roped query
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, scale, bs, g, rep, d2, quant,
                          has_rope),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h_out), q.dtype),
        interpret=interpret,
    )(tbl, lens, *inputs)[:, 0]


def fused_decode_layer(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    w_proj: jax.Array,
    *,
    rope_cos: Optional[jax.Array] = None,
    rope_sin: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """One decode token per sequence: rope the query in-kernel, attend
    over its paged KV blocks, and project the context — fused into one
    kernel launch with one VMEM residency (see module doc).

    ``q`` ``[b, num_heads, dh]`` PRE-rope; ``rope_cos``/``rope_sin``
    ``[b, d2]`` per-sequence angle-table rows (the caller gathers row
    ``pos[i]``, clamped — ``None`` skips rotation, the learned-position
    configs); pools / ``block_tables`` / ``lengths`` exactly as
    :func:`~apex_tpu.ops.paged_attention.ragged_paged_attention`
    (int8 pools pass ``k_scale``/``v_scale``); ``w_proj``
    ``[num_heads*dh, h_out]`` plain float → ``[b, h_out]`` in
    ``q.dtype`` (projection bias, residual and MLP stay with the
    caller — they are cheap elementwise/GEMM ops XLA already fuses).

    ``backend``: ``None`` routes automatically (kernel on TPU or
    under ``APEX_TPU_PALLAS_INTERPRET=1``, reference otherwise);
    ``"kernel"`` / ``"reference"`` pin a path —
    the parity suite (tests/test_decode_fused.py) compares the two.

    Inference-only by design (no custom VJP), like the paged-attention
    kernel it extends.
    """
    _check_paged_shapes(q, k_pool, v_pool, block_tables, lengths,
                        k_scale, v_scale)
    _check_fused_shapes(q, w_proj, rope_cos, rope_sin)
    dh = q.shape[-1]
    scale = (1.0 / dh ** 0.5) if scale is None else float(scale)
    if route_decode_fused(backend) == "reference":
        return decode_layer_reference(
            q, k_pool, v_pool, block_tables, lengths, w_proj,
            rope_cos=rope_cos, rope_sin=rope_sin, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    return _fused_pallas(q, k_pool, v_pool, block_tables, lengths,
                         w_proj, rope_cos, rope_sin, scale,
                         interpret=not on_tpu(),
                         k_scale=k_scale, v_scale=v_scale)
