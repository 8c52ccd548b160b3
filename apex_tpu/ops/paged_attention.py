"""Ragged paged attention — the decode kernel of the paged KV cache.

The paged serving cache (ISSUE 6) stores K/V in a global pool of
fixed-size blocks ``[num_blocks, block_size, kv_groups, dh]``; each
request owns an ordered *block table* of pool indices instead of a
contiguous ``max_len`` stripe.  Decode attention then has to gather a
request's blocks before it can score them — and materializing that
gather (``pool[tables]`` → ``[b, max_blocks·block_size, g, dh]``) is
exactly the HBM round-trip "LLM Inference Acceleration via Efficient
Operation Fusion" (PAPERS.md) warns against.  Following "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for
TPU" (PAPERS.md), the Pallas kernel fuses the gather into the attention
loop: the block table rides in SMEM via scalar prefetch and the
*BlockSpec index map* dereferences it, so each grid step DMAs one
physical block straight into VMEM and folds it into an online-softmax
accumulator — the gathered K/V never exists as a tensor.

Ragged lengths are handled in-kernel: every sequence carries its own
live length, whole blocks past it are skipped (their FLOPs and their
accumulator contribution), and the tail block is masked per-position.
GQA folds the query heads as ``[groups, rep]`` against the group-width
pool exactly like the dense decode path — repeated K/V is never
materialized.

Routing is ``_pallas_utils.resolve_backend``'s: the fused kernel runs
on TPU (or under ``APEX_TPU_PALLAS_INTERPRET=1``, the 8-virtual-device CI
path); everywhere else the XLA gather-based :func:`paged_attention_
reference` — always available, numerics oracle for the parity tests —
executes instead.  The ``backend=`` argument pins a path explicitly
(the kernel parity suite compares the two).

Layout contract (shared with ``serving/paged_cache.py``):

- ``q``            ``[b, num_heads, dh]`` — ONE query token per sequence
  (sq=1, the decode shape);
- ``k_pool/v_pool````[num_blocks, block_size, kv_groups, dh]``;
- ``block_tables`` ``[b, max_blocks]`` int32 — entries ``>= num_blocks``
  are unmapped sentinels (reads clamp + mask, so a short table tail or
  a released lane is safe);
- ``lengths``      ``[b]`` int32 — live tokens per sequence (the query
  token included): position ``t`` is visible iff ``t < lengths[i]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import LANES as _LANES
from apex_tpu.ops._pallas_utils import on_tpu, resolve_backend

__all__ = ["ragged_paged_attention", "paged_attention_reference"]

_NEG_INF = -1e30


def _check_paged_shapes(q, k_pool, v_pool, block_tables, lengths,
                        k_scale=None, v_scale=None):
    if q.ndim != 3:
        raise ValueError(
            f"expected q [b, num_heads, dh] (one decode token per "
            f"sequence), got {q.shape}")
    if k_pool.ndim != 4 or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"expected k/v pools [num_blocks, block_size, kv_groups, "
            f"dh], got k {k_pool.shape} v {v_pool.shape}")
    b, nh, dh = q.shape
    if k_pool.shape[-1] != dh:
        raise ValueError(
            f"head dim mismatch: q has {dh}, pool has {k_pool.shape[-1]}")
    g = k_pool.shape[2]
    if nh % g:
        raise ValueError(
            f"query heads ({nh}) must be a multiple of the pool's "
            f"kv group count ({g})")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(
            f"expected block_tables [b={b}, max_blocks], got "
            f"{block_tables.shape}")
    if lengths.shape != (b,):
        raise ValueError(
            f"expected lengths [b={b}], got {lengths.shape}")
    quant = jnp.dtype(k_pool.dtype) == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(
            "int8 pools need k_scale/v_scale [num_blocks, block_size, "
            "kv_groups] (the block-scaled at-rest form of "
            "serving/paged_cache.py) — refusing to treat raw int8 as "
            "attention values")
    if not quant and (k_scale is not None or v_scale is not None):
        raise ValueError(
            f"k_scale/v_scale only apply to int8 pools, got pool dtype "
            f"{k_pool.dtype}")
    if quant:
        want = k_pool.shape[:3]
        if k_scale.shape != want or v_scale.shape != want:
            raise ValueError(
                f"expected scales {want}, got k {k_scale.shape} "
                f"v {v_scale.shape}")


def paged_attention_reference(q, k_pool, v_pool, block_tables, lengths,
                              *, scale: Optional[float] = None,
                              k_scale=None, v_scale=None):
    """XLA composition: gather the listed blocks, then run the dense
    masked decode attention over them.

    This is the materialized-gather path the fused kernel exists to
    avoid (``pool[tables]`` builds the full ``[b, max_blocks·bs, g,
    dh]`` view in HBM every step) — kept as the always-available
    fallback and the numerics oracle of the parity suite, the same
    role ``mha_reference`` plays for the flash kernel.

    int8 pools (``k_scale``/``v_scale`` given): the gather also pulls
    each block's per-(token, group) scales and dequantizes before the
    math — the matching gather+dequant oracle of the in-kernel
    dequantizing path."""
    _check_paged_shapes(q, k_pool, v_pool, block_tables, lengths,
                        k_scale, v_scale)
    b, nh, dh = q.shape
    nb, bs, g, _ = k_pool.shape
    mb = block_tables.shape[1]
    scale = (1.0 / dh ** 0.5) if scale is None else float(scale)
    # unmapped sentinel entries clamp to block 0; their positions are
    # >= lengths by contract, so the mask below hides the garbage
    tbl = jnp.minimum(block_tables.astype(jnp.int32), nb - 1)
    k = k_pool[tbl].reshape(b, mb * bs, g, dh)
    v = v_pool[tbl].reshape(b, mb * bs, g, dh)
    if k_scale is not None:
        sk = k_scale[tbl].reshape(b, mb * bs, g)
        sv = v_scale[tbl].reshape(b, mb * bs, g)
        k = k.astype(jnp.float32) * sk[..., None]
        v = v.astype(jnp.float32) * sv[..., None]
    rep = nh // g
    qg = q.reshape(b, g, rep, dh)
    s = jnp.einsum("bgrd,btgd->bgrt", qg.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    live = (jnp.arange(mb * bs)[None] <
            lengths.astype(jnp.int32)[:, None])[:, None, None, :]
    s = jnp.where(live, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrt,btgd->bgrd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, nh, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused Pallas kernel.
# ---------------------------------------------------------------------------


def _paged_kernel(scale, bs, g, rep, quant, *refs):
    """Grid (b, max_blocks): sequence-major, one physical K/V block per
    step, online softmax across the block steps.  The block table and
    lengths ride in SMEM (scalar prefetch); the BlockSpec index maps
    already dereferenced the table, so ``k_ref``/``v_ref`` hold the
    right physical block — the fused-gather property.

    ``quant``: the pool is block-scaled int8 and two extra refs carry
    the step's per-(token, group) scale blocks (dereferenced through
    the SAME table index map as the payload), so dequantization is one
    VMEM-resident multiply per block — the float K/V never exists in
    HBM, which is the whole at-rest win."""
    if quant:
        (tbl_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
         m_s, l_s, acc) = refs
    else:
        (tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
         m_s, l_s, acc) = refs
        ks_ref = vs_ref = None
    i, j = pl.program_id(0), pl.program_id(1)
    nh = g * rep

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    length = len_ref[i]

    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [nh, dh]
        k = k_ref[0].astype(jnp.float32)          # [bs, g, dh]
        if quant:
            k = k * ks_ref[0][..., None]          # [bs, g, 1] scales
        qg = q.reshape(g, rep, q.shape[-1])
        # batched over the group axis: [g, rep, dh] x [bs, g, dh]
        # -> [g, rep, bs]; the rep query heads of a group share its
        # single pool-resident K/V block (GQA without repeat)
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
        # rows still fully masked (possible only while length == 0):
        # keep the accumulator at exact zero instead of exp(NaN)
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
        acc[:] = acc[:] * alpha + ctx.reshape(nh, v.shape[-1])
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    # ragged skip: a block whose first position is past the sequence's
    # live length contributes nothing — skip its FLOPs entirely (the
    # DMA for it was clamped to a valid block by the index map)
    pl.when(j * bs < length)(_compute)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)


def _paged_pallas(q, k_pool, v_pool, block_tables, lengths, scale,
                  interpret, k_scale=None, v_scale=None):
    from jax.experimental.pallas import tpu as pltpu

    b, nh, dh = q.shape
    nb, bs, g, _ = k_pool.shape
    mb = block_tables.shape[1]
    rep = nh // g
    quant = k_scale is not None
    # the index map runs for EVERY grid step, skipped blocks included:
    # clamp unmapped sentinels to a valid pool index here (host-side,
    # once) so the DMA source is always in range — the kernel's ragged
    # skip / tail mask keeps the clamped garbage out of the math
    tbl = jnp.minimum(block_tables.astype(jnp.int32), nb - 1)
    lens = lengths.astype(jnp.int32)

    kv_spec = pl.BlockSpec(
        (1, bs, g, dh),
        lambda i, j, tbl_ref, len_ref: (tbl_ref[i, j], 0, 0, 0))
    # the scale pool dereferences through the SAME table entry, so each
    # step's DMA brings the block's payload AND its scales — the
    # gather+dequant is fused exactly like the gather itself
    sc_spec = pl.BlockSpec(
        (1, bs, g),
        lambda i, j, tbl_ref, len_ref: (tbl_ref[i, j], 0, 0))
    in_specs = [
        pl.BlockSpec((1, nh, dh),
                     lambda i, j, tbl_ref, len_ref: (i, 0, 0)),
        kv_spec,
    ]
    inputs = [q, k_pool]
    if quant:
        in_specs.append(sc_spec)
        inputs.append(k_scale)
    in_specs.append(kv_spec)
    inputs.append(v_pool)
    if quant:
        in_specs.append(sc_spec)
        inputs.append(v_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, nh, dh), lambda i, j, tbl_ref, len_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, _LANES), jnp.float32),   # running max
            pltpu.VMEM((nh, _LANES), jnp.float32),   # running normalizer
            pltpu.VMEM((nh, dh), jnp.float32),       # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale, bs, g, rep, quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, dh), q.dtype),
        interpret=interpret,
    )(tbl, lens, *inputs)


def ragged_paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """One decode token per sequence attends over its paged KV blocks.

    ``q`` ``[b, num_heads, dh]``, pools ``[num_blocks, block_size,
    kv_groups, dh]``, ``block_tables`` ``[b, max_blocks]`` (entries
    ``>= num_blocks`` are unmapped), ``lengths`` ``[b]`` live token
    counts → context ``[b, num_heads, dh]``.

    int8 pools (ISSUE 14): pass the pool's per-(token, group) fp32
    scales as ``k_scale``/``v_scale`` ``[num_blocks, block_size,
    kv_groups]`` — the kernel dequantizes each block in VMEM right
    after its table-dereferenced DMA (the float K/V never exists in
    HBM), the reference runs the matching gather+dequant.

    ``backend``: ``None`` routes automatically (fused Pallas kernel on
    TPU or under ``APEX_TPU_PALLAS_INTERPRET=1``; XLA gather reference
    otherwise), ``"kernel"`` / ``"reference"`` pin a path — the parity
    suite compares the two.

    Inference-only by design (no custom VJP): nothing differentiates
    through the serving decode step, and keeping the kernel
    forward-only keeps its VMEM budget at one block.
    """
    _check_paged_shapes(q, k_pool, v_pool, block_tables, lengths,
                        k_scale, v_scale)
    dh = q.shape[-1]
    scale = (1.0 / dh ** 0.5) if scale is None else float(scale)
    if resolve_backend("paged attention", backend) == "reference":
        return paged_attention_reference(
            q, k_pool, v_pool, block_tables, lengths, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    return _paged_pallas(q, k_pool, v_pool, block_tables, lengths,
                         scale, interpret=not on_tpu(),
                         k_scale=k_scale, v_scale=v_scale)
