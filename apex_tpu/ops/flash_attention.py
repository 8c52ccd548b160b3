"""Flash attention — the TPU answer to the reference's fused attention stack.

Reference parity targets: apex/contrib/csrc/fmha (seqlen<=512 BERT fwd/bwd,
varlen via cu_seqlens — fmha_api.cpp:358) and apex/contrib/csrc/
multihead_attn (pre-flash fused MHA with softmax/dropout epilogues). Instead
of porting those CUDA tilings we implement one FlashAttention-2 style
blockwise kernel set in Pallas: O(sq·d) memory, online softmax, fused causal
/ key-padding masking and attention dropout, fp32 accumulation on the MXU.
It also serves as the compute core of the ring-attention context-parallel
path (the reference has no long-context story; SURVEY.md §5).

Layout: [batch, seq, heads, head_dim] (the model's native BSND) in and
out.  The kernels read and write its free reshape [batch, seq, heads x
head_dim], the layout the projections on either side produce and consume,
in lane-dense blocks of 128 lanes: ``128 // d`` heads a block (two at d =
64), one head where d is a multiple of 128 (:func:`_heads_a_block`, from
static shapes alone).  A block's heads are worked one after another on
full-width operands, the others' lanes zeroed (:func:`_take`: a K = 128
product on a 128 x 128 MXU costs the passes of a K = 64 one), and their
128-wide results merged by lane (:func:`_merge`); under GQA a block's
heads read one K/V head, whose half of its block is a function of
``program_id``.  The grid runs (batch x head blocks, q-blocks, kv-blocks)
with kv innermost; VMEM scratch carries the running max / normalizer (a
head) / accumulator across kv steps.  Shapes the rule cannot block (an
odd head count, a toy width that leaves a block part empty, MQA's single
K/V head of 64, a GQA group that a block would straddle) go through the
same kernels as [batch x heads, seq, head_dim] rows, one head a block,
and pay XLA's transposes on the way in and out; no cell does.

Variants:
- ``causal=True`` — upper-triangular mask generated from iota in-kernel;
  score tiles wholly above the diagonal are skipped.  Where the tile is
  1024 x 1024 (every sequence of 1024 or more, :func:`_blocks`) the three
  split kernels work a tile ON the diagonal in static bands of
  :func:`_sub_tile` rows (forward, dq) or columns (dk/dv) that end at
  the diagonal, so the squares above it cost no product, no ``exp`` and
  no mask, with the next band's products issued before this band's
  vector arithmetic; a tile wholly below the diagonal is one unmasked
  product.  Where the keys are one tile (s1024) a band holds every key
  its rows will see, and the forward writes its softmax straight out:
  no running max, sum or accumulator in scratch.
  :func:`causal_work_share` is the share of the score rectangle that is
  still computed.  Segment ids keep whole tiles (their skip is by id
  range, a tile at a time), as do the fused backward (sk <= 512) and
  everything non-causal.
- ``key_padding_mask`` [b, sk] — bool (True = masked) or additive float
  (the reference's ``mask_additive`` MHA mode) — fused in-kernel as an
  additive score term.
- ``dropout_p`` — attention dropout fused in-kernel. The keep mask is a
  counter-based hash of (seed, batch·head, query row, key col) — the
  Philox-counter analog of the reference's in-kernel dropout
  (contrib/csrc/multihead_attn/philox.cuh): stateless, order-independent,
  so the forward and both backward kernels regenerate identical bits for
  every tile with no O(s²) residual.  Dropout is applied to the
  *unnormalized* probabilities feeding the accumulator while the softmax
  normalizer accumulates the un-dropped weights, which equals dropping
  the normalized probabilities.
- generic additive ``bias`` or full boolean ``mask`` — routed to the XLA
  composition (rare paths in the reference too).
- :func:`flash_attention_mla` — multi-head latent attention: a head's q
  and k are ``[128 without position | 64 rotary]``, its v 128 wide, and
  the rotary key is ONE head for all query heads.  The same forward
  kernel with a second product in its scores (:func:`_rope_scores`) and a
  split backward pair of its own grids, causal bands and padded tails as
  above, no mask, segments or dropout; nothing is padded to a common
  width and the rotary key is never copied a head.

Backward: custom_vjp with the standard two-kernel scheme — dq accumulates
over kv blocks, dk/dv over q blocks, both recomputing the probabilities
from the saved logsumexp (no O(s²) residuals) — or, where the padded keys
fit VMEM whole, one fused pass that computes them once; the static shape
decides (:func:`_bwd_plan`).

Under remat: the forward rule tags its two kernel-made residuals, the
output ``o`` and the logsumexp ``lse`` ([b, sq, h] f32), with
``checkpoint_name`` (``REMAT_SAVED_NAMES``), and a rematted transformer
layer (``TransformerConfig.remat``) keeps exactly those two: 17.3 MB a
layer at b8 × s1024 × 16 heads of 64, against running the forward
kernel a second time in the backward pass only to remake them.  Outside
a ``jax.checkpoint`` with that policy the tag is an identity that is
gone at lowering.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import (
    LANES as _LANES, interpret_forced, on_tpu, out_struct)

__all__ = ["causal_work_share", "flash_attention", "flash_attention_mla",
           "flash_attention_packed", "mha_reference",
           "segment_ids_from_cu_seqlens"]

_NEG_INF = -1e30

# checkpoint names of the forward kernel's output and logsumexp: the
# residuals a rematted layer saves instead of rerunning the kernel
REMAT_SAVED_NAMES = ("flash_attention_out", "flash_attention_lse")


def _unify_vma(*arrays):
    """Promote every (non-None) array to the union of the group's varying
    manual axes (jax 0.9 shard_map vma typing).  A Pallas call with
    mixed-vma operands — e.g. a closure-constant mask next to a
    pp-varying activation inside a shard_map pipeline stage — fails the
    dynamic_slice vma check in the interpreter/lowering; unifying here
    makes the kernel's type uniform.  No-op outside shard_map."""
    vmas = []
    for a in arrays:
        if a is None:
            continue
        vmas.append(set(jax.typeof(a).vma))
    union = set().union(*vmas) if vmas else set()
    if not union:
        return arrays
    from apex_tpu.utils.collectives import match_vma

    return tuple(None if a is None else match_vma(a, tuple(union))
                 for a in arrays)


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# In-kernel dropout PRNG: counter-based hash (Philox-counter analog).
#
# pltpu.prng_* is hardware-only (no CPU interpret lowering), so the keep
# mask is a murmur3-style integer hash over global (seed, bh, row, col)
# coordinates — bit-identical on TPU and in CPU interpret mode, and
# trivially order-independent across the three kernels.
# ---------------------------------------------------------------------------


def _u32(x):
    return jnp.uint32(x)


def _keep_mask(seed, bh, q_start, k_start, shape, keep_prob):
    """Boolean keep mask for a (block_q, block_k) tile."""
    row = (
        q_start
        + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    ).astype(jnp.uint32)
    col = (
        k_start
        + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ).astype(jnp.uint32)
    h = seed.astype(jnp.uint32) + bh.astype(jnp.uint32) * _u32(0x9E3779B1)
    h = h ^ (row * _u32(0x85EBCA77))
    h = h ^ (h >> 16)
    h = h * _u32(0x7FEB352D)
    h = h ^ (col * _u32(0xC2B2AE3D))
    h = h ^ (h >> 16)
    h = h * _u32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * _u32(0xC2B2AE35)
    h = h ^ (h >> 16)
    threshold = min(int(round(keep_prob * 4294967296.0)), 4294967295)
    return h < _u32(threshold)


# ---------------------------------------------------------------------------
# Reference XLA path (also the fallback for generic bias / mask).
# ---------------------------------------------------------------------------


def mha_reference(q, k, v, *, causal=False, key_padding_mask=None,
                  mask=None, bias=None, scale=None, dropout_p=0.0,
                  dropout_rng=None, segment_ids=None):
    """Materialized softmax(QK^T)V in fp32 — numerics oracle for the kernel
    and the execution path for variants the kernel doesn't fuse.

    Accepts grouped K/V (fewer heads than Q, GQA/MQA): the group heads
    are broadcast up to the query heads, the semantics the fused kernel
    implements via its index maps without materializing the repeat."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    b, sq, n, d = q.shape
    sk = k.shape[1]
    scale = (1.0 / d ** 0.5) if scale is None else scale
    s = jnp.einsum("bsnd,btnd->bnst", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, _NEG_INF, s)
    if segment_ids is not None:
        # a (seg_q, seg_k) pair supports rectangular (cross-attention)
        # grids; a single [b, s] array is the packed self-attention case
        if isinstance(segment_ids, tuple):
            seg_q, seg_k = (x.astype(jnp.int32) for x in segment_ids)
        else:
            seg_q = seg_k = segment_ids.astype(jnp.int32)
        blocked = (seg_q[:, None, :, None] != seg_k[:, None, None, :]) | (
            seg_k < 0)[:, None, None, :]
        s = jnp.where(blocked, _NEG_INF, s)
    if key_padding_mask is not None:
        if key_padding_mask.dtype == jnp.bool_:
            s = jnp.where(key_padding_mask[:, None, None, :], _NEG_INF, s)
        else:  # additive float mask (reference mask_additive mode)
            s = s + key_padding_mask[:, None, None, :].astype(jnp.float32)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((col > row)[None, None], _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    # fully-blocked rows (e.g. padding queries under segment_ids, or an
    # all-masked key row): softmax of a constant -1e30 row is uniform —
    # zero it to match the kernel's l==0 sentinel (no value/grad leaks
    # across segments through pad slots)
    any_open = jnp.max(s, axis=-1, keepdims=True) > _NEG_INF / 2
    p = jnp.where(any_open, p, 0.0)
    if dropout_p > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bnst,btnd->bsnd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# Forward kernel.
# ---------------------------------------------------------------------------


def _both(pred, more):
    return more if pred is None else pred & more


def _open_pairs(q0, k0, shape, crossed, sk_real=None, sq_real=None):
    """Which (row, col) pairs of a score rectangle whose corner is the
    absolute position (q0, k0) may attend: ``col < sk_real`` and
    ``row < sq_real`` where a bound is given (padded tails),
    ``col <= row`` where the diagonal crosses the rectangle.  None when
    nothing is asked: the rectangle then costs no iota, compare or
    select."""
    pred = None
    if sk_real is not None or crossed:
        col = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if sq_real is not None or crossed:
        row = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if sk_real is not None:
        pred = col < sk_real
    if sq_real is not None:
        pred = _both(pred, row < sq_real)
    if crossed:
        pred = _both(pred, col <= row)
    return pred


def _causal_rects(block, sub, by):
    """The part of a (block, block) score tile ON the diagonal that lies
    at or below it, as static ``(row0, rows, col0, cols, crossed)``
    rectangles in the tile's own coordinates, every one crossed by the
    diagonal.  ``by="rows"``: one band a ``sub``-row block, from column
    0 to the block's own square (forward, dq: one softmax step and one
    accumulation a row block).  ``by="cols"``: one band a ``sub``-column
    block, from its own square down to the tile's last row (dk/dv: one
    accumulation a column block).  What lies above the diagonal's
    squares is in no band, so nothing is computed for it;
    :func:`causal_work_share` counts areas from this same list.  (Masking
    a band's one crossed square alone measured the same as masking the
    band: the mask is not what bounds these kernels.)"""
    if by == "rows":
        return [(r, sub, 0, r + sub, True) for r in range(0, block, sub)]
    return [(c, block - c, c, sub, True) for c in range(0, block, sub)]


def _visit_tile(front, back, finish, hpb, causal, block_q, block_k, sub,
                multi, qi, kj, seg_refs, by="rows"):
    """Work off what grid step (qi, kj) has to compute of its (block_q,
    block_k) score tile for the block's ``hpb`` heads, a rectangle
    ``(row0, rows, col0, cols, crossed)`` and a head at a time in two
    stages: ``front(h, *rect)`` makes head ``h``'s MXU products from the
    operands, ``back(h, *rect, made)`` does the rest and returns the
    head's part; ``finish(*rect, parts)`` merges the heads' parts into
    the outputs or the running state.

    Sub-tiled causal (``sub``; block_q == block_k): a tile below the
    diagonal is one whole unmasked rectangle, the tile on it the static
    nest of :func:`_causal_rects` (bands ``by`` rows or columns), a tile
    above it nothing.  The nest runs the next (band, head)'s ``front``
    before this one's ``back``: they share no value, so the MXU can work
    under the other's vector arithmetic.  A whole tile's heads follow one
    another (two heads' [1024, 1024] squares at once do not fit VMEM).
    With one tile a head (``multi`` False, s1024) only the nest is
    emitted.  Otherwise whole tiles: skipped when wholly above the
    diagonal or when the segment-id ranges of its rows and columns are
    disjoint."""
    def tile(*rect):
        finish(*rect, [back(h, *rect, front(h, *rect))
                       for h in range(hpb)])

    if sub:
        def on_diagonal():
            units = [(h, *rect) for rect in _causal_rects(block_q, sub, by)
                     for h in range(hpb)]
            made, parts = front(*units[0]), []
            for unit, ahead in zip(units, units[1:] + [None]):
                made_ahead = None if ahead is None else front(*ahead)
                parts.append(back(*unit, made))
                if len(parts) == hpb:
                    finish(*unit[1:], parts)
                    parts = []
                made = made_ahead

        if not multi:
            on_diagonal()
            return
        pl.when(kj < qi)(lambda: tile(0, block_q, 0, block_k, False))
        pl.when(kj == qi)(on_diagonal)
        return
    run = None
    if causal:
        # whole kv block above the diagonal -> skip its FLOPs
        run = _tile_runs(block_q, block_k, qi, kj)
    if seg_refs is not None:
        # block-sparse skip of fully-disjoint tiles: if any q/k segment
        # ids match, the id ranges overlap -- so disjoint ranges are a
        # safe (conservative) skip regardless of id ordering
        qseg, kseg = seg_refs[0][0], seg_refs[1][0]
        overlap = (jnp.min(kseg) <= jnp.max(qseg)) & (
            jnp.max(kseg) >= jnp.min(qseg))
        run = _both(run, overlap)

    def whole():
        tile(0, block_q, 0, block_k, causal)

    if run is None:
        whole()
    else:
        pl.when(run)(whole)


def _div(a, b):
    """``a // b`` and ``a % b`` of a non-negative traced int32 by a static
    positive int, as the two primitives: the ``//`` and ``%`` of traced
    values carry a sign correction that the Mosaic lowering traces anew at
    every use (250 times a step's three kernels, most of their lowering
    time)."""
    return jax.lax.div(a, jnp.int32(b))


def _rem(a, b):
    return jax.lax.rem(a, jnp.int32(b))


class _Heads(NamedTuple):
    """How the kernels' 3-D arrays hold the heads.  ``packed``: the arrays
    are ``[b, s, heads x d]``, the free reshape of the model's
    ``[b, s, heads, d]``, and a block is ``hpb`` heads side by side in
    128 lanes (:func:`_heads_a_block`).  Not packed: ``[b x heads, s, d]``
    rows, one head a block (``hpb`` 1).  The grid's first axis runs over
    head blocks, batch-major: ``nb`` query and ``gb`` K/V head blocks a
    batch row, ``rep = nb // gb`` query blocks sharing a K/V block (GQA;
    1 for MHA)."""
    nb: int
    gb: int
    hpb: int
    d: int
    packed: bool
    # latent attention (:func:`flash_attention_mla`): a head's score takes
    # a second product, its rotary part with the one rotary key
    rope: bool = False

    @property
    def width(self):
        return self.hpb * self.d

    @property
    def rep(self):
        return self.nb // self.gb

    def count(self, x3):
        """Head blocks in a kernel array."""
        return x3.shape[0] * x3.shape[2] // self.width

    def batch(self, f):
        """The batch row of query head block ``f``."""
        return _div(f, self.nb)

    def q_block(self, f, i):
        """Block index of query head block ``f``, sequence block ``i``."""
        if not self.packed:
            return f, i, 0
        return _div(f, self.nb), i, _rem(f, self.nb)

    def kv_block(self, fk, j):
        if not self.packed:
            return fk, j, 0
        return _div(fk, self.gb), j, _rem(fk, self.gb)

    def kv_of(self, f):
        """The K/V head block that query head block ``f`` reads (GQA: the
        index map broadcasts a group to its query heads, the repeated
        tensor never exists in HBM)."""
        if self.rep == 1:
            return f
        return (_div(f, self.nb) * self.gb
                + _div(_rem(f, self.nb), self.rep))

    def q_of(self, fk, r):
        """The ``r``-th query head block that reads K/V head block ``fk``."""
        if self.rep == 1:
            return fk
        return _div(fk, self.gb) * self.nb + _rem(fk, self.gb) * self.rep + r

    def slot(self, f, h):
        """Which head of its K/V block head ``h`` of query block ``f``
        reads: its own position for MHA (static); under GQA the block's
        heads share one K/V head, a function of ``program_id``."""
        if self.rep == 1:
            return h
        return _rem(_div(_rem(f, self.nb), self.rep // self.hpb), self.hpb)


def _heads_a_block(n, g, d):
    """The one rule of the kernels' layout, from static shapes: how many
    heads share a 128-lane block of ``[b, s, heads x d]``.  ``128 // d``
    where ``d`` divides 128 and both head counts fill whole blocks (two
    heads at d = 64), one head where ``d`` is a multiple of 128.  0: the
    shape cannot be blocked this way (an odd head count, a toy width, a
    GQA group that a block would straddle) and takes the ``[b x heads, s,
    d]`` route through the same kernels, paying the transposes."""
    if d % _LANES == 0:
        return 1
    hpb = _LANES // d
    if _LANES % d or n % hpb or g % hpb:
        return 0
    rep = n // g
    return hpb if rep == 1 or rep % hpb == 0 else 0


def _layout(q3, k3, gqa, d):
    """The :class:`_Heads` of a kernel call: packed arrays of ``d``-wide
    heads, or (``d`` None) per-head rows with ``gqa = (n, g)`` heads a
    batch row."""
    if d is None:
        return _Heads(*(gqa or (1, 1)), 1, q3.shape[2], False)
    hpb = max(_LANES // d, 1)
    return _Heads(q3.shape[2] // (hpb * d), k3.shape[2] // (hpb * d), hpb,
                  d, True)


def _lane_head(shape, heads):
    return _div(jax.lax.broadcasted_iota(jnp.int32, shape, 1), heads.d)


def _move(x, src, dst, heads):
    """``x`` [rows, width] with head slot ``src``'s lanes moved to slot
    ``dst``.  MHA: the two are one static position and nothing moves.
    GQA: a rotation by whole heads, chosen by the traced distance among
    the static ones (a [rows, 128] operand beside [rows, 1024] scores)."""
    if heads.hpb == 1 or isinstance(dst, int) and isinstance(src, int):
        return x
    from jax.experimental.pallas import tpu as pltpu

    by = _rem(dst - src + heads.hpb, heads.hpb)
    out = x
    for c in range(1, heads.hpb):
        out = jnp.where(by == c, pltpu.roll(x, c * heads.d, 1), out)
    return out


def _take(x, h, slot, heads):
    """Head ``h`` of the block's heads in ``x`` [rows, width], placed in
    the lanes of ``slot`` with zeros in every other head's: the operand
    of a full-width product that contracts this head alone (the zeros
    change no sum; on a 128 x 128 MXU a K = 128 product costs the passes
    of a K = 64 one)."""
    if heads.hpb == 1:
        return x
    x = _move(x, h, slot, heads)
    return jnp.where(_lane_head(x.shape, heads) == slot, x, 0.0)


def _merge(parts, heads, shape):
    """One [rows, width] array from a per-head list: head ``h``'s lanes
    from ``parts[h]`` ([rows, width] or a [rows, 1] column)."""
    if heads.hpb == 1:
        return parts[0]
    lane = _lane_head(shape, heads)
    out = parts[-1]
    for h in range(heads.hpb - 2, -1, -1):
        out = jnp.where(lane == h, parts[h], out)
    return out


def _rope_half(x, f):
    """Latent attention: ``x`` [rows, 128] holds the rotary parts of two
    heads side by side, or something that is to be added to them; the
    lanes of head ``f`` (its parity picks the half) kept, the other
    head's zeroed."""
    half = _div(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1),
                x.shape[1] // 2)
    return jnp.where(half == _rem(f, 2), x, 0.0)


def _rope_scores(qr, kr, f):
    """Head ``f``'s rotary part of a score rectangle: ``qr`` [rows, 128],
    its pair's block of rotary queries, against ``kr`` [cols, 128], the
    one rotary key twice side by side, so that the zeroed half of ``qr``
    takes the other copy out of the sum (a K = 128 product costs the
    passes of a K = 64 one)."""
    return jax.lax.dot_general(
        _rope_half(qr.astype(jnp.float32), f), kr.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _fwd_kernel(scale, causal, sk_real, block_q, block_k, has_kpm,
                has_seg, dropout_p, sub, multi, padded, direct, heads,
                *refs):
    if dropout_p > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    seg_refs = None
    if has_seg:
        seg_refs, refs = refs[:2], refs[2:]
    q_ref, k_ref, v_ref = refs[:3]
    kpm_ref, refs = (refs[3], refs[4:]) if has_kpm else (None, refs[3:])
    if heads.rope:
        (qr_ref, kr_ref), refs = refs[:2], refs[2:]
    o_ref, lse_ref = refs[:2]
    f, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hs = range(heads.hpb)
    slots = [heads.slot(f, h) for h in hs]
    q_start = qi * block_q
    k_start = kj * block_k
    # sub-tiled causal rows always hold an open key (col 0) from their
    # first rectangle on, and need the kv-tail bound only when padded
    bounded = padded or not sub
    guarded = has_kpm or not sub

    def _scores(h, r0, rn, c0, cn, crossed):
        rows, cols = slice(r0, r0 + rn), slice(c0, c0 + cn)
        q = _take(q_ref[0, rows, :].astype(jnp.float32), h, slots[h], heads)
        k = k_ref[0, cols, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if heads.rope:
            s = s + _rope_scores(qr_ref[0, rows, :], kr_ref[0, cols, :], f)
        s = s * scale
        if has_kpm:
            s = s + kpm_ref[0, :, cols]  # additive [1, cols] broadcast

        pred = _open_pairs(q_start + r0, k_start + c0, (rn, cn), crossed,
                           sk_real if bounded else None)
        if has_seg:
            # packed multi-sequence rows: attend within a segment only
            # (negative ids = padding slots, matching nothing)
            qseg = seg_refs[0][0, rows].reshape(rn, 1)
            kseg = seg_refs[1][0, cols].reshape(1, cn)
            pred = _both(pred, (qseg == kseg) & (kseg >= 0))
        return s if pred is None else jnp.where(pred, s, _NEG_INF)

    def _weights(h, r0, rn, c0, cn, s, m_prev):
        """One softmax step of head ``h`` over a rectangle: the rows' new
        maximum, the sums of their weights, and the weights' product with
        v, in the head's own lanes."""
        m_new = jnp.max(s, axis=-1, keepdims=True)
        if m_prev is not None:
            m_new = jnp.maximum(m_prev, m_new)
        p = jnp.exp(s - m_new)
        if guarded:
            # fully-masked-so-far rows: m_new == -inf => exp(NaN) guards
            p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        l_new = jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0], f * heads.hpb + h, q_start + r0,
                              k_start + c0, (rn, cn), 1.0 - dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, c0:c0 + cn, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, _move(pv, slots[h], h, heads)

    def _write(rows, m, l, acc_rows):
        """``m``, ``l``: a column a head; ``acc_rows`` [rows, width]."""
        shape = acc_rows.shape
        safe_l = [jnp.where(x == 0.0, 1.0, x) for x in l]
        o_ref[0, rows, :] = (
            acc_rows / _merge(safe_l, heads, shape)).astype(o_ref.dtype)
        # logsumexp (fully-masked rows get -inf-ish sentinel), a head's
        # value across the head's own lanes
        lse = [jnp.where(x == 0.0, _NEG_INF, mx + jnp.log(sx))
               for x, mx, sx in zip(l, m, safe_l)]
        lse_ref[0, rows, :] = jnp.broadcast_to(
            _merge(lse, heads, shape), shape)

    if direct:
        # one kv tile: a rectangle holds all the keys its rows will see,
        # so its softmax is whole -- no running state, no scratch
        def _whole(h, r0, rn, c0, cn, crossed, s):
            return _weights(h, r0, rn, c0, cn, s, None)

        def _emit(r0, rn, c0, cn, crossed, parts):
            m, l, pv = zip(*parts)
            _write(slice(r0, r0 + rn), m, l, _merge(pv, heads, pv[0].shape))

        _visit_tile(_scores, _whole, _emit, heads.hpb, causal, block_q,
                    block_k, sub, multi, qi, kj, seg_refs)
        return

    # running state: acc by lane like the output, m and l a head
    acc, m_s, l_s = refs[2:]

    @pl.when(kj == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def _step(h, r0, rn, c0, cn, crossed, s):
        rows = slice(r0, r0 + rn)
        m_prev = m_s[h, rows, :1]
        m_new, l_new, pv = _weights(h, r0, rn, c0, cn, s, m_prev)
        alpha = jnp.exp(m_prev - m_new)
        if guarded:
            alpha = jnp.where(m_new > _NEG_INF / 2, alpha, 0.0)
        l_s[h, rows, :] = l_s[h, rows, :] * alpha + l_new
        m_s[h, rows, :] = jnp.broadcast_to(m_new, (rn, m_s.shape[2]))
        return alpha, pv

    def _absorb(r0, rn, c0, cn, crossed, parts):
        rows = slice(r0, r0 + rn)
        alpha, pv = zip(*parts)
        shape = pv[0].shape
        acc[rows, :] = (acc[rows, :] * _merge(alpha, heads, shape)
                        + _merge(pv, heads, shape))

    _visit_tile(_scores, _step, _absorb, heads.hpb, causal, block_q,
                block_k, sub, multi, qi, kj, seg_refs)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        _write(slice(None), [m_s[h, :, :1] for h in hs],
               [l_s[h, :, :1] for h in hs], acc[:])


# Scoped VMEM a kernel may take.  Mosaic's default (16 MB on a v5e) holds
# one head's whole [1024, 1024] tile below the diagonal (s, dp, p, ds in
# float32); the second head of a block follows the first, yet the dk/dv
# kernel's stack reaches 22.5 MB at two heads of 64 (the described-v5e
# compile in tests/test_tpu_aot_compile.py), of 128 MiB on the chip.
_VMEM_LIMIT = 32 * 1024 * 1024


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _jit_once(driver):
    """A kernel driver under ``jax.jit``, its arrays (and the ``None`` of
    an absent mask, segment ids or seed) dynamic and the rest static: the
    layers of a stack that call it alike share one trace of the kernel's
    body and one lowering, where an unrolled 24-layer step traced and
    lowered 72 (BERT's trace-and-lower 6.2 s against 13.4 without this,
    on the sandbox's CPU, and 6.2 on [b x heads, s, d] kernels)."""
    import inspect

    names = list(inspect.signature(driver).parameters)
    arrays = {"q3", "k3", "v3", "do3", "lse3", "o3", "kpm", "seg", "seed",
              "qr3", "kr3"}
    return jax.jit(driver, static_argnames=[
        n for n in names if n not in arrays])


def _specs(heads, block_q, block_k, at, seg, seed, dropout_p):
    """The block specs of a kernel whose grid points ``at`` maps to
    ``(query head block, K/V head block, q block, kv block)``: the spec
    of a q-like and of a k-like array, the key-padding mask's ([b, 1,
    skp] additive f32), and the operands every kernel takes first -- the
    dropout seed and the segment ids of the tile's rows and columns
    ([b, sqp] / [b, skp] int32) -- as (in_specs, args)."""
    from jax.experimental.pallas import tpu as pltpu

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *g: index(*at(*g)),
                            memory_space=pltpu.VMEM)

    w = heads.width
    in_specs, args = [], []
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if seg is not None:
        in_specs += [
            spec((1, block_q), lambda f, fk, i, j: (heads.batch(f), i)),
            spec((1, block_k), lambda f, fk, i, j: (heads.batch(f), j))]
        args += list(seg)
    return (spec((1, block_q, w), lambda f, fk, i, j: heads.q_block(f, i)),
            spec((1, block_k, w), lambda f, fk, i, j: heads.kv_block(fk, j)),
            spec((1, 1, block_k), lambda f, fk, i, j: (heads.batch(f), 0, j)),
            in_specs, args)


@_jit_once
def _fwd_pallas(q3, k3, v3, kpm, seg, seed, scale, causal, sk_real,
                block_q, block_k, dropout_p, interpret, out_dtype=None,
                gqa=None, d=None):
    """The forward kernel on kernel arrays (:func:`_layout`: packed
    ``[b, s, heads x d]`` when ``d`` is given, else ``[b x heads, s, d]``
    rows with ``gqa = (n, g)``).  Returns ``o`` and the logsumexp, both
    shaped like ``q3``: a head's logsumexp rides the head's own lanes."""
    from jax.experimental.pallas import tpu as pltpu

    heads = _layout(q3, k3, gqa, d)
    sqp, skp = q3.shape[1], k3.shape[1]
    grid = (heads.count(q3), sqp // block_q, skp // block_k)
    sub = _sub_tile(block_q, block_k) if causal and seg is None else 0
    direct = bool(sub) and grid[2] == 1

    q_spec, k_spec, kpm_spec, in_specs, args = _specs(
        heads, block_q, block_k, lambda f, i, j: (f, heads.kv_of(f), i, j),
        seg, seed, dropout_p)
    in_specs += [q_spec, k_spec, k_spec]
    args += [q3, k3, v3]
    if kpm is not None:
        in_specs.append(kpm_spec)
        args.append(kpm)

    with jax.named_scope("flash_fwd"):
        o, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale, causal, sk_real,
                              block_q, block_k, kpm is not None,
                              seg is not None, dropout_p, sub,
                              grid[1:] != (1, 1), skp != sk_real, direct,
                              heads),
            grid=grid,
            in_specs=in_specs,
            out_specs=[q_spec, q_spec],
            out_shape=[out_struct(q3.shape, out_dtype or q3.dtype, q3),
                       out_struct(q3.shape, jnp.float32, q3)],
            scratch_shapes=[] if direct else [
                pltpu.VMEM((block_q, heads.width), jnp.float32),
                pltpu.VMEM((heads.hpb, block_q, _LANES), jnp.float32),
                pltpu.VMEM((heads.hpb, block_q, _LANES), jnp.float32),
            ],
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels.
# ---------------------------------------------------------------------------


def _scores_and_dp(q, k, v, do, kpm, scale, rope=None):
    """The backward kernels' first stage for one head over a score
    rectangle: the scaled scores ``q k^T`` (plus the additive key mask)
    and ``do v^T``, ``q`` and ``do`` the head's :func:`_take`; ``rope``
    (latent attention) is the head's rotary part of the scores."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if rope is not None:
        s = s + rope
    s = s * scale
    if kpm is not None:
        s = s + kpm
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return s, dp


def _bwd_tile(refs, has_kpm, has_seg, dropout_p, scale, heads, f, q_start,
              k_start, sk_real, sq_real, guarded):
    """What the backward kernels share: ``products(h, *rect)``, the first
    stage of head ``h`` over a rectangle, and ``grads(h, *rect, made)``,
    the head's ``p`` for dv, its ``ds`` and their operands."""
    if dropout_p > 0.0:
        seed_ref, refs = refs[0], refs[1:]
    seg_refs = None
    if has_seg:
        seg_refs, refs = refs[:2], refs[2:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref = refs[:6]
    kpm_ref, refs = (refs[6], refs[7:]) if has_kpm else (None, refs[6:])
    if heads.rope:
        (qr_ref, kr_ref), refs = refs[:2], refs[2:]
    slots = [heads.slot(f, h) for h in range(heads.hpb)]

    def products(h, r0, rn, c0, cn, crossed):
        rows, cols = slice(r0, r0 + rn), slice(c0, c0 + cn)
        q = _take(q_ref[0, rows, :].astype(jnp.float32), h, slots[h], heads)
        do = _take(do_ref[0, rows, :].astype(jnp.float32), h, slots[h],
                   heads)
        rope = (_rope_scores(qr_ref[0, rows, :], kr_ref[0, cols, :], f)
                if heads.rope else None)
        return (*_scores_and_dp(
            q, k_ref[0, cols, :].astype(jnp.float32),
            v_ref[0, cols, :].astype(jnp.float32), do,
            kpm_ref[0, :, cols] if has_kpm else None, scale, rope), q, do)

    def grads(h, r0, rn, c0, cn, crossed, made):
        """``(p_acc, ds, q, do)``: the probabilities that weigh ``do``
        into dv (dropped and rescaled under dropout), the score gradient
        and the head's two operands from ``products``."""
        s, dp, q, do = made
        rows, cols = slice(r0, r0 + rn), slice(c0, c0 + cn)
        pred = _open_pairs(q_start + r0, k_start + c0, (rn, cn), crossed,
                           sk_real, sq_real)
        if has_seg:
            qseg = seg_refs[0][0, rows].reshape(rn, 1)
            kseg = seg_refs[1][0, cols].reshape(1, cn)
            pred = _both(pred, (qseg == kseg) & (kseg >= 0))
        lse = lse_ref[0, rows, h * heads.d:h * heads.d + 1]
        if guarded:
            # fully-masked rows carry the -inf lse sentinel: s - lse would
            # be ~0 there (additive -1e30 mask == -1e30 sentinel), not
            # -inf -- zero them explicitly or pad keys receive garbage
            # gradients
            pred = _both(pred, lse > _NEG_INF / 2)
        p = jnp.exp(s - lse)
        if pred is not None:
            p = jnp.where(pred, p, 0.0)
        p_acc = p
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0], f * heads.hpb + h, q_start + r0,
                              k_start + c0, (rn, cn), 1.0 - dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            p_acc = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        # delta = rowsum(do * o) of the head, from the forward's output
        do_o = (do_ref[0, rows, :].astype(jnp.float32)
                * o_ref[0, rows, :].astype(jnp.float32))
        if heads.hpb > 1:
            do_o = jnp.where(_lane_head(do_o.shape, heads) == h, do_o, 0.0)
        delta = jnp.sum(do_o, axis=-1, keepdims=True)
        return p_acc, p * (dp - delta) * scale, q, do

    return products, grads, seg_refs, k_ref, refs, slots


def _dq_of(ds, k, h, slots, heads):
    """Head ``h``'s ``ds k`` in the head's own lanes."""
    return _move(jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32), slots[h], h, heads)


def _dkv_of(p_acc, ds, q, do):
    """A head's ``(p^T do, ds^T q)``: in its K/V head's lanes, zeros in
    the others' (``q`` and ``do`` are zero there), so heads add up."""
    return tuple(jax.lax.dot_general(
        a, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        for a, x in ((p_acc, do), (ds, q)))


def _bwd_dq_kernel(scale, causal, sk_real, padded, block_q, block_k,
                   has_kpm, has_seg, dropout_p, sub, multi, heads, *refs):
    f, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q_start, k_start = qi * block_q, kj * block_k
    bounded = padded or not sub     # see _fwd_kernel
    products, grads, seg_refs, k_ref, (dq_ref, dq_acc), slots = _bwd_tile(
        refs, has_kpm, has_seg, dropout_p, scale, heads, f, q_start,
        k_start, sk_real if bounded else None, None, has_kpm or not sub)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _dq(h, r0, rn, c0, cn, crossed, made):
        _, ds, _, _ = grads(h, r0, rn, c0, cn, crossed, made)
        return _dq_of(ds, k_ref[0, c0:c0 + cn, :].astype(jnp.float32), h,
                      slots, heads)

    def _accumulate(r0, rn, c0, cn, crossed, parts):
        dq_acc[r0:r0 + rn, :] += _merge(parts, heads, parts[0].shape)

    _visit_tile(products, _dq, _accumulate, heads.hpb, causal, block_q,
                block_k, sub, multi, qi, kj, seg_refs)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(scale, causal, sq_real, sk_real, padded, block_q,
                    block_k, has_kpm, has_seg, dropout_p, sub, multi, heads,
                    *refs):
    # grid (K/V head blocks, kv, rep, q): one dk/dv block accumulates the
    # rep query head blocks that read it (GQA; rep 1 for MHA); f is the
    # query head block, so the dropout hash matches the forward's bit for
    # bit
    fk, kj = pl.program_id(0), pl.program_id(1)
    r, qi = pl.program_id(2), pl.program_id(3)
    f = heads.q_of(fk, r)
    first = (r == 0) & (qi == 0)
    last = (r == heads.rep - 1) & (qi == pl.num_programs(3) - 1)
    q_start, k_start = qi * block_q, kj * block_k
    bounded = padded or not sub     # see _fwd_kernel
    products, grads, seg_refs, _, outs, _ = _bwd_tile(
        refs, has_kpm, has_seg, dropout_p, scale, heads, f, q_start,
        k_start, sk_real if bounded else None,
        sq_real if bounded else None, has_kpm or not sub)
    dk_ref, dv_ref, dk_acc, dv_acc = outs

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _dkv(h, *rect_made):
        return _dkv_of(*grads(h, *rect_made))

    def _accumulate(r0, rn, c0, cn, crossed, parts):
        dv, dk = (sum(x) for x in zip(*parts))
        dv_acc[c0:c0 + cn, :] += dv
        dk_acc[c0:c0 + cn, :] += dk

    _visit_tile(products, _dkv, _accumulate, heads.hpb, causal, block_q,
                block_k, sub, multi, qi, kj, seg_refs, by="cols")

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(scale, causal, sq_real, sk_real, block_q, skp,
                      has_kpm, has_seg, dropout_p, heads, *refs):
    """Single-pass backward for short key sequences: K/V stay fully
    VMEM-resident, the probability tile is computed ONCE, and dq/dk/dv
    all fall out of the same pass — where the split dq + dkv kernels
    recompute p twice and traverse HBM twice.  This is the class the
    reference serves with its small-seqlen fmha variants
    (fmha_api.cpp:358 `_nl` kernels)."""
    f, qi = pl.program_id(0), pl.program_id(1)
    # the grid walks query head blocks (batch-major, so the rep blocks
    # that read one K/V block are consecutive) while the dk/dv output is
    # the K/V block -- init on its first (block, q-block) step, flush on
    # its last
    r = _rem(_rem(f, heads.nb), heads.rep)
    first = (r == 0) & (qi == 0)
    last = (r == heads.rep - 1) & (qi == pl.num_programs(1) - 1)
    products, grads, _, k_ref, outs, slots = _bwd_tile(
        refs, has_kpm, has_seg, dropout_p, scale, heads, f, qi * block_q,
        0, sk_real, sq_real, True)
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = outs

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    tile = (0, block_q, 0, skp, causal)
    k = k_ref[0].astype(jnp.float32)
    dq = []
    for h in range(heads.hpb):
        p_acc, ds, q, do = grads(h, *tile, products(h, *tile))
        dq.append(_dq_of(ds, k, h, slots, heads))
        dv, dk = _dkv_of(p_acc, ds, q, do)
        dv_acc[:] += dv
        dk_acc[:] += dk
    dq_ref[0] = _merge(dq, heads, dq[0].shape).astype(dq_ref.dtype)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@_jit_once
def _bwd_pallas_fused(q3, k3, v3, do3, lse3, o3, kpm, seg, seed, scale,
                      causal, sq_real, sk_real, block_q, dropout_p,
                      interpret, out_dtype=None, gqa=None, d=None):
    """Driver for :func:`_bwd_fused_kernel` — grid (query head blocks,
    q-blocks), K/V full-length a block (call only when the padded key
    length fits VMEM).  ``lse3`` and ``o3`` are the forward's two outputs
    (:func:`_fwd_pallas`), shaped like ``q3``.  Under GQA the rep
    consecutive query head blocks of a K/V block accumulate into one
    dk/dv output block, which stays resident across their grid steps."""
    from jax.experimental.pallas import tpu as pltpu

    heads = _layout(q3, k3, gqa, d)
    sqp, skp = q3.shape[1], k3.shape[1]
    qspec, kspec, kpm_spec, in_specs, args = _specs(
        heads, block_q, skp, lambda f, i: (f, heads.kv_of(f), i, 0),
        seg, seed, dropout_p)
    in_specs += [qspec, kspec, kspec, qspec, qspec, qspec]
    args += [q3, k3, v3, do3, lse3, o3]
    if kpm is not None:
        in_specs.append(kpm_spec)
        args.append(kpm)
    with jax.named_scope("flash_bwd"):
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale, causal, sq_real,
                              sk_real, block_q, skp, kpm is not None,
                              seg is not None, dropout_p, heads),
            grid=(heads.count(q3), sqp // block_q),
            in_specs=in_specs,
            out_specs=[qspec, kspec, kspec],
            out_shape=[out_struct(q3.shape, out_dtype or q3.dtype, q3),
                       out_struct(k3.shape, out_dtype or k3.dtype, k3),
                       out_struct(k3.shape, out_dtype or v3.dtype, k3)],
            scratch_shapes=[pltpu.VMEM((skp, heads.width), jnp.float32),
                            pltpu.VMEM((skp, heads.width), jnp.float32)],
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(*args)
    return dq, dk, dv


@_jit_once
def _bwd_pallas(q3, k3, v3, do3, lse3, o3, kpm, seg, seed, scale,
                causal, sq_real, sk_real, block_q, block_k, dropout_p,
                interpret, out_dtype=None, gqa=None, d=None):
    """The split backward pair on kernel arrays, with the forward's two
    outputs ``lse3`` and ``o3`` (:func:`_fwd_pallas`; shaped like ``q3``):
    the kernels make ``delta = rowsum(do * o)`` themselves."""
    from jax.experimental.pallas import tpu as pltpu

    heads = _layout(q3, k3, gqa, d)
    sqp, skp = q3.shape[1], k3.shape[1]
    sub = _sub_tile(block_q, block_k) if causal and seg is None else 0
    multi = (sqp, skp) != (block_q, block_k)

    def call(kernel, grid, at, outs):
        """One kernel over ``grid`` (:func:`_specs` has ``at``); ``outs``
        says what each output is like, "q" or "k": its shape and blocks,
        and the rows of its float32 accumulator."""
        qspec, kspec, kpm_spec, in_specs, args = _specs(
            heads, block_q, block_k, at, seg, seed, dropout_p)
        in_specs += [qspec, kspec, kspec, qspec, qspec, qspec]
        args += [q3, k3, v3, do3, lse3, o3]
        if kpm is not None:
            in_specs.append(kpm_spec)
            args.append(kpm)
        like = {"q": (q3, qspec, block_q), "k": (k3, kspec, block_k)}
        return pl.pallas_call(
            functools.partial(
                kernel, block_q, block_k, kpm is not None, seg is not None,
                dropout_p, sub, multi, heads),
            grid=grid, in_specs=in_specs,
            out_specs=[like[x][1] for x in outs],
            out_shape=[out_struct(like[x][0].shape,
                                  out_dtype or like[x][0].dtype, like[x][0])
                       for x in outs],
            scratch_shapes=[
                pltpu.VMEM((like[x][2], heads.width), jnp.float32)
                for x in outs],
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(*args)

    # --- dq: grid (query head blocks, q, kv) ---------------------------
    with jax.named_scope("flash_bwd_dq"):
        dq, = call(
            functools.partial(_bwd_dq_kernel, scale, causal, sk_real,
                              skp != sk_real),
            (heads.count(q3), sqp // block_q, skp // block_k),
            lambda f, i, j: (f, heads.kv_of(f), i, j), "q")

    # --- dk/dv: grid (K/V head blocks, kv, rep, q) ---------------------
    # The rep query head blocks of a K/V block (GQA; 1 for MHA) are a
    # grid dim OUTSIDE the q-block dim, so the dk/dv output block stays
    # fixed across (rep x q-blocks) consecutive steps while the kernel
    # accumulates all of the group's query heads into it; the repeated
    # dk/dv tensor (and the jnp.repeat forward tensor whose autodiff
    # would sum it) never exists in HBM.
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = call(
            functools.partial(_bwd_dkv_kernel, scale, causal, sq_real,
                              sk_real, (sqp, skp) != (sq_real, sk_real)),
            (heads.count(k3), skp // block_k, heads.rep, sqp // block_q),
            lambda fk, j, r, i: (heads.q_of(fk, r), fk, i, j), "kk")
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper.
# ---------------------------------------------------------------------------


def _to_bh(x):
    """[b, s, n, d] → [b*n, s, d]."""
    b, s, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)


def _from_bh(x3, b, n):
    bh, s, d = x3.shape
    return x3.reshape(b, n, s, d).transpose(0, 2, 1, 3)


def _to_kernel(x, hpb):
    """[b, s, n, d] → the kernels' array: the free reshape [b, s, n x d]
    where :func:`_heads_a_block` packs, else [b x n, s, d] rows."""
    b, s, n, d = x.shape
    return x.reshape(b, s, n * d) if hpb else _to_bh(x)


def _from_kernel(x3, b, n, hpb):
    return x3.reshape(b, x3.shape[1], n, -1) if hpb else _from_bh(x3, b, n)


def _head_lanes(n, d):
    """[n, n x d] float32, 1 where a lane belongs to the head."""
    return (jnp.arange(n * d)[None, :] // d
            == jnp.arange(n)[:, None]).astype(jnp.float32)


def _lse_to_kernel(lse, d, hpb):
    """The saved logsumexp [b, s, n] → shaped like the kernels' q array,
    a head's value across the head's ``d`` lanes.  Packed, the spread is
    a product with a 0/1 matrix at full precision (exact: one term a
    sum): as a broadcast XLA lays the [b, s, n, d] array out sequence-
    minor after its small operand and transposes all of it back."""
    b, s, n = lse.shape
    if hpb:
        return jnp.einsum("bsn,nl->bsl", lse, _head_lanes(n, d),
                          precision=jax.lax.Precision.HIGHEST)
    return jnp.broadcast_to(
        lse.transpose(0, 2, 1).reshape(b * n, s, 1), (b * n, s, d))


def _lse_from_kernel(lse3, b, n, hpb):
    """The kernel's logsumexp → [b, s, n], the first lane of every head
    (packed: picked by the same kind of product, for the same reason)."""
    if hpb:
        first = (jnp.arange(lse3.shape[2])[None, :]
                 == jnp.arange(n)[:, None] * (lse3.shape[2] // n))
        return jnp.einsum("bsl,nl->bsn", lse3, first.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    return lse3[:, :, 0].reshape(b, n, -1).transpose(0, 2, 1)


def _blocks(sq, sk):
    """Grid tile of the score rectangle: 1024 x 1024 from 1024 keys on,
    256 x 512 below (other tiles at s512: not measured this round;
    1024 x 2048 once failed to compile on VMEM, so 1024 caps both).

    The large tile amortizes the per-grid-step cost and fetches K/V once
    a head; the causal kernels then skip INSIDE it (:func:`_sub_tile`).
    Per call at b8 x 16 heads x s1024 x d64, bf16, causal, the kernel
    chained 20 times in one jit on a v5e (my chip runs, PR 31; dq and
    dkv include ~250 us of widening ``lse``/``delta`` to 128 lanes):

    ==============================  =======  =======  =======
    tile                            forward  dq       dk/dv
    ==============================  =======  =======  =======
    1024 x 1024 whole (PR 30)       646 us   838 us   1081 us
    1024 x 1024 in bands of 512     394      713      892
    1024 x 1024 in bands of 256     352      644      811
    1024 x 1024 in bands of 128     344      601      822
    512 x 512 grid tiles            1023     968      1251
    256 x 512 grid tiles            1052     1205     1351
    ==============================  =======  =======  =======

    Since PR 33 a grid step holds two heads of 64 in blocks [1024, 128]
    of ``[b, s, heads x d]`` and the backward makes ``delta`` from ``o``
    itself (no widened ``lse``/``delta``).  A layer call inside the GPT
    cell's step, bands of 256 (``scope_times``, my chip runs, PR 33;
    PR 31's kernels read 299 / 391 / 561 the same way):

    ==============================  =======  =======  =======
    two heads a block, bands 256    303 us   363 us   522 us
    ==============================  =======  =======  =======

    At b2 x 32/8 heads x s8192 (8 x 8 tiles, 8 of the 36 executed on the
    diagonal): whole 10.77 / 12.80 / 22.28 ms, bands of 256 9.03 / 11.95
    / 17.60, of 128 9.06 / 11.82 / 17.70, of 512 9.23 / 12.22 / 17.93."""
    bq = min(1024 if sq >= 1024 else 256, pl.cdiv(sq, _LANES) * _LANES)
    bk = min(1024 if sk >= 1024 else 512, pl.cdiv(sk, _LANES) * _LANES)
    return bq, bk


# The longest padded key length that takes the fused single-pass backward
# (K/V whole in VMEM, p computed once for dq, dk and dv); longer keys take
# the split pair.  The ledger holds each side where a cell runs it: BERT
# s512 fused, `flash_bwd_ms` 7.37 a step; GPT s1024 split, 22.86 (PR 31).
# PERF.md section 7(b) has the fused kernel at s1024, for the perf_opt
# that moves this constant.
_FUSED_BWD_MAX_SK = 512
# The fused backward's q block, where it divides the padded query length
# (smaller blocks at s512: not measured this round).
_FUSED_BWD_BQ = 512


def _bwd_plan(sqp, skp, block_q):
    """Which backward runs, from the static padded shape alone:
    ``("fused", q block)`` or ``("split", None)``."""
    if skp > _FUSED_BWD_MAX_SK:
        return "split", None
    bq = min(_FUSED_BWD_BQ, sqp)
    # block_q set the padding, so it always divides sqp (a floor-division
    # grid would drop tail q rows)
    return "fused", bq if sqp % bq == 0 else block_q


def _sub_tile(block_q, block_k):
    """Rows (forward, dq) or columns (dk/dv) of the bands a causal score
    tile ON the diagonal is worked in, 0 for whole tiles: the second
    half of :func:`_blocks`'s rule, a function of the static shapes
    alone.  256 where the tile is 1024 x 1024: within 2% of 128 over the
    three kernels at s1024 and level with it at s8192 (table above) with
    half the unrolled bands; 512 keeps 3 squares of 4 and loses 10%."""
    return 256 if block_q == block_k >= 1024 else 0


def _tile_runs(block_q, block_k, qi, kj):
    """Whether score tile (qi, kj) reaches down to the diagonal."""
    return kj * block_k <= qi * block_q + block_q - 1


def causal_work_share(sq, sk, causal=True):
    """Share of the padded score rectangle's elements that the kernels
    compute for an [sq, sk] attention: a static function of the shapes,
    counted with the rule (:func:`_blocks`, :func:`_sub_tile`), the
    dispatch (:func:`_visit_tile`) and the rectangles
    (:func:`_causal_rects`) that the kernels run.  1.0 when not causal;
    0.625 at s1024 (10 of 16 squares of 256); 33/64 at s8192."""
    if not causal:
        return 1.0
    bq, bk = _blocks(sq, sk)
    sub = _sub_tile(bq, bk)
    on_diagonal = sub and sum(
        rn * cn for _, rn, _, cn, _ in _causal_rects(bq, sub, "rows")
    ) / (bq * bk)
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    done = 0.0
    for qi in range(nq):
        for kj in range(nk):
            if sub:
                done += 1.0 if kj < qi else on_diagonal if kj == qi else 0.0
            else:
                done += bool(_tile_runs(bq, bk, qi, kj))
    return done / (nq * nk)


def _seg_pads(seg, sqp, skp):
    """[b, sq] int32 segment ids → padded (q_view, k_view), pad id −2
    (matches nothing; negative ids are always-masked keys)."""
    if seg is None:
        return None
    seg = seg.astype(jnp.int32)
    segq = _pad_to(seg + 0, sqp, 1) + jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (1, sqp), 1) >= seg.shape[1],
        jnp.int32(-2), 0)
    segk = _pad_to(seg + 0, skp, 1) + jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (1, skp), 1) >= seg.shape[1],
        jnp.int32(-2), 0)
    return segq, segk


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash(q, k, v, kpm, seg, seed, causal, scale, dropout_p):
    o, _ = _flash_fwd(q, k, v, kpm, seg, seed, causal, scale, dropout_p)
    return o


def _flash_fwd(q, k, v, kpm, seg, seed, causal, scale, dropout_p):
    b, sq, n, d = q.shape
    sk = k.shape[1]
    g = k.shape[2]
    hpb = _heads_a_block(n, g, d)
    block_q, block_k = _blocks(sq, sk)
    sqp = pl.cdiv(sq, block_q) * block_q
    skp = pl.cdiv(sk, block_k) * block_k
    q3 = _pad_to(_to_kernel(q, hpb), sqp, 1)
    k3 = _pad_to(_to_kernel(k, hpb), skp, 1)
    v3 = _pad_to(_to_kernel(v, hpb), skp, 1)
    kpm3 = (None if kpm is None
            else _pad_to(kpm.astype(jnp.float32)[:, None, :], skp, 2))
    seg3 = _seg_pads(seg, sqp, skp)
    q3, k3, v3, kpm3, seg3q, seg3k, seed = _unify_vma(
        q3, k3, v3, kpm3,
        None if seg3 is None else seg3[0],
        None if seg3 is None else seg3[1], seed)
    seg3 = None if seg3 is None else (seg3q, seg3k)
    o3, lse3 = _fwd_pallas(q3, k3, v3, kpm3, seg3, seed, scale, causal,
                           sk, block_q, block_k, dropout_p,
                           interpret=not on_tpu(), gqa=(n, g),
                           d=d if hpb else None)
    # the two arrays a rematted layer keeps (module docstring)
    o = checkpoint_name(_from_kernel(o3, b, n, hpb)[:, :sq],
                        REMAT_SAVED_NAMES[0])
    lse = checkpoint_name(_lse_from_kernel(lse3, b, n, hpb)[:, :sq],
                          REMAT_SAVED_NAMES[1])
    return o, (q, k, v, kpm, seg, seed, o, lse)


def _flash_bwd(causal, scale, dropout_p, res, do):
    q, k, v, kpm, seg, seed, o, lse = res
    b, sq, n, d = q.shape
    sk = k.shape[1]
    g = k.shape[2]
    hpb = _heads_a_block(n, g, d)
    block_q, block_k = _blocks(sq, sk)
    sqp = pl.cdiv(sq, block_q) * block_q
    skp = pl.cdiv(sk, block_k) * block_k
    q3 = _pad_to(_to_kernel(q, hpb), sqp, 1)
    k3 = _pad_to(_to_kernel(k, hpb), skp, 1)
    v3 = _pad_to(_to_kernel(v, hpb), skp, 1)
    do3 = _pad_to(_to_kernel(do, hpb), sqp, 1)
    o3 = _pad_to(_to_kernel(o, hpb), sqp, 1)
    lse3 = _pad_to(_lse_to_kernel(lse, d, hpb), sqp, 1)
    kpm3 = (None if kpm is None
            else _pad_to(kpm.astype(jnp.float32)[:, None, :], skp, 2))
    seg3 = _seg_pads(seg, sqp, skp)
    q3, k3, v3, do3, lse3, o3, kpm3, seg3q, seg3k, seed = _unify_vma(
        q3, k3, v3, do3, lse3, o3, kpm3,
        None if seg3 is None else seg3[0],
        None if seg3 is None else seg3[1], seed)
    seg3 = None if seg3 is None else (seg3q, seg3k)
    plan, fused_bq = _bwd_plan(sqp, skp, block_q)
    layout = dict(interpret=not on_tpu(), gqa=(n, g), d=d if hpb else None)
    if plan == "fused":
        dq3, dk3, dv3 = _bwd_pallas_fused(
            q3, k3, v3, do3, lse3, o3, kpm3, seg3, seed, scale,
            causal, sq, sk, fused_bq, dropout_p, **layout)
    else:
        dq3, dk3, dv3 = _bwd_pallas(
            q3, k3, v3, do3, lse3, o3, kpm3, seg3, seed, scale,
            causal, sq, sk, block_q, block_k, dropout_p, **layout)
    dq = _from_kernel(dq3, b, n, hpb)[:, :sq]
    dk = _from_kernel(dk3, b, g, hpb)[:, :sk]
    dv = _from_kernel(dv3, b, g, hpb)[:, :sk]
    # The kernel treats the (float) mask as a constant: the wrapper
    # stop-gradients it, so a zero cotangent is the user-visible truth.
    # Learned additive masks/biases belong on the differentiable XLA
    # ``bias`` path.
    dkpm = None if kpm is None else jnp.zeros_like(kpm)
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    dseed = np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, dkpm, dseg, dseed


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Latent attention (MLA): a head's query and key are [no-position part |
# rotary part], the value is as wide as the no-position part, and the rotary
# key is ONE head that all query heads read.
# ---------------------------------------------------------------------------


def _mla_specs(block_q, block_k, at):
    """The block specs of a latent-attention kernel whose grid points
    ``at`` maps to ``(batch row, head, q block, kv block)``: of a q-like
    array ([b, s, n x 128], one head a block), of a k-like one, of the
    rotary queries ([b, s, n x 64]: a pair of heads a block) and of the
    rotary key ([b, s, 128]: the one key twice, for every head)."""
    from jax.experimental.pallas import tpu as pltpu

    def spec(block, index):
        return pl.BlockSpec((1, block, _LANES),
                            lambda *g: index(*at(*g)),
                            memory_space=pltpu.VMEM)

    return (spec(block_q, lambda b, h, i, j: (b, i, h)),
            spec(block_k, lambda b, h, i, j: (b, j, h)),
            spec(block_q, lambda b, h, i, j: (b, i, _div(h, 2))),
            spec(block_k, lambda b, h, i, j: (b, j, 0)))


def _mla_heads(q3):
    n = q3.shape[2] // _LANES
    return _Heads(n, n, 1, _LANES, True, rope=True)


@_jit_once
def _mla_fwd_pallas(q3, qr3, k3, kr3, v3, scale, causal, sk_real, block_q,
                    block_k, interpret):
    """:func:`_fwd_kernel` with the rotary product in its scores, on
    ``q3``, ``k3``, ``v3`` [b, s, n x 128], ``qr3`` [b, s, n x 64] and
    ``kr3`` [b, s, 128]; grid (batch x heads, q, kv).  Returns ``o`` and
    the logsumexp, both shaped like ``q3``."""
    from jax.experimental.pallas import tpu as pltpu

    heads = _mla_heads(q3)
    n = heads.nb
    sqp, skp = q3.shape[1], k3.shape[1]
    grid = (q3.shape[0] * n, sqp // block_q, skp // block_k)
    sub = _sub_tile(block_q, block_k) if causal else 0
    direct = bool(sub) and grid[2] == 1
    q_spec, k_spec, qr_spec, kr_spec = _mla_specs(
        block_q, block_k, lambda f, i, j: (_div(f, n), _rem(f, n), i, j))
    with jax.named_scope("flash_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, scale, causal, sk_real,
                              block_q, block_k, False, False, 0.0, sub,
                              grid[1:] != (1, 1), skp != sk_real, direct,
                              heads),
            grid=grid,
            in_specs=[q_spec, k_spec, k_spec, qr_spec, kr_spec],
            out_specs=[q_spec, q_spec],
            out_shape=[out_struct(q3.shape, q3.dtype, q3),
                       out_struct(q3.shape, jnp.float32, q3)],
            scratch_shapes=[] if direct else [
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((1, block_q, _LANES), jnp.float32),
                pltpu.VMEM((1, block_q, _LANES), jnp.float32),
            ],
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(q3, k3, v3, qr3, kr3)


def _mla_bwd_dq_kernel(scale, causal, sk_real, padded, block_q, block_k,
                       sub, multi, heads, *refs):
    # grid (batch x head pairs, q, 2, kv): a pair's two heads follow one
    # another inside a q block, so that the pair's block of the rotary
    # queries' gradient stays where it is while both add their half
    pair, qi = pl.program_id(0), pl.program_id(1)
    h2, kj = pl.program_id(2), pl.program_id(3)
    f = pair * 2 + h2
    last = kj == pl.num_programs(3) - 1
    bounded = padded or not sub     # see _fwd_kernel
    kr_ref = refs[7]
    products, grads, _, k_ref, outs, _ = _bwd_tile(
        refs, False, False, 0.0, scale, heads, f, qi * block_q,
        kj * block_k, sk_real if bounded else None, None, not sub)
    dq_ref, dqr_ref, dq_acc, dqr_acc = outs

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when((kj == 0) & (h2 == 0))
    def _init_pair():
        dqr_acc[:] = jnp.zeros_like(dqr_acc)

    def _dq(h, r0, rn, c0, cn, crossed, made):
        ds = grads(h, r0, rn, c0, cn, crossed, made)[1]
        return tuple(jax.lax.dot_general(
            ds, ref[0, c0:c0 + cn, :].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for ref in (k_ref, kr_ref))

    def _accumulate(r0, rn, c0, cn, crossed, parts):
        (dq, dqr), = parts
        dq_acc[r0:r0 + rn, :] += dq
        dqr_acc[r0:r0 + rn, :] += _rope_half(dqr, f)

    _visit_tile(products, _dq, _accumulate, 1, causal, block_q, block_k,
                sub, multi, qi, kj, None)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(last & (h2 == 1))
    def _finalize_pair():
        dqr_ref[0] = dqr_acc[:].astype(dqr_ref.dtype)


def _mla_bwd_dkv_kernel(scale, causal, sq_real, sk_real, padded, block_q,
                        block_k, sub, multi, heads, *refs):
    # grid (batch, kv, heads, q): a head's dk and dv accumulate over its
    # q blocks; the rotary key's gradient, the sum over ALL heads, stays
    # in its block across (heads x q blocks) consecutive steps.  A head
    # adds to its own half of the doubled key's lanes (the other half of
    # its rotary queries is zeroed); the wrapper adds the halves
    bi, kj = pl.program_id(0), pl.program_id(1)
    hd, qi = pl.program_id(2), pl.program_id(3)
    f = bi * heads.nb + hd
    last = qi == pl.num_programs(3) - 1
    bounded = padded or not sub     # see _fwd_kernel
    qr_ref = refs[6]
    products, grads, _, _, outs, _ = _bwd_tile(
        refs, False, False, 0.0, scale, heads, f, qi * block_q,
        kj * block_k, sk_real if bounded else None,
        sq_real if bounded else None, not sub)
    dk_ref, dv_ref, dkr_ref, dk_acc, dv_acc, dkr_acc = outs

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when((qi == 0) & (hd == 0))
    def _init_rope():
        dkr_acc[:] = jnp.zeros_like(dkr_acc)

    def _dkv(h, r0, rn, c0, cn, crossed, made):
        p_acc, ds, q, do = grads(h, r0, rn, c0, cn, crossed, made)
        qr = _rope_half(qr_ref[0, r0:r0 + rn, :].astype(jnp.float32), f)
        return (*_dkv_of(p_acc, ds, q, do), jax.lax.dot_general(
            ds, qr, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))

    def _accumulate(r0, rn, c0, cn, crossed, parts):
        (dv, dk, dkr), = parts
        dv_acc[c0:c0 + cn, :] += dv
        dk_acc[c0:c0 + cn, :] += dk
        dkr_acc[c0:c0 + cn, :] += dkr

    _visit_tile(products, _dkv, _accumulate, 1, causal, block_q, block_k,
                sub, multi, qi, kj, None, by="cols")

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(last & (hd == heads.nb - 1))
    def _finalize_rope():
        dkr_ref[0] = dkr_acc[:].astype(dkr_ref.dtype)


@_jit_once
def _mla_bwd_pallas(q3, qr3, k3, kr3, v3, do3, lse3, o3, scale, causal,
                    sq_real, sk_real, block_q, block_k, interpret):
    """The split backward pair of latent attention (arrays as
    :func:`_mla_fwd_pallas` takes them, ``do3``, ``lse3`` and ``o3``
    shaped like ``q3``): ``(dq, dqr, dk, dkr, dv)``, ``dkr`` [b, s, 128]
    with the even heads' sum in its first half and the odd heads' in its
    second."""
    from jax.experimental.pallas import tpu as pltpu

    heads = _mla_heads(q3)
    n = heads.nb
    sqp, skp = q3.shape[1], k3.shape[1]
    sub = _sub_tile(block_q, block_k) if causal else 0
    multi = (sqp, skp) != (block_q, block_k)

    def call(kernel, grid, at, outs):
        q_spec, k_spec, qr_spec, kr_spec = _mla_specs(block_q, block_k, at)
        like = {"q": (q3, q_spec, block_q), "qr": (qr3, qr_spec, block_q),
                "k": (k3, k_spec, block_k), "kr": (kr3, kr_spec, block_k)}
        return pl.pallas_call(
            functools.partial(kernel, block_q, block_k, sub, multi, heads),
            grid=grid,
            in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, q_spec,
                      qr_spec, kr_spec],
            out_specs=[like[x][1] for x in outs],
            out_shape=[out_struct(like[x][0].shape, like[x][0].dtype,
                                  like[x][0]) for x in outs],
            scratch_shapes=[pltpu.VMEM((like[x][2], _LANES), jnp.float32)
                            for x in outs],
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(q3, k3, v3, do3, lse3, o3, qr3, kr3)

    with jax.named_scope("flash_bwd_dq"):
        dq, dqr = call(
            functools.partial(_mla_bwd_dq_kernel, scale, causal, sk_real,
                              skp != sk_real),
            (q3.shape[0] * n // 2, sqp // block_q, 2, skp // block_k),
            lambda p, i, h2, j: (_div(p, n // 2),
                                 _rem(p, n // 2) * 2 + h2, i, j),
            ("q", "qr"))
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv, dkr = call(
            functools.partial(_mla_bwd_dkv_kernel, scale, causal, sq_real,
                              sk_real, (sqp, skp) != (sq_real, sk_real)),
            (q3.shape[0], skp // block_k, n, sqp // block_q),
            lambda b, j, h, i: (b, h, i, j), ("k", "k", "kr"))
    return dq, dqr, dk, dkr, dv


def _mla_kernel_arrays(q, qr, k, kr, v):
    """The five arrays as the kernels read them, padded to whole blocks:
    the free reshapes [b, s, n x d], and the rotary key twice side by
    side in one block of 128 lanes (2 MB at s8192, where a copy a head
    would be 32)."""
    b, sq, n, _ = q.shape
    sk = k.shape[1]
    block_q, block_k = _blocks(sq, sk)
    sqp = pl.cdiv(sq, block_q) * block_q
    skp = pl.cdiv(sk, block_k) * block_k
    return (_pad_to(q.reshape(b, sq, -1), sqp, 1),
            _pad_to(qr.reshape(b, sq, -1), sqp, 1),
            _pad_to(k.reshape(b, sk, -1), skp, 1),
            _pad_to(jnp.concatenate([kr, kr], axis=-1), skp, 1),
            _pad_to(v.reshape(b, sk, -1), skp, 1)), (block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_mla(q, qr, k, kr, v, causal, scale):
    return _flash_mla_fwd(q, qr, k, kr, v, causal, scale)[0]


def _flash_mla_fwd(q, qr, k, kr, v, causal, scale):
    b, sq, n, _ = q.shape
    arrays, blocks = _mla_kernel_arrays(q, qr, k, kr, v)
    o3, lse3 = _mla_fwd_pallas(*arrays, scale, causal, k.shape[1], *blocks,
                               interpret=not on_tpu())
    o = checkpoint_name(_from_kernel(o3, b, n, 1)[:, :sq],
                        REMAT_SAVED_NAMES[0])
    lse = checkpoint_name(_lse_from_kernel(lse3, b, n, 1)[:, :sq],
                          REMAT_SAVED_NAMES[1])
    return o, (q, qr, k, kr, v, o, lse)


def _flash_mla_bwd(causal, scale, res, do):
    q, qr, k, kr, v, o, lse = res
    b, sq, n, d = q.shape
    sk = k.shape[1]
    arrays, blocks = _mla_kernel_arrays(q, qr, k, kr, v)
    sqp = arrays[0].shape[1]
    dq3, dqr3, dk3, dkr3, dv3 = _mla_bwd_pallas(
        *arrays, _pad_to(do.reshape(b, sq, -1), sqp, 1),
        _pad_to(_lse_to_kernel(lse, d, 1), sqp, 1),
        _pad_to(o.reshape(b, sq, -1), sqp, 1), scale, causal, sq, sk,
        *blocks, interpret=not on_tpu())
    dkr = dkr3[:, :sk].astype(jnp.float32)
    half = kr.shape[-1]
    return (dq3[:, :sq].reshape(q.shape), dqr3[:, :sq].reshape(qr.shape),
            dk3[:, :sk].reshape(k.shape),
            (dkr[..., :half] + dkr[..., half:]).astype(kr.dtype),
            dv3[:, :sk].reshape(v.shape))


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def flash_attention_mla(q, q_rope, k, k_rope, v, *, causal: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """Attention whose heads score with two parts (multi-head latent
    attention, arXiv 2405.04434): ``q`` and ``k`` [b, s, n, d] without
    position, ``q_rope`` [b, s, n, r] and ONE rotary key ``k_rope``
    [b, s, r] for all heads, ``v`` [b, s, n, dv]: ``softmax((q k^T +
    q_rope k_rope^T) * scale) v`` [b, s, n, dv], ``scale`` by default
    ``1 / sqrt(d + r)``.

    On a TPU (or interpreted) with ``d = dv = 128``, ``r = 64`` and an
    even head count the flash kernels run, forward and split backward:
    the score of a tile is the sum of two products in one visit, the
    rotary queries of two heads share a 128-lane block, and the rotary
    key is read once a K/V block for all heads -- it is never broadcast
    to the heads and nothing is padded to a common width.  The kernels
    take causal or full attention and sequences that are no multiple of
    the block.  Every other shape, and every other platform, runs
    :func:`mha_reference` on the concatenated parts."""
    n, d, r = q.shape[2], q.shape[3], q_rope.shape[3]
    scale = (1.0 / (d + r) ** 0.5) if scale is None else float(scale)
    kernels = (d == v.shape[3] == _LANES and 2 * r == _LANES
               and n % 2 == 0 and k.shape[2] == n
               and (on_tpu() or interpret_forced())
               and not jax.typeof(q).vma)
    if kernels:
        return _flash_mla(q, q_rope, k, k_rope, v, causal, scale)
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                              k.shape[:3] + (r,))
    return mha_reference(jnp.concatenate([q, q_rope], -1),
                         jnp.concatenate([k, k_rope], -1), v,
                         causal=causal, scale=scale)


def _seed_from_rng(dropout_rng) -> jax.Array:
    """Collapse a PRNG key (typed or raw uint32 pair) to an int32 seed."""
    data = jax.random.key_data(dropout_rng).reshape(-1)
    seed = data[-1]
    if data.shape[0] > 1:
        seed = seed ^ (data[-2] * jnp.uint32(0x9E3779B1))
    return seed.astype(jnp.int32).reshape(1)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    key_padding_mask: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    dropout_p: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Memory-efficient attention over [b, s, n, d] tensors.

    The Pallas blockwise kernel handles ``causal``, ``key_padding_mask``
    ([b, sk] bool True = masked, or additive float — the reference's
    ``mask_additive`` MHA mode), ``segment_ids`` ([b, s] int32 — packed
    multi-sequence rows attend within their own segment only, with a
    block-sparse skip of fully-disjoint tiles; negative ids mark padding
    slots.  This is the cu_seqlens varlen mode of the reference fmha,
    fmha_api.cpp:358 — see :func:`flash_attention_packed` for the
    cu_seqlens-shaped wrapper) and attention ``dropout`` (fused
    in-kernel, O(sq·d) memory — reference multihead_attn philox.cuh
    analog).  A generic boolean ``mask`` or additive ``bias`` falls back
    to the fused-softmax XLA composition.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [b, s, n, d], got {q.shape}")
    if v.shape[2] != k.shape[2]:
        raise ValueError(
            f"K/V head counts differ: k has {k.shape[2]}, "
            f"v has {v.shape[2]}")
    if k.shape[2] != q.shape[2]:
        # grouped K/V (GQA/MQA): each of the g kv heads serves
        # n//g query heads via kernel index maps — the repeated
        # [b, s, n, d] K/V never materializes in HBM
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"query heads ({q.shape[2]}) must be a multiple of the "
                f"K/V group count ({k.shape[2]})")
    seg_pair = isinstance(segment_ids, tuple)
    if segment_ids is not None and not seg_pair and (
            q.shape[1] != k.shape[1]):
        raise ValueError(
            "a single segment_ids array requires sq == sk (packed "
            "self-attention rows); pass a (seg_q, seg_k) pair for "
            "cross-attention shapes (runs on the XLA path)")
    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else float(scale)
    # per-side segment ids are beyond the fused kernel (it walks one
    # packed diagonal) — the XLA composition handles them exactly
    generic = mask is not None or bias is not None or seg_pair
    # Off-TPU inside shard_map (vma non-empty): the Pallas HLO
    # interpreter's internal while-loop cannot carry mixed varying-axes
    # buffers (jax 0.9 check) — run the XLA composition instead.  On
    # real TPU the kernel runs under shard_map as normal (same choice as
    # distributed_fused_adam's CPU path).
    if not on_tpu() and jax.typeof(q).vma:
        generic = True
    if generic:
        return mha_reference(
            q, k, v, causal=causal, key_padding_mask=key_padding_mask,
            mask=mask, bias=bias, scale=scale, dropout_p=dropout_p,
            dropout_rng=dropout_rng, segment_ids=segment_ids)
    kpm = key_padding_mask
    if kpm is not None:
        if kpm.dtype == jnp.bool_:
            kpm = jnp.where(kpm, jnp.float32(_NEG_INF), jnp.float32(0.0))
        # the fused kernel does not differentiate the mask — learned
        # additive masks must use ``bias`` (XLA path) instead
        kpm = jax.lax.stop_gradient(kpm)
    seg = (None if segment_ids is None
           else jax.lax.stop_gradient(segment_ids.astype(jnp.int32)))
    use_dropout = dropout_p > 0.0 and dropout_rng is not None
    seed = (_seed_from_rng(dropout_rng) if use_dropout
            else jnp.zeros((1,), jnp.int32))
    return _flash(q, k, v, kpm, seg, seed, causal, scale,
                  float(dropout_p) if use_dropout else 0.0)


def segment_ids_from_cu_seqlens(cu_seqlens: jax.Array,
                                total: int) -> jax.Array:
    """[b+1] cumulative sequence starts → [total] int32 segment ids
    (the reference varlen descriptor, fmha_api.cpp:358).  Positions at or
    beyond ``cu_seqlens[-1]`` get id −1 (padding: masked as keys)."""
    pos = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu_seqlens.astype(jnp.int32), pos,
                           side="right").astype(jnp.int32) - 1
    n_seq = cu_seqlens.shape[0] - 1
    return jnp.where(seg >= n_seq, -1, seg)


def flash_attention_packed(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cu_seqlens: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_p: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Varlen (THD) attention over ``[total, n, d]`` packed tensors.

    The reference fmha's defining mode: multiple sequences packed into
    one row with ``cu_seqlens`` boundaries and zero padding compute
    (apex/contrib/fmha/fmha.py:33-60, fmha_api.cpp:358).  Pairs with
    :func:`apex_tpu.ops.rope.fused_apply_rotary_pos_emb_thd` (same
    cu_seqlens layout).  Internally runs the segment-id kernel on a
    [1, total, n, d] view; cross-segment tiles are skipped blockwise.

    Self-attention only (one ``cu_seqlens`` describes both sides, the
    layout of the reference's ``FMHAFun``); for rectangular cross-
    attention grids call :func:`flash_attention` with a
    ``(seg_q, seg_k)`` pair, which runs the XLA composition.
    """
    if q.ndim != 3:
        raise ValueError(f"expected packed [total, n, d], got {q.shape}")
    total = q.shape[0]
    seg = segment_ids_from_cu_seqlens(cu_seqlens, total)
    out = flash_attention(
        q[None], k[None], v[None], causal=causal,
        segment_ids=seg[None], scale=scale, dropout_p=dropout_p,
        dropout_rng=dropout_rng)
    return out[0]
