"""Ring attention — context parallelism over a sequence mesh axis.

The reference implements only Megatron sequence parallelism (activations
sharded between, not inside, attention — apex/transformer/tensor_parallel/
mappings.py:55,95,114) and has **no** ring attention / context parallel /
Ulysses path (SURVEY.md §5). This module is the TPU-native long-context
answer: Q stays resident, K/V rotate around the 'sp' axis via
``lax.ppermute`` while each step runs the Pallas flash-attention kernels on
the local (q, kv-chunk) pair and merges results with a numerically stable
logsumexp combine. Per-device memory is O(s_local·d) regardless of the
global sequence length.

Backward is the true ring algorithm (not autodiff through the scan): dK/dV
accumulators travel around the ring *with* their K/V chunks, each step
calling the flash backward kernels with the **final** logsumexp and delta
(valid because p = exp(s - lse_final) globally); after world-size steps
every accumulator has gone full circle and lands on its home shard.

Causality is resolved per (q-shard, kv-chunk) pair with a 3-way
``lax.switch``: chunks fully below the diagonal attend unmasked, the
diagonal chunk runs the causal kernel, chunks above contribute nothing —
so causal ring attention also skips ~half the FLOPs.

Call inside ``jax.shard_map`` with q/k/v sharded along the sequence axis:

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(None, 'sp', None, None), out_specs=...)
    def f(q, k, v):
        return ring_attention(q, k, v, axis_name='sp', causal=True)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from apex_tpu.ops.flash_attention import (
    _NEG_INF,
    _bwd_pallas,
    _from_bh,
    _fwd_pallas,
    _pad_to,
    _to_bh,
)
from apex_tpu.utils.collectives import (
    match_vma,
    ppermute as _ppermute,
    vma_of,
)
from apex_tpu.ops._pallas_utils import on_tpu

__all__ = ["ring_attention"]


def _merge(o_a, lse_a, o_b, lse_b):
    """Stable combine of two partial attention results ([bh,s,d] f32 with
    per-row lse [bh,s])."""
    lse_max = jnp.maximum(lse_a, lse_b)
    ea = jnp.exp(lse_a - lse_max)
    eb = jnp.exp(lse_b - lse_max)
    lse = lse_max + jnp.log(ea + eb)
    wa = jnp.exp(lse_a - lse)[..., None]
    wb = jnp.exp(lse_b - lse)[..., None]
    return o_a * wa + o_b * wb, lse


def _chunk_mask(s, causal, s_local):
    """Validity predicate on padded [.., sp, sp] scores: real keys only,
    plus the intra-chunk causal triangle on the diagonal chunk."""
    rows, cols = s.shape[-2], s.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    pred = col < s_local
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        pred = pred & (col <= row)
    return pred


def _expand_groups(x3, gqa):
    """[b*g, s, d] → [b*n, s, d] matching the batch-major _to_bh layout
    (row b·n+h reads group h // rep) — reference-path analog of the
    kernels' grouped index maps."""
    if gqa is None:
        return x3
    n, g = gqa
    rep = n // g
    bg, s, d = x3.shape
    b = bg // g
    return jnp.repeat(x3.reshape(b, g, s, d), rep, axis=1).reshape(
        b * n, s, d)


def _reduce_groups(x3, gqa):
    """[b*n, s, d] gradient → [b*g, s, d] by summing each group's rep
    query-head contributions (the transpose of _expand_groups)."""
    if gqa is None:
        return x3
    n, g = gqa
    rep = n // g
    bn, s, d = x3.shape
    b = bn // n
    return x3.reshape(b, g, rep, s, d).sum(axis=2).reshape(b * g, s, d)


def _chunk_fwd_ref(q3, k3, v3, scale, causal, s_local):
    """Closed-form (o, lse) for one chunk — XLA path used off-TPU, where
    the Pallas interpreter cannot run under shard_map vma typing."""
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(jnp.float32),
                   k3.astype(jnp.float32)) * scale
    s = jnp.where(_chunk_mask(s, causal, s_local), s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(m > _NEG_INF / 2, jnp.exp(s - m), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bqk,bkd->bqd", e / safe_l, v3.astype(jnp.float32))
    lse = jnp.where(l[..., 0] == 0.0, _NEG_INF, m[..., 0] + jnp.log(
        safe_l[..., 0]))
    return o, lse


def _chunk_bwd_ref(q3, k3, v3, do3, lse, delta, scale, causal, s_local):
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(jnp.float32),
                   k3.astype(jnp.float32)) * scale
    p = jnp.where(_chunk_mask(s, causal, s_local),
                  jnp.exp(s - lse[..., None]), 0.0)
    do = do3.astype(jnp.float32)
    dv = jnp.einsum("bqk,bqd->bkd", p, do)
    dp = jnp.einsum("bqd,bkd->bqk", do, v3.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, k3.astype(jnp.float32))
    dk = jnp.einsum("bqk,bqd->bkd", ds, q3.astype(jnp.float32))
    return dq, dk, dv


def _chunk_fwd(q3, k3, v3, scale, causal_mode, s_local, block_q,
               block_k, gqa=None):
    """One (q-shard, kv-chunk) flash forward. causal_mode: 0 full,
    1 diagonal (causal), 2 skip.  ``gqa=(n, g)`` keeps the chunk at
    group width: the kernels broadcast via index maps, the reference
    path via an explicit expand."""
    use_pallas = on_tpu()

    def run(causal):
        if use_pallas:
            # f32 kernel outputs: chunk results feed the f32 lse merge /
            # traveling accumulators; rounding to bf16 per chunk would
            # compound error with ring size
            o, lse = _fwd_pallas(q3, k3, v3, None, None, None, scale,
                                 causal, s_local, block_q, block_k, 0.0,
                                 False, out_dtype=jnp.float32, gqa=gqa)
            return o, lse[:, :, 0]
        return _chunk_fwd_ref(q3, _expand_groups(k3, gqa),
                              _expand_groups(v3, gqa), scale, causal,
                              s_local)

    def skip(_):
        # match the full vma typing of the kernel branches
        return match_vma(
            (jnp.zeros(q3.shape, jnp.float32),
             jnp.full(q3.shape[:2], _NEG_INF, jnp.float32)),
            vma_of(q3))

    return jax.lax.switch(
        causal_mode, [lambda _: run(False), lambda _: run(True), skip],
        None)


def _chunk_bwd(q3, k3, v3, do3, lse, o3, scale, causal_mode, s_local,
               block_q, block_k, gqa=None):
    """``lse`` [bh, s] and ``o3`` are the ring's FINAL logsumexp and
    output: the kernels take the logsumexp across the head's lanes and
    make ``delta = rowsum(do * o)`` themselves."""
    use_pallas = on_tpu()

    def run(causal):
        if use_pallas:
            dq, dk, dv = _bwd_pallas(
                q3, k3, v3, do3,
                jnp.broadcast_to(lse[:, :, None], q3.shape), o3, None,
                None, None, scale, causal, s_local, s_local, block_q,
                block_k, 0.0, False, out_dtype=jnp.float32, gqa=gqa)
            return dq, dk, dv
        delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                        axis=-1)
        dq, dk, dv = _chunk_bwd_ref(
            q3, _expand_groups(k3, gqa), _expand_groups(v3, gqa), do3,
            lse, delta, scale, causal, s_local)
        return dq, _reduce_groups(dk, gqa), _reduce_groups(dv, gqa)

    def skip(_):
        return match_vma(
            (jnp.zeros(q3.shape, jnp.float32),
             jnp.zeros(k3.shape, jnp.float32),
             jnp.zeros(v3.shape, jnp.float32)), vma_of(q3))

    return jax.lax.switch(
        causal_mode, [lambda _: run(False), lambda _: run(True), skip],
        None)


def _ring_blocks(s_local):
    """One block size for q AND kv: the padded shard length (a block_q
    multiple) must divide the kernels' kv grid exactly, or trailing real
    keys would be silently dropped.

    Block choice minimizes padded work per ring step: cost ~ padded^2 /
    tile_throughput(b), with relative tile throughputs from the round-3
    v5e sweep (fwd s1024: 256-blocks 1494us, 512 1186us, 1024 946us;
    not measured on today's code).  A flat >=1024 cap would pad e.g.
    s_local=1280 to 2048 (2.56x the score elements) and lose more to
    padding than the bigger tile wins."""
    rel = {256: 1.0, 512: 1.26, 1024: 1.58}
    best, best_cost = None, None
    for b, thr in rel.items():
        padded = pl.cdiv(max(s_local, 1), b) * b
        cost = padded * padded / thr
        if best_cost is None or cost < best_cost:
            best, best_cost = b, cost
    b = min(best, pl.cdiv(s_local, 128) * 128)
    return b, b


def _ring_perm(axis_name):
    n = jax.lax.axis_size(axis_name)
    return [(i, (i + 1) % n) for i in range(n)]


def _mode(my, src, causal):
    """0 attend-all, 1 diagonal, 2 skip — chunk ``src`` vs q-shard ``my``."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(src == my, 1, jnp.where(src < my, 0, 2)).astype(
        jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring(q, k, v, axis_name, causal, scale):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return o


def _ring_fwd_impl(q, k, v, axis_name, causal, scale):
    b, s_local, n, d = q.shape
    gqa = (n, k.shape[2]) if k.shape[2] != n else None
    ndev = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    block_q, block_k = _ring_blocks(s_local)
    sp = (s_local + block_q - 1) // block_q * block_q
    perm = _ring_perm(axis_name)

    q3 = _pad_to(_to_bh(q), sp, 1)
    k3 = _pad_to(_to_bh(k), sp, 1)
    v3 = _pad_to(_to_bh(v), sp, 1)

    def step(t, carry):
        k_cur, v_cur, o_acc, lse_acc = carry
        src = (my - t) % ndev                 # global chunk id held now
        mode = _mode(my, src, causal)
        o_c, lse_c = _chunk_fwd(q3, k_cur, v_cur, scale, mode, s_local,
                                block_q, block_k, gqa=gqa)
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_c, lse_c)
        k_nxt = _ppermute(k_cur, axis_name, perm)
        v_nxt = _ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, o_acc, lse_acc

    o0, lse0 = match_vma(
        (jnp.zeros(q3.shape, jnp.float32),
         jnp.full(q3.shape[:2], _NEG_INF, jnp.float32)), vma_of(q3))
    _, _, o_acc, lse = jax.lax.fori_loop(
        0, ndev, step, (k3, v3, o0, lse0))

    o = _from_bh(o_acc.astype(q.dtype), b, n)[:, :s_local]
    return o, lse


def _ring_vjp_fwd(q, k, v, axis_name, causal, scale):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, causal, scale, res, do):
    q, k, v, o, lse = res
    b, s_local, n, d = q.shape
    gqa = (n, k.shape[2]) if k.shape[2] != n else None
    ndev = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    block_q, block_k = _ring_blocks(s_local)
    sp = (s_local + block_q - 1) // block_q * block_q
    perm = _ring_perm(axis_name)

    q3 = _pad_to(_to_bh(q), sp, 1)
    k3 = _pad_to(_to_bh(k), sp, 1)
    v3 = _pad_to(_to_bh(v), sp, 1)
    do3 = _pad_to(_to_bh(do), sp, 1)
    o3 = _pad_to(_to_bh(o), sp, 1)

    def step(t, carry):
        k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
        src = (my - t) % ndev
        mode = _mode(my, src, causal)
        dq_c, dk_c, dv_c = _chunk_bwd(
            q3, k_cur, v_cur, do3, lse, o3, scale, mode, s_local,
            block_q, block_k, gqa=gqa)
        dq_acc = dq_acc + dq_c
        dk_cur = dk_cur + dk_c
        dv_cur = dv_cur + dv_c
        # rotate kv and its traveling gradient accumulators together
        k_nxt = _ppermute(k_cur, axis_name, perm)
        v_nxt = _ppermute(v_cur, axis_name, perm)
        dk_nxt = _ppermute(dk_cur, axis_name, perm)
        dv_nxt = _ppermute(dv_cur, axis_name, perm)
        return k_nxt, v_nxt, dk_nxt, dv_nxt, dq_acc

    z3, zq = match_vma((jnp.zeros(k3.shape, jnp.float32),
                        jnp.zeros(q3.shape, jnp.float32)), vma_of(q3))
    _, _, dk3, dv3, dq3 = jax.lax.fori_loop(
        0, ndev, step, (k3, v3, z3, z3, zq))
    # after ndev rotations the accumulators are home again

    dq = _from_bh(dq3.astype(q.dtype), b, n)[:, :s_local]
    dk = _from_bh(dk3.astype(k.dtype), b, k.shape[2])[:, :s_local]
    dv = _from_bh(dv3.astype(v.dtype), b, v.shape[2])[:, :s_local]
    return dq, dk, dv


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Context-parallel attention over sequence-sharded [b, s_local, n, d]
    tensors. Must be called inside a ``jax.shard_map`` whose mesh has
    ``axis_name``; every device's shard length must be equal (global seq =
    s_local × axis size, q-shard i owning global positions
    [i·s_local, (i+1)·s_local)).

    Grouped K/V (``[b, s_local, g, d]`` with g dividing the query head
    count) ride the ring at group width: the rotating ppermute messages
    — the dominant ICI traffic of ring attention — shrink by n/g, and
    the chunk kernels broadcast groups via their GQA index maps.  dK/dV
    come back at group width.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [b, s_local, n, d], got {q.shape}")
    if k.shape != v.shape:
        raise ValueError("ring attention requires equal k/v shard shapes")
    if q.shape[:2] + q.shape[3:] != k.shape[:2] + k.shape[3:]:
        raise ValueError(
            f"q/k shard shapes differ beyond the head axis: {q.shape} "
            f"vs {k.shape}")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"query heads ({q.shape[2]}) must be a multiple of the K/V "
            f"group count ({k.shape[2]})")
    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else float(scale)
    return _ring(q, k, v, axis_name, causal, scale)
