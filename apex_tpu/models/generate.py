"""Autoregressive GPT inference: batched flash prefill + ragged decode.

Beyond the reference: apex is a training-acceleration library with no
generation runtime (its GPT exists for scaling tests,
standalone_gpt.py), but a complete framework needs the inference half of
the model family.  TPU-native design (ISSUE 3):

- **prefill/decode split** — :func:`prefill` runs the full-sequence
  training forward (the same ``ops/flash_attention.py`` causal kernel
  the train step uses) and writes the whole KV cache in ONE batched
  pass, so a 512-token prompt costs one forward instead of 512
  sequential decode steps; :func:`decode_step` then extends one token
  per call with dense attention over the cache (sq=1 never benefits
  from the flash kernel's tiling) with fp32 accumulation on the MXU;
- **ragged batching** — the cache position is a ``[b]`` int32 vector,
  so prompts of different lengths batch together left-aligned without
  padding every sequence to the longest: per-sequence attention masks,
  per-sequence rotary offsets (``ops.rope.fused_apply_rotary_pos_emb_
  ragged``) and per-sequence EOS done-flags; the outer decode is a
  ``lax.while_loop`` that exits when every sequence has finished
  instead of always scanning ``max_new_tokens``;
- static shapes throughout — the cache is pre-allocated at ``max_len``
  and masked by position, the one compiled decode body serves every
  step;
- **two cache layouts** (ISSUE 6) — ``cache_layout="contiguous"`` is
  the original per-sequence ``[b, max_len]`` stripe;
  ``cache_layout="paged"`` stores K/V in a global pool of fixed-size
  blocks addressed through per-sequence block tables
  (``serving/paged_cache.py``), with decode attention running the
  fused ragged-paged kernel (``ops/paged_attention.py``).  Both
  layouts decode token-identically (tests/test_generate_paged.py);
  the paged one is what lets the serving engine commit HBM per
  allocated block instead of per ``max_slots × max_len``;
- parameters are the exact training pytree (init_gpt_params /
  tools/import_hf.py), so a trained or imported model generates without
  conversion; numerics follow transformer_lm.py layer-for-layer
  (pre-LN or the post-LN-residual flag, gelu/gelu_tanh/swiglu FFNs,
  learned or rope positions, MHA or grouped-query K/V).

Teacher-forcing parity with ``gpt_forward`` is tested to float
tolerance and prefill-vs-stepwise cache equivalence is pinned exactly
(tests/test_generate.py).  The slot-based continuous-batching engine in
``apex_tpu/serving`` builds on these three primitives.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.transformer_lm import (
    apply_norm, lm_head_weight, rope_cos_sin)
from apex_tpu.observability import metrics as _telemetry
from apex_tpu.ops.fused_sampling import fused_sample

__all__ = ["init_kv_cache", "decode_step", "decode_verify", "prefill",
           "prefill_chunked", "generate", "sample_logits", "extract_kv",
           "inject_kv"]


DEFAULT_BLOCK_SIZE = 16


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  cache_dtype=None, *, cache_layout: str = "contiguous",
                  block_size: int = DEFAULT_BLOCK_SIZE,
                  cache_wire=None):
    """KV cache for ``batch`` sequences of up to ``max_len`` tokens.

    ``cache_layout="contiguous"`` (default): ``[L, b, max_len,
    kv_groups, dh]`` k/v buffers + ``[b]`` positions — every sequence
    owns a max-length stripe.

    ``cache_layout="paged"``: a global block pool ``[L, num_blocks,
    block_size, kv_groups, dh]`` plus per-sequence ``block_tables``
    ``[b, ceil(max_len/block_size)]``.  Here the tables are filled
    linearly (sequence ``i`` owns blocks ``[i·mb, (i+1)·mb)``) — the
    static one-shot form :func:`generate` uses; the serving engine
    allocates tables dynamically through
    :class:`~apex_tpu.serving.paged_cache.BlockManager` instead, which
    is where the pool layout actually pays (HBM per allocated block,
    prefix sharing, preemption).

    Under GQA the cache holds only the group heads — the persistent
    per-token memory shrinks by num_attention_heads/num_query_groups
    (the principal GQA/MQA serving win, arXiv:2305.13245).

    ``cache_dtype`` overrides the buffer dtype (default
    ``cfg.compute_dtype``) so a serving deployment can hold bf16 caches
    under an fp32 compute config — decode casts at the attention einsum
    as it already does for the compute dtype.

    ``pos`` is per-sequence: sequence ``i``'s next token lands at
    ``pos[i]`` and its attention sees ``t <= pos[i]``, which is what
    lets ragged prompts share one batch.

    ``cache_wire="int8"`` (ISSUE 14, paged layout only) stores the
    pool at rest as block-scaled int8 — K/V quantize per (token, kv
    group) at every write edge and the paged-attention kernel
    dequantizes in-VMEM; the dict carries the parallel
    ``k_scale``/``v_scale`` pools.  ~0.53x a bf16 pool's resident
    bytes (``1 + 4/dh`` bytes/element).
    """
    dt = cfg.compute_dtype if cache_dtype is None else cache_dtype
    nh = cfg.kv_groups
    dh = cfg.kv_channels
    if cache_layout == "contiguous":
        if cache_wire not in (None, "native"):
            raise ValueError(
                f"cache_wire={cache_wire!r} is a paged-pool form; the "
                "contiguous stripe layout stores the cache dtype only")
        shape = (cfg.num_layers, batch, max_len, nh, dh)
        return {
            "k": jnp.zeros(shape, dt),
            "v": jnp.zeros(shape, dt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if cache_layout != "paged":
        raise ValueError(
            f"cache_layout={cache_layout!r}: expected 'contiguous' or "
            "'paged'")
    from apex_tpu.serving.paged_cache import blocks_for, init_paged_pool

    mb = blocks_for(max_len, block_size)
    pool = init_paged_pool(cfg, batch * mb, block_size,
                           cache_dtype=cache_dtype,
                           cache_wire=cache_wire)
    tables = (jnp.arange(batch, dtype=jnp.int32)[:, None] * mb
              + jnp.arange(mb, dtype=jnp.int32)[None])
    pool["pos"] = jnp.zeros((batch,), jnp.int32)
    pool["block_tables"] = tables
    return pool


def extract_kv(cache: dict, length: int, *, row: int = 0):
    """Pull sequence ``row``'s first ``length`` tokens of K/V out of a
    cache in EITHER layout → ``(k, v)`` of shape
    ``[L, length, kv_groups, dh]`` (device arrays; ``np.asarray`` them
    to cross a process boundary).

    This is the model-path half of the cluster KV handoff (ISSUE 9): a
    prefill worker extracts the freshly written prompt K/V and ships it
    to a decode pool.  Paged caches dereference the row's block table
    (only the blocks the table names are touched — token order, not
    pool order); contiguous caches slice the row's stripe.  Exactly
    inverted by :func:`inject_kv` on any cache with room:
    ``inject_kv(dst, *extract_kv(src, n))`` leaves ``dst`` decoding
    token-identically to ``src`` (tests/test_serving_handoff.py pins it
    across layout pairs)."""
    if length < 1:
        raise ValueError(f"length={length} must be >= 1")
    if "block_tables" in cache:
        from apex_tpu.serving.paged_cache import (
            blocks_for, dequantize_kv, gather_block_kv)

        bs = cache["k"].shape[2]
        tables = cache["block_tables"]
        need = blocks_for(int(length), bs)
        if need > tables.shape[1]:
            raise ValueError(
                f"length {length} needs {need} blocks but the table "
                f"holds {tables.shape[1]}")
        ids = np.asarray(tables)[row, :need]
        nb = cache["k"].shape[1]
        if (ids >= nb).any() or (ids < 0).any():
            # an unmapped sentinel inside the requested range means
            # `length` exceeds the row's materialized tokens — the
            # gather would CLAMP onto a real pool block and silently
            # ship another request's pages over the wire
            raise ValueError(
                f"length {length} reaches unmapped table entries for "
                f"row {row} (sentinel >= {nb}); it exceeds the row's "
                "materialized tokens")
        k, v = gather_block_kv(cache["k"], cache["v"], ids)
        if "k_scale" in cache:
            # int8 pool: the handoff contract ships FLOAT per-token K/V
            # (the wire layer owns its own quantization); dequantize
            # through the gathered scales — fp32, since the at-rest
            # quantization already spent the precision budget
            idj = jnp.asarray(ids, jnp.int32)
            L, g = cache["k"].shape[0], cache["k"].shape[3]
            sk = jnp.take(cache["k_scale"], idj, axis=1).reshape(
                L, need * bs, g)
            sv = jnp.take(cache["v_scale"], idj, axis=1).reshape(
                L, need * bs, g)
            k = dequantize_kv(k, sk)
            v = dequantize_kv(v, sv)
        return k[:, :length], v[:, :length]
    if length > cache["k"].shape[2]:
        raise ValueError(
            f"length {length} exceeds the cache max_len "
            f"{cache['k'].shape[2]}")
    return cache["k"][:, row, :length], cache["v"][:, row, :length]


def inject_kv(cache: dict, k, v, *, row: int = 0) -> dict:
    """Write per-token K/V ``[L, n, kv_groups, dh]`` into positions
    ``[0, n)`` of sequence ``row`` and set ``pos[row] = n`` — the
    decode-side half of the cluster KV handoff (inverse of
    :func:`extract_kv`).  Paged caches scatter each token through the
    row's block table (cells ``(tables[row, t//bs], t % bs)``; unmapped
    sentinel entries drop, so a short table cannot be corrupted);
    contiguous caches overwrite the row's stripe head.  The arrays are
    cast to the cache dtype — a raw-wire handoff between same-dtype
    caches is bit-exact."""
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    if k.ndim != 4 or k.shape != v.shape:
        raise ValueError(
            f"expected matching [L, n, g, dh] K/V, got {k.shape} / "
            f"{v.shape}")
    n = k.shape[1]
    if "block_tables" in cache:
        from apex_tpu.serving.paged_cache import blocks_for

        tables = cache["block_tables"].astype(jnp.int32)
        nb, bs = cache["k"].shape[1], cache["k"].shape[2]
        mb = tables.shape[1]
        need = blocks_for(int(n), bs)
        if need > mb:
            raise ValueError(
                f"{n} handoff tokens need {need} blocks but the "
                f"table holds {mb}")
        ids = np.asarray(cache["block_tables"])[row, :need]
        if (ids >= nb).any() or (ids < 0).any():
            # scattering through an unmapped sentinel would DROP the
            # write while pos still claims the token — the cache
            # would silently attend over stale pool data
            raise ValueError(
                f"{n} handoff tokens reach unmapped table entries "
                f"for row {row} (sentinel >= {nb}); map blocks for "
                "the full range before injecting")
        t = jnp.arange(n)
        blk = tables[row, jnp.minimum(t // bs, mb - 1)]
        blk = jnp.where(t < mb * bs, blk, nb)
        off = t % bs
        if "k_scale" in cache:
            # int8 pool: quantize the float handoff at the write edge
            # (the shared scatter keeps wire + scale cells paired)
            from apex_tpu.serving.paged_cache import scatter_kv_quantized

            ck, cv, sk, sv = scatter_kv_quantized(
                cache["k"], cache["v"], cache["k_scale"],
                cache["v_scale"], k, v, (slice(None), blk, off))
            return {
                "k": ck, "v": cv, "k_scale": sk, "v_scale": sv,
                "pos": cache["pos"].at[row].set(n),
                "block_tables": cache["block_tables"],
            }
        return {
            "k": cache["k"].at[:, blk, off].set(
                k.astype(cache["k"].dtype), mode="drop"),
            "v": cache["v"].at[:, blk, off].set(
                v.astype(cache["v"].dtype), mode="drop"),
            "pos": cache["pos"].at[row].set(n),
            "block_tables": cache["block_tables"],
        }
    if n > cache["k"].shape[2]:
        raise ValueError(
            f"{n} handoff tokens exceed the cache max_len "
            f"{cache['k'].shape[2]}")
    return {
        "k": cache["k"].at[:, row, :n].set(k.astype(cache["k"].dtype)),
        "v": cache["v"].at[:, row, :n].set(v.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[row].set(n),
    }


def _check_sampling_args(temperature: float,
                         top_k: Optional[int]) -> None:
    """Shared static-argument guard for sample_logits / generate."""
    if temperature < 0:
        raise ValueError(
            f"temperature={temperature}: negative temperatures would "
            "silently invert the distribution (prefer the *least* "
            "likely tokens); pass 0 for greedy or a positive value")
    if top_k is not None and top_k < 1:
        raise ValueError(
            f"top_k={top_k}: pass None (not 0) to disable the cutoff — "
            "a zero-width cutoff would silently break the nucleus mask")


def _check_decode_cfg(cfg: TransformerConfig) -> None:
    """Shared config guard for every cached-inference entry point."""
    if cfg.num_experts:
        raise ValueError(
            "KV-cache decoding does not support MoE configs yet")
    if cfg.attn_mask_type != "causal":
        raise ValueError(
            "KV-cache decoding is causal by construction; "
            f"attn_mask_type={cfg.attn_mask_type!r} would silently "
            "decode with the wrong mask")


def _vector_pos(cache: dict) -> jax.Array:
    """The ``[b]`` int32 cache position.  The pre-PR-3 scalar-counter
    broadcast form is gone — everything in-tree has written vector
    positions since the ragged-decode rework, so a scalar here is a
    stale caller bug, not a layout to silently paper over."""
    pos = cache["pos"]
    if pos.ndim != 1:
        raise ValueError(
            f"cache['pos'] must be a [b] int32 vector, got shape "
            f"{pos.shape}; the legacy scalar-counter broadcast path "
            "was removed (PR 6) — build caches with init_kv_cache")
    return pos.astype(jnp.int32)


def _lora_operands(lora, m: int = 1):
    """Resolve the optional per-request LoRA bundle (``{"idx": [b] int32
    slot ids, "slabs": {target: {"a": [L, G, in, r], "b": [L, G, r,
    out]}}}``, ISSUE 20) into forward operands: the slab pytree (leading
    layer axis — scanned beside the base layer stack) and the sort plan
    over the forward's ``b * m`` rows.  A verify block's token (i, j)
    flattens row-major, so each sequence's slot id repeats m ways.  Both
    the ids and the plan are traced — one compiled step serves every
    adapter mix."""
    if lora is None:
        return None, None
    from apex_tpu.models.lora import lora_plan

    slabs = lora["slabs"]
    n_slots = next(iter(slabs.values()))["a"].shape[1]
    idx = lora["idx"].astype(jnp.int32)
    if m > 1:
        idx = jnp.repeat(idx, m)
    return slabs, lora_plan(idx, n_slots)


def _decode_qkv(cfg, lp, x, pos, rope, rope_q: bool = True, ll=None,
                plan=None):
    """Shared pre-attention math (norm → qkv projection → GQA split →
    per-sequence rotary) for ``x`` [b, s, h] appended at per-sequence
    offsets ``pos`` [b] — token (i, j) sits at absolute position
    ``pos[i] + j`` (s=1 is the decode step, s=k+1 the speculative
    verify block): the contiguous and paged layer bodies differ only in
    where K/V land and how the cache is read, so this is ONE
    implementation of everything before that fork.

    ``rope_q=False`` returns the query PRE-rope (K still ropes for the
    cache write) — the fused decode layer (``ops/decode_step.py``)
    applies the query rotation in-kernel."""
    from apex_tpu.ops.dense import quantized_matmul

    b, s = x.shape[0], x.shape[1]
    nh = cfg.num_attention_heads
    dh = cfg.kv_channels
    h = apply_norm(cfg, x, lp["ln1_scale"], lp["ln1_bias"])
    # quantized_matmul: the plain array path is byte-identical to the
    # historical `h @ kernel.astype(...)`; an int8 weight-slab leaf
    # (models/quantized.quantize_params, ISSUE 14) runs the in-kernel
    # dequantizing matmul so decode reads int8 weight bytes
    qkv = quantized_matmul(h, lp["qkv_kernel"]) + lp["qkv_bias"].astype(
        x.dtype)
    if ll is not None and "qkv" in ll:
        from apex_tpu.models.lora import batched_lora_delta

        qkv = qkv + batched_lora_delta(h, ll["qkv"]["a"],
                                       ll["qkv"]["b"], plan)
    if cfg.is_gqa:
        from apex_tpu.models.transformer_lm import split_qkv_gqa
        q, k, v = split_qkv_gqa(cfg, qkv, b, s, nh)
    else:
        qkv = qkv.reshape(b, s, nh, 3 * dh)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    if rope is not None:
        cos, sin = rope          # [max_len, d]
        from apex_tpu.ops.rope import fused_apply_rotary_pos_emb_ragged

        if rope_q:
            q = fused_apply_rotary_pos_emb_ragged(q, cos, sin, pos)
        k = fused_apply_rotary_pos_emb_ragged(k, cos, sin, pos)
    return h, q, k, v


def _decode_rope_rows(rope, pos):
    """Gather each sequence's angle-table row for its decode position
    (clamped like ``fused_apply_rotary_pos_emb_ragged``) → f32
    ``(cos, sin)`` of ``[b, d]`` — the per-sequence rope operand of the
    fused decode layer."""
    if rope is None:
        return None, None
    cos, sin = rope
    rows = jnp.clip(pos, 0, cos.shape[0] - 1)
    return (jnp.take(cos.astype(jnp.float32), rows, axis=0),
            jnp.take(sin.astype(jnp.float32), rows, axis=0))


def _decode_out_post(cfg, lp, x, h, a, ll=None, plan=None):
    """Post-projection tail (bias → residual → MLP) shared by the
    unfused path and the fused decode layer, whose kernel already owns
    the projection GEMM; ``a`` [b, s, h_model] is the projected
    attention output, bias not yet applied."""
    a = a + lp["proj_bias"].astype(x.dtype)
    res = h if cfg.apply_residual_connection_post_layernorm else x
    x = res + a
    h = apply_norm(cfg, x, lp["ln2_scale"], lp["ln2_bias"])
    from apex_tpu.models.transformer_lm import _mlp, single_device_ctx

    if ll is not None and ("fc1" in ll or "fc2" in ll):
        from apex_tpu.models.lora import lora_mlp

        m = lora_mlp(cfg, lp, h, ll, plan)
    else:
        m = _mlp(cfg, lp, h, single_device_ctx())
    res = h if cfg.apply_residual_connection_post_layernorm else x
    return res + m


def _decode_out(cfg, lp, x, h, ctx_flat, ll=None, plan=None):
    """Shared post-attention math (output projection → residual →
    MLP); ``ctx_flat`` [b, s, nh*dh] (s=1 decode, s=k+1 verify)."""
    from apex_tpu.ops.dense import quantized_matmul

    a = quantized_matmul(ctx_flat, lp["proj_kernel"])
    if ll is not None and "proj" in ll:
        from apex_tpu.models.lora import batched_lora_delta

        a = a + batched_lora_delta(ctx_flat, ll["proj"]["a"],
                                   ll["proj"]["b"], plan)
    return _decode_out_post(cfg, lp, x, h, a, ll=ll, plan=plan)


def _stripe_block(total: int) -> int:
    """Largest block size <= 128 dividing a contiguous stripe length
    (preferring a sublane multiple) — lets the fused decode kernel view
    the ``[b, T, g, dh]`` stripe as a linear ``[b·(T/bs), bs, g, dh]``
    pool without copying a byte."""
    cands = [d for d in range(1, min(total, 128) + 1) if total % d == 0]
    mult8 = [d for d in cands if d % 8 == 0]
    return max(mult8 or cands)


def _layer_decode(cfg, lp, x, cache_k, cache_v, pos, rope,
                  decode_fused: str = "reference", ll=None, plan=None):
    """One layer, one token, contiguous layout: x [b, 1, h] + cache
    slice [b, T, nh, dh]; ``pos`` [b] int32 — each sequence writes and
    attends at its own offset.

    ``decode_fused="kernel"`` runs rope + attention + output projection
    as ONE fused kernel (``ops/decode_step.py``) over the stripe viewed
    as a linear block pool; ``"reference"`` keeps the historical inline
    dense math below bit-for-bit."""
    from apex_tpu.ops.dense import is_quantized

    b = x.shape[0]
    nh = cfg.num_attention_heads
    dh = cfg.kv_channels
    # quantized projection slabs stay on the unfused path — their
    # in-kernel dequantizing matmul (ops/dense) owns the weight tiling;
    # LoRA lanes likewise — the fused kernel owns the projection GEMM,
    # and the per-row delta must land on its output
    fuse = (decode_fused == "kernel" and ll is None
            and not is_quantized(lp["proj_kernel"]))
    h, q, k, v = _decode_qkv(cfg, lp, x, pos, rope, rope_q=not fuse,
                             ll=ll, plan=plan)

    # per-sequence scatter: row (i, pos[i]) only — O(b·nh·dh) written
    # per step, not a full-buffer select; out-of-bounds positions
    # (finished rows parked past the cache) drop, matching the masked
    # semantics below
    b_idx = jnp.arange(b)
    cache_k = cache_k.at[b_idx, pos].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[b_idx, pos].set(v[:, 0].astype(cache_v.dtype))
    if fuse:
        from apex_tpu.ops.decode_step import fused_decode_layer

        T = cache_k.shape[1]
        g = cfg.kv_groups
        bs = _stripe_block(T)
        nbl = T // bs
        tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * nbl
                  + jnp.arange(nbl, dtype=jnp.int32)[None])
        rope_cos, rope_sin = _decode_rope_rows(rope, pos)
        a = fused_decode_layer(
            q[:, 0], cache_k.reshape(b * nbl, bs, g, dh),
            cache_v.reshape(b * nbl, bs, g, dh), tables, pos + 1,
            lp["proj_kernel"], rope_cos=rope_cos, rope_sin=rope_sin,
            backend="kernel")
        return (_decode_out_post(cfg, lp, x, h, a[:, None]),
                cache_k, cache_v)
    t_idx = jnp.arange(cache_k.shape[1])

    # dense attention over the (masked) cache; under GQA the query
    # heads fold as [groups, rep] against the group-width cache — no
    # repeated K/V is ever materialized
    scale = 1.0 / dh ** 0.5
    g = cfg.kv_groups
    rep = nh // g
    qg = q.reshape(b, 1, g, rep, dh)
    s = jnp.einsum("bqgrd,btgd->bgrqt", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    live = (t_idx[None] <= pos[:, None])[:, None, None, None, :]
    s = jnp.where(live, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ctxv = jnp.einsum("bgrqt,btgd->bqgrd", p.astype(cache_v.dtype),
                      cache_v,
                      preferred_element_type=jnp.float32).astype(x.dtype)
    x = _decode_out(cfg, lp, x, h, ctxv.reshape(b, 1, nh * dh),
                    ll=ll, plan=plan)
    return x, cache_k, cache_v


def _layer_decode_paged(cfg, lp, x, cache_k, cache_v, tables, pos, rope,
                        k_scale=None, v_scale=None,
                        decode_fused: str = "reference", ll=None,
                        plan=None):
    """One layer, one token, paged layout: x [b, 1, h] + this layer's
    block pool [num_blocks, block_size, g, dh] + ``tables``
    [b, max_blocks].  The new K/V append to each sequence's tail block
    (one-cell scatter through the table); attention runs through the
    fused decode layer (``ops/decode_step.py``) — ``decode_fused=
    "kernel"`` is rope + paged attention + output projection as ONE
    kernel with one VMEM residency, ``"reference"`` the exact
    historical op sequence (ragged-paged kernel + XLA matmul); either
    way the gathered cache never materializes.

    int8 pool (``k_scale``/``v_scale`` given, ISSUE 14): the append
    quantizes the fresh token per (sequence, group) and scatters wire +
    scale through the same table cell; the attention kernel dequantizes
    in-VMEM (scales ride the table-dereferenced DMA)."""
    from apex_tpu.ops.dense import is_quantized
    from apex_tpu.ops.paged_attention import ragged_paged_attention

    b = x.shape[0]
    nh = cfg.num_attention_heads
    dh = cfg.kv_channels
    # quantized projection slabs stay on the unfused path — their
    # in-kernel dequantizing matmul (ops/dense) owns the weight tiling;
    # LoRA lanes likewise (the fused kernel owns the projection GEMM)
    fuse = ll is None and not is_quantized(lp["proj_kernel"])
    h, q, k, v = _decode_qkv(cfg, lp, x, pos, rope, rope_q=not fuse,
                             ll=ll, plan=plan)

    nb, bs = cache_k.shape[0], cache_k.shape[1]
    mb = tables.shape[1]
    # tail-block append: cell (tables[i, pos//bs], pos % bs).  Unmapped
    # table entries (>= nb: released serving lanes, short tables) and
    # positions past the table's reach drop — a lane can never write
    # into a block it does not own.
    blk = jnp.take_along_axis(
        tables, jnp.minimum(pos // bs, mb - 1)[:, None], axis=1)[:, 0]
    blk = jnp.where(pos < mb * bs, blk, nb)
    off = pos % bs
    if k_scale is not None:
        from apex_tpu.serving.paged_cache import scatter_kv_quantized

        cache_k, cache_v, k_scale, v_scale = scatter_kv_quantized(
            cache_k, cache_v, k_scale, v_scale, k[:, 0], v[:, 0],
            (blk, off))
    else:
        cache_k = cache_k.at[blk, off].set(
            k[:, 0].astype(cache_k.dtype), mode="drop")
        cache_v = cache_v.at[blk, off].set(
            v[:, 0].astype(cache_v.dtype), mode="drop")

    if fuse:
        from apex_tpu.ops.decode_step import fused_decode_layer

        rope_cos, rope_sin = _decode_rope_rows(rope, pos)
        a = fused_decode_layer(
            q[:, 0], cache_k, cache_v, tables, pos + 1,
            lp["proj_kernel"], rope_cos=rope_cos, rope_sin=rope_sin,
            backend=decode_fused, k_scale=k_scale, v_scale=v_scale)
        x = _decode_out_post(cfg, lp, x, h, a[:, None])
        return x, cache_k, cache_v, k_scale, v_scale
    ctx = ragged_paged_attention(q[:, 0], cache_k, cache_v, tables,
                                 pos + 1, k_scale=k_scale,
                                 v_scale=v_scale)
    x = _decode_out(cfg, lp, x, h,
                    ctx.astype(x.dtype).reshape(b, 1, nh * dh),
                    ll=ll, plan=plan)
    return x, cache_k, cache_v, k_scale, v_scale


def decode_step(params: dict, token: jax.Array, cache: dict,
                cfg: TransformerConfig, *,
                decode_fused: Optional[str] = None, lora=None):
    """One decoding step: token [b] int32 at per-sequence position
    ``cache['pos']`` ([b] int32) → (logits [b, v], updated cache).

    ``lora`` (ISSUE 20): ``{"idx": [b] int32 slot ids, "slabs":
    stacked adapter factors}`` — per-row low-rank deltas added at each
    target matmul via the ragged grouped-matmul path
    (``models/lora.py``); slot 0 rows are computed delta-free.  LoRA
    lanes run the unfused reference attention route (the fused kernel
    owns the projection GEMM the delta must land on).

    The cache dict selects the layout: a ``block_tables`` entry means
    paged (pool ``[L, num_blocks, block_size, g, dh]``, tail-block
    append + the fused ragged-paged attention kernel); otherwise the
    contiguous ``[L, b, max_len, g, dh]`` stripe layout.

    ``decode_fused`` picks the fused decode-layer route
    (``ops/decode_step.py``: rope + attention + output projection in
    one kernel): ``"kernel"``/``"reference"`` pin, ``None``/``"auto"``
    resolve here and now (kernel on TPU or in interpret mode) — jitted
    callers (``generate``, the serving engine) resolve the route ONCE
    outside their jit and pass it as a static argument, so that a trace
    made in interpret mode is not replayed outside it."""
    from apex_tpu.ops.decode_step import route_decode_fused

    _check_decode_cfg(cfg)
    decode_fused = route_decode_fused(decode_fused)
    cd = cfg.compute_dtype
    paged = "block_tables" in cache
    pos = _vector_pos(cache)
    x = jnp.take(params["embedding"]["word"].astype(cd), token,
                 axis=0)[:, None]
    if cfg.position_embedding_type == "learned":
        pe = jnp.take(params["embedding"]["position"], pos, axis=0)
        x = x + pe.astype(cd)[:, None]
    rope = None
    if cfg.position_embedding_type == "rope":
        if paged:
            max_pos = cache["block_tables"].shape[1] * cache["k"].shape[2]
        else:
            max_pos = cache["k"].shape[2]
        rope = rope_cos_sin(max_pos, cfg.kv_channels, cfg.rope_theta)

    # one compiled layer body scanned over the stacked layer params
    # (transformer_backbone's shape — compile time constant in depth).
    # LoRA slabs ride the scan xs beside the base layers (None — an
    # empty pytree — when absent, so the no-adapter trace is unchanged)
    slabs, plan = _lora_operands(lora)
    quant = "k_scale" in cache
    new_scales = None
    if paged and quant:
        tables = cache["block_tables"].astype(jnp.int32)

        def body(x, layer_in):
            lp, ck, cv, sk, sv, ll = layer_in
            x, ck, cv, sk, sv = _layer_decode_paged(
                cfg, lp, x, ck, cv, tables, pos, rope, sk, sv,
                decode_fused=decode_fused, ll=ll, plan=plan)
            return x, (ck, cv, sk, sv)

        x, (new_k, new_v, *new_scales) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"], slabs))
    elif paged:
        tables = cache["block_tables"].astype(jnp.int32)

        def body(x, layer_in):
            lp, ck, cv, ll = layer_in
            x, ck, cv, _sk, _sv = _layer_decode_paged(
                cfg, lp, x, ck, cv, tables, pos, rope,
                decode_fused=decode_fused, ll=ll, plan=plan)
            return x, (ck, cv)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], slabs))
    else:
        def body(x, layer_in):
            lp, ck, cv, ll = layer_in
            x, ck, cv = _layer_decode(cfg, lp, x, ck, cv, pos, rope,
                                      decode_fused=decode_fused,
                                      ll=ll, plan=plan)
            return x, (ck, cv)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], slabs))

    x = apply_norm(cfg, x, params["final_ln"]["scale"],
                   params["final_ln"]["bias"])
    logits = jnp.einsum(
        "bsh,vh->bsv", x, lm_head_weight(params, cfg).astype(cd),
        preferred_element_type=jnp.float32)[:, 0]
    cache = {"k": new_k, "v": new_v, "pos": pos + 1}
    if new_scales is not None:
        cache["k_scale"], cache["v_scale"] = new_scales
    if paged:
        cache["block_tables"] = tables
    return logits, cache


def _verify_attention(cfg, x, h, lp, q, kk, vv, pos, ll=None, plan=None):
    """Dense masked attention of ``m`` appended query tokens over a
    gathered/contiguous cache view ``kk``/``vv`` [b, T, g, dh]: query
    ``j`` of sequence ``i`` sees positions ``t <= pos[i] + j`` — the
    causal pattern of a verification block (each drafted token attends
    to the cache prefix plus the drafts before it)."""
    b, m = q.shape[0], q.shape[1]
    nh = cfg.num_attention_heads
    dh = cfg.kv_channels
    g = cfg.kv_groups
    rep = nh // g
    scale = 1.0 / dh ** 0.5
    qg = q.reshape(b, m, g, rep, dh)
    s = jnp.einsum("bqgrd,btgd->bgrqt", qg, kk,
                   preferred_element_type=jnp.float32) * scale
    t_idx = jnp.arange(kk.shape[1])
    qpos = pos[:, None] + jnp.arange(m, dtype=jnp.int32)[None]  # [b, m]
    live = (t_idx[None, None] <= qpos[:, :, None])[:, None, None]
    s = jnp.where(live, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ctxv = jnp.einsum("bgrqt,btgd->bqgrd", p.astype(vv.dtype), vv,
                      preferred_element_type=jnp.float32).astype(x.dtype)
    return _decode_out(cfg, lp, x, h, ctxv.reshape(b, m, nh * dh),
                       ll=ll, plan=plan)


def _layer_verify(cfg, lp, x, cache_k, cache_v, pos, rope, ll=None,
                  plan=None):
    """One layer, ``m`` appended tokens, contiguous layout: x [b, m, h]
    + cache slice [b, T, nh, dh]; writes land at rows
    ``(i, pos[i]+j)`` (out-of-bounds writes drop — rejected tails past
    the stripe are rolled back by the caller's position decrement)."""
    b, m = x.shape[0], x.shape[1]
    h, q, k, v = _decode_qkv(cfg, lp, x, pos, rope, ll=ll, plan=plan)
    b_idx = jnp.arange(b)[:, None]
    wpos = pos[:, None] + jnp.arange(m, dtype=jnp.int32)[None]
    cache_k = cache_k.at[b_idx, wpos].set(
        k.astype(cache_k.dtype), mode="drop")
    cache_v = cache_v.at[b_idx, wpos].set(
        v.astype(cache_v.dtype), mode="drop")
    x = _verify_attention(cfg, x, h, lp, q, cache_k, cache_v, pos,
                          ll=ll, plan=plan)
    return x, cache_k, cache_v


def _layer_verify_paged(cfg, lp, x, cache_k, cache_v, tables, pos, rope,
                        k_scale=None, v_scale=None, ll=None, plan=None):
    """One layer, ``m`` appended tokens, paged layout: the new K/V
    scatter through the block tables (cells ``(tables[i, p//bs],
    p % bs)``, unmapped entries drop), then attention runs over the
    gathered block view.  Unlike the sq=1 decode step this
    materializes the gather — a verification block amortizes the one
    gather over its m tokens, which is exactly the batched-prefill
    economics speculative decoding exists to exploit.  int8 pool: the
    drafted K/V quantize at the write edge and the gathered view
    dequantizes through the gathered scales; rejected drafts roll back
    by the caller's pos decrement exactly as in the native pool (their
    wire cells and scale cells are overwritten together by the next
    append)."""
    b, m = x.shape[0], x.shape[1]
    h, q, k, v = _decode_qkv(cfg, lp, x, pos, rope, ll=ll, plan=plan)
    nb, bs = cache_k.shape[0], cache_k.shape[1]
    mb = tables.shape[1]
    wpos = pos[:, None] + jnp.arange(m, dtype=jnp.int32)[None]  # [b, m]
    blk = jnp.take_along_axis(
        tables, jnp.clip(wpos // bs, 0, mb - 1), axis=1)
    blk = jnp.where(wpos < mb * bs, blk, nb)
    off = wpos % bs
    if k_scale is not None:
        from apex_tpu.serving.paged_cache import scatter_kv_quantized

        cache_k, cache_v, k_scale, v_scale = scatter_kv_quantized(
            cache_k, cache_v, k_scale, v_scale, k, v, (blk, off))
    else:
        cache_k = cache_k.at[blk, off].set(
            k.astype(cache_k.dtype), mode="drop")
        cache_v = cache_v.at[blk, off].set(
            v.astype(cache_v.dtype), mode="drop")
    tbl = jnp.minimum(tables, nb - 1)
    kk = cache_k[tbl].reshape(b, mb * bs, cache_k.shape[2],
                              cache_k.shape[3])
    vv = cache_v[tbl].reshape(b, mb * bs, cache_v.shape[2],
                              cache_v.shape[3])
    if k_scale is not None:
        from apex_tpu.serving.paged_cache import dequantize_kv

        kk = dequantize_kv(kk, k_scale[tbl].reshape(b, mb * bs, -1))
        vv = dequantize_kv(vv, v_scale[tbl].reshape(b, mb * bs, -1))
    x = _verify_attention(cfg, x, h, lp, q, kk, vv, pos, ll=ll,
                          plan=plan)
    return x, cache_k, cache_v, k_scale, v_scale


def decode_verify(params: dict, tokens: jax.Array, cache: dict,
                  cfg: TransformerConfig, *, lora=None):
    """Verification forward: ``m`` tokens per sequence in ONE batched
    pass → (logits [b, m, v], cache with ``pos`` advanced by m).

    ``lora`` (ISSUE 20): same bundle as ``decode_step`` — each
    sequence's slot id applies to all m of its rows, so a LoRA-serving
    engine's spec-verify (and its verify-based adapter prefill) runs
    the same per-row deltas as its decode steps.

    ``tokens`` [b, m] append at each sequence's ``cache['pos']``; token
    (i, j) lands at absolute position ``pos[i]+j``, attends to the
    cache prefix plus the tokens before it in the block, and its
    logits row predicts position ``pos[i]+j+1`` — feeding the gold
    sequence through this must reproduce ``decode_step`` run m times
    (tests/test_speculative.py pins it).

    This is speculative decoding's verify half (``models/
    speculative.py``): k drafted tokens cost one forward instead of k
    sequential decode steps, the per-step weight read amortized m ways
    — the batched-prefill economics of PR 3 applied to decode.
    Rollback of rejected tokens is the caller decrementing ``pos``:
    in BOTH layouts the rejected K/V entries become invisible (masks
    read ``t <= pos``) and are overwritten in place by the next
    append — no copy, and in the paged layout not even a block
    operation (the tail block simply has fewer live cells)."""
    _check_decode_cfg(cfg)
    cd = cfg.compute_dtype
    paged = "block_tables" in cache
    pos = _vector_pos(cache)
    b, m = tokens.shape
    x = jnp.take(params["embedding"]["word"].astype(cd), tokens, axis=0)
    if cfg.position_embedding_type == "learned":
        rows = jnp.clip(pos[:, None] + jnp.arange(m, dtype=jnp.int32),
                        0, cfg.max_position_embeddings - 1)
        pe = jnp.take(params["embedding"]["position"], rows, axis=0)
        x = x + pe.astype(cd)
    rope = None
    if cfg.position_embedding_type == "rope":
        if paged:
            max_pos = cache["block_tables"].shape[1] * cache["k"].shape[2]
        else:
            max_pos = cache["k"].shape[2]
        rope = rope_cos_sin(max_pos, cfg.kv_channels, cfg.rope_theta)

    slabs, plan = _lora_operands(lora, m=m)
    quant = "k_scale" in cache
    new_scales = None
    if paged and quant:
        tables = cache["block_tables"].astype(jnp.int32)

        def body(x, layer_in):
            lp, ck, cv, sk, sv, ll = layer_in
            x, ck, cv, sk, sv = _layer_verify_paged(
                cfg, lp, x, ck, cv, tables, pos, rope, sk, sv,
                ll=ll, plan=plan)
            return x, (ck, cv, sk, sv)

        x, (new_k, new_v, *new_scales) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"], slabs))
    elif paged:
        tables = cache["block_tables"].astype(jnp.int32)

        def body(x, layer_in):
            lp, ck, cv, ll = layer_in
            x, ck, cv, _sk, _sv = _layer_verify_paged(
                cfg, lp, x, ck, cv, tables, pos, rope, ll=ll, plan=plan)
            return x, (ck, cv)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], slabs))
    else:
        def body(x, layer_in):
            lp, ck, cv, ll = layer_in
            x, ck, cv = _layer_verify(cfg, lp, x, ck, cv, pos, rope,
                                      ll=ll, plan=plan)
            return x, (ck, cv)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], slabs))
    x = apply_norm(cfg, x, params["final_ln"]["scale"],
                   params["final_ln"]["bias"])
    logits = jnp.einsum(
        "bsh,vh->bsv", x, lm_head_weight(params, cfg).astype(cd),
        preferred_element_type=jnp.float32)
    cache = {"k": new_k, "v": new_v, "pos": pos + m}
    if new_scales is not None:
        cache["k_scale"], cache["v_scale"] = new_scales
    if paged:
        cache["block_tables"] = tables
    return logits, cache


def _layer_prefill(cfg, lp, x, kpm, rope):
    """One layer over the whole prompt [b, s, h]: the training
    forward's attention block (``transformer_lm._attention`` with
    ``return_kv`` — ONE implementation of the projection/split/rope/
    flash-attention math, so prefill cannot drift from training) plus
    the residual/MLP wiring of ``_layer`` without dropout."""
    from apex_tpu.models.transformer_lm import (
        _attention, _mlp, single_device_ctx)

    ctx = single_device_ctx()
    h = apply_norm(cfg, x, lp["ln1_scale"], lp["ln1_bias"])
    a, k, v = _attention(cfg, lp, h, ctx, kpm, rope, None,
                         return_kv=True)
    res = h if cfg.apply_residual_connection_post_layernorm else x
    x = res + a
    h = apply_norm(cfg, x, lp["ln2_scale"], lp["ln2_bias"])
    m = _mlp(cfg, lp, h, ctx)
    res = h if cfg.apply_residual_connection_post_layernorm else x
    return res + m, k, v


@functools.partial(jax.jit, static_argnames=("cfg", "max_len",
                                             "cache_dtype"))
def prefill(
    params: dict,
    prompt: jax.Array,
    cfg: TransformerConfig,
    *,
    prompt_lens: Optional[jax.Array] = None,
    cache: Optional[dict] = None,
    max_len: Optional[int] = None,
    cache_dtype=None,
):
    """Consume a whole prompt [b, s] in ONE batched forward →
    (last-token logits [b, v], filled KV cache).

    This is the fast half of the prefill/decode split: the prompt runs
    through the full-sequence training forward (flash attention for the
    causal pattern — O(s·d) memory, MXU-tiled) and every layer's
    post-rope K/V lands in the cache in a single dynamic-update, so a
    512-token prompt costs one forward instead of 512 sequential
    :func:`decode_step` calls.

    Ragged batches: ``prompt_lens`` [b] int32 marks each row's real
    length (rows are LEFT-aligned, padding on the right).  Padding keys
    are masked in-kernel via the flash key-padding path; the garbage
    K/V written at a row's padding slots is invisible (decode masks
    ``t <= pos[i]``) and is overwritten slot-by-slot as that sequence
    decodes.  The returned ``cache['pos']`` equals ``prompt_lens``.

    ``cache``: fill an existing cache (e.g. a serving slot buffer of
    ``max_len`` > s); otherwise one is allocated at ``max_len``
    (default ``s``) with ``cache_dtype``.  A PAGED cache (built by
    ``init_kv_cache(..., cache_layout="paged")`` or the serving
    engine's block manager) is recognized by its ``block_tables``
    entry: prefill then writes whole pages — every position scatters
    through the table in one update, padding and unmapped pages
    dropping — and returns the same paged dict.
    """
    _check_decode_cfg(cfg)
    b, s = prompt.shape
    if cache is None:
        cache = init_kv_cache(cfg, b, max_len if max_len else s,
                              cache_dtype=cache_dtype)
    paged = "block_tables" in cache
    cache_len = (cache["block_tables"].shape[1] * cache["k"].shape[2]
                 if paged else cache["k"].shape[2])
    if s > cache_len:
        raise ValueError(
            f"prompt length {s} exceeds the cache max_len {cache_len}")
    cd = cfg.compute_dtype
    lens = (jnp.full((b,), s, jnp.int32) if prompt_lens is None
            else prompt_lens.astype(jnp.int32))
    # key-padding mask (True = masked) only when the batch is ragged —
    # the uniform path keeps the exact training-forward flash variant
    kpm = None
    if prompt_lens is not None:
        kpm = jnp.arange(s)[None] >= lens[:, None]

    x = jnp.take(params["embedding"]["word"].astype(cd), prompt, axis=0)
    if cfg.position_embedding_type == "learned":
        x = x + params["embedding"]["position"][:s].astype(cd)[None]
    rope = None
    if cfg.position_embedding_type == "rope":
        rope = rope_cos_sin(s, cfg.kv_channels, cfg.rope_theta)

    quant = "k_scale" in cache

    def body(x, lp):
        x, k, v = _layer_prefill(cfg, lp, x, kpm, rope)
        if quant:
            # int8 pool: keep the float K/V through the scan and
            # quantize once at the scatter edge below
            return x, (k, v)
        return x, (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype))

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])

    x = apply_norm(cfg, x, params["final_ln"]["scale"],
                   params["final_ln"]["bias"])
    # logits for each row's LAST REAL token only ([b, h] @ head — the
    # [b, s, v] prompt logits are never materialized)
    x_last = jnp.take_along_axis(
        x, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum(
        "bh,vh->bv", x_last, lm_head_weight(params, cfg).astype(cd),
        preferred_element_type=jnp.float32)
    if paged:
        # whole-page scatter through the block tables: position t of
        # row i lands in cell (tables[i, t//bs], t % bs).  Row padding
        # (t >= lens[i]) and unmapped table entries drop, so a ragged
        # prefill can never write into blocks the row does not own.
        tables = cache["block_tables"].astype(jnp.int32)
        nb, bs = cache["k"].shape[1], cache["k"].shape[2]
        mb = tables.shape[1]
        t = jnp.arange(s)
        blk = jnp.take_along_axis(
            tables, jnp.broadcast_to(
                jnp.minimum(t // bs, mb - 1)[None], (b, s)), axis=1)
        blk = jnp.where(t[None] < lens[:, None], blk, nb)
        off = jnp.broadcast_to(t % bs, (b, s))
        if quant:
            # quantize the whole prompt's K/V per (token, group); the
            # shared scatter keeps wire + scale cells paired (padding
            # and unmapped pages drop both together)
            from apex_tpu.serving.paged_cache import scatter_kv_quantized

            ck, cv, sk, sv = scatter_kv_quantized(
                cache["k"], cache["v"], cache["k_scale"],
                cache["v_scale"], ks, vs, (slice(None), blk, off))
            cache = {
                "k": ck, "v": cv, "k_scale": sk, "v_scale": sv,
                "pos": lens,
                "block_tables": tables,
            }
            return logits, cache
        cache = {
            "k": cache["k"].at[:, blk, off].set(ks, mode="drop"),
            "v": cache["v"].at[:, blk, off].set(vs, mode="drop"),
            "pos": lens,
            "block_tables": tables,
        }
        return logits, cache
    cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(
            cache["k"], ks, 0, axis=2),
        "v": jax.lax.dynamic_update_slice_in_dim(
            cache["v"], vs, 0, axis=2),
        "pos": lens,
    }
    return logits, cache


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_chunk_forward(params, chunk, cache, cfg):
    """One jitted chunk of a chunked prefill: ``chunk`` [b, m] appends
    at ``cache['pos']`` and attends to the already-written KV prefix
    plus itself causally — exactly a verification forward, so this IS
    :func:`decode_verify` under a shape-keyed jit (equal chunk sizes
    share one compile; the serving engine additionally pins its chunk
    shape to a single bucket)."""
    return decode_verify(params, chunk, cache, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def _prefill_chunk_forward_donated(params, chunk, cache, cfg):
    """The donated form for chunks after the first: their input cache
    is loop-local (the previous chunk's output), so the pool updates
    in place instead of copying the whole K/V buffer per chunk.  The
    FIRST chunk must not donate — its cache belongs to the caller."""
    return decode_verify(params, chunk, cache, cfg)


def prefill_chunked(
    params: dict,
    prompt: jax.Array,
    cfg: TransformerConfig,
    *,
    chunk_tokens: int,
    prompt_lens: Optional[jax.Array] = None,
    cache: Optional[dict] = None,
    max_len: Optional[int] = None,
    cache_dtype=None,
):
    """Chunked prefill (ISSUE 15, Sarathi-style): consume a prompt
    [b, s] in ``ceil(s / chunk_tokens)`` fixed-size forwards instead of
    one monolithic pass → (last-real-token logits [b, v], filled KV
    cache) — the same contract as :func:`prefill`.

    Each chunk is ONE batched forward whose queries attend to the KV
    prefix the earlier chunks already wrote plus the chunk itself
    causally — the verification-block attention pattern
    (:func:`decode_verify`), which is why chunk c's compute is
    O(chunk · (c·chunk)) and the total stays the O(s²) of the
    monolithic prefill: nothing is recomputed, only *scheduled*
    differently.  That scheduling is the point: a serving engine can
    interleave decode steps for co-resident requests between chunks,
    so a 32k-token prompt stalls its neighbors for one ``chunk_tokens``
    forward at a time instead of one 32k forward
    (``ServingEngine(chunk_tokens=...)`` builds on this; the TPOT
    interference bound is measured by ``bench.py``'s chunked
    starvation row).

    Greedy-token-identity: the final chunk's last-token logits ARE the
    first-token logits — ``argmax`` equal to :func:`prefill`'s, and a
    greedy continuation from the chunked cache is token-identical to
    one from the monolithic cache on BOTH cache layouts
    (tests/test_serving_chunked.py pins it; K/V written by a chunk
    may differ from the monolithic writer's in low-order bits — flash
    vs verify accumulation order — which is also why the serving
    engine never prefix-shares chunk-written blocks).  On an int8
    ``cache_wire`` pool later chunks read the *quantized* prefix
    (monolithic prefill quantizes only at the final scatter), so the
    contract there is the PR-14 one: deterministic,
    first-token-identical, trajectory may diverge.

    Ragged batches: ``prompt_lens`` [b] marks real row lengths.  Rows
    whose prompt ends inside an earlier chunk ride later chunks
    inertly — their writes land past their length (invisible to every
    masked read, overwritten by decode before it ever attends there)
    and their last-token logits are taken from the chunk that held
    position ``lens[i]-1``.

    ``cache`` / ``max_len`` / ``cache_dtype`` behave as in
    :func:`prefill`; a paged cache (``block_tables`` present, int8
    ``cache_wire`` included) scatters each chunk through its block
    tables via the existing verify write edges.
    """
    _check_decode_cfg(cfg)
    if chunk_tokens < 1:
        raise ValueError(
            f"chunk_tokens={chunk_tokens} must be >= 1")
    b, s = prompt.shape
    if cache is None:
        cache = init_kv_cache(cfg, b, max_len if max_len else s,
                              cache_dtype=cache_dtype)
    paged = "block_tables" in cache
    cache_len = (cache["block_tables"].shape[1] * cache["k"].shape[2]
                 if paged else cache["k"].shape[2])
    if s > cache_len:
        raise ValueError(
            f"prompt length {s} exceeds the cache max_len {cache_len}")
    lens = (jnp.full((b,), s, jnp.int32) if prompt_lens is None
            else jnp.asarray(prompt_lens, jnp.int32))
    logits_last = None
    for lo in range(0, s, chunk_tokens):
        hi = min(s, lo + chunk_tokens)
        # rows already complete park at their own length: their chunk
        # writes land past it (masked reads never see them, decode
        # overwrites them in place) and their pos is restored below
        cache = dict(cache, pos=jnp.minimum(lens, lo))
        fwd = (_prefill_chunk_forward if lo == 0
               else _prefill_chunk_forward_donated)
        logits, cache = fwd(params, prompt[:, lo:hi], cache, cfg)
        take = jnp.clip(lens - 1 - lo, 0, hi - lo - 1)
        lg = jnp.take_along_axis(
            logits, take[:, None, None], axis=1)[:, 0]
        hit = (lens - 1 >= lo) & (lens - 1 < hi)
        logits_last = (lg if logits_last is None
                       else jnp.where(hit[:, None], lg, logits_last))
    return logits_last, dict(cache, pos=lens)


def sample_logits(logits, key, *, temperature: float = 0.0,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None,
                  vocab_limit: Optional[int] = None):
    """Pick next tokens [b] from logits [b, v] (greedy at
    ``temperature=0``; otherwise softmax sampling with optional
    ``top_k`` and/or nucleus ``top_p`` cutoffs — both given =
    intersection, top_k first).

    ``vocab_limit`` masks logits at and beyond that id — REQUIRED
    knowledge for padded vocab tables (tools/import_hf.py pads GPT-2's
    50257 to 50304; the zero-logit pad ids would otherwise be sampleable
    and can even win argmax when all real logits are negative).

    Since ISSUE 8 this is a thin wrapper over
    :func:`apex_tpu.ops.fused_sampling.fused_sample`, which fuses the
    whole temperature → top-k/top-p → draw chain into one kernel on the
    decode hot path on a TPU (elsewhere the XLA reference, which is
    bit-identical to the historical op sequence given the same key, so
    seeded callers see no change off-TPU).
    ``temperature == 0`` short-circuits every filter and returns the
    argmax — the cutoffs cannot change which token is largest
    (regression-pinned in tests/test_fused_sampling.py).
    """
    _check_sampling_args(temperature, top_k)
    return fused_sample(logits, key, temperature=temperature,
                        top_k=top_k, top_p=top_p,
                        vocab_limit=vocab_limit)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "temperature", "top_k", "top_p",
    "vocab_limit", "eos_token_id", "cache_dtype", "cache_layout",
    "block_size", "cache_wire", "decode_fused"))
def _generate_impl(params, prompt, prompt_lens, rng, *, cfg,
                   max_new_tokens, temperature, top_k, top_p,
                   vocab_limit, eos_token_id, cache_dtype,
                   cache_layout, block_size, cache_wire=None,
                   decode_fused="reference"):
    """Prefill + while-loop decode; returns (tokens, realized steps)."""
    b, s = prompt.shape
    total = s + max_new_tokens
    cache = init_kv_cache(cfg, b, total, cache_dtype=cache_dtype,
                          cache_layout=cache_layout,
                          block_size=block_size, cache_wire=cache_wire)
    lens = (jnp.full((b,), s, jnp.int32) if prompt_lens is None
            else prompt_lens.astype(jnp.int32))
    logits, cache = prefill(params, prompt, cfg,
                            prompt_lens=prompt_lens, cache=cache)
    tokens = jnp.concatenate(
        [prompt, jnp.zeros((b, max_new_tokens), prompt.dtype)], axis=1)
    col = jnp.arange(total)

    def pick(lg, key):
        return sample_logits(lg, key, temperature=temperature,
                             top_k=top_k, top_p=top_p,
                             vocab_limit=vocab_limit)

    def cond(carry):
        i, done = carry[0], carry[1]
        # the loop only needs max_new_tokens - 1 decode forwards: the
        # first token comes from the prefill logits and the LAST one
        # needs no decode_step (nothing ever consumes its K/V)
        return (i < max_new_tokens - 1) & ~jnp.all(done)

    def body(carry):
        i, done, logits, tokens, cache, key = carry
        key, sub = jax.random.split(key)
        nxt = pick(logits, sub)
        # each live sequence appends at its own end (lens[i] + step) —
        # the emitted EOS itself is written, later steps are not
        wmask = (col[None] == (lens + i)[:, None]) & (~done)[:, None]
        tokens = jnp.where(wmask, nxt[:, None].astype(tokens.dtype),
                           tokens)
        if eos_token_id is not None:
            done = done | (nxt == eos_token_id)
        # the decode batch stays rectangular: finished sequences still
        # step (their logits are ignored) but their cache position is
        # frozen so they stop consuming slots
        prev = cache["pos"]
        logits, cache = decode_step(params, nxt.astype(prompt.dtype),
                                    cache, cfg,
                                    decode_fused=decode_fused)
        cache = dict(cache, pos=jnp.where(done, prev, cache["pos"]))
        return (i + 1, done, logits, tokens, cache, key)

    carry = (jnp.int32(0), jnp.zeros((b,), bool), logits, tokens, cache,
             rng)
    i, done, logits, tokens, _, key = jax.lax.while_loop(cond, body,
                                                         carry)
    # the final token: sampled from the last logits, no decode behind it
    if max_new_tokens > 0:
        _, sub = jax.random.split(key)
        nxt = pick(logits, sub)
        wmask = (col[None] == (lens + i)[:, None]) & (~done)[:, None]
        tokens = jnp.where(wmask, nxt[:, None].astype(tokens.dtype),
                           tokens)
    return tokens, i


def generate(
    params: dict,
    prompt: jax.Array,
    cfg: TransformerConfig,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    vocab_limit: Optional[int] = None,
    prompt_lens: Optional[jax.Array] = None,
    eos_token_id: Optional[int] = None,
    cache_dtype=None,
    cache_layout: str = "contiguous",
    block_size: int = DEFAULT_BLOCK_SIZE,
    cache_wire=None,
    spec=None,
) -> jax.Array:
    """Decode up to ``max_new_tokens`` past ``prompt`` [b, s] →
    [b, s+max_new_tokens].

    ``cache_wire="int8"`` (paged layout only, ISSUE 14) stores the
    block pool at rest as block-scaled int8 — halving-plus the
    resident cache bytes, with K/V quantized at every write and
    dequantized inside the paged-attention kernel.  Greedy output is
    deterministic but MAY diverge from the native-pool trajectory
    (each decoded token's hidden state reads slightly-lossy K/V);
    docs/inference.md "Quantized serving" has the accuracy story and
    the spec-decode accept-rate gate that bounds it.

    ``spec`` enables speculative decoding (``"ngram"`` for n-gram
    self-drafting with the default knobs, a ``models.speculative.
    SpecConfig`` for tuning or a draft-model hook, ``None``/``"off"``
    for the plain path): k drafted tokens are verified by ONE batched
    :func:`decode_verify` forward per round instead of k sequential
    decode steps.  Greedy output is token-identical to ``spec=None``
    on both cache layouts and sampling is distribution-identical
    (``models/speculative.py`` has the correctness argument); the
    realized ``generate.spec.{draft_tokens,accepted_tokens,
    verify_calls}`` counters land in telemetry when configured.

    The decode layer routes through the FUSED decode step
    (``ops/decode_step.py``: rope + attention + output projection in
    one kernel on a TPU, the XLA composition elsewhere) — greedy output
    is token-identical across routes on both layouts and both
    ``cache_wire`` forms (tests/test_decode_fused.py pins it); the
    route is resolved here, outside the jit, and threaded as a static
    argument.

    ``cache_layout="paged"`` runs the same prefill + while-loop decode
    over the block-pool cache (``block_size`` tokens per block, tables
    filled linearly) and the fused ragged-paged attention kernel —
    greedy output is token-identical to the contiguous layout
    (tests/test_generate_paged.py pins it); the layout exists for the
    serving engine, where blocks are allocated dynamically.

    The prompt is consumed by ONE batched :func:`prefill` forward
    (flash attention, whole KV cache written in one pass); decoding is
    a ``lax.while_loop`` over :func:`decode_step` that exits as soon as
    every sequence has emitted ``eos_token_id`` (when given) instead of
    always scanning ``max_new_tokens``.

    ``temperature=0`` is greedy; otherwise softmax sampling with the
    optional ``top_k`` / nucleus ``top_p`` cutoffs of
    :func:`sample_logits`.  ``vocab_limit`` masks padded vocab ids
    (tools/import_hf.py).

    Ragged batches: pass right-padded prompts plus ``prompt_lens`` [b]
    int32.  Each sequence decodes from its own length — generated
    tokens overwrite the row's padding left-to-right, so row ``i``
    holds its prompt in ``[:lens[i]]``, its generation in
    ``[lens[i]:lens[i]+n_i]``, and untouched padding after.  Greedy
    output is token-identical to running each sequence through its own
    unbatched ``generate`` call (tests/test_generate.py pins this).

    When telemetry is configured the call records
    ``generate.prefill_calls`` and ``generate.decode_steps`` counters —
    the decode-step count equals the realized while-loop trip count
    (``== max_new_tokens - 1`` when no sequence stops early: the first
    token comes from the prefill logits and the last needs no decode
    behind it), which is how the prefill-not-per-token property is
    asserted in tests — the count scales with the NEW tokens, never
    with the prompt length.
    """
    b, s = prompt.shape
    total = s + max_new_tokens
    if (cfg.position_embedding_type == "learned"
            and total > cfg.max_position_embeddings):
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_position_embeddings ({cfg.max_position_embeddings}); "
            "the learned position lookup would silently clamp")
    _check_sampling_args(temperature, top_k)
    _check_decode_cfg(cfg)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if prompt_lens is not None:
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    if cache_layout not in ("contiguous", "paged"):
        raise ValueError(
            f"cache_layout={cache_layout!r}: expected 'contiguous' or "
            "'paged'")
    from apex_tpu.models.speculative import resolve_spec, spec_generate

    if resolve_spec(spec) is not None:
        tokens, stats = spec_generate(
            params, prompt, cfg, spec=spec,
            max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, rng=rng, vocab_limit=vocab_limit,
            prompt_lens=prompt_lens, eos_token_id=eos_token_id,
            cache_dtype=cache_dtype, cache_layout=cache_layout,
            block_size=block_size, cache_wire=cache_wire)
        if _telemetry.enabled():
            _telemetry.counter("generate.prefill_calls").inc()
            _telemetry.counter("generate.spec.draft_tokens").inc(
                stats["draft_tokens"])
            _telemetry.counter("generate.spec.accepted_tokens").inc(
                stats["accepted_tokens"])
            _telemetry.counter("generate.spec.verify_calls").inc(
                stats["verify_calls"])
        return tokens
    # resolve the fused-decode route HERE, outside the jit: threading
    # the resolved route through the static args keys the trace cache
    # on it (interpret mode on or off retraces)
    from apex_tpu.ops.decode_step import route_decode_fused

    tokens, n_steps = _generate_impl(
        params, prompt, prompt_lens, rng, cfg=cfg,
        max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p, vocab_limit=vocab_limit,
        eos_token_id=eos_token_id, cache_dtype=cache_dtype,
        cache_layout=cache_layout, block_size=block_size,
        cache_wire=cache_wire, decode_fused=route_decode_fused(None))
    if _telemetry.enabled():
        # host-side counters (the jitted loop cannot emit); reading the
        # realized trip count syncs — acceptable when telemetry is on
        _telemetry.counter("generate.prefill_calls").inc()
        _telemetry.counter("generate.decode_steps").inc(int(n_steps))
    return tokens
