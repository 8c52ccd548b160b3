"""Standalone parallel transformer LM — the flagship model family.

Reference: apex/transformer/testing/standalone_transformer_lm.py (1,574 LoC
Megatron LM: ``Embedding`` :1239, ``ParallelAttention`` :358, ``ParallelMLP``
:165, ``ParallelTransformerLayer`` :598, ``ParallelTransformer`` :780,
``TransformerLanguageModel`` :1358, ``parallel_lm_logits`` :1130).

TPU-native redesign — *one functional core, two parallel modes*:

- Parameters are a plain pytree (layers stacked on a leading ``L`` axis so
  the whole decoder is a single ``lax.scan`` — one compiled layer body
  regardless of depth, the XLA-friendly shape of Megatron's ModuleList).
- The forward is a pure function ``gpt_forward(params, tokens, cfg, ctx)``.
  All tensor-parallel communication is injected through a tiny
  :class:`TPContext`, with two implementations:

  * :func:`gspmd_ctx` — sharding *constraints*; run under ``jit`` over a
    mesh and XLA's SPMD partitioner inserts the collectives the reference
    issues by hand (the recommended path).
  * :func:`manual_ctx` — the eight mapping collectives
    (tensor_parallel/mappings.py) for use inside ``shard_map``; params are
    local shards and head/ffn counts divide by ``tp``. This is the mode the
    pipeline schedules compose with.

- Activations are batch-major ``[b, s, h]`` (TPU/XLA convention) rather
  than the reference's ``[s, b, h]``.
"""

from __future__ import annotations

import functools
import os
import warnings
from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from apex_tpu.models.config import TransformerConfig
from apex_tpu.ops import (
    fused_apply_rotary_pos_emb_cached,
    fused_layer_norm,
    fused_rms_norm,
    scaled_masked_softmax,
    scaled_softmax,
    scaled_upper_triang_masked_softmax,
    softmax_cross_entropy_loss,
)
from apex_tpu.ops.dense import is_quantized as _is_quantized
from apex_tpu.ops.flash_attention import REMAT_SAVED_NAMES
from apex_tpu.ops.swiglu import fused_bias_swiglu_paired
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
)

__all__ = [
    "TPContext",
    "gspmd_ctx",
    "manual_ctx",
    "single_device_ctx",
    "init_gpt_params",
    "gpt_param_specs",
    "gpt_forward",
    "gpt_loss",
    "lm_cross_entropy",
    "apply_norm",
    "rope_cos_sin",
]


# ---------------------------------------------------------------------------
# Tensor-parallel context
# ---------------------------------------------------------------------------


class TPContext(NamedTuple):
    """Injected TP communication — the model's only coupling to parallelism.

    ``tp`` is the degree by which *local* param shards are divided (1 under
    GSPMD where shapes stay global) and ``tp_axis`` the mesh axis name the
    vocab-parallel embed/CE collectives run over. ``copy_in`` enters a
    column-parallel region (reference mappings.py:268
    ``copy_to_tensor_model_parallel_region``); ``reduce_out`` exits a
    row-parallel region (allreduce of partials, mappings.py:83). The
    ``constrain_*`` hooks are GSPMD sharding hints and identity in manual
    mode; ``constrain_col`` receives activations of any rank with the
    tp-sharded dim last.
    """

    tp: int
    tp_axis: str
    copy_in: Callable[[jax.Array], jax.Array]
    reduce_out: Callable[[jax.Array], jax.Array]
    constrain_hidden: Callable[[jax.Array], jax.Array]
    constrain_col: Callable[[jax.Array], jax.Array]
    vocab_parallel: bool
    # context parallelism: when set, core attention stays
    # sequence-sharded over this mesh axis.  cp_mode picks the
    # algorithm: "ring" (K/V chunks ppermute around the ring,
    # O(s_local·n·d) memory — parallel/ring_attention.py) or "ulysses"
    # (all-to-all head re-sharding, one full-sequence flash call per
    # head group, O(s_global·n/sp·d) — parallel/ulysses.py).  The
    # reference has neither (SURVEY §5); this is the TPU-native
    # long-context path, first-class in the flagship model.  cp_qkv_spec
    # is the [b, s, n, d] partitioning the shard_map wrapper pins so the
    # batch (dp) and head (tp) shardings survive the manual region.
    cp_axis: Optional[str] = None
    cp_qkv_spec: Optional[P] = None
    cp_mode: str = "ring"
    # overlapped TP collectives (ops/collective_matmul): when set, the
    # row-parallel exits (attention proj, MLP fc2) call
    # ``row_parallel_matmul(x, w)`` instead of ``reduce_out(x @ w)`` —
    # the hook fuses the matmul with its reduction as a ppermute ring so
    # transfer hops overlap partial-product chunks.  The hook returns
    # ``None`` whenever the ring path does not apply (overlap disabled,
    # no mesh, tp absent/1, indivisible shapes) and the caller falls
    # back to the exact monolithic expression.
    row_parallel_matmul: Optional[Callable] = None


def _constrain(x, spec: P):
    """Apply a sharding constraint when a mesh context is active; no-op
    outside one (single-device tests). Never swallows real sharding errors:
    the mesh/axis check is explicit rather than a blanket except."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    names = set(mesh.axis_names)
    for part in spec:
        axes = part if isinstance(part, tuple) else (part,)
        for a in axes:
            if a is not None and a not in names:
                return x
    return jax.lax.with_sharding_constraint(x, spec)


def gspmd_ctx(batch_axis: str = "dp", tp_axis: str = "tp",
              seq_axis: Optional[str] = None,
              context_parallel: Union[bool, str] = False,
              overlap_comm: Optional[bool] = None) -> TPContext:
    """Constraint-based context: annotate, let XLA partition.

    ``seq_axis`` shards activations along sequence (Megatron SP under
    GSPMD).  ``context_parallel`` additionally keeps core attention
    sequence-sharded over ``seq_axis`` — without it, XLA's default
    strategy all-gathers K/V per device, whose O(s_global) activations
    cap the sequence length.  ``True`` or ``"ring"`` selects ring
    attention (O(s_local) memory); ``"ulysses"`` selects all-to-all
    head re-sharding (one full-sequence flash call per head group —
    needs num_heads divisible by the axis size).

    ``overlap_comm`` routes the row-parallel matmul+reduce exits
    through the ring collective-matmul (``ops/collective_matmul``):
    ``True``/``False`` is explicit, ``None`` (default) inherits
    ``collective_matmul.overlap_scope`` at trace time — which is how
    ``amp.frontend.make_train_step(overlap_comm=...)`` reaches contexts
    it never sees."""
    if context_parallel and seq_axis is None:
        raise ValueError(
            "context_parallel requires seq_axis (the mesh axis the "
            "sequence is sharded over)")
    if context_parallel not in (False, True, "ring", "ulysses"):
        raise ValueError(
            f"context_parallel={context_parallel!r}: expected "
            "False | True | 'ring' | 'ulysses'")
    cp_mode = "ulysses" if context_parallel == "ulysses" else "ring"

    def hidden(x):
        return _constrain(x, P(batch_axis, seq_axis, *([None] * (x.ndim - 2))))

    def col(x):
        return _constrain(
            x, P(batch_axis, *([None] * (x.ndim - 2)), tp_axis))

    def row_mm(x, w):
        # ring matmul-reduce-scatter island over tp; the hidden
        # constraint re-gathers the sequence-scattered result lazily
        # (XLA overlaps that all-gather with downstream compute)
        from apex_tpu.ops.collective_matmul import gspmd_row_parallel_matmul

        y = gspmd_row_parallel_matmul(
            x, w, tp_axis=tp_axis, batch_axis=batch_axis,
            seq_axis=seq_axis, enable=overlap_comm)
        return None if y is None else hidden(y)

    return TPContext(
        tp=1,
        tp_axis=tp_axis,
        copy_in=lambda x: x,
        reduce_out=hidden,
        constrain_hidden=hidden,
        constrain_col=col,
        vocab_parallel=False,
        cp_axis=seq_axis if context_parallel else None,
        cp_qkv_spec=(P(batch_axis, seq_axis, tp_axis, None)
                     if context_parallel else None),
        cp_mode=cp_mode,
        row_parallel_matmul=row_mm if overlap_comm is not False else None,
    )


def manual_ctx(tp: int, axis: str = "tp",
               overlap_comm: Optional[bool] = None) -> TPContext:
    """shard_map context: explicit mapping collectives, local shards.

    ``overlap_comm`` (tri-state like :func:`gspmd_ctx`) swaps the
    row-parallel exits' matmul → psum for the ring
    ``matmul_all_reduce`` (reduce-scatter hops overlapped with the
    partial-product chunks, then an all-gather; backward stays
    communication-free exactly like ``reduce_from``'s identity)."""

    def row_mm(x, w):
        from apex_tpu.ops import collective_matmul as _cm

        if tp <= 1 or not _cm.overlap_enabled(overlap_comm):
            return None
        # scatter the largest leading dim the axis divides (prefer the
        # sequence dim of [b, s, k] inputs); no fit → monolithic psum
        for d in (1, 0) if x.ndim >= 3 else (0,):
            if x.shape[d] % tp == 0:
                return _cm.matmul_all_reduce(x, w, axis, scatter_dim=d)
        return None

    return TPContext(
        tp=tp,
        tp_axis=axis,
        copy_in=lambda x: copy_to_tensor_model_parallel_region(x, axis),
        reduce_out=lambda x: reduce_from_tensor_model_parallel_region(
            x, axis),
        constrain_hidden=lambda x: x,
        constrain_col=lambda x: x,
        vocab_parallel=tp > 1,
        row_parallel_matmul=row_mm if overlap_comm is not False else None,
    )


def single_device_ctx() -> TPContext:
    return TPContext(
        tp=1, tp_axis="tp", copy_in=lambda x: x, reduce_out=lambda x: x,
        constrain_hidden=lambda x: x, constrain_col=lambda x: x,
        vocab_parallel=False,
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_gpt_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Full (unsharded) parameter pytree.

    Init follows the reference: N(0, std) everywhere
    (standalone_transformer_lm.py:146 ``init_method_normal``), with output
    projections scaled by 1/sqrt(2L) (:155 ``scaled_init_method_normal``).
    Layers are stacked on a leading ``num_layers`` axis; a stack whose
    layers differ (``cfg.is_hybrid``) is a list of per-layer trees
    (models/hybrid.py).
    """
    if cfg.is_hybrid:
        from apex_tpu.models.hybrid import init_hybrid_params

        return init_hybrid_params(rng, cfg)
    h, L = cfg.hidden_size, cfg.num_layers
    p = cfg.projection_size
    f = cfg.ffn_hidden_size
    std = cfg.init_method_std
    out_std = std / (2.0 * L) ** 0.5
    dt = cfg.params_dtype

    ks = jax.random.split(rng, 8)

    def nrm(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    # swiglu uses the paired [h, 2, f] layout: sharding the trailing f dim
    # keeps each tp shard a (gate, up) pair (see ops.swiglu paired variant)
    fc1_shape = ((L, h, 2, f) if cfg.activation == "swiglu" else (L, h, f))
    fc1_bias_shape = ((L, 2, f) if cfg.activation == "swiglu" else (L, f))

    layers = {
        "ln1_scale": jnp.ones((L, h), dt),
        "ln1_bias": jnp.zeros((L, h), dt),
        # MHA keeps the legacy per-head-interleaved 3p layout (golden
        # traces + the HF importer depend on it); GQA uses the
        # group-major layout — per query group [q x rep | k | v] — the
        # direct generalization of the MHA per-head [q|k|v] (rep=1),
        # chosen so a contiguous tp chunk of this axis holds whole
        # groups and manual tensor parallelism stays legal (see
        # split_qkv_gqa)
        "qkv_kernel": nrm(ks[1], (L, h, p + 2 * cfg.kv_projection_size),
                          std),
        "qkv_bias": jnp.zeros((L, p + 2 * cfg.kv_projection_size), dt),
        "proj_kernel": nrm(ks[2], (L, p, h), out_std),
        "proj_bias": jnp.zeros((L, h), dt),
        "ln2_scale": jnp.ones((L, h), dt),
        "ln2_bias": jnp.zeros((L, h), dt),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        # swiglu experts carry the concatenated [gate ‖ up] fc1 (2f)
        f1 = 2 * f if cfg.activation == "swiglu" else f
        layers.update({
            "router_kernel": nrm(ks[3], (L, h, E), std),
            "moe_fc1": nrm(ks[4], (L, E, h, f1), std),
            "moe_fc1_bias": jnp.zeros((L, E, f1), dt),
            "moe_fc2": nrm(ks[7], (L, E, f, h), out_std),
            "moe_fc2_bias": jnp.zeros((L, E, h), dt),
        })
    else:
        layers.update({
            "fc1_kernel": nrm(ks[3], fc1_shape, std),
            "fc1_bias": jnp.zeros(fc1_bias_shape, dt),
            "fc2_kernel": nrm(ks[4], (L, f, h), out_std),
            "fc2_bias": jnp.zeros((L, h), dt),
        })

    params = {
        "embedding": {
            "word": nrm(ks[0], (cfg.vocab_size, h), std),
        },
        "layers": layers,
        "final_ln": {
            "scale": jnp.ones((h,), dt),
            "bias": jnp.zeros((h,), dt),
        },
    }
    if cfg.position_embedding_type == "learned":
        params["embedding"]["position"] = nrm(
            ks[5], (cfg.max_position_embeddings, h), std)
    if cfg.untie_embeddings_and_output_weights:
        params["lm_head"] = {"kernel": nrm(ks[6], (cfg.vocab_size, h), std)}
    return params


def gpt_param_specs(cfg: TransformerConfig, *, tp_axis: str = "tp",
                    pp_axis: Optional[str] = None) -> dict:
    """PartitionSpec tree matching :func:`init_gpt_params`.

    Used both for GSPMD ``device_put``/``in_shardings`` and as ``shard_map``
    in_specs (with ``pp_axis`` set, layer stacks gain a leading pipeline
    shard dim — see models/pipeline.py). Mirrors the reference's sharding:
    vocab rows over tp (layers.py:167), qkv/fc1 columns over tp (:429),
    proj/fc2 rows over tp (:613).
    """
    if cfg.is_hybrid:
        raise NotImplementedError(
            "a hybrid stack (cfg.layer_types / num_dense_layers) runs on "
            "one device or data parallel; it has no tp/pp partitioning")
    t = tp_axis
    pp = (pp_axis,) if pp_axis else ()
    swiglu = cfg.activation == "swiglu"

    layer_specs = {
        "ln1_scale": P(*pp, None, None),
        "ln1_bias": P(*pp, None, None),
        "qkv_kernel": P(*pp, None, None, t),
        "qkv_bias": P(*pp, None, t),
        "proj_kernel": P(*pp, None, t, None),
        "proj_bias": P(*pp, None, None),
        "ln2_scale": P(*pp, None, None),
        "ln2_bias": P(*pp, None, None),
    }
    if cfg.num_experts:
        # experts shard over cfg.moe_ep_axis under GSPMD; on the
        # shard_map pipeline path (pp_axis set) the stage fns run their
        # experts locally (make_gpt_pipeline_stage overrides
        # moe_ep_axis=None), so the specs drop 'ep' to match — callers
        # can feed these straight into shard_map in_specs
        ep = None if pp_axis else cfg.moe_ep_axis
        layer_specs.update({
            "router_kernel": P(*pp, None, None, None),
            "moe_fc1": P(*pp, None, ep, None, None),
            "moe_fc1_bias": P(*pp, None, ep, None),
            "moe_fc2": P(*pp, None, ep, None, None),
            "moe_fc2_bias": P(*pp, None, ep, None),
        })
    else:
        layer_specs.update({
            "fc1_kernel": (P(*pp, None, None, None, t) if swiglu
                           else P(*pp, None, None, t)),
            "fc1_bias": (P(*pp, None, None, t) if swiglu
                         else P(*pp, None, t)),
            "fc2_kernel": P(*pp, None, t, None),
            "fc2_bias": P(*pp, None, None),
        })

    specs = {
        "embedding": {"word": P(t, None)},
        "layers": layer_specs,
        "final_ln": {"scale": P(None), "bias": P(None)},
    }
    if cfg.position_embedding_type == "learned":
        specs["embedding"]["position"] = P(None, None)
    if cfg.untie_embeddings_and_output_weights:
        specs["lm_head"] = {"kernel": P(t, None)}
    return specs


# ---------------------------------------------------------------------------
# Forward pieces
# ---------------------------------------------------------------------------


def rope_cos_sin(seq_len: int, dim: int, base: float = 10000.0):
    """Rotary tables [s, d2] (reference fused_rope RotaryPositionEmbedding)."""
    inv = 1.0 / base ** (jnp.arange(0, dim, 2, jnp.float32) / dim)
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope(t, cos, sin):
    # t [b, s, n, d]; cos/sin [s, d] — reshape to broadcast over batch and
    # heads, then reuse the fused op (custom VJP recomputes from cos/sin)
    return fused_apply_rotary_pos_emb_cached(
        t, cos[None, :, None, :], sin[None, :, None, :])


def apply_norm(cfg, x, scale, bias):
    if cfg.normalization == "rmsnorm":
        return fused_rms_norm(x, scale, eps=cfg.layernorm_epsilon)
    return fused_layer_norm(x, scale, bias, eps=cfg.layernorm_epsilon)


def _dropout(x, rate, rng):
    if rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


def _drop_path(x, rate, rng):
    """Stochastic depth: drop a sample's whole residual branch
    (reference DropPath, standalone_transformer_lm.py:712-728 — applied
    to the post-dropout branch output, scaled by 1/keep_prob)."""
    if rate == 0.0 or rng is None:
        return x
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    keep = jax.random.bernoulli(rng, 1.0 - rate, shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


def _core_attention(cfg: TransformerConfig, q, k, v, attention_mask,
                    dropout_rng, ctx: Optional[TPContext] = None):
    """softmax(QK^T/sqrt(d)) V (reference CoreAttention,
    standalone_transformer_lm.py:213 → FusedScaleMaskSoftmax →
    csrc/megatron/scaled_*_softmax).

    Backend: ring attention over ``ctx.cp_axis`` under context
    parallelism (sequence stays sharded through attention); else the
    Pallas flash-attention kernel when the pattern allows (causal /
    unmasked / key-padding, attention dropout fused in-kernel);
    otherwise the fused-softmax family on materialized scores (generic
    4-D masks).
    """
    hd = q.shape[-1]
    scale = 1.0 / hd ** 0.5
    use_dropout = cfg.attention_dropout > 0 and dropout_rng is not None
    causal = cfg.attn_mask_type == "causal"

    def full_kv():
        return _broadcast_kv(q, k, v)

    if ctx is not None and ctx.cp_axis is not None:
        # k/v may still be grouped (GQA): _cp_core_attention keeps them
        # at group width where the mode supports it (ring — rep-x
        # smaller ppermute messages) and broadcasts otherwise
        cp = _cp_core_attention(ctx, q, k, v, causal, scale,
                                attention_mask, use_dropout)
        if cp is not None:
            return cp
    # a 2-D [b, s_k] mask means key padding (True = masked key) — the
    # fused kernels handle it in-kernel without materializing [b,n,sq,sk]
    kpm = None
    if attention_mask is not None and attention_mask.ndim == 2:
        kpm = attention_mask
        attention_mask = None
    if cfg.attention_backend == "flash" and attention_mask is None:
        from apex_tpu.ops.flash_attention import flash_attention
        return flash_attention(
            q, k, v, causal=causal, key_padding_mask=kpm, scale=scale,
            dropout_p=cfg.attention_dropout if use_dropout else 0.0,
            dropout_rng=dropout_rng if use_dropout else None)
    k, v = full_kv()
    if kpm is not None:
        attention_mask = kpm[:, None, None, :]   # broadcastable 4-D
    # [b, s, n, d] x [b, t, n, d] -> [b, n, s, t]
    scores = jnp.einsum(
        "bsnd,btnd->bnst", q, k,
        preferred_element_type=jnp.float32,
    )
    if not cfg.softmax_in_fp32:
        scores = scores.astype(q.dtype)
    if cfg.attn_mask_type == "causal":
        if attention_mask is not None:
            # combine the causal triangle with the user mask rather than
            # silently dropping either (e.g. padding inside a causal LM)
            sq, sk = scores.shape[-2], scores.shape[-1]
            row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
            causal_mask = (col > row)[None, None]
            probs = scaled_masked_softmax(
                scores, attention_mask | causal_mask, scale)
        else:
            probs = scaled_upper_triang_masked_softmax(scores, scale)
    elif attention_mask is not None:
        probs = scaled_masked_softmax(scores, attention_mask, scale)
    else:
        probs = scaled_softmax(scores, scale)
    probs = _dropout(probs, cfg.attention_dropout, dropout_rng)
    ctxv = jnp.einsum(
        "bnst,btnd->bsnd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    return ctxv


def _broadcast_kv(q, k, v):
    """Broadcast grouped (GQA) k/v up to the query head count — THE one
    model-side definition of the repeat, for paths that need equal head
    counts (XLA dense scores, Ulysses, tp-incompatible ring shards); the
    flash/ring kernels broadcast via index maps instead."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    return k, v


_cp_fallback_warned = False


def _cp_degraded_fallback(reason: str) -> None:
    """A context-parallel-configured model is about to take the gathered
    dense path: numerically correct, but K/V get all-gathered across the
    cp axis — the exact memory blowup context parallelism exists to
    avoid.  Loud once-per-process warning (trace-time, so it fires at
    compile, before the step OOMs); ``APEX_TPU_CP_STRICT=1`` raises."""
    global _cp_fallback_warned
    msg = (
        f"context parallelism DEGRADED: {reason}, which the ring/Ulysses "
        "kernels do not cover — falling back to dense attention with "
        "K/V all-gathered over the cp axis. At long context this is the "
        "memory blowup cp exists to avoid (OOM or crawl). Drop the mask "
        "/ attention dropout for cp training, or set APEX_TPU_CP_STRICT=1 "
        "to make this an error.")
    if os.environ.get("APEX_TPU_CP_STRICT", "") not in ("", "0"):
        raise ValueError(msg)
    if not _cp_fallback_warned:
        _cp_fallback_warned = True
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _cp_core_attention(ctx, q, k, v, causal, scale, attention_mask,
                       use_dropout):
    """Run core attention sequence-sharded over ``ctx.cp_axis`` (ring
    or Ulysses per ``ctx.cp_mode``), or return None when the pattern
    forces the gather path.

    Both modes cover the flagship patterns (causal / full, no mask, no
    attention dropout).  Masked or attention-dropout configs fall back
    to the dense core — correct, but K/V get gathered, so long-context
    training should keep those off (hidden dropout is unaffected; it
    rides the sequence-sharded regions).  The fallback warns once per
    process (it is the exact memory blowup cp exists to avoid — at
    s8192 it means OOM-or-crawl with no hint why); set
    ``APEX_TPU_CP_STRICT=1`` to make it a hard error instead."""
    axis = ctx.cp_axis
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axis not in mesh.axis_names:
        return None   # single-device run of a cp-configured model
    if int(mesh.shape[axis]) == 1:
        return None   # cp degree 1: the dense path gathers nothing
    if attention_mask is not None or use_dropout:
        _cp_degraded_fallback(
            "attention_mask is set" if attention_mask is not None
            else "attention dropout is active")
        return None
    if ctx.cp_mode == "ulysses":
        from apex_tpu.parallel.ulysses import ulysses_attention as cp_fn
        grouped_ok = False   # the all-to-all reshards the head axis
    else:
        from apex_tpu.parallel.ring_attention import ring_attention as cp_fn
        grouped_ok = True    # groups ride the ring (rep-x smaller msgs)

    # keep batch (dp) and head (tp) shardings through the manual region;
    # axes absent from the mesh drop to replicated, like _constrain
    names = set(mesh.axis_names)
    spec = P(*(a if (a is None or a in names) else None
               for a in ctx.cp_qkv_spec))
    if k.shape[2] != q.shape[2]:
        # grouped K/V: legal only when the mode supports it AND the
        # head-axis sharding still divides the group count
        head_ax = ctx.cp_qkv_spec[2]
        head_shards = (int(mesh.shape[head_ax])
                       if head_ax in names else 1)
        if not grouped_ok or k.shape[2] % head_shards:
            k, v = _broadcast_kv(q, k, v)
    f = jax.shard_map(
        functools.partial(cp_fn, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)


def split_qkv_gqa(cfg: TransformerConfig, qkv, b, s, nh):
    """Split the GQA group-major layout — per query group
    ``[q x rep | k | v]`` heads — into per-head tensors; THE one
    definition of the layout: the training forward and the KV-cache
    decode both use it, so they cannot drift apart (only the
    cache-parity test would catch that otherwise).

    Group-major (not the block ``[q|k|v]`` sections) so that a
    contiguous tp chunk of the fused axis holds whole groups: the same
    function serves the global view (``nh`` = all query heads) and a
    manual-TP rank's local view (``nh`` = heads/tp, requiring
    ``kv_groups % tp == 0``).  With ``rep == 1`` this degenerates to the
    MHA per-head ``[q|k|v]`` interleave.  Query head ``h`` belongs to
    group ``h // rep`` in both views — the decode path's
    ``q.reshape(b, 1, g, rep, dh)`` fold depends on that ordering."""
    dh = cfg.kv_channels
    rep = cfg.num_attention_heads // cfg.kv_groups
    g = nh // rep   # local group count (nh may be per-rank heads/tp)
    blk = qkv.reshape(b, s, g, rep + 2, dh)
    q = blk[..., :rep, :].reshape(b, s, nh, dh)
    k = blk[..., rep, :]
    v = blk[..., rep + 1, :]
    return q, k, v


def _sequence_minor(t):
    """Pin ``t`` [b, s, n, d] to the sequence-minor layout that XLA gives
    the elementwise chain between a GQA projection and the kernels (it
    keeps arrays whose last axis is 64 or 32 wide out of 128-lane tiles),
    so that ONE bfloat16 copy a tensor moves the chain's result into the
    kernels' ``[b, s, n x d]``.  Unpinned, the kernels' layout spreads
    up the chain as far as its float32 leaves and XLA copies three of
    those for q and three for k (877 MB of copies in the LFM2 block
    where this leaves 302 and ``[b x n, s, d]`` kernels had 419: the
    described-v5e compile, tests/test_tpu_aot_compile.py)."""
    return with_layout_constraint(t, Layout(major_to_minor=(0, 2, 3, 1)))


def _mha_qkv(x, w, bias, nh):
    """The MHA projection ``[b, s, 3, nh x d]``: the weight's columns
    (and the bias) gathered from the stored per-head interleave
    ``[q | k | v] x nh`` into sections ``[Q | K | V]`` before the product,
    so that q, k and v leave it as the lane ranges ``[b, s, nh x d]`` that
    the flash kernels block (``ops/flash_attention._heads_a_block``), and
    not as every head's third of 3d lanes, which XLA transposes into
    place an activation at a time.  The weight is 1/8 of one activation's
    bytes at b8 x s1024.  The parameter's stored layout (checkpoints,
    ``models/generate``'s decode split) does not move: the same
    ``qkv_kernel`` gives the same q, k, v.  The last axis keeps whole
    heads contiguous, so it shards over tp like the interleave does.

    Two fences keep the gather on the weight (the described-v5e compile
    of the cells' steps at two layers): without the barrier XLA folds
    the gather into the products and transposes d(qkv), 50 MB twice a
    layer at b8 x s1024, instead of the 6 MB weight gradient; without
    the pinned layout an unrolled stack (BERT) takes the gathered
    layout for the whole stacked leaf and copies its float32 master and
    both moments in and out of every step (6 x 302 MB)."""
    h = w.shape[0]
    w = with_layout_constraint(w, Layout(major_to_minor=(0, 1)))
    w = jax.lax.optimization_barrier(
        w.reshape(h, nh, 3, -1).transpose(0, 2, 1, 3).reshape(h, 3, -1))
    qkv = jnp.einsum("bsh,hcm->bscm", x, w)
    if bias is not None:
        qkv = qkv + bias.astype(x.dtype).reshape(nh, 3, -1).transpose(
            1, 0, 2).reshape(3, -1)
    return qkv


def _attention(cfg: TransformerConfig, lp: dict, x, ctx: TPContext,
               attention_mask, rope, dropout_rng, return_kv: bool = False):
    """ParallelAttention (reference :358): column-parallel fused QKV,
    core attention, row-parallel output projection.

    ``return_kv=True`` additionally returns the post-rope group-width
    K/V — the KV-cache prefill (models/generate.py) consumes them, so
    the inference prefill and the training forward share ONE
    implementation of the projection/split/rope/core-attention math."""
    nh = cfg.num_attention_heads // ctx.tp
    b, s, _ = x.shape

    with jax.named_scope("qkv"):
        xi = ctx.copy_in(x)
        wq = lp["qkv_kernel"]
        # MHA: q, k, v leave the product as sections [Q | K | V]; GQA and
        # int8 weights keep the stored interleave
        sections = not (cfg.is_gqa or _is_quantized(wq))
        if _is_quantized(wq):
            # weight-only int8 serving path (ISSUE 14): single-device by
            # contract — quantize_params is a serving conversion, manual-TP
            # training never sees quantized leaves
            if ctx.tp > 1:
                raise ValueError(
                    "quantized kernels (models/quantized.quantize_params) "
                    "are a single-device serving path; they cannot shard "
                    f"over the manual tp={ctx.tp} context")
            from apex_tpu.ops.dense import quantized_matmul

            qkv = quantized_matmul(xi, wq)
        elif sections:
            qkv = _mha_qkv(xi, wq.astype(x.dtype), lp.get("qkv_bias"), nh)
        else:
            qkv = xi @ wq.astype(x.dtype)
        if "qkv_bias" in lp and not sections:
            qkv = qkv + lp["qkv_bias"].astype(x.dtype)
        qkv = ctx.constrain_col(qkv)
        if cfg.is_gqa:
            # group-major layout (per group [q x rep | k | v]): a contiguous
            # tp chunk holds whole groups, so manual TP is legal whenever
            # each rank gets an integral number of groups
            if ctx.tp > 1 and cfg.kv_groups % ctx.tp:
                raise ValueError(
                    f"GQA with num_query_groups={cfg.kv_groups} cannot "
                    f"shard over the manual shard_map tensor-parallel "
                    f"context with tp={ctx.tp}: tp must divide the group "
                    "count (each rank needs whole [q x rep | k | v] "
                    "groups). Use a tp that divides num_query_groups, or "
                    "the GSPMD context (make_gpt_train_step over a mesh), "
                    "which replicates KV heads as needed")
            q, k, v = split_qkv_gqa(cfg, qkv, b, s, nh)
        elif sections:
            q, k, v = (qkv[:, :, i].reshape(b, s, nh, -1) for i in range(3))
        else:
            q, k, v = jnp.split(qkv.reshape(b, s, nh, -1), 3, axis=-1)
        if cfg.qk_norm:
            from apex_tpu.models.hybrid import qk_norm_rope

            q, k = (_sequence_minor(t)
                    for t in qk_norm_rope(cfg, lp, q, k, rope))
        elif rope is not None:
            cos, sin = rope
            q = _apply_rope(q, cos, sin)
            k = _apply_rope(k, cos, sin)
    # Under GQA, k/v stay at group width here: the flash kernel consumes
    # them directly (its index maps broadcast each group head to its rep
    # query heads — the repeated tensor never exists in HBM); the paths
    # that need full-width heads (XLA dense, context parallel) broadcast
    # inside _core_attention.  The decode path keeps the cache at group
    # width too — that persistent memory is the GQA win.
    if dropout_rng is not None and ctx.tp > 1:
        # attention probs are head-sharded over tp: each tp rank needs its
        # own dropout stream (the reference's model-parallel RNG,
        # tensor_parallel/random.py CudaRNGStatesTracker); replicated
        # hidden-dropout keys stay shared.
        dropout_rng = jax.random.fold_in(
            dropout_rng, jax.lax.axis_index(ctx.tp_axis))
    with jax.named_scope("core_attention"):
        ctxv = _core_attention(cfg, q, k, v, attention_mask, dropout_rng,
                               ctx)
    with jax.named_scope("proj"):
        ctxv = ctxv.reshape(b, s, -1)
        wp = lp["proj_kernel"]
        if _is_quantized(wp):
            from apex_tpu.ops.dense import quantized_matmul

            out = ctx.reduce_out(quantized_matmul(ctxv, wp))
        else:
            out = _row_parallel_out(ctx, ctxv, wp.astype(x.dtype))
        if "proj_bias" in lp:
            out = out + lp["proj_bias"].astype(x.dtype)
    return (out, k, v) if return_kv else out


def _row_parallel_out(ctx: TPContext, x, w):
    """The row-parallel exit: overlapped ring matmul+reduce when the
    context's hook applies, else the monolithic matmul → reduce_out."""
    if ctx.row_parallel_matmul is not None:
        y = ctx.row_parallel_matmul(x, w)
        if y is not None:
            return y
    return ctx.reduce_out(x @ w)


def _moe_mlp(cfg: TransformerConfig, lp: dict, x, with_load: bool = False):
    """MoE FFN (transformer/moe.py) in place of the dense MLP when
    ``cfg.num_experts`` is set; returns (out, aux_loss).  Experts shard
    over the 'ep' mesh axis — via GSPMD annotations on the capacity
    path, or the explicit compressed/ring-overlapped shard_map island on
    the ragged path (``cfg.moe_routing='ragged'``, wire dtype
    ``cfg.moe_comm``; overlap follows the ambient
    ``collective_matmul.overlap_scope`` the train step sets).  tp inside
    experts is not combined (experts ARE the parallelism for the FFN
    block)."""
    from apex_tpu.transformer.moe import switch_moe_mlp

    names = {"router": "router_kernel", "router_bias": "router_bias",
             "fc1": "moe_fc1", "fc1_bias": "moe_fc1_bias",
             "fc2": "moe_fc2", "fc2_bias": "moe_fc2_bias"}
    moe_params = {k: lp[v] for k, v in names.items() if v in lp}
    o = switch_moe_mlp(
        moe_params, x,
        capacity_factor=cfg.moe_capacity_factor,
        top_k=cfg.moe_top_k,
        ep_axis=cfg.moe_ep_axis,
        activation=cfg.activation,
        routing=cfg.moe_routing,
        moe_comm=cfg.moe_comm,
        router=cfg.moe_router,
        experts_held=cfg.moe_experts_held,
        routed_scaling=cfg.moe_routed_scaling,
        gate_epsilon=cfg.moe_gate_epsilon)
    return (o.out, o.aux_loss, o.expert_load) if with_load else (
        o.out, o.aux_loss)


def _mlp(cfg: TransformerConfig, lp: dict, x, ctx: TPContext):
    """ParallelMLP (reference :165): column-parallel fc1 + fused bias-act,
    row-parallel fc2 (fused bias_swiglu / bias+gelu epilogues).

    Quantized fc kernels (ISSUE 14, ``_is_quantized`` dict leaves from
    ``models/quantized.quantize_params``) run the int8 weight-slab
    matmul instead — single-device serving path; the 3-D swiglu paired
    kernel's trailing axes flatten inside ``dense_quantized`` so the
    ``[b, s, 2, f]`` layout is unchanged."""
    with jax.named_scope("fc1"):
        xi = ctx.copy_in(x)
        w1 = lp["fc1_kernel"]
        if cfg.activation == "swiglu":
            if _is_quantized(w1):
                from apex_tpu.ops.dense import quantized_matmul

                y = quantized_matmul(xi, w1)          # [b, s, 2, f]
            else:
                # paired [h, 2, f] kernel: each tp shard of the f dim is a
                # (gate, up) pair, matching the single-device layout exactly
                y = jnp.einsum("bsh,hcf->bscf", xi, w1.astype(x.dtype))
            y = ctx.constrain_col(y)
            b1 = lp.get("fc1_bias")
            y = fused_bias_swiglu_paired(
                y, None if b1 is None else b1.astype(x.dtype))
        else:
            if _is_quantized(w1):
                from apex_tpu.ops.dense import quantized_matmul

                y = quantized_matmul(xi, w1) + lp["fc1_bias"].astype(x.dtype)
            else:
                y = xi @ w1.astype(x.dtype) + lp["fc1_bias"].astype(x.dtype)
            y = ctx.constrain_col(y)
            # 'gelu_tanh' = the tanh approximation (HF gpt2's gelu_new) —
            # needed for bit-comparable imports of reference-ecosystem
            # checkpoints (tools/import_hf.py)
            y = jax.nn.gelu(
                y.astype(jnp.float32),
                approximate=cfg.activation == "gelu_tanh").astype(x.dtype)
    with jax.named_scope("fc2"):
        w2 = lp["fc2_kernel"]
        if _is_quantized(w2):
            from apex_tpu.ops.dense import quantized_matmul

            out = ctx.reduce_out(quantized_matmul(y, w2))
        else:
            out = _row_parallel_out(ctx, y, w2.astype(x.dtype))
        if "fc2_bias" in lp:
            out = out + lp["fc2_bias"].astype(x.dtype)
        return out


def _layer(cfg: TransformerConfig, lp: dict, x, ctx: TPContext,
           attention_mask, rope, rngs):
    """Pre-LN transformer block (reference ParallelTransformerLayer :598:
    LN → attn → residual → LN → MLP → residual, bias_dropout_add fused).

    ``jax.named_scope`` blocks are the NVTX-range analog (reference DDP
    ``prof`` flag, distributed.py:193; SURVEY.md §5) — they label the
    profiler trace in xprof/TensorBoard without touching the compute.
    """
    r1, r2, r3, r4, r5 = (rngs if rngs is not None
                          else (None,) * 5)
    with jax.named_scope("ln1"):
        h = apply_norm(cfg, x, lp["ln1_scale"], lp["ln1_bias"])
    with jax.named_scope("attention"):
        a = _attention(cfg, lp, h, ctx, attention_mask, rope, r1)
    # residual source: block input, or the LN output under the
    # apply_residual_connection_post_layernorm flag (reference
    # standalone_transformer_lm.py:707-710)
    res = h if cfg.apply_residual_connection_post_layernorm else x
    with jax.named_scope("residual"):
        x = res + _drop_path(_dropout(a, cfg.hidden_dropout, r2),
                             cfg.drop_path_rate, r4)
    with jax.named_scope("ln2"):
        h = apply_norm(cfg, x, lp["ln2_scale"], lp["ln2_bias"])
    with jax.named_scope("mlp"):
        if cfg.num_experts:
            m, aux = _moe_mlp(cfg, lp, h)
        else:
            m = _mlp(cfg, lp, h, ctx)
            aux = jnp.float32(0.0)
    res = h if cfg.apply_residual_connection_post_layernorm else x
    with jax.named_scope("residual"):
        x = res + _drop_path(_dropout(m, cfg.hidden_dropout, r3),
                             cfg.drop_path_rate, r5)
    return ctx.constrain_hidden(x), aux


def vocab_parallel_embed(table, tokens, ctx: TPContext):
    """Masked local lookup + allreduce (reference VocabParallelEmbedding
    :167) in manual mode; plain take under GSPMD."""
    if not ctx.vocab_parallel:
        return jnp.take(table, tokens, axis=0)
    axis = ctx.tp_axis
    n_local = table.shape[0]
    start = jax.lax.axis_index(axis) * n_local
    local = tokens - start
    in_range = (local >= 0) & (local < n_local)
    local = jnp.clip(local, 0, n_local - 1)
    out = jnp.take(table, local, axis=0)
    out = jnp.where(in_range[..., None], out, 0).astype(table.dtype)
    return jax.lax.psum(out, axis)


def embed_tokens(emb: dict, tokens, cfg: TransformerConfig,
                 ctx: TPContext):
    """Word embedding lookup + learned position add (shared by the GSPMD
    forward and the shard_map pipeline stage)."""
    cd = cfg.compute_dtype
    with jax.named_scope("embed"):
        h = vocab_parallel_embed(emb["word"].astype(cd), tokens, ctx)
        if cfg.position_embedding_type == "learned":
            h = h + emb["position"][: tokens.shape[1]].astype(cd)[None]
    return h


def lm_head_weight(params: dict, cfg: TransformerConfig):
    """Tied/untied output-head weight [v, h] (the single home for the
    selection — reference parallel_lm_logits' tied-weight argument)."""
    return (params["lm_head"]["kernel"]
            if cfg.untie_embeddings_and_output_weights
            else params["embedding"]["word"])


def lm_head_logits(params: dict, hidden, cfg: TransformerConfig):
    """Final-hidden → vocab logits with tied/untied head selection
    (reference parallel_lm_logits, standalone_transformer_lm.py:1130)."""
    head = lm_head_weight(params, cfg)
    # [b,s,h] @ [v,h]^T; vocab dim sharded over tp in both modes
    return jnp.einsum(
        "bsh,vh->bsv", hidden, head.astype(cfg.compute_dtype),
        preferred_element_type=jnp.float32,
    )


def transformer_backbone(params: dict, hidden, cfg: TransformerConfig,
                         ctx: TPContext, *, attention_mask=None,
                         dropout_rng=None, apply_final_norm: bool = True,
                         with_aux: bool = False,
                         with_counters: bool = False):
    """The scanned decoder stack + final norm. ``hidden`` [b, s, h].

    ``with_aux=True`` additionally returns the summed per-layer auxiliary
    loss (the MoE load-balance term; 0 for dense configs);
    ``with_counters=True`` (hybrid stacks) the expert layers' assignment
    counters after it (models/hybrid.py ``moe_counters``)."""
    s = hidden.shape[1]
    rope = None
    if cfg.position_embedding_type == "rope":
        rope = rope_cos_sin(s, cfg.kv_channels, cfg.rope_theta)
    if cfg.is_hybrid:
        from apex_tpu.models.hybrid import hybrid_backbone

        if attention_mask is not None or dropout_rng is not None:
            raise ValueError("a hybrid stack takes no attention mask "
                             "and no dropout")
        hidden, counters = hybrid_backbone(params, hidden, cfg, ctx, rope)
        if apply_final_norm:
            with jax.named_scope("final_ln"):
                hidden = apply_norm(cfg, hidden,
                                    params["final_ln"]["scale"],
                                    params["final_ln"].get("bias"))
        out = ((hidden,) + (jnp.float32(0.0),) * with_aux
               + (counters,) * with_counters)
        return out if len(out) > 1 else hidden
    if with_counters:
        raise ValueError("only a hybrid stack counts its assignments")

    n_layers = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]

    def body(carry, layer_in):
        x, aux_acc = carry
        lp, key = layer_in
        rngs = jax.random.split(key, 5) if key is not None else None
        x, aux = _layer(cfg, lp, x, ctx, attention_mask, rope, rngs)
        return (x, aux_acc + aux), None

    # a rematted layer recomputes everything but the flash kernel's
    # output and logsumexp: keeping them (one more x-sized residual a
    # layer) takes the forward kernel out of the backward pass
    step = jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(
            *REMAT_SAVED_NAMES)) if cfg.remat else body

    needs_rng = dropout_rng is not None and (
        cfg.hidden_dropout > 0 or cfg.attention_dropout > 0
        or cfg.drop_path_rate > 0)
    keys = jax.random.split(dropout_rng, n_layers) if needs_rng else None

    aux0 = jnp.float32(0.0)
    # inside shard_map the per-layer aux inherits the hidden's varying
    # axes (e.g. 'pp' in a pipeline stage) — the scan carry must start
    # with the same type
    for axis in jax.typeof(hidden).vma:
        from apex_tpu.utils.collectives import pvary as _pvary_

        aux0 = _pvary_(aux0, axis)
    with jax.named_scope("backbone"):
        if cfg.scan_layers:
            (hidden, aux), _ = jax.lax.scan(
                step, (hidden, aux0), (params["layers"], keys))
        else:
            carry = (hidden, aux0)
            for i in range(n_layers):
                lp = jax.tree_util.tree_map(
                    lambda v: v[i], params["layers"])
                carry, _ = step(carry, (lp, keys[i] if needs_rng else None))
            hidden, aux = carry

    if not apply_final_norm:
        return (hidden, aux) if with_aux else hidden
    with jax.named_scope("final_ln"):
        out = apply_norm(cfg, hidden, params["final_ln"]["scale"],
                         params["final_ln"].get("bias"))
    return (out, aux) if with_aux else out


def gpt_forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
                ctx: Optional[TPContext] = None, *, attention_mask=None,
                dropout_rng=None, with_aux: bool = False):
    """Token ids [b, s] → logits (reference GPTModel.forward,
    standalone_gpt.py:45 → TransformerLanguageModel :1358 →
    parallel_lm_logits :1130).

    Logits come back tp-sharded on the vocab dim in manual mode (pair with
    ``vocab_parallel_cross_entropy``) and full under GSPMD.
    """
    ctx = ctx or single_device_ctx()
    h, aux = gpt_hidden(params, tokens, cfg, ctx,
                        attention_mask=attention_mask,
                        dropout_rng=dropout_rng)
    logits = lm_head_logits(params, h, cfg)
    return (logits, aux) if with_aux else logits


def gpt_hidden(params: dict, tokens: jax.Array, cfg: TransformerConfig,
               ctx: TPContext, *, attention_mask=None, dropout_rng=None,
               with_counters: bool = False):
    """Embed + decoder stack + final norm → (hidden [b,s,h], moe_aux[,
    counters]).  The shared prologue of :func:`gpt_forward` and the fused
    head+CE loss path."""
    h = ctx.constrain_hidden(embed_tokens(params["embedding"], tokens,
                                          cfg, ctx))
    return transformer_backbone(params, h, cfg, ctx,
                                attention_mask=attention_mask,
                                dropout_rng=dropout_rng, with_aux=True,
                                with_counters=with_counters)


def gpt_loss(params: dict, tokens: jax.Array, labels: jax.Array,
             cfg: TransformerConfig, ctx: Optional[TPContext] = None,
             *, attention_mask=None, dropout_rng=None,
             with_counters: bool = False, mtp_labels=None):
    """Mean next-token CE. Uses the fused xentropy op (GSPMD/single) or the
    vocab-parallel CE (manual TP) — reference post_language_model_processing
    (standalone_transformer_lm.py:1547 → tensor_parallel/cross_entropy.py:23).
    ``attention_mask`` (True = masked) feeds ``attn_mask_type='padding'``
    models; causal masking needs none.  ``with_counters=True`` (hybrid
    stacks with experts) returns ``(loss, counters)``.

    With ``cfg.mtp_layers`` the loss is ``main + cfg.mtp_loss_weight x
    mtp``: the multi-token-prediction module (models/hybrid.py
    ``mtp_loss``) reads the stack's normed output and the embedding of
    ``labels`` (the next tokens), and the SAME head scores it against
    ``mtp_labels``, the tokens two ahead.  Both terms come out among the
    counters as ``main_loss`` and ``mtp_loss``, and the module's expert
    layer adds to the assignment counters.
    """
    ctx = ctx or single_device_ctx()
    h, aux, *counters = gpt_hidden(params, tokens, cfg, ctx,
                                   attention_mask=attention_mask,
                                   dropout_rng=dropout_rng,
                                   with_counters=with_counters)

    def head_ce(h, labels):
        if cfg.fused_head_ce and not ctx.vocab_parallel:
            # fused head+CE: chunk the vocab matmul into the loss
            # (ops/lm_head_ce.py) — the [tokens, vocab] logits are never
            # materialized
            from apex_tpu.ops.lm_head_ce import lm_head_cross_entropy

            head = lm_head_weight(params, cfg).astype(cfg.compute_dtype)
            losses = lm_head_cross_entropy(
                h, head, labels, chunk=cfg.head_ce_chunk, ignore_index=-1)
            n_valid = jnp.maximum(jnp.sum(labels != -1), 1)
            return jnp.sum(losses) / n_valid.astype(jnp.float32)
        return lm_cross_entropy(lm_head_logits(params, h, cfg), labels, ctx)

    with jax.named_scope("lm_head_ce"):
        loss = head_ce(h, labels)
    if cfg.mtp_layers:
        if mtp_labels is None:
            raise ValueError("cfg.mtp_layers: the loss takes mtp_labels, "
                             "the tokens two ahead")
        from apex_tpu.models.hybrid import mtp_loss

        mtp_term, more = mtp_loss(params, h, labels, mtp_labels, cfg, ctx,
                                  head_ce)
        if with_counters:
            total = {k: v + more[k] for k, v in counters[0].items()}
            counters = [dict(total, main_loss=loss, mtp_loss=mtp_term)]
        loss = loss + cfg.mtp_loss_weight * mtp_term
    if cfg.num_experts and cfg.moe_aux_loss_coeff:
        # Switch load-balance term, mean over layers
        loss = loss + cfg.moe_aux_loss_coeff * aux / cfg.num_layers
    return (loss, counters[0]) if with_counters else loss


def lm_cross_entropy(logits, labels, ctx: TPContext) -> jax.Array:
    """Mean token CE over (possibly vocab-sharded) logits; labels of -1 are
    padding and contribute zero (both paths agree — the fused xentropy op's
    ``padding_idx`` semantics, xentropy_kernel.cu:431-436)."""
    if ctx.vocab_parallel:
        from apex_tpu.transformer.tensor_parallel.cross_entropy import (
            vocab_parallel_cross_entropy,
        )
        losses = vocab_parallel_cross_entropy(
            logits.astype(jnp.float32), labels, ctx.tp_axis)
        losses = jnp.where(labels == -1, 0.0, losses)
    else:
        losses = softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]),
            jnp.maximum(labels.reshape(-1), 0),
            padding_idx=None,
        )
        losses = jnp.where(labels.reshape(-1) == -1, 0.0, losses)
    # normalize by non-padding count (Megatron loss_mask.sum() semantics)
    n_valid = jnp.maximum(jnp.sum(labels != -1), 1)
    return jnp.sum(losses) / n_valid.astype(jnp.float32)
