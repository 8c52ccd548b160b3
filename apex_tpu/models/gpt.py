"""GPT model wiring: GSPMD train step + shard_map pipeline stages.

Reference analogs: apex/transformer/testing/standalone_gpt.py (``GPTModel``
:45, ``gpt_model_provider`` :33) and the minimal train loops in
tests/L0/run_transformer/run_gpt_minimal_test.py. Two composition modes:

- :func:`make_gpt_train_step` — GSPMD: one jitted AMP train step over a
  ('pp','dp','sp','tp') mesh; dp+tp+sp come from sharding annotations
  (pp stays 1 on this path).
- :func:`make_gpt_pipeline_stage` / :func:`stack_pipeline_params` — the
  shard_map path: the decoder is cut into ``pp`` stages driven by the
  differentiable-scan schedules (pipeline_parallel/schedules.py), tensor
  parallelism via the manual mapping collectives inside each stage.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.amp.frontend import make_train_step
from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.transformer_lm import (
    apply_norm,
    gpt_loss,
    gpt_param_specs,
    gspmd_ctx,
    init_gpt_params,
    lm_cross_entropy,
    manual_ctx,
    single_device_ctx,
    transformer_backbone,
    embed_tokens,
    lm_head_logits,
)

__all__ = [
    "make_gpt_train_step",
    "make_gpt_pipeline_stage",
    "stack_pipeline_params",
    "stack_pipeline_params_vpp",
    "make_gpt_vpp_stage",
    "pipeline_packet",
    "gpt_pipeline_loss_and_grads",
    "gpt_vpp_loss_and_grads",
]


def make_gpt_train_step(
    cfg: TransformerConfig,
    optimizer: Any,
    policy_or_amp="O2",
    mesh: Optional[Mesh] = None,
    *,
    seq_axis: Optional[str] = None,
    context_parallel: Union[bool, str] = False,
    grad_postprocess: Optional[Callable] = None,
    fsdp: bool = False,
    norm_telemetry: bool = False,
    overlap_comm: Optional[bool] = None,
):
    """GSPMD data/tensor/sequence-parallel AMP train step.

    Returns ``(init_fn, step_fn)``; both are jitted against ``mesh`` when
    given. ``init_fn(rng)`` places params per :func:`gpt_param_specs`;
    ``step_fn(state, tokens, labels)`` is the full O2-style AMP step
    (scale → grad → unscale+finite-check → fused update → skip-on-overflow)
    with gradient mean over 'dp' handled by GSPMD sharding propagation.

    ``fsdp=True`` (ZeRO-3) additionally shards every parameter — and,
    through the state pytree, its fp32 master and optimizer moments —
    over the 'dp' axis on top of the tp specs (parallel/fsdp.py
    ``fsdp_augment_specs``); GSPMD inserts the per-layer all-gathers and
    backward reduce-scatters.  Beyond the reference: apex stops at
    ZeRO-2 (DistributedFusedAdam's optimizer-state sharding).

    Batch signature grows with the config: ``cfg.mtp_layers`` appends
    ``mtp_labels`` (the tokens two ahead, for the multi-token-prediction
    module; ``main_loss`` and ``mtp_loss`` then come out in the metrics
    beside ``loss``), ``attn_mask_type='padding'`` appends an
    ``attention_mask`` (True = masked) element, dropout appends a PRNG
    key — ``step(state, tokens, labels[, mtp_labels][, mask][, rng])``.

    ``context_parallel`` (requires ``seq_axis``) keeps core attention
    sequence-sharded — the long-context mode.  ``True``/``"ring"``
    selects ring attention (per-device attention memory O(s_local));
    ``"ulysses"`` selects all-to-all head re-sharding (one
    full-sequence flash call per head group; needs heads divisible by
    the axis size).  Both cover the flagship patterns only:
    ``attn_mask_type='padding'`` and ``attention_dropout > 0`` are
    rejected up front (they would silently fall back to the gathered
    path and OOM at exactly the lengths the flag exists for);
    ``hidden_dropout`` is fine.

    ``overlap_comm=True`` routes the tensor-parallel row-parallel exits
    (attention proj, MLP fc2) through the ring collective-matmul
    (``ops/collective_matmul``): the tp reduction is decomposed into
    ppermute hops overlapped with per-shard matmul chunks instead of one
    serialized all-reduce after the matmul.  Default ``None`` keeps the
    monolithic collectives unless an enclosing
    ``collective_matmul.overlap_scope`` turns the ring on.

    MoE configs (``cfg.num_experts``) additionally honor
    ``cfg.moe_routing``/``cfg.moe_comm``: ``moe_routing='ragged'`` makes
    every expert layer capacity-free (no dropped tokens, no pad slots)
    with its EP dispatch/combine running explicitly through the counted
    ``all_to_all`` wrappers at ``moe_comm`` wire precision — and the
    same ``overlap_comm`` scope that rings the TP exits also rings the
    expert dispatch/combine (per-hop expert compute inside the ring).
    """
    if context_parallel:
        if cfg.attn_mask_type == "padding":
            raise ValueError(
                "context_parallel does not support "
                "attn_mask_type='padding': the ring kernels have no "
                "sharded-mask path, so masked configs would silently "
                "gather K/V (O(s_global) memory). Pack sequences with "
                "segment-free causal rows instead.")
        if cfg.attention_dropout > 0:
            raise ValueError(
                "context_parallel does not support attention_dropout "
                "> 0 (the sequence-sharded attention paths run without "
                "in-kernel dropout); set attention_dropout=0 — "
                "hidden_dropout is unaffected.")
        if context_parallel == "ulysses" and mesh is not None:
            axes = dict(zip(mesh.axis_names, mesh.devices.shape))
            sp_size = axes.get(seq_axis, 1) if seq_axis else 1
            tp_size = axes.get("tp", 1)
            heads = cfg.num_attention_heads
            if heads % tp_size or (heads // tp_size) % sp_size:
                raise ValueError(
                    f"context_parallel='ulysses' needs num_attention_"
                    f"heads ({heads}) divisible by tp ({tp_size}) and "
                    f"the per-tp-rank heads ({heads // max(tp_size, 1)}) "
                    f"divisible by the '{seq_axis}' axis size "
                    f"({sp_size}); use context_parallel='ring' for "
                    "head counts that don't factor.")
    ctx = (gspmd_ctx(seq_axis=seq_axis,
                     context_parallel=context_parallel,
                     overlap_comm=overlap_comm)
           if mesh is not None else None)
    has_dropout = (cfg.hidden_dropout > 0 or cfg.attention_dropout > 0
                   or cfg.drop_path_rate > 0)
    has_mask = cfg.attn_mask_type == "padding"

    # a hybrid stack's expert layers count their assignments
    # (models/hybrid.py MOE_COUNTERS); the counters come out beside
    # ``loss`` and ``overflow`` in the step's metrics
    counted = cfg.is_hybrid and bool(cfg.num_experts)
    if cfg.is_hybrid and mesh is not None:
        raise ValueError(
            "a hybrid stack (cfg.layer_types / num_dense_layers) has no "
            "GSPMD partitioning; run it on one device")

    def loss_fn(params, tokens, labels, *rest):
        rest = list(rest)
        mtp_labels = rest.pop(0) if cfg.mtp_layers else None
        mask = rest.pop(0) if has_mask else None
        rng = rest.pop(0) if has_dropout else None
        return gpt_loss(params, tokens, labels, cfg, ctx,
                        attention_mask=mask, dropout_rng=rng,
                        with_counters=counted, mtp_labels=mtp_labels)

    init_fn, step_fn = make_train_step(
        loss_fn, optimizer, policy_or_amp,
        grad_postprocess=grad_postprocess,
        norm_telemetry=norm_telemetry,
        overlap_comm=overlap_comm,
        **({"has_aux": True} if counted else {}),
    )
    if counted:
        amp_step = step_fn

        def step_fn(state, *batch):   # noqa: F811
            state, metrics = amp_step(state, *batch)
            metrics.update(metrics.pop("aux"))
            return state, metrics

    def init(rng):
        params = init_gpt_params(rng, cfg)
        if mesh is not None:
            specs = gpt_param_specs(cfg)
            if fsdp:
                from apex_tpu.parallel.fsdp import fsdp_augment_specs

                axes = dict(zip(mesh.axis_names, mesh.devices.shape))
                if "dp" not in axes:
                    raise ValueError(
                        "make_gpt_train_step(fsdp=True) shards master "
                        "params over the 'dp' mesh axis, but this mesh "
                        f"has axes {tuple(mesh.axis_names)}; add a 'dp' "
                        "axis (e.g. create_mesh(dp=N)).")
                ndev = axes["dp"]
                specs = fsdp_augment_specs(specs, params, ndev)
            params = jax.device_put(
                params,
                jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), specs,
                    is_leaf=lambda x: isinstance(x, P),
                ),
            )
            state = init_fn(params)
            if fsdp:
                # The optimizer moments and fp32 masters are created as
                # fresh (replicated) arrays.  Every state subtree that
                # mirrors the params structure (masters, bf16 copies,
                # each Adam moment tree) is re-placed on the params'
                # shardings — matched by tree structure, not by array
                # shape, so equal-shape params with different specs
                # cannot collide.
                shardings = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), specs,
                    is_leaf=lambda x: isinstance(x, P))
                pstruct = jax.tree_util.tree_structure(params)

                def matches(sub):
                    try:
                        return (jax.tree_util.tree_structure(sub)
                                == pstruct)
                    except Exception:
                        return False

                replicated = NamedSharding(mesh, P())

                def place(sub):
                    if matches(sub):
                        return jax.device_put(sub, shardings)
                    if isinstance(sub, jax.Array) and sub.ndim == 0:
                        # scalar state ONLY (step counter, loss scale):
                        # explicitly mesh-replicated, so checkpoint
                        # restore cannot pin it to one device while the
                        # masters span the mesh.  Non-scalar arrays in
                        # exotic optimizer-state structures are left
                        # alone — force-replicating a param-sized moment
                        # buffer would silently defeat ZeRO-3.
                        return jax.device_put(sub, replicated)
                    return sub

                state = jax.tree_util.tree_map(
                    place, state, is_leaf=matches)
            return state
        return init_fn(params)

    if mesh is None:
        return init, jax.jit(step_fn, donate_argnums=0)

    batch_sharding = NamedSharding(mesh, P("dp", seq_axis))
    shardings = (None, batch_sharding, batch_sharding)
    if has_mask:
        # (b, 1, sq, sk) or (b, sq, sk) boolean padding mask
        shardings = shardings + (NamedSharding(mesh, P("dp")),)
    if has_dropout:
        shardings = shardings + (NamedSharding(mesh, P()),)
    jstep = jax.jit(step_fn, in_shardings=shardings, donate_argnums=0)

    def step(state, *batch):
        # the mesh context activates the model's with_sharding_constraint
        # annotations (bare PartitionSpecs need an ambient mesh)
        with jax.set_mesh(mesh):
            return jstep(state, *batch)

    return init, step


# ---------------------------------------------------------------------------
# shard_map pipeline path
# ---------------------------------------------------------------------------


def pipeline_packet(tokens_mb: jax.Array, labels_mb: jax.Array,
                    cfg: TransformerConfig, *,
                    attention_mask_mb: Optional[jax.Array] = None,
                    dropout_seeds: Optional[jax.Array] = None) -> dict:
    """The activation packet ppermuted between stages.

    The schedules require one uniform pytree for injection and transfer
    (schedules.py ``pipeline_forward``), so token/label ids ride alongside
    the hidden activation and the last stage banks its per-microbatch loss
    in the ``loss`` slot. [n_micro, mb, s] token arrays → packets of
    hidden [mb, s, h].

    ``attention_mask_mb`` ([n_micro, mb, s] bool, True = masked key) rides
    in the packet when the model needs padding masks
    (cfg.attn_mask_type == 'padding' — BERT-style).  ``dropout_seeds``
    ([n_micro] int32) seeds per-microbatch dropout; each stage folds its
    own pp index in so no two (stage, microbatch) pairs share a stream —
    the pipeline analog of the reference's per-region RNG tracker
    (tensor_parallel/random.py CudaRNGStatesTracker).
    """
    mb, s = tokens_mb.shape[-2], tokens_mb.shape[-1]
    packet = {
        "hidden": jnp.zeros((*tokens_mb.shape[:-2], mb, s, cfg.hidden_size),
                            cfg.compute_dtype),
        "tokens": tokens_mb,
        "labels": labels_mb,
        "loss": jnp.zeros(tokens_mb.shape[:-2], jnp.float32),
    }
    if cfg.num_experts:
        # running MoE load-balance aux: every stage adds its layers'
        # contribution as the packet rides the pipeline; the last stage
        # folds it into the loss (gpt_loss semantics)
        packet["aux"] = jnp.zeros(tokens_mb.shape[:-2], jnp.float32)
    if attention_mask_mb is not None:
        packet["attention_mask"] = attention_mask_mb
    if dropout_seeds is not None:
        packet["dropout_seed"] = dropout_seeds.astype(jnp.int32)
    return packet


def stack_pipeline_params(params: dict, cfg: TransformerConfig,
                          n_stages: int) -> dict:
    """Cut the layer stack into ``n_stages`` chunks with a leading pp axis.

    Embedding / final-LN / head stay unstacked (replicated across pp via
    ``in_specs=P()``; shard_map's AD psums their grads, and only the stages
    that consume them contribute non-zeros — the reference ties embeddings
    with an explicit embedding-group allreduce instead,
    standalone_transformer_lm.py:49 ``MegatronModule.word_embeddings_weight``).
    """
    L = cfg.num_layers
    if L % n_stages:
        raise ValueError(f"num_layers {L} not divisible by pp {n_stages}")
    per = L // n_stages
    layers = jax.tree_util.tree_map(
        lambda v: v.reshape((n_stages, per) + v.shape[1:]), params["layers"])
    out = dict(params)
    out["layers"] = layers
    return out


def gpt_pipeline_loss_and_grads(
    stage_fn: Callable,
    stacked_params: dict,
    packets: dict,
    *,
    n_micro: int,
    pp_axis: str = "pp",
    remat: bool = True,
):
    """Run the 1F1B scan schedule on GPT stage params; call inside shard_map.

    Non-layer params (embedding, final LN, LM head) are replicated across
    'pp'; they are marked pp-varying for the scan schedule's carry typing
    and their gradients psum'd afterwards — the explicit form of the
    reference's embedding-group allreduce
    (apex/transformer/parallel_state.py:184-310 _EMBEDDING_GROUP;
    standalone_transformer_lm.py:49 shared word_embeddings_weight).
    """
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_without_interleaving,
    )
    from apex_tpu.utils.collectives import pvary

    varying = pvary(stacked_params, pp_axis)
    loss, grads = forward_backward_pipelining_without_interleaving(
        stage_fn, packets, varying,
        n_micro=n_micro,
        loss_fn=lambda out, _mb: out["loss"],
        axis=pp_axis,
        remat=remat,
    )
    grads = {
        k: (v if k == "layers"
            else jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, pp_axis), v))
        for k, v in grads.items()
    }
    return loss, grads


def make_gpt_pipeline_stage(cfg: TransformerConfig, n_stages: int,
                            tp: int = 1, *, pp_axis: str = "pp",
                            tp_axis: str = "tp") -> Callable:
    """Build ``stage_fn(stage_params, packet) -> packet`` for the scan
    schedules (reference forward_step, schedules/common.py:253).

    Every device runs the same program; stage behavior is selected by
    ``lax.axis_index(pp_axis)``: stage 0 embeds tokens, inner stages
    transform the hidden, the last stage applies the final norm + LM head
    and writes the per-microbatch loss into the packet. TP inside a stage
    uses the manual mapping collectives over ``tp_axis``.

    Dropout keys and padding masks ride in the packet (see
    :func:`pipeline_packet`); the LM head + CE run under ``lax.cond`` so
    only the last stage pays their FLOPs — safe because all members of a
    tp group share one pp index, so the vocab-parallel collectives inside
    the branch cannot diverge across a tp group.

    MoE configs compose with the pipeline since round 3: each stage runs
    its experts *locally* (replicated within the stage — the packet
    threads the running load-balance aux loss to the last stage, which
    folds it into the CE like ``gpt_loss``).  Sharding experts over an
    'ep' mesh axis *inside* shard_map would need hand-written
    all-to-alls; that combination stays on the GSPMD path
    (``make_gpt_train_step`` over a mesh with an 'ep' axis), where the
    partitioner inserts them from the annotations.
    """
    if cfg.num_experts:
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_ep_axis=None)
    ctx = manual_ctx(tp, tp_axis) if tp > 1 else single_device_ctx()

    def stage_fn(sp: dict, packet: dict) -> dict:
        my = jax.lax.axis_index(pp_axis)
        first = my == 0
        last = my == n_stages - 1
        cd = cfg.compute_dtype
        tokens, labels = packet["tokens"], packet["labels"]
        mask = packet.get("attention_mask")
        seed = packet.get("dropout_seed")
        if cfg.attn_mask_type == "padding" and mask is None:
            raise ValueError(
                "attn_mask_type='padding' needs the key-padding mask in "
                "the packet: pipeline_packet(..., attention_mask_mb=...)"
            )
        if (cfg.hidden_dropout > 0 or cfg.attention_dropout > 0
                or cfg.drop_path_rate > 0) and seed is None:
            raise ValueError(
                "dropout is enabled but the packet carries no "
                "dropout_seed: pipeline_packet(..., dropout_seeds=...) "
                "(silently training without dropout would diverge from "
                "the configured model)"
            )
        rng = None
        if seed is not None and (
                cfg.hidden_dropout > 0 or cfg.attention_dropout > 0
                or cfg.drop_path_rate > 0):
            # distinct stream per (stage, microbatch): the seed is
            # per-microbatch, each stage folds in its pp index (attention
            # additionally folds the tp index in — see _attention)
            rng = jax.random.fold_in(jax.random.PRNGKey(seed), my)

        # first stage only (same lax.cond treatment as the head: under
        # manual TP the vocab-parallel embed carries a psum, and all tp
        # peers share one pp index, so branches cannot diverge).  Both
        # branches pvary'd so their varying-axes types unify.
        from apex_tpu.utils.collectives import pvary as _pvary

        h = jax.lax.cond(
            first,
            lambda: _pvary(
                embed_tokens(sp["embedding"], tokens, cfg, ctx
                             ).astype(packet["hidden"].dtype), pp_axis),
            lambda: _pvary(packet["hidden"], pp_axis))

        # this stage's layer chunk: local leading pp dim of size 1
        layers = jax.tree_util.tree_map(lambda v: v[0], sp["layers"])
        h, aux_local = transformer_backbone(
            {"layers": layers}, h, cfg, ctx, attention_mask=mask,
            dropout_rng=rng, apply_final_norm=False, with_aux=True)
        aux = None
        if cfg.num_experts:
            aux = _pvary(packet["aux"], pp_axis) + aux_local

        def head_and_ce(h_in):
            h_final = apply_norm(cfg, h_in, sp["final_ln"]["scale"],
                                 sp["final_ln"]["bias"])
            logits = lm_head_logits(sp, h_final, cfg)
            ce = lm_cross_entropy(logits, labels, ctx)
            if cfg.num_experts:
                # fold the accumulated load-balance term in exactly like
                # gpt_loss (mean over layers)
                ce = ce + cfg.moe_aux_loss_coeff * aux / cfg.num_layers
            return ce

        # last stage only: the v/12h-per-stage FLOP tax of running the
        # head everywhere (round-1 design) is gone.  The false branch's
        # zero must carry the same varying-axes type as the head output
        # (pp-varying), hence the pvary.
        loss = jax.lax.cond(
            last, head_and_ce,
            lambda _h: _pvary(jnp.float32(0.0), pp_axis), h)

        out = {
            "hidden": h.astype(cd),
            "tokens": tokens,
            "labels": labels,
            "loss": loss,
        }
        if aux is not None:
            out["aux"] = aux
        if mask is not None:
            out["attention_mask"] = mask
        if seed is not None:
            out["dropout_seed"] = seed
        return out

    return stage_fn


# ---------------------------------------------------------------------------
# interleaved virtual-pipeline (vpp) path
# ---------------------------------------------------------------------------


def stack_pipeline_params_vpp(params: dict, cfg: TransformerConfig,
                              n_stages: int, vpp: int) -> dict:
    """Cut the layer stack into ``n_stages * vpp`` chunks stacked
    [vpp, pp, layers_per_chunk, ...] (chunk c = j*pp + d lives on device
    d slot j — the interleaved schedule's placement,
    reference fwd_bwd_pipelining_with_interleaving.py:26 / build_model
    virtual chunks, schedules/common.py:30).

    A ``chunk_id`` leaf rides along so the stage can tell which global
    chunk it is holding (the schedule slices slot j and shard_map shards
    device d; the value that arrives is exactly ``j*pp + d``).
    """
    L = cfg.num_layers
    n_chunks = n_stages * vpp
    if L % n_chunks:
        raise ValueError(
            f"num_layers {L} not divisible by pp*vpp = {n_chunks}")
    per = L // n_chunks
    layers = jax.tree_util.tree_map(
        lambda v: v.reshape((vpp, n_stages, per) + v.shape[1:]),
        params["layers"])
    # the interleaved schedule slices slot j from EVERY leaf, so the
    # replicated (embedding / final-LN / head) params get a broadcast
    # leading vpp dim (lazy under jit — no real copy)
    out = {
        k: jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (vpp,) + a.shape), v)
        for k, v in params.items() if k != "layers"
    }
    out["layers"] = layers
    # float32 so the leaf is differentiable-typed (its grad is zero);
    # value_and_grad in the schedule rejects integer params
    out["chunk_id"] = jnp.arange(n_chunks, dtype=jnp.float32).reshape(
        vpp, n_stages)
    return out


def make_gpt_vpp_stage(cfg: TransformerConfig, n_stages: int, vpp: int,
                       tp: int = 1, *, tp_axis: str = "tp") -> Callable:
    """Chunk-apply function for the interleaved schedule:
    ``stage_fn(chunk_params, packet) -> packet``.

    Chunk identity comes from the ``chunk_id`` leaf (global chunk
    ``c = j*pp + my``): chunk 0 embeds, chunk ``pp*vpp - 1`` runs the
    final norm + LM head + CE — both under ``lax.cond`` so only the
    owning chunk pays the FLOPs (same argument as
    :func:`make_gpt_pipeline_stage`).
    """
    from apex_tpu.utils.collectives import pvary as _pvary

    if cfg.num_experts:
        # experts run locally per chunk; aux rides the packet — see
        # make_gpt_pipeline_stage (EP×PP sharded routing is GSPMD-only)
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_ep_axis=None)
    ctx = manual_ctx(tp, tp_axis) if tp > 1 else single_device_ctx()
    n_chunks = n_stages * vpp
    pp_axis = "pp"

    def stage_fn(sp: dict, packet: dict) -> dict:
        cid = sp["chunk_id"][0] if sp["chunk_id"].ndim else sp["chunk_id"]
        first = cid == 0
        last = cid == n_chunks - 1
        cd = cfg.compute_dtype
        tokens, labels = packet["tokens"], packet["labels"]
        mask = packet.get("attention_mask")
        seed = packet.get("dropout_seed")
        rng = None
        if seed is not None and (
                cfg.hidden_dropout > 0 or cfg.attention_dropout > 0
                or cfg.drop_path_rate > 0):
            rng = jax.random.fold_in(jax.random.PRNGKey(seed),
                                     cid.astype(jnp.int32))

        h = jax.lax.cond(
            first,
            lambda: _pvary(
                embed_tokens(sp["embedding"], tokens, cfg, ctx
                             ).astype(packet["hidden"].dtype), pp_axis),
            lambda: _pvary(packet["hidden"], pp_axis))

        # this chunk's layer slice: leading dims already sliced down to
        # the local (per-chunk) stack by the schedule + shard_map
        layers = jax.tree_util.tree_map(lambda v: v[0], sp["layers"])
        h, aux_local = transformer_backbone(
            {"layers": layers}, h, cfg, ctx, attention_mask=mask,
            dropout_rng=rng, apply_final_norm=False, with_aux=True)
        aux = None
        if cfg.num_experts:
            aux = _pvary(packet["aux"], pp_axis) + aux_local

        def head_and_ce(h_in):
            h_final = apply_norm(cfg, h_in, sp["final_ln"]["scale"],
                                 sp["final_ln"]["bias"])
            logits = lm_head_logits(sp, h_final, cfg)
            ce = lm_cross_entropy(logits, labels, ctx)
            if cfg.num_experts:
                ce = ce + cfg.moe_aux_loss_coeff * aux / cfg.num_layers
            return ce

        loss = jax.lax.cond(
            last, head_and_ce,
            lambda _h: _pvary(jnp.float32(0.0), pp_axis), h)

        out = {
            "hidden": h.astype(cd),
            "tokens": tokens,
            "labels": labels,
            "loss": loss,
        }
        if aux is not None:
            out["aux"] = aux
        if mask is not None:
            out["attention_mask"] = mask
        if seed is not None:
            out["dropout_seed"] = seed
        return out

    return stage_fn


def gpt_vpp_loss_and_grads(
    stage_fn: Callable,
    stacked_params: dict,
    packets: dict,
    *,
    n_micro: int,
    vpp: int,
    pp_axis: str = "pp",
    remat: bool = True,
):
    """Interleaved-schedule loss+grads for GPT; call inside shard_map.

    Same grad handling as :func:`gpt_pipeline_loss_and_grads`: layer
    grads are per-chunk exact, the replicated embedding/head/final-LN
    grads are psum'd over 'pp' (embedding-group allreduce analog)."""
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_with_interleaving,
    )
    from apex_tpu.utils.collectives import pvary

    varying = pvary(stacked_params, pp_axis)
    loss, grads = forward_backward_pipelining_with_interleaving(
        stage_fn, packets, varying,
        n_micro=n_micro,
        num_model_chunks=vpp,
        loss_fn=lambda out, _mb: out["loss"],
        axis=pp_axis,
        remat=remat,
    )
    # layers: exact per-chunk grads, stacked.  Replicated params: sum the
    # per-slot contributions (vpp dim) then psum over pp (the embedding-
    # group allreduce analog).  chunk_id is a constant — dropped.
    out = {}
    for k, v in grads.items():
        if k == "layers":
            out[k] = v
        elif k == "chunk_id":
            continue
        else:
            out[k] = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(jnp.sum(g, axis=0), pp_axis), v)
    return loss, out
