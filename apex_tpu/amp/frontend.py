"""amp.initialize / train-step construction.

Reference flow (apex/amp/frontend.py:259 → _initialize.py:147): cast the
model, patch ``forward`` to cast inputs, build fp32 master weights, patch
``optimizer.step`` to run master→model copies, create per-loss ``LossScaler``s,
and expose ``amp.scale_loss`` as a context manager (handle.py:17).

Under jit the same responsibilities become *construction* of a pure train
step: ``make_train_step(loss_fn, optimizer, policy)`` returns ``init``/``step``
functions where

- params live in ``policy.param_dtype`` (model weights), master weights in
  fp32 inside the train state when ``policy.master_weights``,
- the loss is scaled before grad, grads unscaled + finite-checked after,
- the optimizer update is *selected against* (not branched over) on overflow,
  keeping the whole step host-sync-free — the reference's skip-step patch
  (handle.py:128-154) becomes a ``jnp.where``,
- the scaler state update follows scaler.py:206-226 window doubling.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from apex_tpu.amp import scaler as scaler_lib
from apex_tpu.amp.policy import Policy, policy_for_opt_level

__all__ = [
    "AmpState",
    "initialize",
    "make_train_step",
    "state_dict",
    "load_state_dict",
    "save_train_state",
    "restore_train_state",
]


class AmpState(NamedTuple):
    """What ``amp.initialize`` hands back (policy + scaler)."""

    policy: Policy
    loss_scale_config: scaler_lib.LossScaleConfig
    loss_scale_state: scaler_lib.LossScaleState


def initialize(
    opt_level: Union[str, Policy] = "O1",
    num_losses: int = 1,
    **overrides,
):
    """Resolve an opt level into an :class:`AmpState`.

    ``num_losses`` mirrors the reference's per-loss scaler list
    (_initialize.py:229-233): with ``num_losses > 1`` a *list* of
    independent :class:`AmpState` objects is returned, one per loss, each
    usable with :func:`make_train_step`.
    """
    policy = policy_for_opt_level(opt_level, **overrides)

    def one():
        cfg, state = scaler_lib.init_loss_scale(policy.loss_scale)
        return AmpState(policy, cfg, state)

    if num_losses > 1:
        return [one() for _ in range(num_losses)]
    return one()


class TrainState(NamedTuple):
    step: jax.Array
    params: Any                       # model-dtype params
    master_params: Any                # fp32 masters (== params when disabled)
    opt_state: Any
    loss_scale_state: scaler_lib.LossScaleState
    # per-leaf error-feedback residuals when grad_comm compresses with
    # error feedback (comm.init_error_state layout); None otherwise
    comm_state: Any = None


def make_train_step(
    loss_fn: Callable,
    optimizer: Any,
    policy_or_amp: Union[str, Policy, AmpState] = "O1",
    *,
    axis_name: Optional[str] = None,
    has_aux: bool = False,
    grad_postprocess: Optional[Callable[[Any], Any]] = None,
    accum_steps: int = 1,
    main_grad_dtype=jnp.float32,
    norm_telemetry: bool = False,
    grad_comm=None,
    overlap_comm: Optional[bool] = None,
) -> Tuple[Callable, Callable]:
    """Build ``(init_fn, step_fn)`` implementing the full AMP training step.

    Args:
      loss_fn: ``loss_fn(params, *batch) -> loss`` (or ``(loss, aux)`` with
        ``has_aux``). Receives params already cast to the compute dtype.
      optimizer: an optax-style ``GradientTransformation`` (e.g.
        ``apex_tpu.optimizers.fused_adam(...)``).
      policy_or_amp: opt level name, Policy, or AmpState.
      axis_name: if set, grads are ``lax.pmean``-ed and the overflow flag
        ``lax.pmax``-ed over this mesh axis — the fusion of apex DDP's grad
        allreduce (apex/parallel/distributed.py:426) with the transformer
        GradScaler's found-inf allreduce (apex/transformer/amp/grad_scaler.py:21).
      grad_postprocess: optional hook applied to unscaled fp32 grads
        (e.g. clipping).
      accum_steps: gradient accumulation with **fp32 main-grad** semantics
        (reference ``fused_weight_gradient_dense.cpp:19-20``
        ``wgrad_gemm_accum_fp32`` + the ``main_grad`` path in
        ``apex/transformer/tensor_parallel/layers.py:272``): the batch's
        leading dim is split into ``accum_steps`` microbatches scanned
        sequentially, each microbatch's (bf16-computed) grads are
        accumulated into a persistent ``main_grad_dtype`` buffer, and one
        optimizer step runs on the accumulated total.  This keeps bf16
        training's accumulated wgrad at fp32 fidelity instead of summing
        rounded bf16 grads.
      main_grad_dtype: dtype of the accumulation buffer (fp32 default).
      grad_comm: gradient-communication spec (requires ``axis_name``):
        ``None`` keeps the plain vma-aware pmean; ``"fp32"`` is the
        same reduction spelled explicitly; ``"bf16"`` / ``"int8"`` (or
        a ``comm.GradCommConfig``) route the reduction through
        ``apex_tpu.comm`` — greedy size-bucketed, block-scaled
        quantized reduce-scatter + all-gather collectives.  With
        compression the step differentiates w.r.t. ``pvary``-ed params
        so gradients arrive per-shard (SPMD-AD's implicit psum would
        otherwise reduce at fp32 before compression could help), and
        when the config enables error feedback (int8 default) the
        train state carries per-leaf fp32 residuals
        (``TrainState.comm_state``) so quantization error cancels
        across steps instead of accumulating.  The residuals are
        rank-local: a shard_map wrapper must spec them
        ``P(axis_name)`` (``make_ddp_train_step`` does this; see
        ``comm.error_state_spec`` for custom wrappers).
      overlap_comm: tensor-parallel comm-overlap tri-state.  When set
        (``True``/``False``), ``loss_fn`` is traced inside
        ``ops.collective_matmul.overlap_scope(overlap_comm)``: TP
        contexts built with ``overlap_comm=None`` (the ``gspmd_ctx`` /
        ``manual_ctx`` default) then route their row-parallel exits
        through the overlapped ring collective-matmul (or keep the
        monolithic collectives, on ``False``) without the model wiring
        ever seeing this train-step flag.  ``None`` (default) leaves
        whatever scope the caller established.
      norm_telemetry: when True the metrics dict additionally carries
        ``grad_norm``, ``update_norm``, ``param_norm`` and
        ``update_to_param_ratio`` (``optimizers._common.norm_metrics``
        over the unscaled fp32 grads / the optimizer's updates / the
        master params).  OFF by default: each norm is a full-tree
        reduction.  Record them host-side at the step boundary with
        ``observability.record_step_metrics(metrics)``.

    The returned ``step_fn(state, *batch) -> (state, metrics)`` is pure and
    jittable; metrics carry ``loss``, ``overflow``, ``loss_scale`` and
    ``step`` (this step's index).  Feeding that dict to
    ``observability.record_step_metrics`` at the step boundary is the
    whole diagnostics hookup: it records the gauges, stamps records
    with the step index, fills the flight recorder's ring, and runs
    the anomaly detectors (loss-spike / grad-norm / NaN first-seen —
    with ``norm_telemetry=True`` the grad/update norms give the
    detectors their earliest signal); ``amp.scaler.record_scaler_step``
    additionally feeds the scaler-thrash detector.
    """
    if isinstance(policy_or_amp, AmpState):
        amp_state = policy_or_amp
    else:
        amp_state = initialize(policy_or_amp)
    policy, ls_cfg = amp_state.policy, amp_state.loss_scale_config

    if overlap_comm is not None:
        from apex_tpu.ops.collective_matmul import overlap_scope

        _user_loss_fn = loss_fn

        def loss_fn(params, *batch):   # noqa: F811
            with overlap_scope(overlap_comm):
                return _user_loss_fn(params, *batch)

    comm_cfg = None
    if grad_comm is not None:
        from apex_tpu import comm as comm_lib

        comm_cfg = comm_lib.resolve(grad_comm)
        if axis_name is None:
            raise ValueError(
                "grad_comm is a cross-shard gradient reduction spec and "
                "needs axis_name= to name the mesh axis to reduce over")
    compressing = comm_cfg is not None and comm_cfg.compresses
    use_ef = compressing and comm_cfg.use_error_feedback

    def init_fn(params) -> TrainState:
        # Copy even when the cast is an identity: astype-to-same-dtype
        # aliases, and aliasing the caller's arrays means a later
        # donate_argnums on the train state would delete the caller's own
        # params out from under them.
        def own(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True)
                if isinstance(x, jax.Array) else x,
                tree,
            )

        model_params = own(policy.cast_params(params))
        master = (
            own(policy.cast_master(params))
            if policy.master_weights
            else model_params
        )
        opt_state = optimizer.init(master)
        comm_state = None
        if use_ef:
            from apex_tpu import comm as comm_lib

            # leading rank axis of 1: a shard_map wrapper expands it to
            # the axis size and shards it P(axis) (rank-local residuals)
            comm_state = comm_lib.init_error_state(master)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=model_params,
            master_params=master,
            opt_state=opt_state,
            # own() here too: amp_state is shared by every init() from
            # this factory, and a donated step would otherwise delete the
            # shared scale buffers out from under later init() calls
            loss_scale_state=own(amp_state.loss_scale_state),
            comm_state=comm_state,
        )

    def step_fn(state: TrainState, *batch):
        ls_state = state.loss_scale_state
        diff_params = state.master_params
        if compressing:
            from apex_tpu.utils.collectives import pvary

            # Differentiate w.r.t. shard-VARYING params: under jax≥0.9
            # shard_map, grads w.r.t. replicated params arrive already
            # psummed (fp32, uncompressed).  Typing the params varying
            # stops that implicit collective at the grad boundary, so
            # the per-shard gradients reach the compressed reduction
            # below — which is then the step's ONLY grad communication.
            diff_params = pvary(state.master_params, axis_name)

        def scaled_loss_fn(master_params, *mb):
            # Forward runs on compute-dtype params derived from the masters
            # (reference O2: model holds fp16 copies of fp32 masters).
            with jax.named_scope("cast_params"):
                compute_params = policy.cast_params(master_params)
            if policy.per_op_casts:
                # O1/O4 "patch the world": params pre-cast at the step
                # boundary AND jax entry points patched per the cast
                # lists while the user function traces (amp/patch.py —
                # the wrap.py:31-116 analog).
                from apex_tpu.amp.patch import amp_patch_scope
                from apex_tpu.amp.policy import _effective

                with jax.named_scope("cast_params"):
                    compute_params = policy.cast_to_compute(
                        compute_params, respect_norms=True
                    )
                with amp_patch_scope(_effective(policy.compute_dtype)), \
                        jax.named_scope("model"):
                    out = loss_fn(compute_params, *mb)
            else:
                with jax.named_scope("model"):
                    out = loss_fn(compute_params, *mb)
            loss, aux = (out if has_aux else (out, None))
            return scaler_lib.scale_loss(loss, ls_state), (loss, aux)

        if accum_steps > 1:
            # fp32 main-grad accumulation across microbatches (see
            # docstring).  The scan carries the main_grad buffer; each
            # microbatch's scaled grads are cast up before the add.
            # ``aux`` is reported from the LAST microbatch only (losses
            # are averaged; auxiliary outputs are not).
            def _split_leaf(v, allow_raw_key=False):
                # PRNG keys are not batch data: give each microbatch its
                # own derived key instead of reshaping key words apart.
                # Typed keys are unambiguous anywhere; the legacy raw
                # (2,) uint32 layout is only recognized in the trailing
                # batch arg (the rng position the dropout-enabled step
                # signatures append), so a genuine (2,)-uint32 data leaf
                # elsewhere hits the divisibility error instead of being
                # silently re-split.
                if jax.dtypes.issubdtype(getattr(v, "dtype", None),
                                         jax.dtypes.prng_key) or (
                        allow_raw_key
                        and getattr(v, "dtype", None) == jnp.uint32
                        and getattr(v, "shape", None) == (2,)):
                    return jax.random.split(v, accum_steps)
                if hasattr(v, "shape") and v.shape and (
                        v.shape[0] % accum_steps):
                    raise ValueError(
                        f"accum_steps={accum_steps} does not divide the "
                        f"leading batch dimension {v.shape[0]}; pad or "
                        f"resize the batch so every microbatch is equal.")
                return v.reshape(
                    (accum_steps, v.shape[0] // accum_steps) + v.shape[1:])

            batch_t = tuple(batch)
            micro = tuple(
                jax.tree_util.tree_map(
                    lambda v, last=(i == len(batch_t) - 1):
                        _split_leaf(v, allow_raw_key=last),
                    elem)
                for i, elem in enumerate(batch_t))

            def one_micro(main_grad, mb):
                g, (l, aux_mb) = jax.grad(
                    scaled_loss_fn, has_aux=True)(
                        diff_params, *mb)
                main_grad = jax.tree_util.tree_map(
                    lambda a, gg: a + gg.astype(a.dtype), main_grad, g)
                return main_grad, (l, aux_mb)

            main_grad0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, main_grad_dtype)
                if hasattr(p, "dtype")
                and jnp.issubdtype(p.dtype, jnp.floating) else p,
                state.master_params)
            grads, (losses, aux) = jax.lax.scan(
                one_micro, main_grad0, micro)
            loss = jnp.mean(losses)
            if aux is not None:
                aux = jax.tree_util.tree_map(lambda v: v[-1], aux)
            grads = jax.tree_util.tree_map(
                lambda g: g / accum_steps if hasattr(g, "dtype")
                and jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
        else:
            grads, (loss, aux) = jax.grad(scaled_loss_fn, has_aux=True)(
                diff_params, *batch
            )
        with jax.named_scope("amp_unscale"):
            grads, finite = scaler_lib.unscale_grads(grads, ls_state)

        new_comm_state = state.comm_state
        if axis_name is not None:
            from apex_tpu.utils.collectives import flag_and, grad_mean

            with jax.named_scope("grad_reduce"):
                if compressing:
                    from apex_tpu import comm as comm_lib

                    # bucketed block-scaled quantized all-reduce; residuals
                    # (when error feedback is on) ride the train state in
                    # unscaled-fp32 units, so loss-scale changes between
                    # steps don't corrupt the carried error
                    grads, new_comm_state = comm_lib.reduce_gradients(
                        grads, axis_name, comm_cfg,
                        residuals=state.comm_state if use_ef else None,
                    )
                else:
                    # vma-aware: under shard_map SPMD-AD the grads arrive
                    # pre-summed (see utils/collectives.py) — grad_mean
                    # only divides then.
                    grads = grad_mean(grads, axis_name)
                finite = flag_and(finite, axis_name)

        if grad_postprocess is not None:
            grads = grad_postprocess(grads)

        with jax.named_scope("amp_scale_update"):
            new_ls_state, overflow = scaler_lib.update_loss_scale(
                ls_cfg, ls_state, ~finite
            )

        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.master_params
            )

        # Overflow ⇒ keep old params & opt state (skip-step, handle.py:128-154)
        def select(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old
            )

        with jax.named_scope("apply_update"):
            new_master = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype),
                state.master_params, updates
            )
            new_master = select(new_master, state.master_params)
            new_opt_state = select(new_opt_state, state.opt_state)
            if use_ef:
                # an overflowed step's grads (and thus residuals) are
                # garbage — keep the carried error exactly like the params
                new_comm_state = select(new_comm_state, state.comm_state)
        with jax.named_scope("cast_params"):
            new_params = policy.cast_params(new_master)

        new_state = TrainState(
            step=state.step + jnp.where(overflow, 0, 1),
            params=new_params,
            master_params=new_master if policy.master_weights else new_params,
            opt_state=new_opt_state,
            loss_scale_state=new_ls_state,
            comm_state=new_comm_state,
        )
        metrics = {
            "loss": loss,
            "overflow": overflow,
            "loss_scale": new_ls_state.loss_scale,
            # the index of THIS step (pre-increment): the flight
            # recorder and anomaly detectors key their post-mortems on
            # it (observability.record_step_metrics stamps every record
            # with it), so "first anomalous step" names a real index
            # even in loops that never count steps themselves
            "step": state.step,
        }
        if norm_telemetry:
            from apex_tpu.optimizers._common import norm_metrics

            metrics.update(
                norm_metrics(grads, updates, state.master_params))
        if aux is not None:
            metrics["aux"] = aux
        return new_state, metrics

    # the start-up ledger hears this step's tracing, lowering and compile
    # or cache load (observability.startup_summary); placed last so that
    # no line a traced function runs on moves
    from apex_tpu.observability.device import install_recompile_tracker

    install_recompile_tracker()
    return init_fn, step_fn


# ---- checkpointing (reference amp.state_dict / load_state_dict,
# apex/amp/frontend.py:399-437) ------------------------------------------------


def state_dict(amp_or_train_state) -> dict:
    """Serialize scaler state; mirrors amp.state_dict()'s
    {loss_scalerN: {loss_scale, unskipped}} layout (frontend.py:399-419)."""
    ls = (
        amp_or_train_state.loss_scale_state
        if hasattr(amp_or_train_state, "loss_scale_state")
        else amp_or_train_state
    )
    return {
        "loss_scaler0": {
            "loss_scale": jax.device_get(ls.loss_scale),
            "unskipped": jax.device_get(ls.unskipped),
        }
    }


def load_state_dict(d: dict) -> scaler_lib.LossScaleState:
    entry = d["loss_scaler0"]
    return scaler_lib.LossScaleState(
        loss_scale=jnp.asarray(entry["loss_scale"], jnp.float32),
        unskipped=jnp.asarray(entry["unskipped"], jnp.int32),
    )


# ---- full-state sharded checkpointing (ISSUE 11) -----------------------------
#
# state_dict/load_state_dict above serialize ONLY the scaler (the
# reference surface); a fault-tolerant run must persist the complete
# TrainState — params, fp32 masters, optimizer moments, the comm_state
# error-feedback residuals, the scaler's mid-doubling window, and the
# step counter — bitwise, or the resumed loss trajectory diverges from
# the unkilled run.  These hooks delegate to apex_tpu.checkpoint (per-
# process shard files + an atomically committed manifest; async save
# via checkpoint.AsyncCheckpointer; detector-driven rollback via
# checkpoint.RecoveryManager — see docs/training.md).


def save_train_state(directory: str, step: int, state: TrainState, *,
                     keep=None, extra=None) -> str:
    """Synchronously snapshot a full :class:`TrainState` (every leaf,
    including ``comm_state`` residuals and the loss-scaler window) as
    a committed sharded checkpoint.  Training loops should prefer
    ``apex_tpu.checkpoint.AsyncCheckpointer`` — this is the blocking
    one-shot form (final save, tooling)."""
    from apex_tpu.checkpoint import save_sharded

    return save_sharded(directory, step, state, keep=keep, extra=extra)


def restore_train_state(directory: str, state_like: TrainState, *,
                        step=None, reshard: bool = False) -> TrainState:
    """Restore a :class:`TrainState` snapshot into the structure and
    shardings of ``state_like`` (pass the freshly ``init_fn``-built
    state).  Validates tree structure, shapes, dtypes and mesh
    geometry, checks content digests, and replays bitwise — the
    resumed trajectory is identical to an unkilled run's.
    ``reshard=True`` permits a different mesh geometry (elastic world
    size; shards reassemble through the manifest's layout metadata)."""
    from apex_tpu.checkpoint import restore_sharded

    return restore_sharded(directory, state_like, step=step,
                           reshard=reshard)
