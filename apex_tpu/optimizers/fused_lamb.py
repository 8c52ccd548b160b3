"""FusedLAMB — layer-wise adaptive moments (LAMB) with global grad clipping.

Reference: apex/optimizers/fused_lamb.py — two-phase update matching
csrc/multi_tensor_lamb.cu: phase 1 computes the global grad norm and the
Adam-style moment update per param; phase 2 rescales each param's update by
the trust ratio ||w|| / ||update||. Semantics preserved:

- ``max_grad_norm``: grads are pre-divided by
  ``max(global_norm / max_grad_norm, 1)`` (fused_lamb.py:133-141).
- ``use_nvlamb``: when False (default), params with ``weight_decay == 0``
  skip the adaptive trust ratio (ratio 1), NVLAMB applies it everywhere
  (fused_lamb.py:54).
- ``bias_correction``, ``adam_w_mode``, ``grad_averaging`` as in the
  reference ctor (fused_lamb.py:67).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers._common import (
    GradientTransformation,
    ScheduleOrScalar,
    global_norm,
    resolve_lr,
    tree_map_float,
    tree_zeros_like_f32,
    with_norm_telemetry,
)

__all__ = ["FusedLAMB", "fused_lamb", "LambState"]


class LambState(NamedTuple):
    step: jax.Array
    exp_avg: Any
    exp_avg_sq: Any


def fused_lamb(
    lr: ScheduleOrScalar = 1e-3,
    betas: Tuple[float, float] = (0.9, 0.999),
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    bias_correction: bool = True,
    adam_w_mode: bool = True,
    grad_averaging: bool = True,
    max_grad_norm: float = 1.0,
    use_nvlamb: bool = False,
    norm_telemetry: bool = False,
) -> GradientTransformation:
    """``norm_telemetry=True``: see ``fused_adam`` — the state carries
    the last step's global norms for ``record_opt_norms``; off by
    default (extra full-tree reductions)."""
    beta1, beta2 = betas

    def init(params) -> LambState:
        return LambState(
            step=jnp.zeros((), jnp.int32),
            exp_avg=tree_zeros_like_f32(params),
            exp_avg_sq=tree_zeros_like_f32(params),
        )

    def update(grads, state: LambState, params=None):
        if params is None:
            raise ValueError("fused_lamb requires params")
        step = state.step + 1
        lr_t = resolve_lr(lr, step)

        # Phase 1a: global grad-norm clip (reference :133-141).
        gnorm = global_norm(grads)
        if max_grad_norm is not None and max_grad_norm > 0:
            clip = jnp.maximum(gnorm / max_grad_norm, 1.0)
        else:
            clip = jnp.asarray(1.0, jnp.float32)

        beta3 = (1.0 - beta1) if grad_averaging else 1.0
        if bias_correction:
            bc1 = 1.0 - beta1 ** step.astype(jnp.float32)
            bc2 = 1.0 - beta2 ** step.astype(jnp.float32)
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)

        def scaled_grad(g, p):
            sg = g.astype(jnp.float32) / clip
            if not adam_w_mode and weight_decay != 0.0:
                # L2 mode (kernel MOMENT_MODE_0, multi_tensor_lamb.cu:123-126):
                # decay*p folds into the scaled gradient before the moments.
                sg = sg + weight_decay * p.astype(jnp.float32)
            return sg

        m_tree = tree_map_float(
            lambda g, p, m: beta1 * m + beta3 * scaled_grad(g, p),
            grads, params, state.exp_avg,
        )
        v_tree = tree_map_float(
            lambda g, p, v: beta2 * v
            + (1.0 - beta2) * jnp.square(scaled_grad(g, p)),
            grads, params, state.exp_avg_sq,
        )

        # Phase 2: per-param trust ratio (kernel lamb_stage_2).
        def upd_leaf(m, v, p):
            p32 = p.astype(jnp.float32)
            u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if adam_w_mode and weight_decay != 0.0:
                u = u + weight_decay * p32
            with jax.named_scope("trust_ratio"):
                w_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
                u_norm = jnp.sqrt(jnp.sum(jnp.square(u)))
                ratio = jnp.where(
                    (w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0
                )
            if weight_decay == 0.0 and not use_nvlamb:
                ratio = jnp.asarray(1.0, jnp.float32)
            return -lr_t * ratio * u

        updates = tree_map_float(upd_leaf, m_tree, v_tree, params)
        return updates, LambState(step, m_tree, v_tree)

    tx = GradientTransformation(init, update)
    return with_norm_telemetry(tx) if norm_telemetry else tx


FusedLAMB = fused_lamb
