"""Fused Adam over the flattened parameter buffer (pure XLA).

The reference's performance trick is ``multi_tensor_apply``: one kernel
launch updates the entire parameter list (csrc/multi_tensor_adam.cu +
multi_tensor_apply.cuh packs 110 tensor pointers per launch).  The
TPU-native answer turned out to need no hand-written kernel at all:
under ``jit`` XLA fuses the whole flat Adam chain (two moment updates,
the rsqrt, the weight-decay add) into one HBM pass on its own.

No Pallas kernel: every optimizer takes the XLA flat path (the whole
update's device time in each cell: PERF.md section 5).

``adam_kernel_flat`` is the flat-buffer entry point (the
ZeRO-sharded DistributedFusedAdam layout calls it on raw 1-D shards);
``flat_adam_update`` is the tree-level wrapper kept for the reference's
``multi_tensor_apply``-shaped API surface.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

__all__ = ["flat_adam_update", "adam_kernel_flat"]


@functools.partial(jax.jit, static_argnames=("adam_w_mode",))
def adam_kernel_flat(
    g: jax.Array,
    p: jax.Array,
    m: jax.Array,
    v: jax.Array,
    scalars: jax.Array,
    adam_w_mode: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Adam update on 1-D fp32 buffers.

    ``scalars`` = [lr, beta1, beta2, eps, weight_decay, bc1, bc2] (f32[7]).
    Returns (update, new_m, new_v) with the same length as the inputs.
    XLA fuses the chain into a single pass over HBM.
    """
    lr, beta1, beta2, eps, wd, bc1, bc2 = (scalars[i] for i in range(7))
    if not adam_w_mode:
        g = g + wd * p
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * g * g
    u = -lr * (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if adam_w_mode:
        u = u - lr * wd * p
    return u, m_new, v_new


def flat_adam_update(
    grads: Any, params: Any, m: Any, v: Any,
    lr, beta1, beta2, eps, weight_decay, bc1, bc2,
    adam_w_mode: bool,
):
    """Tree-level wrapper: ravel → flat update → unravel.

    The three unravel closures share one flat layout, so XLA lowers the
    concat/split to views around a single fused update.
    """
    g_flat, unravel = ravel_pytree(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), grads)
    )
    p_flat, _ = ravel_pytree(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    )
    m_flat, _ = ravel_pytree(m)
    v_flat, _ = ravel_pytree(v)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(bc1, jnp.float32),
        jnp.asarray(bc2, jnp.float32),
    ])
    u, m_new, v_new = adam_kernel_flat(
        g_flat, p_flat, m_flat, v_flat, scalars, adam_w_mode=adam_w_mode,
    )
    return unravel(u), unravel(m_new), unravel(v_new)

