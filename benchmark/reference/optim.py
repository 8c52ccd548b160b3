"""Plain Adam and LAMB in float32, after the papers (Kingma & Ba 2015;
You et al. 2020, with the global gradient clip and bias correction of
NVIDIA's FusedLAMB that BERT pre-training used).  Imports nothing from
apex_tpu.  ``init(params) -> state``; ``update(grads, state, params) ->
(new_params, new_state)``; the state is ``{"step", "m", "v"}``.

LAMB's trust ratio is taken per leaf of the parameter tree as it is handed
in.  The configurations here stack all layers of one kind in one leaf, so a
"tensor" is that stack: see Open questions in PERF.md."""

from __future__ import annotations

import jax
import jax.numpy as jnp

_tm = jax.tree_util.tree_map


def _init(params):
    zeros = lambda: _tm(jnp.zeros_like, params)
    return {"step": jnp.zeros((), jnp.int32), "m": zeros(), "v": zeros()}


def adam(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    def update(grads, state, params):
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        m = _tm(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tm(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        new = _tm(lambda p, m, v: p - lr * (m / (1 - b1 ** t))
                  / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, m, v)
        return new, {"step": step, "m": m, "v": v}

    return _init, update


def lamb(lr: float, weight_decay: float = 0.01, b1: float = 0.9,
         b2: float = 0.999, eps: float = 1e-6, max_grad_norm: float = 1.0):
    def update(grads, state, params):
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                             for g in jax.tree_util.tree_leaves(grads)))
        clip = jnp.maximum(gnorm / max_grad_norm, 1.0)
        grads = _tm(lambda g: g / clip, grads)
        m = _tm(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tm(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)

        def leaf(p, m, v):
            u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            u = u + weight_decay * p
            wn, un = jnp.linalg.norm(p.ravel()), jnp.linalg.norm(u.ravel())
            ratio = jnp.where((wn > 0) & (un > 0), wn / un, 1.0)
            return p - lr * ratio * u

        return _tm(leaf, params, m, v), {"step": step, "m": m, "v": v}

    return _init, update


OPTIMIZERS = {"adam": adam, "lamb": lamb}
