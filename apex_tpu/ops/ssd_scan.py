"""The state-space scan of Mamba-2 in its chunked (state-space-duality) form.

Per head ``j`` of group ``g = j // (H / G)``, with a state ``S`` [P, N] that
starts at nought::

    S_t = exp(dt_t a_j) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + d_j x_t

``x`` [b, s, H, P]; ``dt`` [b, s, H] (after its softplus, float32); ``a`` [H]
(negative) and ``d`` [H]; ``B`` and ``C`` [b, s, G, N], shared by the
``H / G`` heads of a group.

The recurrence is linear in ``S``, so a chunk of ``Q`` positions can be
worked as matrix products (Dao & Gu, "Transformers are SSMs", section 6).
With ``l_t = dt_t a_j`` and ``cum`` its running sum inside a chunk:

- within a chunk, ``y_i += sum_{k <= i} exp(cum_i - cum_k) dt_k (C_i . B_k)
  x_k``: a masked ``[Q, Q]`` score matrix per head times ``x``, the
  ``C B^T`` part once a group;
- the chunk's own state, ``sum_k exp(cum_Q - cum_k) dt_k x_k (x) B_k``;
- the states carried from chunk to chunk, ``S_c = exp(cum_Q) S_{c-1} +
  state_c``: the only sequential part, ``s / Q`` steps of a ``lax.scan``;
- what the earlier chunks give, ``y_i += exp(cum_i) C_i . S_{c-1}``.

Decays, their running sums and the carried state are float32; the four
products take their operands in ``x``'s dtype and accumulate in float32.
Every exponent is a sum of ``l <= 0`` over a span that ends at or after its
start, so nothing overflows; the masked half of the score matrix is set to
``-inf`` before the ``exp``, not multiplied by nought after it.

The backward pass is autodiff's through these same products (the builder's
choice, PR 34): under the layer's ``jax.checkpoint`` the chunked forward is
recomputed and transposed, which keeps the backward in matrix products of
the same shapes and needs no second derivation to keep in step.  No Pallas
kernel: ``benchmark``'s ``ssd_scan_ms`` and ``ssd_scan_roofline`` say what
this form costs first.

A sequence that is no multiple of the chunk is padded with ``dt = 0``
positions, which leave the state as it is; the answers do not depend on
the chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan"]


def ssd_scan(x, dt, a, b, c, d, *, chunk: int = 128):
    """``y`` [b, s, H, P] in ``x``'s dtype; see the module docstring."""
    bt, s, n_heads, p = x.shape
    g, n = b.shape[2:]
    if n_heads % g:
        raise ValueError(f"{n_heads} heads do not share {g} groups evenly")
    r = n_heads // g
    dtype = x.dtype
    f32 = jnp.float32
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc, q = (s + pad) // chunk, chunk

    xg = x.reshape(bt, nc, q, g, r, p)
    bg = b.reshape(bt, nc, q, g, n)
    cg = c.reshape(bt, nc, q, g, n)
    # [bt, nc, g, r, q]: the position last, as the score matrix wants it
    dtg = dt.astype(f32).reshape(bt, nc, q, g, r).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(dtg * a.astype(f32).reshape(g, r, 1), axis=-1)

    # within the chunk: (L o C B^T) X
    cb = jnp.einsum("zcqgn,zckgn->zcgqk", cg, bg,
                    preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    scores = (cb[:, :, :, None] * decay * dtg[..., None, :]).astype(dtype)
    y = jnp.einsum("zcgrqk,zckgrp->zcqgrp", scores, xg,
                   preferred_element_type=f32)

    # each chunk's own state, then the states carried across chunks
    to_end = jnp.exp(cum[..., -1:] - cum) * dtg            # [bt, nc, g, r, q]
    weighed = (xg.astype(f32)
               * to_end.transpose(0, 1, 4, 2, 3)[..., None]).astype(dtype)
    states = jnp.einsum("zcqgrp,zcqgn->zcgrpn", weighed, bg,
                        preferred_element_type=f32)
    chunk_decay = jnp.exp(cum[..., -1])                    # [bt, nc, g, r]

    def carry(state, inputs):
        own, decay_c = inputs
        return state * decay_c[..., None, None] + own, state

    _, before = jax.lax.scan(
        carry, jnp.zeros((bt, g, r, p, n), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                    # [bt, nc, g,r,p,n]

    # what the earlier chunks give: exp(cum_i) C_i . S_{c-1}
    carried = jnp.einsum("zcqgn,zcgrpn->zcqgrp", cg, before.astype(dtype),
                         preferred_element_type=f32)
    y = y + carried * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y + d.astype(f32).reshape(g, r, 1) * xg.astype(f32)
    return y.reshape(bt, s + pad, n_heads, p)[:, :s].astype(dtype)
