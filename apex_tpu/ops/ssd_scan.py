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
  state_c``: the only sequential part, ``s / Q`` steps;
- what the earlier chunks give, ``y_i += exp(cum_i) C_i . S_{c-1}``.

Decays, their running sums and the carried state are float32; the four
products take their operands in ``x``'s dtype and accumulate in float32.
Every exponent is a sum of ``l <= 0`` over a span that ends at or after its
start, so nothing overflows; the masked half of the score matrix is set to
``-inf`` before the ``exp``, not multiplied by nought after it.

**The kernels** (``ssd_fwd``, ``ssd_bwd``; PR 35).  Where ``chunk``, ``N``
and a group's ``(H / G) x P`` are multiples of 128, ``P`` divides 128 and
the dtype is float32 or bfloat16, the scan runs as two Pallas kernels over
the grid (batch, group, chunk), the chunks one after another.  A grid step
reads one chunk of one group in the layout the mixer's projection and
convolution leave: ``x`` as a ``[Q, r x P]`` block of ``[b, s, H x P]``
(``r = H / G`` heads side by side, ``128 // P`` of them a 128-lane tile),
``B`` and ``C`` as ``[Q, N]`` blocks of ``[b, s, G x N]`` (or all three by
column block out of the convolution's one ``[x | B | C]``:
:func:`ssd_scan_packed`), ``dt`` and ``cum`` as ``[r, Q]`` blocks.  What
stays in VMEM: ``C B^T`` (once a group), every head's masked decay and
``[Q, Q]`` scores, the four products' results, and the group's carried
state, kept transposed ``[N, r x P]`` float32 in a scratch across the
chunk axis (nought at chunk 0) so that ``C S`` and ``B^T (x o decay)`` run
at full width for all ``r`` heads at once; the per-head ``scores @ x`` is a
128-wide product per head on its tile of two heads, merged by lane;
``d x`` rides the same write.  The forward under differentiation also
writes the state entering each chunk (``[b, G, chunks, N, r x P]`` float32:
134 MB a layer at the Nemotron cell's shape, alive only until that layer's
backward; a states-only sweep instead would re-read ``x``, ``B`` and ``dt``).

The backward (``jax.custom_vjp``) is one sweep over the chunks from the
last to the first, carrying the state's cotangent ``[N, r x P]`` float32
in VMEM.  It recomputes a chunk's decay and ``C B^T`` (transposed, ``[k,
i]``, so that every product of a head is a plain or an ``a b^T`` one),
and writes ``dx``, ``dB`` and ``dC`` (summed over the group's heads in
the step), ``d dt`` and ``d cum`` as ``[r, Q]`` blocks and ``dd`` summed
over the positions.  The gradients through ``cum``: ``cum_i`` gains the
columns' sums of ``dscores o scores`` and ``cum_k`` loses the rows' sums
of the same float32 products (what cancels inside a chunk cancels), plus
the carried terms; sums over a head's ``P`` lanes are one float32
(``HIGHEST``) product with a 0/1 matrix, which also returns them in
``dt``'s layout.  Outside the kernels stay ``dt``'s transpose, its running
sum inside the chunk and, by autodiff of that, the reverse running sum
that turns ``d cum`` into the gradients of ``dt`` and ``a``.  Precision is
the einsum form's: decays, running sums, the carried state and its
cotangent float32; every product's operands, cotangents included, in
``x``'s dtype with float32 accumulation.

**Which side runs** (``backend=``; no environment variable):
``ops/_pallas_utils.resolve_backend`` for the platform, then the static
test of the shape above.  Anything else (chunk 16, ``N`` = 64, a group 64
lanes wide, the toy shapes of the CPU tests) runs the einsum form below,
its backward autodiff's through the same products: the reference side of
the parity tests and the fallback.

A sequence that is no multiple of the chunk is padded with ``dt = 0``
positions, which leave the state as it is; the answers do not depend on
the chunk.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import (
    LANES, on_tpu, out_struct, param_cotangent, resolve_backend)

__all__ = ["ssd_scan", "ssd_scan_packed"]

_F32 = jnp.float32


def ssd_scan(x, dt, a, b, c, d, *, chunk: int = 128,
             backend: Optional[str] = None):
    """``y`` [b, s, H, P] in ``x``'s dtype; see the module docstring.
    ``backend``: ``None``/``"auto"`` (the kernels on a TPU where the shape
    allows them, else the einsum form), ``"kernel"`` or ``"reference"``
    (``ops/_pallas_utils.resolve_backend``)."""
    bt, s, n_heads, p = x.shape
    g, n = b.shape[2:]
    if n_heads % g:
        raise ValueError(f"{n_heads} heads do not share {g} groups evenly")
    if _use_kernels(backend, x.dtype, b.dtype, n_heads, p, g, n, chunk):
        y3 = _ssd_scan_kernels(
            (x.reshape(bt, s, n_heads * p), b.reshape(bt, s, g * n),
             c.reshape(bt, s, g * n)), (0, 0, 0), dt, a, d, g, n, chunk)
        return y3.reshape(x.shape)
    return _ssd_scan_einsums(x, dt, a, b, c, d, chunk)


def ssd_scan_packed(xbc, dt, a, d, *, groups: int, state: int,
                    chunk: int = 128, backend: Optional[str] = None):
    """:func:`ssd_scan` on ``xbc`` [b, s, H P + 2 G N] = ``[x | B | C]``
    side by side, as the mixer's convolution leaves them (H from ``dt``,
    ``groups`` = G, ``state`` = N); returns ``y`` [b, s, H P].  The
    kernels read the three parts out of the one array by column block
    where ``H P`` is a multiple of N; nothing is sliced out first."""
    bt, s, n_heads = dt.shape
    g, n = groups, state
    if n_heads % g:
        raise ValueError(f"{n_heads} heads do not share {g} groups evenly")
    d_in = xbc.shape[-1] - 2 * g * n
    p = d_in // n_heads
    if d_in % n == 0 and _use_kernels(backend, xbc.dtype, xbc.dtype, n_heads,
                                      p, g, n, chunk):
        return _ssd_scan_kernels((xbc,), (0, d_in // n, d_in // n + g), dt,
                                 a, d, g, n, chunk)
    x, b, c = jnp.split(xbc, [d_in, d_in + g * n], -1)
    return ssd_scan(x.reshape(bt, s, n_heads, p), dt, a,
                    b.reshape(bt, s, g, n), c.reshape(bt, s, g, n), d,
                    chunk=chunk, backend=backend).reshape(bt, s, d_in)


def _use_kernels(backend, dtype, b_dtype, n_heads, p, g, n, chunk) -> bool:
    """Whether the kernels run: the platform or the caller's pin
    (``resolve_backend``), then the static test of the shape -- a chunk, a
    state row and a group's heads side by side each fill whole 128-lane
    tiles, and a head does not straddle one."""
    if resolve_backend("ssd_scan", backend) != "kernel":
        return False
    r = n_heads // g
    fits = (chunk % LANES == 0 and n % LANES == 0 and (r * p) % LANES == 0
            and LANES % p == 0 and 2 * r <= LANES
            and dtype in (jnp.bfloat16, jnp.float32) and b_dtype == dtype)
    if not fits and backend == "kernel":
        raise ValueError(
            "ssd_scan: backend='kernel' needs chunk, N and (H / G) x P in "
            f"multiples of {LANES}, P a divisor of {LANES} and float32 or "
            f"bfloat16; got {n_heads} heads of {p} in {g} groups, N {n}, "
            f"chunk {chunk}, {dtype}")
    return fits


def _ssd_scan_einsums(x, dt, a, b, c, d, chunk):
    """The chunked form in einsums, backward by autodiff: the reference side
    of the parity tests and what runs where the kernels do not."""
    bt, s, n_heads, p = x.shape
    g, n = b.shape[2:]
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


# ---------------------------------------------------------------------------
# The Pallas kernels.
# ---------------------------------------------------------------------------
#
# Kernel arrays: ``x``, ``y`` and ``dy`` [b, s, H x P] and ``B``, ``C``
# [b, s, G x N], the free reshapes of the model's arrays; ``dt`` and ``cum``
# [b, G, H / G, s] float32, the position last.  A grid step (batch, group,
# chunk) takes a [Q, r x P] block of ``x`` (r = H / G heads side by side,
# ``128 // P`` of them a 128-lane tile), [Q, N] blocks of ``B`` and ``C`` and
# [r, Q] blocks of ``dt`` and ``cum``.  The carried state of the group is
# kept transposed, [N, r x P] float32, so that every product that touches
# it is full width.

_NT = (((1,), (1,)), ((), ()))       # a @ b^T
_TN = (((0,), (0,)), ((), ()))       # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ())), **kw):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32, **kw)


def _columns(cum, dt):
    """[Q, 128] float32 with head ``h``'s ``cum`` in lane ``h`` and its
    ``dt`` in lane ``r + h``: the two [r, Q] blocks stood on end by one
    transpose, for what has to multiply rows."""
    r, q = cum.shape
    return jnp.concatenate(
        [cum, dt, jnp.zeros((LANES - 2 * r, q), _F32)], axis=0).T


def _spread(parts, lane, p):
    """One [Q, 128] array from a tile's heads: the lanes of head ``i`` of
    the tile from ``parts[i]`` ([Q, 128], or a [Q, 1] column spread over
    them)."""
    out = parts[-1]
    for i in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (i + 1) * p, parts[i], out)
    return jnp.broadcast_to(out, lane.shape)


def _tile_columns(cols, lane, heads, r, p):
    """``cum`` and ``dt`` of a tile's heads over the heads' lanes, [Q, 128]
    each, and ``cum`` at the chunk's end, [1, 128]."""
    cum_x = _spread([cols[:, h:h + 1] for h in heads], lane, p)
    dt_x = _spread([cols[:, r + h:r + h + 1] for h in heads], lane, p)
    return cum_x, dt_x, cum_x[-1:, :]


def _fwd_kernel(r, p, keep_states, x_ref, dt_ref, cum_ref, b_ref, c_ref,
                d_ref, y_ref, *rest):
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    dtype = x_ref.dtype
    q = x_ref.shape[1]
    hpb = LANES // p
    dt, cum = dt_ref[0, 0], cum_ref[0, 0]                  # [r, Q]
    cols = _columns(cum, dt)
    bm, cm = b_ref[0], c_ref[0]
    cb = _dot(cm, bm, _NT)                                 # [i, k]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    before = state[...]                                    # [N, r x P]
    if keep_states:
        rest[0][0, 0, 0] = before
    carried = _dot(cm, before.astype(dtype))               # [Q, r x P]
    for t in range(r // hpb):
        at = slice(t * LANES, (t + 1) * LANES)
        heads = range(t * hpb, (t + 1) * hpb)
        xt = x_ref[0, :, at]
        within = []
        for h in heads:
            decay = jnp.exp(jnp.where(
                causal, cols[:, h:h + 1] - cum[h:h + 1, :], -jnp.inf))
            scores = (cb * decay * dt[h:h + 1, :]).astype(dtype)
            within.append(_dot(scores, xt))
        cum_x, dt_x, end = _tile_columns(cols, lane, heads, r, p)
        xf = xt.astype(_F32)
        y = (_spread(within, lane, p) + jnp.exp(cum_x) * carried[:, at]
             + d_ref[:, at] * xf)
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        weighed = (xf * (jnp.exp(end - cum_x) * dt_x)).astype(dtype)
        state[:, at] = before[:, at] * jnp.exp(end) + _dot(bm, weighed, _TN)


def _bwd_kernel(r, p, x_ref, dt_ref, cum_ref, b_ref, c_ref, d_ref, dy_ref,
                before_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                dd_ref, dstate):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dtype = x_ref.dtype
    q = x_ref.shape[1]
    hpb = LANES // p
    dt, cum = dt_ref[0, 0], cum_ref[0, 0]                  # [r, Q]
    cols = _columns(cum, dt)
    bm, cm = b_ref[0], c_ref[0]
    # the scores transposed, [k, i]: every product of a head is then a
    # plain or an a @ b^T one
    cbt = _dot(bm, cm, _NT)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 0) == q - 1
    head_row = jax.lax.broadcasted_iota(jnp.int32, (r, q), 0)
    # which head's lanes: [r, r x P], for the sums over a head's P lanes
    # (one product in float32 instead of r lane reductions; the result
    # comes in the layout of dt and cum)
    w = r * p
    head_of = jax.lax.broadcasted_iota(jnp.int32, (r, w), 0) * p
    at_lane = jax.lax.broadcasted_iota(jnp.int32, (r, w), 1)
    per_head = ((at_lane >= head_of) & (at_lane < head_of + p)).astype(_F32)

    def head_sums(t, values):                              # [Q, 128] -> [r, Q]
        return _dot(per_head[:, t * LANES:(t + 1) * LANES], values, _NT,
                    precision=jax.lax.Precision.HIGHEST)

    before = before_ref[0, 0, 0]         # the state entering the chunk
    dafter = dstate[...]                 # cotangent of the state leaving it
    before_lo, dafter_lo = before.astype(dtype), dafter.astype(dtype)
    carried = _dot(cm, before_lo)                          # [Q, r x P]
    back = _dot(bm, dafter_lo)                             # [Q, r x P]
    dcbt = jnp.zeros((q, q), _F32)
    dcum = jnp.zeros((r, q), _F32)       # what reaches cum_i, by rows
    ddt = jnp.zeros((r, q), _F32)
    by_k = jnp.zeros((q, LANES), _F32)   # lane h: sum_i dscores o C B^T o L
    grown, weighed = [], []
    for t in range(r // hpb):
        at = slice(t * LANES, (t + 1) * LANES)
        heads = range(t * hpb, (t + 1) * hpb)
        xt, dyt = x_ref[0, :, at], dy_ref[0, :, at]
        xf, dyf = xt.astype(_F32), dyt.astype(_F32)
        cum_x, dt_x, end = _tile_columns(cols, lane, heads, r, p)
        grow, to_end, decay_c = (jnp.exp(cum_x), jnp.exp(end - cum_x),
                                 jnp.exp(end))
        from_dy = []
        for i, h in enumerate(heads):
            dt_k = cols[:, r + h:r + h + 1]
            decay = jnp.exp(jnp.where(
                causal, cum[h:h + 1, :] - cols[:, h:h + 1], -jnp.inf))
            cbl = cbt * decay                              # [k, i]
            mine = (lane >= i * p) & (lane < (i + 1) * p)
            dscores = _dot(jnp.where(mine, xt, jnp.zeros_like(xt)), dyt, _NT)
            dcbt += dscores * decay * dt_k
            # dscores o scores less dt_k: its rows' sums are dt_k's
            # gradient and, times dt_k, what cum_k loses; the columns'
            # sums of the same products are what cum_i gains, so that
            # what cancels inside a chunk cancels
            both = dscores * cbl
            by_k = jnp.where(lane == h, jnp.sum(both, 1, keepdims=True), by_k)
            dcum += jnp.where(head_row == h,
                              jnp.sum(both * dt_k, 0, keepdims=True), 0.0)
            from_dy.append(_dot(cbl.astype(dtype), dyt))   # [k, 128]
        # what dy gives x_k: dt_k (C B^T o L)^T dy and the state's part
        dx = dt_x * (back[:, at] * to_end + _spread(from_dy, lane, p))
        dx_ref[0, :, at] = (dx + d_ref[:, at] * dyf).astype(dx_ref.dtype)
        # through the chunk's own state: dt_k's part, cum_k's loss and,
        # with the carried state's, the gain of the chunk's last position
        x_to_end = xf * to_end
        own = x_to_end * back[:, at]
        ddt += head_sums(t, own)
        own = own * dt_x
        tail = (jnp.sum(own, 0, keepdims=True)
                + decay_c * jnp.sum(dafter[:, at] * before[:, at], 0,
                                    keepdims=True))
        dcum += head_sums(t, dyf * (grow * carried[:, at])
                          + jnp.where(last, tail, 0.0) - own)
        dd_ref[0, :, at] += jnp.sum(dyf * xf, 0, keepdims=True)
        dy_grown = (grow * dyf).astype(dtype)
        grown.append(dy_grown)
        weighed.append((x_to_end * dt_x).astype(dtype))
        dstate[:, at] = dafter[:, at] * decay_c + _dot(cm, dy_grown, _TN)
    by_k = by_k.T[:r]                                      # [r, Q]
    ddt_ref[0, 0] = ddt + by_k
    dcum_ref[0, 0] = dcum - dt * by_k
    dcb = dcbt.astype(dtype)
    grown, weighed = jnp.concatenate(grown, 1), jnp.concatenate(weighed, 1)
    dc_ref[0] = (_dot(dcb, bm, _TN)
                 + _dot(grown, before_lo, _NT)).astype(dc_ref.dtype)
    db_ref[0] = (_dot(dcb, cm)
                 + _dot(weighed, dafter_lo, _NT)).astype(db_ref.dtype)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    # Mosaic's default scoped VMEM (16 MB) holds them: at the Nemotron
    # cell's shape the forward compiles under a limit of 4 MB, the
    # backward under 8 (described-v5e compiles, PR 35)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(dtg, d_x, n, q, at, chunk_of):
    """The block specs of an ``x``-like, a ``dt``-like, a ``B``-like and a
    ``C``-like kernel array and of ``d`` [1, H x P].  ``at`` = the first
    column block of ``x`` (in blocks of r x P), of ``B`` and of ``C`` (in
    blocks of N) in their arrays: noughts for arrays of their own, the
    parts' places where all three are one ``[x | B | C]``; ``chunk_of``
    maps the grid's third index to the chunk."""
    from jax.experimental.pallas import tpu as pltpu

    g, r = dtg.shape[1:3]
    w = d_x.shape[1] // g

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def columns(width, first):
        return spec((1, q, width),
                    lambda i, j, k: (i, chunk_of(k), first + j))

    return (columns(w, at[0]),
            spec((1, 1, r, q), lambda i, j, k: (i, j, 0, chunk_of(k))),
            columns(n, at[1]), columns(n, at[2]),
            spec((1, w), lambda i, j, k: (0, j)))


def _parts(arrays):
    """``x``, ``B`` and ``C`` as the kernels' operands: three arrays, or
    the one ``[x | B | C]`` three times (each with its own block spec)."""
    return arrays if len(arrays) == 3 else arrays * 3


@functools.partial(
    jax.jit, static_argnames=("at", "n", "q", "keep_states", "interpret"))
def _fwd_pallas(arrays, dtg, cum, d_x, at, n, q, keep_states, interpret):
    """``y`` [b, s, H x P] and, with ``keep_states``, the state entering
    every chunk, [b, G, chunks, N, r x P] float32."""
    from jax.experimental.pallas import tpu as pltpu

    like = arrays[0]
    bt, s = like.shape[:2]
    g, r = dtg.shape[1:3]
    hp = d_x.shape[1]
    w = hp // g
    x_spec, dt_spec, b_spec, c_spec, d_spec = _specs(
        dtg, d_x, n, q, at, lambda k: k)
    y_spec = _specs(dtg, d_x, n, q, (0, 0, 0), lambda k: k)[0]
    out_specs, out_shape = [y_spec], [
        out_struct((bt, s, hp), like.dtype, like)]
    if keep_states:
        out_specs.append(pl.BlockSpec(
            (1, 1, 1, n, w), lambda i, j, k: (i, j, k, 0, 0),
            memory_space=pltpu.VMEM))
        out_shape.append(out_struct((bt, g, s // q, n, w), _F32, like))
    x_in, b_in, c_in = _parts(arrays)
    with jax.named_scope("ssd_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, r, w // r, keep_states),
            grid=(bt, g, s // q),
            in_specs=[x_spec, dt_spec, dt_spec, b_spec, c_spec, d_spec],
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((n, w), _F32)],
            compiler_params=_compiler_params(), interpret=interpret,
        )(x_in, dtg, cum, b_in, c_in, d_x)


@functools.partial(jax.jit, static_argnames=("at", "n", "q", "interpret"))
def _bwd_pallas(arrays, dtg, cum, d_x, dy3, states, at, n, q, interpret):
    """One sweep over the chunks from the last to the first; returns the
    cotangents of ``x``, ``dtg``, ``cum``, ``B`` and ``C`` (each an array
    of its own) and of ``d_x`` a batch row, [b, 1, H x P] float32."""
    from jax.experimental.pallas import tpu as pltpu

    like = arrays[0]
    bt, s, hp = dy3.shape
    g, r = dtg.shape[1:3]
    w = hp // g
    nc = s // q

    def back(k):
        return nc - 1 - k

    x_spec, dt_spec, b_spec, c_spec, d_spec = _specs(
        dtg, d_x, n, q, at, back)
    dx_spec, _, db_spec, _, _ = _specs(dtg, d_x, n, q, (0, 0, 0), back)
    x_in, b_in, c_in = _parts(arrays)
    with jax.named_scope("ssd_bwd"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, r, w // r),
            grid=(bt, g, nc),
            in_specs=[x_spec, dt_spec, dt_spec, b_spec, c_spec, d_spec,
                      dx_spec,
                      pl.BlockSpec((1, 1, 1, n, w),
                                   lambda i, j, k: (i, j, back(k), 0, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=[dx_spec, dt_spec, dt_spec, db_spec, db_spec,
                       pl.BlockSpec((1, 1, w), lambda i, j, k: (i, 0, j),
                                    memory_space=pltpu.VMEM)],
            out_shape=[out_struct((bt, s, hp), like.dtype, like),
                       out_struct(dtg.shape, _F32, like),
                       out_struct(dtg.shape, _F32, like),
                       out_struct((bt, s, g * n), like.dtype, like),
                       out_struct((bt, s, g * n), like.dtype, like),
                       out_struct((bt, 1, hp), _F32, like)],
            scratch_shapes=[pltpu.VMEM((n, w), _F32)],
            compiler_params=_compiler_params(), interpret=interpret,
        )(x_in, dtg, cum, b_in, c_in, d_x, dy3, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _scan(arrays, dtg, cum, d_x, at, n, q):
    return _fwd_pallas(arrays, dtg, cum, d_x, at=at, n=n, q=q,
                       keep_states=False, interpret=not on_tpu())[0]


def _scan_fwd(arrays, dtg, cum, d_x, at, n, q):
    y3, states = _fwd_pallas(arrays, dtg, cum, d_x, at=at, n=n, q=q,
                             keep_states=True, interpret=not on_tpu())
    return y3, (arrays, dtg, cum, d_x, states)


def _scan_bwd(at, n, q, saved, dy3):
    arrays, dtg, cum, d_x, states = saved
    dx3, ddt, dcum, db3, dc3, dd = _bwd_pallas(
        arrays, dtg, cum, d_x, dy3, states, at=at, n=n, q=q,
        interpret=not on_tpu())
    darrays = ((dx3, db3, dc3) if len(arrays) == 3
               else (jnp.concatenate([dx3, db3, dc3], -1),))
    return (darrays, param_cotangent(ddt, dtg), param_cotangent(dcum, cum),
            param_cotangent(jnp.sum(dd, 0), d_x))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _ssd_scan_kernels(arrays, at, dt, a, d, g, n, chunk):
    """The kernels' side, on ``x`` [b, s, H P], ``B`` and ``C``
    [b, s, G N] or the one ``[x | B | C]`` (``at``: :func:`_specs`).
    Outside the kernels: ``dt`` turned to [b, G, r, s], its running sum
    ``cum`` inside each chunk (whose transpose autodiff writes: the
    gradients of ``dt`` and ``a`` through ``cum`` are a reverse running
    sum of the kernel's ``dcum``) and ``d`` repeated over its head's
    lanes."""
    bt, s, n_heads = dt.shape
    r = n_heads // g
    pad = -s % chunk
    if pad:
        arrays = tuple(jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                       for t in arrays)
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    dtg = dt.astype(_F32).reshape(bt, sp, g, r).transpose(0, 2, 3, 1)
    steps = dtg * a.astype(_F32).reshape(g, r, 1)
    cum = jnp.cumsum(steps.reshape(bt, g, r, sp // chunk, chunk),
                     axis=-1).reshape(bt, g, r, sp)
    hp = arrays[0].shape[2] - (2 * g * n if len(arrays) == 1 else 0)
    d_x = jnp.repeat(d.astype(_F32), hp // n_heads)[None]
    return _scan(tuple(arrays), dtg, cum, d_x, at, n, chunk)[:, :s]
