"""Mixture-of-Experts MLP with capacity-limited and capacity-free routing.

Beyond the reference: ROCm/apex has no MoE runtime (its testing argparse
reserves ``--num-experts``, arguments.py:389, but nothing consumes it).
Expert parallelism is first-class on a TPU mesh, so apex_tpu supplies it
two ways, selected by ``routing=``:

- ``"capacity"`` — the GShard/Switch formulation: top-k routing with a
  static per-expert capacity, dispatch/combine as one-hot einsums the
  GSPMD partitioner shards (expert-major tensors carry ``P('ep', ...)``
  and XLA inserts the all-to-alls).  Over-capacity tokens drop (reported
  in ``dropped_fraction``) and every expert pads to ``cap`` slots.
- ``"ragged"`` — capacity-free: tokens are *sorted by expert* (argsort of
  the assignment, segment boundaries from a bincount) and the expert FFNs
  run over ragged ``[tokens, h]`` segments via the grouped matmul
  (``ops/grouped_matmul.py``); an inverse-permutation scatter weighted by
  the gates combines.  No token is ever dropped
  (``dropped_fraction == 0`` by construction) and no pad-to-capacity
  slots are computed.  ``router="sigmoid"`` swaps the softmax for float32
  sigmoid scores with a selection-only bias and gates normalised over the
  chosen; ``experts_held=(first, count)`` makes the layer one rank's
  share of an expert-parallel layer, run without its exchange: it routes
  over all experts and computes what its own give (``_ragged_local``).

On a mesh with an ``ep`` axis the ragged path runs expert parallelism
*explicitly* inside a ``jax.shard_map`` island instead of leaving the
all-to-alls to the partitioner:

- dispatch/combine use the counted ``all_to_all`` wrappers
  (``utils/collectives.py``) with wire compression through
  ``comm/quantize`` — ``moe_comm="fp32"|"bf16"|"int8"`` mirrors the
  ``grad_comm=`` surface, per-block fp32 scales ride the header exactly
  like the PR-2 gradient buckets (EQuARX, arXiv:2506.17615);
- under ``overlap_comm`` (the ``ops/collective_matmul`` tri-state /
  ``overlap_scope``) dispatch becomes a ``ppermute`` ring
  (``_ring_visit`` shape) and combine a rotating-accumulator ring
  (``_ring_scatter_sum``) whose per-hop ``part`` runs the local experts'
  grouped FFN for the chunk the traveling accumulator is destined for —
  expert compute overlaps the ring transfers, and the backward is
  hop-wise too (ppermute transposes to the reversed ring; the compressed
  gather carries a straight-through custom VJP whose cotangent rides a
  reduce-scatter ring).

Trace-time telemetry (the PR-1 registry; zero-overhead when
unconfigured): ``moe.dispatch_bytes`` / ``moe.dispatch_raw_bytes`` (wire
vs uncompressed fp32 payload), ``moe.ring_calls`` / ``moe.ring_hops``
(``hops == (ep−1) × calls`` by construction), and the
``moe.dropped_fraction`` gauge (pinned 0.0 on the ragged path).  The
data-dependent per-expert assignment counts come back in
``MoEOutput.expert_load`` for host-side gauges (bench ``--moe``).
The dispatch accounting is structurally audited: the ``static_audit``
dryrun phase traces the EP island and asserts its jaxpr's
``all_to_all`` census equals the counted-wrapper deltas
(``analysis/jaxpr_audit.py`` — an exchange emitted around the counted
wrappers fails CI as accounting drift).

Works on one device (constraints no-op), under ``jit`` over a mesh with
an ``ep`` axis (``parallel.mesh.create_mesh(ep=...)``), and composes
with dp/tp the same way the rest of the model does.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu.comm.quantize import (
    WIRE_DTYPES,
    dequantize_blocks,
    quantize_blocks,
)
from apex_tpu.models.transformer_lm import _constrain
from apex_tpu.observability import metrics as _telemetry
# shared with the ring collective-matmuls so byte/axis accounting
# cannot drift between the TP and EP overlap paths
from apex_tpu.ops.collective_matmul import _mesh_axis, _nbytes
from apex_tpu.ops.grouped_matmul import grouped_matmul, group_ids

__all__ = ["init_moe_params", "switch_moe_mlp", "MoEOutput",
           "MOE_ROUTINGS", "MOE_ROUTERS"]

MOE_ROUTINGS = ("capacity", "ragged")
MOE_ROUTERS = ("softmax", "sigmoid")


class MoEOutput(NamedTuple):
    out: jax.Array          # [b, s, h]
    aux_loss: jax.Array     # scalar load-balance loss
    dropped_fraction: jax.Array  # scalar: tokens over capacity
    # per-expert router assignment counts [E] (all top-k selections,
    # pre-drop) — the host-side load-imbalance signal (bench --moe sets
    # the moe.expert_load_* gauges from it); None on legacy callers
    expert_load: Optional[jax.Array] = None


def init_moe_params(
    rng: jax.Array,
    hidden_size: int,
    ffn_hidden_size: int,
    num_experts: int,
    *,
    init_std: float = 0.02,
    dtype=jnp.float32,
    activation: str = "gelu",
) -> dict:
    """Expert-stacked FFN params [E, ...] + router [h, E].  With
    ``activation='swiglu'`` fc1 carries the concatenated [gate ‖ up]
    columns (trailing dim 2f)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    f1 = 2 * ffn_hidden_size if activation == "swiglu" else ffn_hidden_size

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * init_std).astype(dtype)

    return {
        "router": nrm(k1, (hidden_size, num_experts)),
        "fc1": nrm(k2, (num_experts, hidden_size, f1)),
        "fc1_bias": jnp.zeros((num_experts, f1), dtype),
        "fc2": nrm(k3, (num_experts, ffn_hidden_size, hidden_size)),
        "fc2_bias": jnp.zeros((num_experts, hidden_size), dtype),
    }


def _expert_constrain(x, ep_axis: Optional[str]):
    """Shard the leading expert dim over the ep mesh axis (no-op when no
    mesh / axis — same contract as the model's other constraints)."""
    if ep_axis is None:
        return x
    return _constrain(x, P(ep_axis, *([None] * (x.ndim - 1))))


# ---------------------------------------------------------------------------
# shared routing / aux-loss pieces
# ---------------------------------------------------------------------------


def _router_probs(router, x2, router_noise_rng):
    logits = x2.astype(jnp.float32) @ router.astype(jnp.float32)
    if router_noise_rng is not None:
        logits = logits + jax.random.uniform(
            router_noise_rng, logits.shape, jnp.float32, -1e-2, 1e-2)
    return jax.nn.softmax(logits, axis=-1)


def _topk_routing(scores, top_k):
    """Iterative-argmax top-k (the Switch selection rule, ties and all):
    ``(choice [..., k] int32, picked [..., k] fp32)``.  A taken expert is
    masked with ``-inf``, so scores may be negative (a sigmoid score plus
    a selection bias) and no expert is taken twice."""
    e_n = scores.shape[-1]
    remaining = scores
    choices, picked = [], []
    for _ in range(top_k):
        c = jnp.argmax(remaining, axis=-1)
        hot = jax.nn.one_hot(c, e_n, dtype=jnp.bool_)
        # the chosen score through the mask, not a gather of single
        # elements (slow on a TPU, and its transpose is a scatter)
        picked.append(jnp.sum(jnp.where(hot, remaining, 0.0), axis=-1))
        choices.append(c.astype(jnp.int32))
        remaining = jnp.where(hot, -jnp.inf, remaining)
    return jnp.stack(choices, axis=-1), jnp.stack(picked, axis=-1)


def _sigmoid_routing(router, bias, x2, top_k, scaling=1.0, epsilon=1e-6):
    """The sigmoid router: scores ``r = sigmoid(x W_g)`` in float32 (the
    product too: ``HIGHEST``, no bf16 passes on a TPU), the ``top_k``
    largest of ``r + bias`` chosen (the bias selects, it never weighs),
    gates ``r_e / (sum of the chosen r + epsilon) * scaling``.  Returns
    ``(choice [T, k], gates [T, k], r [T, E])``."""
    r = jax.nn.sigmoid(jnp.dot(
        x2.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    select = r if bias is None else r + bias.astype(jnp.float32)
    choice, _ = _topk_routing(jax.lax.stop_gradient(select), top_k)
    hot = jax.nn.one_hot(choice, r.shape[-1], dtype=jnp.bool_)  # [T, k, E]
    picked = jnp.sum(jnp.where(hot, r[:, None, :], 0.0), axis=-1)
    gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + epsilon)
    return choice, gates * scaling, r


def _aux_loss(probs_mean, sel_counts, n_assignments):
    """Switch eq. 4 generalized to top-k: ``E · Σ_e f_e · P_e`` where
    ``f_e`` counts ALL k selections (not just the argmax — with top_k=2
    the runner-up expert's traffic must be visible to the balance
    term) normalized by the total assignment count."""
    e_n = probs_mean.shape[-1]
    token_frac = sel_counts.astype(jnp.float32) / n_assignments
    return e_n * jnp.sum(token_frac * probs_mean)


# ---------------------------------------------------------------------------
# telemetry (trace-time; module-level helpers fast-path when disabled)
# ---------------------------------------------------------------------------


def _note_dispatch(wire, scales, raw_elements: int) -> None:
    """Wire vs raw bytes THIS rank puts on the interconnect per emitted
    dispatch/combine exchange (trace-time accounting, the
    ``collectives.compressed.*`` discipline)."""
    n = _nbytes(wire) + (_nbytes(scales) if scales is not None else 0)
    _telemetry.counter("moe.dispatch_bytes").inc(n)
    _telemetry.counter("moe.dispatch_raw_bytes").inc(4 * int(raw_elements))


def _note_moe_ring(n: int, rings: int = 1) -> None:
    """``moe.ring_hops == (ep − 1) × moe.ring_calls`` by construction —
    the invariant the overlap tests pin."""
    _telemetry.counter("moe.ring_calls").inc(rings)
    _telemetry.counter("moe.ring_hops").inc((n - 1) * rings)


def _note_dropped(value: float) -> None:
    _telemetry.gauge("moe.dropped_fraction").set(float(value))


def _wire_block(h: int, block: int) -> int:
    """Per-row scale-block size: ``block`` when it tiles ``h`` exactly,
    else one block per row — ``quantize_blocks`` zero-pads to a block
    multiple, and padding a 64-wide row to 256 would *quadruple* the
    int8 wire instead of shrinking it."""
    return block if h % block == 0 else h


# ---------------------------------------------------------------------------
# grouped expert FFN over a sorted ragged layout
# ---------------------------------------------------------------------------


def _expert_matmul(xs, w, offsets, dtype, backend):
    """One expert-slab matmul: a float slab runs the historical
    :func:`grouped_matmul` path byte-identically; a pre-quantized slab
    (``{"wire", "scale"}`` from ``ops/grouped_matmul.
    quantize_group_weights`` via ``models/quantized.quantize_params``,
    ISSUE 14) runs the in-kernel dequantizing grouped matmul so the
    HBM expert-weight read is the int8 bytes."""
    from apex_tpu.ops.dense import is_quantized

    if is_quantized(w):
        from apex_tpu.ops.grouped_matmul import grouped_matmul_quantized

        # the caller's backend pin carries through (a parity run that
        # pinned the reference must not get the kernel's summation
        # order); None keeps the automatic routing
        return grouped_matmul_quantized(
            xs.astype(dtype), w["wire"], w["scale"], offsets,
            backend=backend)
    return grouped_matmul(xs.astype(dtype), w.astype(dtype), offsets,
                          backend=backend)


def _slab_groups(w) -> int:
    from apex_tpu.ops.dense import is_quantized

    if is_quantized(w):
        return int(w["wire"].shape[0])
    return int(w.shape[0])


def _grouped_ffn(xs, offsets, fc1, b1, fc2, b2, activation, dtype,
                 backend=None):
    """Expert FFN over ``xs`` [N, h] sorted by expert with segment
    ``offsets`` [G+1] (window allowed: rows outside stay exactly zero).
    Per-row biases gather through a zero-padded table so sentinel rows
    (outside the window / past the valid count) contribute nothing.
    ``fc1``/``fc2`` may be weight-only quantized slabs (ISSUE 14) —
    see :func:`_expert_matmul`; ``b1``/``b2`` may be ``None`` (bias-free
    experts)."""
    g_n = _slab_groups(fc1)
    gid = group_ids(offsets, xs.shape[0], g_n)

    def rows_of(b):
        if b is None:
            return None
        return jnp.concatenate(
            [b, jnp.zeros((1,) + b.shape[1:], b.dtype)])[gid]

    b1e, b2e = rows_of(b1), rows_of(b2)
    h1 = _expert_matmul(xs, fc1, offsets, dtype, backend)
    if activation == "swiglu":
        from apex_tpu.ops.swiglu import fused_bias_swiglu

        # the op's own fp32 bias path — the same precision contract as
        # the capacity path's per-expert vmapped application
        h1 = fused_bias_swiglu(h1, b1e)
    else:
        if b1e is not None:
            h1 = h1 + b1e.astype(dtype)
        h1 = h1.astype(jnp.float32)
        h1 = (jnp.square(jax.nn.relu(h1)) if activation == "relu2" else
              jax.nn.gelu(h1, approximate=activation == "gelu_tanh")
              ).astype(dtype)
    h2 = _expert_matmul(h1, fc2, offsets, dtype, backend)
    return h2 if b2e is None else h2 + b2e.astype(dtype)


def _sorted_assignment(choice, gates, e_n):
    """Flatten [T, k] assignments into the sorted-by-expert slot layout:
    ``(order [N], counts [E], token_of_sorted [N], gates_sorted [N],
    expert_sorted [N])`` with ``N = T·k``."""
    k = choice.shape[-1]
    fe = choice.reshape(-1)
    order = jnp.argsort(fe)                       # stable
    counts = jnp.bincount(fe, length=e_n).astype(jnp.int32)
    return (order, counts, order // k, gates.reshape(-1)[order],
            fe[order])


# ---------------------------------------------------------------------------
# compressed wire exchanges (straight-through VJPs: the backward wire is
# the same collective on the quantized cotangent)
# ---------------------------------------------------------------------------


def _caa_impl(x, axis_name, wire_dtype, block):
    from apex_tpu.utils.collectives import all_to_all

    xf = x.astype(jnp.float32)
    if wire_dtype == "fp32":
        _note_dispatch(xf, None, xf.size)
        return all_to_all(xf, axis_name, 0, 0, tiled=True)
    wire, scales = quantize_blocks(xf, wire_dtype, block)
    _note_dispatch(wire, scales, xf.size)
    rw = all_to_all(wire, axis_name, 0, 0, tiled=True)
    rs = (all_to_all(scales, axis_name, 0, 0, tiled=True)
          if scales is not None else None)
    return dequantize_blocks(rw, rs, block, x.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _compressed_all_to_all(x, axis_name, wire_dtype, block):
    """``all_to_all`` over dim 0 with the payload quantized on the wire
    (``comm/quantize`` block scales ride as a separate header exchange).
    Straight-through VJP: the cotangent takes the same compressed
    exchange back (all_to_all is its own transpose)."""
    return _caa_impl(x, axis_name, wire_dtype, block)


def _caa_fwd(x, axis_name, wire_dtype, block):
    return _caa_impl(x, axis_name, wire_dtype, block), None


def _caa_bwd(axis_name, wire_dtype, block, _res, g):
    return (_caa_impl(g, axis_name, wire_dtype, block),)


_compressed_all_to_all.defvjp(_caa_fwd, _caa_bwd)


def _crg_impl(x, axis_name, wire_dtype, block, n):
    from apex_tpu.ops.collective_matmul import ring_all_gather

    xf = x.astype(jnp.float32)
    if wire_dtype == "fp32":
        _note_dispatch(xf, None, xf.size)
        _note_moe_ring(n)
        return ring_all_gather(xf, axis_name, dim=0).reshape(
            (n,) + x.shape)
    wire, scales = quantize_blocks(xf, wire_dtype, block)
    _note_dispatch(wire, scales, xf.size)
    gw = ring_all_gather(wire, axis_name, dim=0)
    rings = 1
    gs = None
    if scales is not None:
        gs = ring_all_gather(scales, axis_name, dim=0).reshape(
            (n,) + scales.shape)
        rings += 1
    _note_moe_ring(n, rings)
    return dequantize_blocks(
        gw.reshape((n,) + wire.shape), gs, block, x.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _compressed_ring_gather(x, axis_name, wire_dtype, block, n):
    """All-gather ``x`` [C, ...] → [n, C, ...] as n−1 ``ppermute`` hops
    (``_ring_visit`` shape) with the payload quantized once at the
    source — every hop forwards the int8 wire + scale header, never the
    fp32 tensor.  Straight-through VJP: the cotangent rides the dual
    reduce-scatter ring (hop-wise backward; fp32 accumulator, since
    partial sums cannot ride int8 without per-hop requantization
    error)."""
    return _crg_impl(x, axis_name, wire_dtype, block, n)


def _crg_fwd(x, axis_name, wire_dtype, block, n):
    return _crg_impl(x, axis_name, wire_dtype, block, n), None


def _crg_bwd(axis_name, wire_dtype, block, n, _res, g):
    from apex_tpu.ops.collective_matmul import ring_reduce_scatter

    _note_moe_ring(n)
    gf = g.astype(jnp.float32)
    # the backward leg is fp32 on the wire (the accumulator cannot ride
    # int8 without per-hop requantization error) — book it so overlap
    # rows account fwd+bwd exchanges like the all_to_all rows do
    _note_dispatch(gf, None, gf.size)
    return (ring_reduce_scatter(
        gf.reshape((-1,) + g.shape[2:]), axis_name, dim=0),)


_compressed_ring_gather.defvjp(_crg_fwd, _crg_bwd)


# ---------------------------------------------------------------------------
# ragged (capacity-free) routing
# ---------------------------------------------------------------------------


# rows of the sorted buffer that one ``lax.cond`` covers when only some
# experts are held (a multiple of the grouped kernels' 512-row blocks)
_CHUNK_ROWS = 16384


def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0)


def _live_chunks(rows, n, chunk, out_shape):
    """``sum_c rows(lo_c, args, order, offsets)`` [T, h] over the chunks
    ``lo_c = c · chunk`` of the ``n`` sorted rows that start before the
    last held row (``offsets[-1]``); the others are skipped, forward and
    backward.  A ``lax.scan`` of ``lax.cond``s with a hand-written VJP:
    autodiff's transpose of a skipped ``cond`` would write a zero
    gradient as large as the expert slabs for every skipped chunk and
    add them all up; here the cotangents are accumulated in the scan's
    carry by the live chunks alone (each recomputes its forward, as the
    layer's remat would)."""
    starts = np.arange(0, n, chunk, dtype=np.int32)    # no tracer: closed over

    def scan_live(step, carry, offsets):
        def body(carry, lo):
            return jax.lax.cond(lo < offsets[-1],
                                lambda c: step(c, lo), lambda c: c,
                                carry), None
        return jax.lax.scan(body, carry, starts)[0]

    @jax.custom_vjp
    def run(args, order, offsets):
        return scan_live(
            lambda out, lo: out + rows(lo, args, order, offsets),
            jnp.zeros(out_shape, jnp.float32), offsets)

    def fwd(args, order, offsets):
        return run(args, order, offsets), (args, order, offsets)

    def bwd(res, g):
        args, order, offsets = res

        def step(acc, lo):
            pull = jax.vjp(lambda a: rows(lo, a, order, offsets), args)[1]
            return jax.tree_util.tree_map(jnp.add, acc, pull(g)[0])

        zeros = jax.tree_util.tree_map(jnp.zeros_like, args)
        return (scan_live(step, zeros, offsets), _float0(order),
                _float0(offsets))

    run.defvjp(fwd, bwd)
    return run


def _ragged_local(params, x2, choice, gates, activation, gmm_backend,
                  held=None):
    """Single-shard ragged path: sort-by-expert, grouped FFN, scatter-add
    combine weighted by the gates.  Also the fallback under GSPMD when the
    explicit island does not apply (the partitioner then gathers the
    expert weights — correct, just not expert-parallel).

    ``held = (first, count)``: the expert slabs hold experts ``first ..
    first + count`` of the router's ``E`` only (one rank's share of an
    expert-parallel layer).  Tokens are routed over all ``E``; the
    assignments on held experts sort to the front of the worst-case
    ``T·k`` buffer, and what the absent experts would have added is left
    out of the result.  No token is dropped whatever the imbalance, but
    the work follows the rows that are held: the buffer goes through in
    chunks of ``_CHUNK_ROWS`` sorted rows (:func:`_live_chunks`), a chunk
    that starts past the last held row is skipped whole (gather, grouped
    products, activation, scatter), and inside a chunk the grouped
    products cover the held rows alone.  Returns ``(out [T, h], load
    [E])``."""
    e_n = params["router"].shape[-1]
    t_n, h = x2.shape
    top_k = choice.shape[-1]
    n = t_n * top_k
    first, g_n = held if held is not None else (0, e_n)
    with jax.named_scope("moe_dispatch"):
        fe = choice.reshape(-1)
        load = jnp.sum(fe[:, None] == jnp.arange(e_n, dtype=fe.dtype),
                       axis=0, dtype=jnp.int32)
        local = fe - first
        key = jnp.where((local >= 0) & (local < g_n), local, g_n)
        order = jnp.argsort(key).astype(jnp.int32)          # stable
        offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(load[first:first + g_n], dtype=jnp.int32)])
    chunked = (held is not None and n > _CHUNK_ROWS
               and n % _CHUNK_ROWS == 0)
    chunk = _CHUNK_ROWS if chunked else n

    def rows(lo, args, order, offsets):
        """What sorted rows ``lo .. lo + chunk`` add to the output."""
        x2, gates, w = args
        slot = jax.lax.dynamic_slice(order, (lo,), (chunk,))
        with jax.named_scope("moe_dispatch"):
            tok = slot // top_k
            xs = x2[tok]
        with jax.named_scope("expert_ffn"):
            h2 = _grouped_ffn(
                xs, jnp.clip(offsets - lo, 0, chunk), w["fc1"],
                w.get("fc1_bias"), w["fc2"], w.get("fc2_bias"),
                activation, x2.dtype, gmm_backend)
        with jax.named_scope("moe_combine"):
            # a row past the window is zero by the grouped products'
            # contract, whatever its gate
            return jnp.zeros((t_n, h), jnp.float32).at[tok].add(
                gates.reshape(-1)[slot][:, None] * h2.astype(jnp.float32))

    args = (x2, gates, {k: v for k, v in params.items()
                        if k.startswith("fc")})
    if chunked:
        out = _live_chunks(rows, n, chunk, (t_n, h))(args, order, offsets)
    else:
        out = rows(0, args, order, offsets)
    return out.astype(x2.dtype), load


def _ep_abstract_mesh():
    from apex_tpu.ops.collective_matmul import _abstract_mesh

    return _abstract_mesh()


def _mesh_axis_size(mesh, axis_name) -> int:
    if mesh is None or axis_name is None:
        return 0
    return _mesh_axis(mesh, axis_name)


def _ragged_ep_island(params, x2, *, mesh, ep_axis, top_k,
                      router_noise_rng, activation, moe_comm, block,
                      overlap, gmm_backend):
    """Explicit expert-parallel ragged MoE: a shard_map island over the
    ``ep`` axis.  Tokens enter sharded over ep (``[T, h]`` per rank),
    experts live sharded (``E/ep`` per rank); each rank routes its own
    tokens, sorts them by global expert, and the dispatch/combine either

    - exchanges per-destination chunks through the counted
      ``all_to_all`` wrappers with the payload compressed per
      ``moe_comm`` (per-rank worst-case chunk size ``T·k`` — capacity-
      free means the wire must fit every token landing on one rank), or
    - (``overlap``) ring-gathers the compressed sorted token sets and
      runs the combine as a ``_ring_scatter_sum`` whose per-hop ``part``
      computes the local experts' grouped FFN for the rank the
      traveling accumulator is destined for — expert compute rides
      *inside* the ring, overlapped with the hops.
    """
    from apex_tpu.ops.collective_matmul import _ring_scatter_sum
    from apex_tpu.utils.collectives import all_gather, all_to_all, \
        match_vma, vma_of

    e_n = params["router"].shape[-1]
    tokens_total, h = x2.shape
    ep = _mesh_axis_size(mesh, ep_axis)
    e_local = e_n // ep
    block = _wire_block(h, block)
    dtype = x2.dtype

    def island(router, fc1, b1, fc2, b2, xt):
        t_n = xt.shape[0]                       # tokens per rank
        rank = jax.lax.axis_index(ep_axis)
        rng = router_noise_rng
        if rng is not None:
            rng = jax.random.fold_in(rng, rank)
        probs = _router_probs(router, xt, rng)
        choice, gates = _topk_routing(probs, top_k)
        n_slots = t_n * top_k
        order, counts, tok, gate_s, fe_s = _sorted_assignment(
            choice, gates, e_n)
        xs = xt[tok]                            # [N, h] sorted by expert
        off_full = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(counts, dtype=jnp.int32)])

        # global load / aux: every rank contributes its local counts and
        # prob mass; psum makes both axis-invariant (out_specs P())
        load = jax.lax.psum(counts.astype(jnp.float32), ep_axis)
        probs_mean = jax.lax.psum(
            jnp.sum(probs, axis=0), ep_axis) / tokens_total
        aux = _aux_loss(probs_mean, load, tokens_total * top_k)

        if overlap:
            # ---- ring dispatch: compressed sorted token sets travel
            # the ring; counts ride as the (tiny) header ----
            counts_all = all_gather(counts, ep_axis, axis=0,
                                    tiled=False)            # [ep, E]
            # fp32 into the exchange: the straight-through VJP's
            # cotangent comes back fp32, so the primal must be too
            gathered = _compressed_ring_gather(
                xs.astype(jnp.float32), ep_axis, moe_comm, block,
                ep)                                         # [ep, N, h]

            # ---- combine ring: the rotating accumulator visits every
            # rank; part(d) computes MY experts' grouped FFN over rank
            # d's sorted tokens (their window of the global expert
            # range) the hop the accumulator destined for d is here —
            # compute overlaps transfer, the collective-matmul way ----
            def part(d):
                xd = jnp.take(gathered, d, axis=0)          # [N, h]
                cnt = jnp.take(counts_all, d, axis=0)       # [E]
                offd = jnp.concatenate(
                    [jnp.zeros(1, jnp.int32),
                     jnp.cumsum(cnt, dtype=jnp.int32)])
                window = jax.lax.dynamic_slice(
                    offd, (rank * e_local,), (e_local + 1,))
                return _grouped_ffn(
                    xd.astype(dtype), window, fc1, b1, fc2, b2,
                    activation, dtype, gmm_backend).astype(jnp.float32)

            res_sorted = _ring_scatter_sum(
                ep_axis, ep, (n_slots, h), jnp.float32, part, xs)
            _note_moe_ring(ep)
            # combine ring: the fp32 accumulator chunk is the wire
            # payload (one chunk traveling per rank per trace)
            _note_dispatch(res_sorted, None, res_sorted.size)
        else:
            # ---- counted all_to_all dispatch: per-destination chunks
            # of the sorted layout; the count matrix is the header the
            # receiver rebuilds expert ids from (slots arrive sorted by
            # local expert within each source chunk) ----
            cap = n_slots                       # worst case: all → one
            dest = fe_s // e_local              # [N] destination rank
            doff = off_full[jnp.arange(ep + 1) * e_local]
            within = jnp.arange(n_slots, dtype=jnp.int32) - doff[dest]
            buf = match_vma(jnp.zeros((ep, cap, h), jnp.float32),
                            vma_of(xs))
            buf = buf.at[dest, within].set(xs.astype(jnp.float32))
            cmat = counts.reshape(ep, e_local)
            recv_cmat = all_to_all(cmat, ep_axis, 0, 0, tiled=True)
            recv = _compressed_all_to_all(
                buf, ep_axis, moe_comm, block)  # [ep(src), cap, h]

            # regroup by local expert across sources (stable sort keeps
            # source order within an expert — the return trip relies on
            # positions, not ids)
            rtot = jnp.sum(recv_cmat, axis=1)
            eid = jax.vmap(lambda c: jnp.repeat(
                jnp.arange(e_local, dtype=jnp.int32), c,
                total_repeat_length=cap))(recv_cmat)
            valid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                     < rtot[:, None])
            keys = jnp.where(valid, eid, e_local).reshape(-1)
            order2 = jnp.argsort(keys)
            xs2 = recv.reshape(ep * cap, h)[order2]
            gcounts = jnp.sum(recv_cmat, axis=0)
            goff = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(gcounts, dtype=jnp.int32)])
            h2 = _grouped_ffn(xs2.astype(dtype), goff, fc1, b1, fc2, b2,
                              activation, dtype, gmm_backend)

            ret = match_vma(jnp.zeros((ep * cap, h), jnp.float32),
                            vma_of(h2))
            ret = ret.at[order2].set(h2.astype(jnp.float32))
            back = _compressed_all_to_all(
                ret.reshape(ep, cap, h), ep_axis, moe_comm, block)
            res_sorted = back[dest, within]     # [N, h]

        outf = match_vma(jnp.zeros((t_n, h), jnp.float32),
                         vma_of(res_sorted))
        outf = outf.at[tok].add(gate_s[:, None] * res_sorted)
        return outf.astype(dtype), aux, load

    rest = tuple(None for _ in range(x2.ndim - 1))
    f = jax.shard_map(
        island, mesh=mesh,
        in_specs=(P(), P(ep_axis), P(ep_axis), P(ep_axis), P(ep_axis),
                  P(ep_axis, *rest)),
        out_specs=(P(ep_axis, *rest), P(), P()))
    return f(params["router"], params["fc1"], params["fc1_bias"],
             params["fc2"], params["fc2_bias"], x2)


# ---------------------------------------------------------------------------
# capacity (Switch drop-token) routing — the original einsum formulation
# ---------------------------------------------------------------------------


def _capacity_moe(params, x, *, capacity_factor, top_k, ep_axis,
                  router_noise_rng, activation):
    b, s, h = x.shape
    e_n = params["router"].shape[-1]
    cap = max(1, math.ceil(top_k * s * capacity_factor / e_n))

    probs = _router_probs(params["router"],
                          x.reshape(b * s, h), router_noise_rng
                          ).reshape(b, s, e_n)

    combine = jnp.zeros((b, s, e_n, cap), jnp.float32)
    remaining = probs
    position_in_expert = jnp.zeros((b, e_n), jnp.int32)
    dropped = jnp.zeros((), jnp.float32)
    sel_counts = jnp.zeros((e_n,), jnp.float32)
    for _ in range(top_k):
        choice = jnp.argmax(remaining, axis=-1)           # [b, s]
        gate = jnp.take_along_axis(
            remaining, choice[..., None], axis=-1)[..., 0]  # [b, s]
        onehot = jax.nn.one_hot(choice, e_n)               # [b, s, E]
        # all k selections feed the balance term (and expert_load) —
        # an argmax-only count would hide the runner-up traffic
        sel_counts = sel_counts + jnp.sum(onehot, axis=(0, 1))
        # position of each token within its chosen expert's queue
        pos = (jnp.cumsum(onehot, axis=1) - 1.0)           # [b, s, E]
        pos_tok = jnp.sum(pos * onehot, axis=-1)           # [b, s]
        pos_tok = pos_tok + jnp.take_along_axis(
            position_in_expert.astype(jnp.float32),
            choice, axis=-1)
        keep = pos_tok < cap
        dropped = dropped + jnp.sum(~keep) / (b * s * top_k)
        slot = jax.nn.one_hot(
            jnp.where(keep, pos_tok, cap).astype(jnp.int32),
            cap)                                           # [b, s, cap]
        combine = combine + (gate * keep)[..., None, None] \
            * onehot[..., None] * slot[:, :, None, :]
        position_in_expert = position_in_expert + jnp.sum(
            (onehot * keep[..., None]).astype(jnp.int32), axis=1)
        remaining = remaining * (1.0 - onehot)

    dispatch = (combine > 0.0).astype(x.dtype)             # [b, s, E, cap]

    # token-major -> expert-major (GSPMD inserts the all-to-all here)
    expert_in = jnp.einsum(
        "bsec,bsh->ebch", dispatch, x)                     # [E, b, cap, h]
    expert_in = _expert_constrain(expert_in, ep_axis)
    fc1 = _expert_constrain(params["fc1"], ep_axis)
    fc2 = _expert_constrain(params["fc2"], ep_axis)
    h1 = jnp.einsum("ebch,ehf->ebcf", expert_in, fc1.astype(x.dtype))
    bias1 = _expert_constrain(params["fc1_bias"], ep_axis)
    if activation == "swiglu":
        from apex_tpu.ops.swiglu import fused_bias_swiglu

        # vmap over experts so each expert's [2f] bias rides the op's
        # own fp32 bias path (same precision contract as the dense FFN)
        h1 = jax.vmap(fused_bias_swiglu)(h1, bias1)
    else:
        h1 = h1 + bias1[:, None, None, :].astype(x.dtype)
        h1 = jax.nn.gelu(h1.astype(jnp.float32),
                         approximate=activation == "gelu_tanh"
                         ).astype(x.dtype)
    h2 = jnp.einsum("ebcf,efh->ebch", h1, fc2.astype(x.dtype))
    h2 = h2 + _expert_constrain(params["fc2_bias"], ep_axis)[
        :, None, None, :].astype(x.dtype)
    h2 = _expert_constrain(h2, ep_axis)

    # expert-major -> token-major, weighted by the router gates
    out = jnp.einsum(
        "bsec,ebch->bsh", combine.astype(x.dtype), h2)     # [b, s, h]

    aux = _aux_loss(jnp.mean(probs, axis=(0, 1)), sel_counts,
                    b * s * top_k)
    return MoEOutput(out=out.astype(x.dtype),
                     aux_loss=aux,
                     dropped_fraction=dropped,
                     expert_load=sel_counts)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def switch_moe_mlp(
    params: dict,
    x: jax.Array,
    *,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    ep_axis: Optional[str] = "ep",
    router_noise_rng: Optional[jax.Array] = None,
    activation: str = "gelu",
    routing: str = "capacity",
    moe_comm: str = "fp32",
    comm_block: int = 256,
    overlap_comm: Optional[bool] = None,
    ep_mesh=None,
    gmm_backend: Optional[str] = None,
    router: str = "softmax",
    experts_held: Optional[tuple] = None,
    routed_scaling: float = 1.0,
    gate_epsilon: float = 1e-6,
) -> MoEOutput:
    """Token-choice top-k MoE FFN over ``x`` [b, s, h].

    ``routing="capacity"`` (default): static shapes throughout — each
    expert processes ``ceil(top_k · s · capacity_factor / E)`` token
    slots per batch row; tokens over capacity fall through with a zero
    update (the Switch drop-token rule) and are reported in
    ``dropped_fraction``.  EP comes from the GSPMD partitioner via the
    ``P(ep_axis, ...)`` constraints on the expert-major einsums.

    ``routing="ragged"``: capacity-free — no token is dropped
    (``dropped_fraction == 0.0`` by construction) and no pad slots are
    computed; expert FFNs run over sorted ragged segments through
    ``ops/grouped_matmul``.  ``capacity_factor`` is ignored.  On a mesh
    with a ``>1``-sized ``ep_axis`` (the ambient abstract mesh, or an
    explicit ``ep_mesh``) and divisible token/expert counts, dispatch
    and combine run *explicitly* in a shard_map island through the
    counted ``all_to_all`` wrappers with the wire compressed per
    ``moe_comm`` (``"fp32"|"bf16"|"int8"``, block scales of
    ``comm_block``); ``overlap_comm`` (tri-state — ``None`` reads the
    ambient ``ops.collective_matmul.overlap_scope``) swaps the
    all-to-alls for ``ppermute`` rings with per-hop expert compute.
    When the island does not apply the ragged math runs unsharded
    (GSPMD then gathers the expert weights — correct, not
    expert-parallel).

    ``activation='swiglu'`` expects ``fc1``/``fc1_bias`` with a doubled
    trailing dim ``2f`` ([gate ‖ up] concatenated) and applies the fused
    bias-SwiGLU epilogue (ops/swiglu.py) inside each expert.  The bias
    leaves may be absent (bias-free experts).  ``activation='relu2'``
    (ragged routing only) is ``relu(x)^2`` on an ``f``-wide ``fc1``.

    ``router="sigmoid"`` (ragged routing only): float32 sigmoid scores,
    the ``top_k`` largest of ``score + params["router_bias"]`` chosen
    (the bias selects and never weighs; it gets no gradient), gates
    normalised over the chosen, ``r / (sum + gate_epsilon) *
    routed_scaling`` (:func:`_sigmoid_routing`); no auxiliary loss is
    defined for it (``aux_loss`` is 0).

    ``experts_held=(first, count)`` (ragged routing only): ``fc1``/
    ``fc2`` hold experts ``first .. first + count`` of the router's
    ``E``, this caller being one rank of an expert-parallel layer.  The
    layer routes over all ``E`` and returns the part of the result that
    its own experts give, with no exchange; ``expert_load`` still counts
    all ``E``.  ``None`` means all experts are held.
    """
    if routing not in MOE_ROUTINGS:
        raise ValueError(
            f"routing={routing!r}: expected one of {MOE_ROUTINGS}")
    if moe_comm not in WIRE_DTYPES:
        raise ValueError(
            f"moe_comm={moe_comm!r}: expected one of {WIRE_DTYPES}")
    from apex_tpu.ops.dense import is_quantized

    if is_quantized(params.get("fc1")) or is_quantized(params.get("fc2")):
        # weight-only quantized expert slabs (ISSUE 14) run ONLY on the
        # local ragged path: the capacity einsum would need a dense
        # dequantize (no bandwidth win) and the EP island would ship
        # dict leaves through shard_map specs built for arrays
        if routing != "ragged":
            raise ValueError(
                "quantized expert slabs need routing='ragged' (the "
                "capacity einsum path has no int8 form)")
        if ep_mesh is not None or _mesh_axis_size(
                _ep_abstract_mesh(), ep_axis) >= 2:
            raise ValueError(
                "quantized expert slabs are a single-device serving "
                "path; run them outside an expert-parallel mesh")
    if routing != "ragged" and (router != "softmax"
                                or experts_held is not None
                                or activation == "relu2"):
        raise ValueError(
            "router='sigmoid', experts_held and activation='relu2' need "
            "routing='ragged'")
    if router not in MOE_ROUTERS:
        raise ValueError(
            f"router={router!r}: expected one of {MOE_ROUTERS}")
    if routing == "capacity":
        return _capacity_moe(
            params, x, capacity_factor=capacity_factor, top_k=top_k,
            ep_axis=ep_axis, router_noise_rng=router_noise_rng,
            activation=activation)

    from apex_tpu.ops.collective_matmul import overlap_enabled

    b, s, h = x.shape
    e_n = params["router"].shape[-1]
    x2 = x.reshape(b * s, h)
    _note_dropped(0.0)   # drop-free by construction (asserted in tests)

    mesh = ep_mesh if ep_mesh is not None else _ep_abstract_mesh()
    ep = _mesh_axis_size(mesh, ep_axis)
    local = router != "softmax" or experts_held is not None
    if not local and ep >= 2 and (b * s) % ep == 0 and e_n % ep == 0:
        out2, aux, load = _ragged_ep_island(
            params, x2, mesh=mesh, ep_axis=ep_axis, top_k=top_k,
            router_noise_rng=router_noise_rng, activation=activation,
            moe_comm=moe_comm, block=comm_block,
            overlap=overlap_enabled(overlap_comm),
            gmm_backend=gmm_backend)
    else:
        with jax.named_scope("router"):
            if router == "sigmoid":
                choice, gates, _ = _sigmoid_routing(
                    params["router"], params.get("router_bias"), x2,
                    top_k, routed_scaling, gate_epsilon)
                probs = None
            else:
                probs = _router_probs(params["router"], x2,
                                      router_noise_rng)
                choice, gates = _topk_routing(probs, top_k)
        out2, counts = _ragged_local(
            params, x2, choice, gates, activation, gmm_backend,
            held=experts_held)
        load = counts.astype(jnp.float32)
        aux = (jnp.zeros((), jnp.float32) if probs is None else
               _aux_loss(jnp.mean(probs, axis=0), load, b * s * top_k))

    return MoEOutput(out=out2.reshape(b, s, h).astype(x.dtype),
                     aux_loss=aux,
                     dropped_fraction=jnp.zeros((), jnp.float32),
                     expert_load=load)
