"""Fused dense (GEMM + bias [+ GELU + GEMM]) building blocks — plus the
weight-only int8 quantized matmul path (ISSUE 14).

Reference: csrc/fused_dense_cuda.cu drives cublasLt epilogue fusion
(GEMM+bias, GEMM+bias+GELU with saved pre-GELU, and the bgradb/dgelu
backward epilogues), wrapped by apex/fused_dense/fused_dense.py
(``FusedDense`` :8, ``FusedDenseGeluDense`` :102) and apex/mlp (whole MLP in
two native calls, mlp.py:11,33).

On TPU, XLA performs exactly these epilogue fusions automatically: a
``dot_general`` followed by bias-add/GELU lowers to one MXU op with a fused
epilogue, and the wgrad/dgrad GEMMs fuse their epilogues in backward. So the
functions below are thin, *correct-by-construction* compositions — they
exist to give reference users the same call surface, keep the math in
``preferred_element_type=float32`` (the MXU accumulates fp32), and anchor
the numerics tests. The custom kernel layer the reference needs does not
earn its keep here; profiling on v5e shows XLA emits single fused kernels
for these shapes (coverage: tests/test_rope_swiglu_xentropy.py:228).

**Weight-only quantization** (the serving half of ISSUE 14): decode is
HBM-bandwidth-bound — every generated token re-reads the whole weight
set, so the bytes the weights occupy set tokens/s, not the FLOPs.
:func:`quantize_weight` converts a ``[in, *out]`` kernel to symmetric
int8 with one fp32 scale per ``(in-block, output column)`` (block-scaled
along the contraction axis — the EQuARX neighborhood-scaling design of
``comm/quantize``, applied to weights at rest), and
:func:`dense_quantized` runs ``x @ W`` off the int8 slab: a Pallas
kernel whose k-grid IS the quantization blocking, so each inner-loop
step dequantizes its ``[kb, out]`` tile in VMEM (one multiply by the
tile's scale row after the int8 dot) — the fp32 weights never exist in
HBM and the per-token weight read drops to ~1/4 (fp32) or ~1/2 (bf16)
of the raw bytes.  The XLA reference path dequantizes whole slabs (the
parity oracle); ``backend=`` routes like every other op here
(``_pallas_utils.resolve_backend``).  ``custom_vjp`` keeps the backward
in high precision: ``dx`` is computed against the fp32-dequantized weights, the
frozen wire/scales get zero cotangents (weight-only quantization is a
serving conversion — nothing trains through it).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import on_tpu, resolve_backend

__all__ = ["QUANT_BLOCK", "dense_quantized", "dequantize_weight",
           "fused_dense_function", "fused_dense_gelu_dense_function",
           "is_quantized", "pick_quant_block", "quantize_weight",
           "quantized_matmul"]


def _matmul(x, w):
    return jax.lax.dot_general(
        x, w,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def fused_dense_function(
    x: jax.Array, weight: jax.Array, bias: Optional[jax.Array] = None
) -> jax.Array:
    """y = x @ W + b with fp32 accumulation; W is [in, out].

    (reference fused_dense_function, apex/fused_dense/fused_dense.py:64 —
    note the reference stores torch-convention [out, in]; pass W.T
    equivalents when porting weights.)
    """
    y = _matmul(x, weight)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y.astype(x.dtype)


def fused_dense_gelu_dense_function(
    x: jax.Array,
    weight1: jax.Array,
    bias1: Optional[jax.Array],
    weight2: jax.Array,
    bias2: Optional[jax.Array] = None,
) -> jax.Array:
    """y = GELU(x @ W1 + b1) @ W2 + b2 (reference fused_dense.py:102;
    cublasLt GELU_AUX epilogue ≙ XLA fusing the gelu into the first GEMM)."""
    h = fused_dense_function(x, weight1, bias1)
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=False)
    return fused_dense_function(h.astype(x.dtype), weight2, bias2)


# ---------------------------------------------------------------------------
# Weight-only int8 quantization (ISSUE 14)
# ---------------------------------------------------------------------------

QUANT_BLOCK = 128        # contraction-axis quantization block (= the
_INT8_MAX = 127.0        # kernel's k tile, so dequant IS the inner loop)


def pick_quant_block(in_dim: int, block: Optional[int] = None) -> int:
    """Largest divisor of ``in_dim`` that is ``<= block`` — the
    quantization block must tile the contraction axis exactly (the
    kernel's k grid walks whole blocks; zero-padding weights would
    change the matmul's reduction shape)."""
    block = QUANT_BLOCK if block is None else int(block)
    if block < 1:
        raise ValueError(f"block={block} must be positive")
    want = min(block, in_dim)
    for b in range(want, 0, -1):
        if in_dim % b == 0:
            return b
    return 1


def is_quantized(leaf) -> bool:
    """True for a quantized-weight leaf (the dict form
    :func:`quantize_weight` emits; model code branches on this at every
    matmul site — ``models/quantized.quantize_params`` produces trees
    whose kernels are these dicts)."""
    return isinstance(leaf, dict) and "wire" in leaf and "scale" in leaf


def quantize_weight(w, block: Optional[int] = None) -> dict:
    """Symmetric round-to-nearest int8 along the CONTRACTION axis
    (axis 0): ``w`` ``[in, *out]`` float → ``{"wire": int8 [in, *out],
    "scale": fp32 [in/kb, *out]}`` with one scale per (k-block, output
    column) — ``kb = pick_quant_block(in, block)``.  All-zero columns
    get scale 1 (exact round-trip); a NaN weight poisons its scale
    rather than laundering into finite int8 (same contract as
    ``comm/quantize``).  The block is recoverable from the shapes
    (``in // scale.shape[0]``), so the dict stays a pure array pytree —
    it scans, donates, and shards like the float kernel it replaces."""
    w = jnp.asarray(w)
    if w.ndim < 2:
        raise ValueError(
            f"quantize_weight expects [in, *out] kernels, got {w.shape}")
    in_dim = w.shape[0]
    kb = pick_quant_block(in_dim, block)
    if kb <= 4 and in_dim > kb:
        # a prime-ish in_dim forced a tiny divisor: at 4/kb >= 1
        # scale-bytes per element the "quantized" slab is no smaller
        # than bf16 — the conversion would silently inflate the bytes
        # it exists to halve
        import warnings

        warnings.warn(
            f"quantize_weight: in_dim {in_dim} has no block divisor "
            f"<= {block or QUANT_BLOCK} larger than {kb}; at "
            f"{4 / kb:.1f} scale bytes/element the int8 form saves "
            "nothing over bf16 — pad the kernel or keep it float",
            stacklevel=2)
    out_shape = w.shape[1:]
    wf = w.astype(jnp.float32).reshape((in_dim // kb, kb) + out_shape)
    amax = jnp.max(jnp.abs(wf), axis=1)
    scale = jnp.where(amax == 0, 1.0, amax / _INT8_MAX)
    q = jnp.round(wf / scale[:, None])
    wire = jnp.clip(q, -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    return {"wire": wire.reshape(w.shape), "scale": scale}


def _quant_block_of(wire, scale) -> int:
    in_dim, nkb = wire.shape[0], scale.shape[0]
    if nkb < 1 or in_dim % nkb:
        raise ValueError(
            f"scale blocks ({nkb}) do not tile the contraction axis "
            f"({in_dim})")
    if wire.shape[1:] != scale.shape[1:]:
        raise ValueError(
            f"wire {wire.shape} / scale {scale.shape}: output axes "
            "must match")
    return in_dim // nkb


def dequantize_weight(wire, scale):
    """fp32 weights from a quantized slab (the backward path and the
    reference route; also the ``dequantize_params`` fake-quant oracle)."""
    kb = _quant_block_of(wire, scale)
    nkb = scale.shape[0]
    wf = wire.astype(jnp.float32).reshape((nkb, kb) + wire.shape[1:])
    return (wf * scale[:, None]).reshape(wire.shape)


# -- Pallas kernel ----------------------------------------------------------

_ROW_BLOCK = 128


def _dq_kernel(n_rows, bm, *refs):
    """Grid (row-block, k-block): the k grid dimension IS the
    quantization blocking, so each step's weight tile ``[kb, p]``
    dequantizes with ONE multiply by its scale row right after the
    int8 dot — the inner-loop dequant the at-rest format exists for
    (the scale is constant over the tile's k span, so it commutes with
    the in-tile reduction: ``dot(x, q)·s == dot(x, q·s)``)."""
    x_ref, w_ref, s_ref, o_ref, acc = refs
    i, s = pl.program_id(0), pl.program_id(1)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    xm = jnp.where(rows < n_rows, x_ref[:].astype(jnp.float32), 0.0)
    part = jax.lax.dot(xm, w_ref[:].astype(jnp.float32),
                       preferred_element_type=jnp.float32) * s_ref[0]

    @pl.when(s == 0)
    def _init():
        acc[:] = part

    @pl.when(s > 0)
    def _accum():
        acc[:] = acc[:] + part

    o_ref[:] = acc[:].astype(o_ref.dtype)


def _dq_pallas(x, wire, scale, kb, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, k = x.shape
    p = wire.shape[1]
    nkb = scale.shape[0]
    bm = _ROW_BLOCK if n >= _ROW_BLOCK else max(8, 8 * pl.cdiv(n, 8))
    grid = (pl.cdiv(n, bm), nkb)
    return pl.pallas_call(
        functools.partial(_dq_kernel, n, bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kb), lambda i, s: (i, s)),
            pl.BlockSpec((kb, p), lambda i, s: (s, 0)),
            # scale rows ride a unit MIDDLE axis: Mosaic only takes
            # a unit second-last block dim when it equals the array's
            pl.BlockSpec((1, 1, p), lambda i, s: (s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, p), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, p), jnp.float32)],
        interpret=interpret,
    )(x, wire, scale[:, None, :])


def _dq_impl(x2, wire2, scale2, kb, backend):
    if x2.shape[0] == 0:
        return jnp.zeros((0, wire2.shape[1]), x2.dtype)
    if resolve_backend("quantized matmul", backend) == "reference":
        deq = dequantize_weight(wire2, scale2)
        out = jax.lax.dot(x2.astype(jnp.float32), deq,
                          preferred_element_type=jnp.float32)
        return out.astype(x2.dtype)
    return _dq_pallas(x2, wire2, scale2, kb, interpret=not on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dqmm(x2, wire2, scale2, kb, backend, x_dtype):
    return _dq_impl(x2, wire2, scale2, kb, backend)


def _dqmm_fwd(x2, wire2, scale2, kb, backend, x_dtype):
    return _dqmm(x2, wire2, scale2, kb, backend, x_dtype), (wire2,
                                                            scale2)


def _dqmm_bwd(kb, backend, x_dtype, res, g):
    # high-precision backward: dx against the fp32-dequantized weights
    # (no re-quantization error enters the cotangent); the wire is
    # integer (float0 tangent) and the scales are FROZEN serving
    # constants — zero cotangent by contract, documented at
    # quantize_weight
    wire2, scale2 = res
    deq = dequantize_weight(wire2, scale2)
    dx = jax.lax.dot(g.astype(jnp.float32), deq.T,
                     preferred_element_type=jnp.float32).astype(x_dtype)
    return (dx, np.zeros(wire2.shape, jax.dtypes.float0),
            jnp.zeros_like(scale2))


_dqmm.defvjp(_dqmm_fwd, _dqmm_bwd)


def dense_quantized(x, wire, scale, *, backend: Optional[str] = None):
    """``x [..., in] @ W`` off a pre-quantized weight slab → ``[...,
    *out]`` in ``x.dtype`` (fp32 accumulation; trailing weight axes are
    flattened for the GEMM and restored on the output, so the swiglu
    paired ``[h, 2, f]`` kernel works unchanged).

    ``wire`` int8 ``[in, *out]`` + ``scale`` fp32 ``[in/kb, *out]``
    from :func:`quantize_weight`.  ``backend`` routes like every other
    op (``_pallas_utils.resolve_backend``): the Pallas kernel dequantizes
    each ``[kb, out]`` tile in its inner loop; the reference dequantizes the
    whole slab in XLA — the parity oracle, and exactly what a
    fake-quantized float model computes (the dequantize-then-generate
    pin in tests/test_quantized_matmul.py)."""
    wire = jnp.asarray(wire)
    scale = jnp.asarray(scale)
    kb = _quant_block_of(wire, scale)
    in_dim = wire.shape[0]
    if x.shape[-1] != in_dim:
        raise ValueError(
            f"contraction mismatch: x [..., {x.shape[-1]}] vs wire "
            f"[{in_dim}, ...]")
    out_shape = wire.shape[1:]
    p = 1
    for d in out_shape:
        p *= d
    x2 = x.reshape(-1, in_dim)
    out = _dqmm(x2, wire.reshape(in_dim, p),
                scale.reshape(scale.shape[0], p), kb, backend,
                jnp.dtype(x.dtype).name)
    return out.reshape(x.shape[:-1] + out_shape)


def quantized_matmul(x, leaf, *, backend: Optional[str] = None):
    """The one matmul-site helper: ``leaf`` is either a plain kernel
    array (cast to ``x.dtype`` and multiplied exactly as the historical
    sites did — byte-identical to the pre-quantization code path) or a
    quantized dict, in which case the int8 slab path runs."""
    if is_quantized(leaf):
        return dense_quantized(x, leaf["wire"], leaf["scale"],
                               backend=backend)
    return x @ leaf.astype(x.dtype)
