"""Plain transformer pieces in float32 ``jax.numpy``, shared by the GPT and
BERT references.  Imports nothing from apex_tpu.

Every matrix product goes through ``Precision.mm``/``einsum`` so that one
switch turns the float32 reference (``highest``: no bf16 passes on a TPU)
into the lower-precision control that has to come out as not correct:

- ``float32``: operands as they are, ``precision=HIGHEST``;
- ``bfloat16``: operands and cotangents rounded to 8 exponent and 7
  mantissa bits, accumulated in float32.  Not a control: the precision
  the configurations state, and the unit the check's ``grad_noise`` is
  measured in (``reference/train.py``);
- ``float8``: the usual fp8 training recipe.  Operands rounded to 4 exponent
  and 3 mantissa bits (e4m3) under a per-tensor scale (amax -> 240, the
  format's largest finite value without the ``fn`` extension), accumulated
  in float32; the backward products see those rounded operands
  (straight-through) and a cotangent rounded to 5 exponent and 2 mantissa
  bits (e5m2) under its own per-tensor scale;
- ``int8``: the same recipe on the 255 levels of a symmetric per-tensor
  int8 (amax -> 127), operands and cotangents alike: the lower precision
  a TPU v5e has hardware for (393 TOP/s against 197 TFLOP/s in bfloat16).

The rounding is ``jax.lax.reduce_precision``, which the compiler may not
remove: a round trip through ``astype`` is "excess precision" to XLA on a
TPU and was elided there (a control rounded that way read a gap of exactly
0; my chip run, PR 26).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# (exponent bits, mantissa bits, largest finite value) of a product's
# operands and of the cotangent that comes back through it; for int8 the
# largest value alone (127 steps either side of nought)
_FORMATS = {
    "bfloat16": ((8, 7, None), (8, 7, None)),
    "float8": ((4, 3, 240.0), (5, 2, 57344.0)),
    "int8": ((None, None, 127.0), (None, None, 127.0)),
}


def _rounded(x, fmt):
    exponent, mantissa, largest = fmt
    if largest is None:
        return jax.lax.reduce_precision(x, exponent, mantissa)
    scale = largest / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if exponent is None:
        return jnp.round(x * scale) / scale
    return jax.lax.reduce_precision(x * scale, exponent, mantissa) / scale


def _round_to(x, kind: str):
    """An operand as the product sees it; its gradient passes through."""
    if kind == "float32":
        return x
    return x + jax.lax.stop_gradient(_rounded(x, _FORMATS[kind][0]) - x)


def _round_cotangent(y, kind: str):
    """A product's output, whose cotangent is rounded on its way back."""
    if kind == "float32":
        return y

    @jax.custom_vjp
    def through(y):
        return y

    through.defvjp(lambda y: (y, None),
                   lambda _, g: (_rounded(g, _FORMATS[kind][1]),))
    return through(y)


class Precision:
    """The arithmetic a reference run uses for its matrix products."""

    def __init__(self, kind: str = "float32"):
        if kind != "float32" and kind not in _FORMATS:
            raise ValueError(f"unknown precision {kind!r}")
        self.kind = kind

    def einsum(self, spec: str, a, b):
        a, b = _round_to(a, self.kind), _round_to(b, self.kind)
        return _round_cotangent(
            jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32), self.kind)

    def mm(self, a, b):
        return self.einsum("...k,kn->...n", a, b)


def layer_norm(x, scale, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu(x, kind: str):
    """``gelu`` is the exact erf form, ``gelu_new``/``gelu_tanh`` the tanh
    approximation GPT-2 was trained with."""
    if kind == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    if kind in ("gelu_new", "gelu_tanh"):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation {kind!r}")


def attention(x, lp, *, n_heads: int, causal: bool, prec: Precision):
    """Multi-head self-attention.  The fused QKV kernel is laid out per head
    as ``[q | k | v]`` (the Megatron interleave the configuration assumes)."""
    b, s, h = x.shape
    dh = h // n_heads
    qkv = prec.mm(x, lp["qkv_kernel"]) + lp["qkv_bias"]
    qkv = qkv.reshape(b, s, n_heads, 3 * dh)
    q, k, v = qkv[..., :dh], qkv[..., dh:2 * dh], qkv[..., 2 * dh:]
    scores = prec.einsum("bsnd,btnd->bnst", q, k) / math.sqrt(dh)
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = prec.einsum("bnst,btnd->bsnd", probs, v).reshape(b, s, h)
    return prec.mm(ctx, lp["proj_kernel"]) + lp["proj_bias"]


def layer(x, lp, *, n_heads: int, causal: bool, pre_ln: bool, eps: float,
          act: str, prec: Precision):
    """One transformer block.  ``pre_ln`` puts LayerNorm before each
    sub-block (GPT-2, Megatron BERT); otherwise after each residual add
    (the published BERT)."""
    kw = dict(n_heads=n_heads, causal=causal, prec=prec)

    def mlp(h):
        y = gelu(prec.mm(h, lp["fc1_kernel"]) + lp["fc1_bias"], act)
        return prec.mm(y, lp["fc2_kernel"]) + lp["fc2_bias"]

    if pre_ln:
        x = x + attention(
            layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], eps), lp, **kw)
        return x + mlp(layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], eps))
    x = layer_norm(x + attention(x, lp, **kw),
                   lp["ln1_scale"], lp["ln1_bias"], eps)
    return layer_norm(x + mlp(x), lp["ln2_scale"], lp["ln2_bias"], eps)


def stack(x, layers, **kw):
    """All layers (stacked on a leading axis), one at a time, each
    recomputed in the backward pass so that float32 activations of 24
    layers fit beside the optimizer state."""
    @jax.checkpoint
    def body(h, lp):
        return layer(h, lp, **kw), None

    return jax.lax.scan(body, x, layers)[0]


def blocked_cross_entropy(hidden, head, bias, labels, prec: Precision,
                          block: int = 2048):
    """Sum over rows of the cross-entropy of ``hidden @ head.T + bias``
    against ``labels`` (rows with a negative label add nothing), and the
    count of rows that do count.  Rows go through in blocks so that the
    [rows, vocabulary] logits never exist whole."""
    rows = hidden.shape[0]
    block = math.gcd(rows, block)
    hb = hidden.reshape(rows // block, block, hidden.shape[-1])
    lb = labels.reshape(rows // block, block)

    @jax.checkpoint
    def one(args):
        h, lab = args
        logits = prec.einsum("rh,vh->rv", h, head) + bias
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(lab, 0)[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(lab >= 0, picked, 0.0))

    total = jnp.sum(jax.lax.map(one, (hb, lb)))
    return total, jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)


def normal_tree(key, spec):
    """``spec`` maps names to ``(shape, mean, std)`` or to nested specs of
    the same kind; every leaf is drawn from its own fold of ``key``."""
    paths = []

    def walk(node, path):
        if isinstance(node, dict):
            for name in sorted(node):
                walk(node[name], path + (name,))
        else:
            paths.append(path)

    walk(spec, ())
    out = {}
    for i, path in enumerate(paths):
        node = spec
        for name in path:
            node = node[name]
        shape, mean, std = node
        leaf = mean + std * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        dest = out
        for name in path[:-1]:
            dest = dest.setdefault(name, {})
        dest[path[-1]] = leaf
    return out


def layer_spec(n_layers: int, h: int, ffn: int, std: float) -> dict:
    """Shapes and initial spread of one stack of layers: N(0, std) kernels,
    output projections narrower by sqrt(2L), LayerNorm scales about 1, and
    small random biases so that no term of the arithmetic is multiplied by
    an exact zero."""
    out_std = std / math.sqrt(2.0 * n_layers)
    L = n_layers
    return {
        "ln1_scale": ((L, h), 1.0, std), "ln1_bias": ((L, h), 0.0, std),
        "qkv_kernel": ((L, h, 3 * h), 0.0, std),
        "qkv_bias": ((L, 3 * h), 0.0, std),
        "proj_kernel": ((L, h, h), 0.0, out_std),
        "proj_bias": ((L, h), 0.0, std),
        "ln2_scale": ((L, h), 1.0, std), "ln2_bias": ((L, h), 0.0, std),
        "fc1_kernel": ((L, h, ffn), 0.0, std),
        "fc1_bias": ((L, ffn), 0.0, std),
        "fc2_kernel": ((L, ffn, h), 0.0, out_std),
        "fc2_bias": ((L, h), 0.0, std),
    }
