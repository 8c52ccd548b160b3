"""What decides a served cell's ``correct``: the plain float32 GPT-2 forward
(``transformer.py``, ``HIGHEST`` products, no cache, no kernel) over each
sampled request's prompt and the tokens the engine served for it, teacher
forced.  At every served position it reads the largest logit, the served
token's logit and the log of the softmax's denominator, from which the
entry takes two numbers: the gap by which the served token lies below the
reference's best (a greedy server reads about 0; a gap is a near tie that
rounding tipped, or a fault), and the served token's log-probability,
which the entry sets against the program's own.  Imports nothing from
apex_tpu.

The model served is the bfloat16 one: ``as_served`` rounds the reference's
own float32 weights (from its own generator and the seed) to bfloat16 and
back, so the reference computes exactly that model in float32, and what
separates the program from it is the program's arithmetic alone.

The control (``control_numbers``) is the same forward in a lower precision
in the program's place: every product's operands rounded to int8 (the 255
levels of a symmetric int8) or float8 (e4m3, up to 240), one scale for
each row of an operand along the axes the product contracts (a token's
activations, a weight's column), accumulated in float32.  It need not decode: at each served position it reads the gap
of the token it puts first, and its own log-probability of the served
token.

Every row is padded to one length so that one compiled forward serves every
request: the model is causal, so what follows a row's last real token
changes none of the logits read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as T


def as_served(params):
    """The reference's weights as the cell serves them: bfloat16 values,
    held in float32."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)


class RowScaled:
    """A control's arithmetic for ``transformer.py``'s products: operands
    rounded to ``fmt`` (``int8``, or ``float8``: e4m3 up to 240) under one
    scale for each row of an operand along the contracted axes."""

    def __init__(self, fmt: str):
        self.fmt = fmt

    def einsum(self, spec: str, a, b):
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        contracted = (set(sa) & set(sb)) - set(out) - {"."}
        return jnp.einsum(spec, _rounded(a, sa, contracted, self.fmt),
                          _rounded(b, sb, contracted, self.fmt),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def mm(self, a, b):
        return self.einsum("...k,kn->...n", a, b)


def _rounded(x, spec: str, contracted: set, fmt: str):
    """``x`` on ``fmt``'s grid, one scale for each of its rows along the
    ``contracted`` axes of its einsum ``spec`` (an ellipsis leads)."""
    letters = spec.replace("...", "")
    shift = len(letters) if "..." in spec else 0
    axes = tuple(i - shift for i, c in enumerate(letters) if c in contracted)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30)
    if fmt == "int8":
        return jnp.round(x * (127.0 / amax)) * (amax / 127.0)
    return jax.lax.reduce_precision(x * (240.0 / amax), 4, 3) * (
        amax / 240.0)


def _logits(params, tokens, cfg: dict, prec):
    """``[s, vocab]`` float32 logits of one row ``tokens`` ``[s]``."""
    emb = params["embedding"]
    x = emb["word"][tokens] + emb["position"][:tokens.shape[0]]
    x = T.stack(x[None], params["layers"], n_heads=cfg["n_head"],
                causal=True, pre_ln=True, eps=cfg["layer_norm_epsilon"],
                act=cfg["activation_function"], prec=prec)[0]
    fl = params["final_ln"]
    x = T.layer_norm(x, fl["scale"], fl["bias"], cfg["layer_norm_epsilon"])
    return prec.einsum("sh,vh->sv", x, emb["word"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _read_row(params, tokens, targets, cfg_items, fmt=None):
    """At every position of one row: the largest logit, the logit of
    ``targets`` there, the log of the softmax's denominator and the token
    with the largest logit."""
    prec = RowScaled(fmt) if fmt else T.Precision("float32")
    with jax.default_matmul_precision("highest"):
        logits = _logits(params, tokens, dict(cfg_items), prec)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return (jnp.max(logits, axis=-1), picked,
            jax.nn.logsumexp(logits, axis=-1), jnp.argmax(logits, axis=-1))


def _row(prompt, served, length: int):
    """The forward's input (prompt and every served token but the last,
    padded to ``length``), the served tokens as targets at the positions
    that predict them, and those positions."""
    p, m = len(prompt), len(served)
    tokens = np.zeros((length,), np.int32)
    tokens[:p] = prompt
    tokens[p:p + m - 1] = served[:-1]
    targets = np.zeros((length,), np.int32)
    at = np.arange(p - 1, p + m - 1)
    targets[at] = served
    return tokens, targets, at


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def served_reads(params, cfg: dict, rows, length: int) -> list:
    """``rows`` = ``[(prompt, served tokens)]``: for each row, at its
    served positions, ``(best, picked, lse)`` in float64."""
    out = []
    for prompt, served in rows:
        tokens, targets, at = _row(prompt, served, length)
        read = jax.device_get(_read_row(
            params, jnp.asarray(tokens), jnp.asarray(targets), _items(cfg)))
        out.append(tuple(np.asarray(a[at], np.float64) for a in read[:3]))
    return out


def control_numbers(params, cfg: dict, rows, length: int,
                    fmt: str) -> dict:
    """The control in ``fmt`` on ``rows``, against the float32 reference,
    in the numbers the entry's check compares."""
    margin, gap = [], []
    for prompt, served in rows:
        tokens, targets, at = _row(prompt, served, length)
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        _, picked8, lse8, first = _read_row(params, tokens, targets,
                                            _items(cfg), fmt=fmt)
        best, picked, lse, _ = _read_row(params, tokens, targets,
                                         _items(cfg))
        _, first32, _, _ = _read_row(params, tokens,
                                     first.astype(jnp.int32), _items(cfg))
        best, picked, lse, first32, picked8, lse8 = (
            np.asarray(a, np.float64)[at] for a in jax.device_get(
                (best, picked, lse, first32, picked8, lse8)))
        margin.append(best - first32)
        gap.append(np.abs((picked8 - lse8) - (picked - lse)))
    margin, gap = np.concatenate(margin), np.concatenate(gap)
    return {"argmax_margin_max": float(margin.max()),
            "logprob_gap_max": float(gap.max()),
            "logprob_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "tokens_compared": float(gap.size)}
