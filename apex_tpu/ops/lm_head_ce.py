"""Fused LM-head matmul + softmax cross-entropy, chunked over tokens.

The reference computes ``parallel_lm_logits`` then cross-entropy as two
stages (standalone_transformer_lm.py:1130, :1547), materializing the
full [tokens, vocab] logits.  At GPT-2 bench shape that tensor is
b16·s1024·v50304 fp32 = 3.2 GB — written by the head matmul, read by the
loss, read again by its backward.  On a v5e (819 GB/s) that round
tripping alone costs ~12 ms/step, and the buffer dominates peak memory.

This op fuses the two and *chunks over tokens*: the forward computes
each chunk's logits on the fly, reduces them to the per-token
``(lse, picked, mean)`` scalars the loss needs, and throws the chunk
away; the backward recomputes each chunk's logits from the saved lse
(one extra chunk matmul) and immediately contracts them into ``dhidden``
and the ``dhead`` accumulator.  Peak extra memory is O(chunk · vocab)
instead of O(tokens · vocab); the full logits never touch HBM.

Same per-row semantics as :mod:`apex_tpu.ops.xentropy`
(xentropy_kernel.cu:431-452), with the head matmul folded in.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops._pallas_utils import param_cotangent
from apex_tpu.utils.collectives import match_vma

__all__ = ["lm_head_cross_entropy"]


def _chunks(n: int, chunk: int) -> int:
    return (n + chunk - 1) // chunk


def _pad_rows(x, n_pad):
    if n_pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((n_pad,) + x.shape[1:], x.dtype)], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ce(hidden, head, labels, smoothing, chunk):
    losses, _ = _fwd_math(hidden, head, labels, smoothing, chunk)
    return losses


def _fwd_math(hidden, head, labels, smoothing, chunk):
    """Per-token losses [N] plus the lse residual [N]."""
    n, h = hidden.shape
    v = head.shape[0]
    nc = _chunks(n, chunk)
    n_pad = nc * chunk - n
    hid = _pad_rows(hidden, n_pad).reshape(nc, chunk, h)
    lab = _pad_rows(labels.astype(jnp.int32), n_pad).reshape(nc, chunk)

    def one(carry, inp):
        hc, lc = inp
        logits = jnp.einsum(
            "ch,vh->cv", hc, head.astype(hc.dtype),
            preferred_element_type=jnp.float32)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        loss = (lse - picked) * (1.0 - smoothing)
        if smoothing:
            loss = loss + (lse - jnp.mean(logits, axis=-1)) * smoothing
        return carry, (loss, lse)

    _, (losses, lses) = jax.lax.scan(one, (), (hid, lab))
    return losses.reshape(-1)[:n], lses.reshape(-1)[:n]


def _fused_ce_fwd(hidden, head, labels, smoothing, chunk):
    losses, lses = _fwd_math(hidden, head, labels, smoothing, chunk)
    return losses, (hidden, head, labels, lses)


def _fused_ce_bwd(smoothing, chunk, res, g):
    hidden, head, labels, lses = res
    n, h = hidden.shape
    v = head.shape[0]
    nc = _chunks(n, chunk)
    n_pad = nc * chunk - n
    hid = _pad_rows(hidden, n_pad).reshape(nc, chunk, h)
    lab = _pad_rows(labels.astype(jnp.int32), n_pad).reshape(nc, chunk)
    lse = _pad_rows(lses, n_pad).reshape(nc, chunk)
    # padded rows must contribute nothing to dhead
    gv = _pad_rows(g.astype(jnp.float32), n_pad).reshape(nc, chunk)

    head_f = head.astype(hidden.dtype)

    def one(dhead_acc, inp):
        hc, lc, lsec, gc = inp
        logits = jnp.einsum(
            "ch,vh->cv", hc, head_f,
            preferred_element_type=jnp.float32)
        probs = jnp.exp(logits - lsec[:, None])
        onehot = jax.nn.one_hot(lc, v, dtype=jnp.float32)
        dlogits = probs - smoothing / v - (1.0 - smoothing) * onehot
        dlogits = (dlogits * gc[:, None]).astype(hc.dtype)
        dh = jnp.einsum("cv,vh->ch", dlogits, head_f,
                        preferred_element_type=jnp.float32)
        dhead_acc = dhead_acc + jnp.einsum(
            "cv,ch->vh", dlogits, hc, preferred_element_type=jnp.float32)
        return dhead_acc, dh

    # inside shard_map the accumulator varies like what lands in it
    # (the tokens' shards); the replicated head's cotangent is then
    # summed over those axes once, after the scan
    acc0 = match_vma(jnp.zeros((v, h), jnp.float32),
                     sorted(jax.typeof(hid).vma | jax.typeof(gv).vma))
    dhead, dhs = jax.lax.scan(one, acc0, (hid, lab, lse, gv))
    dhidden = dhs.reshape(nc * chunk, h)[:n].astype(hidden.dtype)
    return (dhidden, param_cotangent(dhead.astype(head.dtype), head),
            None)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def lm_head_cross_entropy(
    hidden: jax.Array,
    head: jax.Array,
    labels: jax.Array,
    *,
    smoothing: float = 0.0,
    chunk: int = 2048,
    ignore_index: Optional[int] = None,
) -> jax.Array:
    """Per-token CE of ``softmax(hidden @ head.T)`` without materializing
    the [tokens, vocab] logits (see module docstring).

    ``hidden`` [N, h] (or [..., h] — leading dims flattened), ``head``
    [v, h], ``labels`` int [N].  Rows whose label equals ``ignore_index``
    get loss 0 (and zero gradients), matching the fused xentropy op's
    ``padding_idx`` semantics.
    """
    lead = hidden.shape[:-1]
    hidden2 = hidden.reshape(-1, hidden.shape[-1])
    labels2 = labels.reshape(-1)
    if ignore_index is not None:
        valid = labels2 != ignore_index
        labels2 = jnp.where(valid, labels2, 0)
    losses = _fused_ce(hidden2, head, labels2, float(smoothing),
                       int(chunk))
    if ignore_index is not None:
        losses = jnp.where(valid, losses, 0.0)
    return losses.reshape(lead)
