"""Operations and least bytes of a GPT-2 decode step, from the
configuration's own keys (``n_layer``, ``n_embd``, ``n_head``,
``vocab_size``) and the step's live lanes; kept with the benchmark so that
every PR counts them the same way.

A decode step gives each live lane one token: that token's K/V is written
at its position and it attends over the ``ctx`` = position + 1 tokens its
lane holds.  Only live lanes count; the engine's free lanes ride along
masked and do no useful work.  A prefill takes a prompt's tokens through
every layer at once, each attending causally over those before it, and
reads the head at the last of them alone.
"""

from __future__ import annotations

BF16 = 2


def _dims(config: dict) -> tuple:
    h = config["n_embd"]
    ffn = config.get("n_inner") or 4 * h
    return config["n_layer"], h, ffn, config["vocab_size"]


def decode_flops(config: dict, lanes: int, ctx_sum: int) -> float:
    """Useful operations of one decode step: each live lane's token
    through every layer's four products (QKV, output projection, MLP in
    and out) and the tied head, plus attention's two products over each
    lane's context (``ctx_sum`` = the sum over live lanes)."""
    layers, h, ffn, vocab = _dims(config)
    matmul_params = layers * (3 * h * h + h * h + 2 * h * ffn) + vocab * h
    return 2.0 * lanes * matmul_params + layers * 4.0 * h * ctx_sum


def prefill_flops(config: dict, prompt: int) -> float:
    """Useful operations of one prompt's prefill: its ``prompt`` tokens
    through every layer's four products, attention's two products over the
    causal triangle, and the head for the last token (the one the first
    output token is sampled from); the bucket's padding does none."""
    layers, h, ffn, vocab = _dims(config)
    layer_params = 3 * h * h + h * h + 2 * h * ffn
    return (2.0 * prompt * layers * layer_params
            + layers * 2.0 * h * prompt * (prompt + 1)
            + 2.0 * vocab * h)


def decode_kernel_bytes(config: dict, lanes: int, ctx_sum: int,
                        with_projection: bool) -> float:
    """Least bytes one decode step's attention kernels move, all layers:
    every live lane's K and V over its context, its query in and its
    context out, in bfloat16; ``with_projection``: the kernel also
    applies the output projection (the fused decode layer), and reads
    its weight once a layer."""
    layers, h, _, _ = _dims(config)
    kv = 2 * h * BF16 * ctx_sum
    q_and_out = 2 * h * BF16 * lanes
    weight = h * h * BF16 if with_projection else 0
    return float(layers * (kv + q_and_out + weight))
