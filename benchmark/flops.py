"""Operations a configuration's forward and backward passes require per
token, from its shapes alone.  Kept with the benchmark so that no PR that
claims a gain can change the count.

``train_flops_per_token`` is the usual ``6 N + 12 L h s``: six operations
per token for every weight a token is multiplied by (forward, and twice
that backward) plus the two attention products.  ``N`` counts the weight
matrices of the layers and the output head (the tied word embedding, a real
[h, V] product), not biases, LayerNorm or position tables.  The attention
term is the full ``s x s`` square, for a causal model too (the PaLM
convention): a kernel that skips the masked half reads the better for it.
The head is counted on ``head_token_share`` of the tokens only: a masked-LM
loss needs logits at the labelled positions alone.  Recomputed operations
(``remat``) are work the program chose, and are not counted.

Shape->FLOP/byte functions of single kernels, for the roofline metrics a
later PR adds (PERF.md, Open questions), belong in this file too.
"""

from __future__ import annotations


def shapes(config: dict) -> dict:
    """The sizes this file needs, under either family's key names."""
    def first(*names, default=None):
        for name in names:
            if config.get(name) is not None:
                return config[name]
        if default is None:
            raise KeyError(f"configuration has none of {names}")
        return default

    h = first("n_embd", "hidden_size")
    return {
        "layers": first("n_layer", "num_hidden_layers"),
        "hidden": h,
        "ffn": first("n_inner", "intermediate_size", default=4 * h),
        "vocab": config["vocab_size"],
        # weights of products in the head before the decoder (a masked-LM
        # head's h x h dense): the configuration says, this file never
        # guesses a family
        "head_dense": config.get("head_dense_weights", 0),
    }


def matmul_weights(config: dict, head_token_share: float = 1.0) -> float:
    s = shapes(config)
    per_layer = 4 * s["hidden"] ** 2 + 2 * s["hidden"] * s["ffn"]
    head = s["vocab"] * s["hidden"] + s["head_dense"]
    return s["layers"] * per_layer + head_token_share * head


def train_flops_per_token(config: dict, seq: int,
                          head_token_share: float = 1.0) -> float:
    s = shapes(config)
    return (6.0 * matmul_weights(config, head_token_share)
            + 12.0 * s["layers"] * s["hidden"] * seq)
