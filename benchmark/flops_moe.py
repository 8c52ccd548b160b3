"""Operations a token requires in the forward and backward passes of a
heterogeneous mixture-of-experts configuration (the ``lfm2_moe`` keys), as
one chip's share of an expert-parallel deployment, and the operations and
bytes of the grouped expert products.  Kept with the benchmark, beside
``flops.py`` (whose ``L x (4 h^2 + 2 h ffn)`` knows one kind of layer only
and would read 2.3 times too high here).

``train_flops_per_token`` is the same ``6 N + 12 L_attn (n d) s``: ``N``
counts every weight a token is multiplied by, layer by layer

- ``conv``: in-projection ``h x 3h`` and out-projection ``h x h`` (the K
  taps are elementwise, not counted);
- ``full_attention``: ``h x (n + 2 g) d`` and ``n d x h``;
- dense FFN: ``3 h f_dense``; expert layer: the router ``h x E`` and the
  HELD experts by their expected load, ``k x held / E`` experts a token at
  ``3 h f`` each (the share of the work that lands on this chip when the
  router is balanced; what the absent experts do is on other chips);
- the head: the held vocabulary's ``V x h``.

The attention term is the full ``s x s`` square of the attention layers
only.  Recomputed operations (remat) are not counted.
"""

from __future__ import annotations

BYTES = 2          # bfloat16 operands and results


def _held(config: dict) -> tuple:
    dep = config["deployment"]
    return dep["experts_held"][1], dep["num_experts_published"]


def layer_weights(config: dict, layer: int) -> float:
    h = config["hidden_size"]
    n, g = config["num_attention_heads"], config["num_key_value_heads"]
    d = h // n
    if config["layer_types"][layer] == "conv":
        op = h * 3 * h + h * h
    else:
        op = h * (n + 2 * g) * d + n * d * h
    if layer < config["num_dense_layers"]:
        return op + 3 * h * config["intermediate_size"]
    held, experts = _held(config)
    per_token = config["num_experts_per_tok"] * held / experts
    return (op + h * experts
            + per_token * 3 * h * config["moe_intermediate_size"])


def matmul_weights(config: dict) -> float:
    layers = sum(layer_weights(config, i)
                 for i in range(config["num_hidden_layers"]))
    return layers + config["vocab_size"] * config["hidden_size"]


def train_flops_per_token(config: dict, seq: int) -> float:
    attn = sum(k == "full_attention" for k in config["layer_types"])
    return (6.0 * matmul_weights(config)
            + 12.0 * attn * config["hidden_size"] * seq)


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def grouped_products(config: dict) -> list:
    """``(k, p)`` of an expert layer's two grouped products."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    return [(h, 2 * f), (f, h)]


def gmm_flops(rows: float, k: int, p: int) -> float:
    """One grouped product over ``rows`` rows: forward, input gradient and
    weight gradient are each ``2 rows k p``."""
    return 2.0 * rows * k * p


def gmm_bytes(rows: float, groups: int, k: int, p: int) -> float:
    """The least a grouped product moves, forward, input gradient or weight
    gradient alike: each reads two of x [rows, k], the held slabs [G, k, p]
    and the [rows, p] rows, and writes the third."""
    return BYTES * (rows * (k + p) + groups * k * p)


def grouped_step_work(config: dict, rows_a_layer: float,
                      forward_runs: int) -> tuple:
    """``(flops, bytes)`` of all grouped-product kernels of one train step:
    ``rows_a_layer`` rows on held experts in each expert layer, the forward
    products run ``forward_runs`` times (2 with remat), each gradient
    once."""
    groups = _held(config)[0]
    runs = forward_runs + 2
    flops = sum(runs * gmm_flops(rows_a_layer, k, p)
                for k, p in grouped_products(config))
    bytes_ = sum(runs * gmm_bytes(rows_a_layer, groups, k, p)
                 for k, p in grouped_products(config))
    n = expert_layers(config)
    return n * flops, n * bytes_
