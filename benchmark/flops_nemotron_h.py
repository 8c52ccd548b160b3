"""Operations a token requires in the forward and backward passes of a
Nemotron-H configuration (the ``nemotron_h`` keys: one mixer a layer by
``hybrid_override_pattern``), as one chip's share of an expert-parallel
deployment; the operations and bytes of the state-space scan and of the
non-gated grouped expert products.  Kept with the benchmark, beside
``flops.py`` and ``flops_moe.py`` (which know one kind of layer, or ``conv``
and ``full_attention`` layers with gated experts).

``train_flops_per_token`` is ``6 N + 12 L_attn (n d) s + 3 L_mamba scan``:

- ``N`` counts every weight a token is multiplied by, layer by layer: ``M``
  the in-projection ``h x (2 d_in + 2 G N + H)`` and the out-projection
  ``d_in x h`` (the K taps are elementwise, not counted); ``E`` the router
  ``h x E``, the HELD experts by their expected load, ``k x held / E``
  experts a token at ``2 h f`` each (what the absent experts do is on other
  chips), and the shared expert whole, ``2 h f_s``; ``*`` ``h x (n + 2 g) d``
  and ``n d x h``; the head: the held vocabulary's ``V x h``;
- the attention term is the full ``s x s`` square of the attention layers;
- ``scan`` is the forward pass of the state-space scan's four products in
  the chunked form at ``chunk_size`` (``scan_flops_per_token``), counted
  once forward and twice backward like any product.

Recomputed operations (remat) are not counted.
"""

from __future__ import annotations

from benchmark import flops_moe

BYTES = 2          # bfloat16 operands and results


_held = flops_moe._held      # (experts held, experts published)


def _mamba(config: dict) -> tuple:
    """``(H, P, G, N)``."""
    return (config["mamba_num_heads"], config["mamba_head_dim"],
            config["n_groups"], config["ssm_state_size"])


def layers_of(config: dict, kind: str) -> int:
    return config["hybrid_override_pattern"].count(kind)


def layer_weights(config: dict, kind: str) -> float:
    h = config["hidden_size"]
    if kind == "M":
        heads, p, g, n = _mamba(config)
        d_in = heads * p
        return h * (2 * d_in + 2 * g * n + heads) + d_in * h
    if kind == "E":
        held, experts = _held(config)
        per_token = config["num_experts_per_tok"] * held / experts
        return (h * experts
                + per_token * 2 * h * config["moe_intermediate_size"]
                + 2 * h * config["moe_shared_expert_intermediate_size"])
    n, g, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    return h * (n + 2 * g) * d + n * d * h


def matmul_weights(config: dict) -> float:
    layers = sum(layer_weights(config, kind)
                 for kind in config["hybrid_override_pattern"])
    return layers + config["vocab_size"] * config["hidden_size"]


def scan_flops_per_token(config: dict) -> float:
    """One forward pass of one layer's scan, a token: ``C B^T`` once a
    group (``2 Q N G``), the score matrix times ``x`` (``2 Q P H``), the
    chunk's state (``2 P N H``) and what the carried state gives
    (``2 N P H``)."""
    heads, p, g, n = _mamba(config)
    q = config["chunk_size"]
    return 2.0 * (q * n * g + q * p * heads + 2 * p * n * heads)


def scan_bytes_per_token(config: dict) -> float:
    """The least one pass of one layer's scan moves, a token: it reads
    ``x`` [H P], ``B`` and ``C`` [G N] (bfloat16) and ``dt`` [H]
    (float32) and writes ``y`` [H P], each once."""
    heads, p, g, n = _mamba(config)
    return BYTES * (2 * heads * p + 2 * g * n) + 4 * heads


def scan_step_work(config: dict, tokens: int, forward_runs: int) -> tuple:
    """``(flops, bytes)`` of the scans of one train step over ``tokens``
    tokens: the forward runs ``forward_runs`` times (2 with remat); the
    backward is twice the forward's products, and moves its bytes twice
    (it reads what the forward read and ``dy``, and writes a gradient for
    each of ``x``, ``B``, ``C``, ``dt``)."""
    passes = forward_runs + 2
    layers = layers_of(config, "M")
    return (layers * passes * tokens * scan_flops_per_token(config),
            layers * passes * tokens * scan_bytes_per_token(config))


def train_flops_per_token(config: dict, seq: int) -> float:
    attention = (12.0 * layers_of(config, "*") * seq
                 * config["num_attention_heads"] * config["head_dim"])
    scan = 3.0 * layers_of(config, "M") * scan_flops_per_token(config)
    return 6.0 * matmul_weights(config) + attention + scan


def grouped_products(config: dict) -> list:
    """``(k, p)`` of an expert layer's two grouped products (not gated:
    ``fc1`` is ``h x f``)."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    return [(h, f), (f, h)]


def grouped_step_work(config: dict, rows_a_layer: float,
                      forward_runs: int) -> tuple:
    """``(flops, bytes)`` of all grouped-product kernels of one train step,
    as ``flops_moe.grouped_step_work`` counts them: ``rows_a_layer`` rows
    on held experts in each expert layer, the forward products
    ``forward_runs`` times, each gradient once."""
    groups = _held(config)[0]
    runs = forward_runs + 2
    flops = sum(runs * flops_moe.gmm_flops(rows_a_layer, k, p)
                for k, p in grouped_products(config))
    bytes_ = sum(runs * flops_moe.gmm_bytes(rows_a_layer, groups, k, p)
                 for k, p in grouped_products(config))
    n = layers_of(config, "E")
    return n * flops, n * bytes_
