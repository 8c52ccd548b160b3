"""Operations a token requires in the forward and backward passes of a
DeepSeek-V3-style configuration (the ``joyai_llm_flash`` keys: multi-head
latent attention in every block, dense SwiGLU then gated experts beside a
gated shared expert, one multi-token-prediction module), as one chip's
share of an expert-parallel deployment; the least operations and bytes of
the step's attention, and the operations and bytes of the gated grouped
expert products.  Kept with the benchmark, beside ``flops.py``,
``flops_moe.py`` and ``flops_nemotron_h.py``.

``train_flops_per_token`` is ``6 N + 6 s n (d_qk + d_v) B``:

- ``N`` counts every weight a token is multiplied by.  A block's latent
  attention: ``h x r_q``, ``r_q x n (d_nope + d_rope)``, ``h x (r_kv +
  d_rope)``, ``r_kv x n (d_nope + d_v)`` and ``n d_v x h``; a dense block's
  FFN ``3 h f_dense``; an expert block's router ``h x E``, the HELD experts
  by their expected load, ``k x held / E`` experts a token at ``3 h f`` each
  (what the absent experts do is on other chips), and the shared experts
  whole, ``3 h f``; the MTP module's ``2h x h`` and its expert block; the
  head TWICE (the main loss and the module's): ``2 V h``;
- the attention term is the full ``s x s`` square of all ``B`` blocks (the
  stack's and the module's): ``q k^T`` over ``d_qk = d_nope + d_rope`` and
  ``P v`` over ``d_v``, forward and twice that backward.

Recomputed operations (remat) are not counted.
"""

from __future__ import annotations

from benchmark import flops_moe

BYTES = 2          # bfloat16 operands and results

_held = flops_moe._held      # (experts held, experts published)


def _widths(config: dict) -> tuple:
    """``(n, d_nope, d_rope, d_v)``."""
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"])


def blocks(config: dict) -> int:
    """Blocks a token passes: the stack's and the MTP modules'."""
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def expert_layers(config: dict) -> int:
    return blocks(config) - config["first_k_dense_replace"]


def mla_weights(config: dict) -> float:
    h = config["hidden_size"]
    n, dn, dr, dv = _widths(config)
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    return (h * rq + rq * n * (dn + dr) + h * (rkv + dr)
            + rkv * n * (dn + dv) + n * dv * h)


def expert_block_weights(config: dict) -> float:
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held, experts = _held(config)
    per_token = config["num_experts_per_tok"] * held / experts
    return (h * experts + per_token * 3 * h * f
            + config["n_shared_experts"] * 3 * h * f)


def matmul_weights(config: dict) -> float:
    h = config["hidden_size"]
    dense = config["first_k_dense_replace"]
    return (blocks(config) * mla_weights(config)
            + dense * 3 * h * config["intermediate_size"]
            + expert_layers(config) * expert_block_weights(config)
            + config["num_nextn_predict_layers"] * 2 * h * h
            + (1 + config["num_nextn_predict_layers"])
            * config["vocab_size"] * h)


def train_flops_per_token(config: dict, seq: int) -> float:
    n, dn, dr, dv = _widths(config)
    return (6.0 * matmul_weights(config)
            + 6.0 * blocks(config) * seq * n * (dn + dr + dv))


def flash_step_work(config: dict, batch: int, seq: int,
                    forward_runs: int = 1) -> tuple:
    """``(flops, bytes)``: the LEAST work of one train step's attention,
    whatever implements it.  Operations over the causal triangle alone,
    ``s (s + 1) / 2`` pairs a head a block: ``2 (d_qk + d_v)`` a pair
    forward, as often as the program runs the forward (once: a rematted
    layer keeps the output and the logsumexp), and backward the scores
    again and the four gradient products, ``2 (3 d_qk + 2 d_v)``.  Bytes:
    q, the keys' two parts (the rotary key ONCE a position, not a head),
    v, o and do read once; o, dq, the two parts of dk and dv written
    once."""
    n, dn, dr, dv = _widths(config)
    pairs = batch * seq * (seq + 1) / 2 * n * blocks(config)
    flops = pairs * (forward_runs * 2 * (dn + dr + dv)
                     + 2 * (3 * (dn + dr) + 2 * dv))
    q, k, v = n * (dn + dr), n * dn + dr, n * dv
    a_token = (q + k + v + 2 * v) + (v + q + k + v)
    return flops, BYTES * a_token * batch * seq * blocks(config)


def grouped_step_work(config: dict, rows_a_layer: float,
                      forward_runs: int) -> tuple:
    """``(flops, bytes)`` of all grouped-product kernels of one train step,
    ``flops_moe.grouped_step_work``'s own count (gated: ``fc1`` is ``h x
    2f``; ``rows_a_layer`` rows on held experts in each expert block, the
    forward products ``forward_runs`` times, each gradient once) over this
    family's expert blocks, the MTP module's among them."""
    return flops_moe.grouped_step_work(
        dict(config, num_hidden_layers=blocks(config),
             num_dense_layers=config["first_k_dense_replace"]),
        rows_a_layer, forward_runs)
