"""Plain float32 BERT encoder with its pre-training loss (masked-LM plus
next-sentence), imports nothing from apex_tpu.

Two departures from the published model, both stated in the configuration
file under ``assumed`` because the program under test has no other form:
``layer_norm_placement`` ``pre`` (Megatron-LM's BERT: LayerNorm before each
sub-block and one more after the last layer) and ``mlm_head_act``
``gelu_tanh``.  With ``post``/``gelu`` this file computes the published
BERT."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import transformer as T

INIT_STD = 0.02


def param_spec(cfg: dict) -> dict:
    h, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    v = cfg["vocab_size"]
    ln = lambda: {"scale": ((h,), 1.0, INIT_STD),
                  "bias": ((h,), 0.0, INIT_STD)}
    spec = {
        "embedding": {
            "word": ((v, h), 0.0, INIT_STD),
            "position": ((cfg["max_position_embeddings"], h), 0.0, INIT_STD),
            "tokentype": ((cfg["type_vocab_size"], h), 0.0, INIT_STD),
        },
        "embedding_ln": ln(),
        "layers": T.layer_spec(L, h, cfg["intermediate_size"], INIT_STD),
        "lm_head": {
            "dense_kernel": ((h, h), 0.0, INIT_STD),
            "dense_bias": ((h,), 0.0, INIT_STD),
            "ln_scale": ((h,), 1.0, INIT_STD),
            "ln_bias": ((h,), 0.0, INIT_STD),
            "decoder_bias": ((v,), 0.0, INIT_STD),
        },
        "binary_head": {
            "pooler_kernel": ((h, h), 0.0, INIT_STD),
            "pooler_bias": ((h,), 0.0, INIT_STD),
            "cls_kernel": ((h, 2), 0.0, INIT_STD),
            "cls_bias": ((2,), 0.0, INIT_STD),
        },
    }
    if cfg["assumed"]["layer_norm_placement"] == "pre":
        spec["final_ln"] = ln()
    return spec


def init_params(key, cfg: dict) -> dict:
    return T.normal_tree(key, param_spec(cfg))


def loss(params, batch, cfg: dict, prec: T.Precision):
    """``batch`` = (tokens, mlm_labels, nsp_labels, tokentype_ids,
    attention_mask).  The traffic has no padding, so the mask (all ones)
    changes nothing and is not read."""
    tokens, mlm_labels, nsp_labels, tokentype_ids, _ = batch
    eps = cfg["layer_norm_eps"]
    pre_ln = cfg["assumed"]["layer_norm_placement"] == "pre"
    emb = params["embedding"]
    s = tokens.shape[1]
    x = (emb["word"][tokens] + emb["position"][:s][None]
         + emb["tokentype"][tokentype_ids])
    x = T.layer_norm(x, params["embedding_ln"]["scale"],
                     params["embedding_ln"]["bias"], eps)
    x = T.stack(x, params["layers"], n_heads=cfg["num_attention_heads"],
                causal=False, pre_ln=pre_ln, eps=eps, act=cfg["hidden_act"],
                prec=prec)
    if pre_ln:
        x = T.layer_norm(x, params["final_ln"]["scale"],
                         params["final_ln"]["bias"], eps)

    lm = params["lm_head"]
    g = T.gelu(prec.mm(x, lm["dense_kernel"]) + lm["dense_bias"],
               cfg["assumed"]["mlm_head_act"])
    g = T.layer_norm(g, lm["ln_scale"], lm["ln_bias"], eps)
    total, count = T.blocked_cross_entropy(
        g.reshape(-1, g.shape[-1]), emb["word"], lm["decoder_bias"],
        mlm_labels.reshape(-1), prec)

    bh = params["binary_head"]
    pooled = jnp.tanh(prec.mm(x[:, 0], bh["pooler_kernel"])
                      + bh["pooler_bias"])
    nsp_logp = jax.nn.log_softmax(
        prec.mm(pooled, bh["cls_kernel"]) + bh["cls_bias"], axis=-1)
    nsp = -jnp.mean(jnp.take_along_axis(
        nsp_logp, nsp_labels[:, None], axis=1))
    return total / count + nsp
