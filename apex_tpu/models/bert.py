"""BERT model family: bidirectional encoder + MLM/NSP pretrain heads.

Reference: apex/transformer/testing/standalone_bert.py (``bert_model_
provider`` → TransformerLanguageModel with add_pooler=True, padding mask)
and the BASELINE.json config 4 workload ('BERT-large pretrain with
FusedLAMB + fused_dense + xentropy'). Reuses the shared decoder backbone
(transformer_lm.transformer_backbone) with ``attn_mask_type='padding'``;
adds token-type embeddings, the embedding LayerNorm, the Megatron-style
LM head (dense+gelu+LN, tied word-embedding decoder + bias) and the
binary NSP head (tanh pooler over [CLS]).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.amp.frontend import make_train_step
from apex_tpu.models.config import TransformerConfig, bert_large
from apex_tpu.models.transformer_lm import (
    apply_norm,
    init_gpt_params,
    transformer_backbone,
)
from apex_tpu.ops.layer_norm import fused_layer_norm
from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

__all__ = ["init_bert_params", "bert_forward", "bert_pretrain_loss",
           "make_bert_train_step", "bert_large"]


def init_bert_params(rng: jax.Array, cfg: TransformerConfig,
                     num_tokentypes: int = 2) -> dict:
    """GPT param layout + BERT extras (tokentype emb, embedding LN,
    MLM head, NSP pooler/classifier)."""
    params = init_gpt_params(rng, cfg)
    h = cfg.hidden_size
    std = cfg.init_method_std
    ks = jax.random.split(jax.random.fold_in(rng, 17), 6)

    def nrm(k, shape):
        return (jax.random.normal(k, shape) * std).astype(jnp.float32)

    params["embedding"]["tokentype"] = nrm(ks[0], (num_tokentypes, h))
    params["embedding_ln"] = {"scale": jnp.ones((h,)),
                              "bias": jnp.zeros((h,))}
    params["lm_head"] = {
        "dense_kernel": nrm(ks[1], (h, h)),
        "dense_bias": jnp.zeros((h,)),
        "ln_scale": jnp.ones((h,)),
        "ln_bias": jnp.zeros((h,)),
        "decoder_bias": jnp.zeros((cfg.vocab_size,)),
    }
    params["binary_head"] = {
        "pooler_kernel": nrm(ks[2], (h, h)),
        "pooler_bias": jnp.zeros((h,)),
        "cls_kernel": nrm(ks[3], (h, 2)),
        "cls_bias": jnp.zeros((2,)),
    }
    return params


def _padding_mask(attention_mask):
    """[b, s] validity (1 = real token) → [b, s] bool key-padding mask
    (True = masked); the backbone fuses it into the flash kernel rather
    than materializing a [b, n, sq, sk] score mask."""
    if attention_mask is None:
        return None
    return attention_mask == 0


def bert_forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
                 *, tokentype_ids=None, attention_mask=None,
                 dropout_rng=None):
    """→ (lm_logits [b,s,v], binary_logits [b,2])."""
    cd = cfg.compute_dtype
    emb = params["embedding"]
    with jax.named_scope("embed"):
        h = jnp.take(emb["word"].astype(cd), tokens, axis=0)
        h = h + emb["position"][: tokens.shape[1]].astype(cd)[None]
        if tokentype_ids is not None:
            h = h + jnp.take(emb["tokentype"].astype(cd), tokentype_ids,
                             axis=0)
    with jax.named_scope("embedding_ln"):
        h = fused_layer_norm(h, params["embedding_ln"]["scale"],
                             params["embedding_ln"]["bias"],
                             eps=cfg.layernorm_epsilon)

    kpm = _padding_mask(attention_mask)
    h = transformer_backbone(params, h, cfg, _ident_ctx(),
                             attention_mask=kpm,
                             dropout_rng=dropout_rng)

    # MLM head (Megatron lm_head: dense+gelu+LN then tied decoder)
    with jax.named_scope("mlm_head"):
        lm = params["lm_head"]
        g = jax.nn.gelu(h @ lm["dense_kernel"].astype(cd)
                        + lm["dense_bias"].astype(cd))
        g = apply_norm(cfg, g, lm["ln_scale"], lm["ln_bias"])
        lm_logits = jnp.einsum(
            "bsh,vh->bsv", g, emb["word"].astype(cd),
            preferred_element_type=jnp.float32) + lm["decoder_bias"]

    # NSP head on [CLS] (position 0)
    with jax.named_scope("nsp_head"):
        bh = params["binary_head"]
        pooled = jnp.tanh(h[:, 0].astype(jnp.float32)
                          @ bh["pooler_kernel"] + bh["pooler_bias"])
        binary_logits = pooled @ bh["cls_kernel"] + bh["cls_bias"]
    return lm_logits, binary_logits


def _ident_ctx():
    from apex_tpu.models.transformer_lm import single_device_ctx

    return single_device_ctx()


def bert_pretrain_loss(params, tokens, mlm_labels, nsp_labels, cfg,
                       *, tokentype_ids=None, attention_mask=None,
                       dropout_rng=None):
    """MLM CE over positions with label >= 0 (others ignored, the -1
    convention) + NSP CE — reference standalone_bert loss composition."""
    lm_logits, bin_logits = bert_forward(
        params, tokens, cfg, tokentype_ids=tokentype_ids,
        attention_mask=attention_mask, dropout_rng=dropout_rng)
    with jax.named_scope("mlm_head"):
        v = lm_logits.shape[-1]
        flat_logits = lm_logits.reshape(-1, v)
        flat_labels = mlm_labels.reshape(-1)
        valid = flat_labels >= 0
        per_tok = softmax_cross_entropy_loss(
            flat_logits, jnp.clip(flat_labels, 0, v - 1), padding_idx=None)
        denom = jnp.maximum(jnp.sum(valid), 1)
        mlm_loss = jnp.sum(jnp.where(valid, per_tok, 0.0)) / denom

    with jax.named_scope("nsp_head"):
        nsp_lp = jax.nn.log_softmax(bin_logits, axis=-1)
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(nsp_lp, nsp_labels[:, None], axis=1))
    return mlm_loss + nsp_loss


def make_bert_train_step(
    cfg: TransformerConfig,
    optimizer: Any,
    policy_or_amp="O2",
    mesh: Optional[Mesh] = None,
    *,
    grad_postprocess: Optional[Callable] = None,
):
    """(init_fn, step_fn); step(state, tokens, mlm_labels, nsp_labels,
    tokentype_ids, attention_mask[, rng]). The BASELINE config pairs this
    with optimizers.fused_lamb."""
    has_dropout = (cfg.hidden_dropout > 0 or cfg.attention_dropout > 0
                   or cfg.drop_path_rate > 0)

    def loss_fn(params, tokens, mlm_labels, nsp_labels, tokentype_ids,
                attention_mask, *rest):
        rng = rest[0] if has_dropout else None
        return bert_pretrain_loss(
            params, tokens, mlm_labels, nsp_labels, cfg,
            tokentype_ids=tokentype_ids, attention_mask=attention_mask,
            dropout_rng=rng)

    init_fn, step_fn = make_train_step(
        loss_fn, optimizer, policy_or_amp,
        grad_postprocess=grad_postprocess)

    def init(rng):
        return init_fn(init_bert_params(rng, cfg))

    if mesh is None:
        return init, jax.jit(step_fn, donate_argnums=0)

    bs = NamedSharding(mesh, P("dp"))
    shardings = (None, bs, bs, bs, bs, bs)
    if has_dropout:
        shardings += (NamedSharding(mesh, P()),)
    jstep = jax.jit(step_fn, in_shardings=shardings, donate_argnums=0)

    def step(state, *batch):
        with jax.set_mesh(mesh):
            return jstep(state, *batch)

    return init, step
