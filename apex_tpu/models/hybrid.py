"""Stacks whose layers differ in kind: the LFM2 family's gated short
convolutions between grouped-query attention layers, dense SwiGLU layers
before sigmoid-routed expert layers.

``TransformerConfig.is_hybrid`` (``layer_types`` or ``num_dense_layers``)
sends ``init_gpt_params`` and ``transformer_backbone`` here.  The parameters
are a list of per-layer trees, each holding the leaves of its own kind, and
the stack is unrolled: each layer is its own ``jax.checkpoint`` under the
homogeneous stack's remat policy (flash attention's output and logsumexp are
kept).  Attention, the dense FFN and the expert layer are the homogeneous
stack's own (``transformer_lm._attention`` / ``_mlp`` / ``_moe_mlp``); what
is new is the short-convolution operator and the q/k norm before rope.

One layer, ``u = norm(x)``:

- ``conv``:  ``[B | C | z] = u W_in``; ``v = B * z``; ``c[t] = sum_j
  w[:, j] * v[t - (K-1) + j]`` (depthwise, causal, zeros before the
  sequence); ``y = (C * c) W_out``;
- ``attention``: the homogeneous stack's, with ``cfg.qk_norm`` an RMSNorm
  over each head's channels of q and k (one weight for all heads) before
  rope;
- then ``x + y``, and the dense FFN (the first ``num_dense_layers``) or the
  expert layer on ``norm(x)``.

One device (or data parallel around it): no tp/pp partitioning, no dropout,
no attention mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.models.config import TransformerConfig
from apex_tpu.ops.flash_attention import REMAT_SAVED_NAMES

__all__ = ["init_hybrid_params", "hybrid_backbone", "short_conv",
           "qk_norm_rope", "moe_counters", "MOE_COUNTERS"]

# the expert layers' counters, summed over the layers: assignments on held
# experts and in all, the largest and the mean count over held experts
MOE_COUNTERS = ("moe_assignments_held", "moe_assignments",
                "moe_held_load_max", "moe_held_load_mean")


def _has_experts(cfg: TransformerConfig, layer: int) -> bool:
    return bool(cfg.num_experts) and layer >= cfg.num_dense_layers


def _kind(cfg: TransformerConfig, layer: int) -> str:
    return cfg.layer_types[layer] if cfg.layer_types else "attention"


def init_hybrid_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """The parameter tree of a hybrid stack: ``layers`` is a list, one tree
    a layer.  N(0, std) kernels, output projections narrower by sqrt(2L),
    norm weights 1, the router's selection bias 0."""
    h, L = cfg.hidden_size, cfg.num_layers
    p, kvp = cfg.projection_size, cfg.kv_projection_size
    std = cfg.init_method_std
    out_std = std / (2.0 * L) ** 0.5
    dt = cfg.params_dtype
    swiglu = cfg.activation == "swiglu"
    layernorm = cfg.normalization != "rmsnorm"

    def nrm(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    def zeros(*shape):
        return jnp.zeros(shape, dt)

    layers = []
    for i, key in enumerate(jax.random.split(rng, L + 1)[1:]):
        ks = jax.random.split(key, 6)
        lp = {"ln1_scale": jnp.ones((h,), dt),
              "ln2_scale": jnp.ones((h,), dt)}
        if layernorm:
            lp.update(ln1_bias=zeros(h), ln2_bias=zeros(h))
        if _kind(cfg, i) == "conv":
            lp.update(
                conv_in_kernel=nrm(ks[0], (h, 3 * h), std),
                conv_kernel=nrm(ks[1], (h, cfg.conv_kernel_size),
                                cfg.conv_kernel_size ** -0.5),
                conv_out_kernel=nrm(ks[2], (h, h), out_std))
        else:
            lp.update(qkv_kernel=nrm(ks[0], (h, p + 2 * kvp), std),
                      proj_kernel=nrm(ks[2], (p, h), out_std))
            if cfg.qk_norm:
                lp.update(q_norm_scale=jnp.ones((cfg.kv_channels,), dt),
                          k_norm_scale=jnp.ones((cfg.kv_channels,), dt))
            if cfg.use_bias:
                lp.update(qkv_bias=zeros(p + 2 * kvp), proj_bias=zeros(h))
        if _has_experts(cfg, i):
            G, E, f = (cfg.held_experts[1], cfg.num_experts,
                       cfg.ffn_hidden_size)
            f1 = 2 * f if swiglu else f
            lp.update(router_kernel=nrm(ks[3], (h, E), std),
                      moe_fc1=nrm(ks[4], (G, h, f1), std),
                      moe_fc2=nrm(ks[5], (G, f, h), out_std))
            if cfg.moe_router == "sigmoid":
                lp["router_bias"] = zeros(E)
            if cfg.use_bias:
                lp.update(moe_fc1_bias=zeros(G, f1), moe_fc2_bias=zeros(G, h))
        else:
            f = (cfg.dense_ffn_hidden_size if cfg.num_experts
                 else cfg.ffn_hidden_size)
            lp.update(
                fc1_kernel=nrm(ks[3], (h, 2, f) if swiglu else (h, f), std),
                fc2_kernel=nrm(ks[4], (f, h), out_std))
            if cfg.use_bias:
                lp.update(fc1_bias=zeros(2, f) if swiglu else zeros(f),
                          fc2_bias=zeros(h))
        layers.append(lp)

    params = {
        "embedding": {"word": nrm(rng, (cfg.vocab_size, h), std)},
        "layers": layers,
        "final_ln": {"scale": jnp.ones((h,), dt)},
    }
    if layernorm:
        params["final_ln"]["bias"] = zeros(h)
    if cfg.position_embedding_type == "learned":
        params["embedding"]["position"] = nrm(
            jax.random.fold_in(rng, 1), (cfg.max_position_embeddings, h),
            std)
    if cfg.untie_embeddings_and_output_weights:
        params["lm_head"] = {"kernel": nrm(
            jax.random.fold_in(rng, 2), (cfg.vocab_size, h), std)}
    return params


def short_conv(cfg: TransformerConfig, lp: dict, x):
    """The gated short convolution on ``x`` [b, s, h]: in-projection to
    ``[B | C | z]``, ``B * z`` through a depthwise causal convolution of
    ``cfg.conv_kernel_size`` taps (``conv_kernel`` [h, K], the last tap on
    the current position), gated by ``C``, out-projection.  The products
    run in ``x``'s dtype, the gates and the taps in float32."""
    dt = x.dtype
    taps, s = cfg.conv_kernel_size, x.shape[1]
    with jax.named_scope("conv_in"):
        bcz = x @ lp["conv_in_kernel"].astype(dt)
    with jax.named_scope("conv_gate"):
        b_, c_, z = (t.astype(jnp.float32) for t in jnp.split(bcz, 3, -1))
        v = jnp.pad(b_ * z, ((0, 0), (taps - 1, 0), (0, 0)))
        w = lp["conv_kernel"].astype(jnp.float32)
        conv = sum(w[:, j] * v[:, j:j + s] for j in range(taps))
        y = (c_ * conv).astype(dt)
    with jax.named_scope("conv_out"):
        return y @ lp["conv_out_kernel"].astype(dt)


def _head_rms(t, weight, eps):
    t32 = t.astype(jnp.float32)
    rs = jax.lax.rsqrt(jnp.mean(jnp.square(t32), axis=-1, keepdims=True)
                       + eps)
    return (t32 * rs * weight.astype(jnp.float32)).astype(t.dtype)


def qk_norm_rope(cfg: TransformerConfig, lp: dict, q, k, rope):
    """RMSNorm over each head's channels of ``q`` and ``k`` [b, s, n, d]
    (one weight for all heads), then rope."""
    from apex_tpu.models.transformer_lm import _apply_rope

    with jax.named_scope("qk_norm"):
        q = _head_rms(q, lp["q_norm_scale"], cfg.layernorm_epsilon)
        k = _head_rms(k, lp["k_norm_scale"], cfg.layernorm_epsilon)
    if rope is not None:
        with jax.named_scope("rope"):
            q = _apply_rope(q, *rope)
            k = _apply_rope(k, *rope)
    return q, k


def moe_counters(cfg: TransformerConfig, load) -> dict:
    """One expert layer's counters from its router's per-expert assignment
    counts ``load`` [E]."""
    first, count = cfg.held_experts
    held = load[first:first + count].astype(jnp.float32)
    return dict(zip(MOE_COUNTERS, (
        jnp.sum(held), jnp.sum(load.astype(jnp.float32)), jnp.max(held),
        jnp.mean(held))))


def _layer(cfg: TransformerConfig, layer: int, ctx, lp: dict, x, rope):
    from apex_tpu.models.transformer_lm import (
        _attention, _mlp, _moe_mlp, apply_norm)

    with jax.named_scope("ln1"):
        u = apply_norm(cfg, x, lp["ln1_scale"], lp.get("ln1_bias"))
    if _kind(cfg, layer) == "conv":
        with jax.named_scope("short_conv"):
            y = short_conv(cfg, lp, u)
    else:
        with jax.named_scope("attention"):
            y = _attention(cfg, lp, u, ctx, None, rope, None)
    with jax.named_scope("residual"):
        x = x + y
    with jax.named_scope("ln2"):
        m = apply_norm(cfg, x, lp["ln2_scale"], lp.get("ln2_bias"))
    counters = None
    if _has_experts(cfg, layer):
        with jax.named_scope("mlp"):
            f, _, load = _moe_mlp(cfg, lp, m, with_load=True)
        counters = moe_counters(cfg, load)
    else:
        with jax.named_scope("dense_ffn"):
            f = _mlp(cfg, lp, m, ctx)
    with jax.named_scope("residual"):
        x = x + f
    return ctx.constrain_hidden(x), counters


def hybrid_backbone(params: dict, hidden, cfg: TransformerConfig, ctx,
                    rope):
    """All layers in turn; returns ``(hidden, counters)``, the expert
    layers' :data:`MOE_COUNTERS` summed over the layers (``{}`` for a stack
    without experts)."""
    if ctx.tp != 1 or ctx.cp_axis is not None:
        raise ValueError("a hybrid stack has no tensor- or context-"
                         "parallel path")
    if (cfg.hidden_dropout or cfg.attention_dropout or cfg.drop_path_rate
            or cfg.attn_mask_type != "causal"):
        raise ValueError("a hybrid stack is causal and takes no dropout")
    policy = jax.checkpoint_policies.save_only_these_names(
        *REMAT_SAVED_NAMES)
    total = {}
    with jax.named_scope("backbone"):
        for i, lp in enumerate(params["layers"]):
            fn = functools.partial(_layer, cfg, i, ctx)
            if cfg.remat:
                fn = jax.checkpoint(fn, policy=policy)
            hidden, counters = fn(lp, hidden, rope)
            for name, v in (counters or {}).items():
                total[name] = total.get(name, 0.0) + v
    return hidden, total
