"""Stacks whose layers differ in kind: the LFM2 family's gated short
convolutions between grouped-query attention layers, dense SwiGLU layers
before sigmoid-routed expert layers; the Nemotron-H family's single
mixers, a Mamba-2 mixer, an expert layer or attention a layer; and the
DeepSeek-V3 layer, multi-head latent attention before a dense FFN or gated
experts beside a gated shared expert, with a multi-token-prediction module
after the stack.

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
- ``mla`` (:func:`mla_attention`; ``n`` heads, ranks ``r_q`` and ``r_kv``):
  ``c_q = norm(u W_qa)``; ``[q_nope | q_rope] = c_q W_qb``, ``n`` heads of
  ``mla_nope_dim`` and ``n`` of ``mla_rope_dim``; ``[c_kv | k_r] = u
  W_kva``, ``c_kv = norm(c_kv)``; ``[k_nope | v] = c_kv W_kvb``; rope in
  pairs ``(2i, 2i+1)`` on ``q_rope`` of every head and on the ONE ``k_r``;
  ``softmax((q_nope k_nope^T + q_rope k_r^T) / sqrt(nope + rope)) v``
  (``ops/flash_attention.flash_attention_mla``); ``W_o``;
- then ``x + y``, and the dense FFN (the first ``num_dense_layers``) or the
  expert layer on ``norm(x)``.

A ``cfg.mixer_only`` stack has no FFN after an operator: every layer is
``x + mixer(norm(x))`` with one norm (``ln1``), the mixer by its kind:

- ``mamba`` (:func:`mamba_mixer`; ``H`` heads of ``P``, ``d_in = H P``,
  ``G`` groups, state size ``N``): ``[z | x | B | C | dt] = u W_in`` of
  widths ``d_in | d_in | G N | G N | H``; ``[x | B | C] = silu(conv([x | B |
  C]) + b_conv)``, depthwise and causal as above; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` in float32 (``A_log``, ``dt_bias`` and
  ``D`` stay float32 in a mixed-precision model's copy of the weights
  too: ``amp/policy.py`` keeps them with the norms' scales); the
  state-space scan
  (``ops/ssd_scan.py``: per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
  B_t``, ``y_t = S_t C_t + D x_t``); ``y = RMSNorm_groups(y * silu(z))``
  over ``G`` groups of ``d_in / G`` channels; ``y W_out``;
- ``moe``: the expert layer itself (:func:`expert_layer`: sigmoid router,
  the held experts' ``relu(u W_1)^2 W_2``, the shared expert added once);
- ``attention``: the homogeneous stack's, without positions where
  ``cfg.position_embedding_type`` is ``'none'``.

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
           "mamba_mixer", "mla_attention", "rope_interleaved",
           "expert_layer", "mtp_hidden", "mtp_loss", "qk_norm_rope", "moe_counters",
           "MOE_COUNTERS"]

# the expert layers' counters, summed over the layers: assignments on held
# experts and in all, the largest and the mean count over held experts
MOE_COUNTERS = ("moe_assignments_held", "moe_assignments",
                "moe_held_load_max", "moe_held_load_mean")


def _kind(cfg: TransformerConfig, layer: int) -> str:
    """Layer ``layer``'s kind; an index past the stack (the MTP module's
    block) is of the last layer's."""
    if not cfg.layer_types:
        return "attention"
    return cfg.layer_types[min(layer, cfg.num_layers - 1)]


def _has_experts(cfg: TransformerConfig, layer: int) -> bool:
    if cfg.mixer_only:
        return _kind(cfg, layer) == "moe"
    return bool(cfg.num_experts) and layer >= cfg.num_dense_layers


def _mamba_widths(cfg: TransformerConfig) -> tuple:
    """``(d_in, G N)``: the mixer's inner width and the width of B (and
    of C)."""
    return (cfg.mamba_num_heads * cfg.mamba_head_dim,
            cfg.ssm_groups * cfg.ssm_state_size)


def init_hybrid_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """The parameter tree of a hybrid stack: ``layers`` is a list, one tree
    a layer.  N(0, std) kernels, output projections narrower by sqrt(2L),
    norm weights 1, the router's selection bias and the convolution's
    bias 0, a Mamba-2 mixer's time constants as ``mamba_leaves`` says."""
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

    def mamba_leaves(ks):
        """``A_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of a
        log-uniform time step; ``D = 1`` (Mamba-2's own draw)."""
        d_in, gn = _mamba_widths(cfg)
        heads = cfg.mamba_num_heads
        lo, hi, floor = cfg.mamba_time_step
        step = jnp.maximum(floor, jnp.exp(jax.random.uniform(
            ks[3], (heads,), jnp.float32, jnp.log(lo), jnp.log(hi))))
        return dict(
            ssm_in_kernel=nrm(ks[0], (h, 2 * d_in + 2 * gn + heads), std),
            conv_kernel=nrm(ks[1], (d_in + 2 * gn, cfg.conv_kernel_size),
                            cfg.conv_kernel_size ** -0.5),
            conv_bias=zeros(d_in + 2 * gn),
            ssm_dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(dt),
            ssm_a_log=jnp.log(jax.random.uniform(
                ks[4], (heads,), jnp.float32, 1.0, 16.0)).astype(dt),
            ssm_d=jnp.ones((heads,), dt),
            ssm_norm_scale=jnp.ones((d_in,), dt),
            ssm_out_kernel=nrm(ks[2], (d_in, h), out_std))

    def attention_leaves(ks):
        lp = dict(qkv_kernel=nrm(ks[0], (h, p + 2 * kvp), std),
                  proj_kernel=nrm(ks[2], (p, h), out_std))
        if cfg.qk_norm:
            lp.update(q_norm_scale=jnp.ones((cfg.kv_channels,), dt),
                      k_norm_scale=jnp.ones((cfg.kv_channels,), dt))
        if cfg.use_bias:
            lp.update(qkv_bias=zeros(p + 2 * kvp), proj_bias=zeros(h))
        return lp

    def mla_leaves(ks):
        """The columns of ``q_b_kernel`` are all heads' parts without
        position, then all heads' rotary parts; those of ``kv_b_kernel``
        all heads' keys, then all heads' values; ``kv_a_kernel``'s last
        ``mla_rope_dim`` columns make the one rotary key."""
        n, rq, rkv = (cfg.num_attention_heads, cfg.mla_q_rank,
                      cfg.mla_kv_rank)
        dn, dr, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
        return dict(
            q_a_kernel=nrm(ks[0], (h, rq), std),
            q_a_norm_scale=jnp.ones((rq,), dt),
            q_b_kernel=nrm(ks[1], (rq, n * (dn + dr)), std),
            kv_a_kernel=nrm(ks[2], (h, rkv + dr), std),
            kv_a_norm_scale=jnp.ones((rkv,), dt),
            kv_b_kernel=nrm(ks[3], (rkv, n * (dn + dv)), std),
            proj_kernel=nrm(ks[4], (n * dv, h), out_std))

    def expert_leaves(ks, shared_ks=None):
        G, E, f = (cfg.held_experts[1], cfg.num_experts,
                   cfg.ffn_hidden_size)
        f1 = 2 * f if swiglu else f
        lp = dict(router_kernel=nrm(ks[3], (h, E), std),
                  moe_fc1=nrm(ks[4], (G, h, f1), std),
                  moe_fc2=nrm(ks[5], (G, f, h), out_std))
        if cfg.moe_router == "sigmoid":
            lp["router_bias"] = zeros(E)
        if cfg.use_bias:
            lp.update(moe_fc1_bias=zeros(G, f1), moe_fc2_bias=zeros(G, h))
        if cfg.moe_shared_expert_size:
            fs = cfg.moe_shared_expert_size
            k1, k2 = (ks[0], ks[2]) if shared_ks is None else shared_ks
            # gated like the dense FFN where the experts are: [h, 2, fs]
            lp.update(shared_fc1_kernel=nrm(
                k1, (h, 2, fs) if swiglu else (h, fs), std),
                shared_fc2_kernel=nrm(k2, (fs, h), out_std))
        return lp

    def layer_leaves(i, key):
        """Layer ``i``'s tree; an index past the stack gives the form of
        the layers after the dense ones (the MTP module's block)."""
        ks = jax.random.split(key, 6)
        kind = _kind(cfg, i)
        lp = {"ln1_scale": jnp.ones((h,), dt)}
        if layernorm:
            lp["ln1_bias"] = zeros(h)
        if not cfg.mixer_only:
            lp["ln2_scale"] = jnp.ones((h,), dt)
            if layernorm:
                lp["ln2_bias"] = zeros(h)
        if kind == "conv":
            lp.update(
                conv_in_kernel=nrm(ks[0], (h, 3 * h), std),
                conv_kernel=nrm(ks[1], (h, cfg.conv_kernel_size),
                                cfg.conv_kernel_size ** -0.5),
                conv_out_kernel=nrm(ks[2], (h, h), out_std))
        elif kind == "mamba":
            lp.update(mamba_leaves(ks))
        elif kind == "attention":
            lp.update(attention_leaves(ks))
        elif kind == "mla":
            more = jax.random.split(jax.random.fold_in(key, 1), 7)
            lp.update(mla_leaves(more))
        if _has_experts(cfg, i):
            lp.update(expert_leaves(
                ks, more[5:] if kind == "mla" else None))
        elif not cfg.mixer_only:
            f = (cfg.dense_ffn_hidden_size if cfg.num_experts
                 else cfg.ffn_hidden_size)
            lp.update(
                fc1_kernel=nrm(ks[3], (h, 2, f) if swiglu else (h, f), std),
                fc2_kernel=nrm(ks[4], (f, h), out_std))
            if cfg.use_bias:
                lp.update(fc1_bias=zeros(2, f) if swiglu else zeros(f),
                          fc2_bias=zeros(h))
        return lp

    layers = [layer_leaves(i, key) for i, key in enumerate(
        jax.random.split(rng, L + 1)[1:])]

    params = {
        "embedding": {"word": nrm(rng, (cfg.vocab_size, h), std)},
        "layers": layers,
        "final_ln": {"scale": jnp.ones((h,), dt)},
    }
    if cfg.mtp_layers:
        key = jax.random.fold_in(rng, 3)
        params["mtp"] = {
            "enorm_scale": jnp.ones((h,), dt),
            "hnorm_scale": jnp.ones((h,), dt),
            "eh_proj_kernel": nrm(key, (2 * h, h), std),
            "layer": layer_leaves(L, jax.random.fold_in(key, 1)),
            "norm_scale": jnp.ones((h,), dt)}
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


def causal_taps(v, w, bias=None):
    """The depthwise causal convolution of ``v`` [b, s, c] (float32) with
    ``w`` [c, K], the last tap on the current position and zeros before
    the sequence: ``sum_j w[:, j] * v[t - (K-1) + j]``, plus ``bias``
    [c]."""
    taps, s = w.shape[1], v.shape[1]
    v = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    conv = sum(w[:, j] * v[:, j:j + s] for j in range(taps))
    return conv if bias is None else conv + bias.astype(jnp.float32)


def short_conv(cfg: TransformerConfig, lp: dict, x):
    """The gated short convolution on ``x`` [b, s, h]: in-projection to
    ``[B | C | z]``, ``B * z`` through a depthwise causal convolution of
    ``cfg.conv_kernel_size`` taps (``conv_kernel`` [h, K], the last tap on
    the current position), gated by ``C``, out-projection.  The products
    run in ``x``'s dtype, the gates and the taps in float32."""
    dt = x.dtype
    with jax.named_scope("conv_in"):
        bcz = x @ lp["conv_in_kernel"].astype(dt)
    with jax.named_scope("conv_gate"):
        b_, c_, z = (t.astype(jnp.float32) for t in jnp.split(bcz, 3, -1))
        y = (c_ * causal_taps(b_ * z, lp["conv_kernel"])).astype(dt)
    with jax.named_scope("conv_out"):
        return y @ lp["conv_out_kernel"].astype(dt)


def mamba_mixer(cfg: TransformerConfig, lp: dict, u):
    """The Mamba-2 mixer on ``u`` [b, s, h] (the module docstring has the
    equations).  The two projections and the scan's products run in
    ``u``'s dtype; the convolution, ``silu``, ``softplus``, the decays and
    the grouped norm in float32."""
    from apex_tpu.ops.ssd_scan import ssd_scan_packed

    dt = u.dtype
    f32 = jnp.float32
    b, s, _ = u.shape
    groups = cfg.ssm_groups
    d_in, gn = _mamba_widths(cfg)
    with jax.named_scope("ssm_in"):
        zxbcdt = u @ lp["ssm_in_kernel"].astype(dt)
        z, xbc, step = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * gn], -1)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(causal_taps(
            xbc.astype(f32), lp["conv_kernel"], lp["conv_bias"])).astype(dt)
    with jax.named_scope("ssd_scan"):
        # [x | B | C] stay one array: the scan's kernels take each part
        # by column block
        step = jax.nn.softplus(step.astype(f32)
                               + lp["ssm_dt_bias"].astype(f32))
        y = ssd_scan_packed(
            xbc, step, -jnp.exp(lp["ssm_a_log"].astype(f32)), lp["ssm_d"],
            groups=groups, state=gn // groups, chunk=cfg.ssm_chunk_size)
    with jax.named_scope("ssm_gate_norm"):
        y = y.astype(f32) * jax.nn.silu(z.astype(f32))
        y = y.reshape(b, s, groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + cfg.layernorm_epsilon)
        y = (y.reshape(b, s, d_in)
             * lp["ssm_norm_scale"].astype(f32)).astype(dt)
    with jax.named_scope("ssm_out"):
        return y @ lp["ssm_out_kernel"].astype(dt)


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


def rope_interleaved(t, cos, sin):
    """Rope on the last axis of ``t`` [b, s, ..., r] in pairs ``(2i,
    2i+1)``: pair ``i`` turned by the angle whose ``cos`` and ``sin`` are
    column ``i`` of the tables ([s, r / 2] or wider: ``rope_cos_sin``'s
    repeat their first half).  float32 inside, ``t``'s dtype out."""
    r = t.shape[-1]
    shape = (1, t.shape[1]) + (1,) * (t.ndim - 3) + (r,)
    cos = jnp.repeat(cos[:, :r // 2], 2, axis=-1).reshape(shape)
    sin = jnp.repeat(sin[:, :r // 2], 2, axis=-1).reshape(shape)
    even = jnp.arange(r) % 2 == 0
    t32 = t.astype(jnp.float32)
    # each lane's partner in its pair; -sin for the first of a pair
    partner = jnp.where(even, jnp.roll(t32, -1, -1), jnp.roll(t32, 1, -1))
    return (t32 * cos + partner * jnp.where(even, -sin, sin)).astype(
        t.dtype)


def mla_attention(cfg: TransformerConfig, lp: dict, u, rope):
    """Multi-head latent attention on ``u`` [b, s, h] (the module
    docstring has the equations; ``rope`` is ``rope_cos_sin``'s pair of
    tables over ``mla_rope_dim``).  The parts of q and of k/v leave
    their own products (``W_qb``'s and ``W_kvb``'s column sections), so
    that the kernels read each where its product wrote it and no
    gradient is put together from parts an activation at a time."""
    from apex_tpu.ops.flash_attention import flash_attention_mla

    dt = u.dtype
    b, s, _ = u.shape
    n, rkv = cfg.num_attention_heads, cfg.mla_kv_rank
    dn, dr = cfg.mla_nope_dim, cfg.mla_rope_dim
    eps = cfg.layernorm_epsilon
    with jax.named_scope("mla_q_latent"):
        c_q = _head_rms(u @ lp["q_a_kernel"].astype(dt),
                        lp["q_a_norm_scale"], eps)
        w = lp["q_b_kernel"].astype(dt)
        q = (c_q @ w[:, :n * dn]).reshape(b, s, n, dn)
        q_r = (c_q @ w[:, n * dn:]).reshape(b, s, n, dr)
    with jax.named_scope("mla_kv_latent"):
        c_kv = u @ lp["kv_a_kernel"].astype(dt)
        k_r = c_kv[..., rkv:]
        c_kv = _head_rms(c_kv[..., :rkv], lp["kv_a_norm_scale"], eps)
        w = lp["kv_b_kernel"].astype(dt)
        k = (c_kv @ w[:, :n * dn]).reshape(b, s, n, dn)
        v = (c_kv @ w[:, n * dn:]).reshape(b, s, n, -1)
    with jax.named_scope("mla_rope"):
        q_r = rope_interleaved(q_r, *rope)
        k_r = rope_interleaved(k_r, *rope)
    with jax.named_scope("core_attention"):
        o = flash_attention_mla(q, q_r, k, k_r, v, causal=True)
    with jax.named_scope("mla_out"):
        return o.reshape(b, s, -1) @ lp["proj_kernel"].astype(dt)


def moe_counters(cfg: TransformerConfig, load) -> dict:
    """One expert layer's counters from its router's per-expert assignment
    counts ``load`` [E]."""
    first, count = cfg.held_experts
    held = load[first:first + count].astype(jnp.float32)
    return dict(zip(MOE_COUNTERS, (
        jnp.sum(held), jnp.sum(load.astype(jnp.float32)), jnp.max(held),
        jnp.mean(held))))


def expert_layer(cfg: TransformerConfig, lp: dict, m):
    """The expert layer on ``m`` [b, s, h]: the routed sum of the held
    experts (``transformer_lm._moe_mlp``) and, where the layer has one
    (``cfg.moe_shared_expert_size``), the shared expert, of the experts'
    own form: ``relu(m W_1)^2 W_2``, or gated, ``(silu(m W_g) * m W_u)
    W_2`` (``shared_fc1_kernel`` [h, 2, f_s], the dense FFN's pairing).
    Every token passes it, no gate of the router weighs it, and it is
    added once.  Returns ``(out, load)``, the router's per-expert
    assignment counts [E] beside the output."""
    from apex_tpu.models.transformer_lm import _moe_mlp

    out, _, load = _moe_mlp(cfg, lp, m, with_load=True)
    if "shared_fc1_kernel" in lp:
        with jax.named_scope("shared_expert"):
            w1 = lp["shared_fc1_kernel"].astype(m.dtype)
            if w1.ndim == 3:
                from apex_tpu.ops.swiglu import fused_bias_swiglu_paired

                y = fused_bias_swiglu_paired(
                    jnp.einsum("bsh,hcf->bscf", m, w1), None)
            else:
                y = (m @ w1).astype(jnp.float32)
                y = jnp.square(jax.nn.relu(y)).astype(m.dtype)
            out = out + y @ lp["shared_fc2_kernel"].astype(m.dtype)
    return out, load


def _mixer_layer(cfg: TransformerConfig, layer: int, ctx, lp: dict, x,
                 rope):
    """One layer of a ``cfg.mixer_only`` stack: ``x + mixer(norm(x))``."""
    from apex_tpu.models.transformer_lm import _attention, apply_norm

    with jax.named_scope("ln1"):
        u = apply_norm(cfg, x, lp["ln1_scale"], lp.get("ln1_bias"))
    kind, counters = _kind(cfg, layer), None
    if kind == "mamba":
        with jax.named_scope("mamba_mixer"):
            y = mamba_mixer(cfg, lp, u)
    elif kind == "moe":
        with jax.named_scope("mlp"):
            y, load = expert_layer(cfg, lp, u)
        counters = moe_counters(cfg, load)
    else:
        with jax.named_scope("attention"):
            y = _attention(cfg, lp, u, ctx, None, rope, None)
    with jax.named_scope("residual"):
        x = x + y
    return ctx.constrain_hidden(x), counters


def _layer(cfg: TransformerConfig, layer: int, ctx, lp: dict, x, rope):
    from apex_tpu.models.transformer_lm import _attention, _mlp, apply_norm

    with jax.named_scope("ln1"):
        u = apply_norm(cfg, x, lp["ln1_scale"], lp.get("ln1_bias"))
    kind = _kind(cfg, layer)
    if kind == "conv":
        with jax.named_scope("short_conv"):
            y = short_conv(cfg, lp, u)
    elif kind == "mla":
        with jax.named_scope("mla_attention"):
            y = mla_attention(cfg, lp, u, rope)
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
            f, load = expert_layer(cfg, lp, m)
        counters = moe_counters(cfg, load)
    else:
        with jax.named_scope("dense_ffn"):
            f = _mlp(cfg, lp, m, ctx)
    with jax.named_scope("residual"):
        x = x + f
    return ctx.constrain_hidden(x), counters


def _remat_policy():
    return jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED_NAMES)


def mtp_hidden(mp: dict, hidden, embedded, cfg: TransformerConfig, ctx,
               rope):
    """The multi-token-prediction module ``mp`` (``params["mtp"]``) on
    the stack's normed output ``hidden`` and the next tokens' embedding
    ``embedded``, both [b, s, h]: ``[norm_e(embedded) ; norm_h(hidden)]
    W_eh`` (two products, one a half of ``W_eh``), one more block of the
    form of the layers after the dense ones, and the module's own output
    norm.  Returns ``(hidden, counters)`` like a layer."""
    from apex_tpu.models.transformer_lm import apply_norm

    h = cfg.hidden_size
    with jax.named_scope("mtp_merge"):
        w = mp["eh_proj_kernel"].astype(hidden.dtype)
        x = (apply_norm(cfg, embedded, mp["enorm_scale"], None) @ w[:h]
             + apply_norm(cfg, hidden, mp["hnorm_scale"], None) @ w[h:])
    fn = functools.partial(_layer, cfg, cfg.num_layers, ctx)
    if cfg.remat:
        fn = jax.checkpoint(fn, policy=_remat_policy())
    x, counters = fn(mp["layer"], x, rope)
    with jax.named_scope("mtp_norm"):
        return apply_norm(cfg, x, mp["norm_scale"], None), counters


def mtp_loss(params: dict, hidden, labels, mtp_labels,
             cfg: TransformerConfig, ctx, head_ce):
    """The multi-token-prediction term of ``gpt_loss``: the module on the
    stack's normed output ``hidden`` and the embedding of ``labels`` (the
    next tokens), then ``head_ce(hidden, labels)``, the model's own head
    and cross-entropy, against ``mtp_labels``, the tokens two ahead.
    Returns ``(loss, counters)``.  Its scopes (``mtp`` around all of it,
    ``mtp_head``) are opened here."""
    from apex_tpu.models.transformer_lm import embed_tokens, rope_cos_sin

    with jax.named_scope("mtp"):
        rope = (rope_cos_sin(hidden.shape[1], cfg.kv_channels,
                             cfg.rope_theta)
                if cfg.position_embedding_type == "rope" else None)
        h2, counters = mtp_hidden(
            params["mtp"], hidden,
            embed_tokens(params["embedding"], jnp.maximum(labels, 0), cfg,
                         ctx), cfg, ctx, rope)
        with jax.named_scope("mtp_head"):
            return head_ce(h2, mtp_labels), counters


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
    policy = _remat_policy()
    total = {}
    with jax.named_scope("backbone"):
        for i, lp in enumerate(params["layers"]):
            fn = functools.partial(
                _mixer_layer if cfg.mixer_only else _layer, cfg, i, ctx)
            if cfg.remat:
                fn = jax.checkpoint(fn, policy=policy)
            hidden, counters = fn(lp, hidden, rope)
            for name, v in (counters or {}).items():
                total[name] = total.get(name, 0.0) + v
    return hidden, total
