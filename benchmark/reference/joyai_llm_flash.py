"""Plain float32 JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``: the
DeepSeek-V3 layer, multi-head latent attention before a dense SwiGLU FFN or
sigmoid-routed gated experts beside a gated shared expert, and one
multi-token-prediction module) with its training loss, as ONE CHIP'S SHARE
of an expert-parallel deployment.  Imports nothing from apex_tpu.  The
configuration is the model's own ``config.json`` keys plus ``deployment``
and ``mtp_loss_weight``.

Bias-free everywhere, ``eps = rms_norm_eps``; ``x`` [s, h], ``n(x; g) = x /
sqrt(mean(x^2) + eps) * g``, ``n`` heads (arXiv 2405.04434 section 2.1 for
the attention, arXiv 2412.19437 sections 2.1-2.2 for router and MTP)::

    MLA(u):  c_q = n(u W_qa; g_q)                              [q_lora_rank]
             [q_nope | q_rope] = c_q W_qb       n heads of 128, n heads of 64
             [c_kv | k_r] = u W_kva                   [kv_lora_rank | 64]
             c_kv = n(c_kv; g_kv)
             [k_nope | v] = c_kv W_kvb          n heads of 128, n heads of 128
             rope on q_rope of every head and on the single k_r: pair
             (2i, 2i+1) of the 64 channels turned by pos * theta^(-2i/64)
             score = (q_nope . k_nope + q_rope . k_r) / sqrt(192), causal,
             softmax; o = P v [n x 128]; out = o W_o
    block:   x = x + MLA(n(x; g_1));  x = x + F(n(x; g_2))
    F, layers < first_k_dense_replace:  (silu(m W_1) * (m W_3)) W_2
    F, layers after:
             r = sigmoid(m W_g)                        [., E] in float32
             S = the k largest of (r + b)          b selects, never weighs
             w_e = r_e / (sum_{e in S} r_e + 1e-20) * routed_scaling_factor
             sum_{e in S, e held} w_e (silu(m W1_e) * (m W3_e)) W2_e
             + (silu(m W1_s) * (m W3_s)) W2_s    the shared expert, no gate
    output:  h = n(x; g_f);  L_main = CE(h W_head^T, labels)   (untied head)
    MTP:     h' = [n(Emb(labels); g_e) ; n(h; g_h)] W_eh       [2h -> h]
             one more block of the expert-layer form (published layer
             index 40), causal, the same rope positions; n(.; g_m); the
             SAME head; L_mtp = CE(., the tokens two ahead)
    loss = L_main + mtp_loss_weight * L_mtp

Embedding and head are shared leaves: each is used twice and its gradient
is the sum.

Departures from the published model, each stated in the configuration's file
under ``reduced`` or ``assumed``:

- **the share**: ``deployment.experts_held = [first, count]`` of the
  router's ``deployment.num_experts_published`` experts have weights here.
  The router keeps its published width and ``num_experts_per_tok``; what the
  absent experts would have added is left out, and that partial result goes
  on to the next layer, exactly as in the program.  MLA, the router and the
  shared expert are whole on every chip;
- the vocabulary is a slice (``vocab_size`` rows of embedding and head),
  logits and loss over it; depth is cut to the first layers, the MTP module
  kept;
- ``b`` is a seeded constant: it enters only the selection, so its gradient
  is nought, and the rule that updates it is not part of the published
  config.  ``n_group = topk_group = 1``: no group limit.  No auxiliary loss;
- assumed, since ``config.json`` does not say: ``mtp_loss_weight`` 0.3; the
  MTP block is an expert layer; ``h`` enters the MTP after the stack's final
  norm and the module has an output norm of its own; ``1e-20``;
- weights are random from the seed: N(0, 0.02), output projections narrower
  by ``sqrt(2 x (layers + MTP modules))``, norm weights about 1, ``b`` small
  random values so that nothing is multiplied by an exact 0 or 1;
- layouts are the program's, so that one tree serves both: ``q_b_kernel``'s
  columns are all heads' parts without position, then all heads' rotary
  parts; ``kv_b_kernel``'s all heads' keys, then all heads' values;
  ``kv_a_kernel``'s last 64 columns make ``k_r``; the rotary channels pair
  as ``(2i, 2i+1)`` (``rope_interleave``); the dense FFN's and the shared
  expert's first kernel is ``[h, 2, f]`` (gate, up), an expert's ``moe_fc1``
  ``[h, 2f]`` (gate columns, then up columns); ``eh_proj_kernel``'s first
  ``h`` rows take the embedding (the order of the halves is a permutation
  of its rows).

Memory: every block is recomputed in the backward pass (``jax.checkpoint``),
latent attention goes HEAD_BLOCK heads at a time from the latents on, in
blocks of Q_BLOCK queries, the dense FFN and the shared expert in blocks of
rows, the experts one at a time and the head in blocks, so that float32 at
b1 x s8192 fits one chip beside the optimizer's state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import transformer as T

INIT_STD = 0.02
Q_BLOCK = 256
HEAD_BLOCK = 8
ROW_BLOCK = 2048


def _held(cfg: dict) -> tuple:
    first, count = cfg["deployment"]["experts_held"]
    return int(first), int(count)


def _widths(cfg: dict) -> tuple:
    """``(n, r_q, r_kv, d_nope, d_rope, d_v)``."""
    return (cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _blocks(cfg: dict) -> int:
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def layer_spec(cfg: dict, dense: bool) -> dict:
    """Shapes, mean and spread of one block's leaves."""
    h = cfg["hidden_size"]
    n, rq, rkv, dn, dr, dv = _widths(cfg)
    std, out_std = INIT_STD, INIT_STD / math.sqrt(2.0 * _blocks(cfg))
    spec = {
        "ln1_scale": ((h,), 1.0, std), "ln2_scale": ((h,), 1.0, std),
        "q_a_kernel": ((h, rq), 0.0, std),
        "q_a_norm_scale": ((rq,), 1.0, std),
        "q_b_kernel": ((rq, n * (dn + dr)), 0.0, std),
        "kv_a_kernel": ((h, rkv + dr), 0.0, std),
        "kv_a_norm_scale": ((rkv,), 1.0, std),
        "kv_b_kernel": ((rkv, n * (dn + dv)), 0.0, std),
        "proj_kernel": ((n * dv, h), 0.0, out_std)}
    if dense:
        f = cfg["intermediate_size"]
        spec.update(fc1_kernel=((h, 2, f), 0.0, std),
                    fc2_kernel=((f, h), 0.0, out_std))
    else:
        f = cfg["moe_intermediate_size"]
        fs = f * cfg["n_shared_experts"]
        experts = cfg["deployment"]["num_experts_published"]
        held = _held(cfg)[1]
        spec.update(router_kernel=((h, experts), 0.0, std),
                    router_bias=((experts,), 0.0, std),
                    moe_fc1=((held, h, 2 * f), 0.0, std),
                    moe_fc2=((held, f, h), 0.0, out_std),
                    shared_fc1_kernel=((h, 2, fs), 0.0, std),
                    shared_fc2_kernel=((fs, h), 0.0, out_std))
    return spec


def init_params(key, cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    top = T.normal_tree(key, {
        "embedding": {"word": ((v, h), 0.0, INIT_STD)},
        "final_ln": {"scale": ((h,), 1.0, INIT_STD)},
        "lm_head": {"kernel": ((v, h), 0.0, INIT_STD)},
        "mtp": {"enorm_scale": ((h,), 1.0, INIT_STD),
                "hnorm_scale": ((h,), 1.0, INIT_STD),
                "eh_proj_kernel": ((2 * h, h), 0.0, INIT_STD),
                "norm_scale": ((h,), 1.0, INIT_STD)}})
    top["layers"] = [
        T.normal_tree(jax.random.fold_in(key, 1000 + i),
                      layer_spec(cfg, i < cfg["first_k_dense_replace"]))
        for i in range(cfg["num_hidden_layers"])]
    top["mtp"]["layer"] = T.normal_tree(
        jax.random.fold_in(key, 2000), layer_spec(cfg, False))
    return top


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(t, theta: float):
    """``t`` [b, s, n, d]: the pair ``(2i, 2i+1)`` of the last axis turned
    by ``pos * theta^(-2i/d)``."""
    s, d = t.shape[1], t.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)[None, :, None, :]
    pairs = t.reshape(t.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(t.shape)


def mla(u, lp, cfg: dict, prec: T.Precision):
    """Latent attention.  The two latents and the rotary key are made
    once; then HEAD_BLOCK heads at a time (their columns of ``W_qb`` and
    ``W_kvb``, their rows of ``W_o``: heads meet only in the sum that
    ``W_o`` makes) take their queries, keys and values, attend in blocks
    of Q_BLOCK queries, and add their part of the output, each group and
    each block recomputed in the backward pass."""
    b, s, h = u.shape
    n, rq, rkv, dn, dr, dv = _widths(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = rms_norm(prec.mm(u, lp["q_a_kernel"]), lp["q_a_norm_scale"], eps)
    c_kv = prec.mm(u, lp["kv_a_kernel"])
    k_r = rope(c_kv[..., None, rkv:], theta)[:, :, 0]           # [b, s, dr]
    c_kv = rms_norm(c_kv[..., :rkv], lp["kv_a_norm_scale"], eps)
    hb, bq = math.gcd(n, HEAD_BLOCK), math.gcd(s, Q_BLOCK)
    ng, nq = n // hb, s // bq

    def by_group(w, d, rows=False):
        """A kernel's ``n x d`` columns (or rows) as [groups, ., hb x d]."""
        if rows:
            return w.reshape(ng, hb * d, -1)
        return jnp.moveaxis(w.reshape(w.shape[0], ng, hb * d), 1, 0)

    @jax.checkpoint
    def group(out, w):
        q = prec.mm(c_q, w["q"]).reshape(b, s, hb, dn)
        q_r = rope(prec.mm(c_q, w["q_r"]).reshape(b, s, hb, dr), theta)
        k = prec.mm(c_kv, w["k"]).reshape(b, s, hb, dn)
        v = prec.mm(c_kv, w["v"]).reshape(b, s, hb, dv)

        @jax.checkpoint
        def rows(i):
            """Query block ``i % nq`` of batch row ``i // nq``."""
            r, at = i // nq, (i % nq) * bq
            take = lambda t: jax.lax.dynamic_slice_in_dim(   # noqa: E731
                t[r], at, bq)
            scores = (prec.einsum("qnd,tnd->nqt", take(q), k[r])
                      + prec.einsum("qnd,td->nqt", take(q_r), k_r[r]))
            scores = scores / math.sqrt(dn + dr)
            keep = jnp.arange(s)[None, :] <= (at + jnp.arange(bq))[:, None]
            probs = jax.nn.softmax(
                jnp.where(keep[None], scores, -jnp.inf), -1)
            return prec.einsum("nqt,tnd->qnd", probs, v[r])

        ctx = jax.lax.map(rows, jnp.arange(b * nq))
        return out + prec.mm(ctx.reshape(b, s, hb * dv), w["o"]), None

    q_b, kv_b = lp["q_b_kernel"], lp["kv_b_kernel"]
    return jax.lax.scan(group, jnp.zeros_like(u), {
        "q": by_group(q_b[:, :n * dn], dn),
        "q_r": by_group(q_b[:, n * dn:], dr),
        "k": by_group(kv_b[:, :n * dn], dn),
        "v": by_group(kv_b[:, n * dn:], dv),
        "o": by_group(lp["proj_kernel"], dv, rows=True)})[0]


def _by_rows(fn, x):
    """``fn`` over blocks of rows of ``x`` [rows, h], each recomputed in the
    backward pass."""
    rows = x.shape[0]
    block = math.gcd(rows, ROW_BLOCK)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(rows // block, block, -1))
    return out.reshape(rows, -1)


def gated_ffn(m, w1, w2, prec: T.Precision):
    """``(silu(m W_gate) * (m W_up)) W_2`` with ``w1`` [h, 2, f]."""
    def rows(x):
        y = prec.einsum("rh,hcf->rcf", x, w1)
        return prec.mm(jax.nn.silu(y[:, 0]) * y[:, 1], w2)
    return _by_rows(rows, m)


def route(m, lp, cfg: dict, prec: T.Precision):
    """``(choice [T, k], weights [T, k])``."""
    r = jax.nn.sigmoid(prec.mm(m, lp["router_kernel"]))
    remaining = jax.lax.stop_gradient(r + lp["router_bias"])
    choice = []
    for _ in range(cfg["num_experts_per_tok"]):
        c = jnp.argmax(remaining, axis=-1)
        choice.append(c)
        remaining = jnp.where(
            jnp.arange(r.shape[-1])[None, :] == c[:, None], -jnp.inf,
            remaining)
    choice = jnp.stack(choice, axis=-1)
    picked = jnp.take_along_axis(r, choice, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return choice, weights * cfg["routed_scaling_factor"]


def routed_experts(m, lp, cfg: dict, prec: T.Precision, held=None):
    """The held experts' part of the routed sum for ``m`` [T, h], a loop
    over the held experts; ``held = (first, count)`` in the configuration's
    place lets a test sum the shares of a deployment."""
    first, count = held if held is not None else _held(cfg)
    choice, weights = route(m, lp, cfg, prec)

    @jax.checkpoint
    def one(f, args):
        e, w1, w2 = args
        w_e = jnp.sum(jnp.where(choice == first + e, weights, 0.0), axis=-1)
        gate, up = jnp.split(prec.mm(m, w1), 2, axis=-1)
        return f + w_e[:, None] * prec.mm(jax.nn.silu(gate) * up, w2), None

    return jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(count), lp["moe_fc1"], lp["moe_fc2"]))[0]


def ffn(m, lp, cfg: dict, prec: T.Precision, held=None):
    """``F`` on ``m`` [T, h]: dense where the block has ``fc1_kernel``."""
    if "fc1_kernel" in lp:
        return gated_ffn(m, lp["fc1_kernel"], lp["fc2_kernel"], prec)
    return routed_experts(m, lp, cfg, prec, held) + gated_ffn(
        m, lp["shared_fc1_kernel"], lp["shared_fc2_kernel"], prec)


def block(x, lp, cfg: dict, prec: T.Precision, held=None):
    eps = cfg["rms_norm_eps"]
    x = x + mla(rms_norm(x, lp["ln1_scale"], eps), lp, cfg, prec)
    b, s, h = x.shape
    m = rms_norm(x, lp["ln2_scale"], eps).reshape(b * s, h)
    return x + ffn(m, lp, cfg, prec, held).reshape(b, s, h)


def losses(params, batch, cfg: dict, prec: T.Precision):
    """``(L_main, L_mtp)``; ``batch`` = (tokens, labels, the tokens two
    ahead), each [b, s] inside the slice of the vocabulary; labels of -1
    are left out of a mean."""
    tokens, labels, labels2 = batch
    eps = cfg["rms_norm_eps"]
    word, head = params["embedding"]["word"], params["lm_head"]["kernel"]

    def remat_block(x, lp):
        return jax.checkpoint(lambda x, lp: block(x, lp, cfg, prec))(x, lp)

    def cross_entropy(x, labels):
        total, count = T.blocked_cross_entropy(
            x.reshape(-1, x.shape[-1]), head, 0.0, labels.reshape(-1), prec)
        return total / count

    x = word[tokens]
    for lp in params["layers"]:
        x = remat_block(x, lp)
    x = rms_norm(x, params["final_ln"]["scale"], eps)
    main = cross_entropy(x, labels)
    mp = params["mtp"]
    merged = jnp.concatenate([
        rms_norm(word[jnp.maximum(labels, 0)], mp["enorm_scale"], eps),
        rms_norm(x, mp["hnorm_scale"], eps)], axis=-1)
    x = remat_block(prec.mm(merged, mp["eh_proj_kernel"]), mp["layer"])
    x = rms_norm(x, mp["norm_scale"], eps)
    return main, cross_entropy(x, labels2)


def loss(params, batch, cfg: dict, prec: T.Precision):
    main, mtp = losses(params, batch, cfg, prec)
    return main + cfg["mtp_loss_weight"] * mtp
