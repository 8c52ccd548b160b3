"""Plain float32 LFM2-MoE (``model_type`` ``lfm2_moe``: gated short
convolutions between grouped-query attention layers, dense SwiGLU layers and
then sigmoid-routed experts) with its next-token loss, as ONE CHIP'S SHARE of
an expert-parallel deployment.  Imports nothing from apex_tpu.  The
configuration is the model's own ``config.json`` keys plus ``deployment``.

One layer, ``x`` [s, h], ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``::

    u = RMS(x; g_op)
    conv:       [B | C | z] = u W_in;  v = B * z
                c[t] = sum_{j<K} w[:, j] * v[t - (K-1) + j],  v[<0] = 0
                y = (C * c) W_out
    attention:  q, k, v = u W_qkv   (32 query heads over 8 K/V heads of 64)
                q = RMS_64(q; g_q); k = RMS_64(k; g_k)   one weight, all heads
                q, k = rope(q), rope(k)     rotate-half over the 64, theta 1e6
                y = concat_heads(causal softmax(q k^T / 8) v) W_o
    x = x + y;  m = RMS(x; g_ffn)
    dense (layer < num_dense_layers):  f = (silu(m W_1) * (m W_3)) W_2
    experts:    r = sigmoid(m W_g)                       [., E] in float32
                S = the k largest of (r + b)             b selects, never weighs
                w_e = r_e / (sum_{e in S} r_e + 1e-6) * routed_scaling_factor
                f = sum_{e in S, e held} w_e (silu(m W1_e) * (m W3_e)) W2_e
    x = x + f
    after the last layer: RMS(x; g_out); logits = x E^T    (tied head)

Departures from the published model, each stated in the configuration's file
under ``reduced`` or ``assumed``:

- **the share**: ``deployment.experts_held = [first, count]`` of the
  router's ``deployment.num_experts_published`` experts have weights here.
  The router keeps its published width and ``num_experts_per_tok``; what the
  absent experts would have added to ``f`` is left out, and that partial
  result goes on to the next layer, exactly as in the program;
- the vocabulary is a slice (``vocab_size`` rows), logits and loss over it;
- depth and the count of leading dense layers are cut (``reduced``);
- ``b`` (``use_expert_bias``) is a seeded constant: it enters only the
  selection, so its gradient is nought, and the rule that updates it is not
  part of the published config.  No auxiliary loss;
- assumed, since the config does not say: tied head, the final norm
  (``embedding_norm``) applied last, the ``1e-6``, the chunk order
  ``B, C, z``, one q/k norm weight for all heads;
- weights are random from the seed; ``b`` and every norm weight get small
  random values so that nothing is multiplied by an exact 0 or 1;
- layouts are the program's, so that one tree serves both: the fused QKV
  kernel holds, per K/V head, ``[q x 4 | k | v]`` blocks of 64 columns; the
  dense FFN's ``fc1_kernel`` is ``[h, 2, f]`` (gate, up); an expert's
  ``moe_fc1`` is ``[h, 2f]`` (gate columns, then up columns).

Memory: every layer is recomputed in the backward pass (``jax.checkpoint``),
attention goes through in blocks of queries, the dense FFN in blocks of rows,
the experts one at a time and the head in blocks, so that float32 at b2 x
s8192 fits one chip beside the optimizer's state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import transformer as T

INIT_STD = 0.02
Q_BLOCK = 1024
ROW_BLOCK = 4096


def _kinds(cfg: dict) -> list:
    return ["attention" if k == "full_attention" else k
            for k in cfg["layer_types"]]


def _held(cfg: dict) -> tuple:
    first, count = cfg["deployment"]["experts_held"]
    return int(first), int(count)


def layer_spec(cfg: dict, layer: int) -> dict:
    """Shapes, mean and spread of one layer's leaves."""
    h = cfg["hidden_size"]
    n, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // n
    L = cfg["num_hidden_layers"]
    std, out_std = INIT_STD, INIT_STD / math.sqrt(2.0 * L)
    spec = {"ln1_scale": ((h,), 1.0, std), "ln2_scale": ((h,), 1.0, std)}
    if _kinds(cfg)[layer] == "conv":
        taps = cfg["conv_L_cache"]
        spec.update(
            conv_in_kernel=((h, 3 * h), 0.0, std),
            conv_kernel=((h, taps), 0.0, 1.0 / math.sqrt(taps)),
            conv_out_kernel=((h, h), 0.0, out_std))
    else:
        spec.update(
            qkv_kernel=((h, (n + 2 * g) * d), 0.0, std),
            q_norm_scale=((d,), 1.0, std), k_norm_scale=((d,), 1.0, std),
            proj_kernel=((n * d, h), 0.0, out_std))
    if layer < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        spec.update(fc1_kernel=((h, 2, f), 0.0, std),
                    fc2_kernel=((f, h), 0.0, out_std))
    else:
        f = cfg["moe_intermediate_size"]
        experts = cfg["deployment"]["num_experts_published"]
        held = _held(cfg)[1]
        spec.update(router_kernel=((h, experts), 0.0, std),
                    router_bias=((experts,), 0.0, std),
                    moe_fc1=((held, h, 2 * f), 0.0, std),
                    moe_fc2=((held, f, h), 0.0, out_std))
    return spec


def init_params(key, cfg: dict) -> dict:
    h = cfg["hidden_size"]
    top = T.normal_tree(key, {
        "embedding": {"word": ((cfg["vocab_size"], h), 0.0, INIT_STD)},
        "final_ln": {"scale": ((h,), 1.0, INIT_STD)}})
    top["layers"] = [
        T.normal_tree(jax.random.fold_in(key, 1000 + i), layer_spec(cfg, i))
        for i in range(cfg["num_hidden_layers"])]
    return top


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(t, theta: float):
    """Rotate-half over the whole last dimension; ``t`` [b, s, n, d]."""
    s, d = t.shape[1], t.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    t1, t2 = t[..., : d // 2], t[..., d // 2:]
    return t * jnp.cos(ang) + jnp.concatenate([-t2, t1], -1) * jnp.sin(ang)


def short_conv(u, lp, prec: T.Precision):
    bcz = prec.mm(u, lp["conv_in_kernel"])
    b_, c_, z = jnp.split(bcz, 3, axis=-1)
    w = lp["conv_kernel"]
    taps, s = w.shape[1], u.shape[1]
    v = jnp.pad(b_ * z, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w[:, j] * v[:, j:j + s] for j in range(taps))
    return prec.mm(c_ * conv, lp["conv_out_kernel"])


def attention(u, lp, cfg: dict, prec: T.Precision):
    b, s, h = u.shape
    n, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, rep = h // n, n // g
    eps = cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    qkv = prec.mm(u, lp["qkv_kernel"]).reshape(b, s, g, rep + 2, d)
    q = qkv[..., :rep, :].reshape(b, s, n, d)
    k, v = qkv[..., rep, :], qkv[..., rep + 1, :]
    q = rope(rms_norm(q, lp["q_norm_scale"], eps), theta)
    k = rope(rms_norm(k, lp["k_norm_scale"], eps), theta)
    # [b*g*blocks, rep, bq, d] query blocks; each sees its K/V head whole
    bq = math.gcd(s, Q_BLOCK)
    nq = s // bq
    qb = q.reshape(b, nq, bq, g, rep, d).transpose(0, 3, 1, 4, 2, 5)
    qb = qb.reshape(b * g * nq, rep, bq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * g, s, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * g, s, d)

    @jax.checkpoint
    def one(i):
        scores = prec.einsum("rqd,td->rqt", qb[i], kh[i // nq])
        scores = scores / math.sqrt(d)
        qpos = (i % nq) * bq + jnp.arange(bq)
        keep = jnp.arange(s)[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        return prec.einsum("rqt,td->rqd", probs, vh[i // nq])

    ctx = jax.lax.map(one, jnp.arange(b * g * nq))
    ctx = ctx.reshape(b, g, nq, rep, bq, d).transpose(0, 2, 4, 1, 3, 5)
    return prec.mm(ctx.reshape(b, s, n * d), lp["proj_kernel"])


def _by_rows(fn, x):
    """``fn`` over blocks of rows of ``x`` [rows, h], each recomputed in the
    backward pass."""
    rows = x.shape[0]
    block = math.gcd(rows, ROW_BLOCK)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(rows // block, block, -1))
    return out.reshape(rows, -1)


def dense_ffn(m, lp, prec: T.Precision):
    def rows(x):
        y = prec.einsum("rh,hcf->rcf", x, lp["fc1_kernel"])
        return prec.mm(jax.nn.silu(y[:, 0]) * y[:, 1], lp["fc2_kernel"])
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
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return choice, weights * cfg["routed_scaling_factor"]


def expert_ffn(m, lp, cfg: dict, prec: T.Precision, held=None):
    """The held experts' part of the layer's output for ``m`` [T, h]."""
    first, count = held if held is not None else _held(cfg)
    choice, weights = route(m, lp, cfg, prec)

    @jax.checkpoint
    def one(f, args):
        e, w1, w2 = args
        w_e = jnp.sum(jnp.where(choice == first + e, weights, 0.0), axis=-1)
        y = prec.mm(m, w1)
        gate, up = jnp.split(y, 2, axis=-1)
        return f + w_e[:, None] * prec.mm(jax.nn.silu(gate) * up, w2), None

    return jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(count), lp["moe_fc1"], lp["moe_fc2"]))[0]


def layer(x, lp, kind: str, cfg: dict, prec: T.Precision):
    eps = cfg["norm_eps"]
    u = rms_norm(x, lp["ln1_scale"], eps)
    x = x + (short_conv(u, lp, prec) if kind == "conv"
             else attention(u, lp, cfg, prec))
    b, s, h = x.shape
    m = rms_norm(x, lp["ln2_scale"], eps).reshape(b * s, h)
    f = (dense_ffn(m, lp, prec) if "fc1_kernel" in lp
         else expert_ffn(m, lp, cfg, prec))
    return x + f.reshape(b, s, h)


def loss(params, batch, cfg: dict, prec: T.Precision):
    """``batch`` = (tokens [b, s], labels [b, s]), both inside the slice of
    the vocabulary; labels of -1 are left out of the mean."""
    tokens, labels = batch
    word = params["embedding"]["word"]
    x = word[tokens]
    for kind, lp in zip(_kinds(cfg), params["layers"]):
        x = jax.checkpoint(
            lambda x, lp, kind=kind: layer(x, lp, kind, cfg, prec))(x, lp)
    x = rms_norm(x, params["final_ln"]["scale"], cfg["norm_eps"])
    total, count = T.blocked_cross_entropy(
        x.reshape(-1, x.shape[-1]), word, 0.0, labels.reshape(-1), prec)
    return total / count
