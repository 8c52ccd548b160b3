"""Plain float32 Nemotron-H (``model_type`` ``nemotron_h``: one mixer a layer,
a Mamba-2 mixer ``M``, an expert layer ``E`` or grouped-query attention ``*``
by ``hybrid_override_pattern``) with its next-token loss, as ONE CHIP'S SHARE
of an expert-parallel deployment.  Imports nothing from apex_tpu.  The
configuration is the model's own ``config.json`` keys plus ``deployment``.

Every layer is ``x <- x + mixer(RMS(x; g))``, ``RMS(x; g) = x / sqrt(mean(x^2)
+ eps) * g``; ``x`` [s, h]::

    M:  [z | x | B | C | dt] = u W_in      widths d_in | d_in | G N | G N | H
        [x | B | C] = silu(conv([x | B | C]) + b_conv)    depthwise, causal,
                      K taps, the last on the current position, zeros before
        dt = softplus(dt + dt_bias);  A = -exp(A_log)               [H] each
        head j of group g = j // (H / G), state S [P, N] from nought:
            S_t = exp(dt_t A_j) S_{t-1} + dt_t x_t (x) B_t
            y_t = S_t C_t + D_j x_t
        y = RMS_groups(y * silu(z); g_ssm)     G groups of d_in / G channels
        out = y W_out
    E:  r = sigmoid(u W_g)                               [., E] in float32
        S = the k largest of (r + b)             b selects, never weighs
        w_e = r_e / (sum_{e in S} r_e + 1e-20) * routed_scaling_factor
        out = sum_{e in S, e held} w_e relu(u W1_e)^2 W2_e
              + relu(u W1_s)^2 W2_s               the shared expert, no gate
    *:  q, k, v = u W_qkv      (32 query heads over 2 K/V heads of 128)
        out = concat_heads(causal softmax(q k^T / sqrt(128)) v) W_o
    after the last layer: RMS(x; g_out); logits = x W_head^T   (untied head)

The recurrence is a ``lax.scan`` over positions, one at a time: nothing of
the program's chunked algorithm is in it.

Departures from the published model, each stated in the configuration's file
under ``reduced``, ``assumed`` or ``not_held``:

- **the tower**: ``config.json`` defines one tower under next-token training,
  and that is what this is.  The model card speaks of a second (denoiser)
  tower with adaLN, cross-tower conditioning and block-diffusion decoding;
  the config has no key for any of it and nothing of it is here;
- **the share**: ``deployment.experts_held = [first, count]`` of the router's
  ``deployment.num_experts_published`` experts have weights here.  The
  router keeps its published width and ``num_experts_per_tok``; what the
  absent experts would have added is left out, and that partial result goes
  on to the next layer, exactly as in the program.  The shared expert is
  whole on every chip;
- the vocabulary is a slice (``vocab_size`` rows of embedding and head),
  logits and loss over it; depth is cut to the pattern's first layers;
- no position embedding of any kind (``rope_theta`` and
  ``partial_rotary_factor`` are unread: the family uses none);
- assumed, since the config does not say: ``d_in = mamba_num_heads x
  mamba_head_dim`` (not ``expand x hidden_size``), the column order of
  ``W_in``, the gate before the grouped norm, ``1e-20``;
- ``b`` is a seeded constant: it enters only the selection, so its gradient
  is nought, and the rule that updates it is not part of the published
  config.  No auxiliary loss;
- weights are random from the seed: ``A_log = log U(1, 16)``, ``dt_bias``
  the inverse softplus of a log-uniform time step in ``[time_step_min,
  time_step_max]`` floored at ``time_step_floor``, ``D`` about 1, norm
  weights about 1, ``b`` and ``b_conv`` small random values so that nothing
  is multiplied by an exact 0 or 1; output projections narrower by
  ``sqrt(2 L)`` (``rescale_prenorm_residual``) and centred, the router's
  columns and ``b`` centred within each chip's group of experts
  (:func:`_levelled`: random weights lack the balance that training
  keeps, and one chip's share of the assignments swung by a third from
  seed to seed);
- layouts are the program's, so that one tree serves both: the fused QKV
  kernel holds, per K/V head, ``[q x 16 | k | v]`` blocks of 128 columns;
  an expert's ``moe_fc1`` is ``[h, f]``.

A lower ``precision`` (the check's unit and its controls) rounds the
operands of every matrix product, and of the recurrence its ``x``, ``B`` and
``C`` and the cotangent of its ``y``; decays stay float32, as in the
program.

Memory: every layer is recomputed in the backward pass (``jax.checkpoint``),
the Mamba-2 mixer's projections one group of heads at a time and its
recurrence in blocks of positions, attention in blocks of queries, the
experts one at a time and the head in blocks, so that float32 at b1 x s8192
fits one chip beside the optimizer's state.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp

from . import transformer as T

INIT_STD = 0.02
Q_BLOCK = 256
TIME_BLOCK = 16
UNROLL = 4      # positions a loop iteration of the recurrence (speed alone)
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def _kinds(cfg: dict) -> list:
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def _held(cfg: dict) -> tuple:
    first, count = cfg["deployment"]["experts_held"]
    return int(first), int(count)


def _mamba_widths(cfg: dict) -> tuple:
    """``(d_in, G N, H)``."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"],
            cfg["n_groups"] * cfg["ssm_state_size"], cfg["mamba_num_heads"])


def layer_spec(cfg: dict, kind: str) -> dict:
    """Shapes, mean and spread of one layer's normally drawn leaves."""
    h = cfg["hidden_size"]
    std = INIT_STD
    out_std = INIT_STD / math.sqrt(2.0 * cfg["num_hidden_layers"])
    spec = {"ln1_scale": ((h,), 1.0, std)}
    if kind == "mamba":
        d_in, gn, heads = _mamba_widths(cfg)
        taps = cfg["conv_kernel"]
        spec.update(
            ssm_in_kernel=((h, 2 * d_in + 2 * gn + heads), 0.0, std),
            conv_kernel=((d_in + 2 * gn, taps), 0.0, 1.0 / math.sqrt(taps)),
            conv_bias=((d_in + 2 * gn,), 0.0, std),
            ssm_d=((heads,), 1.0, std),
            ssm_norm_scale=((d_in,), 1.0, std),
            ssm_out_kernel=((d_in, h), 0.0, out_std))
    elif kind == "moe":
        f, fs = (cfg["moe_intermediate_size"],
                 cfg["moe_shared_expert_intermediate_size"])
        experts = cfg["deployment"]["num_experts_published"]
        held = _held(cfg)[1]
        spec.update(
            router_kernel=((h, experts), 0.0, std),
            router_bias=((experts,), 0.0, std),
            moe_fc1=((held, h, f), 0.0, std),
            moe_fc2=((held, f, h), 0.0, out_std),
            shared_fc1_kernel=((h, fs), 0.0, std),
            shared_fc2_kernel=((fs, h), 0.0, out_std))
    else:
        n, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
        spec.update(qkv_kernel=((h, (n + 2 * g) * d), 0.0, std),
                    proj_kernel=((n * d, h), 0.0, out_std))
    return spec


def _time_constants(key, cfg: dict) -> dict:
    """``A_log`` and ``dt_bias`` as Mamba-2 draws them."""
    heads = cfg["mamba_num_heads"]
    k_a, k_dt = jax.random.split(key)
    step = jnp.exp(jax.random.uniform(
        k_dt, (heads,), jnp.float32, math.log(cfg["time_step_min"]),
        math.log(cfg["time_step_max"])))
    step = jnp.maximum(step, cfg["time_step_floor"])
    return {
        "ssm_a_log": jnp.log(jax.random.uniform(
            k_a, (heads,), jnp.float32, 1.0, 16.0)),
        # softplus(dt_bias) = step
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step))}


def _levelled(lp: dict, kind: str, cfg: dict) -> dict:
    """The draw's stand-in for the balance that training keeps (the
    published model levels its experts' loads with a bias update whose
    rule is not in ``config.json``).  Plainly drawn, ``relu^2`` and the
    gated ``silu`` have a positive mean, so every output projection adds
    one common direction to the residual stream (15% of a normed input's
    power from the third layer on); random router columns turn it into a
    fixed score offset per expert, and one chip's 8 experts then see 4.2
    to 6.8% of the assignments by seed where balance gives 6.25%.  So:
    output projections are centred over their input index, which keeps
    the common direction out (under 1% of the power), and the router's
    columns and ``b`` are centred within each chip's group of held
    experts, so that what is left of it cancels to first order in every
    chip's share.  Each leaf keeps its spread to within 1/16."""
    lp = dict(lp)
    if kind == "mamba":
        w = lp["ssm_out_kernel"]
        lp["ssm_out_kernel"] = w - jnp.mean(w, axis=0, keepdims=True)
    elif kind == "moe":
        for name, axis in (("moe_fc2", 1), ("shared_fc2_kernel", 0)):
            lp[name] = lp[name] - jnp.mean(lp[name], axis=axis,
                                           keepdims=True)
        count = _held(cfg)[1]
        for name in ("router_kernel", "router_bias"):
            w = lp[name]
            grouped = w.reshape(w.shape[:-1] + (-1, count))
            lp[name] = (grouped - jnp.mean(grouped, axis=-1, keepdims=True)
                        ).reshape(w.shape)
    return lp


def init_params(key, cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    top = T.normal_tree(key, {
        "embedding": {"word": ((v, h), 0.0, INIT_STD)},
        "final_ln": {"scale": ((h,), 1.0, INIT_STD)},
        "lm_head": {"kernel": ((v, h), 0.0, INIT_STD)}})
    top["layers"] = []
    for i, kind in enumerate(_kinds(cfg)):
        k = jax.random.fold_in(key, 1000 + i)
        lp = _levelled(T.normal_tree(k, layer_spec(cfg, kind)), kind, cfg)
        if kind == "mamba":
            lp.update(_time_constants(jax.random.fold_in(k, 99), cfg))
        top["layers"].append(lp)
    return top


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def causal_conv(v, w, bias):
    """``v`` [b, s, c], ``w`` [c, K]: tap ``j`` acts on position ``t - (K-1)
    + j``, zeros before the sequence."""
    taps, s = w.shape[1], v.shape[1]
    v = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, j] * v[:, j:j + s] for j in range(taps)) + bias


def recurrence(x, dt, a, b, c, d):
    """The state-space recurrence, position by position.  ``x`` [bt, s, H,
    P]; ``dt`` [bt, s, H]; ``a``, ``d`` [H]; ``b``, ``c`` [bt, s, G, N].
    The state is kept as [bt, G, H / G, P, N], so that a group's ``B`` and
    ``C`` meet its heads without a copy a head.  The positions go in
    blocks of TIME_BLOCK inside blocks of TIME_BLOCK, each recomputed in
    the backward pass: what is kept of the 8,192 states is one for each
    outer block, then one for each inner block, then TIME_BLOCK states."""
    bt, s, heads, p = x.shape
    g, n = b.shape[2:]
    r = heads // g
    inner = math.gcd(s, TIME_BLOCK)
    outer = math.gcd(s // inner, TIME_BLOCK)

    def one(state, inputs):
        x_t, dt_t, b_t, c_t = inputs      # [bt,g,r,p] [bt,g,r] [bt,g,n] x 2
        decay = jnp.exp(dt_t * a.reshape(g, r))
        state = (state * decay[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, :, None, None, :])
        y_t = jnp.sum(state * c_t[:, :, None, None, :], axis=-1)
        return state, y_t + d.reshape(g, r, 1) * x_t

    @jax.checkpoint
    def positions(state, inputs):
        return jax.lax.scan(one, state, inputs, unroll=UNROLL)

    @jax.checkpoint
    def blocks(state, inputs):
        return jax.lax.scan(positions, state, inputs)

    def by_time(t, *rest):              # [., outer, inner, bt, ...]
        t = t.reshape(bt, s // (outer * inner), outer, inner, *rest)
        return jnp.moveaxis(t, 0, 3)

    _, y = jax.lax.scan(
        blocks, jnp.zeros((bt, g, r, p, n), jnp.float32),
        (by_time(x, g, r, p), by_time(dt, g, r), by_time(b, g, n),
         by_time(c, g, n)))
    return jnp.moveaxis(y.reshape(s, bt, heads, p), 0, 1)


def mamba_mixer(u, lp, cfg: dict, prec: T.Precision):
    """The mixer.  The in-projection with the convolution, and the gated
    norm with the out-projection, go one group of ``H / G`` heads at a time
    (a group's heads read that group's ``B`` and ``C`` alone and the norm
    is over that group's ``d_in / G`` channels, so a group takes its own
    columns of ``W_in``, channels of the convolution and rows of ``W_out``,
    and the groups meet only in the sum that ``W_out`` makes), each
    recomputed in the backward pass; the recurrence between them takes all
    heads at once, so that its one pass over the positions is the only
    one."""
    bt, s, h = u.shape
    d_in, _, heads = _mamba_widths(cfg)
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    r, wide = heads // g, d_in // g
    widths = {"z": wide, "x": wide, "b": n, "c": n, "dt": r}

    def by_group(leaf, names, axis):
        """``leaf``'s ``axis`` holds one section of ``g x width`` a name:
        each as [g, ..., width, ...], the group first."""
        ends = list(itertools.accumulate(g * widths[k] for k in names))
        out = {}
        for k, part in zip(names, jnp.split(leaf, ends[:-1], axis=axis)):
            shape = part.shape[:axis] + (g, widths[k]) + part.shape[axis + 1:]
            out[k] = jnp.moveaxis(part.reshape(shape), axis, 0)
        return out

    w_in = by_group(lp["ssm_in_kernel"], "z x b c dt".split(), 1)

    @jax.checkpoint
    def project(p):
        x, b, c = (jax.nn.silu(causal_conv(
            prec.mm(u, p["w_in"][k]), p["taps"][k], p["bias"][k]))
            for k in "xbc")
        dt = jax.nn.softplus(prec.mm(u, p["w_in"]["dt"]) + p["dt_bias"])
        return x, b, c, dt

    x, b, c, dt = jax.lax.map(project, {
        "w_in": {k: w_in[k] for k in ("x", "b", "c", "dt")},
        "taps": by_group(lp["conv_kernel"], "xbc", 0),
        "bias": by_group(lp["conv_bias"], "xbc", 0),
        "dt_bias": lp["ssm_dt_bias"].reshape(g, r)})  # each [g, bt, s, .]
    # the recurrence's operands, rounded as a product's would be
    x, b, c = (T._round_to(jnp.moveaxis(t, 0, 2), prec.kind)
               for t in (x, b, c))
    y = recurrence(x.reshape(bt, s, heads, -1),
                   jnp.moveaxis(dt, 0, 2).reshape(bt, s, heads),
                   -jnp.exp(lp["ssm_a_log"]), b, c, lp["ssm_d"])
    y = T._round_cotangent(y, prec.kind).reshape(bt, s, g, wide)

    @jax.checkpoint
    def gate_norm_project(out, args):
        y_g, w_z, scale, w_out = args
        y_g = y_g * jax.nn.silu(prec.mm(u, w_z))
        y_g = y_g * scale * jax.lax.rsqrt(
            jnp.mean(jnp.square(y_g), axis=-1, keepdims=True)
            + cfg["norm_eps"])
        return out + prec.mm(y_g, w_out), None

    return jax.lax.scan(
        gate_norm_project, jnp.zeros_like(u),
        (jnp.moveaxis(y, 2, 0), w_in["z"],
         lp["ssm_norm_scale"].reshape(g, wide),
         lp["ssm_out_kernel"].reshape(g, wide, h)))[0]


def attention(u, lp, cfg: dict, prec: T.Precision):
    b, s, _ = u.shape
    n, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    rep = n // g
    qkv = prec.mm(u, lp["qkv_kernel"]).reshape(b, s, g, rep + 2, d)
    q = qkv[..., :rep, :]
    k, v = qkv[..., rep, :], qkv[..., rep + 1, :]
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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


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
    over the held experts."""
    first, count = held if held is not None else _held(cfg)
    choice, weights = route(m, lp, cfg, prec)

    @jax.checkpoint
    def one(f, args):
        e, w1, w2 = args
        w_e = jnp.sum(jnp.where(choice == first + e, weights, 0.0), axis=-1)
        return f + w_e[:, None] * prec.mm(relu2(prec.mm(m, w1)), w2), None

    return jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(count), lp["moe_fc1"], lp["moe_fc2"]))[0]


def shared_expert(m, lp, prec: T.Precision):
    return prec.mm(relu2(prec.mm(m, lp["shared_fc1_kernel"])),
                   lp["shared_fc2_kernel"])


def expert_layer(u, lp, cfg: dict, prec: T.Precision):
    b, s, h = u.shape
    m = u.reshape(b * s, h)
    out = routed_experts(m, lp, cfg, prec) + jax.checkpoint(
        lambda m, lp: shared_expert(m, lp, prec))(m, lp)
    return out.reshape(b, s, h)


MIXERS = {"mamba": mamba_mixer, "moe": expert_layer, "attention": attention}


def layer(x, lp, kind: str, cfg: dict, prec: T.Precision):
    u = rms_norm(x, lp["ln1_scale"], cfg["norm_eps"])
    return x + MIXERS[kind](u, lp, cfg, prec)


def loss(params, batch, cfg: dict, prec: T.Precision):
    """``batch`` = (tokens [b, s], labels [b, s]), both inside the slice of
    the vocabulary; labels of -1 are left out of the mean."""
    tokens, labels = batch
    x = params["embedding"]["word"][tokens]
    for kind, lp in zip(_kinds(cfg), params["layers"]):
        x = jax.checkpoint(
            lambda x, lp, kind=kind: layer(x, lp, kind, cfg, prec))(x, lp)
    x = rms_norm(x, params["final_ln"]["scale"], cfg["norm_eps"])
    total, count = T.blocked_cross_entropy(
        x.reshape(-1, x.shape[-1]), params["lm_head"]["kernel"], 0.0,
        labels.reshape(-1), prec)
    return total / count
