"""The Nemotron-H hybrid stack (models/hybrid.py's single mixers: the Mamba-2
mixer over ops/ssd_scan.py, the relu2 expert layer with its shared expert,
GQA attention without positions) against the plain float32 reference the
benchmark keeps (benchmark/reference/nemotron_h.py), at a toy size on the
CPU: the benchmark cell's pattern (``EMEMEM*``, 4 of 16 experts held),
seeded random weights, float32 compute.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models import hybrid                              # noqa: E402
from apex_tpu.models.config import TransformerConfig, nemotron_h  # noqa: E402
from apex_tpu.models.transformer_lm import (                    # noqa: E402
    gpt_loss, init_gpt_params)
from apex_tpu.ops.ssd_scan import ssd_scan                      # noqa: E402
from apex_tpu.transformer import moe                            # noqa: E402
from benchmark.reference import nemotron_h as ref               # noqa: E402
from benchmark.reference import optim                           # noqa: E402
from benchmark.reference import transformer as T                # noqa: E402

F32 = T.Precision("float32")
B, S = 2, 40            # 40 positions: no multiple of the toy chunk of 16
PATTERN = "EMEMEM*"


def _toy(pattern=PATTERN, experts=16, held=(4, 4), **over):
    """The reference's configuration (the published file's keys) at toy
    widths: hidden 64, 8 Mamba heads of 8 in 2 groups with a state of 16,
    4 query heads over 2 K/V heads of 16, experts of 32 beside a shared
    expert of 48, 6 a token."""
    cfg = {
        "hidden_size": 64, "num_hidden_layers": len(pattern),
        "hybrid_override_pattern": pattern,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48,
        "n_routed_experts": held[1], "num_experts_per_tok": 6,
        "routed_scaling_factor": 2.5, "vocab_size": 128, "norm_eps": 1e-5,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4,
        "deployment": {"num_experts_published": experts,
                       "experts_held": list(held)},
    }
    cfg.update(over)
    return cfg


def _program_cfg(cfg, dtype=jnp.float32, **kw):
    skip = ("n_routed_experts", "deployment")
    return nemotron_h(
        **{k: v for k, v in cfg.items() if k not in skip},
        n_routed_experts=cfg["deployment"]["num_experts_published"],
        experts_held=cfg["deployment"]["experts_held"],
        compute_dtype=dtype, remat=True, **kw)


def _batch(cfg, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (B, S + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _leaves(tree):
    return [(jax.tree_util.keystr(p), v) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_trees_match():
    """The reference makes its weights in the program's tree, and the
    program's own draw has the reference's shapes: one norm a layer, no
    second one, no FFN beside an operator."""
    cfg = _toy()
    mine = jax.eval_shape(lambda k: ref.init_params(k, cfg),
                          jax.random.key(0))
    theirs = jax.eval_shape(
        lambda k: init_gpt_params(k, _program_cfg(cfg)), jax.random.key(0))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(theirs))
    for (name, a), (_, b) in zip(_leaves(mine), _leaves(theirs)):
        assert a.shape == b.shape, name
    assert not any("ln2" in n or n.endswith("]['fc1_kernel']")
                   for n, _ in _leaves(theirs))


def test_the_programs_own_draw_of_the_time_constants():
    """``softplus(dt_bias)`` lies in [time_step_min, time_step_max], ``A =
    -exp(A_log)`` in [-16, -1], ``D`` is 1."""
    params = init_gpt_params(jax.random.key(1), _program_cfg(_toy("M")))
    lp = params["layers"][0]
    step = np.asarray(jax.nn.softplus(lp["ssm_dt_bias"]))
    assert np.all(step >= 1e-3 * 0.999) and np.all(step <= 0.1 * 1.001)
    a = -np.exp(np.asarray(lp["ssm_a_log"]))
    assert np.all(a <= -1.0) and np.all(a >= -16.0)
    assert np.all(np.asarray(lp["ssm_d"]) == 1.0)


@pytest.mark.parametrize("pattern,fused_head,route", [
    ("M", False, "reference"), ("M", False, "kernel"),
    ("E", False, "reference"), ("E", False, "kernel"),
    ("M*", False, "reference"), ("M*", False, "kernel"),
    (PATTERN, False, "reference"), (PATTERN, True, "kernel")])
def test_program_matches_reference_float32(pattern, fused_head, route,
                                           monkeypatch):
    """Loss and every leaf's gradient, float32 on both sides, to 1e-5
    relative: each kind of layer (attention behind a Mamba-2 mixer: a
    pattern has an M or an E) and the cell's stack; on the XLA
    route and with the Pallas kernels interpreted."""
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET",
                       "1" if route == "kernel" else "0")
    cfg = _toy(pattern)
    params = ref.init_params(jax.random.key(3), cfg)
    batch = _batch(cfg)
    want, want_g = jax.value_and_grad(ref.loss)(params, batch, cfg, F32)
    pcfg = _program_cfg(cfg, fused_head_ce=fused_head)
    got, got_g = jax.value_and_grad(
        lambda p: gpt_loss(p, *batch, pcfg))(params)
    assert abs(float(got) - float(want)) / float(want) < 1e-5
    for (name, g), (_, w) in zip(_leaves(got_g), _leaves(want_g)):
        if name.endswith("['router_bias']"):
            # it selects and never weighs: no gradient on either side
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
            continue
        assert _rel(g, w) < 1e-5, name


def test_program_matches_reference_bfloat16():
    """bfloat16 compute against the float32 reference.  Tolerances as
    tests/test_lfm2_moe.py argues them: a bfloat16 rounding is 0.4% of a
    value and every product of 7 layers rounds operands and cotangents
    (median leaf 3%, loss 1e-3); a score within rounding of the sixth
    largest flips one token's expert, and at this size that is a tenth of
    an expert's rows (worst leaf 50%, which still fails a leaf that is
    missing or doubled)."""
    cfg = _toy()
    params = ref.init_params(jax.random.key(3), cfg)
    batch = _batch(cfg)
    want, want_g = jax.value_and_grad(ref.loss)(params, batch, cfg, F32)
    pcfg = _program_cfg(cfg, dtype=jnp.bfloat16)
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    got, got_g = jax.value_and_grad(
        lambda p: gpt_loss(p, *batch, pcfg))(half)
    assert abs(float(got) - float(want)) / float(want) < 1e-3
    gaps = [_rel(g, w) for (n, g), (_, w) in zip(
        _leaves(got_g), _leaves(want_g)) if "router_bias" not in n]
    assert np.median(gaps) < 0.03, np.median(gaps)
    assert max(gaps) < 0.5, max(gaps)


def test_one_adam_step_matches_the_reference():
    """The whole float32 train step (``make_gpt_train_step``, O0) from the
    reference's weights: its first moment is the reference's gradient as
    Adam keeps it, and the parameters move as the reference's Adam moves
    them (an element whose gradient is all but nought moves by rounding:
    Adam's first step is ``lr x sign(g)``, so the change is compared as a
    whole leaf)."""
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg = _toy()
    params = ref.init_params(jax.random.key(3), cfg)
    batch = _batch(cfg)
    init, step = make_gpt_train_step(
        _program_cfg(cfg, fused_head_ce=True), fused_adam(lr=1e-4), "O0")
    state = init(jax.random.key_data(jax.random.key(0)))
    # copies: the step donates its state
    state = state._replace(
        master_params=jax.tree_util.tree_map(jnp.copy, params),
        params=jax.tree_util.tree_map(jnp.copy, params))
    state, metrics = step(state, *batch)

    opt_init, opt_update = optim.adam(lr=1e-4)
    loss, grads = jax.value_and_grad(ref.loss)(params, batch, cfg, F32)
    want, want_opt = opt_update(grads, opt_init(params), params)
    assert abs(float(metrics["loss"]) - float(loss)) / float(loss) < 1e-5
    moved = 0
    for (name, m), (_, w), (_, new), (_, old), (_, ref_new) in zip(
            _leaves(state.opt_state.exp_avg), _leaves(want_opt["m"]),
            _leaves(state.master_params), _leaves(params), _leaves(want)):
        if name.endswith("['router_bias']"):
            assert np.array_equal(np.asarray(new), np.asarray(old))
            continue
        assert _rel(m, w) < 1e-5, name
        assert _rel(new - old, ref_new - old) < 2e-2, name
        moved += 1
    assert moved == len(_leaves(params)) - 3      # three expert layers


def _scan_case(seed, s=S, bt=2, heads=8, p=4, g=2, n=16):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (bt, s, heads, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (bt, s, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.7)),
            jax.random.normal(ks[3], (bt, s, g, n)),
            jax.random.normal(ks[4], (bt, s, g, n)),
            jax.random.normal(ks[5], (heads,))), jax.random.normal(
                ks[6], (bt, s, heads, p))


@pytest.mark.parametrize("chunk", [16, 64])
def test_ssd_scan_against_the_recurrence(chunk):
    """The chunked scan against the recurrence taken position by position
    (the reference's), forward and the gradients of x, dt, A, B, C and D,
    at 40 positions: two chunks of 16 and a padded third, or one padded
    chunk of 64.  The answers do not depend on the chunk."""
    args, w = _scan_case(0)
    want = ref.recurrence(*args)
    got = ssd_scan(*args, chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    got_g = jax.grad(lambda *a: jnp.vdot(ssd_scan(*a, chunk=chunk), w),
                     argnums=range(6))(*args)
    want_g = jax.grad(lambda *a: jnp.vdot(ref.recurrence(*a), w),
                      argnums=range(6))(*args)
    for name, g, wg in zip(("x", "dt", "A", "B", "C", "D"), got_g, want_g):
        assert _rel(g, wg) < 1e-5, name


def test_ssd_scan_is_causal_and_carries_its_state():
    """Position t sees nothing after t; a strong decay forgets, a weak one
    carries the first chunk's input into the last."""
    (x, dt, a, b, c, d), _ = _scan_case(1, s=64)
    y = ssd_scan(x, dt, a, b, c, d, chunk=16)
    later = ssd_scan(x.at[:, 32:].set(0.0), dt, a, b, c, d, chunk=16)
    np.testing.assert_allclose(later[:, :32], y[:, :32], rtol=1e-6,
                               atol=1e-6)
    first = x.at[:, 16:].set(0.0)
    d0 = jnp.zeros_like(d)
    kept = ssd_scan(first, dt, a * 1e-3, b, c, d0, chunk=16)
    lost = ssd_scan(first, dt, a * 1e3, b, c, d0, chunk=16)
    assert float(jnp.abs(kept[:, 48:]).max()) > 1e-2
    assert float(jnp.abs(lost[:, 48:]).max()) < 1e-6


def test_ssd_scan_rejects_heads_that_do_not_fill_groups():
    (x, dt, a, b, c, d), _ = _scan_case(2)
    with pytest.raises(ValueError, match="groups"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, 2), c, d)


def test_mamba_mixer_is_causal():
    cfg = _toy("M")
    pcfg = _program_cfg(cfg)
    lp = ref.init_params(jax.random.key(5), cfg)["layers"][0]
    u = jax.random.normal(jax.random.key(6), (B, S, 64), jnp.float32)
    y = hybrid.mamba_mixer(pcfg, lp, u)
    later = hybrid.mamba_mixer(pcfg, lp, u.at[:, S // 2:].set(0.0))
    assert y.shape == u.shape
    np.testing.assert_allclose(later[:, : S // 2], y[:, : S // 2],
                               rtol=1e-5, atol=1e-6)


def test_causal_taps_with_a_bias_against_a_loop_over_t():
    v = jax.random.normal(jax.random.key(0), (B, 9, 6), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (6, 4), jnp.float32)
    bias = jax.random.normal(jax.random.key(2), (6,), jnp.float32)
    want = np.zeros((B, 9, 6), np.float32)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w[:, j] * v[:, t - 3 + j])
    np.testing.assert_allclose(hybrid.causal_taps(v, w, bias),
                               want + np.asarray(bias), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(hybrid.causal_taps(v, w), want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(ref.causal_conv(v, w, bias),
                               want + np.asarray(bias), rtol=1e-5,
                               atol=1e-6)


def _router_case(seed, experts=128, tokens=256, h=64):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k1, (tokens, h), jnp.float32),
            jax.random.normal(k2, (h, experts), jnp.float32) * 0.2,
            jax.random.normal(k3, (experts,), jnp.float32) * 0.5)


@pytest.mark.parametrize("seed", [0, 1])
def test_router_gates_are_scaled_scores(seed):
    """6 of 128: the chosen sets are the reference's wherever the sixth
    and seventh largest of ``r + b`` are clear of one another; the gates
    are the scores (not the biased scores) over their sum, times 2.5."""
    m, w_g, b = _router_case(seed)
    choice, gates, r = moe._sigmoid_routing(w_g, b, m, 6, 2.5, 1e-20)
    want_c, want_w = ref.route(
        m, {"router_kernel": w_g, "router_bias": b},
        _toy(experts=128), F32)
    ranked = np.sort(np.asarray(r + b), axis=-1)
    clear = ranked[:, -6] - ranked[:, -7] > 1e-5
    assert clear.mean() > 0.9
    np.testing.assert_array_equal(
        np.sort(np.asarray(choice), -1)[clear],
        np.sort(np.asarray(want_c), -1)[clear])
    np.testing.assert_allclose(np.sum(gates, -1), 2.5, rtol=1e-5)
    same = np.all(np.asarray(choice) == np.asarray(want_c), axis=-1)
    np.testing.assert_allclose(np.asarray(gates)[same],
                               np.asarray(want_w)[same], rtol=1e-5)
    picked = np.take_along_axis(np.asarray(r), np.asarray(choice), -1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)


def _expert_layer(seed=0, experts=32, h=64, f=32, fs=48):
    ks = jax.random.split(jax.random.key(seed), 7)
    lp = {"router_kernel": jax.random.normal(ks[0], (h, experts)) * 0.2,
          "router_bias": jax.random.normal(ks[1], (experts,)) * 0.1,
          "moe_fc1": jax.random.normal(ks[2], (experts, h, f)) * 0.1,
          "moe_fc2": jax.random.normal(ks[3], (experts, f, h)) * 0.1,
          "shared_fc1_kernel": jax.random.normal(ks[4], (h, fs)) * 0.1,
          "shared_fc2_kernel": jax.random.normal(ks[5], (fs, h)) * 0.1}
    return lp, jax.random.normal(ks[6], (B, S, h), jnp.float32)


def _share_cfg(first, count, experts=32):
    return _program_cfg(_toy("E", experts=experts, held=(first, count)))


def _share_lp(lp, first, count):
    return {**lp, "moe_fc1": lp["moe_fc1"][first:first + count],
            "moe_fc2": lp["moe_fc2"][first:first + count]}


def _routed(lp, x, first, count):
    params = {"router": lp["router_kernel"],
              "router_bias": lp["router_bias"],
              "fc1": lp["moe_fc1"][first:first + count],
              "fc2": lp["moe_fc2"][first:first + count]}
    return moe.switch_moe_mlp(
        params, x, top_k=6, activation="relu2", routing="ragged",
        router="sigmoid", experts_held=(first, count),
        routed_scaling=2.5, gate_epsilon=1e-20)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Experts 2i, 2i+1 for i = 0..15 (16 chips share the layer): the
    shares' routed parts plus the shared expert, which every chip computes
    alike, counted ONCE, add up to what the uncut reference gives for the
    whole layer, and so do the gradients of the input, the router, each
    share's own experts and the shared expert.  (The grouped kernels'
    route through a held-experts layer: tests/test_lfm2_moe.py's eight
    shares, and the ``E`` case above.)"""
    lp, x = _expert_layer()
    cfg = _toy("E", experts=32, held=(0, 32))
    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)

    def uncut(lp, x):
        return ref.expert_layer(x, lp, cfg, F32)

    def shares(lp, x):
        routed = sum(_routed(lp, x, 2 * i, 2).out for i in range(16))
        # chip 0's whole layer less its routed part: the shared expert as
        # the program computes it
        whole = hybrid.expert_layer(
            _share_cfg(0, 2), _share_lp(lp, 0, 2), x)[0]
        return routed + (whole - _routed(lp, x, 0, 2).out)

    np.testing.assert_allclose(shares(lp, x), uncut(lp, x),
                               rtol=1e-4, atol=1e-5)
    got = jax.grad(lambda lp, x: jnp.vdot(shares(lp, x), w),
                   argnums=(0, 1))(lp, x)
    want = jax.grad(lambda lp, x: jnp.vdot(uncut(lp, x), w),
                    argnums=(0, 1))(lp, x)
    for name in ("router_kernel", "moe_fc1", "moe_fc2",
                 "shared_fc1_kernel", "shared_fc2_kernel"):
        assert _rel(got[0][name], want[0][name]) < 1e-5, name
    assert _rel(got[1], want[1]) < 1e-5
    load = _routed(lp, x, 0, 2).expert_load
    assert float(jnp.sum(load)) == B * S * 6


def test_every_share_adds_the_same_shared_expert():
    """A chip whose experts no token chose still gives the shared
    expert's part, and that part does not depend on which experts are
    held."""
    lp, x = _expert_layer(1)
    lp["router_bias"] = lp["router_bias"].at[:2].set(-10.0)
    only_shared = hybrid.expert_layer(
            _share_cfg(0, 2), _share_lp(lp, 0, 2), x)[0]
    want = ref.shared_expert(x.reshape(B * S, -1), lp, F32)
    np.testing.assert_allclose(only_shared.reshape(B * S, -1), want,
                               rtol=1e-4, atol=1e-5)
    other = hybrid.expert_layer(
        _share_cfg(8, 2), _share_lp(lp, 8, 2), x)[0]
    routed = _routed(lp, x, 8, 2).out
    np.testing.assert_allclose(other - routed, only_shared, rtol=1e-4,
                               atol=1e-5)


def test_counters_come_out_of_the_train_step():
    """The O2 step's own outputs carry the expert layers' counters, summed
    over the three expert layers; the losses are finite."""
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.models.hybrid import MOE_COUNTERS
    from apex_tpu.optimizers import fused_adam

    cfg = _toy()
    init, step = make_gpt_train_step(
        _program_cfg(cfg, dtype=jnp.bfloat16, fused_head_ce=True),
        fused_adam(lr=1e-4), "O2")
    state = init(jax.random.key_data(jax.random.key(0)))
    losses = []
    for i in range(3):
        state, m = step(state, *_batch(cfg, i))
        losses.append(float(m["loss"]))
    assert set(MOE_COUNTERS) <= set(m)
    assert float(m["moe_assignments"]) == 3 * B * S * 6
    assert 0 < float(m["moe_assignments_held"]) < float(m["moe_assignments"])
    assert np.isclose(4 * float(m["moe_held_load_mean"]),
                      float(m["moe_assignments_held"]))
    assert all(np.isfinite(losses)) and not bool(m["overflow"])


def test_o2_keeps_the_time_constants_float32():
    """Under O2 the model's copy of ``A_log``, ``dt_bias`` and ``D`` stays
    float32 and equal to the masters, as the norms' scales do; the
    projections beside them are half precision (bfloat16 on a TPU)."""
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    init, _ = make_gpt_train_step(
        _program_cfg(_toy(), dtype=jnp.bfloat16), fused_adam(lr=1e-4), "O2")
    state = init(jax.random.key_data(jax.random.key(0)))
    mamba = [(lp, mp) for lp, mp in zip(state.params["layers"],
                                        state.master_params["layers"])
             if "ssm_a_log" in lp]
    assert mamba
    for lp, mp in mamba:
        for name in ("ssm_a_log", "ssm_dt_bias", "ssm_d", "ssm_norm_scale"):
            assert lp[name].dtype == jnp.float32, name
            np.testing.assert_array_equal(lp[name], mp[name])
        assert lp["ssm_in_kernel"].dtype.itemsize == 2
        assert lp["conv_bias"].dtype.itemsize == 2


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("mamba", "attention"), num_layers=2, num_experts=4,
          moe_routing="ragged", mamba_num_heads=8), "single mixers"),
    (dict(layer_types=("mamba",), num_layers=1,
          mamba_num_heads=6, ssm_groups=4), "ssm_groups"),
    (dict(layer_types=("mamba", "conv"), num_layers=2, mamba_num_heads=8),
     "single mixers"),
    (dict(layer_types=("moe",), num_layers=1), "single mixers"),
    (dict(activation="relu2"), "hybrid"),
    (dict(position_embedding_type="none"), "hybrid"),
    (dict(layer_types=("moe",), num_layers=1,
          num_experts=4, moe_routing="ragged", activation="gelu",
          moe_shared_expert_size=8), "shared expert"),
], ids=["experts_need_moe_layer", "heads_fill_groups", "no_conv_mixer",
        "moe_needs_experts", "relu2_is_hybrid", "no_positions_is_hybrid",
        "shared_needs_relu2_or_swiglu"])
def test_config_refuses(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**kw)


def test_pattern_must_fit_the_depth():
    cfg = _toy()
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        _program_cfg({**cfg, "num_hidden_layers": 3})
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        _program_cfg({**cfg, "hybrid_override_pattern": "EMEMEMX"})
    with pytest.raises(ValueError, match="single mixers"):
        _program_cfg(_toy("**"))


def test_relu2_needs_the_ragged_path():
    lp, x = _expert_layer(2, experts=4)
    with pytest.raises(ValueError, match="relu2"):
        moe.switch_moe_mlp(
            {"router": lp["router_kernel"], "fc1": lp["moe_fc1"],
             "fc2": lp["moe_fc2"]}, x, activation="relu2")
