"""The DeepSeek-V3 layer as JoyAI-LLM-Flash publishes it (models/hybrid.py's
``mla`` kind over ops/flash_attention.flash_attention_mla, gated experts
beside a gated shared expert, the multi-token-prediction module in
``gpt_loss``) against the plain float32 reference the benchmark keeps
(benchmark/reference/joyai_llm_flash.py), at a toy size on the CPU: one dense
layer, two expert layers and the MTP module, 4 of 16 experts held, seeded
random weights.
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
from apex_tpu.models.config import joyai_llm_flash              # noqa: E402
from apex_tpu.models.transformer_lm import (                    # noqa: E402
    gpt_loss, init_gpt_params, rope_cos_sin)
from benchmark.reference import joyai_llm_flash as ref          # noqa: E402
from benchmark.reference import optim                           # noqa: E402
from benchmark.reference import transformer as T                # noqa: E402

F32 = T.Precision("float32")
B, S = 2, 40


def _toy(experts=16, held=(4, 4), **over):
    """The reference's configuration (the published file's keys) at toy
    widths: hidden 64, 4 heads of 16 + 8 and v 16 through latents of 48 and
    32, dense FFN 128, experts of 32 beside a shared expert, 4 a token."""
    cfg = {
        "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "first_k_dense_replace": 1,
        "n_routed_experts": held[1], "n_shared_experts": 1,
        "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
        "vocab_size": 128, "num_nextn_predict_layers": 1,
        "mtp_loss_weight": 0.3, "rms_norm_eps": 1e-6, "rope_theta": 3.2e7,
        "deployment": {"num_experts_published": experts,
                       "experts_held": list(held)},
    }
    cfg.update(over)
    return cfg


def _program_cfg(cfg, dtype=jnp.float32, **kw):
    skip = ("n_routed_experts", "deployment")
    return joyai_llm_flash(
        **{k: v for k, v in cfg.items() if k not in skip},
        n_routed_experts=cfg["deployment"]["num_experts_published"],
        experts_held=cfg["deployment"]["experts_held"],
        compute_dtype=dtype, remat=True, **kw)


def _batch(cfg, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (B, S + 2)).astype(np.int32)
    return ids[:, :-2], ids[:, 1:-1], ids[:, 2:]


def _program_loss(pcfg, batch):
    tokens, labels, labels2 = batch
    return lambda p: gpt_loss(p, tokens, labels, pcfg, mtp_labels=labels2)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _leaves(tree):
    return [(jax.tree_util.keystr(p), v) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def toy():
    """The default toy configuration with the reference's weights, one
    batch, and the reference's loss and gradients on them (one compile for
    the tests that compare with it)."""
    cfg = _toy()
    params = ref.init_params(jax.random.key(3), cfg)
    batch = _batch(cfg)
    want, want_g = jax.value_and_grad(ref.loss)(params, batch, cfg, F32)
    return cfg, params, batch, want, want_g


def test_trees_match():
    """The reference makes its weights in the program's tree, and the
    program's own draw has the reference's shapes; the MTP module holds
    its two input norms, ``eh_proj``, one expert-layer block and its
    output norm."""
    cfg = _toy()
    mine = jax.eval_shape(lambda k: ref.init_params(k, cfg),
                          jax.random.key(0))
    theirs = jax.eval_shape(
        lambda k: init_gpt_params(k, _program_cfg(cfg)), jax.random.key(0))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(theirs))
    for (name, a), (_, b) in zip(_leaves(mine), _leaves(theirs)):
        assert a.shape == b.shape, name
    assert set(theirs["mtp"]) == {"enorm_scale", "hnorm_scale",
                                  "eh_proj_kernel", "layer", "norm_scale"}
    assert "moe_fc1" in theirs["mtp"]["layer"]
    assert "fc1_kernel" in theirs["layers"][0]
    assert theirs["layers"][1]["shared_fc1_kernel"].shape == (64, 2, 32)


def test_interleaved_rope_is_a_complex_rotation():
    """Pair ``(2i, 2i+1)`` as the complex number ``x_2i + i x_2i+1``
    times ``exp(i pos theta^(-2i/d))``; the program's roll-and-select
    form and the reference's pair form both give it."""
    d, s, theta = 8, 11, 3.2e7
    t = np.random.default_rng(0).standard_normal((2, s, 3, d)).astype(
        np.float32)
    z = t[..., 0::2] + 1j * t[..., 1::2]
    ang = np.arange(s)[:, None] * theta ** (-np.arange(0, d, 2) / d)
    z = z * np.exp(1j * ang)[None, :, None, :]
    want = np.stack([z.real, z.imag], -1).reshape(t.shape)
    got = hybrid.rope_interleaved(jnp.asarray(t), *rope_cos_sin(s, d, theta))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.rope(jnp.asarray(t), theta)),
                               want, atol=1e-5)
    # the one rotary key has no head axis
    got = hybrid.rope_interleaved(jnp.asarray(t[:, :, 0]),
                                  *rope_cos_sin(s, d, theta))
    np.testing.assert_allclose(np.asarray(got), want[:, :, 0], atol=1e-5)


@pytest.mark.parametrize("fused_head", [False, True])
def test_program_matches_reference_float32(fused_head, toy):
    """Loss, both of its terms and every leaf's gradient, float32 on both
    sides, to 1e-5 relative (sums in another order, nothing else)."""
    cfg, params, batch, want, want_g = toy
    pcfg = _program_cfg(cfg, fused_head_ce=fused_head)
    got, got_g = jax.value_and_grad(_program_loss(pcfg, batch))(params)
    assert abs(float(got) - float(want)) / float(want) < 1e-5
    for (name, g), (_, w) in zip(_leaves(got_g), _leaves(want_g)):
        if name.endswith("['router_bias']"):
            # it selects and never weighs: no gradient on either side
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
            continue
        assert _rel(g, w) < 1e-5, name
    _, counters = gpt_loss(params, batch[0], batch[1], pcfg,
                           mtp_labels=batch[2], with_counters=True)
    main, mtp = ref.losses(params, batch, cfg, F32)
    assert abs(float(counters["main_loss"]) - float(main)) < 1e-5
    assert abs(float(counters["mtp_loss"]) - float(mtp)) < 1e-5
    # two expert layers and the module's: 3 x tokens x 4 assignments
    assert float(counters["moe_assignments"]) == 3 * B * S * 4


def test_kernel_route_at_the_published_head_widths(monkeypatch):
    """One block at the kernels' widths (128 + 64, v 128, two heads),
    the flash kernels interpreted, against the reference: the program
    hands the kernels what the reference's equations say."""
    from apex_tpu.ops import flash_attention as fa

    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(fa, "mha_reference", lambda *a, **k: pytest.fail(
        "the published widths took the reference"))
    cfg = _toy(num_hidden_layers=1, num_attention_heads=2,
               qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    params = ref.init_params(jax.random.key(4), cfg)
    batch = _batch(cfg)
    want, want_g = jax.value_and_grad(ref.loss)(params, batch, cfg, F32)
    got, got_g = jax.value_and_grad(
        _program_loss(_program_cfg(cfg), batch))(params)
    assert abs(float(got) - float(want)) / float(want) < 1e-5
    for (name, g), (_, w) in zip(_leaves(got_g), _leaves(want_g)):
        if "router_bias" not in name:
            assert _rel(g, w) < 2e-5, name


def test_program_matches_reference_bfloat16(toy):
    """bfloat16 compute against the float32 reference.  Tolerances as
    tests/test_lfm2_moe.py argues them: a bfloat16 rounding is 0.4% of a
    value and every product of 4 blocks rounds operands and cotangents
    (median leaf 3%, loss 1e-3); a score within rounding of the fourth
    largest flips one token's expert, and at this size that is a tenth of
    an expert's rows (worst leaf 50%, which still fails a leaf that is
    missing or doubled)."""
    cfg, params, batch, want, want_g = toy
    pcfg = _program_cfg(cfg, dtype=jnp.bfloat16)
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    got, got_g = jax.value_and_grad(_program_loss(pcfg, batch))(half)
    assert abs(float(got) - float(want)) / float(want) < 1e-3
    gaps = [_rel(g, w) for (n, g), (_, w) in zip(
        _leaves(got_g), _leaves(want_g)) if "router_bias" not in n]
    assert np.median(gaps) < 0.03, np.median(gaps)
    assert max(gaps) < 0.5, max(gaps)


def _state_from(init, params):
    state = init(jax.random.key_data(jax.random.key(0)))
    # copies: the step donates its state
    return state._replace(
        master_params=jax.tree_util.tree_map(jnp.copy, params),
        params=jax.tree_util.tree_map(
            lambda m, p: m.astype(p.dtype), params, state.params))


def test_three_adam_steps_match_the_reference():
    """The whole float32 train step (``make_gpt_train_step``, O0) from the
    reference's weights, three steps on three batches: every step's loss,
    the first moment after one step (the reference's gradient as Adam
    keeps it) and the parameters' change after three (an element whose
    gradient is all but nought moves by rounding: Adam's first step is
    ``lr x sign(g)``, so the change is compared as a whole leaf)."""
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg = _toy()
    params = ref.init_params(jax.random.key(3), cfg)
    init, step = make_gpt_train_step(
        _program_cfg(cfg, fused_head_ce=True), fused_adam(lr=1e-4), "O0")
    state = _state_from(init, params)
    opt_init, opt_update = optim.adam(lr=1e-4)
    want, want_opt = params, opt_init(params)
    for i in range(3):
        batch = _batch(cfg, seed=i)
        state, metrics = step(state, *batch)
        loss, grads = jax.value_and_grad(ref.loss)(want, batch, cfg, F32)
        want, want_opt = opt_update(grads, want_opt, want)
        assert abs(float(metrics["loss"]) - float(loss)) / float(loss) < 1e-5
        assert {"main_loss", "mtp_loss", "overflow",
                "moe_assignments_held"} <= set(metrics)
        if i == 0:
            for (name, m), (_, w) in zip(
                    _leaves(state.opt_state.exp_avg),
                    _leaves(want_opt["m"])):
                if "router_bias" not in name:
                    assert _rel(m, w) < 1e-5, name
    for (name, new), (_, old), (_, ref_new) in zip(
            _leaves(state.master_params), _leaves(params), _leaves(want)):
        if name.endswith("['router_bias']"):
            assert np.array_equal(np.asarray(new), np.asarray(old))
            continue
        assert _rel(new - old, ref_new - old) < 3e-2, name


def test_o2_step_tracks_the_reference(toy):
    """The O2 step (bfloat16 on a TPU, float16 here) from the reference's
    weights: loss to 2e-3, the first moment's median leaf to 3%: the
    precision's rounding, as the bfloat16 test argues; the two latent
    norms' scales and the module's norms stay float32 in the model's
    copy."""
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg, params, batch, want, grads = toy
    init, step = make_gpt_train_step(
        _program_cfg(cfg, fused_head_ce=True), fused_adam(lr=1e-4), "O2")
    state = _state_from(init, params)
    for name, leaf in _leaves(state.params):
        if "norm" in name:
            assert leaf.dtype == jnp.float32, name
    assert state.params["layers"][1]["q_b_kernel"].dtype != jnp.float32
    state, metrics = step(state, *batch)
    assert not bool(metrics["overflow"])
    assert abs(float(metrics["loss"]) - float(want)) / float(want) < 2e-3
    gaps = [_rel(m, 0.1 * g) for (n, m), (_, g) in zip(
        _leaves(state.opt_state.exp_avg), _leaves(grads))
        if "router_bias" not in n]
    assert np.median(gaps) < 0.03, np.median(gaps)


def test_the_shares_add_up():
    """16 experts in 4 shares of 4: the routed parts of all shares, plus
    the shared expert and the residual counted once, equal the uncut
    layer's output, in the reference (``held``) and in the program
    (``experts_held``) alike, on the same weights."""
    cfg = _toy(held=(0, 16))
    lp = ref.init_params(jax.random.key(5), cfg)["layers"][1]
    x = jax.random.normal(jax.random.key(6), (B, S, 64), jnp.float32)
    m = ref.rms_norm(x, lp["ln2_scale"], 1e-6).reshape(B * S, 64)
    shared = ref.gated_ffn(m, lp["shared_fc1_kernel"],
                           lp["shared_fc2_kernel"], F32)
    whole = ref.ffn(m, lp, cfg, F32)
    parts, program_parts = [], []
    for first in range(0, 16, 4):
        share = dict(lp, moe_fc1=lp["moe_fc1"][first:first + 4],
                     moe_fc2=lp["moe_fc2"][first:first + 4])
        parts.append(ref.routed_experts(m, share, cfg, F32,
                                        held=(first, 4)))
        pcfg = _program_cfg(_toy(held=(first, 4)))
        out, _ = hybrid.expert_layer(pcfg, share, m.reshape(B, S, 64))
        program_parts.append(out.reshape(B * S, 64) - shared)
        assert _rel(out.reshape(B * S, 64), parts[-1] + shared) < 1e-5
    assert _rel(sum(parts) + shared, whole) < 1e-5
    assert _rel(sum(program_parts) + shared, whole) < 1e-5
    # no share is idle: the comparison is of four non-zero parts
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


def test_lambda_nought_is_the_main_loss():
    """``mtp_loss_weight = 0`` reproduces the main loss and leaves the
    MTP module's own leaves without gradient; with it on, the embedding's
    and the head's gradients are the sums of their two uses."""
    cfg = _toy()
    params = ref.init_params(jax.random.key(3), cfg)
    batch = _batch(cfg)
    off = _program_cfg(_toy(mtp_loss_weight=0.0))
    loss, grads = jax.value_and_grad(_program_loss(off, batch))(params)
    main, _ = ref.losses(params, batch, cfg, F32)
    assert abs(float(loss) - float(main)) < 1e-6
    assert all(not np.any(np.asarray(g)) for g in
               jax.tree_util.tree_leaves(grads["mtp"]))

    pcfg = _program_cfg(cfg)

    def term(which):
        def f(p):
            _, c = gpt_loss(p, batch[0], batch[1], pcfg,
                            mtp_labels=batch[2], with_counters=True)
            return c[which]
        return jax.grad(f)(params)

    both = jax.grad(_program_loss(pcfg, batch))(params)
    g_main, g_mtp = term("main_loss"), term("mtp_loss")
    for path in (("embedding", "word"), ("lm_head", "kernel")):
        a, b, c = (g[path[0]][path[1]] for g in (both, g_main, g_mtp))
        assert float(jnp.abs(c).max()) > 0
        assert _rel(a, b + 0.3 * c) < 1e-5, path


def test_the_step_needs_its_third_input():
    cfg = _program_cfg(_toy())
    params = init_gpt_params(jax.random.key(0), cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="mtp_labels"):
        gpt_loss(params, ids, ids, cfg)


def test_config_checks():
    from apex_tpu.models.config import TransformerConfig

    with pytest.raises(ValueError, match="mla"):
        TransformerConfig(layer_types=("mla", "mla"), num_layers=2)
    with pytest.raises(ValueError, match="mtp_layers"):
        TransformerConfig(mtp_layers=1)
    cfg = _program_cfg(_toy())
    assert cfg.layer_types == ("mla",) * 3 and not cfg.mixer_only
    assert cfg.kv_channels == cfg.mla_rope_dim == 8
    assert cfg.moe_shared_expert_size == 32 and cfg.mtp_layers == 1
