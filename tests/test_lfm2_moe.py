"""The LFM2-MoE hybrid stack (models/hybrid.py, transformer/moe.py's sigmoid
router and held-experts layer) against the plain float32 reference the
benchmark keeps (benchmark/reference/lfm2_moe.py), at a toy size on the CPU:
the same pattern as the benchmark's cell (1 dense + 4 expert layers, one
attention layer in the period), seeded random weights, float32 compute.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.models.config import lfm2_moe                     # noqa: E402
from apex_tpu.models.hybrid import short_conv                   # noqa: E402
from apex_tpu.models.transformer_lm import gpt_loss             # noqa: E402
from apex_tpu.transformer import moe                            # noqa: E402
from benchmark.reference import lfm2_moe as ref                 # noqa: E402
from benchmark.reference import transformer as T                # noqa: E402

F32 = T.Precision("float32")
B, S = 2, 32


def _toy(experts=16, held=(4, 4), **over):
    """The reference's configuration (the published file's keys) at toy
    widths: hidden 64, 4 query heads over 2 K/V heads of 16."""
    cfg = {
        "hidden_size": 64, "num_hidden_layers": 5,
        "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_dense_layers": 1, "num_experts": held[1],
        "num_experts_per_tok": 4, "vocab_size": 128, "conv_L_cache": 3,
        "norm_eps": 1e-5, "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": 1000000},
        "deployment": {"num_experts_published": experts,
                       "experts_held": list(held)},
    }
    cfg.update(over)
    return cfg


def _program_cfg(cfg, dtype=jnp.float32, **kw):
    keys = ("hidden_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "moe_intermediate_size",
            "num_dense_layers", "num_experts_per_tok", "vocab_size",
            "conv_L_cache", "norm_eps")
    return lfm2_moe(
        **{k: cfg[k] for k in keys},
        num_experts=cfg["deployment"]["num_experts_published"],
        experts_held=cfg["deployment"]["experts_held"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
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


def _chunk_of(monkeypatch, rows):
    """Walk every sorted buffer in chunks of ``rows`` rows (the layer and
    its counters both ask ``moe.routing_chunk_rows``)."""
    monkeypatch.setattr(moe, "routing_chunk_rows", lambda n, e_n, g_n: rows)


@pytest.fixture(params=[None, 64], ids=["one_chunk", "chunks_of_64"])
def chunked(request, monkeypatch):
    """The sorted buffer in one piece (the toy's ``B·S·4`` = 256 rows are
    fewer than the chunk its shape gives) and in chunks of 64 rows, each
    under its ``lax.cond``."""
    if request.param:
        _chunk_of(monkeypatch, request.param)
    return request.param


def test_trees_match():
    """The reference makes its weights in the program's tree."""
    from apex_tpu.models.transformer_lm import init_gpt_params

    cfg = _toy()
    mine = jax.eval_shape(lambda k: ref.init_params(k, cfg),
                          jax.random.key(0))
    theirs = jax.eval_shape(
        lambda k: init_gpt_params(k, _program_cfg(cfg)),
        jax.random.key(0))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(theirs))
    for (name, a), (_, b) in zip(_leaves(mine), _leaves(theirs)):
        assert a.shape == b.shape, name


@pytest.mark.parametrize("route", ["reference", "kernel"])
@pytest.mark.parametrize("fused_head", [False, True])
def test_program_matches_reference_float32(route, fused_head, chunked,
                                           monkeypatch):
    """Loss and every leaf's gradient, float32 on both sides, to 1e-5
    relative; on the XLA route and with the Pallas kernels interpreted."""
    monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET",
                       "1" if route == "kernel" else "0")
    cfg = _toy()
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
    """bfloat16 compute against the float32 reference.  Tolerance: a
    bfloat16 rounding is 2^-8 = 0.4% of a value, and activations, weights
    and cotangents are rounded in each of a layer's products over 5
    layers: the median leaf's gradient is off by 1.0-1.3% (seeds 0-3
    here), the loss by under 6e-5, and the limits are 3% and 1e-3.  A
    score within rounding of the fourth largest flips one token's expert;
    at this size an expert sees about 16 rows, so one flip moves that
    layer's expert and router gradients by 10-30% (14-33% measured, and
    the reference's own bfloat16 arithmetic flips just so: 33% on seed
    1).  The worst leaf is therefore held to 50% only, which still fails
    a leaf that is missing or doubled.  The benchmark's check measures the
    same gap at the published size in units of the reference's own
    bfloat16 rounding (grad_noise), flips included."""
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


def test_short_conv_against_a_loop_over_t():
    """The operator alone, forward and gradient, against the sum written
    out position by position."""
    cfg = _toy()
    pcfg = _program_cfg(cfg)
    lp = ref.init_params(jax.random.key(5), cfg)["layers"][0]
    h, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    u = jax.random.normal(jax.random.key(6), (B, S, h), jnp.float32)

    def loop(lp, u):
        bcz = u @ lp["conv_in_kernel"]
        b_, c_, z = bcz[..., :h], bcz[..., h:2 * h], bcz[..., 2 * h:]
        v = b_ * z
        rows = []
        for t in range(S):
            acc = jnp.zeros((B, h), jnp.float32)
            for j in range(taps):
                src = t - (taps - 1) + j
                if src >= 0:
                    acc = acc + lp["conv_kernel"][:, j] * v[:, src]
            rows.append(acc)
        return (c_ * jnp.stack(rows, axis=1)) @ lp["conv_out_kernel"]

    w = jax.random.normal(jax.random.key(7), (B, S, h), jnp.float32)
    got, got_g = jax.value_and_grad(
        lambda lp, u: jnp.vdot(short_conv(pcfg, lp, u), w),
        argnums=(0, 1))(lp, u)
    want, want_g = jax.value_and_grad(
        lambda lp, u: jnp.vdot(loop(lp, u), w), argnums=(0, 1))(lp, u)
    np.testing.assert_allclose(
        short_conv(pcfg, lp, u), loop(lp, u), rtol=1e-5, atol=1e-6)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for name in ("conv_in_kernel", "conv_kernel", "conv_out_kernel"):
        assert _rel(got_g[0][name], want_g[0][name]) < 1e-5, name
    assert _rel(got_g[1], want_g[1]) < 1e-5
    # causal: position t sees nothing after t
    later = u.at[:, S // 2:].set(0.0)
    np.testing.assert_allclose(
        short_conv(pcfg, lp, later)[:, : S // 2],
        short_conv(pcfg, lp, u)[:, : S // 2], rtol=1e-6, atol=1e-7)


def _router_case(seed, experts=64, tokens=256, h=64):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k1, (tokens, h), jnp.float32),
            jax.random.normal(k2, (h, experts), jnp.float32) * 0.2,
            jax.random.normal(k3, (experts,), jnp.float32) * 0.5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_choice_and_weights(seed):
    """The chosen sets are the reference's wherever the fourth and fifth
    largest of ``r + b`` differ by more than 1e-5 (nearer than that a
    float32 product summed in another order may swap them); the weights
    sum to 1 over the four and are the scores, not the biased scores."""
    m, w_g, b = _router_case(seed)
    choice, gates, r = moe._sigmoid_routing(w_g, b, m, 4)
    cfg = _toy(experts=64)
    want_c, want_w = ref.route(
        m, {"router_kernel": w_g, "router_bias": b}, cfg, F32)
    ranked = np.sort(np.asarray(r + b), axis=-1)
    clear = ranked[:, -4] - ranked[:, -5] > 1e-5
    assert clear.mean() > 0.9
    np.testing.assert_array_equal(
        np.sort(np.asarray(choice), -1)[clear],
        np.sort(np.asarray(want_c), -1)[clear])
    np.testing.assert_allclose(np.sum(gates, -1), 1.0, atol=1e-5)
    same = np.all(np.asarray(choice) == np.asarray(want_c), axis=-1)
    np.testing.assert_allclose(np.asarray(gates)[same],
                               np.asarray(want_w)[same], rtol=1e-5)
    picked = np.take_along_axis(np.asarray(r), np.asarray(choice), -1)
    np.testing.assert_allclose(
        gates, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)


def test_a_negative_score_is_never_chosen_twice():
    """``r + b`` can be negative; a taken expert is masked with ``-inf``
    (multiplying its score by 0 would make it the largest again)."""
    scores = -jnp.abs(jax.random.normal(jax.random.key(0), (64, 8))) - 0.1
    choice, picked = moe._topk_routing(scores, 4)
    assert all(len(set(row)) == 4 for row in np.asarray(choice).tolist())
    want = np.sort(np.asarray(scores), -1)[:, ::-1][:, :4]
    np.testing.assert_array_equal(np.asarray(picked), want)
    m, w_g, _ = _router_case(3)
    choice, _, _ = moe._sigmoid_routing(w_g, jnp.full((64,), -5.0), m, 4)
    assert all(len(set(row)) == 4 for row in np.asarray(choice).tolist())


def _expert_layer(seed=0, experts=64, h=64, f=32, tokens=B * S):
    ks = jax.random.split(jax.random.key(seed), 5)
    lp = {"router_kernel": jax.random.normal(ks[0], (h, experts)) * 0.2,
          "router_bias": jax.random.normal(ks[1], (experts,)) * 0.1,
          "moe_fc1": jax.random.normal(ks[2], (experts, h, 2 * f)) * 0.1,
          "moe_fc2": jax.random.normal(ks[3], (experts, f, h)) * 0.1}
    return lp, jax.random.normal(ks[4], (B, tokens // B, h), jnp.float32)


def _share(lp, x, first, count, backend=None):
    params = {"router": lp["router_kernel"],
              "router_bias": lp["router_bias"],
              "fc1": lp["moe_fc1"][first:first + count],
              "fc2": lp["moe_fc2"][first:first + count]}
    return moe.switch_moe_mlp(
        params, x, top_k=4, activation="swiglu", routing="ragged",
        router="sigmoid", experts_held=(first, count), gmm_backend=backend)


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_the_eight_shares_add_up_to_the_uncut_layer(backend, chunked):
    """Experts 8i .. 8i+7 for i = 0..7: the shares' outputs sum to what the
    uncut reference gives for the whole layer, and so do the gradients of
    the input, the router and each share's own experts."""
    lp, x = _expert_layer()
    cfg = _toy(experts=64, held=(0, 64))
    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)

    def uncut(lp, x):
        m = x.reshape(-1, x.shape[-1])
        return ref.expert_ffn(m, lp, cfg, F32).reshape(x.shape)

    def shares(lp, x):
        return sum(_share(lp, x, 8 * i, 8, backend).out for i in range(8))

    np.testing.assert_allclose(shares(lp, x), uncut(lp, x),
                               rtol=1e-4, atol=1e-5)
    got = jax.grad(lambda lp, x: jnp.vdot(shares(lp, x), w),
                   argnums=(0, 1))(lp, x)
    want = jax.grad(lambda lp, x: jnp.vdot(uncut(lp, x), w),
                    argnums=(0, 1))(lp, x)
    for name in ("router_kernel", "moe_fc1", "moe_fc2"):
        assert _rel(got[0][name], want[0][name]) < 1e-5, name
    assert _rel(got[1], want[1]) < 1e-5
    load = _share(lp, x, 0, 8, backend).expert_load
    assert float(jnp.sum(load)) == x.shape[0] * x.shape[1] * 4


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_all_experts_absent_gives_zero_and_a_finite_gradient(backend,
                                                             chunked):
    """A bias of -10 on the held experts sends every token's four choices
    to absent ones: the share's output is exactly 0 and every gradient is
    finite (0 for the experts, which saw no row)."""
    lp, x = _expert_layer(1)
    lp["router_bias"] = lp["router_bias"].at[:8].set(-10.0)
    out = _share(lp, x, 0, 8, backend)
    assert not np.any(np.asarray(out.out))
    assert float(jnp.sum(out.expert_load[:8])) == 0
    grads = jax.grad(lambda lp, x: jnp.sum(_share(lp, x, 0, 8, backend).out
                                           ** 2), argnums=(0, 1))(lp, x)
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf)))
    assert not np.any(np.asarray(grads[0]["moe_fc1"]))


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_all_tokens_on_one_held_expert_drops_none(backend, chunked):
    """The worst imbalance: a bias of +10 puts expert 3 among every
    token's four.  Its segment is then all T rows of the buffer, and the
    share still equals the reference's for the same share."""
    lp, x = _expert_layer(2)
    lp["router_bias"] = lp["router_bias"].at[3].set(10.0)
    out = _share(lp, x, 0, 8, backend)
    tokens = x.shape[0] * x.shape[1]
    assert float(out.expert_load[3]) == tokens
    assert float(out.dropped_fraction) == 0.0
    cfg = _toy(experts=64, held=(0, 8))
    held = {**lp, "moe_fc1": lp["moe_fc1"][:8], "moe_fc2": lp["moe_fc2"][:8]}
    want = ref.expert_ffn(x.reshape(tokens, -1), held, cfg, F32)
    np.testing.assert_allclose(out.out.reshape(tokens, -1), want,
                               rtol=1e-4, atol=1e-5)


def test_counters_come_out_of_the_train_step(chunked):
    """The step's own outputs carry the expert layers' counters, summed
    over the four expert layers."""
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
    assert float(m["moe_assignments"]) == 4 * B * S * 4
    assert 0 < float(m["moe_assignments_held"]) < float(m["moe_assignments"])
    assert float(m["moe_held_load_max"]) >= float(m["moe_held_load_mean"])
    assert np.isclose(4 * float(m["moe_held_load_mean"]),
                      float(m["moe_assignments_held"]))
    # the live chunks' rows: the whole buffer of each of the four layers
    # in one chunk, else whole chunks of 64 that cover the held rows
    routed, held = float(m["moe_routed_rows"]), float(
        m["moe_assignments_held"])
    if chunked is None:
        assert routed == 4 * B * S * 4
    else:
        assert routed % chunked == 0 and held <= routed < held + 4 * chunked
    assert all(np.isfinite(losses)) and not bool(m["overflow"])


def _cell_shape(name):
    """``(n, E, G)`` of a benchmark cell's expert layers: the rows of the
    routing buffer (``T·k``), the router's width and the experts held."""
    def read(*parts):
        with open(os.path.join(ROOT, "benchmark", *parts)) as f:
            return json.load(f)

    cell = read("workloads", name + ".json")
    cfg = read("configs", cell["config"] + ".json")
    held = cfg["deployment"]
    return (cell["batch"] * cell["seq"] * cfg["num_experts_per_tok"],
            held["num_experts_published"], held["experts_held"][1])


def test_the_chunk_comes_from_the_shape():
    """The cell's buffer, 2 x 8192 x 4 = 65,536 rows of which 8 of 64
    experts get 8,192 when balanced, goes in chunks of twice that; every
    expert held, or a buffer within the chunk, is one chunk; the chunk is a
    multiple of 512 rows that divides the buffer, else the whole of it."""
    n, e_n, g_n = _cell_shape("lfm2_24b_a2b_ep8_train_b2s8192")
    assert (n, e_n, g_n) == (65536, 64, 8)
    assert moe.routing_chunk_rows(n, e_n, g_n) == 16384
    assert moe.routing_chunk_rows(n, e_n, e_n) == n
    assert moe.routing_chunk_rows(B * S * 4, 64, 8) == B * S * 4
    # 1,920 rows wanted: 2,048 does not divide 7,680, 2,560 does
    assert moe.routing_chunk_rows(7680, 64, 8) == 2560
    # 896 wanted, and no multiple of 512 below 3,584 divides it
    assert moe.routing_chunk_rows(3584, 64, 8) == 3584


def test_routed_rows_count_the_live_chunks(monkeypatch):
    """One layer's ``moe_routed_rows`` is ``ceil(held / chunk) · chunk``
    with the chunk its shape gives, the whole buffer where that is one
    chunk."""
    from apex_tpu.models.hybrid import moe_counters

    pcfg = _program_cfg(_toy(experts=64, held=(8, 8)))
    load = jnp.zeros(64, jnp.int32).at[8:16].set(
        jnp.array([90, 0, 7, 30, 1, 0, 12, 5]))       # 145 held rows
    tokens = 64                                        # 256 rows: one chunk
    assert float(moe_counters(pcfg, load, tokens)["moe_routed_rows"]) == 256
    _chunk_of(monkeypatch, 64)
    assert float(moe_counters(pcfg, load, tokens)["moe_routed_rows"]) == 192
    _chunk_of(monkeypatch, 32)
    assert float(moe_counters(pcfg, load, tokens)["moe_routed_rows"]) == 160
    assert float(moe_counters(pcfg, load * 0, tokens)["moe_routed_rows"]) == 0


def _crowded(live):
    """The toy expert layer with a bias of +10 on ``live - 1`` held
    experts: each is among every token's four, so that the held rows of
    the 256-row buffer pass ``live - 1`` chunks of 64."""
    lp, x = _expert_layer(2)
    lp["router_bias"] = lp["router_bias"].at[
        jnp.array([3, 5][:live - 1])].set(10.0)
    return lp, x


def _share_and_grads(lp, x, backend):
    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)
    out = _share(lp, x, 0, 8, backend)
    grads = jax.grad(lambda lp, x: jnp.vdot(
        _share(lp, x, 0, 8, backend).out, w), argnums=(0, 1))(lp, x)
    return out, grads, w


@pytest.mark.parametrize("backend", ["reference", "kernel"])
@pytest.mark.parametrize("live", [2, 3])
def test_held_rows_past_one_chunk_match_the_reference(backend, live,
                                                      monkeypatch):
    """Held rows in two and in three live chunks of 64: the output and the
    gradients of the input, the router and the held experts are the
    reference's for the same share; no row is dropped."""
    _chunk_of(monkeypatch, 64)
    lp, x = _crowded(live)
    out, got, w = _share_and_grads(lp, x, backend)
    assert math.ceil(float(jnp.sum(out.expert_load[:8])) / 64) == live
    tokens = x.shape[0] * x.shape[1]
    cfg = _toy(experts=64, held=(0, 8))

    def want(lp, x):
        held = {**lp, "moe_fc1": lp["moe_fc1"][:8],
                "moe_fc2": lp["moe_fc2"][:8]}
        return ref.expert_ffn(x.reshape(tokens, -1), held, cfg,
                              F32).reshape(x.shape)

    np.testing.assert_allclose(out.out, want(lp, x), rtol=1e-4, atol=1e-5)
    exp = jax.grad(lambda lp, x: jnp.vdot(want(lp, x), w),
                   argnums=(0, 1))(lp, x)
    for name in ("router_kernel", "moe_fc1", "moe_fc2"):
        assert _rel(got[0][name], exp[0][name]) < 1e-5, name
    assert _rel(got[1], exp[1]) < 1e-5


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_one_chunk_and_three_chunks_agree(backend, monkeypatch):
    """The same held rows walked as one 256-row chunk and as three live
    chunks of 64 (and one skipped) give the same output and the same
    router gradients to the last bit at float32: every chunk scatter-adds
    into the carried output in the buffer's order.  The gradients of the
    input and of the held experts pass through the grouped products'
    transposes, which the CPU compiles for 64 rows otherwise than for 256:
    they agree to float32 rounding (measured 6e-8 and 7.2e-7 at most, on
    entries of order 1)."""
    lp, x = _crowded(3)
    one = _share_and_grads(lp, x, backend)[:2]
    _chunk_of(monkeypatch, 64)
    many = _share_and_grads(lp, x, backend)[:2]
    for (name, a), (_, b) in zip(_leaves(one), _leaves(many)):
        if "router" in name or name.startswith("[0]"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-6,
                                       err_msg=name)
