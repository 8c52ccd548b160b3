"""Flagship GPT model tests.

Reference analogs: tests/L0/run_transformer/run_gpt_minimal_test.py and
test_pipeline_parallel_fwd_bwd.py — loss/grad parity of the parallel model
against a sequential single-device run of the same params.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.models import (
    TransformerConfig,
    gpt_pipeline_loss_and_grads,
    gpt_forward,
    gpt_loss,
    gpt_param_specs,
    gspmd_ctx,
    init_gpt_params,
    make_gpt_pipeline_stage,
    make_gpt_train_step,
    manual_ctx,
    pipeline_packet,
    stack_pipeline_params,
)
from apex_tpu.optimizers import fused_adam
from apex_tpu.parallel.mesh import create_mesh
from apex_tpu.transformer.pipeline_parallel import (
    forward_backward_pipelining_without_interleaving,
)

shard_map = jax.shard_map


def tiny_cfg(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_position_embeddings", 32)
    kw.setdefault("compute_dtype", jnp.float32)   # exact parity checks
    return TransformerConfig(**kw)


def data(cfg, b=4, s=16, seed=0):
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32)
    return tokens, labels


class TestSingleDevice:
    def test_forward_shapes_and_loss(self):
        cfg = tiny_cfg()
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        tokens, labels = data(cfg)
        logits = gpt_forward(params, tokens, cfg)
        assert logits.shape == (4, 16, cfg.vocab_size)
        loss = gpt_loss(params, tokens, labels, cfg)
        assert jnp.isfinite(loss)
        # random init ⇒ loss ≈ log(vocab)
        assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0

    # rope/swiglu/rms have default-tier kernel coverage; their combo
    # rides the slow tier. untied embeddings have no other coverage
    # anywhere, so that variant stays default.
    @pytest.mark.parametrize("variant", [
        pytest.param("rope_swiglu_rms", marks=pytest.mark.slow),
        "untied"])
    def test_variants(self, variant):
        if variant == "rope_swiglu_rms":
            cfg = tiny_cfg(position_embedding_type="rope",
                           activation="swiglu", normalization="rmsnorm")
        else:
            cfg = tiny_cfg(untie_embeddings_and_output_weights=True)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        tokens, labels = data(cfg)
        loss, grads = jax.value_and_grad(gpt_loss)(
            params, tokens, labels, cfg)
        assert jnp.isfinite(loss)
        flat = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
        # every param gets gradient signal somewhere
        assert any(float(jnp.max(jnp.abs(g))) > 0 for g in flat)

    def test_scan_matches_unrolled(self):
        cfg_s = tiny_cfg(scan_layers=True)
        cfg_u = tiny_cfg(scan_layers=False)
        params = init_gpt_params(jax.random.PRNGKey(1), cfg_s)
        tokens, labels = data(cfg_s)
        l1 = gpt_loss(params, tokens, labels, cfg_s)
        l2 = gpt_loss(params, tokens, labels, cfg_u)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)

    def test_padding_mask_isolates_positions(self):
        # bert_large-style bidirectional model: a fully-masked-out key
        # position must not affect other positions' logits
        cfg = tiny_cfg(attn_mask_type="padding")
        params = init_gpt_params(jax.random.PRNGKey(7), cfg)
        tokens, labels = data(cfg)
        b, s = tokens.shape
        mask = jnp.zeros((b, 1, s, s), bool).at[:, :, :, -1].set(True)
        logits = gpt_forward(params, tokens, cfg, attention_mask=mask)
        tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % cfg.vocab_size)
        logits2 = gpt_forward(params, tokens2, cfg, attention_mask=mask)
        np.testing.assert_allclose(
            np.asarray(logits[:, :-1]), np.asarray(logits2[:, :-1]),
            atol=1e-5)
        # and the masked loss path runs through gpt_loss too
        loss = gpt_loss(params, tokens, labels, cfg, attention_mask=mask)
        assert jnp.isfinite(loss)

    def test_causal_combines_with_user_mask(self):
        # causal LM + explicit padding mask: both must apply
        cfg = tiny_cfg()   # attn_mask_type='causal'
        params = init_gpt_params(jax.random.PRNGKey(8), cfg)
        tokens, _ = data(cfg)
        b, s = tokens.shape
        pad = jnp.zeros((b, 1, s, s), bool).at[:, :, :, s // 2].set(True)
        logits = gpt_forward(params, tokens, cfg, attention_mask=pad)
        # perturbing the masked-out key position changes nothing downstream
        tokens2 = tokens.at[:, s // 2].set(
            (tokens[:, s // 2] + 1) % cfg.vocab_size)
        logits2 = gpt_forward(params, tokens2, cfg, attention_mask=pad)
        np.testing.assert_allclose(
            np.asarray(logits[:, s // 2 + 1:]),
            np.asarray(logits2[:, s // 2 + 1:]), atol=1e-5)
        # and causality still holds with the mask present
        tokens3 = tokens.at[:, -1].set((tokens[:, -1] + 1) % cfg.vocab_size)
        logits3 = gpt_forward(params, tokens3, cfg, attention_mask=pad)
        np.testing.assert_allclose(
            np.asarray(logits[:, :-1]), np.asarray(logits3[:, :-1]),
            atol=1e-5)

    def test_causality(self):
        cfg = tiny_cfg()
        params = init_gpt_params(jax.random.PRNGKey(2), cfg)
        tokens, _ = data(cfg)
        logits = gpt_forward(params, tokens, cfg)
        # perturb the last token: logits at earlier positions unchanged
        tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % cfg.vocab_size)
        logits2 = gpt_forward(params, tokens2, cfg)
        np.testing.assert_allclose(
            np.asarray(logits[:, :-1]), np.asarray(logits2[:, :-1]),
            atol=1e-5)
        assert float(jnp.max(jnp.abs(logits[:, -1] - logits2[:, -1]))) > 1e-4


class TestManualTP:
    # one loss param stays default: both exercise identical manual-TP
    # machinery, and swiglu is the superset (extra gated projection);
    # the gelu variant rides the slow tier with the grads test
    @pytest.mark.parametrize("activation", [
        pytest.param("gelu", marks=pytest.mark.slow), "swiglu"])
    def test_tp_loss_matches_single_device(self, activation):
        tp = 2
        cfg = tiny_cfg(activation=activation)
        params = init_gpt_params(jax.random.PRNGKey(3), cfg)
        tokens, labels = data(cfg)
        ref = float(gpt_loss(params, tokens, labels, cfg))

        mesh = create_mesh(tp=tp)
        specs = gpt_param_specs(cfg)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P())
        def run(p, t, y):
            ctx = manual_ctx(tp)
            return gpt_loss(p, t, y, cfg, ctx)

        got = float(run(params, tokens, labels))
        np.testing.assert_allclose(got, ref, rtol=1e-5)

    @pytest.mark.slow   # manual-TP loss variants keep the default-tier TP coverage
    def test_tp_grads_match_single_device(self):
        tp = 2
        cfg = tiny_cfg()
        params = init_gpt_params(jax.random.PRNGKey(4), cfg)
        tokens, labels = data(cfg)
        ref_grads = jax.grad(gpt_loss)(params, tokens, labels, cfg)

        mesh = create_mesh(tp=tp)
        specs = gpt_param_specs(cfg)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=specs)
        def run(p, t, y):
            ctx = manual_ctx(tp)
            return jax.grad(gpt_loss)(p, t, y, cfg, ctx)

        grads = run(params, tokens, labels)
        for path in [("embedding", "word"), ("layers", "qkv_kernel"),
                     ("layers", "fc2_kernel"), ("final_ln", "scale")]:
            g, r = grads, ref_grads
            for k in path:
                g, r = g[k], r[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=2e-4,
                err_msg=str(path))


class TestGSPMD:
    @pytest.mark.slow   # dryrun gspmd phase covers AMP mesh step + parity
    def test_train_step_runs_and_learns(self):
        cfg = tiny_cfg(compute_dtype=jnp.bfloat16)
        mesh = create_mesh(tp=2, dp=4)
        init, step = make_gpt_train_step(
            cfg, fused_adam(lr=1e-3), "O2", mesh)
        state = init(jax.random.PRNGKey(0))
        tokens, labels = data(cfg, b=8)
        losses = []
        for _ in range(5):
            state, metrics = step(state, tokens, labels)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        assert int(state.step) == 5

    def test_gspmd_loss_matches_single_device(self):
        cfg = tiny_cfg()
        mesh = create_mesh(tp=2, dp=2, pp=2)
        params = init_gpt_params(jax.random.PRNGKey(5), cfg)
        tokens, labels = data(cfg)
        ref = float(gpt_loss(params, tokens, labels, cfg))
        with jax.set_mesh(mesh):
            got = float(
                jax.jit(gpt_loss, static_argnums=(3, 4))(
                    params, tokens, labels, cfg, gspmd_ctx()))
        np.testing.assert_allclose(got, ref, rtol=1e-5)


class TestPipeline:
    # both params ride the slow tier (CI every push): these are
    # single-shot loss/grad parity assertions, exactly what the dryrun
    # pipeline phase re-asserts on every driver run; the schedule logic
    # keeps default-tier coverage via test_pipeline.py's toy stages
    @pytest.mark.parametrize(
        "tp", [pytest.param(1, marks=pytest.mark.slow),
               pytest.param(2, marks=pytest.mark.slow)])
    def test_pipeline_loss_and_grads_match_sequential(self, tp):
        pp, n_micro, mb = 2, 4, 2
        cfg = tiny_cfg(num_layers=4, remat=False)
        params = init_gpt_params(jax.random.PRNGKey(6), cfg)
        tokens, labels = data(cfg, b=n_micro * mb)

        ref_loss, ref_grads = jax.value_and_grad(gpt_loss)(
            params, tokens, labels, cfg)

        stacked = stack_pipeline_params(params, cfg, pp)
        tokens_mb = tokens.reshape(n_micro, mb, -1)
        labels_mb = labels.reshape(n_micro, mb, -1)
        packets = pipeline_packet(tokens_mb, labels_mb, cfg)

        mesh = create_mesh(pp=pp, tp=tp)
        stage_fn = make_gpt_pipeline_stage(cfg, pp, tp)
        pspecs = gpt_param_specs(cfg, pp_axis="pp")
        if tp == 1:
            pspecs = jax.tree_util.tree_map(
                lambda s: P(*(a if a != "tp" else None for a in s)),
                pspecs, is_leaf=lambda x: isinstance(x, P))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(pspecs, P()), out_specs=(P(), pspecs))
        def run(p, mbs):
            return gpt_pipeline_loss_and_grads(
                stage_fn, p, mbs, n_micro=n_micro)

        loss, grads = run(stacked, packets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)

        ref_stacked = stack_pipeline_params(ref_grads, cfg, pp)
        for path in [("embedding", "word"), ("layers", "qkv_kernel"),
                     ("layers", "fc1_kernel"), ("final_ln", "scale")]:
            g, r = grads, ref_stacked
            for k in path:
                g, r = g[k], r[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=3e-4, err_msg=str(path))


class TestPipelineMasksAndDropout:
    """Padding masks + dropout through the pipeline
    packet (BERT-style models under PP)."""

    @pytest.mark.slow   # dryrun pipeline feature phase runs the same mask packet
    def test_padding_mask_matches_sequential(self):
        pp, n_micro, mb = 2, 2, 2
        cfg = tiny_cfg(num_layers=4, remat=False,
                       attn_mask_type="padding")
        params = init_gpt_params(jax.random.PRNGKey(7), cfg)
        tokens, labels = data(cfg, b=n_micro * mb)
        s = tokens.shape[-1]
        # mask out a tail of keys per sequence
        lens = np.array([10, 16, 12, 16])
        kpm = jnp.asarray(np.arange(s)[None, :] >= lens[:, None])

        ref_loss, ref_grads = jax.value_and_grad(gpt_loss)(
            params, tokens, labels, cfg, attention_mask=kpm)

        stacked = stack_pipeline_params(params, cfg, pp)
        packets = pipeline_packet(
            tokens.reshape(n_micro, mb, -1),
            labels.reshape(n_micro, mb, -1), cfg,
            attention_mask_mb=kpm.reshape(n_micro, mb, -1))

        mesh = create_mesh(pp=pp, tp=1)
        stage_fn = make_gpt_pipeline_stage(cfg, pp, 1)
        pspecs = gpt_param_specs(cfg, pp_axis="pp")
        pspecs = jax.tree_util.tree_map(
            lambda sp: P(*(a if a != "tp" else None for a in sp)),
            pspecs, is_leaf=lambda x: isinstance(x, P))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(pspecs, P()), out_specs=(P(), pspecs))
        def run(p, mbs):
            return gpt_pipeline_loss_and_grads(
                stage_fn, p, mbs, n_micro=n_micro)

        loss, grads = run(stacked, packets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        ref_stacked = stack_pipeline_params(ref_grads, cfg, pp)
        for path in [("embedding", "word"), ("layers", "qkv_kernel")]:
            g, r = grads, ref_stacked
            for k in path:
                g, r = g[k], r[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=3e-4,
                err_msg=str(path))

    @pytest.mark.slow   # dryrun pipeline feature phase covers masks+dropout
    def test_dropout_runs_and_is_seed_deterministic(self):
        pp, n_micro, mb = 2, 2, 2
        cfg = tiny_cfg(num_layers=4, remat=False,
                       hidden_dropout=0.1, attention_dropout=0.1)
        params = init_gpt_params(jax.random.PRNGKey(8), cfg)
        tokens, labels = data(cfg, b=n_micro * mb)
        stacked = stack_pipeline_params(params, cfg, pp)
        seeds = jnp.arange(n_micro, dtype=jnp.int32) + 7
        packets = pipeline_packet(
            tokens.reshape(n_micro, mb, -1),
            labels.reshape(n_micro, mb, -1), cfg, dropout_seeds=seeds)

        mesh = create_mesh(pp=pp, tp=1)
        stage_fn = make_gpt_pipeline_stage(cfg, pp, 1)
        pspecs = gpt_param_specs(cfg, pp_axis="pp")
        pspecs = jax.tree_util.tree_map(
            lambda sp: P(*(a if a != "tp" else None for a in sp)),
            pspecs, is_leaf=lambda x: isinstance(x, P))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(pspecs, P()), out_specs=(P(), pspecs))
        def run(p, mbs):
            return gpt_pipeline_loss_and_grads(
                stage_fn, p, mbs, n_micro=n_micro)

        loss1, grads1 = run(stacked, packets)
        loss2, _ = run(stacked, packets)
        # same seeds -> identical stochastic loss; grads finite
        np.testing.assert_allclose(float(loss1), float(loss2))
        # different seeds -> different dropout mask
        packets2 = pipeline_packet(
            tokens.reshape(n_micro, mb, -1),
            labels.reshape(n_micro, mb, -1), cfg,
            dropout_seeds=seeds + 100)
        loss3, _ = run(stacked, packets2)
        assert float(loss3) != float(loss1)
        for leaf in jax.tree_util.tree_leaves(grads1):
            assert np.all(np.isfinite(np.asarray(leaf)))


class TestVirtualPipeline:
    """Interleaved (vpp) schedule driving the real GPT model — chunk
    identity from the chunk_id leaf, embed/head on their owning chunks
    only (reference fwd_bwd_pipelining_with_interleaving.py:26 +
    build_model virtual chunks)."""

    @pytest.mark.slow   # dryrun vpp phase asserts the same parity
    def test_vpp_loss_and_grads_match_sequential(self):
        from apex_tpu.models.gpt import (
            gpt_vpp_loss_and_grads,
            make_gpt_vpp_stage,
            stack_pipeline_params_vpp,
        )

        pp, vpp, n_micro, mb = 2, 2, 4, 2
        cfg = tiny_cfg(num_layers=8, remat=False)
        params = init_gpt_params(jax.random.PRNGKey(9), cfg)
        tokens, labels = data(cfg, b=n_micro * mb)

        ref_loss, ref_grads = jax.value_and_grad(gpt_loss)(
            params, tokens, labels, cfg)

        stacked = stack_pipeline_params_vpp(params, cfg, pp, vpp)
        packets = pipeline_packet(
            tokens.reshape(n_micro, mb, -1),
            labels.reshape(n_micro, mb, -1), cfg)

        mesh = create_mesh(pp=pp, tp=1)
        stage_fn = make_gpt_vpp_stage(cfg, pp, vpp)
        base = gpt_param_specs(cfg, pp_axis="pp")
        base = jax.tree_util.tree_map(
            lambda sp: P(*(a if a != "tp" else None for a in sp)),
            base, is_leaf=lambda x: isinstance(x, P))
        # in: every non-layer leaf vpp-broadcast (leading None); layers
        # [vpp, pp, per, ...] shard dim 1; chunk_id [vpp, pp]
        pspecs_in = jax.tree_util.tree_map(
            lambda sp: P(None, *sp), base,
            is_leaf=lambda x: isinstance(x, P))
        pspecs_in["layers"] = jax.tree_util.tree_map(
            lambda sp: P(None, *sp), base["layers"],
            is_leaf=lambda x: isinstance(x, P))
        pspecs_in["chunk_id"] = P(None, "pp")
        # out: layer grads stacked, replicated grads plain (vpp-summed)
        pspecs_out = dict(base)
        pspecs_out["layers"] = pspecs_in["layers"]

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(pspecs_in, P()), out_specs=(P(), pspecs_out))
        def run(p, mbs):
            return gpt_vpp_loss_and_grads(
                stage_fn, p, mbs, n_micro=n_micro, vpp=vpp)

        loss, grads = run(stacked, packets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)

        ref_layers = stack_pipeline_params_vpp(
            ref_grads, cfg, pp, vpp)["layers"]
        for path, ref_tree in [
            (("embedding", "word"), ref_grads),
            (("final_ln", "scale"), ref_grads),
            (("layers", "qkv_kernel"), {"layers": ref_layers}),
            (("layers", "fc2_kernel"), {"layers": ref_layers}),
        ]:
            g, r = grads, ref_tree
            for k in path:
                g, r = g[k], r[k]
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=3e-4,
                err_msg=str(path))


class TestGPTMoE:
    """GPT-MoE model family (cfg.num_experts) — Switch FFN in the
    backbone with the load-balance aux loss in the training objective."""

    def test_forward_and_loss_finite(self):
        cfg = tiny_cfg(num_experts=4, remat=False)
        params = init_gpt_params(jax.random.PRNGKey(10), cfg)
        assert "router_kernel" in params["layers"]
        assert "fc1_kernel" not in params["layers"]
        tokens, labels = data(cfg)
        loss = gpt_loss(params, tokens, labels, cfg)
        assert np.isfinite(float(loss))

    def test_aux_loss_included(self):
        cfg0 = tiny_cfg(num_experts=4, remat=False, moe_aux_loss_coeff=0.0)
        cfg1 = tiny_cfg(num_experts=4, remat=False, moe_aux_loss_coeff=1.0)
        params = init_gpt_params(jax.random.PRNGKey(11), cfg0)
        tokens, labels = data(cfg0)
        l0 = float(gpt_loss(params, tokens, labels, cfg0))
        l1 = float(gpt_loss(params, tokens, labels, cfg1))
        assert l1 > l0  # the balance term is positive (>= 1 per layer)

    @pytest.mark.slow   # gspmd_expert_parallel/forward_and_loss keep MoE coverage
    def test_train_step_learns_and_routes(self):
        from apex_tpu.optimizers import fused_adam

        cfg = tiny_cfg(num_experts=4, remat=False)
        init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-3), "O0")
        state = init(jax.random.PRNGKey(12))
        tokens, labels = data(cfg)
        router0 = np.asarray(
            state.master_params["layers"]["router_kernel"]).copy()
        state, m0 = step(state, tokens, labels)
        for _ in range(10):
            state, m = step(state, tokens, labels)
        assert float(m["loss"]) < float(m0["loss"])
        # router actually moved (gradients flow through the gates)
        router1 = np.asarray(state.master_params["layers"]["router_kernel"])
        assert np.abs(router1 - router0).sum() > 0

    @pytest.mark.slow   # dryrun moe phase covers expert-parallel parity
    def test_gspmd_expert_parallel_step(self):
        from apex_tpu.optimizers import fused_adam

        cfg = tiny_cfg(num_experts=4, remat=False)
        mesh = create_mesh(dp=2, ep=4, tp=1, pp=1)
        init, step = make_gpt_train_step(
            cfg, fused_adam(lr=1e-3), "O2", mesh)
        state = init(jax.random.PRNGKey(13))
        tokens, labels = data(cfg, b=4)
        state, m = step(state, tokens, labels)
        assert np.isfinite(float(m["loss"]))


class TestGPTMoESwiglu:
    """Round-3: the MoE + SwiGLU combination (gate lifted)."""

    @pytest.mark.slow   # MoE+SwiGLU combo; components covered separately
    def test_forward_and_train(self):
        from apex_tpu.optimizers import fused_adam

        cfg = tiny_cfg(num_experts=4, activation="swiglu", remat=False)
        params = init_gpt_params(jax.random.PRNGKey(20), cfg)
        f = cfg.ffn_hidden_size
        assert params["layers"]["moe_fc1"].shape[-1] == 2 * f
        tokens, labels = data(cfg)
        loss = gpt_loss(params, tokens, labels, cfg)
        assert np.isfinite(float(loss))

        init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-3), "O0")
        state = init(jax.random.PRNGKey(21))
        state, m0 = step(state, tokens, labels)
        for _ in range(8):
            state, m = step(state, tokens, labels)
        assert float(m["loss"]) < float(m0["loss"])


class TestGPTMoEPipeline:
    """Round-3: MoE composes with the shard_map pipeline — experts run
    locally per stage, the aux loss rides the packet to the last stage."""

    def _run_pipeline(self, cfg, params, tokens, labels, pp, n_micro, mb,
                      vpp=None):
        from apex_tpu.models.gpt import stack_pipeline_params_vpp

        stacked = (stack_pipeline_params_vpp(params, cfg, pp, vpp)
                   if vpp else stack_pipeline_params(params, cfg, pp))
        tokens_mb = tokens.reshape(n_micro, mb, -1)
        labels_mb = labels.reshape(n_micro, mb, -1)
        packets = pipeline_packet(tokens_mb, labels_mb, cfg)
        mesh = create_mesh(pp=pp, tp=1)
        # pp_axis set -> gpt_param_specs already drops 'ep' (local experts)
        pspecs = gpt_param_specs(cfg, pp_axis="pp")
        pspecs = jax.tree_util.tree_map(
            lambda s: P(*(a if a != "tp" else None for a in s)),
            pspecs, is_leaf=lambda x: isinstance(x, P))
        if vpp:
            from apex_tpu.models.gpt import (
                gpt_vpp_loss_and_grads, make_gpt_vpp_stage)

            vspecs = jax.tree_util.tree_map(
                lambda s: P(None, *s), pspecs,
                is_leaf=lambda x: isinstance(x, P))
            grad_specs = dict(pspecs)
            grad_specs["layers"] = vspecs["layers"]
            in_v = dict(vspecs)
            in_v["chunk_id"] = P(None, "pp")
            stage_fn = make_gpt_vpp_stage(cfg, pp, vpp)

            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(in_v, P()), out_specs=(P(), grad_specs))
            def run(p, mbs):
                return gpt_vpp_loss_and_grads(
                    stage_fn, p, mbs, n_micro=n_micro, vpp=vpp)
        else:
            stage_fn = make_gpt_pipeline_stage(cfg, pp, 1)

            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(pspecs, P()), out_specs=(P(), pspecs))
            def run(p, mbs):
                return gpt_pipeline_loss_and_grads(
                    stage_fn, p, mbs, n_micro=n_micro)

        return run(stacked, packets)

    @pytest.mark.slow   # dryrun pipeline phase asserts MoE x PP parity
    def test_moe_pipeline_matches_sequential(self):
        pp, n_micro, mb = 2, 2, 2
        cfg = tiny_cfg(num_experts=4, num_layers=4, remat=False)
        params = init_gpt_params(jax.random.PRNGKey(30), cfg)
        tokens, labels = data(cfg, b=n_micro * mb)

        def ref_loss(p):
            per = [gpt_loss(p, tokens.reshape(n_micro, mb, -1)[i],
                            labels.reshape(n_micro, mb, -1)[i], cfg)
                   for i in range(n_micro)]
            return jnp.mean(jnp.stack(per))

        ref_l, ref_g = jax.value_and_grad(ref_loss)(params)
        loss, grads = self._run_pipeline(
            cfg, params, tokens, labels, pp, n_micro, mb)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
        # expert + router grads agree with the sequential model
        ref_stacked = stack_pipeline_params(ref_g, cfg, pp)
        for key in ("router_kernel", "moe_fc1", "moe_fc2"):
            np.testing.assert_allclose(
                np.asarray(grads["layers"][key]),
                np.asarray(ref_stacked["layers"][key]),
                atol=3e-4, err_msg=key)

    @pytest.mark.slow
    def test_moe_vpp_matches_sequential(self):
        pp, vpp, n_micro, mb = 2, 2, 4, 2
        cfg = tiny_cfg(num_experts=4, num_layers=4, remat=False)
        params = init_gpt_params(jax.random.PRNGKey(31), cfg)
        tokens, labels = data(cfg, b=n_micro * mb)

        def ref_loss(p):
            per = [gpt_loss(p, tokens.reshape(n_micro, mb, -1)[i],
                            labels.reshape(n_micro, mb, -1)[i], cfg)
                   for i in range(n_micro)]
            return jnp.mean(jnp.stack(per))

        ref_l, _ = jax.value_and_grad(ref_loss)(params)
        loss, _ = self._run_pipeline(
            cfg, params, tokens, labels, pp, n_micro, mb, vpp=vpp)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)


class TestResidualPostLayernorm:
    """apply_residual_connection_post_layernorm (reference
    standalone_transformer_lm.py:620,707,738): residual taken from the
    LN output instead of the block input."""

    def test_flag_changes_output_and_matches_manual(self):
        import dataclasses

        from apex_tpu.models.transformer_lm import (
            apply_norm, gpt_forward, single_device_ctx, _attention, _mlp)

        cfg = tiny_cfg(num_layers=1, remat=False, scan_layers=False,
                       compute_dtype=jnp.float32)
        cfg_post = dataclasses.replace(
            cfg, apply_residual_connection_post_layernorm=True)
        params = init_gpt_params(jax.random.PRNGKey(40), cfg)
        tokens, _ = data(cfg)

        pre = gpt_forward(params, tokens, cfg)
        post = gpt_forward(params, tokens, cfg_post)
        assert not np.allclose(np.asarray(pre), np.asarray(post))

        # manual single-layer recomputation of the post-LN-residual rule
        ctx = single_device_ctx()
        from apex_tpu.models.transformer_lm import embed_tokens

        lp = jax.tree_util.tree_map(lambda v: v[0], params["layers"])
        x = embed_tokens(params["embedding"], tokens, cfg_post, ctx)
        h = apply_norm(cfg_post, x, lp["ln1_scale"], lp["ln1_bias"])
        x = h + _attention(cfg_post, lp, h, ctx, None, None, None)
        h = apply_norm(cfg_post, x, lp["ln2_scale"], lp["ln2_bias"])
        x = h + _mlp(cfg_post, lp, h, ctx)
        x = apply_norm(cfg_post, x, params["final_ln"]["scale"],
                       params["final_ln"]["bias"])
        from apex_tpu.models.transformer_lm import lm_head_logits

        want = lm_head_logits(params, x, cfg_post)
        np.testing.assert_allclose(np.asarray(post), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


class TestDropPath:
    """drop_path stochastic depth (reference DropPath,
    standalone_transformer_lm.py:712-728)."""

    def test_whole_branch_dropped_per_sample(self):
        from apex_tpu.models.transformer_lm import _drop_path

        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(16, 8, 4), jnp.float32)
        out = np.asarray(_drop_path(x, 0.5, jax.random.PRNGKey(0)))
        kept = dropped = 0
        for i in range(16):
            if np.all(out[i] == 0.0):
                dropped += 1
            else:
                # kept samples carry the WHOLE branch, scaled 1/(1-p)
                np.testing.assert_allclose(
                    out[i], np.asarray(x)[i] / 0.5, rtol=1e-6)
                kept += 1
        assert kept > 0 and dropped > 0, (kept, dropped)

        # and it actually perturbs a model forward
        import dataclasses

        cfg = tiny_cfg(num_layers=1, remat=False, scan_layers=False,
                       compute_dtype=jnp.float32)
        cfg_dp = dataclasses.replace(cfg, drop_path_rate=0.99)
        params = init_gpt_params(jax.random.PRNGKey(41), cfg)
        tokens, _ = data(cfg, b=8)
        from apex_tpu.models.transformer_lm import gpt_forward

        got = gpt_forward(params, tokens, cfg_dp,
                          dropout_rng=jax.random.PRNGKey(0))
        base = gpt_forward(params, tokens, cfg,
                           dropout_rng=jax.random.PRNGKey(0))
        assert not np.allclose(np.asarray(got), np.asarray(base))
        assert np.isfinite(np.asarray(got)).all()

    def test_eval_mode_unaffected(self):
        import dataclasses

        cfg = tiny_cfg(num_layers=2, remat=False,
                       compute_dtype=jnp.float32)
        cfg_dp = dataclasses.replace(cfg, drop_path_rate=0.5)
        params = init_gpt_params(jax.random.PRNGKey(42), cfg)
        tokens, _ = data(cfg)
        from apex_tpu.models.transformer_lm import gpt_forward

        # no rng -> deterministic eval path, identical to rate 0
        a = gpt_forward(params, tokens, cfg)
        b = gpt_forward(params, tokens, cfg_dp)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_expected_value_preserved(self):
        import dataclasses

        cfg = tiny_cfg(num_layers=1, remat=False, scan_layers=False,
                       compute_dtype=jnp.float32, hidden_size=32,
                       num_attention_heads=2)
        cfg_dp = dataclasses.replace(cfg, drop_path_rate=0.3)
        params = init_gpt_params(jax.random.PRNGKey(43), cfg)
        tokens, _ = data(cfg, b=4)
        from apex_tpu.models.transformer_lm import gpt_forward

        base = np.asarray(gpt_forward(params, tokens, cfg))
        outs = []
        fwd = jax.jit(lambda r: gpt_forward(params, tokens, cfg_dp,
                                            dropout_rng=r))
        for i in range(300):
            outs.append(np.asarray(fwd(jax.random.PRNGKey(i))))
        mean = np.mean(outs, axis=0)
        # E[drop_path(x)] == x: the scaled-branch mean approaches the
        # deterministic forward (loose tolerance; 300 samples)
        err = np.abs(mean - base).mean() / (np.abs(base).mean() + 1e-6)
        assert err < 0.15, err
