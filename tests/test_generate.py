"""KV-cache decoding: teacher-forcing parity with the training forward,
prefill-vs-stepwise cache equivalence, and ragged-batch decode parity."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.generate import (
    decode_step, generate, init_kv_cache, prefill, sample_logits)
from apex_tpu.models.transformer_lm import gpt_forward, init_gpt_params


def _cfg(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_position_embeddings", 24)
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


VARIANTS = [
    {},
    {"position_embedding_type": "rope"},
    {"activation": "swiglu"},
    {"activation": "gelu_tanh"},
    {"apply_residual_connection_post_layernorm": True},
    {"normalization": "rmsnorm"},
]


class TestDecodeParity:
    @pytest.mark.parametrize("variant", VARIANTS)
    def test_stepwise_logits_match_full_forward(self, variant):
        """Feeding the gold sequence token-by-token through the cached
        decode must reproduce the training forward's logits at every
        position — the strongest possible pin of the cache math."""
        cfg = _cfg(**variant)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        b, s = 2, 12
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)),
                             jnp.int32)

        want = np.asarray(gpt_forward(params, tokens, cfg))

        cache = init_kv_cache(cfg, b, s)
        step = jax.jit(lambda t, c: decode_step(params, t, c, cfg))
        for i in range(s):
            logits, cache = step(tokens[:, i], cache)
            np.testing.assert_allclose(
                np.asarray(logits), want[:, i], atol=2e-4, rtol=2e-4,
                err_msg=f"{variant} position {i}")


class TestGenerate:
    def test_greedy_matches_argmax_of_forward(self):
        cfg = _cfg()
        params = init_gpt_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.RandomState(1)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 4)),
                             jnp.int32)
        out = generate(params, prompt, cfg, max_new_tokens=6)
        assert out.shape == (2, 10)
        np.testing.assert_array_equal(np.asarray(out[:, :4]),
                                      np.asarray(prompt))
        # reference: greedy re-decode with the full forward each step
        seq = np.asarray(prompt)
        for _ in range(6):
            logits = np.asarray(gpt_forward(
                params, jnp.asarray(seq, jnp.int32), cfg))
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            seq = np.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(out), seq)

    def test_sampling_is_seeded_and_topk_restricts(self):
        cfg = _cfg()
        params = init_gpt_params(jax.random.PRNGKey(2), cfg)
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        a = generate(params, prompt, cfg, max_new_tokens=8,
                     temperature=1.0, top_k=5, rng=jax.random.PRNGKey(7))
        b = generate(params, prompt, cfg, max_new_tokens=8,
                     temperature=1.0, top_k=5, rng=jax.random.PRNGKey(7))
        c = generate(params, prompt, cfg, max_new_tokens=8,
                     temperature=1.0, top_k=5, rng=jax.random.PRNGKey(8))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_imported_hf_weights_generate(self):
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.import_hf import config_from_hf, params_from_hf

        hfc = transformers.GPT2Config(
            n_layer=2, n_embd=64, n_head=4, vocab_size=100,
            n_positions=32, resid_pdrop=0.0, embd_pdrop=0.0,
            attn_pdrop=0.0)
        torch.manual_seed(3)
        hf = transformers.GPT2LMHeadModel(hfc).eval()
        cfg = config_from_hf(hfc, compute_dtype=jnp.float32)
        params = params_from_hf(hf.state_dict(), cfg)

        prompt = jnp.asarray([[5, 17, 31]], jnp.int32)
        ours = generate(params, prompt, cfg, max_new_tokens=5,
                        vocab_limit=hfc.vocab_size)
        with torch.no_grad():
            theirs = hf.generate(
                torch.asarray(np.asarray(prompt)), max_new_tokens=5,
                do_sample=False, pad_token_id=0)
        np.testing.assert_array_equal(np.asarray(ours),
                                      theirs.numpy())


    def test_vocab_limit_masks_padded_ids(self):
        cfg = _cfg(vocab_size=128)
        params = init_gpt_params(jax.random.PRNGKey(5), cfg)
        prompt = jnp.asarray([[1, 2]], jnp.int32)
        out = generate(params, prompt, cfg, max_new_tokens=10,
                       temperature=1.0, rng=jax.random.PRNGKey(0),
                       vocab_limit=7)
        assert np.asarray(out)[:, 2:].max() < 7

    def test_overflowing_learned_positions_raise(self):
        cfg = _cfg(max_position_embeddings=8)
        params = init_gpt_params(jax.random.PRNGKey(6), cfg)
        prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        with pytest.raises(ValueError, match="exceeds"):
            generate(params, prompt, cfg, max_new_tokens=8)

    def test_moe_and_padding_configs_rejected(self):
        cfg = _cfg(num_experts=2)
        params = init_gpt_params(jax.random.PRNGKey(7), cfg)
        with pytest.raises(ValueError, match="MoE"):
            decode_step(params, jnp.asarray([1], jnp.int32),
                        init_kv_cache(cfg, 1, 4), cfg)
        cfg2 = _cfg(attn_mask_type="padding")
        params2 = init_gpt_params(jax.random.PRNGKey(8), cfg2)
        with pytest.raises(ValueError, match="causal"):
            decode_step(params2, jnp.asarray([1], jnp.int32),
                        init_kv_cache(cfg2, 1, 4), cfg2)


def _ragged_batch(rng, vocab, lens):
    """Left-aligned right-padded [b, max(lens)] batch + per-row prompts."""
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]
    batch = np.zeros((len(lens), max(lens)), np.int32)
    for i, p in enumerate(prompts):
        batch[i, : len(p)] = p
    return jnp.asarray(batch), prompts


class TestPrefill:
    """The batched flash prefill must fill EXACTLY the cache the
    sequential decode would have built — the cache-equivalence pin that
    keeps the prefill/decode split honest."""

    # the GQA x rope variant is the riskiest; the activation/norm
    # variants ride the slow tier (prefill reuses the same layer math)
    @pytest.mark.parametrize("variant", [
        {},
        {"position_embedding_type": "rope", "num_query_groups": 2},
        pytest.param({"activation": "swiglu", "normalization": "rmsnorm"},
                     marks=pytest.mark.slow),
    ])
    def test_prefill_cache_matches_stepwise_decode(self, variant):
        cfg = _cfg(**variant)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        b, s = 2, 10
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)),
                             jnp.int32)

        cache = init_kv_cache(cfg, b, s)
        for i in range(s):
            _, cache = decode_step(params, tokens[:, i], cache, cfg)

        logits, pcache = prefill(params, tokens, cfg)
        np.testing.assert_allclose(
            np.asarray(pcache["k"]), np.asarray(cache["k"]),
            atol=2e-4, rtol=2e-4, err_msg=f"{variant} k")
        np.testing.assert_allclose(
            np.asarray(pcache["v"]), np.asarray(cache["v"]),
            atol=2e-4, rtol=2e-4, err_msg=f"{variant} v")
        np.testing.assert_array_equal(np.asarray(pcache["pos"]),
                                      np.full((b,), s))
        # prefill's last-token logits == the training forward's
        want = np.asarray(gpt_forward(params, tokens, cfg))[:, -1]
        np.testing.assert_allclose(np.asarray(logits), want,
                                   atol=2e-4, rtol=2e-4)

    def test_prefill_into_longer_cache_then_decode(self):
        """Teacher-forcing split point: prefill the first half, decode
        the second half stepwise — logits must match the full forward
        at every decoded position (extends TestDecodeParity across the
        prefill/decode seam)."""
        cfg = _cfg()
        params = init_gpt_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.RandomState(1)
        b, s, tail = 2, 12, 5
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)),
                             jnp.int32)
        want = np.asarray(gpt_forward(params, tokens, cfg))

        head = s - tail
        logits, cache = prefill(params, tokens[:, :head], cfg, max_len=s)
        np.testing.assert_allclose(np.asarray(logits), want[:, head - 1],
                                   atol=2e-4, rtol=2e-4)
        for i in range(head, s):
            logits, cache = decode_step(params, tokens[:, i], cache, cfg)
            np.testing.assert_allclose(
                np.asarray(logits), want[:, i], atol=2e-4, rtol=2e-4,
                err_msg=f"position {i}")

    def test_ragged_prefill_matches_per_sequence(self):
        cfg = _cfg(position_embedding_type="rope")
        params = init_gpt_params(jax.random.PRNGKey(2), cfg)
        rng = np.random.RandomState(2)
        lens = [3, 7]
        batch, prompts = _ragged_batch(rng, cfg.vocab_size, lens)
        logits, cache = prefill(params, batch, cfg,
                                prompt_lens=jnp.asarray(lens))
        np.testing.assert_array_equal(np.asarray(cache["pos"]), lens)
        for i, p in enumerate(prompts):
            solo_logits, solo = prefill(params, jnp.asarray(p[None]), cfg)
            n = len(p)
            np.testing.assert_allclose(
                np.asarray(cache["k"])[:, i, :n],
                np.asarray(solo["k"])[:, 0],
                atol=2e-4, rtol=2e-4, err_msg=f"row {i} k")
            np.testing.assert_allclose(
                np.asarray(logits)[i], np.asarray(solo_logits)[0],
                atol=2e-4, rtol=2e-4, err_msg=f"row {i} logits")


class TestRaggedGenerate:
    def test_ragged_greedy_matches_unbatched(self):
        cfg = _cfg()
        params = init_gpt_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.RandomState(3)
        lens = [3, 8]          # each solo length is its own compile
        batch, prompts = _ragged_batch(rng, cfg.vocab_size, lens)
        new = 6
        out = generate(params, batch, cfg, max_new_tokens=new,
                       prompt_lens=jnp.asarray(lens))
        assert out.shape == (len(lens), max(lens) + new)
        for i, p in enumerate(prompts):
            solo = generate(params, jnp.asarray(p[None]), cfg,
                            max_new_tokens=new)
            np.testing.assert_array_equal(
                np.asarray(out)[i, lens[i]: lens[i] + new],
                np.asarray(solo)[0, lens[i]:],
                err_msg=f"row {i}")

    def test_ragged_gqa_rope_matches_unbatched(self):
        """GQA + rope through the [b] position vector — the riskiest
        combination (grouped cache heads x per-sequence rotary
        offsets)."""
        cfg = _cfg(position_embedding_type="rope", num_query_groups=2)
        params = init_gpt_params(jax.random.PRNGKey(4), cfg)
        rng = np.random.RandomState(4)
        lens = [2, 6]
        batch, prompts = _ragged_batch(rng, cfg.vocab_size, lens)
        new = 5
        out = generate(params, batch, cfg, max_new_tokens=new,
                       prompt_lens=jnp.asarray(lens))
        for i, p in enumerate(prompts):
            solo = generate(params, jnp.asarray(p[None]), cfg,
                            max_new_tokens=new)
            np.testing.assert_array_equal(
                np.asarray(out)[i, lens[i]: lens[i] + new],
                np.asarray(solo)[0, lens[i]:],
                err_msg=f"row {i}")

    def test_eos_stops_early_and_freezes_rows(self):
        from apex_tpu.observability import metrics as telemetry

        cfg = _cfg()
        params = init_gpt_params(jax.random.PRNGKey(5), cfg)
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        ref = np.asarray(generate(params, prompt, cfg, max_new_tokens=8))
        eos = int(ref[0, 3])   # the FIRST generated token: stops at once
        reg = telemetry.configure()
        try:
            out = generate(params, prompt, cfg, max_new_tokens=8,
                           eos_token_id=eos)
            # identical up to and including the emitted EOS, padding after
            np.testing.assert_array_equal(np.asarray(out)[0, :4],
                                          ref[0, :4])
            np.testing.assert_array_equal(np.asarray(out)[0, 4:], 0)
            # the while_loop exited early: fewer decode steps than budget
            steps = reg.counter("generate.decode_steps").value
            assert steps < 8, steps
        finally:
            telemetry.shutdown()


class TestTraceCounts:
    """The acceptance pin of the prefill/decode split: the prompt does
    NOT pass through the per-token decode loop."""

    def _counts(self, b, s, new):
        from apex_tpu.observability import metrics as telemetry

        cfg = _cfg(max_position_embeddings=max(24, s + new))
        params = init_gpt_params(jax.random.PRNGKey(6), cfg)
        rng = np.random.RandomState(6)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)),
                             jnp.int32)
        reg = telemetry.configure()
        try:
            generate(params, prompt, cfg, max_new_tokens=new)
            return (reg.counter("generate.prefill_calls").value,
                    reg.counter("generate.decode_steps").value)
        finally:
            telemetry.shutdown()

    # new - 1 decode forwards: the first token comes from the prefill
    # logits, the last needs no decode behind it — the count scales
    # with the NEW tokens, never with the prompt length

    def test_prefill_once_decode_counts_new_tokens_only(self):
        prefills, steps = self._counts(b=2, s=16, new=5)
        assert prefills == 1
        assert steps == 5 - 1      # not s + new

    @pytest.mark.slow   # the [b=4, s=512] acceptance geometry; CI slow job
    def test_prefill_512_one_forward(self):
        prefills, steps = self._counts(b=4, s=512, new=8)
        assert prefills == 1
        assert steps == 8 - 1      # not 512 + 8


class TestSamplingSatellites:
    def test_negative_temperature_raises(self):
        cfg = _cfg()
        params = init_gpt_params(jax.random.PRNGKey(7), cfg)
        prompt = jnp.asarray([[1, 2]], jnp.int32)
        with pytest.raises(ValueError, match="temperature"):
            generate(params, prompt, cfg, max_new_tokens=2,
                     temperature=-0.5)
        with pytest.raises(ValueError, match="temperature"):
            sample_logits(jnp.zeros((1, 8)), jax.random.PRNGKey(0),
                          temperature=-1.0)

    def test_topk_without_topp_restricts_support(self):
        """The lax.top_k fast path (no full vocab sort) must still
        confine sampling to the k best logits."""
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(2, 64), jnp.float32)
        top3 = np.argsort(np.asarray(logits), axis=-1)[:, -3:]
        for seed in range(20):
            toks = np.asarray(sample_logits(
                logits, jax.random.PRNGKey(seed), temperature=1.0,
                top_k=3))
            for row in range(2):
                assert toks[row] in top3[row], (seed, row, toks)
        # top_k=1 at full temperature degenerates to greedy
        np.testing.assert_array_equal(
            np.asarray(sample_logits(logits, jax.random.PRNGKey(0),
                                     temperature=1.0, top_k=1)),
            np.asarray(sample_logits(logits, jax.random.PRNGKey(0))))

    def test_cache_dtype_override(self):
        cfg = _cfg()   # fp32 compute
        cache = init_kv_cache(cfg, 2, 8)
        assert cache["k"].dtype == cfg.compute_dtype
        assert cache["pos"].shape == (2,)
        bf16 = init_kv_cache(cfg, 2, 8, cache_dtype=jnp.bfloat16)
        assert bf16["k"].dtype == jnp.bfloat16
        # decode runs with the downcast cache (casts at the einsum)
        params = init_gpt_params(jax.random.PRNGKey(8), cfg)
        logits, bf16 = decode_step(
            params, jnp.asarray([1, 2], jnp.int32), bf16, cfg)
        assert bf16["k"].dtype == jnp.bfloat16
        assert logits.shape == (2, cfg.vocab_size)
        out = generate(params, jnp.asarray([[1, 2, 3]], jnp.int32), cfg,
                       max_new_tokens=4, cache_dtype=jnp.bfloat16)
        assert out.shape == (1, 7)


class TestTopP:
    def test_nucleus_restricts_support(self):
        """top_p at its degenerate limit must behave greedily — even at
        temperature 1.0, where a no-op filter would sample the whole
        distribution and diverge from argmax almost surely."""
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.generate import generate
        from apex_tpu.models.transformer_lm import init_gpt_params

        cfg = TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=2,
            vocab_size=32, max_position_embeddings=16,
            compute_dtype=jnp.float32)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        prompt = jnp.asarray([[1, 2]], jnp.int32)

        greedy = generate(params, prompt, cfg, max_new_tokens=6)
        for tp in (0.0, 1e-6):
            # full temperature: only the nucleus filter itself can make
            # this match argmax — a no-op regression fails loudly
            nucleus = generate(params, prompt, cfg, max_new_tokens=6,
                               temperature=1.0, top_p=tp,
                               rng=jax.random.PRNGKey(3))
            np.testing.assert_array_equal(
                np.asarray(greedy), np.asarray(nucleus),
                err_msg=f"top_p={tp}")

    def test_top_p_with_top_k_composes(self):
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.generate import generate
        from apex_tpu.models.transformer_lm import init_gpt_params

        cfg = TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=2,
            vocab_size=32, max_position_embeddings=16,
            compute_dtype=jnp.float32)
        params = init_gpt_params(jax.random.PRNGKey(1), cfg)
        prompt = jnp.asarray([[3, 4, 5]], jnp.int32)
        out = generate(params, prompt, cfg, max_new_tokens=5,
                       temperature=0.8, top_k=8, top_p=0.9,
                       rng=jax.random.PRNGKey(7))
        assert out.shape == (1, 8)
        assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))


class TestQkvSectionsKeepTheParameterLayout:
    """The training forward gathers the MHA projection's columns into
    ``[Q | K | V]`` sections before the product (``_mha_qkv``), so that
    the flash kernels read q, k, v as lane ranges; the stored
    ``qkv_kernel`` keeps its per-head interleave.  Heads of 64 take the
    kernels' ``[b, s, heads x d]`` route (two heads a 128-lane block)."""

    VARIANTS = [
        pytest.param({}, id="mha"),
        pytest.param({"position_embedding_type": "rope",
                      "num_query_groups": 2}, id="gqa_rope"),
    ]

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_prefill_kv_is_the_stepwise_decodes(self, variant):
        """``prefill`` returns the training forward's K/V (it shares
        ``_attention``); the decode step splits the stored layout
        itself: the two caches agree, at the cache-parity test's b, s."""
        cfg = _cfg(hidden_size=256, **variant)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (2, 10)), jnp.int32)
        cache = init_kv_cache(cfg, 2, 10)
        for i in range(10):
            _, cache = decode_step(params, tokens[:, i], cache, cfg)
        _, pcache = prefill(params, tokens, cfg)
        for name in "kv":
            np.testing.assert_allclose(
                np.asarray(pcache[name]), np.asarray(cache[name]),
                atol=2e-4, rtol=2e-4, err_msg=name)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_loss_and_grads_match_the_dense_backend(self, variant):
        """``gpt_loss`` and its gradients in bfloat16 on one seed, flash
        kernels against the XLA scores-softmax-values composition: equal
        to bfloat16 rounding, the ``qkv_kernel`` gradient in the stored
        layout included."""
        import dataclasses

        from apex_tpu.models.transformer_lm import gpt_loss

        cfg = _cfg(hidden_size=256, compute_dtype=jnp.bfloat16, **variant)
        params = init_gpt_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.RandomState(1)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 24)),
                             jnp.int32)
        labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 24)),
                             jnp.int32)

        def run(backend):
            c = dataclasses.replace(cfg, attention_backend=backend)
            return jax.value_and_grad(
                lambda p: gpt_loss(p, tokens, labels, c))(params)

        (l1, g1), (l2, g2) = run("flash"), run("fused_softmax")
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-2)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(g1),
                jax.tree_util.tree_leaves(g2)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(b) + 1e-6, (
                jax.tree_util.keystr(path))
