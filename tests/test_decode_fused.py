"""ops/decode_step.py: fused decode-layer megakernel (ISSUE 17).

The tentpole acceptance pins: the Pallas rope + quantized-KV paged
attention + output-projection kernel must match the XLA reference
composition at ragged lengths that straddle block boundaries
(``len % block_size ∈ {0, 1, block_size−1}``) across MHA/GQA/MQA and
both ``cache_wire`` forms, fp32 tight and bf16 loose; ``generate()``
routed through the kernel must be greedy token-identical to the
reference route on both cache layouts, composing with speculative
decoding and the serving engine's preempt→resume cycle; and a bad
``backend=`` must fail loudly by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.generate import generate
from apex_tpu.ops import decode_step as decode_step_mod
from apex_tpu.models.transformer_lm import init_gpt_params
from apex_tpu.ops.decode_step import (
    decode_layer_reference, fused_decode_layer, route_decode_fused)
from apex_tpu.serving.paged_cache import quantize_kv


def _cfg(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_position_embeddings", 64)
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


def _case(rng, *, b, mb, nb, bs, nh, g, dh, lens, h_out=None,
          dtype=jnp.float32, rope=True, quant=False):
    """Random pools + per-row block tables + rope rows + projection —
    the full fused-layer argument set (the paged-attention ``_case``
    plus the layer-level pieces)."""
    h_out = nh * dh if h_out is None else h_out
    kp = jnp.asarray(rng.randn(nb, bs, g, dh), dtype)
    vp = jnp.asarray(rng.randn(nb, bs, g, dh), dtype)
    q = jnp.asarray(rng.randn(b, nh, dh), dtype)
    w = jnp.asarray(rng.randn(nh * dh, h_out) / (nh * dh) ** 0.5, dtype)
    order = rng.permutation(nb)
    tbl = np.full((b, mb), nb + 3, np.int32)      # sentinel past nb
    used = 0
    for i, n in enumerate(lens):
        k = -(-n // bs)
        tbl[i, :k] = order[used: used + k]
        used += k
    assert used <= nb, "test geometry needs more pool blocks"
    kw = dict(k_scale=None, v_scale=None)
    if quant:
        kp, kw["k_scale"] = quantize_kv(kp)
        vp, kw["v_scale"] = quantize_kv(vp)
    if rope:
        theta = rng.uniform(-np.pi, np.pi, (b, dh))
        kw["rope_cos"] = jnp.asarray(np.cos(theta), dtype)
        kw["rope_sin"] = jnp.asarray(np.sin(theta), dtype)
    return (q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens, jnp.int32),
            w), kw


class TestKernelParity:
    """Kernel (interpret path, same as every other Pallas suite here)
    vs the XLA reference at boundary-straddling ragged lengths."""

    @pytest.mark.parametrize("quant", [False, True],
                             ids=["native", "int8"])
    @pytest.mark.parametrize("nh,g", [(4, 4), (8, 2), (4, 1)],
                             ids=["mha", "gqa", "mqa"])
    def test_block_boundary_lengths_fp32(self, nh, g, quant,
                                         monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        bs = 8
        rng = np.random.RandomState(0)
        args, kw = _case(rng, b=4, mb=4, nb=16, bs=bs, nh=nh, g=g,
                         dh=64, lens=[2 * bs, 2 * bs + 1, 3 * bs - 1, 1],
                         quant=quant)
        ref = decode_layer_reference(*args, **kw)
        ker = fused_decode_layer(*args, backend="kernel", **kw)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_parity_loose(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        bs = 8
        rng = np.random.RandomState(1)
        args, kw = _case(rng, b=3, mb=3, nb=12, bs=bs, nh=4, g=2,
                         dh=64, lens=[bs, bs + 1, 2 * bs - 1],
                         dtype=jnp.bfloat16)
        ref = decode_layer_reference(*args, **kw)
        ker = fused_decode_layer(*args, backend="kernel", **kw)
        assert ker.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(ker, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2)

    def test_no_rope_path(self, monkeypatch):
        """rope_cos/sin=None skips rotation in BOTH paths (the
        learned-position configs)."""
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.RandomState(2)
        args, kw = _case(rng, b=2, mb=2, nb=6, bs=4, nh=4, g=4, dh=64,
                         lens=[5, 8], rope=False)
        ref = decode_layer_reference(*args, **kw)
        ker = fused_decode_layer(*args, backend="kernel", **kw)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_narrow_projection(self, monkeypatch):
        """h_out != nh*dh — the projection tile is not square."""
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.RandomState(3)
        args, kw = _case(rng, b=2, mb=2, nb=6, bs=4, nh=4, g=2, dh=64,
                         lens=[4, 7], h_out=96)
        ref = decode_layer_reference(*args, **kw)
        ker = fused_decode_layer(*args, backend="kernel", **kw)
        assert ker.shape == (2, 96)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestRouting:
    def test_bad_backend_raises_by_name(self):
        with pytest.raises(ValueError, match="fused decode.*backend"):
            route_decode_fused("fused")

    def test_auto_follows_interpret(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        assert route_decode_fused("auto") == "kernel"
        monkeypatch.delenv("APEX_TPU_PALLAS_INTERPRET", raising=False)
        from apex_tpu.ops.decode_step import on_tpu
        if not on_tpu():
            assert route_decode_fused("auto") == "reference"


class TestShapeChecks:
    def _args(self):
        rng = np.random.RandomState(4)
        return _case(rng, b=2, mb=2, nb=6, bs=4, nh=4, g=4, dh=64,
                     lens=[4, 6])

    def test_quantized_weight_slab_rejected(self):
        args, kw = self._args()
        q, kp, vp, tbl, lens, w = args
        slab = {"wire": w, "scales": jnp.ones((1,))}
        with pytest.raises(ValueError, match="quantized weight slab"):
            fused_decode_layer(q, kp, vp, tbl, lens, slab, **kw)

    def test_wrong_projection_shape(self):
        args, kw = self._args()
        q, kp, vp, tbl, lens, w = args
        with pytest.raises(ValueError, match="w_proj"):
            fused_decode_layer(q, kp, vp, tbl, lens, w[:-1], **kw)

    def test_rope_rows_must_pair_and_match(self):
        args, kw = self._args()
        q, kp, vp, tbl, lens, w = args
        with pytest.raises(ValueError, match="together"):
            fused_decode_layer(q, kp, vp, tbl, lens, w,
                               rope_cos=kw["rope_cos"])
        with pytest.raises(ValueError, match="rope rows"):
            fused_decode_layer(q, kp, vp, tbl, lens, w,
                               rope_cos=kw["rope_cos"][:1],
                               rope_sin=kw["rope_sin"][:1])

    def test_odd_rotary_dim(self):
        args, kw = self._args()
        q, kp, vp, tbl, lens, w = args
        with pytest.raises(ValueError, match="rotary dim"):
            fused_decode_layer(q, kp, vp, tbl, lens, w,
                               rope_cos=kw["rope_cos"][:, :3],
                               rope_sin=kw["rope_sin"][:, :3])


class TestGenerateTokenIdentity:
    """The end-to-end acceptance pin: generate() routed through the
    fused kernel is greedy token-identical to the reference route on
    both cache layouts and both cache_wire forms.  ``generate()`` takes
    no route: the test pins what it resolves (``route_decode_fused``,
    looked up on each call) and leaves every other kernel interpreted
    on both sides."""

    def _run(self, monkeypatch, route, **gen_kw):
        cfg = _cfg(position_embedding_type="rope", num_query_groups=2)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        lens = [3, 9, 6]
        batch = np.zeros((3, max(lens)), np.int32)
        for i, n in enumerate(lens):
            batch[i, :n] = rng.randint(0, cfg.vocab_size, (n,))
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(decode_step_mod, "route_decode_fused",
                            lambda backend: route)
        return np.asarray(generate(
            params, jnp.asarray(batch), cfg, max_new_tokens=7,
            prompt_lens=jnp.asarray(lens), **gen_kw))

    @pytest.mark.parametrize("gen_kw", [
        dict(cache_layout="paged", block_size=4),
        dict(cache_layout="paged", block_size=4, cache_wire="int8"),
        dict(cache_layout="contiguous"),
    ], ids=["paged-native", "paged-int8", "contiguous"])
    def test_fused_matches_reference(self, monkeypatch, gen_kw):
        want = self._run(monkeypatch, "reference", **gen_kw)
        got = self._run(monkeypatch, "kernel", **gen_kw)
        np.testing.assert_array_equal(got, want)

    def test_spec_decode_composes(self, monkeypatch):
        """Fused route under speculative decoding: the verify forward
        stays unfused (multi-token), the per-token decode fuses —
        greedy output is still token-identical."""
        kw = dict(cache_layout="paged", block_size=4, spec="ngram")
        want = self._run(monkeypatch, "reference", **kw)
        got = self._run(monkeypatch, "kernel", **kw)
        np.testing.assert_array_equal(got, want)


class TestServingComposition:
    def test_preempt_resume_fused_parity(self, monkeypatch):
        """Fused decode inside the serving engine survives a
        preempt→resume cycle token-for-token against solo generate()
        on the SAME route."""
        from apex_tpu.serving import ServingEngine

        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        cfg = _cfg(position_embedding_type="rope", num_query_groups=2)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(7)
        p1 = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        p2 = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        # 6 blocks of 4: both admit, both outgrow the pool mid-decode
        # -> the youngest gets preempted and later resumes
        engine = ServingEngine(params, cfg, max_slots=2, max_len=32,
                               prompt_buckets=(8,),
                               cache_layout="paged", block_size=4,
                               num_blocks=6, reserve_blocks=0)
        assert engine.stats()["decode_fused"] == "kernel"
        resps = engine.run([dict(prompt=p1, max_new_tokens=10),
                            dict(prompt=p2, max_new_tokens=10)])
        for r, p in zip(resps, (p1, p2)):
            solo = np.asarray(generate(
                params, jnp.asarray(p[None]), cfg,
                max_new_tokens=10))[0, 6:]
            np.testing.assert_array_equal(
                r.tokens, solo, err_msg=f"request {r.request_id}")
        assert engine.idle
