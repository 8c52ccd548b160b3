"""Weight-only int8 quantized matmuls (ISSUE 14): ops/dense.py's
block-scaled slab path and ops/grouped_matmul.py's expert-slab path —
kernel-vs-reference parity (fp32 tight / bf16 loose, interpret path on
the 8-virtual-device mesh), the high-precision custom VJP, the
``backend=`` routing, quantize_params over the model
family, and the fake-quant oracle pin
(``generate(quantize_params(p)) == generate(dequantize_params(...))``
greedy token-for-token — the int8 path computes exactly what it
claims)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.dense import (
    dense_quantized, dequantize_weight, is_quantized, pick_quant_block,
    quantize_weight, quantized_matmul)
from apex_tpu.ops.grouped_matmul import (
    _dequantize_group, grouped_matmul, grouped_matmul_quantized,
    quantize_group_weights)


class TestQuantizeWeight:
    def test_round_trip_error_bounded(self):
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(96, 40) * 0.3, jnp.float32)
        qw = quantize_weight(w, block=32)
        assert qw["wire"].dtype == jnp.int8
        assert qw["scale"].shape == (3, 40)
        deq = dequantize_weight(qw["wire"], qw["scale"])
        # symmetric RTN: |w - deq| <= scale/2 per element
        bound = np.repeat(np.asarray(qw["scale"]), 32, axis=0) / 2
        assert (np.abs(np.asarray(deq - w)) <= bound + 1e-7).all()

    def test_zero_columns_exact(self):
        w = jnp.zeros((64, 8), jnp.float32)
        qw = quantize_weight(w)
        np.testing.assert_array_equal(
            np.asarray(dequantize_weight(qw["wire"], qw["scale"])), 0.0)
        # all-zero block -> scale 1 (the comm/quantize contract)
        np.testing.assert_array_equal(np.asarray(qw["scale"]), 1.0)

    def test_pick_block_divides(self):
        assert pick_quant_block(96, 128) == 96
        assert pick_quant_block(256, 128) == 128
        assert pick_quant_block(100, 128) == 100
        assert pick_quant_block(7, 128) == 7
        with pytest.raises(ValueError, match="positive"):
            pick_quant_block(64, 0)

    def test_is_quantized(self):
        w = jnp.ones((8, 4))
        assert not is_quantized(w)
        assert is_quantized(quantize_weight(w))


class TestDenseParity:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    def test_kernel_vs_reference(self, dtype, tol):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(37, 96), dtype)   # ragged row count
        w = jnp.asarray(rng.randn(96, 40) * 0.3, jnp.float32)
        qw = quantize_weight(w, block=32)
        ref = dense_quantized(x, qw["wire"], qw["scale"],
                              backend="reference")
        ker = dense_quantized(x, qw["wire"], qw["scale"],
                              backend="kernel")
        assert ref.dtype == dtype and ker.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(ker, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)

    def test_matches_fake_quant_matmul(self):
        """The quantized path computes exactly x @ dequantize(w) —
        the claim the fake-quant generate pin scales up."""
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(5, 64), jnp.float32)
        w = jnp.asarray(rng.randn(64, 24) * 0.3, jnp.float32)
        qw = quantize_weight(w, block=16)
        deq = dequantize_weight(qw["wire"], qw["scale"])
        out = dense_quantized(x, qw["wire"], qw["scale"],
                              backend="reference")
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ deq),
                                   atol=1e-6, rtol=1e-6)

    def test_swiglu_paired_3d_kernel(self):
        """[h, 2, f] paired kernels flatten for the GEMM and restore
        on the output — the _mlp swiglu drop-in."""
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(4, 6, 64), jnp.float32)
        w = jnp.asarray(rng.randn(64, 2, 24) * 0.3, jnp.float32)
        qw = quantize_weight(w, block=32)
        out = dense_quantized(x, qw["wire"], qw["scale"],
                              backend="kernel")
        assert out.shape == (4, 6, 2, 24)
        want = jnp.einsum("bsh,hcf->bscf", x,
                          dequantize_weight(qw["wire"], qw["scale"]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_backward_high_precision(self):
        """dx flows against the fp32-dequantized weights (both
        routes); the frozen wire/scales take no gradient."""
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(6, 64), jnp.float32)
        w = jnp.asarray(rng.randn(64, 16) * 0.3, jnp.float32)
        qw = quantize_weight(w, block=16)
        deq = dequantize_weight(qw["wire"], qw["scale"])
        want = jax.grad(lambda x: jnp.sum((x @ deq) ** 2))(x)
        for backend in ("reference", "kernel"):
            got = jax.grad(lambda x: jnp.sum(dense_quantized(
                x, qw["wire"], qw["scale"], backend=backend) ** 2))(x)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       atol=5e-5, rtol=5e-5)
        ds = jax.grad(lambda s: jnp.sum(dense_quantized(
            x, qw["wire"], s, backend="reference")))(qw["scale"])
        np.testing.assert_array_equal(np.asarray(ds), 0.0)

    def test_plain_leaf_passthrough_bitwise(self):
        """quantized_matmul over a float array is byte-identical to
        the historical `x @ w.astype(x.dtype)` site."""
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(3, 32), jnp.bfloat16)
        w = jnp.asarray(rng.randn(32, 8), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(quantized_matmul(x, w), np.float32),
            np.asarray(x @ w.astype(x.dtype), np.float32))

    def test_validation(self):
        x = jnp.zeros((4, 32))
        qw = quantize_weight(jnp.ones((16, 8)))
        with pytest.raises(ValueError, match="contraction mismatch"):
            dense_quantized(x, qw["wire"], qw["scale"])
        with pytest.raises(ValueError, match="do not tile"):
            dense_quantized(jnp.zeros((4, 16)), qw["wire"],
                            jnp.ones((3, 8)))
        with pytest.raises(ValueError, match="expects"):
            quantize_weight(jnp.ones((8,)))


class TestRouting:
    def test_auto_routes_and_rejects(self, monkeypatch):
        rng = np.random.RandomState(6)
        x = jnp.asarray(rng.randn(4, 32), jnp.float32)
        qw = quantize_weight(jnp.asarray(rng.randn(32, 8), jnp.float32))
        # off-TPU auto == reference (bitwise)
        auto = dense_quantized(x, qw["wire"], qw["scale"])
        ref = dense_quantized(x, qw["wire"], qw["scale"],
                              backend="reference")
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        ker = dense_quantized(x, qw["wire"], qw["scale"])
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        with pytest.raises(ValueError, match="backend"):
            dense_quantized(x, qw["wire"], qw["scale"],
                            backend="nonsense")


class TestGroupedParity:
    def _case(self, rng, G=3, k=64, p=48, N=40):
        x = jnp.asarray(rng.randn(N, k), jnp.float32)
        w = jnp.asarray(rng.randn(G, k, p) * 0.3, jnp.float32)
        return x, w, quantize_group_weights(w, block=16)

    @pytest.mark.parametrize("off", [
        [0, 12, 12, 40],          # one empty group
        [0, 40, 40, 40],          # everything on one expert
        [0, 1, 20, 40],           # singleton segment
    ])
    def test_kernel_vs_reference_segment_layouts(self, off):
        rng = np.random.RandomState(7)
        x, w, qw = self._case(rng)
        offs = jnp.asarray(off, jnp.int32)
        ref = grouped_matmul_quantized(x, qw["wire"], qw["scale"], offs,
                                       backend="reference")
        ker = grouped_matmul_quantized(x, qw["wire"], qw["scale"], offs,
                                       backend="kernel")
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        # and against the float primitive over the dequantized slab
        want = grouped_matmul(x, _dequantize_group(qw["wire"],
                                                   qw["scale"]),
                              offs, backend="reference")
        np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_window_offsets_zero_outside(self):
        rng = np.random.RandomState(8)
        x, w, qw = self._case(rng)
        offs = jnp.asarray([8, 20, 20, 32], jnp.int32)
        for backend in ("reference", "kernel"):
            out = grouped_matmul_quantized(
                x, qw["wire"], qw["scale"], offs, backend=backend)
            np.testing.assert_array_equal(np.asarray(out[:8]), 0.0)
            np.testing.assert_array_equal(np.asarray(out[32:]), 0.0)

    def test_bf16_loose(self):
        rng = np.random.RandomState(9)
        x, w, qw = self._case(rng)
        xb = x.astype(jnp.bfloat16)
        offs = jnp.asarray([0, 16, 28, 40], jnp.int32)
        ref = grouped_matmul_quantized(xb, qw["wire"], qw["scale"],
                                       offs, backend="reference")
        ker = grouped_matmul_quantized(xb, qw["wire"], qw["scale"],
                                       offs, backend="kernel")
        assert ref.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(ker, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2)

    def test_backward_high_precision(self):
        rng = np.random.RandomState(10)
        x, w, qw = self._case(rng)
        offs = jnp.asarray([0, 16, 28, 40], jnp.int32)
        deq = _dequantize_group(qw["wire"], qw["scale"])
        want = jax.grad(lambda x: jnp.sum(grouped_matmul(
            x, deq, offs, backend="reference") ** 2))(x)
        got = jax.grad(lambda x: jnp.sum(grouped_matmul_quantized(
            x, qw["wire"], qw["scale"], offs,
            backend="reference") ** 2))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)

    def test_validation(self):
        x = jnp.zeros((8, 16))
        qw = quantize_group_weights(jnp.ones((2, 16, 4)))
        with pytest.raises(ValueError, match="offsets length"):
            grouped_matmul_quantized(x, qw["wire"], qw["scale"],
                                     jnp.zeros((4,), jnp.int32))
        with pytest.raises(ValueError, match="does not tile"):
            grouped_matmul_quantized(x, qw["wire"], jnp.ones((2, 3, 4)),
                                     jnp.zeros((3,), jnp.int32))
        with pytest.raises(ValueError, match="expects"):
            quantize_group_weights(jnp.ones((16, 4)))


class TestQuantizedParams:
    def _model(self, activation="gelu"):
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.transformer_lm import init_gpt_params

        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.float32, remat=False,
            activation=activation)
        return cfg, init_gpt_params(jax.random.PRNGKey(0), cfg)

    @pytest.mark.parametrize("activation", ["gelu", "swiglu"])
    def test_fake_quant_oracle_greedy_identical(self, activation):
        """THE correctness pin: generation off the int8 slabs is
        token-identical to a float model holding the dequantized
        weights — int8 changed the bytes, not the math."""
        from apex_tpu.models.generate import generate
        from apex_tpu.models.quantized import (
            dequantize_params, quantize_params)

        cfg, params = self._model(activation)
        qp = quantize_params(params)
        fq = dequantize_params(qp)
        rng = np.random.RandomState(11)
        prompt = jnp.asarray(rng.randint(0, 128, (2, 9)), jnp.int32)
        out_q = np.asarray(generate(qp, prompt, cfg, max_new_tokens=8))
        out_fq = np.asarray(generate(fq, prompt, cfg, max_new_tokens=8))
        np.testing.assert_array_equal(out_q, out_fq)

    def test_bytes_shrink_and_structure(self):
        from apex_tpu.models.quantized import (
            is_quantized_tree, param_bytes, quantize_params)

        cfg, params = self._model()
        qp = quantize_params(params)
        assert is_quantized_tree(qp) and not is_quantized_tree(params)
        assert is_quantized(qp["layers"]["qkv_kernel"])
        assert qp["layers"]["qkv_kernel"]["wire"].dtype == jnp.int8
        # embedding/head stay float (gather + tied head, documented)
        assert not is_quantized(qp["embedding"]["word"])
        # layer kernels dominate this config, so the tree shrinks hard
        assert param_bytes(qp) < 0.5 * param_bytes(params)
        with pytest.raises(ValueError, match="already quantized"):
            quantize_params(qp)

    def test_prefill_logits_close(self):
        """Quantized-weight prefill tracks the float forward within
        the int8 weight budget (loose — the bound is a sanity rail,
        the exact pin is the fake-quant oracle)."""
        from apex_tpu.models.generate import prefill
        from apex_tpu.models.quantized import quantize_params

        cfg, params = self._model()
        rng = np.random.RandomState(12)
        prompt = jnp.asarray(rng.randint(0, 128, (2, 12)), jnp.int32)
        lg_f, _ = prefill(params, prompt, cfg)
        lg_q, _ = prefill(quantize_params(params), prompt, cfg)
        np.testing.assert_allclose(np.asarray(lg_q), np.asarray(lg_f),
                                   atol=0.5, rtol=0.5)

    def test_manual_tp_rejects_quantized(self):
        """The quantized tree is a serving artifact: the manual-TP
        forward refuses it loudly instead of sharding dict leaves."""
        from apex_tpu.models.quantized import quantize_params
        from apex_tpu.models.transformer_lm import _attention

        cfg, params = self._model()
        qp = quantize_params(params)

        class _FakeTP:
            tp = 2
            tp_axis = "tp"

            def copy_in(self, x):
                return x

        lp = jax.tree_util.tree_map(lambda x: x[0], qp["layers"])
        with pytest.raises(ValueError, match="single-device serving"):
            _attention(cfg, lp, jnp.zeros((1, 2, 64)), _FakeTP(),
                       None, None, None)


class TestQuantizedMoE:
    def test_ragged_quantized_slabs_match_fake_quant(self):
        from apex_tpu.transformer.moe import init_moe_params, \
            switch_moe_mlp

        params = init_moe_params(jax.random.PRNGKey(0), hidden_size=32,
                                 ffn_hidden_size=64, num_experts=4)
        x = jnp.asarray(np.random.RandomState(13).randn(2, 8, 32),
                        jnp.float32)
        qp = dict(params,
                  fc1=quantize_group_weights(params["fc1"], block=16),
                  fc2=quantize_group_weights(params["fc2"], block=16))
        fq = dict(params,
                  fc1=_dequantize_group(qp["fc1"]["wire"],
                                        qp["fc1"]["scale"]),
                  fc2=_dequantize_group(qp["fc2"]["wire"],
                                        qp["fc2"]["scale"]))
        out_q = switch_moe_mlp(qp, x, routing="ragged", ep_axis=None)
        out_fq = switch_moe_mlp(fq, x, routing="ragged", ep_axis=None)
        np.testing.assert_allclose(np.asarray(out_q.out),
                                   np.asarray(out_fq.out),
                                   atol=1e-5, rtol=1e-5)
        # zero drops still holds on the quantized path
        assert float(out_q.dropped_fraction) == 0.0

    def test_capacity_routing_rejected(self):
        from apex_tpu.transformer.moe import init_moe_params, \
            switch_moe_mlp

        params = init_moe_params(jax.random.PRNGKey(0), hidden_size=32,
                                 ffn_hidden_size=64, num_experts=4)
        qp = dict(params,
                  fc1=quantize_group_weights(params["fc1"]))
        x = jnp.zeros((2, 8, 32), jnp.float32)
        with pytest.raises(ValueError, match="routing='ragged'"):
            switch_moe_mlp(qp, x, routing="capacity", ep_axis=None)
