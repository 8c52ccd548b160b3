"""ops/paged_attention.py: fused kernel vs XLA gather reference.

The acceptance pin of ISSUE 6's kernel half: the Pallas
ragged-paged-attention kernel (block tables dereferenced in the
BlockSpec index maps, online softmax across block steps) must match the
materialized-gather reference at ragged lengths that straddle block
boundaries — ``len % block_size ∈ {0, 1, block_size−1}`` — in fp32
tight and bf16 loose, MHA and GQA, on the interpret path the existing
kernel tests use."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.paged_attention import (
    paged_attention_reference, ragged_paged_attention)


def _case(rng, *, b, mb, nb, bs, nh, g, dh, lens, dtype=jnp.float32,
          shuffle=True):
    """Random pool + per-row block tables over distinct blocks; rows
    own ``ceil(len/bs)`` mapped entries, the rest are unmapped
    sentinels (>= nb)."""
    kp = jnp.asarray(rng.randn(nb, bs, g, dh), dtype)
    vp = jnp.asarray(rng.randn(nb, bs, g, dh), dtype)
    q = jnp.asarray(rng.randn(b, nh, dh), dtype)
    order = rng.permutation(nb) if shuffle else np.arange(nb)
    tbl = np.full((b, mb), nb + 3, np.int32)   # sentinel well past nb
    used = 0
    for i, n in enumerate(lens):
        k = -(-n // bs)
        tbl[i, :k] = order[used: used + k]
        used += k
    assert used <= nb, "test geometry needs more pool blocks"
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens, jnp.int32)


class TestKernelParity:
    @pytest.mark.parametrize("nh,g", [(4, 4), (8, 2), (4, 1)])
    def test_block_boundary_lengths_fp32(self, nh, g):
        """lens straddle every boundary class: bs-aligned, one past,
        one short — the ragged tail masking and whole-block skip."""
        bs = 8
        rng = np.random.RandomState(0)
        q, kp, vp, tbl, lens = _case(
            rng, b=4, mb=4, nb=16, bs=bs, nh=nh, g=g, dh=64,
            lens=[2 * bs, 2 * bs + 1, 3 * bs - 1, 1])
        ref = paged_attention_reference(q, kp, vp, tbl, lens)
        ker = ragged_paged_attention(q, kp, vp, tbl, lens,
                                     backend="kernel")
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_parity_loose(self):
        bs = 8
        rng = np.random.RandomState(1)
        q, kp, vp, tbl, lens = _case(
            rng, b=3, mb=3, nb=12, bs=bs, nh=4, g=2, dh=64,
            lens=[bs, bs + 1, 2 * bs - 1], dtype=jnp.bfloat16)
        ref = paged_attention_reference(q, kp, vp, tbl, lens)
        ker = ragged_paged_attention(q, kp, vp, tbl, lens,
                                     backend="kernel")
        np.testing.assert_allclose(
            np.asarray(ker, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2)

    def test_scrambled_tables_match_contiguous_layout(self):
        """The same K/V reached through shuffled blocks must score
        identically to an identity-table layout — attention depends on
        the logical sequence, never on physical block placement."""
        bs, b, dh, nh, g = 4, 2, 64, 4, 2
        rng = np.random.RandomState(2)
        lens = [11, 7]
        nb = 8
        # identity layout: row i owns blocks [i*4, i*4+4)
        kp = jnp.asarray(rng.randn(nb, bs, g, dh), jnp.float32)
        vp = jnp.asarray(rng.randn(nb, bs, g, dh), jnp.float32)
        q = jnp.asarray(rng.randn(b, nh, dh), jnp.float32)
        ident = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
        perm = np.asarray(rng.permutation(nb), np.int32)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(nb)
        kp2 = kp[jnp.asarray(perm)]
        vp2 = vp[jnp.asarray(perm)]
        scrambled = jnp.asarray(inv)[ident]
        lens_j = jnp.asarray(lens, jnp.int32)
        a = ragged_paged_attention(q, kp, vp, ident, lens_j,
                                   backend="kernel")
        bb = ragged_paged_attention(q, kp2, vp2, scrambled, lens_j,
                                    backend="kernel")
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=1e-6, rtol=1e-6)

    def test_matches_dense_masked_attention(self):
        """Reference-vs-first-principles: an identity table must equal
        a plain masked softmax over the flattened pool rows."""
        bs, dh = 4, 64
        rng = np.random.RandomState(3)
        q, kp, vp, tbl, lens = _case(
            rng, b=2, mb=3, nb=6, bs=bs, nh=2, g=2, dh=dh,
            lens=[9, 5], shuffle=False)
        out = paged_attention_reference(q, kp, vp, tbl, lens)
        for i, n in enumerate(np.asarray(lens)):
            blocks = np.asarray(tbl)[i, : -(-int(n) // bs)]
            k = np.asarray(kp)[blocks].reshape(-1, 2, dh)[:n]
            v = np.asarray(vp)[blocks].reshape(-1, 2, dh)[:n]
            qi = np.asarray(q)[i]                     # [nh=2, dh], g=2
            s = np.einsum("hd,thd->ht", qi, k) / np.sqrt(dh)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want = np.einsum("ht,thd->hd", p, v)
            np.testing.assert_allclose(np.asarray(out)[i], want,
                                       atol=2e-5, rtol=2e-5)


class TestInt8PoolParity:
    """ISSUE 14: the dequantizing kernel (scales dereferenced through
    the same table index map, dequant in VMEM) vs the gather+dequant
    reference — the same tail-block geometries as the float suite."""

    def _quant_case(self, rng, *, b, mb, nb, bs, nh, g, dh, lens,
                    dtype=jnp.float32):
        from apex_tpu.serving.paged_cache import quantize_kv

        q, kp, vp, tbl, lens_j = _case(
            rng, b=b, mb=mb, nb=nb, bs=bs, nh=nh, g=g, dh=dh,
            lens=lens, dtype=dtype)
        kq, ks = quantize_kv(kp)
        vq, vs = quantize_kv(vp)
        return q, kp, vp, kq, ks, vq, vs, tbl, lens_j

    @pytest.mark.parametrize("nh,g", [(4, 4), (8, 2), (4, 1)])
    def test_block_boundary_lengths_fp32(self, nh, g):
        """lens straddle every boundary class: bs-aligned, one past,
        one short — kernel == dequantizing reference fp32-tight."""
        bs = 8
        rng = np.random.RandomState(20)
        (q, _kp, _vp, kq, ks, vq, vs, tbl, lens) = self._quant_case(
            rng, b=4, mb=4, nb=16, bs=bs, nh=nh, g=g, dh=64,
            lens=[2 * bs, 2 * bs + 1, 3 * bs - 1, 1])
        ref = paged_attention_reference(q, kq, vq, tbl, lens,
                                        k_scale=ks, v_scale=vs)
        ker = ragged_paged_attention(q, kq, vq, tbl, lens,
                                     backend="kernel",
                                     k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_quantization_error_bounded_vs_float_pool(self):
        """The dequantized attention tracks the float-pool oracle
        within the per-(token, group) int8 budget — loose, but a real
        bound: a broken scale layout shows up as O(1) error."""
        bs = 8
        rng = np.random.RandomState(21)
        (q, kp, vp, kq, ks, vq, vs, tbl, lens) = self._quant_case(
            rng, b=3, mb=3, nb=12, bs=bs, nh=4, g=2, dh=64,
            lens=[bs, bs + 1, 2 * bs - 1])
        full = paged_attention_reference(q, kp, vp, tbl, lens)
        quant = ragged_paged_attention(q, kq, vq, tbl, lens,
                                       backend="kernel",
                                       k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(quant), np.asarray(full),
                                   atol=5e-2, rtol=5e-2)

    def test_bf16_queries_loose(self):
        bs = 8
        rng = np.random.RandomState(22)
        (q, _kp, _vp, kq, ks, vq, vs, tbl, lens) = self._quant_case(
            rng, b=2, mb=3, nb=8, bs=bs, nh=4, g=2, dh=64,
            lens=[2 * bs, bs + 1], dtype=jnp.bfloat16)
        ref = paged_attention_reference(q, kq, vq, tbl, lens,
                                        k_scale=ks, v_scale=vs)
        ker = ragged_paged_attention(q, kq, vq, tbl, lens,
                                     backend="kernel",
                                     k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(
            np.asarray(ker, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2)

    def test_scale_validation(self):
        rng = np.random.RandomState(23)
        (q, kp, vp, kq, ks, vq, vs, tbl, lens) = self._quant_case(
            rng, b=2, mb=2, nb=4, bs=4, nh=2, g=2, dh=64, lens=[5, 3])
        with pytest.raises(ValueError, match="int8 pools need"):
            ragged_paged_attention(q, kq, vq, tbl, lens)
        with pytest.raises(ValueError, match="only apply to int8"):
            ragged_paged_attention(q, kp, vp, tbl, lens,
                                   k_scale=ks, v_scale=vs)
        with pytest.raises(ValueError, match="expected scales"):
            ragged_paged_attention(q, kq, vq, tbl, lens,
                                   k_scale=ks[:, :2], v_scale=vs)


class TestRoutingAndValidation:
    def test_backend_routing(self, monkeypatch):
        rng = np.random.RandomState(4)
        q, kp, vp, tbl, lens = _case(
            rng, b=2, mb=2, nb=4, bs=4, nh=2, g=2, dh=64, lens=[5, 3])
        # off-TPU auto == reference; forced interpret == kernel
        auto = ragged_paged_attention(q, kp, vp, tbl, lens)
        ref = ragged_paged_attention(q, kp, vp, tbl, lens,
                                     backend="reference")
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        ker = ragged_paged_attention(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        with pytest.raises(ValueError, match="backend"):
            ragged_paged_attention(q, kp, vp, tbl, lens,
                                   backend="nonsense")

    def test_shape_validation(self):
        q = jnp.zeros((2, 4, 64))
        kp = jnp.zeros((4, 8, 2, 64))
        tbl = jnp.zeros((2, 2), jnp.int32)
        lens = jnp.zeros((2,), jnp.int32)
        with pytest.raises(ValueError, match="one decode token"):
            ragged_paged_attention(q[:, :, None], kp, kp, tbl, lens)
        with pytest.raises(ValueError, match="multiple"):
            ragged_paged_attention(jnp.zeros((2, 3, 64)), kp, kp, tbl,
                                   lens)
        with pytest.raises(ValueError, match="block_tables"):
            ragged_paged_attention(q, kp, kp, tbl[:1], lens)
        with pytest.raises(ValueError, match="lengths"):
            ragged_paged_attention(q, kp, kp, tbl, lens[:1])
        with pytest.raises(ValueError, match="head dim"):
            ragged_paged_attention(jnp.zeros((2, 4, 32)), kp, kp, tbl,
                                   lens)
