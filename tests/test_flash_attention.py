"""Flash attention kernel vs materialized reference.

Mirrors the reference fmha test pattern (apex/contrib/test/fmha/test_fmha.py:
fused kernel vs PyTorch-composed attention at loose fp16 tolerances).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import flash_attention, mha_reference


def make_qkv(b, s, n, d, dtype=jnp.float32, seed=0, sk=None):
    rng = np.random.RandomState(seed)
    sk = s if sk is None else sk
    q = jnp.asarray(rng.randn(b, s, n, d), dtype) * 0.5
    k = jnp.asarray(rng.randn(b, sk, n, d), dtype) * 0.5
    v = jnp.asarray(rng.randn(b, sk, n, d), dtype) * 0.5
    return q, k, v


TOL = dict(atol=2e-5, rtol=2e-5)
TOL_BF16 = dict(atol=2e-2, rtol=2e-2)


class TestForward:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", [(2, 128, 2, 64), (1, 384, 4, 32)])
    def test_matches_reference(self, causal, shape):
        q, k, v = make_qkv(*shape)
        got = flash_attention(q, k, v, causal=causal)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    def test_unaligned_seq_len(self):
        # seq 100 → padded to the 128-row block internally
        q, k, v = make_qkv(2, 100, 2, 64)
        got = flash_attention(q, k, v, causal=True)
        want = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    def test_cross_attention_lengths(self):
        q, k, v = make_qkv(2, 64, 2, 64, sk=192)
        got = flash_attention(q, k, v)
        want = mha_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    def test_key_padding_mask(self):
        b, s, n, d = 2, 128, 2, 64
        q, k, v = make_qkv(b, s, n, d)
        lengths = np.array([80, 128])
        kpm = jnp.asarray(
            np.arange(s)[None, :] >= lengths[:, None])
        got = flash_attention(q, k, v, key_padding_mask=kpm)
        want = mha_reference(q, k, v, key_padding_mask=kpm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    def test_bf16(self):
        q, k, v = make_qkv(2, 128, 2, 64, dtype=jnp.bfloat16)
        got = flash_attention(q, k, v, causal=True)
        want = mha_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want), **TOL_BF16)

    def test_generic_mask_falls_back(self):
        q, k, v = make_qkv(1, 64, 2, 32)
        mask = jnp.zeros((1, 1, 64, 64), bool).at[:, :, :, 10].set(True)
        got = flash_attention(q, k, v, mask=mask)
        want = mha_reference(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


class TestBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v = make_qkv(2, 128, 2, 64, seed=3)

        def f_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal)
            return jnp.sum(o * jnp.cos(o.astype(jnp.float32)))

        def f_ref(q, k, v):
            o = mha_reference(q, k, v, causal=causal)
            return jnp.sum(o * jnp.cos(o.astype(jnp.float32)))

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"d{name}")

    def test_grads_with_key_padding(self):
        b, s, n, d = 2, 128, 2, 32
        q, k, v = make_qkv(b, s, n, d, seed=4)
        kpm = jnp.asarray(np.arange(s)[None, :] >= np.array([96, 128])[:, None])

        g1 = jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, key_padding_mask=kpm)), argnums=(0, 1, 2))(
                q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(
            mha_reference(*a, key_padding_mask=kpm)), argnums=(0, 1, 2))(
                q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"d{name}")

    def test_grads_unaligned(self):
        q, k, v = make_qkv(1, 100, 2, 64, seed=5)
        g1 = jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, causal=True)), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(
            mha_reference(*a, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"d{name}")


class TestKernelDropout:
    """In-kernel attention dropout (hash-PRNG Philox analog).

    Mirrors the reference multihead_attn dropout checks: determinism per
    seed, correct keep statistics, and fwd/bwd mask consistency.
    """

    def test_dropout_deterministic_per_seed(self):
        q, k, v = make_qkv(2, 128, 2, 32, seed=10)
        rng = jax.random.PRNGKey(7)
        a = flash_attention(q, k, v, dropout_p=0.3, dropout_rng=rng)
        b = flash_attention(q, k, v, dropout_p=0.3, dropout_rng=rng)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = flash_attention(q, k, v, dropout_p=0.3,
                            dropout_rng=jax.random.PRNGKey(8))
        assert not np.allclose(np.asarray(a), np.asarray(c))

    def test_dropout_zero_equals_dense(self):
        q, k, v = make_qkv(2, 128, 2, 32, seed=11)
        base = flash_attention(q, k, v)
        out = flash_attention(q, k, v, dropout_p=0.0,
                              dropout_rng=jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(base), np.asarray(out), **TOL)

    def test_dropout_statistics_via_identity_values(self):
        """With v = I, rows of the output are the dropped attention
        probabilities: zero fraction ~ p, kept entries scaled 1/(1-p)."""
        b, s, n, d = 1, 128, 1, 128
        q, k, _ = make_qkv(b, s, n, d, seed=12)
        v = jnp.eye(d)[None, :, None, :]
        p_drop = 0.4
        out = flash_attention(q, k, v, dropout_p=p_drop,
                              dropout_rng=jax.random.PRNGKey(3))
        probs = flash_attention(q, k, v)  # dense P
        dense = np.asarray(probs, np.float64)
        dropped = np.asarray(out, np.float64)
        # kept entries = dense / (1-p): ratio is 1/(1-p) or 0
        ratio = dropped / np.maximum(dense, 1e-30)
        kept = ratio > 0.5
        np.testing.assert_allclose(
            ratio[kept], 1.0 / (1.0 - p_drop), rtol=1e-3)
        zero_frac = 1.0 - kept.mean()
        assert abs(zero_frac - p_drop) < 0.02, zero_frac

    def test_dropout_mask_consistent_fwd_bwd(self):
        """grad wrt v of sum(out) = column sums of dropped P — matches the
        forward-observed mask exactly if fwd/bwd regenerate the same
        bits."""
        b, s, n, d = 1, 128, 1, 128
        q, k, _ = make_qkv(b, s, n, d, seed=13)
        v = jnp.eye(d)[None, :, None, :]
        rng = jax.random.PRNGKey(5)
        p_drop = 0.25

        out = flash_attention(q, k, v, dropout_p=p_drop, dropout_rng=rng)
        P_dropped = np.asarray(out)[0, :, 0, :]  # [sq, sk]

        dv = jax.grad(lambda vv: jnp.sum(flash_attention(
            q, k, vv, dropout_p=p_drop, dropout_rng=rng)))(v)
        # dL/dv[t, e] = sum_q P_dropped[q, t] (same for every column e)
        col_sums = P_dropped.sum(axis=0)
        got = np.asarray(dv)[0, :, 0, :].mean(axis=-1)
        np.testing.assert_allclose(got, col_sums, atol=1e-5, rtol=1e-4)

    def test_dropout_grad_finite_differences(self):
        """Analytic grads match finite differences through the kernel
        (the dropout mask is deterministic given the seed)."""
        b, s, n, d = 1, 8, 1, 8
        q, k, v = make_qkv(b, s, n, d, seed=14)
        rng = jax.random.PRNGKey(9)

        def f(q_):
            return jnp.sum(jnp.sin(flash_attention(
                q_, k, v, dropout_p=0.3, dropout_rng=rng)))

        g = np.asarray(jax.grad(f)(q))
        eps = 1e-3
        rs = np.random.RandomState(0)
        for _ in range(5):
            i = tuple(rs.randint(x) for x in q.shape)
            dq = np.zeros(q.shape, np.float32)
            dq[i] = eps
            fd = (float(f(q + dq)) - float(f(q - dq))) / (2 * eps)
            np.testing.assert_allclose(fd, g[i], atol=5e-3, rtol=5e-2)

    def test_dropout_with_causal_and_padding(self):
        q, k, v = make_qkv(2, 96, 2, 32, seed=15)
        kpm = jnp.asarray(
            np.arange(96)[None, :] >= np.array([64, 96])[:, None])
        rng = jax.random.PRNGKey(11)
        out = flash_attention(q, k, v, causal=True, key_padding_mask=kpm,
                              dropout_p=0.2, dropout_rng=rng)
        assert np.all(np.isfinite(np.asarray(out)))
        grads = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, key_padding_mask=kpm, dropout_p=0.2,
            dropout_rng=rng)), argnums=(0, 1, 2))(q, k, v)
        for g in grads:
            assert np.all(np.isfinite(np.asarray(g)))

    def test_additive_key_padding_mask(self):
        """Float (additive) key_padding_mask — the reference MHA
        mask_additive mode — fused in-kernel."""
        b, s, n, d = 2, 128, 2, 32
        q, k, v = make_qkv(b, s, n, d, seed=16)
        add = np.zeros((b, s), np.float32)
        add[0, 100:] = -1e30
        add[1, 64:] = -1e30
        out_add = flash_attention(q, k, v,
                                  key_padding_mask=jnp.asarray(add))
        kpm = jnp.asarray(add < 0)
        out_bool = flash_attention(q, k, v, key_padding_mask=kpm)
        np.testing.assert_allclose(
            np.asarray(out_add), np.asarray(out_bool), **TOL)

    def test_fully_masked_sequence_zero_grads(self):
        """Regression: a fully padded sequence (all keys masked) must get
        exact-zero dk/dv and zero dq — the additive-mask bwd kernels must
        honor the lse sentinel, not recompute p = exp(0) = 1."""
        b, s, n, d = 2, 64, 2, 32
        q, k, v = make_qkv(b, s, n, d, seed=17)
        kpm = jnp.asarray(
            np.stack([np.ones(s, bool), np.zeros(s, bool)]))  # row0 all pad
        dq, dk, dv = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, key_padding_mask=kpm)), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_array_equal(np.asarray(dq)[0], 0.0)
        np.testing.assert_array_equal(np.asarray(dk)[0], 0.0)
        np.testing.assert_array_equal(np.asarray(dv)[0], 0.0)
        # the unmasked sequence still gets real gradients
        assert np.abs(np.asarray(dv)[1]).sum() > 0


class TestPackedSegments:
    """Packed multi-sequence (cu_seqlens / segment-id) attention — the
    reference fmha varlen mode (fmha_api.cpp:358, fmha.py:33-60)."""

    def _packed_case(self, lengths, n=2, d=32, seed=20, total=None):
        total = total if total is not None else sum(lengths)
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(total, n, d), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(total, n, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(total, n, d), jnp.float32) * 0.5
        cu = jnp.asarray(np.cumsum([0] + list(lengths)), jnp.int32)
        return q, k, v, cu

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_per_sequence(self, causal):
        from apex_tpu.ops.flash_attention import flash_attention_packed

        lengths = [60, 100, 96]
        q, k, v, cu = self._packed_case(lengths)
        out = flash_attention_packed(q, k, v, cu, causal=causal)
        # oracle: run each sequence separately through the dense ref
        start = 0
        for L in lengths:
            want = mha_reference(
                q[None, start:start + L], k[None, start:start + L],
                v[None, start:start + L], causal=causal)[0]
            np.testing.assert_allclose(
                np.asarray(out[start:start + L]), np.asarray(want),
                atol=3e-5, rtol=3e-5)
            start += L

    def test_padding_tail_isolated(self):
        from apex_tpu.ops.flash_attention import flash_attention_packed

        lengths = [50, 70]
        q, k, v, cu = self._packed_case(lengths, total=160)  # 40 pad slots
        out = flash_attention_packed(q, k, v, cu, causal=False)
        want = flash_attention_packed(
            q[:120], k[:120], v[:120], cu, causal=False)
        # valid positions are unaffected by whatever sits in the padding
        np.testing.assert_allclose(np.asarray(out[:120]),
                                   np.asarray(want), atol=3e-5, rtol=3e-5)

    def test_grads_match_per_sequence(self):
        from apex_tpu.ops.flash_attention import flash_attention_packed

        lengths = [40, 88]
        q, k, v, cu = self._packed_case(lengths)

        def packed_loss(q, k, v):
            o = flash_attention_packed(q, k, v, cu, causal=True)
            return jnp.sum(o * o)

        gq, gk, gv = jax.grad(packed_loss, argnums=(0, 1, 2))(q, k, v)

        start = 0
        for L in lengths:
            sl = slice(start, start + L)

            def seq_loss(qs, ks, vs):
                o = mha_reference(qs[None], ks[None], vs[None],
                                  causal=True)[0]
                return jnp.sum(o * o)

            rq, rk, rv = jax.grad(seq_loss, argnums=(0, 1, 2))(
                q[sl], k[sl], v[sl])
            np.testing.assert_allclose(np.asarray(gq[sl]), np.asarray(rq),
                                       atol=5e-5, rtol=5e-5)
            np.testing.assert_allclose(np.asarray(gk[sl]), np.asarray(rk),
                                       atol=5e-5, rtol=5e-5)
            np.testing.assert_allclose(np.asarray(gv[sl]), np.asarray(rv),
                                       atol=5e-5, rtol=5e-5)
            start += L

    def test_segment_ids_batched(self):
        """[b, s] segment ids on the 4-D API: two packed rows."""
        b, s, n, d = 2, 128, 2, 32
        q, k, v = make_qkv(b, s, n, d, seed=21)
        seg = np.zeros((b, s), np.int32)
        seg[0, 64:] = 1
        seg[1, 40:] = 1
        got = flash_attention(q, k, v, causal=True,
                              segment_ids=jnp.asarray(seg))
        want = mha_reference(q, k, v, causal=True,
                             segment_ids=jnp.asarray(seg))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL)

    def test_cu_seqlens_helper(self):
        from apex_tpu.ops.flash_attention import segment_ids_from_cu_seqlens

        cu = jnp.asarray([0, 3, 3, 7], jnp.int32)   # empty segment 1
        seg = segment_ids_from_cu_seqlens(cu, 9)
        np.testing.assert_array_equal(
            np.asarray(seg), [0, 0, 0, 2, 2, 2, 2, -1, -1])


class TestDropoutGradCorrectness:
    def test_dropout_grads_match_reference_with_same_mask(self):
        """Advisor round-2 finding: verify the dropout-path *gradients*
        against autodiff through a dense composition that applies the
        identical keep mask (reconstructed from the kernel's counter-based
        hash), catching any fwd/bwd scaling or coordinate mismatch."""
        from apex_tpu.ops.flash_attention import (
            _keep_mask, _seed_from_rng)

        b, s, n, d = 1, 128, 2, 32
        p_drop = 0.3
        q, k, v = make_qkv(b, s, n, d, seed=22)
        rng = jax.random.PRNGKey(5)
        seed = _seed_from_rng(rng)

        def fused_loss(q, k, v):
            o = flash_attention(q, k, v, dropout_p=p_drop, dropout_rng=rng)
            return jnp.sum(o * o)

        # dense composition with the SAME keep bits per (bh, row, col)
        def dense_loss(q, k, v):
            scale = 1.0 / d ** 0.5
            s_ = jnp.einsum("bsnd,btnd->bnst", q, k,
                            preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(s_, axis=-1)
            keeps = jnp.stack([
                _keep_mask(seed, jnp.int32(bh), 0, 0, (s, s), 1 - p_drop)
                for bh in range(b * n)]).reshape(b, n, s, s)
            p = jnp.where(keeps, p / (1 - p_drop), 0.0)
            o = jnp.einsum("bnst,btnd->bsnd", p.astype(v.dtype), v)
            return jnp.sum(o * o)

        gf = jax.grad(fused_loss, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for name, a, bb in zip("qkv", gf, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), atol=1e-4, rtol=1e-4,
                err_msg=f"d{name} mismatch under dropout")


class TestTHDIntegration:
    def test_thd_rope_feeds_packed_attention(self):
        """The THD RoPE layout (ops/rope.py) and the packed varlen kernel
        share the cu_seqlens descriptor — apply rotary embeddings per
        sequence then attend per segment, matching the per-sequence
        composition exactly (reference fmha varlen + fused_rope thd)."""
        from apex_tpu.ops.flash_attention import flash_attention_packed
        from apex_tpu.ops.rope import (fused_apply_rotary_pos_emb,
                                       fused_apply_rotary_pos_emb_thd)

        n, d = 2, 32
        lengths = [48, 80]
        total = sum(lengths)
        rng = np.random.RandomState(30)
        t = jnp.asarray(rng.randn(total, n, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(total, n, d), jnp.float32) * 0.5
        cu = jnp.asarray(np.cumsum([0] + lengths), jnp.int32)
        freqs_full = jnp.asarray(
            rng.randn(max(lengths), 1, 1, d) * 0.1, jnp.float32)

        q_thd = fused_apply_rotary_pos_emb_thd(t, cu, freqs_full)
        out = flash_attention_packed(q_thd, q_thd, v, cu, causal=True)

        start = 0
        for L in lengths:
            sl = slice(start, start + L)
            # per-sequence: sbhd rope (restarts positions) + dense attn
            q_seq = fused_apply_rotary_pos_emb(
                t[sl][:, None], freqs_full[:L])[:, 0]
            want = mha_reference(q_seq[None], q_seq[None], v[sl][None],
                                 causal=True)[0]
            np.testing.assert_allclose(
                np.asarray(out[sl]), np.asarray(want),
                atol=5e-5, rtol=5e-5)
            start += L


class TestGroupedKV:
    """GQA/MQA-aware kernels: grouped K/V ([b, s, g, d] with g < n) feed
    the kernels directly — index maps broadcast each group head to its
    rep query heads, and the dkv grid accumulates a whole group per
    dk/dv row, so the repeated [b, s, n, d] tensor (and the autodiff
    sum of its repeat) never exists in HBM."""

    def _grouped(self, b=2, s=128, n=8, g=2, d=32, seed=21, dtype=None):
        rng = np.random.RandomState(seed)
        dt = dtype or jnp.float32
        q = jnp.asarray(rng.randn(b, s, n, d), dt) * 0.5
        k = jnp.asarray(rng.randn(b, s, g, d), dt) * 0.5
        v = jnp.asarray(rng.randn(b, s, g, d), dt) * 0.5
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_repeated(self, causal):
        """Grouped input must equal the kernel run on explicitly
        repeated K/V — same math, different HBM footprint."""
        q, k, v = self._grouped()
        rep = q.shape[2] // k.shape[2]
        got = flash_attention(q, k, v, causal=causal)
        want = flash_attention(q, jnp.repeat(k, rep, axis=2),
                               jnp.repeat(v, rep, axis=2), causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)

    @pytest.mark.parametrize("g", [1, 4])   # MQA and GQA widths
    def test_grads_match_reference(self, g):
        """dq/dk/dv of the grouped kernel vs autodiff of the reference
        composition (repeat inside, so dk/dv come back grouped)."""
        q, k, v = self._grouped(g=g, seed=22)

        def f_kernel(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True))

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True))

        g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(g1, g2, "qkv"):
            assert a.shape == b_.shape, name
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4,
                err_msg=f"grouped d{name}")

    def test_key_padding_and_dropout_parity(self):
        """kpm is batch-indexed and the dropout hash keys off the query
        head — both must be invariant to grouped-vs-repeated K/V."""
        q, k, v = self._grouped(seed=23)
        rep = q.shape[2] // k.shape[2]
        kpm = jnp.asarray(
            np.arange(128)[None, :] >= np.array([96, 128])[:, None])
        rng = jax.random.PRNGKey(7)
        got = flash_attention(q, k, v, causal=True, key_padding_mask=kpm,
                              dropout_p=0.3, dropout_rng=rng)
        want = flash_attention(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            causal=True, key_padding_mask=kpm, dropout_p=0.3,
            dropout_rng=rng)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)

    def test_segment_ids_grads(self):
        """Packed rows with grouped K/V: block-sparse skip + the grouped
        dkv accumulation must agree with the reference."""
        q, k, v = self._grouped(seed=24)
        seg = jnp.asarray(
            np.repeat(np.arange(4), 32)[None].repeat(2, 0), jnp.int32)
        g1 = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, segment_ids=seg)),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(mha_reference(
            *a, causal=True, segment_ids=seg)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4,
                err_msg=f"grouped+seg d{name}")

    def test_fused_backward_matches_split(self, flash_bwd):
        """The fused single-pass backward supports grouping too: its
        dk/dv output block stays resident across a group's consecutive
        q-head grid rows.  Must agree with the split pair exactly."""
        q, k, v = self._grouped(seed=25)

        def grads():
            return jax.grad(lambda *a: jnp.sum(flash_attention(
                *a, causal=True)), argnums=(0, 1, 2))(q, k, v)

        flash_bwd("fused")
        g_fused = grads()
        flash_bwd("split")
        g_split = grads()
        assert g_fused[1].shape == k.shape   # grouped dk
        for a, b_, name in zip(g_fused, g_split, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=1e-5, rtol=1e-5,
                err_msg=f"grouped fused d{name}")

    def test_fused_backward_mqa_with_dropout(self, flash_bwd):
        """MQA extreme through the fused kernel with dropout: the
        reconstructed per-q-head dropout stream must match split."""
        q, k, v = self._grouped(g=1, seed=26)
        rng = jax.random.PRNGKey(11)

        def grads():
            return jax.grad(lambda *a: jnp.sum(flash_attention(
                *a, causal=True, dropout_p=0.25, dropout_rng=rng)),
                argnums=(0, 1, 2))(q, k, v)

        flash_bwd("fused")
        g_fused = grads()
        flash_bwd("split")
        g_split = grads()
        for a, b_, name in zip(g_fused, g_split, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=1e-5, rtol=1e-5,
                err_msg=f"mqa fused+dropout d{name}")

    def test_invalid_group_ratio_rejected(self):
        q, k, v = self._grouped(n=8, g=3)
        with pytest.raises(ValueError, match="multiple"):
            flash_attention(q, k, v)
        q2, k2, v2 = self._grouped(n=8, g=2)
        with pytest.raises(ValueError, match="head counts differ"):
            flash_attention(q2, k2, v2[:, :, :1])


class TestBackwardModeRouting:
    """``_bwd_plan`` sends short keys (padded sk <= 512) to the fused
    single-pass backward and longer keys to the split dq/dkv pair, so
    both kernels get implicit coverage from the other grad tests; the
    ``flash_bwd`` fixture pins each kernel here regardless of where the
    crossover sits."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_split_backward_matches_reference(self, flash_bwd, causal):
        flash_bwd("split")
        q, k, v = make_qkv(2, 128, 2, 64, seed=11)
        kpm = jnp.asarray(
            np.arange(128)[None, :] >= np.array([96, 128])[:, None])
        g1 = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=causal, key_padding_mask=kpm)),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(mha_reference(
            *a, causal=causal, key_padding_mask=kpm)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"split d{name}")

    def test_fused_segment_ids_match_split(self, flash_bwd):
        seg = jnp.asarray(
            np.repeat(np.arange(4), 32)[None].repeat(2, 0), jnp.int32)
        q, k, v = make_qkv(2, 128, 2, 32, seed=13)

        def grads():
            return jax.grad(lambda *a: jnp.sum(flash_attention(
                *a, causal=True, segment_ids=seg)),
                argnums=(0, 1, 2))(q, k, v)

        flash_bwd("fused")
        g_fused = grads()
        flash_bwd("split")
        g_split = grads()
        for a, b, name in zip(g_fused, g_split, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5,
                err_msg=f"d{name}")


class TestCausalSubTiles:
    """Sequences of 1024 or more run 1024 x 1024 score tiles that the
    causal kernels work in 256-sided squares: none above the diagonal is
    computed, only the squares the diagonal crosses are masked, a grid
    tile below the diagonal is one unmasked product.  Small b*h keeps
    the interpreter in seconds."""

    @pytest.mark.parametrize("with_kpm", [False, True])
    @pytest.mark.parametrize("sq,n,g", [
        (1024, 2, 2),        # one tile a head: the static nest
        (2048, 2, 1),        # 2 x 2 tiles: below / on / above, GQA rep grid
        (1024 + 40, 1, 1),   # a padded tail inside a sub-tile
    ])
    def test_forward_and_grads_match_reference(self, sq, n, g, with_kpm):
        d = 32
        rng = np.random.RandomState(sq + n)
        q = jnp.asarray(rng.randn(1, sq, n, d), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(1, sq, g, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(1, sq, g, d), jnp.float32) * 0.5
        kw = dict(causal=True)
        if with_kpm:
            # masks key 0 too: the first rows have no open key at all
            kw["key_padding_mask"] = jnp.asarray(
                (np.arange(sq) % 7 == 0)[None])

        def loss(fn):
            def f(q, k, v):
                o = fn(q, k, v, **kw)
                return jnp.sum(o * jnp.cos(o)), o
            return f

        (_, o1), g1 = jax.value_and_grad(
            loss(flash_attention), argnums=(0, 1, 2), has_aux=True)(q, k, v)
        (_, o2), g2 = jax.value_and_grad(
            loss(mha_reference), argnums=(0, 1, 2), has_aux=True)(q, k, v)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), **TOL)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"d{name}")

    def test_dropout_bits_do_not_depend_on_the_tiling(self):
        """The keep mask hashes absolute (row, col): sub-tiles draw the
        bits of ``_keep_mask`` over the whole square."""
        from apex_tpu.ops.flash_attention import _keep_mask, _seed_from_rng

        s, d, p_drop = 1024, 32, 0.25
        q, k, v = make_qkv(1, s, 1, d, seed=31)
        rng = jax.random.PRNGKey(9)
        got = flash_attention(q, k, v, causal=True, dropout_p=p_drop,
                              dropout_rng=rng)
        s_ = jnp.einsum("bsnd,btnd->bnst", q, k) / d ** 0.5
        s_ = jnp.where(np.tril(np.ones((s, s), bool)), s_, -1e30)
        keep = _keep_mask(_seed_from_rng(rng), jnp.int32(0), 0, 0, (s, s),
                          1 - p_drop)
        p = jnp.where(keep, jax.nn.softmax(s_, axis=-1) / (1 - p_drop), 0.0)
        want = jnp.einsum("bnst,btnd->bsnd", p, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    @pytest.mark.parametrize("s,share", [
        (512, 1.0),          # 256 x 512 tiles, both reach the diagonal
        (1024, 10 / 16),     # 10 of 16 squares of 256, 4 of them masked
        (8192, 33 / 64),     # 28 tiles below + 8 on the diagonal of 64
    ])
    def test_causal_work_share(self, s, share):
        from apex_tpu.ops.flash_attention import causal_work_share

        assert causal_work_share(s, s) == share
        assert causal_work_share(s, s, causal=False) == 1.0


def _fwd_and_grads(fn, q, k, v, **kw):
    def loss(q, k, v):
        o = fn(q, k, v, **kw)
        return jnp.sum(o * jnp.cos(o)), o

    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return (o, *g)


def _assert_same(got, want, tol=1e-4):
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                                   rtol=tol, err_msg=name)


class TestHeadsABlock:
    """The kernels' boundary is ``[b, s, heads x d]`` in 128-lane blocks
    of ``128 // d`` heads (``_heads_a_block``): every head of a block is
    worked on masked full-width operands and merged by lane; under GQA a
    block's heads read one K/V head, in either half of its block."""

    @pytest.fixture
    def packed_only(self, monkeypatch):
        """The per-head route's transposes refuse to run."""
        from apex_tpu.ops import flash_attention as fa

        def refuse(*a):
            raise AssertionError("took the [b x heads, s, d] route")

        monkeypatch.setattr(fa, "_to_bh", refuse)

    @pytest.mark.parametrize("n,g,d,hpb", [
        (16, 16, 64, 2), (2, 2, 64, 2), (32, 8, 64, 2), (4, 2, 64, 2),
        (4, 4, 128, 1), (2, 1, 128, 1), (2, 2, 256, 1), (8, 8, 32, 4),
        (16, 4, 32, 4),
        # fall back: an odd head count, a toy width that leaves a block
        # part empty, a K/V head count that does (MQA), a group that a
        # block would straddle
        (3, 3, 64, 0), (2, 2, 32, 0), (8, 1, 64, 0), (6, 2, 64, 0),
        (4, 4, 48, 0),
    ])
    def test_rule(self, n, g, d, hpb):
        from apex_tpu.ops.flash_attention import _heads_a_block

        assert _heads_a_block(n, g, d) == hpb

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("n,g,d", [
        (2, 2, 64), (4, 4, 64), (16, 16, 64),   # MHA, two heads a block
        (2, 2, 128),                            # one head a block
        (8, 4, 64),     # GQA rep 2: a q block's K/V head in half 0 and 1
        (16, 4, 64),    # GQA rep 4
        (8, 8, 32),     # four heads a block
        (16, 4, 32),    # four heads a block, GQA: three rotations
    ])
    def test_matches_reference(self, n, g, d, causal, packed_only):
        rng = np.random.RandomState(n * d + g)
        q = jnp.asarray(rng.randn(1, 128, n, d), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(1, 128, g, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(1, 128, g, d), jnp.float32) * 0.5
        _assert_same(_fwd_and_grads(flash_attention, q, k, v, causal=causal),
                     _fwd_and_grads(mha_reference, q, k, v, causal=causal))

    @pytest.mark.parametrize("plan", ["fused", "split"])
    @pytest.mark.parametrize("case", [
        "cross_lengths", "key_padding", "segment_ids", "unaligned"])
    def test_variants(self, case, plan, flash_bwd, packed_only):
        flash_bwd(plan)
        n, g, d, sq, sk = 4, 2, 64, 128, 128
        kw = {}
        if case == "cross_lengths":
            sq, sk = 128, 320
        elif case == "key_padding":
            kw["key_padding_mask"] = jnp.asarray(
                np.arange(sk)[None, :] >= np.array([80, 128])[:, None])
        elif case == "segment_ids":
            kw["segment_ids"] = jnp.asarray(
                np.repeat([[0, 1, 2, -1], [0, 0, 1, 1]], sk // 4, axis=1))
            kw["causal"] = True
        else:
            sq = sk = 100
            kw["causal"] = True
        rng = np.random.RandomState(len(case))
        q = jnp.asarray(rng.randn(2, sq, n, d), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(2, sk, g, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(2, sk, g, d), jnp.float32) * 0.5
        _assert_same(_fwd_and_grads(flash_attention, q, k, v, **kw),
                     _fwd_and_grads(mha_reference, q, k, v, **kw))

    @pytest.mark.parametrize("sq,n,g", [
        (1024, 2, 2),        # the direct forward, bands of two heads
        (1024 + 40, 2, 2),   # 2 x 2 tiles, whole / banded / skipped, padded
        (2048, 4, 2),        # the same under GQA
    ])
    def test_causal_sub_tiles(self, sq, n, g, packed_only):
        rng = np.random.RandomState(sq + n)
        q = jnp.asarray(rng.randn(1, sq, n, 64), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(1, sq, g, 64), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(1, sq, g, 64), jnp.float32) * 0.5
        _assert_same(_fwd_and_grads(flash_attention, q, k, v, causal=True),
                     _fwd_and_grads(mha_reference, q, k, v, causal=True))

    @pytest.mark.parametrize("plan", ["fused", "split"])
    @pytest.mark.parametrize("n,g", [(4, 4), (8, 2)])
    def test_dropout_is_the_per_head_routes(self, n, g, plan, flash_bwd,
                                            monkeypatch):
        """The keep mask hashes (seed, batch x heads + head, row, col):
        a seed gives the bits it gave when every head was a grid row."""
        from apex_tpu.ops import flash_attention as fa

        flash_bwd(plan)
        rng = np.random.RandomState(n)
        q = jnp.asarray(rng.randn(2, 128, n, 64), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(2, 128, g, 64), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(2, 128, g, 64), jnp.float32) * 0.5
        kw = dict(causal=True, dropout_p=0.3,
                  dropout_rng=jax.random.PRNGKey(5))
        packed = _fwd_and_grads(flash_attention, q, k, v, **kw)
        monkeypatch.setattr(fa, "_heads_a_block", lambda n, g, d: 0)
        _assert_same(packed, _fwd_and_grads(flash_attention, q, k, v, **kw),
                     tol=1e-5)

    def test_fallback_shape_matches_reference(self):
        """Three heads of 64 fill no whole number of blocks: the rule
        sends them through the same kernels a head a row."""
        q, k, v = make_qkv(2, 128, 3, 64, seed=7)
        _assert_same(_fwd_and_grads(flash_attention, q, k, v, causal=True),
                     _fwd_and_grads(mha_reference, q, k, v, causal=True))


class TestLatentAttention:
    """``flash_attention_mla``: q/k of a head are [128 without position |
    64 rotary], v is 128 wide, the rotary key is ONE head for all query
    heads.  The kernels (interpreted here) against ``mha_reference`` on
    the expanded tensors: q and k concatenated to 192, the rotary key
    broadcast to the heads."""

    @staticmethod
    def _inputs(s, n, seed=0, dtype=jnp.float32, d=128, r=64, dv=128):
        rng = np.random.RandomState(seed)

        def draw(*shape):
            return jnp.asarray(rng.randn(*shape), dtype) * 0.5

        return (draw(1, s, n, d), draw(1, s, n, r), draw(1, s, n, d),
                draw(1, s, r), draw(1, s, n, dv))

    @staticmethod
    def _expanded(q, qr, k, kr, v, causal):
        kr = jnp.broadcast_to(kr[:, :, None, :], k.shape[:3] + kr.shape[-1:])
        return mha_reference(jnp.concatenate([q, qr], -1),
                             jnp.concatenate([k, kr], -1), v, causal=causal)

    @staticmethod
    def _all(fn, args, causal):
        def loss(*args):
            o = fn(*args, causal)
            return jnp.sum(o * jnp.cos(o)), o

        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(*args)
        return (o, *g)

    @pytest.mark.parametrize("s,n,causal", [
        (256, 2, False),
        (256, 2, True),
        (300, 4, False),        # no multiple of the block: padded rows
        (300, 2, True),
        (1024, 2, True),        # one tile a head: the static nest
        (2048, 2, True),        # 2 x 2 tiles: below / on / above
        (1024 + 40, 2, True),   # a padded tail inside a sub-tile
        (1024 + 40, 2, False),
    ])
    def test_kernels_match_reference_on_expanded(self, s, n, causal,
                                                 monkeypatch):
        from apex_tpu.ops import flash_attention as fa

        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(
            fa, "mha_reference", lambda *a, **k: pytest.fail(
                "the kernels' shape took the reference"))
        args = self._inputs(s, n, seed=s + n)
        got = self._all(lambda *a: fa.flash_attention_mla(
            *a[:5], causal=a[5]), args, causal)
        want = self._all(self._expanded, args, causal)
        for a, b, name in zip(got, want,
                              ("o", "dq", "dq_rope", "dk", "dk_rope", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4, err_msg=name)

    def test_bf16_output_and_gradient_dtypes(self, monkeypatch):
        from apex_tpu.ops.flash_attention import flash_attention_mla

        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        args = self._inputs(256, 2, dtype=jnp.bfloat16)
        got = self._all(lambda *a: flash_attention_mla(
            *a[:5], causal=a[5]), args, True)
        want = self._all(self._expanded,
                         [a.astype(jnp.float32) for a in args], True)
        for a, b, x in zip(got, want, (args[4],) + args):
            assert a.dtype == jnp.bfloat16 and a.shape == x.shape
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), atol=4e-2, rtol=4e-2)

    @pytest.mark.parametrize("shape", [
        dict(d=32, r=16, dv=32),      # toy widths
        dict(d=128, r=64, dv=64),     # v narrower than the keys
        dict(d=128, r=32, dv=128),    # a rotary part that fills no half
    ])
    def test_other_shapes_take_the_reference(self, shape, monkeypatch):
        """Off the kernels' shape, and off the TPU without interpret
        mode, the same function is ``mha_reference`` on the expanded
        tensors, which takes unequal q/k and v widths."""
        from apex_tpu.ops.flash_attention import flash_attention_mla

        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        args = self._inputs(64, 3, **shape)
        got = self._all(lambda *a: flash_attention_mla(
            *a[:5], causal=a[5]), args, True)
        want = self._all(self._expanded, args, True)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_off_tpu_default_is_the_reference(self, monkeypatch):
        from apex_tpu.ops import flash_attention as fa

        monkeypatch.delenv("APEX_TPU_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(fa, "_flash_mla", lambda *a: pytest.fail(
            "kernels off the TPU without interpret mode"))
        args = self._inputs(128, 2)
        got = fa.flash_attention_mla(*args, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._expanded(*args, True)),
            atol=1e-6)
