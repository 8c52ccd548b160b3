"""Ring attention (context parallelism) vs single-device attention.

The reference has no long-context path to mirror (SURVEY.md §5: 'No ring
attention / context parallel / blockwise / Ulysses anywhere'), so the
oracle is our own single-device flash/materialized attention on the
gathered sequence.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.flash_attention import mha_reference
from apex_tpu.parallel.mesh import create_mesh
from apex_tpu.parallel.ring_attention import ring_attention

shard_map = jax.shard_map


def data(b, s, n, d, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, n, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(b, s, n, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(b, s, n, d), jnp.float32) * 0.5
    return q, k, v


def ring_fn(mesh, causal):
    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))
    def f(q, k, v):
        return ring_attention(q, k, v, "sp", causal=causal)
    return f


class TestRingForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_device(self, causal):
        b, s, n, d = 2, 256, 2, 64
        q, k, v = data(b, s, n, d)
        mesh = create_mesh(sp=4)
        got = ring_fn(mesh, causal)(q, k, v)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_unaligned_local_len(self):
        # s_local = 48 → internal padding inside each shard
        b, s, n, d = 1, 192, 2, 32
        q, k, v = data(b, s, n, d, seed=1)
        mesh = create_mesh(sp=4)
        got = ring_fn(mesh, True)(q, k, v)
        want = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_eight_way(self):
        b, s, n, d = 1, 256, 2, 32
        q, k, v = data(b, s, n, d, seed=2)
        mesh = create_mesh(sp=8)
        got = ring_fn(mesh, True)(q, k, v)
        want = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def ring_grads_fn(mesh, causal):
    """Shared shard_map grad harness: grads of a psum'd nonlinear loss
    through the ring, one definition for the MHA and grouped tests."""
    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")))
    def ring_grads(q, k, v):
        def loss(q, k, v):
            o = ring_attention(q, k, v, "sp", causal=causal)
            # local loss; total = psum over shards happens implicitly
            # through the cotangent of each shard being identical
            return jnp.sum(o * (1.0 + 0.1 * o))
        return jax.grad(
            lambda *a: jax.lax.psum(loss(*a), "sp"), argnums=(0, 1, 2))(
                q, k, v)
    return ring_grads


def ref_grads(q, k, v, causal):
    return jax.grad(
        lambda *a: jnp.sum(
            mha_reference(*a, causal=causal)
            * (1.0 + 0.1 * mha_reference(*a, causal=causal))),
        argnums=(0, 1, 2))(q, k, v)


class TestRingBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_single_device(self, causal):
        b, s, n, d = 1, 256, 2, 32
        q, k, v = data(b, s, n, d, seed=3)
        mesh = create_mesh(sp=4)
        g_ring = ring_grads_fn(mesh, causal)(q, k, v)
        g_ref = ref_grads(q, k, v, causal)
        for a, b_, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4,
                err_msg=f"d{name}")


class TestRingGroupedKV:
    """Grouped K/V ride the ring at group width (round-5 GQA-aware
    flash): ppermute messages shrink by n/g, dK/dV come back grouped."""

    def _grouped(self, b=1, s=256, n=8, g=2, d=32, seed=31):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, s, n, d), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(b, s, g, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(b, s, g, d), jnp.float32) * 0.5
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, causal):
        q, k, v = self._grouped()
        mesh = create_mesh(sp=4)
        got = ring_fn(mesh, causal)(q, k, v)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_grads_match_reference(self):
        q, k, v = self._grouped(seed=32)
        mesh = create_mesh(sp=4)
        g_ring = ring_grads_fn(mesh, True)(q, k, v)
        g_ref = ref_grads(q, k, v, True)
        assert g_ring[1].shape == k.shape   # grouped dk, not full-width
        for a, b_, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4,
                err_msg=f"grouped ring d{name}")

    def test_invalid_group_ratio_rejected(self):
        q, k, v = self._grouped(n=8, g=3)
        mesh = create_mesh(sp=4)
        with pytest.raises(ValueError, match="multiple"):
            ring_fn(mesh, True)(q, k, v)


def test_ring_kernel_call_signature_interpret():
    """Regression (round-3 review): the ring path calls the flash
    _fwd_pallas/_bwd_pallas wrappers positionally; run those exact call
    shapes in interpret mode so a signature change breaks here on CPU
    instead of only at TPU trace time."""
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.ops.flash_attention import _bwd_pallas, _fwd_pallas

    rng = np.random.RandomState(0)
    bh, s, d = 2, 128, 32
    q3 = jnp.asarray(rng.randn(bh, s, d), jnp.float32)
    o, lse = _fwd_pallas(q3, q3, q3, None, None, None, 0.125, True,
                         s, 128, 128, 0.0, True, out_dtype=jnp.float32)
    # the logsumexp comes back, and goes in, across the head's lanes;
    # the backward takes the forward's output and makes delta itself
    assert o.shape == q3.shape and lse.shape == q3.shape
    dq, dk, dv = _bwd_pallas(
        q3, q3, q3, o, lse, o, None, None, None, 0.125, True,
        s, s, 128, 128, 0.0, True, out_dtype=jnp.float32)
    assert dq.shape == q3.shape and dk.shape == q3.shape

    # the grouped (gqa=) call shapes the ring uses for GQA: b=1, n=2
    # query-head rows against g=1 kv rows, run through the actual
    # kernels in interpret mode — a grouped-specific signature or grid
    # mismatch must break here on CPU, not at TPU trace time
    k3 = jnp.asarray(rng.randn(1, s, d), jnp.float32)
    o_g, lse_g = _fwd_pallas(q3, k3, k3, None, None, None, 0.125, True,
                             s, 128, 128, 0.0, True,
                             out_dtype=jnp.float32, gqa=(2, 1))
    assert o_g.shape == q3.shape
    dq_g, dk_g, dv_g = _bwd_pallas(
        q3, k3, k3, o_g, lse_g, o_g, None, None, None, 0.125, True,
        s, s, 128, 128, 0.0, True, out_dtype=jnp.float32, gqa=(2, 1))
    assert dq_g.shape == q3.shape
    assert dk_g.shape == k3.shape and dv_g.shape == k3.shape


def test_long_context_memory_scaling():
    """The O(s_local) per-device memory claim (ring_attention.py:11),
    demonstrated with XLA's own compiled-memory analysis at a sequence
    length where the dense path's score matrix alone is multiple GB.

    Dense attention at s=32768 materializes the s x s probs (>= 4.3 GB
    fp32); ring attention sharded 8-way touches only per-chunk buffers.
    Both are compiled abstractly (no data, nothing executed) so the
    comparison is XLA's allocation plan, not a fragile OOM probe.
    """
    b, s, n, d = 1, 32768, 1, 64
    mesh = create_mesh(sp=8)
    spec = jax.ShapeDtypeStruct((b, s, n, d), jnp.float32)

    ring_c = ring_fn(mesh, True).lower(spec, spec, spec).compile()
    dense_c = jax.jit(
        lambda q, k, v: mha_reference(q, k, v, causal=True)).lower(
            spec, spec, spec).compile()
    ring_ma = ring_c.memory_analysis()
    dense_ma = dense_c.memory_analysis()
    if ring_ma is None or dense_ma is None:
        pytest.skip("backend does not expose memory_analysis")

    dense_temp = dense_ma.temp_size_in_bytes
    ring_temp = ring_ma.temp_size_in_bytes
    # the dense plan really contains the s^2 scores...
    assert dense_temp >= s * s * 4, (dense_temp, s * s * 4)
    # ...and the ring plan is at least an order of magnitude below it
    # (per-device buffers scale with s_local = s/8, not s; the CPU
    # fallback kernel materializes s_local^2 chunk scores, the TPU
    # Pallas kernel not even that)
    assert ring_temp * 8 <= dense_temp, (ring_temp, dense_temp)


class TestContextParallelGPT:
    """Ring attention as the flagship model's core attention
    (gspmd_ctx(context_parallel=True)): loss and grads must match the
    single-device run of the same params — the long-context mode is not
    allowed to change the math."""

    def _cfg(self):
        from apex_tpu.models.config import TransformerConfig

        return TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.float32)

    @pytest.mark.slow   # dryrun gspmd-cp phase asserts the same fp32 parity
    def test_loss_and_grads_match_single_device(self):
        from apex_tpu.models.transformer_lm import (
            gpt_loss, gspmd_ctx, init_gpt_params)

        cfg = self._cfg()
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)

        ref_l, ref_g = jax.value_and_grad(gpt_loss)(
            params, tokens, labels, cfg)

        mesh = create_mesh(dp=2, sp=4)
        ctx = gspmd_ctx(seq_axis="sp", context_parallel=True)
        with jax.set_mesh(mesh):
            got_l, got_g = jax.jit(jax.value_and_grad(
                lambda p: gpt_loss(p, tokens, labels, cfg, ctx)))(params)

        np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
        la = jax.tree_util.tree_leaves(got_g)
        lb = jax.tree_util.tree_leaves(ref_g)
        for a, b, in zip(la, lb):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4)

    @pytest.mark.slow   # gate asserts ring parity every driver run
    def test_train_step_context_parallel(self):
        from apex_tpu.models.gpt import make_gpt_train_step
        from apex_tpu.optimizers import fused_adam

        cfg = self._cfg()
        mesh = create_mesh(dp=2, sp=4)
        init, step = make_gpt_train_step(
            cfg, fused_adam(lr=1e-3), "O2", mesh, seq_axis="sp",
            context_parallel=True)
        state = init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        tokens = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        losses = []
        for _ in range(3):
            state, m = step(state, tokens, labels)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses

    @pytest.mark.slow   # compile-heavy; CI slow job
    def test_cp_composes_with_remat_and_scan(self):
        """The long-context production shape uses remat + scanned
        layers (the bench s8192 config): both cp modes must compose
        with them (shard_map inside a remat'd lax.scan body)."""
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.gpt import make_gpt_train_step
        from apex_tpu.optimizers import fused_adam

        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.bfloat16, remat=True, scan_layers=True)
        mesh = create_mesh(dp=2, sp=4)
        rng = np.random.RandomState(9)
        tokens = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        for mode in ("ring", "ulysses"):
            init, step = make_gpt_train_step(
                cfg, fused_adam(lr=1e-3), "O2", mesh, seq_axis="sp",
                context_parallel=mode)
            state = init(jax.random.PRNGKey(0))
            state, m = step(state, tokens, labels)
            assert np.isfinite(float(m["loss"])), mode

    def test_requires_seq_axis(self):
        from apex_tpu.models.transformer_lm import gspmd_ctx

        with pytest.raises(ValueError, match="requires seq_axis"):
            gspmd_ctx(context_parallel=True)

    def test_degraded_fallback_warns_once(self, monkeypatch):
        """A cp-configured forward whose pattern forces the gathered
        dense path (mask / attention dropout) must say so loudly: the
        all-gathered K/V is the memory blowup cp exists to avoid, and
        at s8192 the silent version is an unexplained OOM."""
        import warnings

        import apex_tpu.models.transformer_lm as tlm

        monkeypatch.delenv("APEX_TPU_CP_STRICT", raising=False)
        monkeypatch.setattr(tlm, "_cp_fallback_warned", False)
        ctx = tlm.gspmd_ctx(seq_axis="sp", context_parallel=True)
        q = jnp.zeros((2, 8, 4, 8), jnp.float32)
        mask = jnp.zeros((2, 1, 8, 8), bool)
        # no active mesh (single-device debug run of the cp config):
        # the dense path gathers nothing, so no alarm may fire
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            assert tlm._cp_core_attention(
                ctx, q, q, q, True, 1.0, mask, False) is None
        with jax.set_mesh(create_mesh(dp=2, sp=4)):
            with pytest.warns(RuntimeWarning, match="DEGRADED"):
                out = tlm._cp_core_attention(
                    ctx, q, q, q, True, 1.0, mask, False)
            assert out is None  # caller takes the dense path
            with warnings.catch_warnings():  # once per process, not per call
                warnings.simplefilter("error")
                assert tlm._cp_core_attention(
                    ctx, q, q, q, True, 1.0, mask, False) is None

    def test_degraded_fallback_strict_raises(self, monkeypatch):
        import apex_tpu.models.transformer_lm as tlm

        monkeypatch.setenv("APEX_TPU_CP_STRICT", "1")
        monkeypatch.setattr(tlm, "_cp_fallback_warned", False)
        ctx = tlm.gspmd_ctx(seq_axis="sp", context_parallel=True)
        q = jnp.zeros((2, 8, 4, 8), jnp.float32)
        with jax.set_mesh(create_mesh(dp=2, sp=4)):
            with pytest.raises(ValueError, match="DEGRADED"):
                # attention dropout active → the kernels don't cover it
                tlm._cp_core_attention(ctx, q, q, q, True, 1.0, None, True)

    def test_clean_cp_path_does_not_warn(self, monkeypatch):
        """The supported pattern (causal, no mask, no attention dropout)
        must stay warning-free — the fallback alarm may not cry wolf."""
        import warnings

        import apex_tpu.models.transformer_lm as tlm

        monkeypatch.delenv("APEX_TPU_CP_STRICT", raising=False)
        monkeypatch.setattr(tlm, "_cp_fallback_warned", False)
        ctx = tlm.gspmd_ctx(seq_axis="sp", context_parallel=True)
        mesh = create_mesh(dp=2, sp=4)
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(2, 16, 4, 8), jnp.float32)
        with jax.set_mesh(mesh):
            with warnings.catch_warnings():
                warnings.simplefilter("error", RuntimeWarning)
                out = jax.jit(lambda q: tlm._cp_core_attention(
                    ctx, q, q, q, True, 1.0, None, False))(q)
        assert out is not None and out.shape == q.shape

    def test_rejects_unsupported_configs(self):
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.gpt import make_gpt_train_step
        from apex_tpu.optimizers import fused_adam

        mesh = create_mesh(dp=2, sp=4)
        bad = [
            TransformerConfig(
                num_layers=2, hidden_size=64, num_attention_heads=4,
                vocab_size=128, max_position_embeddings=64,
                attn_mask_type="padding"),
            TransformerConfig(
                num_layers=2, hidden_size=64, num_attention_heads=4,
                vocab_size=128, max_position_embeddings=64,
                attention_dropout=0.1),
        ]
        for cfg in bad:
            with pytest.raises(ValueError, match="context_parallel"):
                make_gpt_train_step(
                    cfg, fused_adam(lr=1e-3), "O2", mesh, seq_axis="sp",
                    context_parallel=True)


class TestUlysses:
    """All-to-all sequence parallelism (the second long-context mode)."""

    def test_matches_single_device(self):
        import functools

        from apex_tpu.parallel.ulysses import ulysses_attention

        b, s, n, d = 2, 256, 8, 32
        q, k, v = data(b, s, n, d, seed=21)
        mesh = create_mesh(sp=4)
        for causal in (False, True):
            f = jax.jit(jax.shard_map(
                functools.partial(ulysses_attention, axis_name="sp",
                                  causal=causal),
                mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp")))
            got = f(q, k, v)
            want = mha_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5,
                err_msg=f"causal={causal}")

    def test_grads_match_single_device(self):
        import functools

        from apex_tpu.parallel.ulysses import ulysses_attention

        b, s, n, d = 1, 128, 4, 32
        q, k, v = data(b, s, n, d, seed=22)
        mesh = create_mesh(sp=4)

        def shard_loss(*a):
            f = jax.shard_map(
                functools.partial(ulysses_attention, axis_name="sp",
                                  causal=True),
                mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"))
            o = f(*a)
            return jnp.sum(o * (1.0 + 0.1 * o))

        g = jax.jit(jax.grad(shard_loss, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(
            lambda *a: (lambda o: jnp.sum(o * (1.0 + 0.1 * o)))(
                mha_reference(*a, causal=True)),
            argnums=(0, 1, 2))(q, k, v)
        for a, r, nm in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), atol=1e-4, rtol=1e-4,
                err_msg=f"d{nm}")

    def test_head_divisibility_error(self):
        import functools

        from apex_tpu.parallel.ulysses import ulysses_attention

        q, k, v = data(1, 64, 3, 16, seed=23)   # 3 heads, sp=4
        mesh = create_mesh(sp=4)
        f = jax.shard_map(
            functools.partial(ulysses_attention, axis_name="sp"),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"))
        with pytest.raises(ValueError, match="divisible"):
            f(q, k, v)

    def test_gpt_ulysses_head_check_up_front(self):
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.gpt import make_gpt_train_step
        from apex_tpu.optimizers import fused_adam

        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64)
        mesh = create_mesh(sp=8)
        with pytest.raises(ValueError, match="divisible"):
            make_gpt_train_step(
                cfg, fused_adam(lr=1e-3), "O2", mesh, seq_axis="sp",
                context_parallel="ulysses")

    @pytest.mark.slow   # gate asserts ulysses parity every driver run
    def test_gpt_train_step_ulysses(self):
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.gpt import make_gpt_train_step
        from apex_tpu.optimizers import fused_adam

        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=64,
            compute_dtype=jnp.float32)
        mesh = create_mesh(dp=2, sp=4)
        init, step = make_gpt_train_step(
            cfg, fused_adam(lr=1e-3), "O2", mesh, seq_axis="sp",
            context_parallel="ulysses")
        state = init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(3)
        tokens = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        losses = []
        for _ in range(3):
            state, m = step(state, tokens, labels)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
