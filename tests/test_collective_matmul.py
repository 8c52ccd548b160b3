"""Ring collective-matmul (ops/collective_matmul) on the 8-device mesh.

Parity contract (the ISSUE-5 acceptance semantics, also enforced by the
driver's ``tp_overlap`` dryrun phase): every overlapped ring form must
match its monolithic counterpart — forward AND backward — to fp32-tight
tolerances, with bf16 inputs allowed bf16-rounding slack.  Plus the
telemetry invariant: each ring loop books exactly ``n−1`` hops, so
``collectives.ring.hops == (tp−1) × collectives.ring.calls`` on any
fixed-tp program.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import apex_tpu.observability as obs
from apex_tpu.ops import collective_matmul as cm

shard_map = jax.shard_map


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    obs.shutdown()


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("tp",))


def _mm_ref(x, w):
    # the monolithic math with the SAME accumulation contract as the ring
    # (_mm: fp32 accumulate, result_type output)
    y = jax.lax.dot_general(
        x, w, dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return y.astype(jnp.result_type(x, w))


def _tols(dtype):
    # fp32 tight; bf16 pays output rounding (and CPU bf16 matmul noise)
    return ((1e-5, 1e-5) if dtype == jnp.float32 else (5e-2, 5e-2))


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.randn(*shape), dtype)


def _replicated(fn):
    """A gathered value is held identically by every rank but typed
    varying (ppermute hops — and jax.lax.all_gather — cannot prove
    replication), so to leave shard_map through ``out_specs=P()`` it is
    reduced first: pmean over identical copies is the identity."""
    return lambda *a: jax.lax.pmean(fn(*a), "tp")


class TestRingPrimitives:
    """ring_all_gather / ring_reduce_scatter vs the monolithic lax ops."""

    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_ring_all_gather_fwd_bwd(self, n):
        rng = np.random.RandomState(0)
        x = _rand(rng, (n * 2, 3), jnp.float32)
        cot = _rand(rng, (n * 2, 3), jnp.float32)
        mesh = _mesh(n)

        def ring(x_):
            return shard_map(
                _replicated(
                    functools.partial(cm.ring_all_gather, axis_name="tp")),
                mesh=mesh, in_specs=P("tp"), out_specs=P())(x_)

        def mono(x_):
            return shard_map(
                _replicated(lambda v: jax.lax.all_gather(
                    v, "tp", axis=0, tiled=True)),
                mesh=mesh, in_specs=P("tp"), out_specs=P())(x_)

        np.testing.assert_allclose(np.asarray(ring(x)), np.asarray(mono(x)),
                                   rtol=0, atol=0)
        # autodiff transposes the ppermute ring into the reversed ring
        g_ring = jax.grad(lambda v: jnp.vdot(ring(v), cot))(x)
        g_mono = jax.grad(lambda v: jnp.vdot(mono(v), cot))(x)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_mono),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("n", [2, 8])
    def test_ring_reduce_scatter_fwd_bwd(self, n):
        rng = np.random.RandomState(1)
        x = _rand(rng, (n * 2, 3), jnp.float32)
        cot = _rand(rng, (n * 2, 3), jnp.float32)
        mesh = _mesh(n)

        def ring(x_):
            # replicate in, shard-summed out: each rank contributes the
            # full x (rank-scaled so shards genuinely differ)
            def f(v):
                from apex_tpu.utils.collectives import pvary

                v = pvary(v, "tp") * (jax.lax.axis_index("tp") + 1.0)
                return cm.ring_reduce_scatter(v, "tp", dim=0)

            return shard_map(f, mesh=mesh, in_specs=P(),
                             out_specs=P("tp"))(x_)

        def mono(x_):
            def f(v):
                from apex_tpu.utils.collectives import pvary

                v = pvary(v, "tp") * (jax.lax.axis_index("tp") + 1.0)
                return jax.lax.psum_scatter(v, "tp", scatter_dimension=0,
                                            tiled=True)

            return shard_map(f, mesh=mesh, in_specs=P(),
                             out_specs=P("tp"))(x_)

        np.testing.assert_allclose(np.asarray(ring(x)), np.asarray(mono(x)),
                                   rtol=1e-6, atol=1e-6)
        g_ring = jax.grad(lambda v: jnp.vdot(ring(v), cot))(x)
        g_mono = jax.grad(lambda v: jnp.vdot(mono(v), cot))(x)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_mono),
                                   rtol=1e-6, atol=1e-6)

    def test_indivisible_dim_raises(self):
        mesh = _mesh(8)
        x = jnp.ones((9, 2))
        with pytest.raises(ValueError, match="not divisible"):
            shard_map(
                functools.partial(cm.ring_reduce_scatter, axis_name="tp"),
                mesh=mesh, in_specs=P(), out_specs=P("tp"))(x)


class TestAllGatherMatmul:
    """all_gather(x) @ w as the overlapped ring, fwd + custom-vjp bwd."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [2, 8])
    def test_fwd_bwd_parity(self, dtype, n):
        rng = np.random.RandomState(2)
        s, b, k, p = n * 2, 3, 16, n * 4
        x = _rand(rng, (s, b, k), dtype)      # sequence-sharded input
        w = _rand(rng, (k, p), dtype)         # column-sharded weight
        cot = _rand(rng, (s, b, p), jnp.float32)
        mesh = _mesh(n)
        rtol, atol = _tols(dtype)

        ring = shard_map(
            functools.partial(cm.all_gather_matmul, axis_name="tp"),
            mesh=mesh, in_specs=(P("tp"), P(None, "tp")),
            out_specs=P(None, None, "tp"))

        np.testing.assert_allclose(
            np.asarray(ring(x, w), np.float32),
            np.asarray(_mm_ref(x, w), np.float32), rtol=rtol, atol=atol)

        def loss_ring(x_, w_):
            return jnp.vdot(ring(x_, w_).astype(jnp.float32), cot)

        def loss_mono(x_, w_):
            return jnp.vdot(_mm_ref(x_, w_).astype(jnp.float32), cot)

        gx_r, gw_r = jax.grad(loss_ring, argnums=(0, 1))(x, w)
        gx_m, gw_m = jax.grad(loss_mono, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx_r, np.float32),
                                   np.asarray(gx_m, np.float32),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(np.asarray(gw_r, np.float32),
                                   np.asarray(gw_m, np.float32),
                                   rtol=rtol, atol=max(atol, 1e-4))

    def test_contraction_mismatch_raises(self):
        with pytest.raises(ValueError, match="contraction mismatch"):
            cm.all_gather_matmul(jnp.ones((4, 8)), jnp.ones((16, 4)), "tp")


class TestMatmulReduceScatter:
    """reduce_scatter(x @ w) as the rotating-accumulator ring."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [2, 8])
    def test_fwd_bwd_parity(self, dtype, n):
        rng = np.random.RandomState(3)
        s, b, k, p = n * 2, 3, n * 4, 12
        x = _rand(rng, (s, b, k), dtype)      # contraction tp-sharded
        w = _rand(rng, (k, p), dtype)         # row-sharded weight
        cot = _rand(rng, (s, b, p), jnp.float32)
        mesh = _mesh(n)
        rtol, atol = _tols(dtype)

        ring = shard_map(
            functools.partial(cm.matmul_reduce_scatter, axis_name="tp"),
            mesh=mesh, in_specs=(P(None, None, "tp"), P("tp")),
            out_specs=P("tp"))

        np.testing.assert_allclose(
            np.asarray(ring(x, w), np.float32),
            np.asarray(_mm_ref(x, w), np.float32), rtol=rtol, atol=atol)

        def loss_ring(x_, w_):
            return jnp.vdot(ring(x_, w_).astype(jnp.float32), cot)

        def loss_mono(x_, w_):
            return jnp.vdot(_mm_ref(x_, w_).astype(jnp.float32), cot)

        gx_r, gw_r = jax.grad(loss_ring, argnums=(0, 1))(x, w)
        gx_m, gw_m = jax.grad(loss_mono, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx_r, np.float32),
                                   np.asarray(gx_m, np.float32),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(np.asarray(gw_r, np.float32),
                                   np.asarray(gw_m, np.float32),
                                   rtol=rtol, atol=max(atol, 1e-4))

    def test_matmul_all_reduce_fwd_bwd(self):
        n = 8
        rng = np.random.RandomState(4)
        s, b, k, p = 8, 2, n * 4, 12
        x = _rand(rng, (s, b, k), jnp.float32)
        w = _rand(rng, (k, p), jnp.float32)
        cot = _rand(rng, (s, b, p), jnp.float32)
        mesh = _mesh(n)

        ring = shard_map(
            _replicated(
                functools.partial(cm.matmul_all_reduce, axis_name="tp")),
            mesh=mesh, in_specs=(P(None, None, "tp"), P("tp")),
            out_specs=P())

        np.testing.assert_allclose(
            np.asarray(ring(x, w)), np.asarray(_mm_ref(x, w)),
            rtol=1e-5, atol=1e-5)
        gx_r, gw_r = jax.grad(
            lambda a, b_: jnp.vdot(ring(a, b_), cot), argnums=(0, 1))(x, w)
        gx_m, gw_m = jax.grad(
            lambda a, b_: jnp.vdot(_mm_ref(a, b_), cot),
            argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx_r), np.asarray(gx_m),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw_r), np.asarray(gw_m),
                                   rtol=1e-5, atol=1e-4)


class TestRingTelemetry:
    """collectives.ring.* trace-time invariant: hops == (tp−1) × calls."""

    def test_hops_equal_tp_minus_one_per_call(self):
        n = 8
        reg = obs.configure(stderr_summary=False)
        rng = np.random.RandomState(5)
        x = _rand(rng, (n * 2, 2, 16), jnp.float32)
        w = _rand(rng, (16, n * 4), jnp.float32)
        mesh = _mesh(n)

        c0 = reg.counter("collectives.ring.calls").value
        h0 = reg.counter("collectives.ring.hops").value
        b0 = reg.counter("collectives.ring.bytes").value
        ring = shard_map(
            functools.partial(cm.all_gather_matmul, axis_name="tp"),
            mesh=mesh, in_specs=(P("tp"), P(None, "tp")),
            out_specs=P(None, None, "tp"))
        # fwd trace + bwd trace: every ring loop, in either direction,
        # must book exactly n−1 hops
        jax.grad(lambda a, b_: jnp.sum(ring(a, b_)), argnums=(0, 1))(x, w)
        calls = reg.counter("collectives.ring.calls").value - c0
        hops = reg.counter("collectives.ring.hops").value - h0
        bys = reg.counter("collectives.ring.bytes").value - b0
        assert calls > 0
        assert hops == (n - 1) * calls
        assert bys > 0

    def test_ppermute_counters_ride_along(self):
        n = 8
        reg = obs.configure(stderr_summary=False)
        x = jnp.ones((n * 2, 4))
        mesh = _mesh(n)
        p0 = reg.counter("collectives.ppermute.calls").value
        shard_map(
            functools.partial(cm.ring_all_gather, axis_name="tp"),
            mesh=mesh, in_specs=P("tp"), out_specs=P("tp"))(x)
        # n−1 hops, each through the counted ppermute wrapper
        assert (reg.counter("collectives.ppermute.calls").value - p0
                == n - 1)


class TestOverlapScope:
    def test_tri_state_resolution(self):
        assert cm.overlap_enabled(True) is True
        assert cm.overlap_enabled(False) is False
        assert cm.overlap_enabled(None) is False        # default off
        with cm.overlap_scope(True):
            assert cm.overlap_enabled(None) is True
            assert cm.overlap_enabled(False) is False   # explicit wins
            with cm.overlap_scope(False):
                assert cm.overlap_enabled(None) is False
            assert cm.overlap_enabled(None) is True
        assert cm.overlap_enabled(None) is False

    def test_scope_restores_on_exception(self):
        with pytest.raises(RuntimeError):
            with cm.overlap_scope(True):
                raise RuntimeError("boom")
        assert cm.overlap_enabled(None) is False


class TestMappingsOverlap:
    """The sequence-parallel mappings under overlap_comm ride the ring in
    BOTH directions of the fwd/bwd table and stay numerically identical
    to the monolithic collectives."""

    def test_gather_from_sp_region_overlap_parity(self):
        from apex_tpu.transformer import tensor_parallel as tp
        from apex_tpu.transformer import parallel_state

        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=8)
        try:
            x = jnp.arange(16.0).reshape(8, 2)

            def run(overlap):
                @functools.partial(shard_map, mesh=mesh, in_specs=P("tp"),
                                   out_specs=P("tp"))
                def grads(x_):
                    def f(x__):
                        full = tp.gather_from_sequence_parallel_region(
                            x__, True, "tp", overlap)
                        w = jax.lax.axis_index("tp") + 1.0
                        return jnp.sum(full) * w

                    return jax.grad(f)(x_)

                @functools.partial(shard_map, mesh=mesh, in_specs=P("tp"),
                                   out_specs=P())
                def fwd(x_):
                    return jax.lax.pmean(
                        tp.gather_from_sequence_parallel_region(
                            x_, True, "tp", overlap), "tp")

                return fwd(x), grads(x)

            f_on, g_on = run(True)
            f_off, g_off = run(False)
            np.testing.assert_allclose(np.asarray(f_on), np.asarray(f_off))
            np.testing.assert_allclose(np.asarray(g_on), np.asarray(g_off))
            # the bwd reduce-scatter sums rank+1 over 8 ranks = 36
            np.testing.assert_allclose(np.asarray(g_on),
                                       np.full((8, 2), 36.0))
        finally:
            parallel_state.destroy_model_parallel()

    def test_reduce_scatter_to_sp_region_overlap_parity(self):
        from apex_tpu.transformer import tensor_parallel as tp
        from apex_tpu.transformer import parallel_state

        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=8)
        try:
            x = jnp.arange(16.0).reshape(8, 2)

            def run(overlap):
                @functools.partial(shard_map, mesh=mesh, in_specs=P(),
                                   out_specs=P("tp"))
                def fwd(x_):
                    y = tp.copy_to_tensor_model_parallel_region(x_)
                    return tp.reduce_scatter_to_sequence_parallel_region(
                        y, "tp", overlap)

                @functools.partial(shard_map, mesh=mesh, in_specs=P(),
                                   out_specs=P("tp"))
                def grads(x_):
                    def f(x__):
                        y = tp.reduce_scatter_to_sequence_parallel_region(
                            x__, "tp", overlap)
                        return jnp.sum(y * (jax.lax.axis_index("tp") + 1.0))

                    return jax.grad(f)(x_)[None][0]

                return fwd(x), grads(x)

            f_on, g_on = run(True)
            f_off, g_off = run(False)
            np.testing.assert_allclose(np.asarray(f_on), np.asarray(f_off))
            np.testing.assert_allclose(np.asarray(g_on), np.asarray(g_off))
            np.testing.assert_allclose(np.asarray(f_on), np.asarray(x) * 8)
        finally:
            parallel_state.destroy_model_parallel()


class TestGspmdIslandFallback:
    """The GSPMD wrappers return None whenever the ring path does not
    apply, so layer call sites always have the monolithic fallback."""

    def test_disabled_returns_none(self):
        x, w = jnp.ones((8, 2, 4)), jnp.ones((4, 8))
        assert cm.sequence_parallel_matmul(x, w, mode="gather",
                                           enable=False) is None
        assert cm.gspmd_row_parallel_matmul(x, w, enable=False) is None

    def test_no_mesh_returns_none(self):
        x, w = jnp.ones((8, 2, 4)), jnp.ones((4, 8))
        assert cm.sequence_parallel_matmul(x, w, mode="gather",
                                           enable=True) is None
        assert cm.gspmd_row_parallel_matmul(x, w, enable=True) is None

    def test_bad_mode_raises(self):
        x, w = jnp.ones((8, 4)), jnp.ones((4, 8))
        with pytest.raises(ValueError, match="mode"):
            cm.sequence_parallel_matmul(x, w, mode="nope", enable=True)
