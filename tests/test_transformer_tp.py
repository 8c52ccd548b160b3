"""Tensor-parallel toolkit tests on the 8-device CPU mesh.

Reference analogs: tests/L0/run_transformer/test_parallel_state.py,
test_mapping.py, test_layers.py, test_cross_entropy.py, test_random.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer import tensor_parallel as tp

shard_map = jax.shard_map


@pytest.fixture()
def tp8_mesh():
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=8
    )
    yield mesh
    parallel_state.destroy_model_parallel()


class TestParallelState:
    def test_sizes_and_errors(self, tp8_mesh):
        assert parallel_state.get_tensor_model_parallel_world_size() == 8
        assert parallel_state.get_data_parallel_world_size() == 1
        assert parallel_state.get_pipeline_model_parallel_world_size() == 1
        assert parallel_state.model_parallel_is_initialized()
        assert "tp=8" in parallel_state.get_rank_info()

    def test_uninitialized_raises(self):
        parallel_state.destroy_model_parallel()
        with pytest.raises(RuntimeError):
            parallel_state.get_mesh()

    def test_virtual_pp_state(self):
        parallel_state.initialize_model_parallel(
            1, 2, virtual_pipeline_model_parallel_size_=4
        )
        assert parallel_state.get_virtual_pipeline_model_parallel_world_size() == 4
        assert parallel_state.get_virtual_pipeline_model_parallel_rank() == 0
        parallel_state.set_virtual_pipeline_model_parallel_rank(2)
        assert parallel_state.get_virtual_pipeline_model_parallel_rank() == 2
        parallel_state.destroy_model_parallel()


class TestMappings:
    def _run(self, mesh, fn, *args, in_specs, out_specs):
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)(*args)

    def test_copy_fwd_identity_bwd_allreduce(self, tp8_mesh):
        x = jnp.arange(8.0)

        def f(x_):
            # forward: every rank sees the full x
            y = tp.copy_to_tensor_model_parallel_region(x_)
            return jnp.sum(y * (jax.lax.axis_index("tp") + 1.0))

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P(),
                           out_specs=P())
        def grads(x_):
            return jax.grad(f)(x_)

        g = grads(x)
        # bwd allreduce: sum of rank+1 over 8 ranks = 36
        np.testing.assert_allclose(np.asarray(g), np.full(8, 36.0))

    def test_reduce_fwd_allreduce(self, tp8_mesh):
        x = jnp.arange(8.0)

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P("tp"),
                           out_specs=P("tp"))
        def f(x_):
            return tp.reduce_from_tensor_model_parallel_region(x_)

        out = f(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))

    def test_scatter_gather_last_dim_roundtrip(self, tp8_mesh):
        x = jnp.arange(16.0).reshape(2, 8)

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P(),
                           out_specs=P("tp"))
        def f(x_):
            local = tp.scatter_to_tensor_model_parallel_region(x_)
            assert local.shape == (2, 1)
            return tp.gather_from_tensor_model_parallel_region(local)[None]

        out = f(x)   # (8, 2, 8): every shard reconstructed the full x
        for i in range(8):
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(x))

    def test_sequence_parallel_roundtrip_and_reduce_scatter(self, tp8_mesh):
        x = jnp.arange(16.0).reshape(8, 2)

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P(),
                           out_specs=P("tp"))
        def f(x_):
            local = tp.scatter_to_sequence_parallel_region(x_)
            assert local.shape == (1, 2)
            return tp.gather_from_sequence_parallel_region(local)[None]

        out = f(x)
        for i in range(8):
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(x))

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P(),
                           out_specs=P("tp"))
        def rs(x_):
            y = tp.copy_to_tensor_model_parallel_region(x_)
            return tp.reduce_scatter_to_sequence_parallel_region(y)

        out = rs(x)   # each shard's row = sum over 8 replicas of its row
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 8)

    def test_gather_seq_parallel_bwd_reduce_scatter(self, tp8_mesh):
        x = jnp.ones((1, 2))

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P("tp"),
                           out_specs=P("tp"))
        def grads(x_):
            def f(x__):
                full = tp.gather_from_sequence_parallel_region(x__)
                w = jax.lax.axis_index("tp") + 1.0
                return jnp.sum(full) * w

            return jax.grad(f)(x_)

        g = grads(jnp.ones((8, 2)))
        # cotangent of full = rank+1 everywhere; reduce-scatter sums over
        # ranks for this shard's row: Σ(rank+1) = 36
        np.testing.assert_allclose(np.asarray(g), np.full((8, 2), 36.0))


class TestVocabParallelCE:
    def test_matches_single_device(self, tp8_mesh):
        from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

        rng = np.random.RandomState(0)
        logits = rng.randn(6, 64).astype(np.float32) * 2
        labels = rng.randint(0, 64, size=(6,))
        ref = softmax_cross_entropy_loss(
            jnp.asarray(logits), jnp.asarray(labels), padding_idx=-1
        )

        @functools.partial(shard_map, mesh=tp8_mesh,
                           in_specs=(P(None, "tp"), P()), out_specs=P())
        def f(lg, lb):
            return tp.vocab_parallel_cross_entropy(lg, lb)

        loss = f(jnp.asarray(logits), jnp.asarray(labels))
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=1e-5)

    def test_gradients_match(self, tp8_mesh):
        from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

        rng = np.random.RandomState(1)
        logits = rng.randn(4, 32).astype(np.float32)
        labels = rng.randint(0, 32, size=(4,))
        g_ref = jax.grad(
            lambda l: jnp.sum(
                softmax_cross_entropy_loss(l, jnp.asarray(labels),
                                           padding_idx=-1)
            )
        )(jnp.asarray(logits))

        @functools.partial(shard_map, mesh=tp8_mesh,
                           in_specs=(P(None, "tp"), P()),
                           out_specs=P(None, "tp"))
        def grads(lg, lb):
            return jax.grad(
                lambda l: jnp.sum(tp.vocab_parallel_cross_entropy(l, lb))
            )(lg)

        g = grads(jnp.asarray(logits), jnp.asarray(labels))
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-5)


class TestGSPMDLayers:
    def test_column_row_mlp_matches_dense(self, tp8_mesh):
        """Column→Row parallel MLP under GSPMD == single-device math."""
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(4, 16), jnp.float32)

        import flax.linen as nn

        class TwoLayer(nn.Module):
            @nn.compact
            def __call__(self, x_):
                h, _ = tp.ColumnParallelLinear(
                    input_size=16, output_size=32, gather_output=False
                )(x_)
                h = jax.nn.gelu(h)
                y, _ = tp.RowParallelLinear(
                    input_size=32, output_size=16, input_is_parallel=True
                )(h)
                return y

        model = TwoLayer()
        variables = model.init(jax.random.PRNGKey(0), x)

        # params carry partitioning metadata
        import flax

        col_kernel = variables["params"]["ColumnParallelLinear_0"]["kernel"]
        assert isinstance(col_kernel, nn.Partitioned)
        assert col_kernel.names == (None, "tp")

        # single-device reference from unboxed params
        unboxed = flax.core.meta.unbox(variables)
        k1 = np.asarray(unboxed["params"]["ColumnParallelLinear_0"]["kernel"])
        b1 = np.asarray(unboxed["params"]["ColumnParallelLinear_0"]["bias"])
        k2 = np.asarray(unboxed["params"]["RowParallelLinear_0"]["kernel"])
        b2 = np.asarray(unboxed["params"]["RowParallelLinear_0"]["bias"])
        expect = np.asarray(jax.nn.gelu(np.asarray(x) @ k1 + b1)) @ k2 + b2

        # run under the mesh with sharded params
        with jax.sharding.set_mesh(tp8_mesh):
            shardings = nn.get_sharding(variables, tp8_mesh)
            sharded_vars = jax.device_put(unboxed, shardings)
            y = jax.jit(lambda v, x_: model.apply(v, x_))(sharded_vars, x)
        np.testing.assert_allclose(np.asarray(y), expect, atol=1e-5)

    def test_vocab_parallel_embedding(self, tp8_mesh):
        import flax
        import flax.linen as nn

        emb = tp.VocabParallelEmbedding(num_embeddings=64, embedding_dim=16)
        ids = jnp.asarray([[1, 5, 63], [0, 32, 7]])
        variables = emb.init(jax.random.PRNGKey(0), ids)
        table = variables["params"]["embedding"]
        assert isinstance(table, nn.Partitioned)
        assert table.names == ("tp", None)

        unboxed = flax.core.meta.unbox(variables)
        expect = np.asarray(unboxed["params"]["embedding"])[np.asarray(ids)]
        with jax.sharding.set_mesh(tp8_mesh):
            shardings = nn.get_sharding(variables, tp8_mesh)
            sharded = jax.device_put(unboxed, shardings)
            y = jax.jit(lambda v, i: emb.apply(v, i))(sharded, ids)
        np.testing.assert_allclose(np.asarray(y), expect, atol=1e-6)


class TestSequenceParallelParity:
    """ISSUE 5 satellite: the ``sequence_parallel_enabled`` Column/Row
    layers vs their non-SP counterparts, forward AND backward, on the
    virtual mesh — the mappings.py fwd/bwd table asserted directly
    instead of only through the gspmd dryrun.  SP only moves the
    shardings (gather → matmul → reduce-scatter vs replicated matmul +
    all-reduce); the global values must not move."""

    def _run_mlp(self, mesh, x, sp_enabled, overlap=False):
        import flax
        import flax.linen as nn

        class Mlp(nn.Module):
            @nn.compact
            def __call__(self, x_):
                h, _ = tp.ColumnParallelLinear(
                    input_size=32, output_size=64, gather_output=False,
                    sequence_parallel_enabled=sp_enabled,
                    overlap_comm=overlap)(x_)
                h = jax.nn.gelu(h)
                y, _ = tp.RowParallelLinear(
                    input_size=64, output_size=32,
                    input_is_parallel=True,
                    sequence_parallel_enabled=sp_enabled,
                    overlap_comm=overlap)(h)
                return y

        model = Mlp()
        variables = flax.core.meta.unbox(
            model.init(jax.random.PRNGKey(0), x))

        def loss(v, x_):
            return jnp.sum(model.apply(v, x_).astype(jnp.float32) ** 2)

        with jax.set_mesh(mesh):
            y = jax.jit(lambda v, x_: model.apply(v, x_))(variables, x)
            l, g = jax.jit(jax.value_and_grad(loss))(variables, x)
        return np.asarray(y), float(l), g

    def test_sp_matches_non_sp_fwd_bwd(self, tp8_mesh):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(16, 2, 32), jnp.float32)  # [s, b, h]
        y_sp, l_sp, g_sp = self._run_mlp(tp8_mesh, x, sp_enabled=True)
        y_no, l_no, g_no = self._run_mlp(tp8_mesh, x, sp_enabled=False)
        np.testing.assert_allclose(y_sp, y_no, atol=1e-5)
        np.testing.assert_allclose(l_sp, l_no, rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_sp),
                        jax.tree_util.tree_leaves(g_no)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_sp_overlap_matches_monolithic(self, tp8_mesh):
        """overlap_comm rides the ring collective-matmul through the
        same layers; fwd+bwd must agree with the monolithic SP path."""
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(16, 2, 32), jnp.float32)
        y_on, l_on, g_on = self._run_mlp(tp8_mesh, x, sp_enabled=True,
                                         overlap=True)
        y_off, l_off, g_off = self._run_mlp(tp8_mesh, x, sp_enabled=True,
                                            overlap=False)
        np.testing.assert_allclose(y_on, y_off, atol=1e-5)
        np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_on),
                        jax.tree_util.tree_leaves(g_off)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestSequenceParallelMappingTable:
    """The mappings.py fwd/bwd table, asserted pair-by-pair under
    shard_map (runs on any toolchain): gather fwd == all-gather with
    bwd reduce-scatter (to_model_parallel) or split; reduce-scatter fwd
    with bwd all-gather — and the overlap_comm ring forms match the
    monolithic collectives in BOTH directions."""

    def test_scatter_bwd_is_gather(self, tp8_mesh):
        # scatter fwd: rank r keeps rows [r]; bwd: all-gather of cots
        x = jnp.arange(16.0).reshape(8, 2)

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P(),
                           out_specs=P("tp"))
        def grads(x_):
            def f(x__):
                local = tp.scatter_to_sequence_parallel_region(x__)
                w = jax.lax.axis_index("tp") + 1.0
                return jnp.sum(local) * w

            return jax.grad(f)(x_)[
                jax.lax.axis_index("tp")][None]

        g = grads(x)
        # each row's cotangent is its owner rank's weight (rank+1)
        np.testing.assert_allclose(
            np.asarray(g)[:, 0], np.arange(1.0, 9.0))

    @pytest.mark.parametrize("overlap", [False, True])
    def test_gather_not_to_model_parallel_bwd_splits(self, tp8_mesh,
                                                     overlap):
        x = jnp.ones((8, 2))

        @functools.partial(shard_map, mesh=tp8_mesh, in_specs=P("tp"),
                           out_specs=P("tp"))
        def grads(x_):
            def f(x__):
                full = tp.gather_from_sequence_parallel_region(
                    x__, False, "tp", overlap)
                w = jax.lax.axis_index("tp") + 1.0
                return jnp.sum(full) * w

            return jax.grad(f)(x_)

        g = grads(x)
        # bwd is a plain split: each shard keeps ITS row of the
        # cotangent (rank+1), no cross-rank sum
        np.testing.assert_allclose(
            np.asarray(g)[:, 0], np.arange(1.0, 9.0))

    def test_overlap_scope_inherited_by_mappings(self, tp8_mesh):
        """overlap_comm=None (the default) reads the innermost
        overlap_scope at trace time — how make_train_step(overlap_comm=)
        reaches mappings it never sees.  The ring form under scope must
        match the monolithic form traced outside it."""
        from apex_tpu.ops.collective_matmul import overlap_scope

        import apex_tpu.observability as obs

        reg = obs.configure(stderr_summary=False)
        try:
            x = jnp.arange(16.0).reshape(8, 2)

            @functools.partial(shard_map, mesh=tp8_mesh,
                               in_specs=P("tp"), out_specs=P())
            def fwd(x_):
                # gathered values are typed varying: pmean over the
                # identical copies is the identity, typed invariant
                return jax.lax.pmean(
                    tp.gather_from_sequence_parallel_region(x_), "tp")

            base = reg.counter("collectives.ring.calls").value
            out_mono = fwd(x)
            assert reg.counter("collectives.ring.calls").value == base

            @functools.partial(shard_map, mesh=tp8_mesh,
                               in_specs=P("tp"), out_specs=P())
            def fwd2(x_):
                return jax.lax.pmean(
                    tp.gather_from_sequence_parallel_region(x_), "tp")

            with overlap_scope(True):
                out_ring = fwd2(x)
            assert reg.counter("collectives.ring.calls").value > base
            np.testing.assert_allclose(np.asarray(out_ring),
                                       np.asarray(out_mono))
        finally:
            obs.shutdown()


class TestRNG:
    def test_tracker_fork_streams(self):
        from apex_tpu.transformer.tensor_parallel import (
            get_rng_tracker,
            model_parallel_seed,
        )

        model_parallel_seed(1234)
        tracker = get_rng_tracker()
        with tracker.fork() as k1:
            a = jax.random.normal(k1, (4,))
        with tracker.fork() as k2:
            b = jax.random.normal(k2, (4,))
        assert not np.allclose(np.asarray(a), np.asarray(b))
        with pytest.raises(KeyError):
            with tracker.fork("nope"):
                pass

    def test_checkpoint_reexport(self):
        from apex_tpu.transformer.tensor_parallel import checkpoint

        f = checkpoint(lambda x: jnp.sin(x) * x)
        g = jax.grad(f)(1.5)
        expect = float(jnp.sin(1.5) + 1.5 * jnp.cos(1.5))
        np.testing.assert_allclose(float(g), expect, rtol=1e-6)
