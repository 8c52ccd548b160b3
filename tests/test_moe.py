"""Switch MoE + expert parallelism tests (beyond-reference component;
the reference reserves --num-experts but ships no MoE runtime).

ISSUE 10 additions: the capacity-free ragged routing is parity-pinned
against the capacity path at generous capacity_factor (both see every
token), the explicit EP island (counted all_to_all dispatch, compressed
wire, ring overlap) against the unsharded ragged math, and the grouped
matmul kernel against its XLA segment-sum reference at adversarial
segment layouts.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.parallel.mesh import create_mesh
from apex_tpu.transformer.moe import init_moe_params, switch_moe_mlp


def _data(b=2, s=16, h=32, seed=0):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randn(b, s, h) * 0.5, jnp.float32)


class TestSwitchMoE:
    def test_single_expert_equals_dense_mlp(self):
        """E=1 routes every token to the one expert with gate=softmax=1,
        so the MoE equals the dense FFN exactly (capacity >= s)."""
        h, f = 32, 64
        params = init_moe_params(jax.random.PRNGKey(0), h, f, 1)
        x = _data(h=h)
        out = switch_moe_mlp(params, x, capacity_factor=1.0,
                             ep_axis=None)
        # capacity = s/1 * 1.0 = s -> nothing dropped
        assert float(out.dropped_fraction) == 0.0
        dense = jax.nn.gelu(
            (x @ params["fc1"][0] + params["fc1_bias"][0]).astype(
                jnp.float32), approximate=False).astype(jnp.float32)
        dense = dense @ params["fc2"][0] + params["fc2_bias"][0]
        np.testing.assert_allclose(
            np.asarray(out.out), np.asarray(dense), atol=1e-5, rtol=1e-5)
        assert float(out.aux_loss) == pytest.approx(1.0, rel=1e-5)

    def test_capacity_drops_reported(self):
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(1), h, f, E)
        # bias the router hard toward expert 0 so capacity overflows
        params["router"] = params["router"].at[:, 0].add(10.0)
        x = _data(h=h)
        out = switch_moe_mlp(params, x, capacity_factor=1.0)
        assert float(out.dropped_fraction) > 0.0
        # dropped tokens pass through with zero update
        assert np.isfinite(np.asarray(out.out)).all()

    def test_top2_routes_more_mass(self):
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(2), h, f, E)
        x = _data(h=h, seed=3)
        out1 = switch_moe_mlp(params, x, top_k=1, capacity_factor=4.0)
        out2 = switch_moe_mlp(params, x, top_k=2, capacity_factor=4.0)
        # top-2 output includes top-1's contribution plus the runner-up's
        n1 = float(jnp.sum(jnp.abs(out1.out)))
        n2 = float(jnp.sum(jnp.abs(out2.out)))
        assert n2 > n1

    def test_grads_flow_to_router_and_experts(self):
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(4), h, f, E)
        x = _data(h=h, seed=5)

        def loss(p):
            o = switch_moe_mlp(p, x, capacity_factor=2.0)
            return jnp.mean(o.out ** 2) + 0.01 * o.aux_loss

        g = jax.grad(loss)(params)
        for name in ("router", "fc1", "fc2"):
            assert float(jnp.sum(jnp.abs(g[name]))) > 0.0, name

    def test_expert_parallel_matches_single_device(self):
        """ep=4 GSPMD sharding must be numerically identical to the
        unsharded run (the all-to-alls are layout, not math)."""
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(6), h, f, E)
        x = _data(b=4, h=h, seed=7)
        ref = switch_moe_mlp(params, x, capacity_factor=2.0,
                             ep_axis=None)

        mesh = create_mesh(ep=4, tp=1, pp=1, sp=1)

        def put_experts(p):
            return jax.device_put(p, {
                "router": NamedSharding(mesh, P()),
                "fc1": NamedSharding(mesh, P("ep")),
                "fc1_bias": NamedSharding(mesh, P("ep")),
                "fc2": NamedSharding(mesh, P("ep")),
                "fc2_bias": NamedSharding(mesh, P("ep")),
            })

        sharded = put_experts(params)

        @jax.jit
        def run(p, xx):
            o = switch_moe_mlp(p, xx, capacity_factor=2.0)
            return o.out, o.aux_loss

        with jax.set_mesh(mesh):
            out, aux = run(sharded, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.out), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(
            float(aux), float(ref.aux_loss), rtol=1e-6)

    def test_aux_loss_prefers_balance(self):
        """Uniform routing gives aux = 1 (minimum); collapsed routing
        gives aux ~ E."""
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(8), h, f, E)
        x = _data(h=h, seed=9)
        collapsed = dict(params)
        collapsed["router"] = params["router"] * 0 + jnp.asarray(
            [10.0, 0, 0, 0])
        # positive activations so the (bias-free) router's expert-0
        # logit is large-positive for every token
        aux_c = float(switch_moe_mlp(
            collapsed, jnp.abs(x) + 0.1).aux_loss)
        balanced = dict(params)
        balanced["router"] = params["router"] * 0
        # perfectly uniform probs: aux == 1 regardless of argmax ties
        aux_b = float(switch_moe_mlp(balanced, x).aux_loss)
        assert aux_c > 2.0
        assert aux_b == pytest.approx(1.0, rel=1e-5)


def _offsets(counts):
    return jnp.asarray(np.concatenate([[0], np.cumsum(counts)]),
                       jnp.int32)


class TestGroupedMatmul:
    """Kernel-vs-reference parity for ops/grouped_matmul at the segment
    layouts that break naive implementations: empty segments, length-1
    segments, uneven splits, everything on one expert, and windows."""

    @pytest.mark.parametrize("counts", [
        [0, 37, 0],                 # all tokens on one expert
        [1, 0, 1, 35],              # empty + singleton segments
        [5, 0, 20, 1, 11],          # uneven
        [9, 9, 9, 10],              # near-even
    ])
    def test_kernel_matches_reference_fwd_bwd(self, counts):
        from apex_tpu.ops.grouped_matmul import (
            grouped_matmul, grouped_matmul_reference)

        rng = np.random.RandomState(0)
        n, k, p = sum(counts), 32, 48
        x = jnp.asarray(rng.randn(n, k), jnp.float32)
        w = jnp.asarray(rng.randn(len(counts), k, p) * 0.1, jnp.float32)
        off = _offsets(counts)
        ref = grouped_matmul_reference(x, w, off)
        # dense per-segment truth
        offn = np.asarray(off)
        for g in range(len(counts)):
            seg = np.asarray(x)[offn[g]:offn[g + 1]] @ np.asarray(w)[g]
            np.testing.assert_allclose(
                np.asarray(ref)[offn[g]:offn[g + 1]], seg,
                atol=1e-4, rtol=1e-4)
        ker = grouped_matmul(x, w, off, backend="kernel")
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
        cot = jnp.asarray(rng.randn(n, p), jnp.float32)

        def loss(a, b, backend):
            return jnp.vdot(grouped_matmul(a, b, off, backend=backend),
                            cot)

        gk = jax.grad(functools.partial(loss, backend="kernel"),
                      argnums=(0, 1))(x, w)
        gr = jax.grad(functools.partial(loss, backend="reference"),
                      argnums=(0, 1))(x, w)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_window_offsets_zero_outside(self):
        """offsets[0] > 0 / offsets[-1] < N (the EP ring's local-expert
        window): rows outside come back exactly zero on both routes."""
        from apex_tpu.ops.grouped_matmul import (
            grouped_matmul, grouped_matmul_reference)

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(40, 32), jnp.float32)
        w = jnp.asarray(rng.randn(3, 32, 16) * 0.1, jnp.float32)
        off = jnp.asarray([7, 12, 12, 30], jnp.int32)
        for backend in ("reference", "kernel"):
            out = np.asarray(grouped_matmul(x, w, off, backend=backend))
            assert (out[:7] == 0).all() and (out[30:] == 0).all(), backend
        np.testing.assert_allclose(
            np.asarray(grouped_matmul(x, w, off, backend="kernel")),
            np.asarray(grouped_matmul_reference(x, w, off)),
            atol=1e-4, rtol=1e-4)

    def test_traced_offsets_under_jit(self):
        from apex_tpu.ops.grouped_matmul import (
            grouped_matmul, grouped_matmul_reference)

        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(24, 16), jnp.float32)
        w = jnp.asarray(rng.randn(4, 16, 8), jnp.float32)
        off = _offsets([3, 0, 17, 4])
        out = jax.jit(functools.partial(
            grouped_matmul, backend="kernel"))(x, w, off)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(grouped_matmul_reference(x, w, off)),
            atol=1e-4, rtol=1e-4)

    def test_backend_validation(self):
        from apex_tpu.ops.grouped_matmul import grouped_matmul

        x = jnp.zeros((4, 8))
        w = jnp.zeros((2, 8, 8))
        with pytest.raises(ValueError, match="bogus"):
            grouped_matmul(x, w, jnp.zeros((3,), jnp.int32),
                           backend="bogus")
        with pytest.raises(ValueError, match="offsets length"):
            grouped_matmul(x, w, jnp.zeros((2,), jnp.int32))


class TestRaggedRouting:
    """Capacity-free routing vs the capacity path at generous
    capacity_factor — both see every token, so the math must agree."""

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_matches_capacity_fp32_fwd_bwd(self, top_k):
        h, f, E = 32, 64, 8
        params = init_moe_params(jax.random.PRNGKey(0), h, f, E)
        x = _data(h=h, seed=11)

        def loss(p, routing):
            o = switch_moe_mlp(
                p, x, capacity_factor=float(E), top_k=top_k,
                ep_axis=None, routing=routing)
            return (jnp.mean(o.out.astype(jnp.float32) ** 2)
                    + 0.01 * o.aux_loss), o

        (lc, oc), gc = jax.value_and_grad(
            functools.partial(loss, routing="capacity"),
            has_aux=True)(params)
        (lr, orag), gr = jax.value_and_grad(
            functools.partial(loss, routing="ragged"),
            has_aux=True)(params)
        np.testing.assert_allclose(np.asarray(orag.out),
                                   np.asarray(oc.out),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(lr), float(lc), rtol=1e-6)
        np.testing.assert_allclose(float(orag.aux_loss),
                                   float(oc.aux_loss), rtol=1e-6)
        for name in gc:
            np.testing.assert_allclose(
                np.asarray(gr[name]), np.asarray(gc[name]),
                atol=2e-5, rtol=2e-3, err_msg=name)

    def test_matches_capacity_bf16_loose(self):
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(1), h, f, E)
        x = _data(h=h, seed=12).astype(jnp.bfloat16)
        cap = switch_moe_mlp(params, x, capacity_factor=float(E),
                             top_k=2, ep_axis=None)
        rag = switch_moe_mlp(params, x, top_k=2, ep_axis=None,
                             routing="ragged")
        np.testing.assert_allclose(
            np.asarray(rag.out, np.float32),
            np.asarray(cap.out, np.float32), atol=5e-2, rtol=5e-2)

    def test_swiglu_ragged_parity(self):
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(2), h, f, E,
                                 activation="swiglu")
        x = _data(h=h, seed=13)
        cap = switch_moe_mlp(params, x, capacity_factor=float(E),
                             top_k=2, ep_axis=None,
                             activation="swiglu")
        rag = switch_moe_mlp(params, x, top_k=2, ep_axis=None,
                             routing="ragged", activation="swiglu")
        np.testing.assert_allclose(np.asarray(rag.out),
                                   np.asarray(cap.out),
                                   atol=1e-5, rtol=1e-5)

    def test_dropped_fraction_exactly_zero_by_construction(self):
        """The capacity path drops under a hard-biased router; the
        ragged path must report EXACTLY 0.0 (not merely small) on the
        identical input — drop-freedom is structural, not statistical."""
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(3), h, f, E)
        params["router"] = params["router"].at[:, 0].add(10.0)
        x = _data(h=h)
        cap = switch_moe_mlp(params, x, capacity_factor=1.0,
                             ep_axis=None)
        assert float(cap.dropped_fraction) > 0.0
        rag = switch_moe_mlp(params, x, ep_axis=None, routing="ragged")
        assert float(rag.dropped_fraction) == 0.0
        assert np.isfinite(np.asarray(rag.out)).all()
        # every assignment lands on an expert: loads sum to b*s*top_k
        assert float(jnp.sum(rag.expert_load)) == x.shape[0] * x.shape[1]

    def test_top2_aux_counts_runner_up_traffic(self):
        """The balance term must see ALL k selections: with every
        token's top-1 spread but every top-2 on one expert, the
        argmax-only formula reports balance while the correct one
        reports the pileup (satellite fix)."""
        h, f, E = 32, 64, 4
        params = init_moe_params(jax.random.PRNGKey(4), h, f, E)
        x = _data(h=h, seed=14)
        out = switch_moe_mlp(params, x, capacity_factor=float(E),
                             top_k=2, ep_axis=None)
        # recompute both formulas from the router math
        logits = np.asarray(x, np.float64).reshape(-1, h) @ np.asarray(
            params["router"], np.float64)
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        top1 = probs.argmax(-1)
        masked = probs.copy()
        masked[np.arange(len(top1)), top1] = -1
        top2 = masked.argmax(-1)
        counts = (np.bincount(top1, minlength=E)
                  + np.bincount(top2, minlength=E))
        want = E * float(
            (counts / counts.sum() * probs.mean(0)).sum())
        argmax_only = E * float(
            (np.bincount(top1, minlength=E) / len(top1)
             * probs.mean(0)).sum())
        np.testing.assert_allclose(float(out.aux_loss), want, rtol=1e-4)
        assert abs(want - argmax_only) > 1e-6, (
            "fixture failed to separate the two formulas")
        np.testing.assert_allclose(np.asarray(out.expert_load), counts)

    def test_routing_validation(self):
        params = init_moe_params(jax.random.PRNGKey(0), 8, 16, 2)
        x = jnp.zeros((1, 4, 8))
        with pytest.raises(ValueError, match="routing"):
            switch_moe_mlp(params, x, routing="bogus")
        with pytest.raises(ValueError, match="moe_comm"):
            switch_moe_mlp(params, x, routing="ragged",
                           moe_comm="fp8")


class TestRaggedEPIsland:
    """The explicit expert-parallel island on the 8-virtual-device ep
    mesh: counted all_to_all dispatch with compressed wire, ring
    overlap, and the moe.* telemetry invariants."""

    E = 8

    def _setup(self, seed=0, dtype=jnp.float32):
        h, f = 32, 64
        params = init_moe_params(jax.random.PRNGKey(seed), h, f, self.E)
        x = _data(b=2, s=16, h=h, seed=seed).astype(dtype)
        mesh = create_mesh(ep=8)
        return params, x, mesh

    def _loss(self, params, x, **kw):
        o = switch_moe_mlp(params, x, top_k=2, routing="ragged", **kw)
        return (jnp.mean(o.out.astype(jnp.float32) ** 2)
                + 0.01 * o.aux_loss), o

    def test_island_matches_local_fp32_fwd_bwd(self):
        params, x, mesh = self._setup()
        (l_ref, o_ref), g_ref = jax.value_and_grad(
            functools.partial(self._loss, ep_axis=None),
            has_aux=True)(params, x)
        (l_is, o_is), g_is = jax.jit(jax.value_and_grad(
            functools.partial(self._loss, ep_mesh=mesh),
            has_aux=True))(params, x)
        np.testing.assert_allclose(np.asarray(o_is.out),
                                   np.asarray(o_ref.out),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(l_is), float(l_ref), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(o_is.expert_load),
                                   np.asarray(o_ref.expert_load))
        for name in g_ref:
            np.testing.assert_allclose(
                np.asarray(g_is[name]), np.asarray(g_ref[name]),
                atol=2e-5, rtol=2e-3, err_msg=name)

    def test_int8_dispatch_within_tolerance_and_wire_ratio(self):
        """int8 wire parity within the PR-2 error-feedback-style bound,
        and the trace-time telemetry must show wire < 0.3x raw."""
        from apex_tpu.observability import configure, shutdown
        from apex_tpu.observability import metrics as _telemetry

        params, x, mesh = self._setup(seed=5)
        _, o_ref = self._loss(params, x, ep_axis=None)
        reg = _telemetry.registry()
        owned = reg is None
        if owned:
            configure(stderr_summary=False)
            reg = _telemetry.registry()
        w0 = reg.counter("moe.dispatch_bytes").value
        r0 = reg.counter("moe.dispatch_raw_bytes").value
        try:
            (_, o), _ = jax.jit(jax.value_and_grad(
                functools.partial(self._loss, ep_mesh=mesh,
                                  moe_comm="int8"),
                has_aux=True))(params, x)
            wire = reg.counter("moe.dispatch_bytes").value - w0
            raw = reg.counter("moe.dispatch_raw_bytes").value - r0
        finally:
            if owned:
                shutdown()
        # int8 step bound on the FFN-output scale (coherent-sum form,
        # like the dryrun comm phase's reduce-scatter bound)
        scale = float(np.abs(np.asarray(o_ref.out)).max()) + 1e-6
        err = float(np.abs(np.asarray(o.out, np.float32)
                           - np.asarray(o_ref.out, np.float32)).max())
        assert err < 0.05 * scale, f"int8 err {err:.3e} vs {scale:.3e}"
        assert raw > 0 and wire < 0.3 * raw, (
            f"moe telemetry: wire {wire} not < 0.3x raw {raw}")

    def test_overlap_parity_and_ring_invariant(self):
        """Ring-overlapped dispatch/combine == the all_to_all island
        (fwd+bwd), and moe.ring_hops == (ep-1) x moe.ring_calls."""
        from apex_tpu.observability import configure, shutdown
        from apex_tpu.observability import metrics as _telemetry

        params, x, mesh = self._setup(seed=6)
        reg = _telemetry.registry()
        owned = reg is None
        if owned:
            configure(stderr_summary=False)
            reg = _telemetry.registry()
        c0 = reg.counter("moe.ring_calls").value
        h0 = reg.counter("moe.ring_hops").value
        try:
            (l_off, o_off), g_off = jax.jit(jax.value_and_grad(
                functools.partial(self._loss, ep_mesh=mesh,
                                  overlap_comm=False),
                has_aux=True))(params, x)
            assert reg.counter("moe.ring_calls").value == c0, (
                "overlap off must not ring")
            (l_on, o_on), g_on = jax.jit(jax.value_and_grad(
                functools.partial(self._loss, ep_mesh=mesh,
                                  overlap_comm=True),
                has_aux=True))(params, x)
            calls = reg.counter("moe.ring_calls").value - c0
            hops = reg.counter("moe.ring_hops").value - h0
        finally:
            if owned:
                shutdown()
        assert calls > 0 and hops == (8 - 1) * calls, (
            f"moe ring telemetry: hops {hops} != (ep-1) x calls "
            f"(7 x {calls})")
        np.testing.assert_allclose(np.asarray(o_on.out),
                                   np.asarray(o_off.out),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(float(l_on), float(l_off), rtol=1e-5)
        for name in g_off:
            np.testing.assert_allclose(
                np.asarray(g_on[name]), np.asarray(g_off[name]),
                atol=2e-5, rtol=2e-3, err_msg=name)

    def test_bf16_wire_loose(self):
        params, x, mesh = self._setup(seed=7)
        _, o_ref = self._loss(params, x, ep_axis=None)
        for overlap in (False, True):
            _, o = jax.jit(functools.partial(
                self._loss, ep_mesh=mesh, moe_comm="bf16",
                overlap_comm=overlap))(params, x)
            np.testing.assert_allclose(
                np.asarray(o.out, np.float32),
                np.asarray(o_ref.out, np.float32),
                atol=2e-2, rtol=2e-2)

    def test_bf16_compute_backward_through_ring(self):
        """bf16 activations through the overlap island, fwd AND bwd —
        pins the straight-through VJP's primal/cotangent dtype contract
        (the exchange runs fp32 internally regardless of compute
        dtype)."""
        params, x, mesh = self._setup(seed=7, dtype=jnp.bfloat16)
        (loss, _), grads = jax.jit(jax.value_and_grad(
            functools.partial(self._loss, ep_mesh=mesh,
                              overlap_comm=True),
            has_aux=True))(params, x)
        assert np.isfinite(float(loss))
        for name, g in grads.items():
            a = np.asarray(g, np.float32)
            assert np.isfinite(a).all() and np.abs(a).sum() > 0, name

    def test_ambient_mesh_activates_island(self):
        """Under jax.set_mesh the island self-activates from the
        abstract mesh — no explicit ep_mesh plumbing needed."""
        params, x, mesh = self._setup(seed=8)
        _, o_ref = self._loss(params, x, ep_axis=None)
        sharded = jax.device_put(params, {
            "router": NamedSharding(mesh, P()),
            "fc1": NamedSharding(mesh, P("ep")),
            "fc1_bias": NamedSharding(mesh, P("ep")),
            "fc2": NamedSharding(mesh, P("ep")),
            "fc2_bias": NamedSharding(mesh, P("ep")),
        })

        @jax.jit
        def run(p, xx):
            out, o = self._loss(p, xx)
            return out, o.out

        with jax.set_mesh(mesh):
            _, out = run(sharded, x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(o_ref.out),
                                   atol=1e-5, rtol=1e-5)

    def test_indivisible_tokens_fall_back_to_local(self):
        """b*s not divisible by ep: the island declines and the local
        ragged math runs (correctness over parallelism)."""
        h, f = 32, 64
        params = init_moe_params(jax.random.PRNGKey(9), h, f, self.E)
        x = _data(b=1, s=9, h=h, seed=9)   # 9 tokens, ep=8
        mesh = create_mesh(ep=8)
        ref = switch_moe_mlp(params, x, ep_axis=None, routing="ragged")
        got = switch_moe_mlp(params, x, routing="ragged", ep_mesh=mesh)
        np.testing.assert_allclose(np.asarray(got.out),
                                   np.asarray(ref.out),
                                   atol=1e-6, rtol=1e-6)
