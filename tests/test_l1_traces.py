"""L1 convergence-trace tests.

Rebuild of the reference's L1 strategy (tests/L1/common/run_test.sh:19-40
+ compare.py): a deterministic short training run is traced (loss +
global grad norm per step); the fp32 O0 trace is pinned against a stored
golden file (catches any numerical regression, 1-step resolution), and
the mixed-precision levels must track the O0 trace within per-level
tolerances (the reference compares O1/O2/O3 runs against a stored O0
baseline of ResNet-50; here the workload is the tiny in-repo GPT).

Regenerate the golden file after an *intentional* numerics change:
    python tests/test_l1_traces.py --regen
"""

import json
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.amp.frontend import make_train_step
from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.transformer_lm import gpt_loss, init_gpt_params
from apex_tpu.optimizers import fused_adam
from apex_tpu.optimizers._common import GradientTransformation, global_norm

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "l1_trace_o0.json")
GOLDEN_GQA = os.path.join(os.path.dirname(__file__), "data",
                          "l1_trace_gqa_o0.json")
N_STEPS = 12


class _NormState(NamedTuple):
    inner: Any
    grad_norm: jax.Array


def _norm_tracking(tx: GradientTransformation) -> GradientTransformation:
    """Record the global grad norm in the optimizer state (the L1 trace's
    second channel, reference compare.py)."""

    def init(params):
        return _NormState(tx.init(params), jnp.zeros((), jnp.float32))

    def update(grads, state, params=None):
        updates, inner = tx.update(grads, state.inner, params)
        return updates, _NormState(inner, global_norm(grads))

    return GradientTransformation(init, update)


def _cfg(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_position_embeddings", 32)
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


def _data(cfg, b=8, s=16):
    rng = np.random.RandomState(1234)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32)
    return tokens, labels


def run_trace(opt_level: str, n_steps: int = N_STEPS, cfg=None):
    """Deterministic training trace: (losses, grad_norms) per step."""
    cfg = cfg if cfg is not None else _cfg()
    params = init_gpt_params(jax.random.PRNGKey(42), cfg)
    tokens, labels = _data(cfg)

    def loss_fn(p, t, l):
        return gpt_loss(p, t, l, cfg)

    tx = _norm_tracking(fused_adam(lr=1e-3))
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level)
    step_fn = jax.jit(step_fn)
    state = init_fn(params)
    losses, norms = [], []
    for _ in range(n_steps):
        state, metrics = step_fn(state, tokens, labels)
        losses.append(float(metrics["loss"]))
        norms.append(float(state.opt_state.grad_norm))
    return np.array(losses), np.array(norms)


class TestL1Traces:
    def test_o0_matches_stored_golden(self):
        """1-step-resolution regression pin for fp32 numerics."""
        assert os.path.exists(GOLDEN), (
            "golden trace missing; run `python tests/test_l1_traces.py "
            "--regen` and commit tests/data/l1_trace_o0.json")
        with open(GOLDEN) as f:
            gold = json.load(f)
        losses, norms = run_trace("O0")
        np.testing.assert_allclose(
            losses, np.array(gold["loss"]), rtol=2e-5, atol=1e-6,
            err_msg="O0 loss trace drifted from the stored baseline")
        np.testing.assert_allclose(
            norms, np.array(gold["grad_norm"]), rtol=2e-4, atol=1e-5,
            err_msg="O0 grad-norm trace drifted from the stored baseline")

    @pytest.mark.parametrize("level,loss_tol,norm_tol", [
        ("O1", 2e-2, 0.15),
        ("O2", 2e-2, 0.15),
        ("O5", 2e-2, 0.15),
    ])
    def test_amp_levels_track_o0(self, level, loss_tol, norm_tol):
        """Mixed precision must converge along the fp32 trajectory
        (reference run_test.sh opt-level cross-product vs O0 baseline)."""
        ref_losses, ref_norms = run_trace("O0")
        losses, norms = run_trace(level)
        np.testing.assert_allclose(
            losses, ref_losses, rtol=loss_tol,
            err_msg=f"{level} loss trace diverged from O0")
        np.testing.assert_allclose(
            norms, ref_norms, rtol=norm_tol,
            err_msg=f"{level} grad-norm trace diverged from O0")
        # and training must actually make progress
        assert losses[-1] < losses[0]


def run_trace_mesh(dp: int, tp: int, sp: int = 1,
                   context_parallel=False, n_steps: int = N_STEPS):
    """The same O0 trace under GSPMD dp/tp (and optionally sp context
    parallelism) on the 8-device mesh — the reference
    tests/L1/cross_product_distributed analog (run.sh repeats the
    convergence comparison under a 2-GPU launch)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.models.transformer_lm import gpt_param_specs, gspmd_ctx
    from apex_tpu.parallel.mesh import create_mesh

    cfg = _cfg()
    mesh = create_mesh(dp=dp, tp=tp, pp=1, sp=sp)
    ctx = (gspmd_ctx(seq_axis="sp", context_parallel=context_parallel)
           if context_parallel else gspmd_ctx())
    params = init_gpt_params(jax.random.PRNGKey(42), cfg)
    params = jax.device_put(
        params,
        jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), gpt_param_specs(cfg),
            is_leaf=lambda x: isinstance(x, P)))
    tokens, labels = _data(cfg)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp")))
    labels = jax.device_put(labels, NamedSharding(mesh, P("dp")))

    def loss_fn(p, t, l):
        return gpt_loss(p, t, l, cfg, ctx)

    tx = _norm_tracking(fused_adam(lr=1e-3))
    init_fn, step_fn = make_train_step(loss_fn, tx, "O0")
    step_fn = jax.jit(step_fn)
    losses, norms = [], []
    with jax.set_mesh(mesh):
        state = init_fn(params)
        for _ in range(n_steps):
            state, metrics = step_fn(state, tokens, labels)
            losses.append(float(metrics["loss"]))
            norms.append(float(state.opt_state.grad_norm))
    return np.array(losses), np.array(norms)


class TestL1TracesDistributed:
    """Multi-device L1: the dp and dp×tp shardings must track the stored
    single-device golden — same model, same batch, same trajectory."""

    # [4-2] stays default: it is the only default-tier MULTI-STEP
    # optimizer-trajectory parity check across shardings (the dryrun
    # gate deliberately stops at single-shot loss/grads). The pure-dp
    # re-factoring of the same golden rides the slow tier.
    @pytest.mark.parametrize(
        "dp,tp", [pytest.param(8, 1, marks=pytest.mark.slow), (4, 2)])
    def test_sharded_trace_matches_golden(self, dp, tp):
        if len(jax.devices()) < dp * tp:
            pytest.skip("needs the 8-device mesh")
        with open(GOLDEN) as f:
            gold = json.load(f)
        losses, norms = run_trace_mesh(dp, tp)
        np.testing.assert_allclose(
            losses, np.array(gold["loss"]), rtol=1e-4, atol=1e-5,
            err_msg=f"dp={dp},tp={tp} loss trace drifted from the "
                    "single-device golden")
        np.testing.assert_allclose(
            norms, np.array(gold["grad_norm"]), rtol=1e-3, atol=1e-4,
            err_msg=f"dp={dp},tp={tp} grad-norm trace drifted from the "
                    "single-device golden")

    # ring stays default-tier: the only multi-STEP trajectory pin of the
    # long-context path (the dryrun gate asserts single-shot parity);
    # ulysses re-pins the same golden through the other collective
    # pattern and rides the slow tier
    @pytest.mark.parametrize(
        "mode", ["ring", pytest.param("ulysses", marks=pytest.mark.slow)])
    def test_context_parallel_trace_matches_golden(self, mode):
        """Context parallelism is not allowed to bend the optimizer
        trajectory: 12 steps under dp=2 x sp=4 must track the stored
        single-device golden."""
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device mesh")
        with open(GOLDEN) as f:
            gold = json.load(f)
        losses, norms = run_trace_mesh(2, 1, sp=4, context_parallel=mode)
        np.testing.assert_allclose(
            losses, np.array(gold["loss"]), rtol=1e-4, atol=1e-5,
            err_msg=f"cp={mode} loss trace drifted from the golden")
        np.testing.assert_allclose(
            norms, np.array(gold["grad_norm"]), rtol=1e-3, atol=1e-4,
            err_msg=f"cp={mode} grad-norm trace drifted from the golden")


class TestL1TracesGQA:
    """The GQA path gets its own golden: the group-major
    layout landed in round 5 and future refactors must not bend its
    numerics.  Same regen protocol: `python tests/test_l1_traces.py
    --regen` rewrites both goldens."""

    def test_gqa_o0_matches_stored_golden(self):
        assert os.path.exists(GOLDEN_GQA), (
            "GQA golden trace missing; run `python tests/test_l1_traces"
            ".py --regen` and commit tests/data/l1_trace_gqa_o0.json")
        with open(GOLDEN_GQA) as f:
            gold = json.load(f)
        losses, norms = run_trace("O0", cfg=_cfg(num_query_groups=2))
        np.testing.assert_allclose(
            losses, np.array(gold["loss"]), rtol=2e-5, atol=1e-6,
            err_msg="GQA O0 loss trace drifted from the stored baseline")
        np.testing.assert_allclose(
            norms, np.array(gold["grad_norm"]), rtol=2e-4, atol=1e-5,
            err_msg="GQA O0 grad-norm trace drifted from the baseline")

    @pytest.mark.slow   # O2 tracks its own-golden's trajectory; CI job
    def test_gqa_amp_tracks_o0(self):
        ref_losses, _ = run_trace("O0", cfg=_cfg(num_query_groups=2))
        losses, _ = run_trace("O2", cfg=_cfg(num_query_groups=2))
        np.testing.assert_allclose(
            losses, ref_losses, rtol=2e-2,
            err_msg="GQA O2 loss trace diverged from GQA O0")
        assert losses[-1] < losses[0]


if __name__ == "__main__":
    import sys
    if "--regen" in sys.argv:
        for path, cfg in ((GOLDEN, None),
                          (GOLDEN_GQA, _cfg(num_query_groups=2))):
            losses, norms = run_trace("O0", cfg=cfg)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump({"loss": losses.tolist(),
                           "grad_norm": norms.tolist()}, f, indent=1)
            print(f"wrote {path}")
