"""Fused sampling parity suite (ISSUE 8).

Three layers of pinning:

- **reference path = the historical sampler, bit for bit**: a local
  reimplementation of the pre-fusion op chain (temperature → lax.top_k
  / sort → nucleus cumsum → ``jax.random.categorical``) is the oracle;
  ``fused_sample(backend="reference")`` (and therefore the
  ``sample_logits`` thin wrapper) must match it exactly under matched
  PRNG keys, every filter combination, fp32 and bf16.
- **kernel path**: greedy rows are exact; the filters select exactly
  the reference support (bisection cutoffs vs ``filter_logits``); the
  draw is distributional — χ² over a tiled batch (the in-kernel
  counter RNG is per-row, so one call yields N independent draws).
  Runs through the Pallas interpret path on the 8-virtual-device CPU
  mesh (conftest), the same route the CI uses for the flash/paged
  kernels.
- **routing**: ``backend=None`` follows interpret mode; a malformed
  ``backend=`` raises.

Plus the greedy short-circuit satellite: ``temperature == 0`` returns
the argmax under ANY top_k/top_p combination — the filters cannot
change which token is largest.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generate import sample_logits
from apex_tpu.ops.fused_sampling import (
    filter_logits, fused_sample, sample_reference)

_NEG_INF = -1e30


def _naive_sample(logits, key, *, temperature=0.0, top_k=None,
                  top_p=None, vocab_limit=None):
    """The pre-ISSUE-8 ``sample_logits`` op chain, verbatim — the
    bit-compatibility oracle for the reference path."""
    if vocab_limit is not None:
        over = jnp.arange(logits.shape[-1]) >= vocab_limit
        logits = jnp.where(over[None], _NEG_INF, logits)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_p is None:
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits < kth, _NEG_INF, logits)
        return jax.random.categorical(key, logits).astype(jnp.int32)
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k is not None:
        kth = sorted_l[:, top_k - 1][:, None]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
        rank = jnp.arange(sorted_l.shape[-1])[None]
        sorted_l = jnp.where(rank >= top_k, _NEG_INF, sorted_l)
    probs = jax.nn.softmax(sorted_l, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep = (csum - probs) < top_p
    n_keep = jnp.maximum(jnp.sum(keep, axis=-1), 1)
    cutoff = jnp.take_along_axis(sorted_l, (n_keep - 1)[:, None],
                                 axis=-1)
    logits = jnp.where(logits < cutoff, _NEG_INF, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


FILTERS = [
    dict(),
    dict(top_k=5),
    dict(top_p=0.7),
    dict(top_k=8, top_p=0.8),
    dict(vocab_limit=40),
    dict(top_k=4, top_p=0.9, vocab_limit=50),
]


class TestReferenceBitCompat:
    @pytest.mark.parametrize("kw", FILTERS)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matched_key_equality_with_historical_chain(self, kw, dtype):
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(4, 64), dtype) * 2
        for seed in range(5):
            key = jax.random.PRNGKey(seed)
            want = _naive_sample(logits, key, temperature=0.8, **kw)
            got = fused_sample(logits, key, temperature=0.8,
                               backend="reference", **kw)
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(got), err_msg=str(kw))
            # the thin wrapper routes here off-TPU: same bits
            wrapped = sample_logits(logits, key, temperature=0.8, **kw)
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(wrapped))

    def test_vector_temperature_matches_engine_composition(self):
        """The serving engine's mixed-temperature contract: greedy rows
        argmax, sampled rows temperature-1 over pre-scaled logits —
        same key, same bits."""
        rng = np.random.RandomState(1)
        logits = jnp.asarray(rng.randn(5, 32), jnp.float32)
        temps = jnp.asarray([0.0, 0.5, 0.0, 1.3, 2.0], jnp.float32)
        key = jax.random.PRNGKey(3)
        greedy = _naive_sample(logits, key)
        sampled = _naive_sample(
            logits / jnp.maximum(temps, 1e-6)[:, None], key,
            temperature=1.0, top_k=6)
        want = jnp.where(temps > 0, sampled, greedy)
        got = fused_sample(logits, key, temperature=temps, top_k=6,
                           backend="reference")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


class TestGreedyShortCircuit:
    @pytest.mark.parametrize("kw", FILTERS)
    @pytest.mark.parametrize("backend", ["reference", "kernel"])
    def test_greedy_is_argmax_under_any_filter_combo(self, kw, backend):
        """The ISSUE 8 satellite: temperature 0 skips the filtering
        work entirely — top-k/top-p cannot change the argmax, so the
        output must equal the bare argmax for EVERY combination."""
        rng = np.random.RandomState(2)
        logits = jnp.asarray(rng.randn(6, 96), jnp.float32)
        want = np.asarray(logits).argmax(-1)
        if kw.get("vocab_limit"):
            want = np.asarray(logits)[:, : kw["vocab_limit"]].argmax(-1)
        got = fused_sample(logits, jax.random.PRNGKey(0),
                           temperature=0.0, backend=backend, **kw)
        np.testing.assert_array_equal(want, np.asarray(got),
                                      err_msg=f"{backend} {kw}")

    def test_sample_logits_greedy_unchanged_by_filters(self):
        rng = np.random.RandomState(3)
        logits = jnp.asarray(rng.randn(3, 50), jnp.float32)
        base = np.asarray(sample_logits(logits, jax.random.PRNGKey(0)))
        for kw in FILTERS:
            got = sample_logits(logits, jax.random.PRNGKey(0), **kw)
            want = base
            if kw.get("vocab_limit"):
                want = np.asarray(logits)[:, : kw["vocab_limit"]
                                          ].argmax(-1)
            np.testing.assert_array_equal(want, np.asarray(got),
                                          err_msg=str(kw))


class TestKernelPath:
    """``backend="kernel"`` — the fused Pallas kernel through the
    interpret route on the virtual-device mesh."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_support_matches_reference_filters(self, dtype):
        """Every kernel sample must land inside the EXACT support the
        reference filter chain keeps (bisection cutoff == sorted
        cutoff), for top-k, top-p, and their intersection."""
        rng = np.random.RandomState(4)
        row = jnp.asarray(rng.randn(1, 160), dtype) * 2
        tiled = jnp.tile(row, (256, 1))
        for kw in (dict(top_k=3), dict(top_p=0.6),
                   dict(top_k=7, top_p=0.8)):
            scaled = (row.astype(jnp.float32) / 0.9)
            f = np.asarray(filter_logits(scaled, **kw))[0]
            support = set(np.where(f > _NEG_INF / 2)[0].tolist())
            toks = np.asarray(fused_sample(
                tiled, jax.random.PRNGKey(11), temperature=0.9,
                backend="kernel", **kw))
            assert set(toks.tolist()) <= support, kw

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_chi_squared_distribution_parity(self, dtype):
        """One kernel call over N tiled rows = N independent draws
        (per-row counter RNG); their histogram must match the softmax
        distribution — χ²(v−1) under the 99.9th-percentile bound."""
        rng = np.random.RandomState(5)
        v, n = 8, 8192
        row = rng.randn(1, v).astype(np.float32)
        logits = jnp.asarray(row, dtype)
        p = np.asarray(jax.nn.softmax(
            logits.astype(jnp.float32) / 1.0))[0]
        toks = np.asarray(fused_sample(
            jnp.tile(logits, (n, 1)), jax.random.PRNGKey(9),
            temperature=1.0, backend="kernel"))
        counts = np.bincount(toks, minlength=v)
        chi2 = (((counts - n * p) ** 2) / (n * p)).sum()
        assert chi2 < 24.32, chi2      # chi2(7).ppf(0.999)

    def test_chi_squared_with_topk_filter(self):
        """The same distribution check against the FILTERED target —
        the kernel's cutoff + draw must compose correctly."""
        rng = np.random.RandomState(6)
        v, n, k = 16, 8192, 4
        row = jnp.asarray(rng.randn(1, v), jnp.float32)
        f = filter_logits(row / 0.8, top_k=k)
        p = np.asarray(jax.nn.softmax(f))[0]
        toks = np.asarray(fused_sample(
            jnp.tile(row, (n, 1)), jax.random.PRNGKey(13),
            temperature=0.8, top_k=k, backend="kernel"))
        counts = np.bincount(toks, minlength=v)
        live = p > 0
        assert counts[~live].sum() == 0
        chi2 = (((counts[live] - n * p[live]) ** 2)
                / (n * p[live])).sum()
        assert chi2 < 16.27, chi2      # chi2(3).ppf(0.999)

    def test_vector_temperature_greedy_rows_exact(self):
        rng = np.random.RandomState(7)
        logits = jnp.asarray(rng.randn(6, 200), jnp.float32)
        temps = jnp.asarray([0.0, 1.0, 0.0, 0.7, 0.0, 2.0], jnp.float32)
        got = np.asarray(fused_sample(logits, jax.random.PRNGKey(1),
                                      temperature=temps, top_k=5,
                                      backend="kernel"))
        want = np.asarray(logits).argmax(-1)
        greedy_rows = np.asarray(temps) == 0
        np.testing.assert_array_equal(got[greedy_rows],
                                      want[greedy_rows])

    def test_seeded_determinism_and_key_sensitivity(self):
        rng = np.random.RandomState(8)
        logits = jnp.asarray(rng.randn(64, 128), jnp.float32)
        a = np.asarray(fused_sample(logits, jax.random.PRNGKey(0),
                                    temperature=1.0, backend="kernel"))
        b = np.asarray(fused_sample(logits, jax.random.PRNGKey(0),
                                    temperature=1.0, backend="kernel"))
        c = np.asarray(fused_sample(logits, jax.random.PRNGKey(1),
                                    temperature=1.0, backend="kernel"))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_unpadded_vocab_and_vocab_limit(self):
        """A non-lane-multiple vocab pads in the wrapper; neither the
        padding nor ids past vocab_limit may ever be sampled."""
        rng = np.random.RandomState(9)
        logits = jnp.asarray(rng.randn(128, 53), jnp.float32)
        toks = np.asarray(fused_sample(logits, jax.random.PRNGKey(2),
                                       temperature=1.5,
                                       backend="kernel"))
        assert toks.max() < 53
        toks = np.asarray(fused_sample(logits, jax.random.PRNGKey(2),
                                       temperature=1.5, vocab_limit=7,
                                       backend="kernel"))
        assert toks.max() < 7


class TestRouting:
    def test_auto_follows_interpret_mode(self, monkeypatch):
        """reference vs kernel draw different stochastic streams from
        the same key — that observable difference proves interpret mode
        actually switched the path."""
        rng = np.random.RandomState(10)
        logits = jnp.asarray(rng.randn(64, 256), jnp.float32)
        key = jax.random.PRNGKey(5)
        ref = np.asarray(fused_sample(logits, key, temperature=1.0,
                                      backend="reference"))
        kern = np.asarray(fused_sample(logits, key, temperature=1.0,
                                       backend="kernel"))
        assert not np.array_equal(ref, kern)
        monkeypatch.delenv("APEX_TPU_PALLAS_INTERPRET", raising=False)
        np.testing.assert_array_equal(
            ref, np.asarray(fused_sample(logits, key, temperature=1.0)))
        monkeypatch.setenv("APEX_TPU_PALLAS_INTERPRET", "1")
        np.testing.assert_array_equal(
            kern, np.asarray(fused_sample(logits, key, temperature=1.0)))

    def test_malformed_backend_argument_raises(self):
        with pytest.raises(ValueError, match="backend"):
            fused_sample(jnp.zeros((1, 8)), jax.random.PRNGKey(0),
                         temperature=1.0, backend="fast")

    def test_invalid_sampling_args_raise(self):
        with pytest.raises(ValueError, match="temperature"):
            fused_sample(jnp.zeros((1, 8)), jax.random.PRNGKey(0),
                         temperature=-1.0)
        with pytest.raises(ValueError, match="top_k"):
            fused_sample(jnp.zeros((1, 8)), jax.random.PRNGKey(0),
                         temperature=1.0, top_k=0)

    def test_sample_reference_export_matches_wrapper(self):
        rng = np.random.RandomState(12)
        logits = jnp.asarray(rng.randn(3, 24), jnp.float32)
        key = jax.random.PRNGKey(4)
        np.testing.assert_array_equal(
            np.asarray(sample_reference(logits, key, temperature=0.6,
                                        top_k=3)),
            np.asarray(fused_sample(logits, key, temperature=0.6,
                                    top_k=3, backend="reference")))
