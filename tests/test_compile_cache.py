"""serving/compile_cache.py: persistent AOT compile cache (ISSUE 17).

The cold-start acceptance pins: an executable saved by one process
must load in a FRESH process and produce bitwise-identical logits; a
changed :func:`code_version` digest must invalidate (miss, never a
wrong hit); a torn cache entry or manifest must degrade to a miss,
never a crash; and :func:`warmup_ladder` must prime every executable
the engine needs so a second engine over the same directory serves
with zero compile misses."""

import hashlib
import json
import os
import pickle
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.transformer_lm import init_gpt_params
from apex_tpu.serving import ServingEngine
from apex_tpu.serving import compile_cache as cc_mod
from apex_tpu.serving.compile_cache import (
    CompileCache, code_version, warmup_ladder)


def _cfg(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_position_embeddings", 64)
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@jax.jit
def _double(x):
    return x * 2.0


class TestCompileCacheUnit:
    def test_round_trip_same_dir_is_hit(self, tmp_path):
        x = jnp.arange(8, dtype=jnp.float32)
        a = CompileCache(str(tmp_path))
        fn = a.load_or_compile("double", _double, (x,))
        assert a.misses == 1 and a.hits == 0
        np.testing.assert_array_equal(np.asarray(fn(x)),
                                      np.asarray(x) * 2)
        # a fresh instance over the same dir (= a fresh process's view)
        b = CompileCache(str(tmp_path))
        fn2 = b.load_or_compile("double", _double, (x,))
        assert b.hits == 1 and b.misses == 0
        np.testing.assert_array_equal(np.asarray(fn2(x)),
                                      np.asarray(x) * 2)
        assert b.stats()["entries"] == 1

    def test_memo_short_circuits_counters(self, tmp_path):
        x = jnp.ones((4,), jnp.float32)
        cc = CompileCache(str(tmp_path))
        cc.load_or_compile("double", _double, (x,))
        cc.load_or_compile("double", _double, (x,))
        # second call served from the per-process memo: no new counts
        assert (cc.hits, cc.misses) == (0, 1)

    def test_sds_and_concrete_share_a_key(self, tmp_path):
        x = jnp.ones((4,), jnp.float32)
        sds = jax.ShapeDtypeStruct(x.shape, x.dtype)
        cc = CompileCache(str(tmp_path))
        assert (cc.key_for("double", (sds,))
                == cc.key_for("double", (x,)))

    def test_key_covers_avals_and_parts(self, tmp_path):
        cc = CompileCache(str(tmp_path))
        a = jnp.ones((4,), jnp.float32)
        b = jnp.ones((8,), jnp.float32)
        c = jnp.ones((4,), jnp.bfloat16)
        k = cc.key_for("f", (a,))
        assert cc.key_for("f", (b,)) != k
        assert cc.key_for("f", (c,)) != k
        assert cc.key_for("g", (a,)) != k
        assert cc.key_for("f", (a,), key_parts={"bucket": 8}) != k

    def test_stale_code_version_invalidates(self, tmp_path,
                                            monkeypatch):
        x = jnp.ones((4,), jnp.float32)
        a = CompileCache(str(tmp_path))
        a.load_or_compile("double", _double, (x,))
        assert a.misses == 1
        # the package "changed": same dir, new digest -> a different
        # key, so the old entry is orphaned, never wrongly hit
        monkeypatch.setattr(cc_mod, "code_version", lambda: "stale!")
        b = CompileCache(str(tmp_path))
        fn = b.load_or_compile("double", _double, (x,))
        assert b.misses == 1 and b.hits == 0
        np.testing.assert_array_equal(np.asarray(fn(x)),
                                      np.asarray(x) * 2)

    def test_torn_entry_is_miss_not_crash(self, tmp_path):
        x = jnp.ones((4,), jnp.float32)
        a = CompileCache(str(tmp_path))
        key = a.key_for("double", (x,))
        a.load_or_compile("double", _double, (x,))
        path = os.path.join(str(tmp_path), key + ".xc")
        with open(path, "wb") as f:
            f.write(b"\x00torn bytes, not a pickle")
        b = CompileCache(str(tmp_path))
        fn = b.load_or_compile("double", _double, (x,))
        assert b.misses == 1 and b.hits == 0
        np.testing.assert_array_equal(np.asarray(fn(x)),
                                      np.asarray(x) * 2)
        # the recompile overwrote the torn entry: next reader hits
        c = CompileCache(str(tmp_path))
        c.load_or_compile("double", _double, (x,))
        assert c.hits == 1

    def test_unpicklable_but_valid_pickle_is_miss(self, tmp_path):
        """A well-formed pickle of the WRONG shape (version skew)
        must also degrade to a miss."""
        x = jnp.ones((4,), jnp.float32)
        a = CompileCache(str(tmp_path))
        key = a.key_for("double", (x,))
        with open(os.path.join(str(tmp_path), key + ".xc"), "wb") as f:
            pickle.dump({"not": "an executable"}, f)
        fn = a.load_or_compile("double", _double, (x,))
        assert a.misses == 1
        np.testing.assert_array_equal(np.asarray(fn(x)),
                                      np.asarray(x) * 2)

    def test_torn_manifest_degrades_to_empty(self, tmp_path):
        with open(os.path.join(str(tmp_path), "manifest.json"),
                  "w") as f:
            f.write("{torn json")
        cc = CompileCache(str(tmp_path))
        assert cc.stats()["entries"] == 0
        x = jnp.ones((4,), jnp.float32)
        cc.load_or_compile("double", _double, (x,))
        # the save re-indexes: the manifest heals
        with open(os.path.join(str(tmp_path), "manifest.json")) as f:
            m = json.load(f)
        assert len(m) == 1

    def test_not_aot_able_returns_none(self, tmp_path):
        cc = CompileCache(str(tmp_path))
        assert cc.load_or_compile("plain", lambda x: x,
                                  (jnp.ones(2),)) is None

    def test_code_version_is_stable_in_process(self):
        assert code_version() == code_version()
        assert len(code_version()) == 16


def _mk_engine(model, d, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prompt_buckets", (8, 16))
    kw.setdefault("cache_layout", "paged")
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 24)
    return ServingEngine(params, cfg,
                         compile_cache_dir=(None if d is None
                                            else str(d)), **kw)


def _reqs(cfg, n=2):
    rng = np.random.RandomState(3)
    return [dict(prompt=rng.randint(0, cfg.vocab_size,
                                    (5 + i,)).astype(np.int32),
                 max_new_tokens=6) for i in range(n)]


class TestEngineRoundTrip:
    def test_cached_engine_tokens_identical_and_second_run_hits(
            self, model, tmp_path):
        cfg, params = model
        want = [r.tokens for r in _mk_engine(model, None).run(
            _reqs(cfg))]
        cold = _mk_engine(model, tmp_path)
        got = [r.tokens for r in cold.run(_reqs(cfg))]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        st = cold.stats()["compile_cache"]
        assert st["misses"] > 0
        # fresh engine over the primed dir: loads, no compiles
        warm = _mk_engine(model, tmp_path)
        got2 = [r.tokens for r in warm.run(_reqs(cfg))]
        for g, w in zip(got2, want):
            np.testing.assert_array_equal(g, w)
        st2 = warm.stats()["compile_cache"]
        assert st2["hits"] > 0 and st2["misses"] == 0

    def test_no_cache_dir_stats_none(self, model):
        assert _mk_engine(model, None).stats()["compile_cache"] is None

    def test_warmup_ladder_primes_everything(self, model, tmp_path):
        cfg, _ = model
        eng = _mk_engine(model, tmp_path, chunk_tokens=8)
        out = warmup_ladder(eng)
        assert out["skipped"] == [], out["skipped"]
        # prefill+insert per bucket, decode, sample, chunk
        assert out["entries"] == 2 * len(eng.buckets) + 3
        assert out["misses"] == out["entries"] and out["hits"] == 0
        assert out["ms"] > 0
        # a fresh engine warms from disk alone...
        warm = _mk_engine(model, tmp_path, chunk_tokens=8)
        out2 = warmup_ladder(warm)
        assert out2["hits"] == out["entries"]
        assert out2["misses"] == 0 and out2["skipped"] == []
        # ...and then serves with ZERO further cache misses
        got = [r.tokens for r in warm.run(_reqs(cfg))]
        assert warm.stats()["compile_cache"]["misses"] == 0
        want = [r.tokens for r in _mk_engine(
            model, None, chunk_tokens=8).run(_reqs(cfg))]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_warmup_without_cache_is_a_noop(self, model):
        out = warmup_ladder(_mk_engine(model, None))
        assert out["entries"] == 0
        assert out["skipped"] == [("*", "no compile_cache_dir")]


_FRESH = r"""
import hashlib, json, sys
import jax
import jax.numpy as jnp
import numpy as np
from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.generate import prefill
from apex_tpu.models.transformer_lm import init_gpt_params
from apex_tpu.serving.compile_cache import CompileCache

cfg = TransformerConfig(num_layers=1, hidden_size=32,
                        num_attention_heads=2, vocab_size=64,
                        max_position_embeddings=16,
                        compute_dtype=jnp.float32, remat=False)
params = init_gpt_params(jax.random.PRNGKey(0), cfg)
prompt = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0]], jnp.int32)
lens = jnp.asarray([4], jnp.int32)
cc = CompileCache(sys.argv[1])
fn = cc.load_or_compile(
    "prefill", prefill, (params, prompt, cfg),
    dict(prompt_lens=lens, max_len=8, cache_dtype=None),
    key_parts={"bucket": 8})
logits, _cache = fn(params, prompt, prompt_lens=lens)
print(json.dumps({
    "digest": hashlib.sha256(
        np.asarray(logits, np.float32).tobytes()).hexdigest(),
    "hits": cc.hits, "misses": cc.misses}))
"""


class TestFreshProcess:
    def test_fresh_process_load_bitwise_logits(self, tmp_path):
        """THE round-trip pin: process A compiles and saves, process B
        (no shared jit caches, no shared memo) loads the serialized
        executable and its logits are byte-for-byte identical."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        runs = []
        for _ in range(2):
            out = subprocess.run(
                [sys.executable, "-c", _FRESH, str(tmp_path)],
                capture_output=True, text=True, timeout=300, env=env,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
            assert out.returncode == 0, out.stderr[-2000:]
            runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
        cold, warm = runs
        assert cold["misses"] == 1 and cold["hits"] == 0
        assert warm["hits"] == 1 and warm["misses"] == 0
        assert warm["digest"] == cold["digest"]
