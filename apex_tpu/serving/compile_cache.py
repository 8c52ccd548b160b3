"""Persistent on-disk XLA compile cache for the serving tier (ISSUE 17).

Worker cold-start is trace-bound, not load-bound: a freshly spawned
worker re-traces and re-compiles the engine's ENTIRE bucket ladder —
one prefill + insert executable per prompt bucket, the decode step,
the sampler, the chunk step — before it can serve its first token,
which is why ``PoolController`` scale-ups historically blocked their
tick loop and flash crowds had to ride on pre-warmed ``min_*`` sizing.
Every one of those compiles is a pure function of static facts the
process knows up front, so this module makes them a *file*:

- :class:`CompileCache` persists ``jit(...).lower().compile()``
  executables (``jax.experimental.serialize_executable``) under a key
  that covers everything that could invalidate them: the call-site
  name and its static knobs (bucket, ``cache_wire``, spec config,
  ``chunk_tokens``), the exact input avals, the mesh geometry
  (device counts + backend platform), and a :func:`code_version`
  digest over the package's own source.  A stale digest is simply a
  different key — old entries are never *wrongly* hit, only orphaned.
- Writes follow the PR 11 artifact discipline: payloads and the
  manifest are written to a temp file and ``os.replace``d, so a
  crashed writer leaves either the old bytes or the new bytes, never
  a torn file.  A torn/corrupt/incompatible entry deserializes with
  an error and is treated as a MISS (recompiled and overwritten), not
  a crash — the cache can only ever make a worker faster.
- :func:`warmup_ladder` AOT-compiles (or loads) the whole ladder for
  one engine from ``ShapeDtypeStruct``s — no real batches, no device
  traffic — so ``ServingEngine(compile_cache_dir=)`` plus a primed
  directory turns the spawn-time trace storm into a few
  ``deserialize_and_load`` calls.

AOT call convention: a loaded/compiled executable is invoked with the
DYNAMIC arguments only — ``static_argnames`` are baked in at lowering
(``fn = cache.load_or_compile(...); fn(*dynamic_args)``).  The engine
routes its call sites accordingly (``ServingEngine._cc``).

Telemetry: ``serving.compile_cache.{hits,misses}`` counters and the
``serving.compile_cache.load_ms`` histogram; misses additionally land
in the existing ``compile.ms`` ledger via ``jax.monitoring`` (loads do
not compile, which is exactly what makes cold vs warm start visible —
``tools/telemetry_report.py compile_cache_summary`` reads both sides).
``docs/serving.md`` has the operator runbook (cache dir lifecycle,
priming, invalidation).
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import tempfile
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import serialize_executable as _se

from apex_tpu.observability import metrics as _telemetry
from apex_tpu.observability.device import compile_label

__all__ = ["CompileCache", "code_version", "warmup_ladder"]

_MANIFEST = "manifest.json"


@functools.lru_cache(maxsize=1)
def code_version() -> str:
    """Digest of everything that can silently invalidate a serialized
    executable: the package's own source text (any .py under
    ``apex_tpu/``), the jax version, and the backend platform.  Part
    of every cache key — an upgraded package or jax never *hits* a
    stale entry, it just compiles fresh under a new key."""
    h = hashlib.sha256()
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for root, dirs, files in os.walk(pkg):
        dirs.sort()
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            h.update(os.path.relpath(path, pkg).encode())
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                continue
    h.update(jax.__version__.encode())
    h.update(jax.default_backend().encode())
    return h.hexdigest()[:16]


def _leaf_sig(x) -> Any:
    """One leaf's contribution to the aval digest.  Arrays and
    ``ShapeDtypeStruct``s reduce to (shape, dtype) — a warmup lowering
    from SDSs and a serve-time call with concrete arrays must land on
    the SAME key.  Non-array leaves (a config dataclass riding in a
    static position) contribute their repr."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return [list(shape), str(dtype)]
    return repr(x)


def _avals_digest(args, kwargs) -> str:
    leaves, treedef = jax.tree_util.tree_flatten((args, dict(kwargs)))
    blob = json.dumps([str(treedef)] + [_leaf_sig(x) for x in leaves])
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


class CompileCache:
    """One on-disk executable store (module doc).  Safe to share a
    directory across processes: entry writes are atomic renames keyed
    by content-addressing inputs, so concurrent writers of the same
    key produce identical bytes and last-rename-wins is benign."""

    def __init__(self, cache_dir: str):
        self.dir = str(cache_dir)
        os.makedirs(self.dir, exist_ok=True)
        self._exe: Dict[str, Any] = {}       # per-process memo
        self._manifest = self._read_manifest()
        self.hits = 0
        self.misses = 0

    # -- keys ---------------------------------------------------------------

    def key_for(self, name: str, args=(), kwargs=None,
                key_parts: Optional[dict] = None) -> str:
        ident = {
            "name": name,
            "parts": {str(k): repr(v)
                      for k, v in (key_parts or {}).items()},
            "avals": _avals_digest(args, kwargs or {}),
            "code": code_version(),
            "mesh": [jax.device_count(), jax.local_device_count(),
                     jax.default_backend()],
        }
        return hashlib.sha256(
            json.dumps(ident, sort_keys=True).encode()).hexdigest()[:24]

    # -- the one entry point ------------------------------------------------

    def load_or_compile(self, name: str, jitfn, args=(), kwargs=None,
                        *, key_parts: Optional[dict] = None):
        """Return an AOT executable for ``jitfn`` at these avals —
        loaded from disk when a compatible serialized copy exists,
        compiled (and persisted) otherwise.  Call the result with the
        DYNAMIC args only.  Returns ``None`` when AOT is unavailable
        for this function on this backend (caller falls back to the
        plain jit call); cache trouble (torn entry, unpicklable tree)
        is downgraded to a miss, never an exception."""
        kwargs = kwargs or {}
        key = self.key_for(name, args, kwargs, key_parts)
        fn = self._exe.get(key)
        if fn is not None:
            return fn
        fn = self._load(key)
        if fn is not None:
            self.hits += 1
            _telemetry.counter("serving.compile_cache.hits").inc()
        else:
            self.misses += 1
            _telemetry.counter("serving.compile_cache.misses").inc()
            try:
                fn = jitfn.lower(*args, **kwargs).compile()
            except Exception:
                return None          # not AOT-able (e.g. no .lower)
            self._save(key, name, fn, key_parts)
        self._exe[key] = fn
        return fn

    # -- disk ---------------------------------------------------------------

    def _entry_path(self, key: str) -> str:
        return os.path.join(self.dir, key + ".xc")

    def _load(self, key: str):
        t0 = time.perf_counter()
        try:
            with open(self._entry_path(key), "rb") as f:
                payload, in_tree, out_tree, device_ids = pickle.load(f)
            # load onto the devices the executable was compiled for —
            # left to its default the loader spreads it over every
            # device of the backend
            by_id = {d.id: d for d in jax.devices()}
            fn = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception:
            # missing = cold; anything else = torn/corrupt/incompatible
            # bytes — either way the answer is "compile it", not a crash
            return None
        _telemetry.histogram("serving.compile_cache.load_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        return fn

    def _save(self, key: str, name: str, compiled,
              key_parts: Optional[dict]) -> None:
        try:
            payload, in_tree, out_tree = _se.serialize(compiled)
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()]
            blob = pickle.dumps((payload, in_tree, out_tree, device_ids))
        except Exception:
            return     # unserializable executable: memo-only this run
        self._atomic_write(self._entry_path(key), blob)
        self._manifest[key] = {
            "name": name,
            "parts": {str(k): repr(v)
                      for k, v in (key_parts or {}).items()},
            "bytes": len(blob),
            "code": code_version(),
            "created": time.time(),
        }
        self._write_manifest()

    def _atomic_write(self, path: str, blob: bytes) -> None:
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def _read_manifest(self) -> dict:
        try:
            with open(os.path.join(self.dir, _MANIFEST)) as f:
                m = json.load(f)
            return m if isinstance(m, dict) else {}
        except (OSError, ValueError):
            # missing/torn manifest degrades to empty — entries are
            # rediscovered (and re-indexed) as they are saved again
            return {}

    def _write_manifest(self) -> None:
        blob = json.dumps(self._manifest, indent=1,
                          sort_keys=True).encode()
        self._atomic_write(os.path.join(self.dir, _MANIFEST), blob)

    # -- operator surface ---------------------------------------------------

    def stats(self) -> dict:
        return {"dir": self.dir, "entries": len(self._manifest),
                "hits": self.hits, "misses": self.misses}


# -- AOT bucket-ladder warmup ----------------------------------------------

def _sds(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def _tree_sds(tree):
    return jax.tree_util.tree_map(_sds, tree)


def warmup_ladder(engine) -> dict:
    """AOT-compile (or load from ``engine``'s compile cache) every
    executable the engine can need: one prefill + KV-insert pair per
    prompt bucket, the decode step, the sampler, and — when chunked
    prefill is on — the chunk step.  Shapes come from
    ``ShapeDtypeStruct``s and ``jax.eval_shape``, so warmup moves no
    batch data and allocates nothing on device beyond what XLA's
    compiler itself needs.

    Per-entry failures are collected, not raised: warmup is an
    optimization and an exotic config must degrade to trace-at-first-
    use, never block a worker from coming up.  Returns a summary dict
    (``entries``, ``hits``, ``misses``, ``skipped`` with reasons,
    ``ms``) — ``tools/measure_all.py cold_vs_warm_start`` and the
    worker READY path both log it."""
    from apex_tpu.models.generate import prefill
    from apex_tpu.serving.engine import (
        _insert_slot, _make_chunk_fn, _make_decode_fn, _make_sample_fn)
    from apex_tpu.serving.paged_cache import (
        blocks_for, paged_insert_prefill, paged_insert_prefill_q)

    cc = engine._compile_cache
    if cc is None:
        return {"entries": 0, "hits": 0, "misses": 0,
                "skipped": [("*", "no compile_cache_dir")], "ms": 0.0}
    t0 = time.perf_counter()
    hits0, miss0 = cc.hits, cc.misses
    entries = 0
    skipped = []
    p_sds = _tree_sds(engine.params)
    cache_sds = _tree_sds(engine.cache)
    key_sds = _sds(engine._key)
    paged = engine._mgr is not None
    ms = engine.max_slots

    def _one(label, fn):
        nonlocal entries
        try:
            with compile_label("serving.warmup"):
                if fn() is not None:
                    entries += 1
                else:
                    skipped.append((label, "not AOT-able"))
        except Exception as e:      # noqa: BLE001 — see docstring
            skipped.append((label, f"{type(e).__name__}: {e}"[:200]))

    logits_sds = None
    for bucket in engine.buckets:
        padded = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        lens = jax.ShapeDtypeStruct((1,), jnp.int32)
        lower_kw = dict(prompt_lens=lens, max_len=bucket,
                        cache_dtype=engine._cache_dtype)
        _one(f"prefill[{bucket}]", lambda: cc.load_or_compile(
            "prefill", prefill, (p_sds, padded, engine.cfg), lower_kw,
            key_parts=engine._cc_parts(bucket=bucket)))
        try:
            logits_sds, small_sds = jax.eval_shape(
                lambda p, t, l, _b=bucket: prefill(
                    p, t, engine.cfg, prompt_lens=l, max_len=_b,
                    cache_dtype=engine._cache_dtype),
                p_sds, padded, lens)
        except Exception as e:      # noqa: BLE001
            skipped.append((f"insert[{bucket}]",
                            f"{type(e).__name__}: {e}"[:200]))
            continue
        ks, vs = small_sds["k"], small_sds["v"]
        length = jnp.int32(0)
        if paged:
            wid = jax.ShapeDtypeStruct(
                (blocks_for(bucket, engine.block_size),), jnp.int32)
            if engine.cache_wire == "int8":
                _one(f"insert[{bucket}]", lambda: cc.load_or_compile(
                    "paged_insert_prefill_q", paged_insert_prefill_q,
                    (cache_sds["k"], cache_sds["v"],
                     cache_sds["k_scale"], cache_sds["v_scale"],
                     ks, vs, wid, length),
                    dict(block_size=engine.block_size),
                    key_parts=engine._cc_parts(bucket=bucket)))
            else:
                _one(f"insert[{bucket}]", lambda: cc.load_or_compile(
                    "paged_insert_prefill", paged_insert_prefill,
                    (cache_sds["k"], cache_sds["v"], ks, vs, wid,
                     length),
                    dict(block_size=engine.block_size),
                    key_parts=engine._cc_parts(bucket=bucket)))
        else:
            _one(f"insert[{bucket}]", lambda: cc.load_or_compile(
                "_insert_slot", _insert_slot,
                (cache_sds, ks, vs, length, length),
                key_parts=engine._cc_parts(bucket=bucket)))

    sampling = engine._sampling
    decode_fn = _make_decode_fn(engine.cfg, sampling["top_k"],
                                sampling["top_p"],
                                sampling["vocab_limit"], paged,
                                engine._spec, engine._decode_fused)
    pend = jax.ShapeDtypeStruct((ms,), jnp.int32)
    temps = jax.ShapeDtypeStruct((ms,), jnp.float32)
    active = jax.ShapeDtypeStruct((ms,), jnp.bool_)
    dargs = [p_sds, cache_sds]
    if paged:
        dargs.append(jax.ShapeDtypeStruct(
            (ms, engine._tables.shape[1]), jnp.int32))
    if engine._spec is not None:
        dargs += [_tree_sds(engine._history), _tree_sds(engine._hist_len)]
    dargs += [pend, temps, active, key_sds]
    _one("decode", lambda: cc.load_or_compile(
        "decode", decode_fn, tuple(dargs),
        key_parts=engine._cc_parts()))

    if logits_sds is not None:
        sample_fn = _make_sample_fn(sampling["top_k"], sampling["top_p"],
                                    sampling["vocab_limit"])
        _one("sample", lambda: cc.load_or_compile(
            "sample", sample_fn,
            (logits_sds, jax.ShapeDtypeStruct((1,), jnp.float32),
             key_sds),
            key_parts=engine._cc_parts()))

    if engine.chunk_tokens:
        chunk_fn = _make_chunk_fn(engine.cfg, paged)
        chunk = jax.ShapeDtypeStruct((engine.chunk_tokens,), jnp.int32)
        pos = jnp.int32(0)
        if paged:
            cargs = (p_sds, cache_sds,
                     jax.ShapeDtypeStruct((engine._tables.shape[1],),
                                          jnp.int32),
                     chunk, pos, pos, pos)
        else:
            cargs = (p_sds, cache_sds, chunk, pos, pos, pos)
        _one("chunk", lambda: cc.load_or_compile(
            "chunk", chunk_fn, cargs, key_parts=engine._cc_parts()))

    out = {"entries": entries, "hits": cc.hits - hits0,
           "misses": cc.misses - miss0, "skipped": skipped,
           "ms": round((time.perf_counter() - t0) * 1e3, 3)}
    _telemetry.event("serving.compile_cache.warmup", **dict(
        out, skipped=len(skipped)))
    return out
