"""Summarize apex_tpu telemetry JSONL files into a per-metric table.

    python tools/telemetry_report.py FILE.jsonl [FILE2.jsonl ...]
    python tools/telemetry_report.py --since-step 1000 FILE.jsonl

Reads one or more telemetry streams (the JSONL sink of
``apex_tpu.observability`` — schema in docs/observability.md) and
prints:

- spans/observations: count, total, mean, p50, p95, max (exact — every
  observation is in the stream, unlike the live in-process summary's
  bounded window);
- counters: the cumulative total per name — last flush record per run
  segment (the JSONL sink appends, so one file can hold several runs,
  each opening with a ``meta`` record), summed across segments and
  files, so both multi-host runs and repeated runs into one path
  aggregate correctly;
- gauges: count, last, min, max;
- events: count per name;
- sketches (schema v3): the mergeable log-bucket histogram states the
  registry flushes for high-volume serving series — merged exactly
  across segments/files (same discipline as
  ``tools/aggregate_telemetry.py``, which is the dedicated fleet-merge
  tool) and reported as p50/p95/p99 with the sketch's bounded relative
  error;
- truncation flags (schema v3 ``summary`` records): any series whose
  *live in-process* quantiles were computed over a truncated window
  (the deque histograms keep the last 4096 observations — before v3, a
  p95 over the last 4096 of N≫4096 observations looked exact) is
  called out by name with observed-vs-retained counts.  The JSONL
  span/observe series themselves are exact — the flag is about what
  the in-process summary (stderr table, flight dumps, OpenMetrics
  summary families) could see;
- derived views when their series are present: ring collectives
  (``collectives.ring.*`` → implied tp), speculative decoding
  (``generate.spec.*`` → accept rate + verify-call amortization), the
  paged serving engine (``serving.blocks_*`` +
  ``serving.preemptions`` → block-pool high-water, preemption rate,
  prefix-share ratio), async checkpointing (``checkpoint.*`` →
  save/restore ms p50/p95, bytes, overlap ratio, rollback count), the
  persistent AOT compile cache (``serving.compile_cache.*`` +
  ``worker.ready_ms`` → hit rate, load p50/p95 vs the ``compile.ms``
  ledger, worker READY wall), and
  the Tier-B jaxpr audit (``audit.*`` → per-entry-point
  census-vs-counter deltas — accounting drift visible in reports, not
  just in the static_audit CI gate), and the Tier-C concurrency
  stress (``audit.tierc.*`` → realized scrape/flush/save/churn counts
  with the zero-underflow / zero-new-findings gates).

``--since-step N`` keeps only records stamped with ``step >= N``
(schema v2 stamps every record emitted after the loop declared a step
index); records that carry no ``step`` at all — the ``meta`` record,
pre-loop configuration, trace-time counters — are kept, so the filter
narrows the time series without hiding run identity.

Tolerance policy (a post-mortem tool must read wounded data): garbage
lines warn and are skipped; records with a *newer* ``schema_version``
warn once and are best-effort parsed; records *missing* the field
entirely (a hand-edited stream, a pre-ISSUE-1 writer) warn once and
are parsed the same way — one corrupt or future-version record never
hides a whole campaign's data.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

SUPPORTED_SCHEMA = 3

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_sketch_module():
    """``apex_tpu/observability/sketches.py`` by path (stdlib-only by
    contract) — this report must run on boxes without jax."""
    path = os.path.join(_ROOT, "apex_tpu", "observability", "sketches.py")
    spec = importlib.util.spec_from_file_location("_apex_sketch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pct(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def load_records(paths: Iterable[str], out=None) -> List[dict]:
    """Parse every line of every file; each record is tagged with its
    source file index under ``_src`` (counter aggregation needs it)."""
    out = sys.stdout if out is None else out
    records: List[dict] = []
    for src, path in enumerate(paths):
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    print(f"warning: {path}:{lineno}: unparseable line "
                          "skipped", file=out)
                    continue
                if not isinstance(rec, dict):
                    print(f"warning: {path}:{lineno}: non-object record "
                          "skipped", file=out)
                    continue
                rec["_src"] = src
                records.append(rec)
    return records


def filter_since_step(records: List[dict],
                      since_step: Optional[int]) -> List[dict]:
    """Keep records stamped ``step >= since_step``; records with no
    ``step`` field (meta, pre-loop, trace-time) pass through."""
    if since_step is None:
        return records
    return [r for r in records
            if not isinstance(r.get("step"), (int, float))
            or r["step"] >= since_step]


def _tags_suffix(tags) -> str:
    """``{k=v,...}`` display suffix for tagged series (ISSUE 7: the
    per-``slo_class`` goodput counters and latency sketches are real
    metric dimensions — collapsing them would re-mix the classes)."""
    if not tags or not isinstance(tags, dict):
        return ""
    inner = ",".join(f"{k}={v}" for k, v in sorted(tags.items()))
    return "{" + inner + "}"


def summarize(records: List[dict]) -> dict:
    spans: Dict[str, List[float]] = {}
    counters: Dict[Tuple[int, int, str], float] = {}
    sketches: Dict[Tuple[int, int, str], dict] = {}
    truncated: Dict[str, dict] = {}
    gauges: Dict[str, List[float]] = {}
    events: Dict[str, int] = {}
    unknown_schema = set()
    missing_schema = 0
    epoch: Dict[int, int] = {}   # per-file run segment (meta-delimited)
    for rec in records:
        ver = rec.get("schema_version")
        if isinstance(ver, (int, float)) and ver > SUPPORTED_SCHEMA:
            unknown_schema.add(ver)
        elif ver is None:
            missing_schema += 1
        rtype, name = rec.get("type"), rec.get("name")
        if rtype == "meta":
            # the JSONL sink appends, so one file can hold several runs;
            # each run starts with a meta record and restarts its
            # counters from zero — segment so totals sum, not clobber
            epoch[rec["_src"]] = epoch.get(rec["_src"], -1) + 1
        if rtype in ("span", "observe") and name is not None:
            try:
                spans.setdefault(name, []).append(float(rec["value"]))
            except (KeyError, TypeError, ValueError):
                pass
        elif rtype == "counter" and name is not None:
            try:
                # cumulative within a run: keep the last flush value per
                # (file, run segment)
                key = (rec["_src"], epoch.get(rec["_src"], 0),
                       name + _tags_suffix(rec.get("tags")))
                counters[key] = float(rec["value"])
            except (KeyError, TypeError, ValueError):
                pass
        elif rtype == "sketch" and name is not None:
            # cumulative like counters: last serialized state per
            # (file, run segment) is that stream's final sketch
            if isinstance(rec.get("value"), dict):
                key = (rec["_src"], epoch.get(rec["_src"], 0),
                       name + _tags_suffix(rec.get("tags")))
                sketches[key] = rec["value"]
        elif rtype == "summary" and name is not None:
            # per-histogram truncation accounting (ISSUE 7 satellite):
            # remember any series whose live quantile window dropped
            # observations — last state per display key wins
            v = rec.get("value")
            if isinstance(v, dict) and v.get("truncated"):
                truncated[name + _tags_suffix(rec.get("tags"))] = v
        elif rtype == "gauge" and name is not None:
            try:
                # tagged gauges keep their tag suffix (ISSUE 14: the
                # per-dtype serving.cache_* series must stay separable
                # when one stream holds both ablation engines);
                # untagged gauges keep their historical bare keys
                gauges.setdefault(
                    name + _tags_suffix(rec.get("tags")), []).append(
                        float(rec["value"]))
            except (KeyError, TypeError, ValueError):
                pass
        elif rtype == "event" and name is not None:
            events[name] = events.get(name, 0) + 1
    counter_totals: Dict[str, float] = {}
    for (_src, _epoch, cname), val in counters.items():
        counter_totals[cname] = counter_totals.get(cname, 0.0) + val
    sketch_summaries: Dict[str, dict] = {}
    if sketches:
        sk = _load_sketch_module()
        by_series: Dict[str, list] = {}
        for (_src, _epoch, sname), state in sketches.items():
            try:
                by_series.setdefault(sname, []).append(
                    sk.LogBucketSketch.from_dict(state))
            except (KeyError, TypeError, ValueError):
                pass
        for sname, parts in by_series.items():
            merged = sk.LogBucketSketch.merged(parts)
            if merged is not None:
                sketch_summaries[sname] = merged.summary()
    return {
        "spans": spans,
        "counters": counter_totals,
        "sketches": sketch_summaries,
        "truncated": truncated,
        "gauges": gauges,
        "events": events,
        "unknown_schema": sorted(unknown_schema),
        "missing_schema": missing_schema,
    }


def ring_summary(counters: Dict[str, float]) -> Optional[dict]:
    """Derived view of the ``collectives.ring.*`` counters (the
    overlapped TP collective-matmul paths): per-call hop count and the
    implied ring size, since each ring loop books exactly tp−1 hops —
    ``hops == (tp−1) × calls`` on a fixed-tp program.  None when the
    stream carries no ring calls."""
    calls = counters.get("collectives.ring.calls", 0.0)
    if not calls:
        return None
    hops = counters.get("collectives.ring.hops", 0.0)
    per_call = hops / calls
    integral = abs(per_call - round(per_call)) < 1e-9
    return {
        "calls": calls,
        "hops": hops,
        "bytes": counters.get("collectives.ring.bytes", 0.0),
        "hops_per_call": per_call,
        "tp": int(round(per_call)) + 1 if integral else None,
    }


def spec_summary(counters: Dict[str, float]) -> Optional[dict]:
    """Derived view of the speculative-decoding counters
    (``generate.spec.*``, ISSUE 8): accept rate = accepted/draft —
    how much of the drafter's work the target model agreed with — and
    the verify-call amortization, emitted tokens per verify forward =
    ``(accepted + verify_calls) / verify_calls`` (every verify also
    yields its correction/bonus token, so the floor is 1.0 and the
    ceiling is k+1).  None when the stream carries no draft counters
    (spec off, or a pre-ISSUE-8 writer)."""
    draft = counters.get("generate.spec.draft_tokens", 0.0)
    if not draft:
        return None
    accepted = counters.get("generate.spec.accepted_tokens", 0.0)
    verify = counters.get("generate.spec.verify_calls", 0.0)
    return {
        "draft_tokens": draft,
        "accepted_tokens": accepted,
        "verify_calls": verify,
        "accept_rate": accepted / draft,
        "tokens_per_verify": ((accepted + verify) / verify) if verify
        else None,
    }


def moe_summary(summary: dict) -> Optional[dict]:
    """Derived view of the expert-parallel MoE telemetry (``moe.*``,
    ISSUE 10): dispatch wire bytes vs the raw fp32 payload (the
    compression the EP fast path actually achieved on the wire), the
    ring hop check — each MoE ring books exactly ep−1 hops, so
    ``hops == (ep−1) × calls`` and the implied ep falls out — and the
    expert-load imbalance max/mean ratio from the bench-probe gauges
    (1.0 = perfectly balanced routing).  None when the stream carries
    no MoE series (dense models, pre-ISSUE-10 writers)."""
    counters = summary["counters"]
    gauges = summary["gauges"]
    wire = counters.get("moe.dispatch_bytes", 0.0)
    raw = counters.get("moe.dispatch_raw_bytes", 0.0)
    calls = counters.get("moe.ring_calls", 0.0)
    load_max = gauges.get("moe.expert_load_max")
    if not (wire or raw or calls or load_max):
        return None
    out = {
        "dispatch_bytes": wire,
        "dispatch_raw_bytes": raw,
        "wire_over_raw": (wire / raw) if raw else None,
        "ring_calls": calls,
        "ring_hops": counters.get("moe.ring_hops", 0.0),
        "hops_per_call": None,
        "ep": None,
    }
    if calls:
        per = out["ring_hops"] / calls
        out["hops_per_call"] = per
        if abs(per - round(per)) < 1e-9:
            out["ep"] = int(round(per)) + 1
    if load_max:
        lmax = load_max[-1]
        lmean = (gauges.get("moe.expert_load_mean") or [0.0])[-1]
        out["expert_load_max"] = lmax
        out["expert_load_mean"] = lmean
        out["load_imbalance"] = (lmax / lmean) if lmean else None
    return out


def checkpoint_summary(summary: dict) -> Optional[dict]:
    """Derived view of the async-checkpoint telemetry (``checkpoint.*``,
    ISSUE 11): save/restore wall p50/p95 (ms, from the span series —
    exact, every save is in the stream), bytes written, the last
    observed overlap ratio (1.0 = the write was entirely hidden behind
    the next step), and the rollback count (each one is an
    ``anomaly.rollback`` incident the flight recorder also holds).
    None when the stream carries no checkpoint series (runs without a
    saver, pre-ISSUE-11 writers)."""
    spans = summary["spans"]
    counters = summary["counters"]
    saves = counters.get("checkpoint.saves", 0.0)
    restores = counters.get("checkpoint.restores", 0.0)
    rollbacks = counters.get("checkpoint.rollbacks", 0.0)
    if not (saves or restores or rollbacks):
        return None

    def _ms(name):
        vals = sorted(spans.get(name) or [])
        if not vals:
            return None
        return {"p50": _pct(vals, 0.50) * 1e3,
                "p95": _pct(vals, 0.95) * 1e3,
                "count": len(vals)}

    overlap = summary["gauges"].get("checkpoint.overlap_ratio")
    return {
        "saves": saves,
        "restores": restores,
        "rollbacks": rollbacks,
        "bytes": counters.get("checkpoint.bytes", 0.0),
        "save_ms": _ms("checkpoint.save"),
        "blocking_ms": _ms("checkpoint.blocking"),
        "restore_ms": _ms("checkpoint.restore"),
        "overlap_ratio": overlap[-1] if overlap else None,
    }


def audit_summary(counters: Dict[str, float]) -> Optional[dict]:
    """Derived view of the Tier-B jaxpr-audit telemetry (``audit.*``,
    ISSUE 12): for every audited entry point, the per-collective-kind
    jaxpr census vs the trace-time ``collectives.*`` counter delta the
    auditor observed while tracing it.  ``census > counted`` is the
    accounting hole the static_audit gate fails on (a collective
    emitted around the counted wrappers); ``counted > census`` is the
    benign custom_vjp re-trace direction.

    ISSUE 13 adds the **tier-C row** under the reserved key
    ``"tier_c"``: the ``audit.tierc.*`` counters the
    ``concurrency_audit`` stress smoke emits (scrapes / flushes /
    saves / admits / preempts, the realized ``sketch_count`` vs
    ``sketch_expected``, and the must-be-zero gates
    ``refcount_underflows`` / ``new_findings`` /
    ``scrape_parse_failures`` / ``prefetch_leaked`` /
    ``threads_wedged`` / ``pool_undrained``).  ``clean`` folds every
    gate present in the stream, so a smoke the dryrun phase failed
    can never render as ok — with ONE documented exception: the
    apex-tpu-* thread-leak check runs after telemetry shutdown and is
    therefore gate-only.  None when the stream carries no audit
    counters (runs without ``tools/lint.py --audit`` or the
    ``dryrun_static_audit``/``dryrun_concurrency_audit`` stages)."""
    entries: Dict[str, dict] = {}
    tier_c: Dict[str, float] = {}
    for key, val in counters.items():
        if not key.startswith("audit."):
            continue
        base, _, tag = key.partition("{")
        entry = "?"
        if tag.startswith("entry="):
            entry = tag[len("entry="):].rstrip("}")
        parts = base.split(".")
        if len(parts) != 3:
            continue
        if parts[1] == "tierc":
            tier_c[parts[2]] = tier_c.get(parts[2], 0.0) + val
            continue
        if parts[1] not in ("census", "counted"):
            continue
        kind = parts[2]
        slot = entries.setdefault(entry, {}).setdefault(
            kind, {"census": 0.0, "counted": 0.0})
        slot[parts[1]] += val
    if not entries and not tier_c:
        return None
    out: Dict[str, dict] = {}
    for entry, kinds in sorted(entries.items()):
        rows = {}
        for kind, v in sorted(kinds.items()):
            rows[kind] = {
                "census": v["census"],
                "counted": v["counted"],
                "delta": v["census"] - v["counted"],
            }
        out[entry] = {
            "kinds": rows,
            "drift": any(r["delta"] > 0 for r in rows.values()),
        }
    if tier_c:
        zero_gates = ("refcount_underflows", "new_findings",
                      "scrape_parse_failures", "prefetch_leaked",
                      "threads_wedged", "pool_undrained")
        clean = all(tier_c.get(g, 0.0) == 0.0 for g in zero_gates)
        if "sketch_count" in tier_c and "sketch_expected" in tier_c:
            clean = clean and (tier_c["sketch_count"]
                               == tier_c["sketch_expected"])
        out["tier_c"] = {
            "stress": dict(sorted(tier_c.items())),
            "clean": clean,
        }
    return out


def serving_summary(summary: dict) -> Optional[dict]:
    """Derived view of the paged serving engine's telemetry (ISSUE 6):
    block-pool high-water mark, preemption rate per admitted request,
    and the prefix-share ratio — shared physical blocks at the pool's
    high-water instant are the HBM that sharing saved.  None when the
    stream carries no paged-pool gauges (contiguous engines emit only
    the slot/queue series)."""
    gauges = summary["gauges"]
    in_use = gauges.get("serving.blocks_in_use")
    if not in_use:
        return None
    counters = summary["counters"]
    high_water = max(in_use)
    shared = gauges.get("serving.prefix_shared_blocks", [0.0])
    # the engine sets both gauges in the same _set_gauges call, so the
    # series align record-for-record and "shared at the high-water
    # instant" is the paired sample; a truncated/merged stream where
    # they diverge falls back to the series max (an upper bound)
    if len(shared) == len(in_use):
        shared_at_hw = shared[max(range(len(in_use)),
                                  key=in_use.__getitem__)]
    else:
        shared_at_hw = max(shared)
    requests = counters.get("serving.requests", 0.0)
    preemptions = counters.get("serving.preemptions", 0.0)
    return {
        "blocks_high_water": high_water,
        "blocks_last": in_use[-1],
        "preemptions": preemptions,
        "requests": requests,
        "preemption_rate": (preemptions / requests) if requests else 0.0,
        "prefix_shared_high_water": max(shared),
        "prefix_share_ratio": (shared_at_hw / high_water) if high_water
        else 0.0,
    }


def quantized_cache_summary(summary: dict) -> Optional[dict]:
    """Derived view of the at-rest KV-pool accounting (ISSUE 14): the
    ``serving.cache_bytes{dtype=}`` / ``serving.cache_capacity_tokens
    {dtype=}`` / ``serving.cache_blocks_hw{dtype=}`` gauges, folded
    per dtype into bytes-per-resident-token and — when the stream
    holds two dtypes (the ``--cache-dtype`` ablation) — the implied
    admission multiple at matched pool bytes (tokens-per-byte ratio of
    the cheapest form over the dearest).  None when the stream carries
    no cache_bytes series (pre-ISSUE-14 writers)."""
    gauges = summary["gauges"]
    per_dtype: Dict[str, dict] = {}
    for key, vals in gauges.items():
        if not key.startswith("serving.cache_bytes{dtype="):
            continue
        dtype = key[len("serving.cache_bytes{dtype="):].rstrip("}")
        cap = gauges.get(
            f"serving.cache_capacity_tokens{{dtype={dtype}}}")
        hw = gauges.get(f"serving.cache_blocks_hw{{dtype={dtype}}}")
        entry = {
            "cache_bytes": vals[-1],
            "capacity_tokens": cap[-1] if cap else None,
            "pool_high_water_blocks": max(hw) if hw else None,
            "bytes_per_token": (vals[-1] / cap[-1])
            if cap and cap[-1] else None,
        }
        per_dtype[dtype] = entry
    if not per_dtype:
        return None
    out = {"dtypes": per_dtype, "admission_multiple": None}
    rated = {d: e["bytes_per_token"] for d, e in per_dtype.items()
             if e["bytes_per_token"]}
    if len(rated) >= 2:
        cheap = min(rated, key=rated.get)
        dear = max(rated, key=rated.get)
        out["admission_multiple"] = rated[dear] / rated[cheap]
        out["cheapest"] = cheap
        out["dearest"] = dear
    return out


def controller_summary(summary: dict) -> Optional[dict]:
    """Derived view of the elastic pool controller's telemetry
    (``controller.*``, ISSUE 15): actions taken by kind and pool
    (``controller.actions{action=,pool=}`` counters), requests drained
    losslessly off scaled-down workers, chip-seconds consumed (the
    integral of live workers over wall time — the number the diurnal
    ablation trades against goodput), and the final pool sizes.  None
    when the stream carries no controller series (static topologies,
    pre-ISSUE-15 writers)."""
    counters = summary["counters"]
    gauges = summary["gauges"]
    actions: Dict[Tuple[str, str], float] = {}
    for name, val in counters.items():
        if not name.startswith("controller.actions{"):
            continue
        inner = name[len("controller.actions{"):].rstrip("}")
        tags = dict(p.split("=", 1) for p in inner.split(",") if "=" in p)
        key = (tags.get("action", "?"), tags.get("pool", "?"))
        actions[key] = actions.get(key, 0.0) + val
    chip = gauges.get("controller.chip_seconds")
    pool_sizes = {}
    for name, vals in gauges.items():
        if name.startswith("controller.pool_size{pool="):
            pool = name[len("controller.pool_size{pool="):].rstrip("}")
            pool_sizes[pool] = vals[-1]
    if not (actions or chip or pool_sizes):
        return None
    return {
        "actions": {f"{a}:{p}": v
                    for (a, p), v in sorted(actions.items())},
        "spawns": sum(v for (a, _p), v in actions.items()
                      if a == "spawn"),
        "drains": sum(v for (a, _p), v in actions.items()
                      if a == "drain"),
        "drained_requests": counters.get(
            "controller.drained_requests", 0.0),
        "chip_seconds": chip[-1] if chip else None,
        "pool_size_last": pool_sizes or None,
    }


def compile_cache_summary(summary: dict) -> Optional[dict]:
    """Derived view of the persistent AOT compile cache (ISSUE 17):
    hit rate over ``load_or_compile`` calls
    (``serving.compile_cache.{hits,misses}``), the cache-load wall
    p50/p95 (``serving.compile_cache.load_ms`` — what a warm start
    pays per executable) against the cumulative XLA compile ledger
    (``compile.count`` / ``compile.ms``: backend steps that compiled,
    what every miss costs; ``compile.load.count`` / ``compile.load.ms``:
    those JAX's persistent cache served — the start-up ledger's
    jax.monitoring mirror), warmup-ladder runs, and the
    worker READY wall (``worker.ready_ms`` gauge — one sample per
    worker process, so count ≈ workers in the stream).  None when the
    stream carries no compile-cache or READY series (engines without
    ``compile_cache_dir``, pre-ISSUE-17 writers)."""
    counters = summary["counters"]
    hits = counters.get("serving.compile_cache.hits", 0.0)
    misses = counters.get("serving.compile_cache.misses", 0.0)
    ready = summary["gauges"].get("worker.ready_ms")
    if not (hits or misses or ready):
        return None
    load = sorted(summary["spans"].get(
        "serving.compile_cache.load_ms") or [])
    calls = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "hit_rate": (hits / calls) if calls else None,
        "load_ms": ({"p50": _pct(load, 0.50), "p95": _pct(load, 0.95),
                     "count": len(load)} if load else None),
        "compile_count": counters.get("compile.count", 0.0),
        "compile_ms_total": counters.get("compile.ms", 0.0),
        "load_count": counters.get("compile.load.count", 0.0),
        "load_ms_total": counters.get("compile.load.ms", 0.0),
        "warmups": summary["events"].get(
            "serving.compile_cache.warmup", 0),
        "ready_ms": ({"count": len(ready), "last": ready[-1],
                      "min": min(ready), "max": max(ready)}
                     if ready else None),
    }


def host_tier_summary(summary: dict) -> Optional[dict]:
    """Derived view of the hierarchical KV cache's host-DRAM tier
    (ISSUE 18): take-side hit rate over parked-page lookups
    (``serving.host_tier.{hits,misses}``), the resume-vs-replay ratio
    (paged-in resumptions over prefill replays — the fraction of
    re-admissions the tier turned into a scatter instead of a forward
    pass), page-in latency p50/p95 from the mergeable
    ``serving.host_tier.page_in_ms`` sketch, the parked-bytes
    high-water mark, and fleet prefix-affinity routing hits
    (``cluster.prefix_affinity_hits``).  None when the stream carries
    no host-tier series (tier off, pre-ISSUE-18 writers)."""
    counters = summary["counters"]
    gauges = summary["gauges"]
    hits = counters.get("serving.host_tier.hits", 0.0)
    misses = counters.get("serving.host_tier.misses", 0.0)
    hbytes = gauges.get("serving.host_tier.bytes")
    if not (hits or misses or hbytes):
        return None
    sketches = summary.get("sketches") or {}
    resumes = counters.get("serving.host_tier.resumes", 0.0)
    replays = counters.get("serving.host_tier.replays", 0.0)
    readmits = resumes + replays
    lookups = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "hit_rate": (hits / lookups) if lookups else None,
        "evictions": counters.get("serving.host_tier.evictions", 0.0),
        "page_ins": counters.get("serving.host_tier.page_ins", 0.0),
        "prefetches": counters.get("serving.host_tier.prefetches", 0.0),
        "resumes": resumes,
        "replays": replays,
        "resume_ratio": (resumes / readmits) if readmits else None,
        "bytes_high_water": max(hbytes) if hbytes else 0.0,
        "pages_high_water": max(
            gauges.get("serving.host_tier.pages") or [0.0]),
        "page_in_ms": sketches.get("serving.host_tier.page_in_ms"),
        "page_out_ms": sketches.get("serving.host_tier.page_out_ms"),
        "prefix_affinity_hits": counters.get(
            "cluster.prefix_affinity_hits", 0.0),
    }


def adapter_summary(summary: dict) -> Optional[dict]:
    """Derived view of the multi-tenant LoRA adapter pool (ISSUE 20):
    acquire-side hit rate over slab-pool lookups
    (``serving.adapter.{hits,misses}``), evictions, residency
    high-water from the ``serving.adapter.{resident,bytes}`` gauges,
    per-adapter request counts from the tagged
    ``serving.adapter.requests{adapter=N}`` counters, and fleet
    adapter-affinity routing hits (``cluster.adapter_affinity_hits``).
    None when the stream carries no adapter series (pool off,
    pre-ISSUE-20 writers)."""
    counters = summary["counters"]
    gauges = summary["gauges"]
    hits = counters.get("serving.adapter.hits", 0.0)
    misses = counters.get("serving.adapter.misses", 0.0)
    per_adapter: Dict[str, float] = {}
    prefix = "serving.adapter.requests{adapter="
    for name, val in counters.items():
        if name.startswith(prefix) and name.endswith("}"):
            per_adapter[name[len(prefix):-1]] = val
    if not (hits or misses or per_adapter):
        return None
    lookups = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "hit_rate": (hits / lookups) if lookups else None,
        "evictions": counters.get("serving.adapter.evictions", 0.0),
        "requests": sum(per_adapter.values()),
        "per_adapter": per_adapter,
        "distinct_adapters": len(per_adapter),
        "resident_high_water": max(
            gauges.get("serving.adapter.resident") or [0.0]),
        "bytes_high_water": max(
            gauges.get("serving.adapter.bytes") or [0.0]),
        "adapter_affinity_hits": counters.get(
            "cluster.adapter_affinity_hits", 0.0),
    }


def print_report(summary: dict, out=None) -> None:
    out = sys.stdout if out is None else out
    if summary["unknown_schema"]:
        print("warning: records with newer schema_version "
              f"{summary['unknown_schema']} (supported <= "
              f"{SUPPORTED_SCHEMA}); summarizing known fields", file=out)
    if summary.get("missing_schema"):
        print(f"warning: {summary['missing_schema']} record(s) missing "
              "schema_version; best-effort parse", file=out)
    spans = summary["spans"]
    if spans:
        print("== spans / observations ==", file=out)
        print(f"{'name':<44} {'count':>7} {'total':>11} {'mean':>11} "
              f"{'p50':>11} {'p95':>11} {'max':>11}", file=out)
        for name in sorted(spans):
            vals = sorted(spans[name])
            total = sum(vals)
            print(f"{name:<44} {len(vals):>7} {total:>11.5g} "
                  f"{total / len(vals):>11.5g} {_pct(vals, 0.50):>11.5g} "
                  f"{_pct(vals, 0.95):>11.5g} {vals[-1]:>11.5g}", file=out)
    sketches = summary.get("sketches") or {}
    if sketches:
        print("== sketches (merged exactly across segments/files) ==",
              file=out)
        print(f"{'name':<44} {'count':>8} {'p50':>11} {'p95':>11} "
              f"{'p99':>11} {'max':>11}", file=out)
        for name in sorted(sketches):
            s = sketches[name]
            print(f"{name:<44} {s['count']:>8} {s['p50']:>11.5g} "
                  f"{s['p95']:>11.5g} {s['p99']:>11.5g} "
                  f"{s['max']:>11.5g}", file=out)
    truncated = summary.get("truncated") or {}
    if truncated:
        print("== TRUNCATED live quantile windows ==", file=out)
        for name in sorted(truncated):
            v = truncated[name]
            print(f"  {name}: live p50/p95 covered only the last "
                  f"{v.get('retained', '?')} of {v.get('observed', '?')} "
                  "observations — in-process summaries (stderr table, "
                  "flight dumps) are NOT exact for this series; the "
                  "span table above (full stream) is", file=out)
    counters = summary["counters"]
    if counters:
        print("== counters ==", file=out)
        print(f"{'name':<44} {'total':>13}", file=out)
        for name in sorted(counters):
            print(f"{name:<44} {counters[name]:>13g}", file=out)
    ring = ring_summary(counters) if counters else None
    if ring:
        print("== ring collectives (collectives.ring.*) ==", file=out)
        print(f"  calls {ring['calls']:g}  hops {ring['hops']:g}  "
              f"bytes {ring['bytes']:g}", file=out)
        if ring["tp"] is not None:
            print(f"  hops/call {ring['hops_per_call']:g} -> ring size "
                  f"(tp) {ring['tp']}", file=out)
        else:
            print(f"  hops/call {ring['hops_per_call']:.3g} — NOT an "
                  "integer: the stream mixes ring sizes (several tp "
                  "geometries in one run), per-call invariant still "
                  "hops == (tp-1) x calls within each", file=out)
    spec = spec_summary(counters) if counters else None
    if spec:
        print("== speculative decoding (generate.spec.*) ==", file=out)
        print(f"  draft {spec['draft_tokens']:g}  accepted "
              f"{spec['accepted_tokens']:g} -> accept rate "
              f"{spec['accept_rate']:.3g}", file=out)
        if spec["tokens_per_verify"] is not None:
            print(f"  verify calls {spec['verify_calls']:g} -> "
                  f"tokens/verify {spec['tokens_per_verify']:.3g} "
                  "(amortization; ceiling is k+1)", file=out)
    moe = moe_summary(summary)
    if moe:
        print("== expert-parallel MoE (moe.*) ==", file=out)
        if moe["dispatch_raw_bytes"]:
            print(f"  dispatch wire {moe['dispatch_bytes']:g} / raw "
                  f"{moe['dispatch_raw_bytes']:g} -> "
                  f"{moe['wire_over_raw']:.3g}x on the wire", file=out)
        if moe["ring_calls"]:
            if moe["ep"] is not None:
                print(f"  ring calls {moe['ring_calls']:g}  hops "
                      f"{moe['ring_hops']:g} -> hops/call "
                      f"{moe['hops_per_call']:g} -> ep "
                      f"{moe['ep']}", file=out)
            else:
                print(f"  ring hops/call {moe['hops_per_call']:.3g} — "
                      "NOT an integer: the stream mixes ep sizes; the "
                      "invariant hops == (ep-1) x calls still holds "
                      "within each", file=out)
        if moe.get("load_imbalance") is not None:
            print(f"  expert load max {moe['expert_load_max']:g} / "
                  f"mean {moe['expert_load_mean']:g} -> imbalance "
                  f"{moe['load_imbalance']:.3g} (1.0 = balanced)",
                  file=out)
    ckpt = checkpoint_summary(summary)
    if ckpt:
        print("== checkpointing (checkpoint.*) ==", file=out)
        line = (f"  saves {ckpt['saves']:g}  bytes {ckpt['bytes']:g}")
        if ckpt["overlap_ratio"] is not None:
            line += f"  overlap ratio {ckpt['overlap_ratio']:.3g}"
        print(line, file=out)
        for label, key in (("save", "save_ms"),
                           ("loop-thread blocking", "blocking_ms"),
                           ("restore", "restore_ms")):
            ms = ckpt[key]
            if ms:
                print(f"  {label} ms p50 {ms['p50']:.4g}  p95 "
                      f"{ms['p95']:.4g}  (n={ms['count']})", file=out)
        if ckpt["restores"]:
            print(f"  restores {ckpt['restores']:g}", file=out)
        if ckpt["rollbacks"]:
            print(f"  ROLLBACKS {ckpt['rollbacks']:g} — detector-driven "
                  "recovery fired; see the flight-recorder dump "
                  "(tools/health_report.py) for the incident(s)",
                  file=out)
    audit = audit_summary(counters) if counters else None
    if audit:
        print("== jaxpr audit (audit.*) ==", file=out)
        tier_c = audit.get("tier_c")
        for entry, info in audit.items():
            if entry == "tier_c":
                continue
            flag = ("ACCOUNTING DRIFT — census exceeds counters; see "
                    "the static_audit gate" if info["drift"] else "ok")
            print(f"  {entry}: {flag}", file=out)
            for kind, r in info["kinds"].items():
                mark = ""
                if r["delta"] > 0:
                    mark = "  <-- uncounted collective(s)"
                elif r["delta"] < 0:
                    mark = "  (custom_vjp re-trace overcount)"
                print(f"    {kind:<14} census {r['census']:g}  counted "
                      f"{r['counted']:g}{mark}", file=out)
        if tier_c:
            flag = ("ok" if tier_c["clean"] else
                    "FAILED — see the concurrency_audit gate")
            s = tier_c["stress"]
            print(f"  tier C (concurrency stress): {flag}", file=out)
            print("    "
                  + "  ".join(f"{k} {v:g}" for k, v in s.items()),
                  file=out)
    qcache = quantized_cache_summary(summary)
    if qcache:
        print("== quantized KV cache (serving.cache_bytes{dtype=}) ==",
              file=out)
        for dtype, e in sorted(qcache["dtypes"].items()):
            bpt = e["bytes_per_token"]
            line = f"  {dtype}: pool {e['cache_bytes']:g} B"
            if bpt is not None:
                line += f"  {bpt:.4g} B/resident-token"
            if e["pool_high_water_blocks"] is not None:
                line += (f"  high-water "
                         f"{e['pool_high_water_blocks']:g} blocks")
            print(line, file=out)
        if qcache["admission_multiple"] is not None:
            print(f"  admission multiple at matched bytes: "
                  f"{qcache['admission_multiple']:.3g}x "
                  f"({qcache['cheapest']} over {qcache['dearest']})",
                  file=out)
    ctrl = controller_summary(summary)
    if ctrl:
        print("== elastic pool controller (controller.*) ==", file=out)
        parts = [f"spawns {ctrl['spawns']:g}",
                 f"drains {ctrl['drains']:g}",
                 f"drained requests {ctrl['drained_requests']:g}"]
        if ctrl["chip_seconds"] is not None:
            parts.append(f"chip-seconds {ctrl['chip_seconds']:g}")
        print("  " + "  ".join(parts), file=out)
        for key, v in sorted(ctrl["actions"].items()):
            print(f"    {key:<20} {v:g}", file=out)
        if ctrl["pool_size_last"]:
            sizes = "  ".join(
                f"{pool}:{int(v)}" for pool, v in
                sorted(ctrl["pool_size_last"].items()))
            print(f"  final pool sizes {sizes}", file=out)
    cc = compile_cache_summary(summary)
    if cc:
        print("== compile cache (serving.compile_cache.*) ==", file=out)
        line = f"  hits {cc['hits']:g}  misses {cc['misses']:g}"
        if cc["hit_rate"] is not None:
            line += f" -> hit rate {cc['hit_rate']:.3g}"
        if cc["warmups"]:
            line += f"  (warmup ladders {cc['warmups']:g})"
        print(line, file=out)
        if cc["load_ms"]:
            ld = cc["load_ms"]
            print(f"  load ms p50 {ld['p50']:.4g}  p95 {ld['p95']:.4g}  "
                  f"(n={ld['count']})", file=out)
        if cc["compile_count"]:
            print(f"  XLA compiles {cc['compile_count']:g} -> "
                  f"{cc['compile_ms_total']:g} ms total (what each "
                  "miss costs)", file=out)
        if cc["load_count"]:
            print(f"  XLA persistent-cache loads {cc['load_count']:g} -> "
                  f"{cc['load_ms_total']:g} ms total", file=out)
        if cc["ready_ms"]:
            r = cc["ready_ms"]
            print(f"  worker READY ms last {r['last']:g}  min "
                  f"{r['min']:g}  max {r['max']:g}  "
                  f"(n={r['count']} workers)", file=out)
    ht = host_tier_summary(summary)
    if ht:
        print("== host-DRAM KV tier (serving.host_tier.*) ==", file=out)
        line = f"  hits {ht['hits']:g}  misses {ht['misses']:g}"
        if ht["hit_rate"] is not None:
            line += f" -> hit rate {ht['hit_rate']:.3g}"
        if ht["evictions"]:
            line += f"  evictions {ht['evictions']:g}"
        print(line, file=out)
        if ht["resume_ratio"] is not None:
            print(f"  resumes {ht['resumes']:g} / replays "
                  f"{ht['replays']:g} -> resume ratio "
                  f"{ht['resume_ratio']:.3g} (1.0 = every re-admission "
                  "was a page-in, no prefill replayed)", file=out)
        print(f"  parked high-water {ht['bytes_high_water']:g} B / "
              f"{ht['pages_high_water']:g} pages  page-ins "
              f"{ht['page_ins']:g}  prefetches {ht['prefetches']:g}",
              file=out)
        for label, key in (("page-in", "page_in_ms"),
                           ("page-out", "page_out_ms")):
            s = ht[key]
            if s:
                print(f"  {label} ms p50 {s['p50']:.4g}  p95 "
                      f"{s['p95']:.4g}  (n={s['count']})", file=out)
        if ht["prefix_affinity_hits"]:
            print(f"  prefix-affinity routed dispatches "
                  f"{ht['prefix_affinity_hits']:g}", file=out)
    ad = adapter_summary(summary)
    if ad:
        print("== multi-tenant adapters (serving.adapter.*) ==",
              file=out)
        line = f"  pool hits {ad['hits']:g}  misses {ad['misses']:g}"
        if ad["hit_rate"] is not None:
            line += f" -> hit rate {ad['hit_rate']:.3g}"
        if ad["evictions"]:
            line += f"  evictions {ad['evictions']:g}"
        print(line, file=out)
        print(f"  requests {ad['requests']:g} across "
              f"{ad['distinct_adapters']} adapter(s)  resident "
              f"high-water {ad['resident_high_water']:g} slab(s) / "
              f"{ad['bytes_high_water']:g} B", file=out)
        if ad["per_adapter"]:
            top = sorted(ad["per_adapter"].items(),
                         key=lambda kv: (-kv[1], kv[0]))[:8]
            print("  requests by adapter " + "  ".join(
                f"{aid}:{int(v)}" for aid, v in top), file=out)
        if ad["adapter_affinity_hits"]:
            print(f"  adapter-affinity routed dispatches "
                  f"{ad['adapter_affinity_hits']:g}", file=out)
    serving = serving_summary(summary)
    if serving:
        print("== paged serving (serving.blocks_*) ==", file=out)
        print(f"  block-pool high-water {serving['blocks_high_water']:g} "
              f"(last {serving['blocks_last']:g} — nonzero after a "
              "drained run means leaked blocks)", file=out)
        print(f"  preemptions {serving['preemptions']:g} / "
              f"{serving['requests']:g} requests -> rate "
              f"{serving['preemption_rate']:.3g}", file=out)
        print(f"  prefix-shared high-water "
              f"{serving['prefix_shared_high_water']:g} -> share ratio "
              f"{serving['prefix_share_ratio']:.3g} of pool high-water",
              file=out)
    gauges = summary["gauges"]
    if gauges:
        print("== gauges ==", file=out)
        print(f"{'name':<44} {'count':>7} {'last':>11} {'min':>11} "
              f"{'max':>11}", file=out)
        for name in sorted(gauges):
            vals = gauges[name]
            print(f"{name:<44} {len(vals):>7} {vals[-1]:>11.5g} "
                  f"{min(vals):>11.5g} {max(vals):>11.5g}", file=out)
    events = summary["events"]
    if events:
        print("== events ==", file=out)
        print(f"{'name':<44} {'count':>7}", file=out)
        for name in sorted(events):
            print(f"{name:<44} {events[name]:>7}", file=out)
    if not (spans or counters or gauges or events):
        print("(no telemetry records found)", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize apex_tpu telemetry JSONL files.")
    ap.add_argument("files", nargs="+", help="telemetry .jsonl file(s)")
    ap.add_argument(
        "--since-step", type=int, default=None, metavar="N",
        help="only summarize records stamped with step >= N (records "
             "without a step stamp are kept)")
    args = ap.parse_args(argv)
    records = filter_since_step(load_records(args.files), args.since_step)
    print_report(summarize(records))
    return 0


if __name__ == "__main__":
    sys.exit(main())
