"""One-command measurement campaign: every bench/dryrun stage in
order, each its own subprocess with its log under measure_logs/.

    python tools/measure_all.py

This parent process stays off the JAX backend (it only spawns stages
and times them), so the one process that needs the chip at any moment
is the running stage; stages run one after another.  A stage that
needs a TPU fails on its own when there is none (``bench.py`` exits
non-zero without one).  Stages are ordered most-valuable-first so a
campaign cut short loses the least.

Stages: lint and the chip-free dryrun audits; ``bench.py`` (the
workload matrix) and its ``--decode`` ablations (``--cache-layout``,
``--spec``, ``--cache-dtype``, ``--host-tier``, ``--adapters``,
``--decode-fused``); ``tools/exporter_smoke.py``; the CPU-pinned
topology rows (``--serve-trace``, ``--serve-trace --controller``,
``--cold-start``); ``--tp-overlap``, ``--moe``, ``--ckpt`` with their
dryrun parity phases; ``tests/test_on_tpu_kernels.py`` on the chip;
``bench_kernels.py``; and a final
``aggregate_telemetry`` merge into ``measure_logs/fleet_aggregate.json``.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGS = os.path.join(ROOT, "measure_logs")


def _run(name, cmd, env_extra=None, timeout=7200, stall=900):
    """Run a stage, logging to measure_logs/<name>.log.

    Two kill conditions: a hard wall (``timeout``) and a STALL watchdog
    (``stall`` seconds with no new log bytes) — a hung stage must not
    burn the rest of the chip window."""
    os.makedirs(LOGS, exist_ok=True)
    log = os.path.join(LOGS, f"{name}.log")
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", ".")
    # Unbuffered children: the stall watchdog below keys on log-file
    # growth, and a block-buffered healthy stage (python buffers stdout
    # when it's not a tty) can sit on >900s of progress lines and get
    # killed as "stalled" (ADVICE round 5).
    env.setdefault("PYTHONUNBUFFERED", "1")
    # each stage streams its own telemetry (this parent must not touch
    # JAX — configuring telemetry here would — so it records nothing)
    env.setdefault("APEX_TPU_TELEMETRY",
                   os.path.join(LOGS, f"{name}.telemetry.jsonl"))
    if env_extra:
        env.update(env_extra)
    t0 = time.time()
    print(f"[measure_all] {name}: {' '.join(cmd)} (log: {log})",
          flush=True)
    with open(log, "w") as f:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=f,
                                stderr=subprocess.STDOUT)
        last_size, last_change = 0, time.time()
        while True:
            rc = proc.poll()
            if rc is not None:
                break
            now = time.time()
            size = os.path.getsize(log)
            if size != last_size:
                last_size, last_change = size, now
            reason = None
            if now - t0 > timeout:
                reason = f"TIMED OUT after {timeout}s"
            elif now - last_change > stall:
                reason = f"STALLED — no log output for {stall}s"
            if reason:
                proc.kill()
                proc.wait()
                print(f"[measure_all] {name}: {reason}", flush=True)
                return 124
            time.sleep(10)
    dt = time.time() - t0
    status = "ok" if rc == 0 else f"FAILED rc={rc}"
    print(f"[measure_all] {name}: {status} in {dt:.0f}s", flush=True)
    return rc


def main():
    os.makedirs(LOGS, exist_ok=True)
    # Value-first ordering: the headline workload matrix and the
    # Mosaic-validation tier run BEFORE the long kernel ledgers, so a
    # campaign cut short costs the least-valuable stages.
    results = {}
    # static analysis first (ISSUE 12): Tier A is seconds and chip-free,
    # and the Tier-B jaxpr audit is tracing-only — a broken invariant
    # should abort-signal before any chip time is spent.  The audit's
    # census/counted counters land in their own JSONL so the campaign's
    # telemetry_report shows the audit_summary section.
    results["lint"] = _run(
        "lint", [sys.executable, "tools/lint.py"], timeout=600)
    results["dryrun_static_audit"] = _run(
        "dryrun_static_audit",
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env_extra={"APEX_TPU_DRYRUN_PHASE": "static_audit",
                   "APEX_TPU_TELEMETRY": os.path.join(
                       LOGS, "audit_telemetry.jsonl")},
        timeout=1200)
    # Tier C (ISSUE 13): the concurrency/lifecycle lint repo-wide plus
    # the seeded stress smoke (scrape/flush/save/admit churn with
    # exact-count + zero-underflow + clean-shutdown gates).  Chip-free
    # and fast, so it rides the same early abort-signal block; its
    # audit.tierc.* counters append to the same audit stream the
    # telemetry_report tier-C row reads.
    results["dryrun_concurrency_audit"] = _run(
        "dryrun_concurrency_audit",
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env_extra={"APEX_TPU_DRYRUN_PHASE": "concurrency_audit",
                   "APEX_TPU_TELEMETRY": os.path.join(
                       LOGS, "audit_telemetry.jsonl")},
        timeout=900)
    results["bench"] = _run("bench", [sys.executable, "bench.py"],
                            timeout=3600)
    # the inference fast path (prefill/decode split + serving engine):
    # its own stage so the decode rows land in a dedicated JSON line
    # (BENCH-comparable) even if the full matrix above partially
    # failed.  --cache-layout contiguous,paged (ISSUE 6) adds the
    # paged rows and the matched-HBM cache_layout_ablation row
    # (starvation-mix concurrency + preemption counts); every row
    # carries its layout so trajectory comparisons never mix the two
    # 3600s: the two-layout sweep roughly triples the single-layout
    # stage (every row twice + the starvation mixes + the ablation)
    results["bench_decode"] = _run(
        "bench_decode", [sys.executable, "bench.py", "--decode",
                         "--cache-layout", "contiguous,paged"],
        timeout=3600)
    # speculative decoding + fused sampling (ISSUE 8): the --spec
    # ablation stage — off vs n-gram self-drafting over the
    # accept-rate sweep (repetition high-accept / random low-accept),
    # both KV layouts, layout-tagged rows with draft/accepted counters
    # and the stderr accept-rate table in the stage log
    results["bench_spec"] = _run(
        "bench_spec", [sys.executable, "bench.py", "--decode",
                       "--spec", "off,ngram",
                       "--cache-layout", "contiguous,paged"],
        timeout=3600)
    # quantized serving (ISSUE 14): byte-matched bf16-vs-int8 pool
    # admission rows (the >= 1.8x concurrency gate), the spec-decode
    # accept-rate delta gate, and the weight-only quantized matmul
    # byte/rate rows — its own JSON line + stderr gate table
    results["bench_cache_dtype"] = _run(
        "bench_cache_dtype", [sys.executable, "bench.py", "--decode",
                              "--cache-dtype", "bf16,int8"],
        timeout=3600)
    # hierarchical KV cache (ISSUE 18): host-DRAM offload tier off vs
    # on — preemption starvation mix (resume-from-host-tier overhead
    # vs the prefill replay it displaces + greedy token identity) and
    # the shared-system-prompt trace (cold prefixes page back in from
    # host DRAM instead of re-prefilling).  Chip-free numerics: the
    # raw wire is bitwise, so the row gates on identity + overhead
    results["bench_host_tier"] = _run(
        "bench_host_tier", [sys.executable, "bench.py", "--decode",
                            "--host-tier", "off,on"],
        timeout=1800)
    # ...then the kv_tier dryrun phase: page-in resume + chunk-digest
    # page-in token-identical to the solo generate() oracle, and the
    # cross-tier refcount census (zero HBM blocks in use, no
    # per-request host copies, byte ledger exact) at idle
    results["dryrun_kv_tier"] = _run(
        "dryrun_kv_tier",
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env_extra={"APEX_TPU_DRYRUN_PHASE": "kv_tier"}, timeout=1800)
    # multi-tenant LoRA serving (ISSUE 20): heterogeneous-adapter
    # batched decode (ragged grouped matmul over the refcounted slab
    # pool) vs the merged-weights engine at batch parity vs the
    # sequential per-adapter baseline — tokens/s per mode, greedy
    # token identity against the merged reference, and the pool-churn
    # ledger (hits/misses/evictions, zero pinned refs after drain)
    results["bench_adapters"] = _run(
        "bench_adapters", [sys.executable, "bench.py", "--decode",
                           "--adapters", "1,8,64"],
        timeout=1800)
    # ...then the lora_serving dryrun phase: merged-vs-batched token
    # identity on the mixed-adapter batch and the pool ledger census
    # after churn (every slot exactly one of free/pinned/evictable,
    # zero leaked refs)
    results["dryrun_lora_serving"] = _run(
        "dryrun_lora_serving",
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env_extra={"APEX_TPU_DRYRUN_PHASE": "lora_serving"},
        timeout=1800)
    # fused decode-layer megakernel (ISSUE 17): reference composition
    # vs the one-launch fused kernel — per-token ms per route plus the
    # per-layer op/launch structural ledger.  On the chip the ms
    # column is the fusion win; the row carries backend/skipped so a
    # CPU fallback run self-describes as interpreter-timed
    results["bench_decode_fused"] = _run(
        "bench_decode_fused", [sys.executable, "bench.py", "--decode",
                               "--decode-fused", "off,on"],
        timeout=3600)
    # TP comm overlap (ISSUE 5): the ring collective-matmul off/on
    # ablation rows, then the tp_overlap dryrun parity phase alone on
    # the 8-virtual-device mesh (overlapped == monolithic fwd+bwd and
    # the hops == (tp-1) x calls telemetry invariant)
    # live export surface (ISSUE 7): engine up with export_port=0, one
    # /metrics scrape validated by the strict OpenMetrics parser, clean
    # teardown.  Cheap, and it gates the serving SLO telemetry the
    # decode stage's BENCH rows now carry.
    # ISSUE 9: the smoke now also spawns the two-process cluster and
    # scrapes router + both pools
    results["exporter_smoke"] = _run(
        "exporter_smoke", [sys.executable, "tools/exporter_smoke.py"],
        timeout=900)
    # cluster serve-trace (ISSUE 9): the bursty open-loop trace
    # against single-engine vs the two-process prefill/decode
    # topology.  bench pins the whole run (and the spawned workers)
    # to CPU — it measures topology cost under identical numerics,
    # and a second process could not attach to the claimed chip
    # anyway — so this stage is chip-free by construction.
    results["serve_trace"] = _run(
        "serve_trace", [sys.executable, "bench.py", "--serve-trace",
                        "--cache-layout", "paged"],
        timeout=1800)
    # elastic controller + chunked prefill (ISSUE 15): the diurnal +
    # flash-crowd trace, controller on/off x chunked on/off (goodput /
    # p95 TTFT-TPOT / chip-seconds / zero-lost drains) plus the
    # chunked-prefill starvation gate (decode TPOT p95 with one long
    # prompt co-resident <= 2x the no-long-prompt baseline).
    # Chip-free like serve_trace (bench CPU-pins the topology rows).
    results["serve_trace_controller"] = _run(
        "serve_trace_controller",
        [sys.executable, "bench.py", "--serve-trace", "--controller"],
        timeout=2400)
    # persistent compile cache (ISSUE 17): decode-worker READY time
    # with an empty cache dir (cold: trace + AOT-compile the bucket
    # ladder) vs the same dir primed (warm: deserialize) — the
    # worker-internal ready_ms ratio, gate warm <= 0.4x cold.
    # CPU-pinned by bench itself (a spawned worker could not attach
    # the claimed chip), so chip-free like serve_trace.
    results["cold_vs_warm_start"] = _run(
        "cold_vs_warm_start",
        [sys.executable, "bench.py", "--cold-start"], timeout=1800)
    results["bench_tp_overlap"] = _run(
        "bench_tp_overlap",
        [sys.executable, "bench.py", "--tp-overlap"], timeout=1800)
    results["dryrun_tp_overlap"] = _run(
        "dryrun_tp_overlap",
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env_extra={"APEX_TPU_DRYRUN_PHASE": "tp_overlap"}, timeout=1800)
    # MoE expert-parallel fast path (ISSUE 10): the routing x wire x
    # overlap ablation rows (ragged vs capacity vs the dense twin at
    # matched active params), then the moe_ep dryrun parity phase on
    # the 8-virtual-device ep mesh (ragged == capacity fwd+bwd, int8
    # dispatch wire < 0.3x raw, moe.ring hop invariant)
    results["bench_moe"] = _run(
        "bench_moe", [sys.executable, "bench.py", "--moe"],
        timeout=1800)
    results["dryrun_moe_ep"] = _run(
        "dryrun_moe_ep",
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env_extra={"APEX_TPU_DRYRUN_PHASE": "moe_ep"}, timeout=1800)
    # elastic fault-tolerant training (ISSUE 11): the async-checkpoint
    # overhead row (steady-state step time with the sharded saver
    # inside the timed window vs without — the <5% gate) and the
    # ckpt_recovery dryrun phase (bitwise resume through the full DDP
    # int8-EF state, kill -9 a worker subprocess mid-step + restart +
    # bitwise trajectory check, injected NaN -> detector-driven
    # rollback + LR re-warm + flight-recorder incident)
    results["bench_ckpt"] = _run(
        "bench_ckpt", [sys.executable, "bench.py", "--ckpt"],
        timeout=1800)
    results["dryrun_ckpt_recovery"] = _run(
        "dryrun_ckpt_recovery",
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env_extra={"APEX_TPU_DRYRUN_PHASE": "ckpt_recovery"},
        timeout=1800)
    results["tpu_tier"] = _run(
        "tpu_tier", [sys.executable, "-m", "pytest",
                     "tests/test_on_tpu_kernels.py", "-m", "tpu", "-q"],
        env_extra={"APEX_TPU_TEST_ON_TPU": "1"}, timeout=3600)
    results["bench_kernels"] = _run(
        "bench_kernels", [sys.executable, "bench_kernels.py", "--json",
                          "KERNEL_BENCH.json"])

    print("\n[measure_all] stage results:", json.dumps(results))
    # final stage (ISSUE 7): merge the stages' telemetry streams into
    # the fleet summary — the output format is what a multi-host
    # autoscaler consumes
    streams = sorted(glob.glob(os.path.join(LOGS, "*.telemetry.jsonl")))
    if streams:
        agg_json = os.path.join(LOGS, "fleet_aggregate.json")
        results["aggregate_telemetry"] = _run(
            "aggregate_telemetry",
            [sys.executable, os.path.join(ROOT, "tools",
                                          "aggregate_telemetry.py"),
             "--json", agg_json, *streams], timeout=600)
        print(f"[measure_all] fleet aggregate -> {agg_json}")
    return 1 if any(rc != 0 for rc in results.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
