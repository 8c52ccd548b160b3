"""Exporter smoke: engine up with live export, one scrape, validate,
tear down — then the same for the disaggregated cluster.

    python tools/exporter_smoke.py
    python tools/exporter_smoke.py --skip-cluster   # single-engine only

The ``tools/measure_all.py`` campaign stage for ISSUE 7 (+9): boots a
tiny serving engine with ``observability.configure(export_port=0)``
(an ephemeral localhost port — the stage can never collide with a real
exporter), drives a handful of requests across two SLO classes, then

1. scrapes ``/metrics`` once and validates it with the strict
   OpenMetrics parser (``observability/openmetrics.parse`` — a
   malformed exposition is a hard failure, not a warning);
2. checks the scrape carries the serving SLO families
   (``serving_ttft_ms`` histogram buckets, goodput counters);
3. checks ``/healthz`` answers (any status — health is a latch on
   detector firings, and a smoke run may legitimately trip the
   admission-stall detector while the queue drains);
4. shuts down and verifies the exporter thread actually exited (a
   leaked daemon thread would outlive every later stage).

Cluster half (ISSUE 9): spawns one prefill + one decode worker as
their own processes (each exporting on an ephemeral port), routes a
few requests across them, and scrapes ALL THREE surfaces — the
router's (``cluster_route_total``, queue gauges), the decode pool's
(``serving_kv_injected_total`` proves the handoff landed), and the
prefill pool's — each through the strict parser, plus each
``/healthz``.

Exit 0 = the live export surface works end to end on this box.
"""

from __future__ import annotations

import json
import sys
import threading
import urllib.error
import urllib.request


def _scrape_valid(openmetrics, url: str, want_names=(), label=""):
    """One strict scrape; returns the parsed doc or raises/returns
    None on failure (caller turns that into a stage failure)."""
    text = urllib.request.urlopen(url + "/metrics", timeout=10).read()
    parsed = openmetrics.parse(text.decode("utf-8"))
    if not parsed["eof"]:
        print(f"[exporter_smoke] FAIL: {label} exposition missing "
              "# EOF")
        return None
    names = {n for n, _l, _v in parsed["samples"]}
    for want in want_names:
        if want not in names:
            print(f"[exporter_smoke] FAIL: {want} missing from "
                  f"{label} scrape ({len(names)} sample names)")
            return None
    return parsed


def smoke_cluster() -> int:
    """Router + two worker processes, all three /metrics scraped."""
    import numpy as np

    from apex_tpu import observability as obs
    from apex_tpu.observability import openmetrics
    from apex_tpu.observability.exporter import THREAD_NAME
    from apex_tpu.serving.cluster import Router
    from apex_tpu.serving.cluster.worker import spawn_worker

    reg = obs.configure(export_port=0, tags={"pool": "router"})
    router_url = reg.exporter.url
    flags = ["--vocab", "256", "--max-len", "64", "--export-port", "0"]
    procs = []
    try:
        pf_proc, pf_addr, pf_url = spawn_worker(
            "prefill", extra_args=flags)
        procs.append(pf_proc)
        dc_proc, dc_addr, dc_url = spawn_worker(
            "decode", extra_args=flags + ["--max-slots", "2"])
        procs.append(dc_proc)
        router = Router([pf_addr], [dc_addr])
        rng = np.random.RandomState(0)
        for i in range(4):
            router.submit(rng.randint(0, 256, (6,)),
                          max_new_tokens=4,
                          slo_class="interactive" if i % 2
                          else "standard")
        done = router.run(max_wall_s=120)
        if len(done) != 4:
            print(f"[exporter_smoke] FAIL: cluster completed "
                  f"{len(done)}/4 requests")
            return 1
        scrapes = (
            (router_url, "router", ("cluster_route_total",
                                    "cluster_handoff_bytes_total")),
            (pf_url, "prefill pool", ()),
            (dc_url, "decode pool", ("serving_kv_injected_total",
                                     "serving_requests_total")),
        )
        for url, label, want in scrapes:
            if url is None:
                print(f"[exporter_smoke] FAIL: {label} exported no "
                      "metrics url")
                return 1
            parsed = _scrape_valid(openmetrics, url, want, label)
            if parsed is None:
                return 1
            try:
                urllib.request.urlopen(url + "/healthz", timeout=10)
            except urllib.error.HTTPError:
                pass                      # 503 still answers
            print(f"[exporter_smoke] {label}: "
                  f"{len(parsed['samples'])} samples, healthz up")
        router.close(shutdown_workers=True)
    finally:
        from apex_tpu.serving.cluster.worker import shutdown_worker

        for proc in procs:
            try:
                shutdown_worker(proc)
            except Exception:
                proc.kill()
        obs.shutdown()
    leaked = [t.name for t in threading.enumerate()
              if t.name == THREAD_NAME]
    if leaked:
        print("[exporter_smoke] FAIL: exporter thread survived "
              "cluster shutdown")
        return 1
    print("[exporter_smoke] OK: router + both pools scraped clean")
    return 0


def main() -> int:
    import os

    import jax
    import numpy as np

    # a toy-model smoke of the export surface whose second half spawns
    # worker processes: a chip belongs to one process, so everything is
    # pinned to the CPU before backend init (workers inherit os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    print("[exporter_smoke] every process pinned to the CPU")

    from apex_tpu import observability as obs
    from apex_tpu.models.config import gpt_125m
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.observability import openmetrics
    from apex_tpu.observability.exporter import THREAD_NAME
    from apex_tpu.serving import ServingEngine

    reg = obs.configure(export_port=0)
    url = reg.exporter.url
    print(f"[exporter_smoke] exporter up at {url}")
    cfg = gpt_125m(num_layers=2, hidden_size=64, num_attention_heads=4,
                   vocab_size=256, max_position_embeddings=128)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(params, cfg, max_slots=2, max_len=64)
    rng = np.random.RandomState(0)
    for i in range(4):
        engine.submit(rng.randint(0, 256, (8,)), max_new_tokens=4,
                      slo_class="interactive" if i % 2 else "standard")
    while not engine.idle:
        engine.step()

    text = urllib.request.urlopen(url + "/metrics", timeout=5).read()
    parsed = openmetrics.parse(text.decode("utf-8"))   # raises = fail
    if not parsed["eof"]:
        print("[exporter_smoke] FAIL: exposition missing # EOF")
        return 1
    names = {n for n, _l, _v in parsed["samples"]}
    for want in ("serving_ttft_ms_bucket", "serving_ttft_ms_count",
                 "serving_requests_total", "serving_slot_occupancy"):
        if want not in names:
            print(f"[exporter_smoke] FAIL: {want} missing from scrape "
                  f"({len(names)} sample names)")
            return 1
    goodput = [n for n in names if n.startswith("serving_goodput_")]
    if not goodput:
        print("[exporter_smoke] FAIL: no serving_goodput_* samples")
        return 1
    try:
        health = json.loads(urllib.request.urlopen(
            url + "/healthz", timeout=5).read().decode("utf-8"))
    except urllib.error.HTTPError as e:        # 503 = latched unhealthy;
        health = json.loads(e.read().decode("utf-8"))   # still answers
    print(f"[exporter_smoke] {len(parsed['samples'])} samples, "
          f"types {len(parsed['types'])}, healthz={health.get('status')}")
    obs.shutdown()
    leaked = [t.name for t in threading.enumerate()
              if t.name == THREAD_NAME]
    if leaked:
        print("[exporter_smoke] FAIL: exporter thread survived shutdown")
        return 1
    print("[exporter_smoke] OK: scrape valid, SLO families present, "
          "clean teardown")
    if "--skip-cluster" in sys.argv[1:]:
        return 0
    return smoke_cluster()


if __name__ == "__main__":
    sys.exit(main())
