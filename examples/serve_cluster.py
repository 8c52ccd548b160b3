"""Disaggregated prefill/decode serving demo (apex_tpu/serving/cluster).

The two-process topology on one host: a prefill worker and a decode
worker spawn as their OWN OS processes, a router in this process
admits requests by SLO class, dispatches prefill → ships the KV cache
over a localhost socket → injects it into the decode pool, and checks
the result against the single-process engine.  It pins itself and its
workers to the CPU (a chip belongs to one process, and this one computes
the reference itself)::

    python examples/serve_cluster.py --requests 12

What it prints per request: SLO class, router-measured TTFT / e2e, the
KV handoff bytes, and at the end the token-identity verdict vs the
single-engine path (raw wire must match token-for-token — greedy
decode cannot tell it crossed a process boundary) plus the router's
pool stats and autoscale hints.

Knobs worth playing with:

- ``--wire-dtype int8`` — block-scaled handoff compression (~4× fewer
  wire bytes; outputs may diverge from the single-engine path, which
  the demo then reports honestly);
- ``--cache-layout contiguous`` — the decode pool without paging;
- ``--kill-decode`` — terminates the decode worker mid-run to show
  requeue-not-lose (the router re-prefills onto... nothing, in this
  1-worker demo, so it reports the stall via its pool detector — run
  with 2+ decode workers in real deployments).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from apex_tpu.utils.jax_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=9)
    ap.add_argument("--wire-dtype", default="raw",
                    choices=("raw", "bf16", "int8"))
    ap.add_argument("--cache-layout", default="paged",
                    choices=("contiguous", "paged"))
    ap.add_argument("--kill-decode", action="store_true",
                    help="terminate the decode worker mid-run "
                         "(demonstrates the requeue + pool-stall path)")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="stream router cluster.* metrics to this "
                         "JSONL file")
    args = ap.parse_args()

    # three processes on one host, and this one computes the reference:
    # a chip belongs to one process, so all of them are pinned to the
    # CPU before backend init (the workers inherit os.environ)
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    print("== one-host topology demo: every process pinned to the CPU ==")

    if args.telemetry:
        from apex_tpu import observability as obs

        obs.configure(jsonl_path=args.telemetry)

    from apex_tpu.models.config import TransformerConfig
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.cluster import Router
    from apex_tpu.serving.cluster.worker import spawn_worker

    model = dict(layers=2, hidden=64, heads=4, vocab=256, max_pos=128,
                 seed=0)
    cfg = TransformerConfig(
        num_layers=model["layers"], hidden_size=model["hidden"],
        num_attention_heads=model["heads"], vocab_size=model["vocab"],
        max_position_embeddings=model["max_pos"],
        compute_dtype=jnp.float32, remat=False)
    params = init_gpt_params(jax.random.PRNGKey(model["seed"]), cfg)

    rng = np.random.RandomState(0)
    classes = ("interactive", "standard", "batch")
    reqs = [dict(prompt=rng.randint(0, cfg.vocab_size,
                                    (4 + 3 * (i % 5),)).tolist(),
                 max_new_tokens=4 + 2 * (i % 3),
                 slo_class=classes[i % 3])
            for i in range(args.requests)]

    print("== single-engine reference ==", flush=True)
    engine = ServingEngine(params, cfg, max_slots=3, max_len=64,
                           cache_layout=args.cache_layout, block_size=8)
    for kw in reqs:
        engine.submit(**kw)
    ref = {}
    while not engine.idle:
        for r in engine.step():
            ref[r.request_id] = r.tokens.tolist()
    print(f"   {len(ref)} requests served in-process")

    print("== spawning the pools (two more OS processes) ==",
          flush=True)
    flags = []
    for k, v in model.items():
        flags += [f"--{k.replace('_', '-')}", str(v)]
    flags += ["--max-len", "64"]
    procs = []
    try:
        pf_proc, pf_addr, _ = spawn_worker("prefill", extra_args=flags)
        procs.append(pf_proc)
        dc_proc, dc_addr, _ = spawn_worker(
            "decode", extra_args=flags + [
                "--max-slots", "3", "--cache-layout", args.cache_layout,
                "--block-size", "8"])
        procs.append(dc_proc)
        print(f"   prefill pool @ {pf_addr}, decode pool @ {dc_addr}")
        router = Router([pf_addr], [dc_addr],
                        wire_dtype=args.wire_dtype,
                        queue_caps={"batch": 32})
        t0 = time.perf_counter()
        for kw in reqs:
            router.submit(**kw)
        if args.kill_decode:
            # mid-flight kill: dispatched requests requeue, the pool
            # detector latches, nothing is silently lost
            router.step()
            dc_proc.terminate()
            print("   !! decode worker killed mid-run")
            try:
                router.run(max_wall_s=10)
            except RuntimeError as e:
                print(f"   router: {e}")
            st = router.stats()
            print(f"   requeued (not lost): {st['requeued']}, still "
                  f"pending: {st['queued'] + st['inflight']}")
            return
        out = router.run(max_wall_s=300)
        wall = time.perf_counter() - t0
        for r in sorted(out, key=lambda r: r.request_id):
            print(f"   [{r.request_id:>2}] {r.slo_class:<12} "
                  f"ttft {r.ttft_ms:7.1f} ms   e2e {r.e2e_ms:7.1f} ms  "
                  f"handoff {r.handoff_bytes:>7} B   "
                  f"{'SLO met' if r.slo_met else 'SLO MISSED'}")
        same = ([ref[k] for k in sorted(ref)]
                == [r.tokens.tolist()
                    for r in sorted(out, key=lambda r: r.request_id)])
        print(f"== disaggregated: {len(out)} served in {wall:.2f}s, "
              f"token-identical to single engine: {same} "
              f"(wire_dtype={args.wire_dtype}) ==")
        print("   pools:", {p: [w['alive'] for w in v]
                            for p, v in router.stats()["pools"].items()})
        print("   autoscale:", router.autoscale_signal())
        router.close(shutdown_workers=True)
    finally:
        from apex_tpu.serving.cluster.worker import shutdown_worker

        for proc in procs:
            try:
                shutdown_worker(proc)
            except Exception:
                pass
        if args.telemetry:
            from apex_tpu import observability as obs

            obs.shutdown()
            print(f"   telemetry -> {args.telemetry}")


if __name__ == "__main__":
    main()
