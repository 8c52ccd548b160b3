"""Continuous-batching GPT serving demo (apex_tpu/serving).

Runs the slot-based ServingEngine over a randomly initialized tiny GPT:
a burst of mixed-length requests (more than the engine has slots) flows
through prefill → batched decode → completion, with new requests
admitted into freed slots mid-flight.  CPU-runnable::

    JAX_PLATFORMS=cpu python examples/serve_gpt.py --requests 12 --slots 4

Pass ``--telemetry out.jsonl`` to stream the serving metrics
(``serving.prefill_ms``, ``serving.decode_tokens_per_sec``,
``serving.slot_occupancy``, ``serving.queue_depth``) through the
observability registry; ``tools/telemetry_report.py`` summarizes them.

With real weights, pair with ``tools/import_hf.py`` exactly like
models/generate.py — the engine consumes the training parameter pytree
unchanged.
"""

import argparse
import time

import jax
import numpy as np

from apex_tpu.models.config import gpt_tiny
from apex_tpu.models.transformer_lm import init_gpt_params
from apex_tpu.serving import ServingEngine
from apex_tpu.utils.jax_cache import enable_compile_cache


def main():
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--max-new", type=int, default=24)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--telemetry", default=None, metavar="PATH",
                   help="stream metrics JSONL to PATH")
    args = p.parse_args()

    if args.telemetry:
        from apex_tpu.observability import configure

        configure(jsonl_path=args.telemetry, stderr_summary=True)

    cfg = gpt_tiny(max_position_embeddings=args.max_len)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(params, cfg, max_slots=args.slots,
                           max_len=args.max_len)

    rng = np.random.RandomState(0)
    reqs = []
    for i in range(args.requests):
        n = int(rng.randint(4, args.max_len - args.max_new))
        reqs.append(dict(
            prompt=rng.randint(0, cfg.vocab_size, (n,)),
            max_new_tokens=args.max_new,
            temperature=args.temperature,
        ))

    t0 = time.perf_counter()
    responses = engine.run(reqs)
    wall = time.perf_counter() - t0

    gen = sum(r.tokens.size for r in responses)
    for r in responses:
        head = " ".join(str(t) for t in r.tokens[:8])
        print(f"request {r.request_id}: prompt={r.prompt.size} tokens, "
              f"generated={r.tokens.size} ({r.finish_reason}), "
              f"prefill={r.prefill_ms:.1f}ms, tokens: {head} ...")
    print(f"\n{len(responses)} requests, {gen} tokens in {wall:.2f}s "
          f"({gen / wall:.1f} tok/s) on {args.slots} slots "
          f"(stats: {engine.stats()})")


if __name__ == "__main__":
    main()
