"""Byte-level GPT language modeling on a real text file, end to end.

The flagship-model counterpart of examples/imagenet_rn50.py: train a GPT
on any UTF-8 text file with the round-3 training stack and sample from
it afterwards —

- AMP opt levels via ``make_gpt_train_step`` (O2 default) with the
  chunked fused LM-head+CE (``cfg.fused_head_ce`` — the [tokens, vocab]
  logits never touch HBM);
- byte-level tokens (vocab 256, padded to 384 for tp divisibility), so
  no external tokenizer is needed;
- background-thread prefetch of random crops from the memory-mapped
  corpus;
- fault-tolerant checkpointing (``--ckpt-dir``): async sharded
  snapshots every ``--ckpt-every`` steps through
  ``apex_tpu.checkpoint`` (the write overlaps the next step), bitwise
  resume from the newest committed manifest on restart, and — when
  telemetry is on — detector-driven rollback-to-last-good + LR
  re-warm instead of a dead job on a NaN/loss spike
  (docs/training.md);
- KV-cache generation (models/generate.py) prints a sample at the end;
- optional telemetry (``--telemetry out.jsonl``): per-step spans plus
  loss-scale / loss / grad-norm gauges in the shared JSONL schema —
  summarize with ``python tools/telemetry_report.py out.jsonl``
  (docs/observability.md).

Run:   python examples/gpt_lm.py --data my.txt --steps 200
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import observability as obs
from apex_tpu.amp.scaler import record_scaler_step
from apex_tpu.data import device_prefetch
from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.generate import generate
from apex_tpu.models.gpt import make_gpt_train_step
from apex_tpu.optimizers import fused_adam
from apex_tpu.checkpoint import (
    RecoveryManager, latest_step, restore_sharded, save_sharded)
from apex_tpu.utils.jax_cache import enable_compile_cache

VOCAB = 384          # 256 byte values, padded for tp divisibility


def batches(data: np.ndarray, batch: int, seq: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    n = len(data) - seq - 1
    while True:
        starts = rng.randint(0, n, batch)
        tok = np.stack([data[s:s + seq] for s in starts])
        lab = np.stack([data[s + 1:s + seq + 1] for s in starts])
        yield tok.astype(np.int32), lab.astype(np.int32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", required=True, help="UTF-8 text file")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--opt-level", default="O2")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100,
                    help="async sharded snapshot cadence (steps); with "
                         "--telemetry, a NaN/loss-spike detector firing "
                         "rolls back to the last snapshot + LR re-warm")
    ap.add_argument("--sample-tokens", type=int, default=120)
    ap.add_argument("--top-k", type=int, default=40,
                    help="0 disables the top-k cutoff")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus sampling mass (composes with --top-k)")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write telemetry JSONL here (also enables "
                         "per-step grad-norm metrics)")
    args = ap.parse_args()

    telemetry = args.telemetry is not None
    if telemetry:
        obs.configure(jsonl_path=args.telemetry, stderr_summary=True)

    data = np.frombuffer(open(args.data, "rb").read(), np.uint8)
    if len(data) < args.seq + 2:
        raise ValueError(
            f"{args.data} has {len(data)} bytes; need > seq+1 "
            f"({args.seq + 1}) to cut training windows")
    print(f"corpus: {len(data):,} bytes")

    cfg = TransformerConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads, vocab_size=VOCAB,
        max_position_embeddings=max(args.seq,
                                    args.seq + args.sample_tokens),
        fused_head_ce=True, head_ce_chunk=1024,
        compute_dtype=jnp.bfloat16)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=args.lr),
                                     args.opt_level,
                                     norm_telemetry=telemetry)
    state = init(jax.random.PRNGKey(0))

    start = 0
    mgr = None
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state = restore_sharded(args.ckpt_dir, state)
            start = last
            print(f"resumed from step {start} (bitwise)")
        # async sharded snapshots + (with telemetry) detector-driven
        # rollback-to-last-good instead of a dead job on a NaN
        mgr = RecoveryManager(args.ckpt_dir, save_every=args.ckpt_every)

    stream = device_prefetch(batches(data, args.batch, args.seq, seed=start))
    t0 = time.perf_counter()
    m = None
    for i in range(start, args.steps):
        tok, lab = next(stream)
        with obs.span("train_step"):
            state, m = step(state, tok, lab)
            if telemetry:
                # dispatch is async: fence inside the span so it
                # measures the step, not the microseconds of queueing
                # it.  Only when telemetry is on — the span is a no-op
                # otherwise, and an unconditional fence would serialize
                # host dispatch against the device every step.
                obs.fence(m["loss"])
        if telemetry:
            # host-side at the step boundary: loss-scale gauge +
            # overflow counters + train.* gauges (incl. grad_norm)
            record_scaler_step(m)
            obs.record_step_metrics(m)
        if mgr is not None:
            state, rolled = mgr.after_step(state, m)
            if rolled:
                # APPLY the re-warm, don't just announce it: rebuild
                # the step with the schedule anchored at the restored
                # step (one recompile per incident — which the restore
                # already paid for in spirit); full LR resumes after
                # rewarm_steps optimizer steps
                _, step = make_gpt_train_step(
                    cfg, fused_adam(lr=mgr.rewarm_schedule(args.lr)),
                    args.opt_level, norm_telemetry=telemetry)
                print(f"rollback: resumed from step "
                      f"{mgr.last_rollback_step}; LR re-warm x"
                      f"{mgr.lr_scale():.2f} -> 1.0")
        if (i + 1) % 50 == 0:
            print(f"step {i + 1}: loss {float(m['loss']):.4f}")
    loss = float(m["loss"]) if m is not None else float("nan")
    dt = time.perf_counter() - t0
    if telemetry:
        obs.shutdown()   # flush counters + print the summary table
    tps = (args.steps - start) * args.batch * args.seq / max(dt, 1e-9)
    print(f"final loss {loss:.4f}  ({tps:,.0f} tokens/s)")

    if args.ckpt_dir:
        if mgr is not None:
            mgr.saver.close()   # drain any in-flight async snapshot
        save_sharded(args.ckpt_dir, args.steps, state, keep=3)

    # sample from the trained model (bf16 params from the state)
    prompt_text = bytes(data[: min(32, args.seq)]).decode(
        "utf-8", errors="replace")
    prompt = jnp.asarray(
        np.frombuffer(bytes(data[: min(32, args.seq)]), np.uint8)[None],
        jnp.int32)
    out = generate(state.params, prompt, cfg,
                   max_new_tokens=args.sample_tokens,
                   temperature=args.temperature,
                   top_k=args.top_k or None,
                   top_p=args.top_p, rng=jax.random.PRNGKey(1),
                   vocab_limit=256)
    text = bytes(np.asarray(out[0], np.uint8)).decode(
        "utf-8", errors="replace")
    print("--- sample ---")
    print(text)
    print("--------------")
    assert prompt_text == text[: len(prompt_text)]


if __name__ == "__main__":
    main()
