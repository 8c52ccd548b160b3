"""Quickest proof that apex_tpu still starts on the chip.

One process drives the two main paths through the entry points a user
calls, at the full width of GPT-2 125M (random weights from ``--seed``):

- ``train``: ``make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")`` —
  the trainer call of ``bench.py``'s headline row — 5 steps, batch
  16 x 1024, on one fixed batch;
- ``serve``: ``ServingEngine(..., cache_layout="paged", top_k=40,
  top_p=0.9)`` through ``submit``/``run``: 8 requests over 4 slots, 6
  greedy ones checked token for token against ``models.generate.
  generate()`` and 2 sampled ones through the sampler kernel.

``--chips 4`` runs the four-chip path and its comparison only:
``make_ddp_train_step`` over ``create_mesh(dp=4)`` against the one-chip
``make_gpt_train_step`` on the same batch in the same process.

Without ``--tiny`` the script refuses to start unless JAX reports a
TPU; ``--tiny`` is the CPU rehearsal (tiny sizes, interpret-mode
kernels) and refuses to start when JAX does report one.  Nothing is
caught and carried on: a phase that raises ends the run non-zero.  The
last line of stdout is one JSON object naming the device as JAX reports
it::

    python chip_smoke.py                      # one chip
    python chip_smoke.py --chips 4            # four chips, DDP only
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# bf16 keeps 8 significant bits: one step at magnitude m is m * 2**-7.
# A greedy mismatch against the oracle is excused only when the oracle's
# own top-2 logits at the first differing position sit closer than that
# (a tie two correct programs may break differently).
_BF16_STEP = 2.0 ** -7


class _Size(NamedTuple):
    """Everything that differs between the chip run and the rehearsal."""
    cfg_kw: dict
    batch: int
    seq: int
    max_len: int
    prompt_lo: int
    prompt_hi: int
    new_tokens: int


_FULL = _Size(cfg_kw={}, batch=16, seq=1024, max_len=1024,
              prompt_lo=16, prompt_hi=512, new_tokens=32)
# CPU rehearsal only: a run on the chip rejects --tiny
_TINY = _Size(cfg_kw=dict(num_layers=2, hidden_size=128,
                          num_attention_heads=4, vocab_size=512),
              batch=4, seq=64, max_len=64, prompt_lo=4, prompt_hi=24,
              new_tokens=6)


def _platform() -> str:
    return jax.devices()[0].platform


def _check_platform(tiny: bool) -> None:
    """Refuse to start on the wrong device, before any work."""
    platform = _platform()
    if tiny and platform == "tpu":
        raise SystemExit(
            "chip_smoke: --tiny is the CPU rehearsal; it does not run "
            "on a TPU")
    if not tiny and platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX reports platform {platform!r}, not a TPU "
            "— nothing was run (use --tiny with JAX_PLATFORMS=cpu to "
            "rehearse)")


def _cfg(size: _Size):
    from apex_tpu.models.config import gpt_125m

    return gpt_125m(max_position_embeddings=size.max_len, remat=False,
                    scan_layers=False, fused_head_ce=True, **size.cfg_kw)


def _batch(size: _Size, cfg, seed: int):
    rng = np.random.RandomState(seed)
    shape = (size.batch, size.seq)
    return (jnp.asarray(rng.randint(0, cfg.vocab_size, shape), jnp.int32),
            jnp.asarray(rng.randint(0, cfg.vocab_size, shape), jnp.int32))


def _run_steps(step, state, tokens, labels, n: int):
    """``n`` fenced steps → (state, losses, overflow flags, seconds)."""
    losses, skipped, secs = [], [], []
    for _ in range(n):
        t0 = time.perf_counter()
        state, m = step(state, tokens, labels)
        jax.block_until_ready((state, m))
        secs.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        skipped.append(bool(m["overflow"]))
    return state, losses, skipped, secs


def phase_train(size: _Size, seed: int, tiny: bool) -> None:
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg = _cfg(size)
    tokens, labels = _batch(size, cfg, seed)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = init(jax.random.PRNGKey(seed))

    lowered = step.lower(state, tokens, labels)
    if not tiny:
        n_kernels = lowered.as_text().count("tpu_custom_call")
        print(f"train: lowered step holds {n_kernels} tpu_custom_call")
        if not n_kernels:
            raise RuntimeError(
                "train: no tpu_custom_call in the lowered step — the "
                "Pallas kernels are not on the path")
    t0 = time.perf_counter()
    step = lowered.compile()        # the one compile of this phase
    compile_s = time.perf_counter() - t0

    steps = 5
    state, losses, skipped, step_s = _run_steps(
        step, state, tokens, labels, steps)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(f"train: device_kind={dev.device_kind!r} "
          f"compile_s={compile_s:.1f}")
    print("train: step_s=" + " ".join(f"{s:.4f}" for s in step_s)
          + f" tokens_per_s={size.batch * size.seq / np.median(step_s):.0f}"
          + f" peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print("train: losses=" + " ".join(f"{v:.4f}" for v in losses)
          + f" overflow_skipped={sum(skipped)}/{steps}")
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train: non-finite loss in {losses}")
    if steps - sum(skipped) < 3:
        raise RuntimeError(
            f"train: {sum(skipped)} of {steps} steps were "
            "overflow-skipped; at least 3 must apply")
    if not losses[-1] < losses[0]:
        raise RuntimeError(
            f"train: last loss {losses[-1]} is not below the first "
            f"{losses[0]}")


def _oracle_margin(params, cfg, prefix) -> tuple:
    """Top-2 logits of the oracle's own next-token distribution after
    ``prefix`` (one prefill forward)."""
    from apex_tpu.models.generate import prefill

    logits, _ = prefill(params, jnp.asarray(prefix)[None], cfg)
    top2 = jax.lax.top_k(logits[0].astype(jnp.float32), 2)[0]
    return float(top2[0]), float(top2[1])


def _kernel_parity(cfg, seed: int) -> None:
    """The serving kernels, each against its in-file XLA reference, at
    this model's widths on a small input (bf16 in and out, so 2e-2 — the
    tolerance of the interpret-mode parity tests): the fused decode
    layer, the sampler and the int8 matmul that Mosaic used to refuse,
    and the two the other decode routes run (paged attention for LoRA
    and int8-projection lanes, grouped matmul for adapters and MoE)."""
    from apex_tpu.ops.decode_step import (
        decode_layer_reference, fused_decode_layer)
    from apex_tpu.ops.dense import quantize_weight, quantized_matmul
    from apex_tpu.ops.fused_sampling import filter_logits, fused_sample
    from apex_tpu.ops.grouped_matmul import grouped_matmul
    from apex_tpu.ops.paged_attention import (
        paged_attention_reference, ragged_paged_attention)

    rng = np.random.RandomState(seed)
    cd = cfg.compute_dtype
    h, nh, dh = (cfg.hidden_size, cfg.num_attention_heads,
                 cfg.kv_channels)
    b, bs, mb = 8, 16, 8

    def rand(*shape, scale=1.0, dtype=cd):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    args = (rand(b, nh, dh), rand(b * mb, bs, cfg.kv_groups, dh),
            rand(b * mb, bs, cfg.kv_groups, dh),
            jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb),
            jnp.asarray(rng.randint(1, bs * mb + 1, (b,)), jnp.int32),
            rand(nh * dh, h, scale=0.02, dtype=jnp.float32))
    def close(got, want, what):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=2e-2, rtol=2e-2, err_msg=f"{what} vs its reference")

    close(fused_decode_layer(*args, backend="kernel"),
          decode_layer_reference(*args, attention_backend="reference"),
          "fused decode layer")
    close(ragged_paged_attention(*args[:5], backend="kernel"),
          paged_attention_reference(*args[:5]), "paged attention")

    x, w = rand(b, h), quantize_weight(rand(h, 4 * h, scale=0.02,
                                            dtype=jnp.float32))
    close(quantized_matmul(x, w, backend="kernel"),
          quantized_matmul(x, w, backend="reference"), "quantized matmul")

    rows, groups = 256, 8
    gm = (rand(rows, h), rand(groups, h, 4 * h, scale=0.02),
          jnp.asarray(np.sort(np.concatenate(
              [[0, rows], rng.randint(0, rows + 1, groups - 1)])),
              jnp.int32))
    close(grouped_matmul(*gm, backend="kernel"),
          grouped_matmul(*gm, backend="reference"), "grouped matmul")

    logits = rand(b, cfg.vocab_size, scale=2.0, dtype=jnp.float32)
    temps = jnp.asarray([0.0, 0.8] * (b // 2), jnp.float32)
    kw = dict(top_k=40, top_p=0.9)
    toks = np.asarray(fused_sample(logits, jax.random.PRNGKey(seed),
                                   temperature=temps, backend="kernel",
                                   **kw))
    kept = np.asarray(filter_logits(
        logits / jnp.maximum(temps, 1e-6)[:, None], **kw)) > -1e29
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    for i, t in enumerate(toks):
        ok = t == greedy[i] if float(temps[i]) == 0.0 else kept[i, t]
        if not ok:
            raise RuntimeError(
                f"serve: sampler kernel row {i} (temperature "
                f"{float(temps[i])}) drew token {t} outside the "
                "reference's support")
    print("serve: decode-layer, paged-attention, quantized-matmul, "
          "grouped-matmul and sampler kernels agree with their "
          "references")


def phase_serve(size: _Size, seed: int) -> None:
    from apex_tpu.models.generate import generate
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.ops.decode_step import route_decode_fused
    from apex_tpu.serving import ServingEngine

    cfg = _cfg(size)
    params = init_gpt_params(jax.random.PRNGKey(seed), cfg)
    # one resolver decides all three (ops/_pallas_utils.py)
    print(f"serve: routes decode_fused, sampler, paged_attention = "
          f"{route_decode_fused(None)}")
    _kernel_parity(cfg, seed)

    rng = np.random.RandomState(seed)
    n_req = 8
    lens = np.linspace(size.prompt_lo, size.prompt_hi, n_req).astype(int)
    rng.shuffle(lens)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    # the two sampled requests sit inside the burst, not at its end, so
    # they share decode batches with greedy rows
    sampled_ids = {2, 5}
    temps = [0.8 if i in sampled_ids else 0.0 for i in range(n_req)]

    def requests():
        return [dict(prompt=p, max_new_tokens=size.new_tokens,
                     temperature=t) for p, t in zip(prompts, temps)]

    def engine():
        return ServingEngine(params, cfg, cache_layout="paged",
                             max_slots=4, max_len=size.max_len,
                             top_k=40, top_p=0.9,
                             rng=jax.random.PRNGKey(seed))

    # warm-up engine: every prefill bucket and the decode step compile
    # here; the timed engine below replays the same traffic
    t0 = time.perf_counter()
    engine().run(requests())
    warm_s = time.perf_counter() - t0
    eng = engine()
    t0 = time.perf_counter()
    resps = eng.run(requests())
    run_s = time.perf_counter() - t0
    n_tok = sum(r.tokens.size for r in resps)
    # the rate is over the whole run, the 8 prefills included
    print(f"serve: warmup_and_compile_s={warm_s:.1f} run_s={run_s:.2f} "
          f"generated={n_tok} generated_tokens_per_s={n_tok / run_s:.1f} "
          f"prompt_lens={sorted(int(n) for n in lens)}")
    if len(resps) != n_req or not eng.idle:
        raise RuntimeError(
            f"serve: {len(resps)} of {n_req} requests completed")
    for r in resps:
        if r.tokens.size != size.new_tokens or r.finish_reason != "length":
            raise RuntimeError(
                f"serve: request {r.request_id} ended {r.finish_reason} "
                f"after {r.tokens.size} tokens")

    # the oracle of tests/test_serving.py: ONE ragged generate() call
    greedy_ids = [i for i in range(n_req) if i not in sampled_ids]
    g_lens = [int(lens[i]) for i in greedy_ids]
    batch = np.zeros((len(greedy_ids), max(g_lens)), np.int32)
    for row, i in enumerate(greedy_ids):
        batch[row, : g_lens[row]] = prompts[i]
    t0 = time.perf_counter()
    want = np.asarray(generate(
        params, jnp.asarray(batch), cfg, max_new_tokens=size.new_tokens,
        prompt_lens=jnp.asarray(g_lens), cache_layout="paged"))
    print(f"serve: oracle generate() {time.perf_counter() - t0:.1f}s")
    by_id = {r.request_id: r for r in resps}
    ties = 0
    for row, i in enumerate(greedy_ids):
        n = g_lens[row]
        ref = want[row, n: n + size.new_tokens]
        got = by_id[i].tokens
        diff = np.nonzero(got != ref)[0]
        if not diff.size:
            continue
        at = int(diff[0])
        top1, top2 = _oracle_margin(
            params, cfg, np.concatenate([prompts[i], ref[:at]]))
        margin = top1 - top2
        tol = _BF16_STEP * max(abs(top1), abs(top2))
        print(f"serve: request {i} first differs from the oracle at new "
              f"token {at}: engine {int(got[at])} oracle {int(ref[at])} "
              f"oracle top-2 margin {margin:.6f} (bf16 step {tol:.6f})")
        if margin > tol:
            raise RuntimeError(
                f"serve: request {i} diverges from generate() at new "
                f"token {at} with a top-2 margin of {margin} — not a "
                "bf16 tie")
        ties += 1
    for i in sampled_ids:
        t = by_id[i].tokens
        if t.min() < 0 or t.max() >= cfg.vocab_size:
            raise RuntimeError(
                f"serve: sampled request {i} holds ids outside "
                f"[0, {cfg.vocab_size}): {t}")
    print(f"serve: {len(greedy_ids) - ties} greedy requests identical to "
          f"generate(), {ties} excused at a bf16 tie, "
          f"{len(sampled_ids)} sampled requests in range")


def phase_ddp(size: _Size, seed: int, chips: int) -> None:
    """Data-parallel training over ``chips`` devices against the one-chip
    trainer on the same batch, in this one process."""
    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.models.transformer_lm import gpt_loss, init_gpt_params
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel import create_mesh, make_ddp_train_step

    cfg = _cfg(size)
    tokens, labels = _batch(size, cfg, seed)
    steps = 3

    init1, step1 = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state1 = init1(jax.random.PRNGKey(seed))
    _, want, _, secs1 = _run_steps(step1, state1, tokens, labels, steps)

    mesh = create_mesh(dp=chips, devices=jax.devices()[:chips])
    init, step = make_ddp_train_step(
        lambda p, t, l: gpt_loss(p, t, l, cfg), fused_adam(lr=1e-4),
        "O2", mesh, batch_axes=2)
    state = init(init_gpt_params(jax.random.PRNGKey(seed), cfg))
    shard = NamedSharding(mesh, P("dp"))
    tokens_s, labels_s = (jax.device_put(x, shard)
                          for x in (tokens, labels))
    homes = {s.device for s in tokens_s.addressable_shards}
    if len(homes) != chips:
        raise RuntimeError(
            f"ddp: the batch's shards sit on {len(homes)} devices, "
            f"not {chips}")
    state, got, _, secs = _run_steps(step, state, tokens_s, labels_s,
                                     steps)
    print("ddp: one-chip losses=" + " ".join(f"{v:.4f}" for v in want)
          + f" step_s={secs1[-1]:.4f}")
    print(f"ddp: dp={chips} losses=" + " ".join(f"{v:.4f}" for v in got)
          + f" step_s={secs[-1]:.4f} first_call_s={secs[0]:.1f}")
    rel = [abs(a - b) / abs(b) for a, b in zip(got, want)]
    if not all(np.isfinite(got)) or max(rel) > 2e-2:
        raise RuntimeError(
            f"ddp: losses {got} differ from the one-chip {want} by "
            f"{max(rel):.4f} relative (limit 2e-2)")
    if _platform() == "tpu":
        # the CPU backend reports no memory statistics
        for d in jax.devices()[:chips]:
            used = (d.memory_stats() or {}).get("bytes_in_use", 0)
            print(f"ddp: {d} bytes_in_use={used}")
            if not used > 0:
                raise RuntimeError(
                    f"ddp: {d} holds nothing — the work is not spread "
                    f"over {chips} chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = the data-parallel phase and its one-chip "
                         "comparison only")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at a toy size; rejected on a TPU")
    args = ap.parse_args(argv)

    if args.tiny and args.chips > 1:
        # virtual CPU devices for the rehearsal; read at backend init,
        # which nothing has triggered yet
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()

    _check_platform(args.tiny)
    if len(jax.devices()) < args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} needs {args.chips} "
            f"devices, JAX reports {len(jax.devices())}")

    if not args.tiny:
        # (the CPU rehearsal compiles in seconds and keeps no cache)
        from apex_tpu.utils.jax_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        print("chip_smoke: compile cache at "
              + (cache_dir or os.environ["JAX_COMPILATION_CACHE_DIR"]
                 + " (JAX_COMPILATION_CACHE_DIR)"))
    size = _TINY if args.tiny else _FULL
    if args.chips > 1:
        phase_ddp(size, args.seed, args.chips)
    else:
        phase_train(size, args.seed, args.tiny)
        phase_serve(size, args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
