"""Benchmark suite: the workload matrix on one chip.

Headline (the JSON line's value): GPT-2 125M AMP-O2 fused train step,
tokens/sec/chip, ``vs_baseline`` = speedup over the plain fp32 + unfused
(optax per-tensor Adam) step on the same hardware — the value
proposition apex sells (amp + fused optimizers vs eager fp32,
README.md:3-6; the reference publishes no absolute numbers).

The ``details`` field carries the rest of the matrix, each with its own
unit and (where meaningful) MFU against the chip's bf16 peak:

- ``gpt2_125m``      — tokens/s/chip + MFU (AMP O2, flash attention,
                       FusedAdam)
- ``resnet50``       — imgs/s/chip + MFU (AMP O2 + SyncBN path; DDP
                       degenerates to 1 device here — the multi-chip
                       path is exercised by dryrun_multichip)
- ``bert_large``     — tokens/s/chip + MFU (AMP O2 + FusedLAMB)
- ``rnnt_transducer``— joint+loss train steps/s (contrib transducer)
- ``mlp_fused_adam`` — fused-vs-unfused optimizer step ratio (the
                       FusedAdam north-star: examples/simple analog)
- ``gpt2_125m_decode`` — the inference fast path (batched flash
                       prefill + ragged decode); ``--decode`` runs the
                       inference rows alone plus the continuous-batching
                       serving mixes (``serving_continuous_batching``)

Prints ONE JSON line: {"schema_version", "metric", "value", "unit",
"vs_baseline", "backend", "skipped", "details", "runtime"}.
``backend`` is the platform the rows ran on and ``skipped`` is False or
the reason string.  The benchmark needs a TPU and exits non-zero
without one; ``--cpu-smoke`` runs toy sizes on the CPU to check control
flow (``backend`` then says "cpu", and its rates are not device
numbers).  The topology rows (``--serve-trace``, ``--cold-start``)
spawn worker processes, so they pin the CPU before backend init and say
so (``cpu_pinned``).  All rows are timed through the
shared ``observability.StepTimer`` (docs/observability.md documents the
fencing semantics); set ``APEX_TPU_TELEMETRY=<path>.jsonl`` to stream
per-row span records too, ``APEX_TPU_TELEMETRY_TRACE=<path>.json`` for
a Perfetto timeline of the whole run.  The ``runtime`` block is the
ISSUE 4 accounting (always on): backend-compile count/ms per row label
(an unexpected ``<row>.retrace`` entry means a compile landed inside
the timed window) and HBM bytes-in-use/peak where the platform reports
memory_stats.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.config import bert_large, gpt_125m
from apex_tpu.models.bert import make_bert_train_step
from apex_tpu.models.gpt import make_gpt_train_step
from apex_tpu.observability import (
    SCHEMA_VERSION, StepTimer, configure_from_env,
    install_recompile_tracker, runtime_summary)
from apex_tpu.optimizers import fused_adam, fused_lamb
from apex_tpu.utils.jax_cache import enable_compile_cache


_HEADLINE = "gpt2_125m_amp_o2_fused_train_tokens_per_sec_per_chip"

# bf16 dense MXU peak FLOP/s per chip, keyed by a substring of
# ``device_kind`` (source: Google Cloud TPU documentation, the "TPU v4",
# "TPU v5e", "TPU v5p" and "TPU v6e" system-architecture pages)
_PEAKS = {
    "v4": 275e12,
    "v5 lite": 197e12,       # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,            # v5p reports plain "TPU v5" (after "v5 lite")
    "v6 lite": 918e12,       # trillium
    "v6e": 918e12,
}


def _chip_peak_flops() -> float:
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in _PEAKS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no bf16 peak on record for device_kind {kind!r}; add it to "
        "_PEAKS with its source — an MFU against a guessed peak is "
        "not a measurement")


def _param_count(tree) -> int:
    return sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(tree)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating))


def _time_fn(fn, n_warmup=2, iters=10, name="bench_row"):
    # The shared measurement path (ISSUE 1): observability.StepTimer
    # implements this exact protocol — per-warmup fencing, one trailing
    # fence across the timed iterations, and the scalar-materialization
    # fence — and its rows also land in the telemetry stream as
    # `step.<name>` spans when APEX_TPU_TELEMETRY is set.
    return StepTimer(name, warmup=n_warmup, iters=iters).time(fn)


def bench_gpt(on_tpu, size="125m", query_groups=None, baseline=True):
    """``query_groups`` runs the same geometry with grouped K/V through
    the GQA-aware flash kernels (round 5): vs the MHA row this measures
    the rep-x K/V HBM-traffic reduction plus the smaller qkv projection
    (param counts differ, so compare per-row MFU, not tokens/s).
    ``baseline=False`` skips the fp32+unfused reference half (chip-time
    saver for variant rows)."""
    if query_groups and not on_tpu:
        return {"skipped": "tpu-only row"}
    if on_tpu:
        # an early sweep on a v5e favoured unrolled layers over the
        # scanned stack, b16, and fused_head_ce (chunked head+CE keeps
        # the 3.2 GB logits out of HBM); not measured on today's code
        if size == "350m":
            # ~355M params (GPT-2 medium geometry); remat+scan to fit
            batch, seq, iters = 8, 1024, 10
            cfg = gpt_125m(num_layers=24, hidden_size=1024,
                           num_attention_heads=16,
                           max_position_embeddings=seq, remat=True,
                           scan_layers=True, fused_head_ce=True)
        else:
            batch, seq, iters = 16, 1024, 20
            cfg = gpt_125m(max_position_embeddings=seq, remat=False,
                           scan_layers=False, fused_head_ce=True,
                           num_query_groups=query_groups)
    else:
        if size == "350m":
            # no meaningful CPU smoke distinct from the 125m row
            return {"skipped": "tpu-only row"}
        batch, seq, iters = 2, 128, 2
        cfg = gpt_125m(num_layers=2, hidden_size=256,
                       num_attention_heads=4, vocab_size=8192,
                       max_position_embeddings=seq)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)

    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = init(jax.random.PRNGKey(0))
    n_params = _param_count(state.master_params)

    def one(carry):
        s = carry[0] if carry else state
        s, m = step(s, tokens, labels)
        return s, m["loss"]

    fused_s = _time_fn(one, iters=iters, name="gpt2")
    del state

    base_s = None
    if baseline:
        # baseline: fp32 everywhere, unfused per-tensor Adam (eager analog)
        import optax
        cfg_fp32 = dataclasses.replace(cfg, compute_dtype=jnp.float32)
        init0, step0 = make_gpt_train_step(cfg_fp32, optax.adam(1e-4), "O0")
        state0 = init0(jax.random.PRNGKey(0))

        def one0(carry):
            s = carry[0] if carry else state0
            s, m = step0(s, tokens, labels)
            return s, m["loss"]

        base_s = _time_fn(one0, iters=max(2, iters // 2),
                          name="gpt2_fp32_unfused")
        del state0

    tokens_per_s = batch * seq / fused_s
    # train FLOPs/token: 6N matmul + 12·L·d_model·s attention (fwd+bwd)
    flops_per_tok = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = tokens_per_s * flops_per_tok / _chip_peak_flops()
    out = {
        "tokens_per_sec_per_chip": round(tokens_per_s, 1),
        "step_ms": round(fused_s * 1e3, 2),
        "mfu": round(mfu, 4),
        "params": n_params,
        "batch": batch, "seq": seq,
    }
    if base_s is not None:
        out["speedup_vs_fp32_unfused"] = round(base_s / fused_s, 3)
    if query_groups:
        out["query_groups"] = query_groups
    return out


def bench_gpt_longctx(on_tpu):
    """GPT-2 125M geometry at s8192 — the long-context single-chip
    row.  Flash attention keeps memory O(s·d) and remat+scan
    keep the activations inside HBM; MFU accounting includes the
    attention term, which at s8192 is no longer negligible."""
    if not on_tpu:
        return {"skipped": "tpu-only row"}
    batch, seq, iters = 2, 8192, 6
    cfg = gpt_125m(max_position_embeddings=seq, remat=True,
                   scan_layers=True, fused_head_ce=True)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = init(jax.random.PRNGKey(0))
    n_params = _param_count(state.master_params)

    def one(carry):
        s = carry[0] if carry else state
        s, m = step(s, tokens, labels)
        return s, m["loss"]

    sec = _time_fn(one, iters=iters, name="gpt2_longctx")
    tokens_per_s = batch * seq / sec
    flops_per_tok = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = tokens_per_s * flops_per_tok / _chip_peak_flops()
    return {
        "tokens_per_sec_per_chip": round(tokens_per_s, 1),
        "step_ms": round(sec * 1e3, 2),
        "mfu": round(mfu, 4),
        "params": n_params,
        "batch": batch, "seq": seq,
    }


def bench_longctx_cp_compare(on_tpu, batch=2, seq=8192, iters=4):
    """Ring vs Ulysses at matched geometry — the measured form of the
    trade-off documented in parallel/ulysses.py:14-20 (ring: per-step
    ppermutes, O(s_local·n·d) memory; Ulysses: two large all-to-alls,
    O(s_global·n/sp·d)).  Context parallelism needs a real sp axis, so
    this row runs only when ≥2 same-platform devices are attached (a
    pod slice); on the single-chip bench it reports skipped rather than
    a degenerate sp=1 non-measurement."""
    n_dev = len(jax.devices())
    if not on_tpu:
        return {"skipped": "tpu-only row"}
    if n_dev < 2:
        return {"skipped": f"needs >=2 devices for a cp axis (have "
                           f"{n_dev}); runs on first pod contact"}
    from apex_tpu.parallel.mesh import create_mesh

    cfg = gpt_125m(max_position_embeddings=seq, remat=True,
                   scan_layers=True, fused_head_ce=True)
    # sp must divide the head count (Ulysses re-shards heads across sp;
    # 12 heads → sp ≤ 4) and fit the device count as a power of two —
    # the mesh is built over exactly sp devices so non-power-of-two
    # slices still measure on their largest usable subset
    head_pow2 = cfg.num_attention_heads & -cfg.num_attention_heads
    sp = min(1 << (n_dev.bit_length() - 1), head_pow2)
    if sp < 2:
        return {"skipped": f"no usable sp axis (devices={n_dev}, "
                           f"heads={cfg.num_attention_heads})"}
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    mesh = create_mesh(sp=sp, devices=jax.devices()[:sp])
    out = {"sp": sp, "batch": batch, "seq": seq}
    for mode in ("ring", "ulysses"):
        try:
            init, step = make_gpt_train_step(
                cfg, fused_adam(lr=1e-4), "O2", mesh, seq_axis="sp",
                context_parallel=mode)
            state = init(jax.random.PRNGKey(0))

            def one(carry):
                s = carry[0] if carry else state
                s, m = step(s, tokens, labels)
                return s, m["loss"]

            sec = _time_fn(one, iters=iters, name=f"cp_{mode}")
            out[mode] = {
                "step_ms": round(sec * 1e3, 2),
                "tokens_per_sec": round(batch * seq / sec, 1),
            }
        except Exception as e:   # e.g. head count not divisible by sp
            out[mode] = {"error": f"{type(e).__name__}: {e}"[:160]}
    if "step_ms" in out.get("ring", {}) and "step_ms" in out.get(
            "ulysses", {}):
        out["ring_over_ulysses"] = round(
            out["ring"]["step_ms"] / out["ulysses"]["step_ms"], 3)
    return out


def bench_decode(on_tpu, query_groups=None, cache_layout="contiguous"):
    """Autoregressive inference throughput (beyond-reference row: apex
    ships no generation path; ours is models/generate.py).

    Since the prefill/decode split (ISSUE 3) the prompt costs ONE
    batched flash forward instead of ``prompt`` sequential decode
    steps, so the row reports the two phases separately: the prefill
    forward (prompt tokens/s) and the per-token decode loop (new
    tokens/s, prefill time subtracted).  ``query_groups`` enables the
    GQA variant — the cache shrinks by heads/groups, the decode
    bandwidth story GQA exists for.  ``cache_layout`` (ISSUE 6) runs
    the same geometry over the contiguous stripe cache or the paged
    block pool + ragged-paged-attention kernel; every row carries the
    layout so BENCH trajectory comparisons never mix the two."""
    from apex_tpu.models.generate import (
        generate, init_kv_cache, prefill)
    from apex_tpu.models.transformer_lm import init_gpt_params

    if on_tpu:
        batch, prompt, new = 8, 32, 128
        cfg = gpt_125m(max_position_embeddings=prompt + new,
                       num_query_groups=query_groups)
    else:
        batch, prompt, new = 2, 8, 8
        # the smoke config has 4 heads: clamp groups so the GQA code
        # path (kv_groups != heads) actually runs off-TPU too
        smoke_groups = 2 if query_groups else None
        cfg = gpt_125m(num_layers=2, hidden_size=128,
                       num_attention_heads=4, vocab_size=1024,
                       max_position_embeddings=prompt + new,
                       num_query_groups=smoke_groups)
    rng = np.random.RandomState(0)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prompt)),
                         jnp.int32)

    def run_prefill(_):
        # the cache alloc rides inside the timed body in BOTH layouts
        # (contiguous allocates inside prefill when cache=None)
        cache = init_kv_cache(cfg, batch, prompt + new,
                              cache_layout=cache_layout)
        lg, _cache = prefill(params, tokens, cfg, cache=cache)
        return (lg, lg)

    pf_sec = _time_fn(run_prefill, n_warmup=1,
                      iters=5 if on_tpu else 2, name="prefill")

    def run(_):
        out = generate(params, tokens, cfg, max_new_tokens=new,
                       cache_layout=cache_layout)
        return (out, out)

    sec = _time_fn(run, n_warmup=1, iters=5 if on_tpu else 2,
                   name="decode")
    decode_sec = sec - pf_sec
    noisy = decode_sec <= 0
    if noisy:
        # separately-timed prefill exceeded the e2e run (CPU-smoke
        # noise at tiny shapes): fall back to the honest e2e
        # denominator instead of printing a fantasy rate
        decode_sec = sec
    out = {
        "decode_tokens_per_sec": round(batch * new / decode_sec, 1),
        "ms_per_token": round(decode_sec / new * 1e3, 3),
        "prefill_ms": round(pf_sec * 1e3, 3),
        "prefill_tokens_per_sec": round(batch * prompt / pf_sec, 1),
        "e2e_ms": round(sec * 1e3, 2),
        "batch": batch, "prompt": prompt, "new_tokens": new,
        "cache_layout": cache_layout,
    }
    if noisy:
        out["noisy_prefill_timing"] = True
    if query_groups is not None:
        out["num_query_groups"] = cfg.kv_groups
    return out


def _count_eqns(jaxpr, prim=None):
    """Recursive jaxpr equation census: total ops when ``prim`` is
    None, else occurrences of that primitive — the structural
    launch/glue ledger of the decode-fused ablation.  Recursion stops
    at ``pallas_call`` boundaries: a kernel BODY is one launch, not
    glue the XLA scheduler sees."""
    n = 0
    for eqn in jaxpr.eqns:
        if prim is None or eqn.primitive.name == prim:
            n += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_eqns(sub, prim)
    return n


def bench_decode_fused(on_tpu, modes=("off", "on")):
    """ISSUE 17 tentpole ablation: the decode layer as three separate
    stages + XLA glue (reference: rope, ragged paged attention, output
    projection — each round-tripping activations through HBM) vs ONE
    fused Pallas launch with one VMEM residency
    (``ops/decode_step.py``; each mode pins ``decode_step``'s
    ``decode_fused=``).

    Two measurements per mode: the greedy decode per-token ms (one
    jitted ``decode_step`` a token after one prefill, as the serving
    engine dispatches them), and the STRUCTURAL per-layer ledger from
    the traced jaxprs — total equations (the glue XLA must schedule
    around) and ``pallas_call`` launch sites.  Off-TPU the kernel runs
    under the Pallas interpreter, so the wall-clock column measures
    interpreter overhead, not fusion wins — the honest CPU signal is
    the op/launch delta; the ms column becomes meaningful on the chip
    (``tools/measure_all.py bench_decode_fused`` runs it there)."""
    from apex_tpu.models.generate import (
        decode_step, init_kv_cache, prefill)
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.ops.decode_step import (
        decode_layer_reference, fused_decode_layer)

    if on_tpu:
        batch, prompt, new = 8, 32, 128
        cfg = gpt_125m(max_position_embeddings=prompt + new,
                       position_embedding_type="rope",
                       num_query_groups=4)
    else:
        batch, prompt, new = 2, 8, 8
        cfg = gpt_125m(num_layers=2, hidden_size=128,
                       num_attention_heads=4, vocab_size=1024,
                       max_position_embeddings=prompt + new,
                       position_embedding_type="rope",
                       num_query_groups=2)
    rng = np.random.RandomState(0)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prompt)),
                         jnp.int32)

    def prefilled():
        cache = init_kv_cache(cfg, batch, prompt + new,
                              cache_layout="paged")
        return prefill(params, tokens, cfg, cache=cache)

    # prefill is route-independent: time it once, subtract per mode
    def run_prefill(_):
        lg, _cache = prefilled()
        return (lg, lg)

    pf_sec = _time_fn(run_prefill, n_warmup=1,
                      iters=5 if on_tpu else 2, name="prefill")
    out = {
        "cache_layout": "paged", "batch": batch, "prompt": prompt,
        "new_tokens": new, "num_query_groups": cfg.kv_groups,
        "prefill_ms": round(pf_sec * 1e3, 3),
        # honesty flag: off-TPU the kernel route runs under the
        # Pallas interpreter — ms columns are interpreter overhead
        "interpret_kernel": not on_tpu,
    }
    for mode in modes:
        route = "kernel" if mode == "on" else "reference"
        step = jax.jit(functools.partial(decode_step, cfg=cfg,
                                         decode_fused=route))

        def run(_):
            logits, cache = prefilled()
            for _i in range(new):
                logits, cache = step(
                    params, jnp.argmax(logits, -1).astype(jnp.int32),
                    cache)
            return (logits, logits)

        sec = _time_fn(run, n_warmup=1, iters=5 if on_tpu else 2,
                       name=f"decode_fused_{mode}")
        decode_sec = sec - pf_sec
        noisy = decode_sec <= 0
        if noisy:
            decode_sec = sec
        row = {
            "route": route,
            "decode_tokens_per_sec": round(batch * new / decode_sec, 1),
            "ms_per_token": round(decode_sec / new * 1e3, 3),
            "e2e_ms": round(sec * 1e3, 2),
        }
        if noisy:
            row["noisy_prefill_timing"] = True
        out[f"fused_{mode}"] = row
    if "off" in modes and "on" in modes:
        out["ms_per_token_delta"] = round(
            out["fused_off"]["ms_per_token"]
            - out["fused_on"]["ms_per_token"], 3)
        out["speedup_x"] = round(
            out["fused_off"]["ms_per_token"]
            / max(out["fused_on"]["ms_per_token"], 1e-9), 3)

    # the structural ledger: one decode layer at serving-ish shapes,
    # traced (not run) — deterministic on every backend
    b, nh, g, dh, bs, nb, mb = 2, 4, 2, 64, 8, 4, 2
    lrng = np.random.RandomState(1)
    q = jnp.asarray(lrng.randn(b, nh, dh), jnp.float32)
    kp = jnp.asarray(lrng.randn(nb, bs, g, dh), jnp.float32)
    vp = jnp.asarray(lrng.randn(nb, bs, g, dh), jnp.float32)
    tbl = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lens = jnp.asarray([9, 13], jnp.int32)
    w = jnp.asarray(lrng.randn(nh * dh, 128), jnp.float32)
    theta = lrng.uniform(-np.pi, np.pi, (b, dh))
    cos = jnp.asarray(np.cos(theta), jnp.float32)
    sin = jnp.asarray(np.sin(theta), jnp.float32)

    def ref_layer(q, kp, vp, tbl, lens, w, cos, sin):
        return decode_layer_reference(q, kp, vp, tbl, lens, w,
                                      rope_cos=cos, rope_sin=sin)

    def fused_layer(q, kp, vp, tbl, lens, w, cos, sin):
        return fused_decode_layer(q, kp, vp, tbl, lens, w,
                                  rope_cos=cos, rope_sin=sin,
                                  backend="kernel")

    ledger = {}
    for name, fn in (("reference", ref_layer), ("fused", fused_layer)):
        jx = jax.make_jaxpr(fn)(q, kp, vp, tbl, lens, w, cos, sin)
        ledger[name] = {
            "eqns": _count_eqns(jx.jaxpr),
            "kernel_launches": _count_eqns(jx.jaxpr, "pallas_call"),
        }
    ledger["eqns_saved"] = (ledger["reference"]["eqns"]
                            - ledger["fused"]["eqns"])
    out["layer_ops"] = ledger
    return out


def _serving_mixes(on_tpu):
    """The shared request mixes: the two ends of production traffic
    plus the long-prompt-starvation mix of ISSUE 6 — a few near-max_len
    prompts pinning lanes for many steps amid a stream of short
    requests.  Under slot admission each long request reserves a whole
    max_len stripe, so concurrency (and slot occupancy) collapses to
    the slot count; the mix is what the paged ablation row measures."""
    if on_tpu:
        return 8, gpt_125m(max_position_embeddings=1024), {
            "prefill_heavy": dict(n=16, prompt=512, new=16,
                                  slo_class="standard"),
            "decode_heavy": dict(n=16, prompt=32, new=128,
                                 slo_class="interactive"),
            "long_prompt_starvation": dict(
                n=16, prompt=32, new=32, n_long=2, long_prompt=768,
                long_new=64, slo_class="interactive"),
        }
    return 4, gpt_125m(num_layers=2, hidden_size=128,
                       num_attention_heads=4, vocab_size=1024,
                       max_position_embeddings=256), {
        "prefill_heavy": dict(n=4, prompt=48, new=4,
                              slo_class="standard"),
        "decode_heavy": dict(n=4, prompt=8, new=24,
                             slo_class="interactive"),
        "long_prompt_starvation": dict(
            n=6, prompt=8, new=8, n_long=1, long_prompt=96, long_new=16,
            slo_class="interactive"),
    }


def _mix_requests(rng, vocab, m):
    """Materialize one mix: ``n_long`` long requests submitted FIRST
    (they pin lanes while the short stream queues behind them).  SLO
    classes (ISSUE 7): long requests are ``batch`` (no deadline — they
    meet their SLO by completing), short ones take the mix's class
    (default ``standard``), so the per-class goodput split in the
    BENCH row reflects the traffic shape."""
    reqs = [dict(prompt=rng.randint(0, vocab, (m["long_prompt"],)),
                 max_new_tokens=m["long_new"], slo_class="batch")
            for _ in range(m.get("n_long", 0))]
    reqs += [dict(prompt=rng.randint(0, vocab, (m["prompt"],)),
                  max_new_tokens=m["new"],
                  slo_class=m.get("slo_class", "standard"))
             for _ in range(m["n"])]
    return reqs


def _pct_of(vals, q):
    vals = sorted(vals)
    if not vals:
        return 0.0
    idx = min(len(vals) - 1, max(0, round(q * (len(vals) - 1))))
    return vals[idx]


def _slo_fields(resps):
    """Per-class TTFT/TPOT/goodput summary from the responses' own SLO
    accounting (ISSUE 7) — the baseline BENCH format the first
    ``--serve-trace`` bench (ROADMAP item 4) extends.  Exact
    percentiles over the mix's requests (this is per-run bench data,
    not the fleet sketch path)."""
    out = {}
    by_cls = {}
    for r in resps:
        by_cls.setdefault(r.slo_class, []).append(r)
    for cls, rs in sorted(by_cls.items()):
        tpots = [r.tpot_ms for r in rs if r.tokens.size > 1]
        met = sum(1 for r in rs if r.slo_met)
        out[cls] = {
            "requests": len(rs),
            "ttft_ms_p50": round(_pct_of([r.ttft_ms for r in rs], .5), 3),
            "ttft_ms_p95": round(_pct_of([r.ttft_ms for r in rs], .95), 3),
            "tpot_ms_p50": round(_pct_of(tpots, .5), 4),
            "tpot_ms_p95": round(_pct_of(tpots, .95), 4),
            "e2e_ms_p50": round(_pct_of([r.e2e_ms for r in rs], .5), 3),
            "e2e_ms_p95": round(_pct_of([r.e2e_ms for r in rs], .95), 3),
            "queue_wait_ms_p95": round(
                _pct_of([r.queue_wait_ms for r in rs], .95), 3),
            "goodput_rate": round(met / len(rs), 4),
        }
    return out


def _drive_engine(engine, reqs):
    """Submit + step to drain, tracking the concurrency high-water mark
    (``run()`` hides it); returns (responses, wall_s, max_concurrent)."""
    import time as _time

    for kw in reqs:
        engine.submit(**kw)
    resps, hw = [], 0
    t0 = _time.perf_counter()
    while not engine.idle:
        resps.extend(engine.step())
        hw = max(hw, engine.stats()["active"])
    wall = _time.perf_counter() - t0          # step() syncs every token
    return resps, wall, hw


def bench_serving(on_tpu, cache_layout="contiguous"):
    """Continuous-batching serving engine (apex_tpu/serving) under a
    prefill-heavy mix, a decode-heavy mix, and the long-prompt
    starvation mix (ISSUE 6) — each driving more requests than lanes so
    admission-into-freed-lanes is on the measured path; the reported
    tokens/s is end-to-end (prefills + decode steps + the per-step host
    sync a real serving loop pays).  ``cache_layout`` picks the KV
    storage; the row carries it so trajectories never mix layouts."""
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine

    slots, cfg, mixes = _serving_mixes(on_tpu)
    rng = np.random.RandomState(0)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rows = {"max_slots": slots, "cache_layout": cache_layout}
    for name, m in mixes.items():
        longest = max(m["prompt"] + m["new"],
                      m.get("long_prompt", 0) + m.get("long_new", 0))
        engine_kw = dict(max_slots=slots,
                         max_len=min(cfg.max_position_embeddings,
                                     2 * longest),
                         cache_layout=cache_layout)
        reqs = _mix_requests(rng, cfg.vocab_size, m)
        ServingEngine(params, cfg, **engine_kw).run(reqs)  # warmup
        engine = ServingEngine(params, cfg, **engine_kw)
        resps, wall, hw = _drive_engine(engine, reqs)
        gen_tokens = sum(r.tokens.size for r in resps)
        rows[name] = {
            "requests": len(reqs), "prompt": m["prompt"],
            "new_tokens": m["new"],
            "wall_ms": round(wall * 1e3, 2),
            "gen_tokens_per_sec": round(gen_tokens / wall, 1),
            "prefill_ms_mean": round(
                sum(r.prefill_ms for r in resps) / len(resps), 3),
            "max_concurrent_requests": hw,
            # ISSUE 7: per-class TTFT/TPOT/goodput from the responses'
            # SLO accounting — the --serve-trace baseline format
            "slo": _slo_fields(resps),
        }
        if m.get("n_long"):
            rows[name]["long_requests"] = m["n_long"]
            rows[name]["long_prompt"] = m["long_prompt"]
        if cache_layout == "paged":
            rows[name]["preemptions"] = engine.stats()["preemptions"]
    return rows


def bench_cache_layout_ablation(on_tpu, layouts):
    """The ISSUE 6 headline ablation: both layouts under the
    long-prompt starvation mix at MATCHED KV bytes.  The contiguous
    engine gets S slots × max_len stripes; the paged engine gets the
    SAME pool bytes (num_blocks = S·max_len/block_size) but 4× the
    lanes — slot admission reserves worst-case HBM per request, block
    admission reserves only touched blocks, so the paged row should
    carry more concurrent requests (``max_concurrent_requests``) and
    pay for overcommit with counted ``preemptions`` rather than
    queue stalls."""
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine

    slots, cfg, mixes = _serving_mixes(on_tpu)
    m = mixes["long_prompt_starvation"]
    max_len = min(cfg.max_position_embeddings,
                  2 * (m["long_prompt"] + m["long_new"]))
    block_size = 16
    pool_blocks = slots * (max_len // block_size)   # slot-layout bytes
    rng = np.random.RandomState(1)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rows = {"mix": "long_prompt_starvation", "max_len": max_len,
            "pool_tokens": pool_blocks * block_size}
    for layout in layouts:
        engine_kw = dict(max_slots=slots, max_len=max_len)
        if layout == "paged":
            engine_kw.update(cache_layout="paged", block_size=block_size,
                             num_blocks=pool_blocks, max_slots=4 * slots)
        reqs = _mix_requests(rng, cfg.vocab_size, m)
        ServingEngine(params, cfg, **engine_kw).run(reqs)  # warmup
        engine = ServingEngine(params, cfg, **engine_kw)
        resps, wall, hw = _drive_engine(engine, reqs)
        gen_tokens = sum(r.tokens.size for r in resps)
        row = {
            "cache_layout": layout,
            "decode_tokens_per_sec": round(gen_tokens / wall, 1),
            "max_concurrent_requests": hw,
            "requests": len(reqs),
            "wall_ms": round(wall * 1e3, 2),
            "kv_bytes": int((engine.cache["k"].size
                             + engine.cache["v"].size)
                            * engine.cache["k"].dtype.itemsize),
        }
        if layout == "paged":
            st = engine.stats()
            row["preemptions"] = st["preemptions"]
            row["num_blocks"] = st["num_blocks"]
        rows[layout] = row
    if "contiguous" in rows and "paged" in rows:
        rows["paged_over_contiguous_concurrency"] = round(
            rows["paged"]["max_concurrent_requests"]
            / max(rows["contiguous"]["max_concurrent_requests"], 1), 2)
    return rows


def bench_cache_dtype_ablation(on_tpu, wires, platform="cpu"):
    """Quantized-serving ablation (ISSUE 14): the paged pool at rest in
    bf16 vs block-scaled int8, at MATCHED pool bytes.

    Three row families, every one carrying the PR-11 ``backend`` /
    ``skipped`` fields so a CPU-smoke run is machine-readably caveated:

    - **admission rows** — the long-prompt starvation mix against
      byte-matched pools: int8 blocks cost ``(1 + 4/dh)/itemsize`` of
      native blocks, so the same HBM holds ~1.88x the blocks under a
      bf16 baseline and the realized ``max_concurrent_requests``
      multiple (plus preemption counts) is the headline —
      ``admitted_concurrency_multiple`` with the >= 1.8 acceptance
      gate;
    - **spec-decode accept-rate gate** — the PR-8 n-gram sweep over
      both pool forms; the accept-rate delta is the cheap proxy for
      distribution drift of int8-at-rest (``accept_gate_ok`` asserts
      it bounded) and ``greedy_divergence_rate`` reports how many
      token positions actually moved (documented, not hidden — the
      first token never diverges, prefill logits precede any
      quantization);
    - **weight-only matmul rows** — ``generate`` decode rate with
      float params vs ``models/quantized.quantize_params`` (int8
      weight slabs, in-kernel dequant) plus the resident
      ``param_bytes`` ratio.  On CPU the rate is NOT the story (the
      win is HBM bandwidth); the byte ratio is.
    """
    from apex_tpu.models.generate import generate
    from apex_tpu.models.quantized import param_bytes, quantize_params
    from apex_tpu.models.speculative import SpecConfig, spec_generate
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine

    bad = [w for w in wires if w not in ("bf16", "int8")]
    if bad:
        raise ValueError(f"cache dtypes {bad}: expected bf16, int8")
    # dh = 64 geometry (hidden/heads): the per-(token, group) scale
    # rides one fp32 per dh lane, so dh sets the int8 byte ratio —
    # 1 + 4/64 = 1.0625 B/elem vs bf16's 2 (the 1.88x block multiple)
    if on_tpu:
        cfg = gpt_125m(max_position_embeddings=1024)
        slots, bs, max_len = 48, 16, 512
        n_short, short_prompt, short_new = 48, 62, 4
        n_long, long_prompt, long_new = 2, 384, 8
        base_blocks = 112
        spec_prompt, spec_new = 64, 96
    else:
        cfg = gpt_125m(num_layers=2, hidden_size=128,
                       num_attention_heads=2, vocab_size=1024,
                       max_position_embeddings=256)
        slots, bs, max_len = 24, 16, 128
        n_short, short_prompt, short_new = 20, 30, 4
        n_long, long_prompt, long_new = 1, 96, 8
        base_blocks = 24
        spec_prompt, spec_new = 16, 48
    rng = np.random.RandomState(0)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    g, dh = cfg.kv_groups, cfg.kv_channels
    bf16_block_bytes = bs * g * dh * 2
    int8_block_bytes = bs * g * (dh + 4)
    reqs = [dict(prompt=rng.randint(0, cfg.vocab_size, (long_prompt,)),
                 max_new_tokens=long_new, slo_class="batch")
            for _ in range(n_long)]
    reqs += [dict(prompt=rng.randint(0, cfg.vocab_size, (short_prompt,)),
                  max_new_tokens=short_new)
             for _ in range(n_short)]

    def engine_for(wire):
        kw = dict(max_slots=slots, max_len=max_len, cache_layout="paged",
                  block_size=bs, cache_dtype=jnp.bfloat16,
                  reserve_blocks=1)
        if wire == "int8":
            kw.update(cache_wire="int8",
                      num_blocks=base_blocks * bf16_block_bytes
                      // int8_block_bytes)
        else:
            kw.update(num_blocks=base_blocks)
        return ServingEngine(params, cfg, **kw)

    rows = {"mix": "long_prompt_starvation", "block_size": bs,
            "max_len": max_len, "requests": len(reqs),
            "backend": platform, "skipped": False}
    for wire in wires:
        engine_for(wire).run(list(reqs))              # warmup compiles
        engine = engine_for(wire)
        resps, wall, hw = _drive_engine(engine, list(reqs))
        st = engine.stats()
        gen_tokens = sum(r.tokens.size for r in resps)
        rows[wire] = {
            "cache_wire": wire,
            "num_blocks": st["num_blocks"],
            "cache_bytes": st["cache_bytes"],
            "max_concurrent_requests": hw,
            "preemptions": st["preemptions"],
            "completed": len(resps),
            "wall_ms": round(wall * 1e3, 2),
            "gen_tokens_per_sec": round(gen_tokens / wall, 1),
            "backend": platform,
            "skipped": False,
        }
    if "bf16" in rows and "int8" in rows:
        rows["admitted_concurrency_multiple"] = round(
            rows["int8"]["max_concurrent_requests"]
            / max(rows["bf16"]["max_concurrent_requests"], 1), 2)
        rows["pool_bytes_ratio"] = round(
            rows["int8"]["cache_bytes"] / rows["bf16"]["cache_bytes"], 3)

    # -- spec-decode accept-rate gate (the quality proxy) -------------------
    pattern = rng.randint(0, cfg.vocab_size, (4,))
    rep_prompt = jnp.asarray(
        np.tile(pattern, (2, -(-spec_prompt // 4)))[:, :spec_prompt],
        jnp.int32)
    spec_rows = {"backend": platform, "skipped": False}
    outs = {}
    for wire in wires:
        cw = "int8" if wire == "int8" else None
        out, stats = spec_generate(
            params, rep_prompt, cfg, spec=SpecConfig(k=8),
            max_new_tokens=spec_new, cache_layout="paged",
            block_size=bs, cache_dtype=jnp.bfloat16, cache_wire=cw)
        outs[wire] = np.asarray(out)[:, spec_prompt:]
        draft = max(stats["draft_tokens"], 1)
        spec_rows[wire] = {
            "accept_rate": round(stats["accepted_tokens"] / draft, 4),
            "draft_tokens": stats["draft_tokens"],
            "accepted_tokens": stats["accepted_tokens"],
            "verify_calls": stats["verify_calls"],
        }
    if "bf16" in spec_rows and "int8" in spec_rows:
        delta = abs(spec_rows["bf16"]["accept_rate"]
                    - spec_rows["int8"]["accept_rate"])
        spec_rows["accept_rate_delta"] = round(delta, 4)
        spec_rows["accept_gate_ok"] = delta <= ACCEPT_RATE_GATE
        spec_rows["greedy_divergence_rate"] = round(float(
            (outs["bf16"] != outs["int8"]).mean()), 4)
    rows["spec_accept_gate"] = spec_rows

    # -- weight-only quantized matmul rows ----------------------------------
    wq_rows = {"backend": platform, "skipped": False}
    qparams = quantize_params(params)
    prompt = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (4, spec_prompt)), jnp.int32)
    for name, p in (("float", params), ("int8_weights", qparams)):
        def run(_, p=p):
            out = generate(p, prompt, cfg, max_new_tokens=short_new * 4,
                           cache_layout="paged", block_size=bs)
            return (out, out)

        sec = _time_fn(run, n_warmup=1, iters=3 if on_tpu else 2,
                       name=f"wq_{name}")
        wq_rows[name] = {
            "decode_tokens_per_sec": round(
                4 * short_new * 4 / sec, 1),
            "param_bytes": param_bytes(p),
        }
    wq_rows["weight_bytes_ratio"] = round(
        wq_rows["int8_weights"]["param_bytes"]
        / wq_rows["float"]["param_bytes"], 3)
    wq_rows["note"] = ("CPU smoke: the weight win is HBM bandwidth — "
                       "the byte ratio is the signal, not the rate"
                       if not on_tpu else "")
    rows["weight_only"] = wq_rows
    return rows


# the spec-decode accept-rate delta bound between the bf16 and int8
# pool forms — the cheap perplexity-drift proxy of ISSUE 14 (the same
# constant gates the test in tests/test_serving_quantized.py)
ACCEPT_RATE_GATE = 0.10


def bench_spec_ablation(on_tpu, specs, cache_layout="contiguous"):
    """Speculative-decoding ablation (ISSUE 8): ``generate`` timed with
    spec off vs n-gram self-drafting, over the accept-rate sweep —
    ``repetition`` (synthetic-repetition prompts, greedy: the
    high-accept end, where prompt-lookup drafting should land most of
    its k tokens) vs ``random`` (uniform random prompts sampled at
    temperature 1 over the full vocab: the adversarial low-accept end,
    where almost every draft is rejected and spec pays verify overhead
    for nothing).  Each row carries the layout tag, the realized
    draft/accepted/verify counters, the accept rate, and
    ``decode_tokens_per_sec`` — so the headline multiple AND its
    sensitivity to traffic shape are both on the record."""
    from apex_tpu.models.generate import generate, init_kv_cache, prefill
    from apex_tpu.models.speculative import SpecConfig, spec_generate
    from apex_tpu.models.transformer_lm import init_gpt_params

    if on_tpu:
        batch, prompt_len, new, iters, k = 8, 64, 128, 5, 8
        cfg = gpt_125m(max_position_embeddings=512)
    else:
        batch, prompt_len, new, iters, k = 2, 16, 48, 2, 8
        cfg = gpt_125m(num_layers=2, hidden_size=128,
                       num_attention_heads=4, vocab_size=1024,
                       max_position_embeddings=256)
    rng = np.random.RandomState(0)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    pattern = rng.randint(0, cfg.vocab_size, (4,))
    rep_prompt = jnp.asarray(
        np.tile(pattern, (batch, -(-prompt_len // 4)))[:, :prompt_len],
        jnp.int32)
    rnd_prompt = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    sweeps = {
        "repetition": (rep_prompt, 0.0),
        "random": (rnd_prompt, 1.0),
    }
    rows = {"cache_layout": cache_layout, "spec_k": k,
            "batch": batch, "prompt": prompt_len, "new_tokens": new}
    for sweep, (prompt, temp) in sweeps.items():
        def run_prefill(_, prompt=prompt):
            cache = init_kv_cache(cfg, batch, prompt_len + new,
                                  cache_layout=cache_layout)
            lg, _c = prefill(params, prompt, cfg, cache=cache)
            return (lg, lg)

        pf_sec = _time_fn(run_prefill, n_warmup=1, iters=iters,
                          name=f"spec_{sweep}_prefill")
        srow = {}
        for mode in specs:
            if mode == "off":
                def run(_, prompt=prompt, temp=temp):
                    out = generate(params, prompt, cfg,
                                   max_new_tokens=new, temperature=temp,
                                   cache_layout=cache_layout)
                    return (out, out)

                stats = None
            else:
                spec_cfg = SpecConfig(k=k)

                def run(_, prompt=prompt, temp=temp, spec_cfg=spec_cfg):
                    out, _s = spec_generate(
                        params, prompt, cfg, spec=spec_cfg,
                        max_new_tokens=new, temperature=temp,
                        cache_layout=cache_layout)
                    return (out, out)

                _out, stats = spec_generate(
                    params, prompt, cfg, spec=spec_cfg,
                    max_new_tokens=new, temperature=temp,
                    cache_layout=cache_layout)
            sec = _time_fn(run, n_warmup=1, iters=iters,
                           name=f"spec_{sweep}_{mode}")
            decode_sec = sec - pf_sec
            noisy = decode_sec <= 0
            if noisy:
                decode_sec = sec
            entry = {
                "decode_tokens_per_sec": round(batch * new / decode_sec,
                                               1),
                "ms_per_token": round(decode_sec / new * 1e3, 3),
                "e2e_ms": round(sec * 1e3, 2),
                "cache_layout": cache_layout,
            }
            if noisy:
                entry["noisy_prefill_timing"] = True
            if stats is not None:
                draft = max(stats["draft_tokens"], 1)
                verify = max(stats["verify_calls"], 1)
                entry.update({
                    "draft_tokens": stats["draft_tokens"],
                    "accepted_tokens": stats["accepted_tokens"],
                    "verify_calls": stats["verify_calls"],
                    "accept_rate": round(
                        stats["accepted_tokens"] / draft, 4),
                    # emitted tokens amortized per verify forward —
                    # the number the decode multiple tracks
                    "tokens_per_verify": round(
                        (stats["accepted_tokens"] + verify) / verify, 3),
                })
            srow[mode] = entry
        if "off" in srow and "ngram" in srow:
            srow["ngram_over_off"] = round(
                srow["ngram"]["decode_tokens_per_sec"]
                / max(srow["off"]["decode_tokens_per_sec"], 1e-9), 3)
        rows[sweep] = srow
    return rows


def _print_spec_table(details, out=None):
    """Human-readable stderr table for the --spec ablation (the JSON
    line is the machine record; this is the at-a-glance one) — the
    accept-rate column is the satellite the campaign log reads."""
    import sys

    out = sys.stderr if out is None else out
    print("== spec ablation (decode) ==", file=out)
    print(f"{'layout':<12} {'sweep':<12} {'spec':<7} {'tok/s':>9} "
          f"{'accept%':>8} {'tok/verify':>10} {'draft':>7} {'acc':>7} "
          f"{'verify':>7}", file=out)
    for name, rows in sorted(details.items()):
        if not isinstance(rows, dict) or "spec_k" not in rows:
            continue
        layout = rows.get("cache_layout", "?")
        for sweep, srow in rows.items():
            if not isinstance(srow, dict) or "off" not in srow:
                continue
            for mode, e in srow.items():
                if not isinstance(e, dict):
                    continue
                acc = e.get("accept_rate")
                print(
                    f"{layout:<12} {sweep:<12} {mode:<7} "
                    f"{e.get('decode_tokens_per_sec', 0.0):>9.1f} "
                    f"{'-' if acc is None else f'{100 * acc:.1f}':>8} "
                    f"{e.get('tokens_per_verify', '-'):>10} "
                    f"{e.get('draft_tokens', '-'):>7} "
                    f"{e.get('accepted_tokens', '-'):>7} "
                    f"{e.get('verify_calls', '-'):>7}", file=out)
            if "ngram_over_off" in srow:
                print(f"{layout:<12} {sweep:<12} {'x':<7} "
                      f"{srow['ngram_over_off']:>9} (ngram/off)",
                      file=out)


def _print_cache_dtype_table(rows, out=None):
    """Human-readable stderr table for the --cache-dtype ablation (the
    JSON line is the machine record) — concurrency multiple, preempts,
    the accept-rate gate verdict, and the weight byte ratio."""
    import sys

    out = sys.stderr if out is None else out
    print("== quantized serving (--cache-dtype) ==", file=out)
    if "error" in rows:
        print(f"  ERROR: {rows['error']}", file=out)
        return
    print(f"{'wire':<6} {'blocks':>7} {'pool MB':>8} {'max conc':>9} "
          f"{'preempt':>8} {'tok/s':>9}", file=out)
    for wire in ("bf16", "int8"):
        r = rows.get(wire)
        if not isinstance(r, dict):
            continue
        print(f"{wire:<6} {r['num_blocks']:>7} "
              f"{r['cache_bytes'] / 1e6:>8.2f} "
              f"{r['max_concurrent_requests']:>9} "
              f"{r['preemptions']:>8} {r['gen_tokens_per_sec']:>9.1f}",
              file=out)
    if "admitted_concurrency_multiple" in rows:
        print(f"admitted concurrency multiple (int8/bf16): "
              f"{rows['admitted_concurrency_multiple']} at pool-bytes "
              f"ratio {rows['pool_bytes_ratio']}", file=out)
    sg = rows.get("spec_accept_gate", {})
    if "accept_rate_delta" in sg:
        verdict = "OK" if sg.get("accept_gate_ok") else "FAILED"
        print(f"spec accept-rate: bf16 {sg['bf16']['accept_rate']} vs "
              f"int8 {sg['int8']['accept_rate']} (delta "
              f"{sg['accept_rate_delta']} <= {ACCEPT_RATE_GATE}: "
              f"{verdict}); greedy divergence "
              f"{sg.get('greedy_divergence_rate')}", file=out)
    wq = rows.get("weight_only", {})
    if "weight_bytes_ratio" in wq:
        print(f"weight-only int8: param bytes x{wq['weight_bytes_ratio']}"
              f" of float ({wq['float']['param_bytes']} -> "
              f"{wq['int8_weights']['param_bytes']})", file=out)


# -- serve-trace: single-engine vs disaggregated topology (ISSUE 9) ---------

# the tiny trace model, expressed as worker CLI flags so the spawned
# pool members materialize IDENTICAL parameters from the same seed
_TRACE_MODEL = dict(layers=2, hidden=64, heads=4, vocab=256,
                    max_pos=128, seed=0)
_TRACE_ENGINE = dict(max_slots=3, max_len=64, block_size=8)


def _trace_cfg():
    from apex_tpu.models.config import TransformerConfig

    m = _TRACE_MODEL
    return TransformerConfig(
        num_layers=m["layers"], hidden_size=m["hidden"],
        num_attention_heads=m["heads"], vocab_size=m["vocab"],
        max_position_embeddings=m["max_pos"],
        compute_dtype=jnp.float32, remat=False)


def _bursty_trace(rng, vocab, n_requests=18, calm_gap_s=0.15,
                  burst_every=6, burst_len=3):
    """Open-loop arrival trace: a calm exponential stream punctuated by
    near-simultaneous bursts (every ``burst_every``-th arrival opens a
    ``burst_len`` back-to-back volley) — the tail-forming load shape a
    router exists for.  Classes cycle interactive (short, tight
    deadlines) / standard / batch (long, deadline-free); all greedy so
    the two topologies must agree token-for-token."""
    shapes = (("interactive", 8, 6), ("standard", 16, 8),
              ("batch", 28, 12))
    trace = []
    t = 0.0
    i = 0
    while len(trace) < n_requests:
        in_burst = (i % burst_every) == 0
        volley = burst_len if in_burst else 1
        for _ in range(volley):
            if len(trace) >= n_requests:
                break
            cls, plen, new = shapes[len(trace) % len(shapes)]
            trace.append((round(t, 4), dict(
                prompt=rng.randint(0, vocab, (plen,)).tolist(),
                max_new_tokens=new, temperature=0.0, slo_class=cls)))
            t += 0.002                      # burst spacing: ~zero
        t += float(rng.exponential(calm_gap_s))
        i += 1
    return trace


def _replay_single(engine, trace, max_wall_s=300.0):
    """Open-loop replay against one ServingEngine: arrivals submit at
    their trace offsets regardless of completions (same discipline as
    Router.run_trace), steps run continuously."""
    import time as _time

    order = sorted(trace, key=lambda item: item[0])
    t0 = _time.perf_counter()
    i = 0
    resps = []
    while i < len(order) or not engine.idle:
        now = _time.perf_counter() - t0
        while i < len(order) and order[i][0] <= now:
            engine.submit(**order[i][1])
            i += 1
        resps.extend(engine.step())
        if engine.idle and i < len(order):
            wait = order[i][0] - (_time.perf_counter() - t0)
            if wait > 0:
                _time.sleep(min(wait, 0.002))
        if _time.perf_counter() - t0 > max_wall_s:
            break
    return resps, _time.perf_counter() - t0


def bench_serve_trace(cache_layout="paged", wire_dtype="raw",
                      n_requests=18):
    """The disaggregation anchor (ISSUE 9 / ROADMAP item 4): ONE bursty
    open-loop arrival trace replayed against (a) the single-process
    ServingEngine and (b) the two-process prefill/decode topology —
    real OS processes, real sockets, the KV cache crossing the wire —
    on one host, reporting measured per-class TTFT/e2e p50/p95 +
    goodput for both, the realized handoff bytes, and whether greedy
    outputs stayed token-identical across the handoff (``wire_dtype=
    "raw"`` must; the compressed wire forms trade that for bytes).

    CPU-pinned by design (main() forces the platform): this row
    measures TOPOLOGY cost — routing, framing, wire, injection — under
    identical numerics, not chip throughput."""
    import time as _time

    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.cluster import Router
    from apex_tpu.serving.cluster.worker import spawn_worker

    cfg = _trace_cfg()
    params = init_gpt_params(jax.random.PRNGKey(_TRACE_MODEL["seed"]),
                             cfg)
    rng = np.random.RandomState(7)
    trace = _bursty_trace(rng, cfg.vocab_size, n_requests=n_requests)
    engine_kw = dict(max_slots=_TRACE_ENGINE["max_slots"],
                     max_len=_TRACE_ENGINE["max_len"],
                     cache_layout=cache_layout)
    if cache_layout == "paged":
        engine_kw["block_size"] = _TRACE_ENGINE["block_size"]

    row = {"cache_layout": cache_layout, "wire_dtype": wire_dtype,
           "requests": len(trace),
           "trace_span_s": round(trace[-1][0], 3)}

    # -- topology A: one process, one engine ---------------------------
    ServingEngine(params, cfg, **engine_kw).run(
        [dict(prompt=t[1]["prompt"], max_new_tokens=2)
         for t in trace[:2]])                       # compile warmup
    engine = ServingEngine(params, cfg, **engine_kw)
    single, wall_a = _replay_single(engine, trace)
    row["single_engine"] = {
        "wall_s": round(wall_a, 3),
        "completed": len(single),
        "gen_tokens_per_sec": round(
            sum(r.tokens.size for r in single) / wall_a, 1),
        "slo": _slo_fields(single),
    }

    # -- topology B: router + prefill process + decode process ---------
    model_flags = []
    for flag, key in (("--layers", "layers"), ("--hidden", "hidden"),
                      ("--heads", "heads"), ("--vocab", "vocab"),
                      ("--max-pos", "max_pos"), ("--seed", "seed")):
        model_flags += [flag, str(_TRACE_MODEL[key])]
    decode_flags = model_flags + [
        "--max-slots", str(_TRACE_ENGINE["max_slots"]),
        "--max-len", str(_TRACE_ENGINE["max_len"]),
        "--cache-layout", cache_layout,
        "--block-size", str(_TRACE_ENGINE["block_size"])]
    prefill_flags = model_flags + [
        "--max-len", str(_TRACE_ENGINE["max_len"]),
        "--wire-dtype", wire_dtype]
    procs = []
    try:
        pf_proc, pf_addr, _ = spawn_worker("prefill",
                                           extra_args=prefill_flags)
        procs.append(pf_proc)
        dc_proc, dc_addr, _ = spawn_worker("decode",
                                           extra_args=decode_flags)
        procs.append(dc_proc)
        router = Router([pf_addr], [dc_addr], wire_dtype=wire_dtype)
        # warmup: compile both workers' buckets before the clock runs
        for t in trace[:2]:
            router.submit(t[1]["prompt"], max_new_tokens=2)
        router.run(max_wall_s=180)
        t0 = _time.perf_counter()
        disagg = router.run_trace(trace, max_wall_s=300)
        wall_b = _time.perf_counter() - t0
        row["disaggregated"] = {
            "wall_s": round(wall_b, 3),
            "completed": len(disagg),
            "gen_tokens_per_sec": round(
                sum(r.tokens.size for r in disagg) / wall_b, 1),
            "handoff_bytes_total": sum(r.handoff_bytes
                                       for r in disagg),
            "requeued": router.stats()["requeued"],
            "slo": _slo_fields(disagg),
        }
        # the acceptance pin, measured in the bench itself: same trace,
        # same greedy sampling — the handoff must not change one token.
        # Compared in SUBMISSION order (request ids sort identically
        # within each topology but the router's warmup offsets its id
        # space, so ids themselves are not comparable across them).
        seq_a = [r.tokens.tolist()
                 for r in sorted(single, key=lambda r: r.request_id)]
        seq_b = [r.tokens.tolist()
                 for r in sorted(disagg, key=lambda r: r.request_id)]
        row["token_identical"] = seq_a == seq_b
        if not row["token_identical"]:
            row["token_mismatch_indices"] = [
                i for i in range(max(len(seq_a), len(seq_b)))
                if (seq_a[i: i + 1] or [None])
                != (seq_b[i: i + 1] or [None])][:8]
        router.close(shutdown_workers=True)
    finally:
        from apex_tpu.serving.cluster.worker import shutdown_worker

        for proc in procs:
            try:
                shutdown_worker(proc)
            except Exception:
                proc.kill()
    return row


def bench_chunked_starvation(platform="cpu"):
    """The chunked-prefill interference gate (ISSUE 15): one long
    prompt admitted into a pool of decoding lanes must not spike every
    co-resident request's TPOT.

    Three runs of the same engine geometry:

    - ``baseline`` — the short-request stream alone (the no-long-prompt
      TPOT floor);
    - ``monolithic`` — a long prompt admitted mid-stream through the
      one-shot prefill: every co-resident decode stalls for the whole
      prefill forward (the unbounded spike this row documents);
    - ``chunked`` — same trace with ``chunk_tokens`` set: the long
      prompt streams its prefill one chunk per step, interleaved with
      the shorts' decode.

    The acceptance gate: chunked short-request TPOT p95 <= 2x the
    baseline p95 (``tpot_gate_ok``) — each mixed step pays one chunk
    forward on top of the decode, never the whole prompt.  Greedy
    token-identity chunked-vs-monolithic rides every run
    (``token_identical``)."""
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine

    from apex_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        num_layers=2, hidden_size=128, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=640,
        compute_dtype=jnp.float32, remat=False)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(11)
    chunk = 64
    long_prompt, long_new = 448, 4
    shorts = [dict(prompt=rng.randint(0, 256, (16,)),
                   max_new_tokens=24, slo_class="standard")
              for _ in range(3)]
    long_req = dict(prompt=rng.randint(0, 256, (long_prompt,)),
                    max_new_tokens=long_new, slo_class="batch")

    def engine(chunk_tokens=None):
        return ServingEngine(
            params, cfg, max_slots=4, max_len=576,
            cache_layout="paged", block_size=16,
            chunk_tokens=chunk_tokens)

    def drive(eng, with_long):
        # shorts first (they claim lanes and start decoding), the long
        # admitted mid-stream into the free lane — its prefill lands
        # while every short is mid-decode, which is the starvation shape
        for kw in shorts:
            eng.submit(**{k: (v.copy() if hasattr(v, "copy") else v)
                          for k, v in kw.items()})
        for _ in range(2):
            eng.step()
        if with_long:
            eng.submit(**dict(long_req, prompt=long_req["prompt"].copy()))
        resps = []
        while not eng.idle:
            resps.extend(eng.step())
        return resps

    def tpot_p95(resps):
        vals = [r.tpot_ms for r in resps
                if r.slo_class == "standard" and r.tokens.size > 1]
        return round(_pct_of(vals, .95), 4)

    rows = {"backend": platform, "skipped": False,
            "chunk_tokens": chunk, "long_prompt": long_prompt,
            "short_requests": len(shorts)}
    drive(engine(), False)                       # warmup compiles
    rows["baseline_tpot_ms_p95"] = tpot_p95(drive(engine(), False))
    mono = drive(engine(), True)
    rows["monolithic_tpot_ms_p95"] = tpot_p95(mono)
    drive(engine(chunk), True)                   # warmup chunk compile
    chunked = drive(engine(chunk), True)
    rows["chunked_tpot_ms_p95"] = tpot_p95(chunked)
    base = max(rows["baseline_tpot_ms_p95"], 1e-9)
    rows["monolithic_over_baseline"] = round(
        rows["monolithic_tpot_ms_p95"] / base, 2)
    rows["chunked_over_baseline"] = round(
        rows["chunked_tpot_ms_p95"] / base, 2)
    # THE GATE: chunking bounds the interference at 2x the
    # no-long-prompt floor (the monolithic ratio is the documented
    # spike it replaces)
    rows["tpot_gate_ok"] = rows["chunked_over_baseline"] <= 2.0
    rows["token_identical"] = (
        sorted((r.request_id, tuple(r.tokens.tolist())) for r in mono)
        == sorted((r.request_id, tuple(r.tokens.tolist()))
                  for r in chunked))
    return rows


def bench_host_tier_ablation(platform="cpu", modes=("off", "on")):
    """Hierarchical KV cache ablation (ISSUE 18): the host-DRAM
    offload tier off vs on, under the two traces it exists for.

    - **starvation mix** — a pool sized to preempt the youngest of
      three co-resident requests: with the tier OFF the preempted
      request re-admits through a full prefill replay; ON it resumes
      via a raw-wire page-in (one jitted scatter).  The row reports
      the preempted requests' preempt-overhead p95 per mode and the
      acceptance ratio (``resume_over_replay_overhead`` — the page-in
      must beat the forward pass it replaces), plus greedy
      token-identity across modes (the raw wire is bitwise, so the
      tier must be numerically invisible).
    - **shared-system-prompt trace** — sequential arrivals sharing a
      64-token system prefix, admitted chunked so every full chunk's
      digest publishes: OFF, each arrival re-prefills the cold prefix
      (the pool freed it at completion); ON, the parked digests page
      back in and only the private tail prefills.  The row reports
      TTFT p95 per mode and the host-tier hit ledger.

    CPU-pinned like the serve-trace rows; every row carries backend/
    skipped so a smoke run self-describes."""
    from apex_tpu.models.config import TransformerConfig
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine

    cfg = TransformerConfig(
        num_layers=2, hidden_size=128, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=256,
        compute_dtype=jnp.float32, remat=False)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(18)
    tier_kw = {"off": {}, "on": {"host_tier_bytes": 1 << 26}}

    # -- starvation mix: preemption -> resume-vs-replay --------------
    starve = [dict(prompt=rng.randint(0, 256, (64,)),
                   max_new_tokens=24) for _ in range(3)]

    def starve_engine(mode):
        # 18 blocks of 8 admit two 64-token prompts (16 blocks) but
        # cannot hold both grown to 88 tokens (22): the youngest
        # preempts mid-decode and re-admits
        return ServingEngine(
            params, cfg, max_slots=3, max_len=160,
            prompt_buckets=(64,), cache_layout="paged", block_size=8,
            num_blocks=18, reserve_blocks=0, **tier_kw[mode])

    def drive(eng, reqs):
        return eng.run([{k: (v.copy() if hasattr(v, "copy") else v)
                         for k, v in r.items()} for r in reqs])

    for mode in dict.fromkeys(modes):            # warmup compiles —
        drive(starve_engine(mode), starve)       # incl. the page-in
                                                 # scatter (on only)
    rows = {"backend": platform, "skipped": False,
            "modes": list(modes)}
    starve_rows, tokens_by_mode = {}, {}
    for mode in modes:
        eng = starve_engine(mode)
        resps = drive(eng, starve)
        overhead = sorted(r.preempt_overhead_ms for r in resps
                          if r.preemptions)
        st = eng.stats()
        row = {"preemptions": st["preemptions"],
               "preempted_requests": len(overhead),
               "preempt_overhead_ms_p95": round(
                   _pct_of(overhead, .95), 4) if overhead else None,
               # per preemption CYCLE: the tier makes each cycle so
               # cheap the scheduler may churn through more of them,
               # so per-request totals compare unlike counts — the
               # resume-vs-replay question is what ONE re-admission
               # costs
               "preempt_overhead_ms_per_cycle": round(
                   sum(overhead) / st["preemptions"], 4)
               if st["preemptions"] else None,
               "tpot_ms_p95": round(_pct_of(
                   [r.tpot_ms for r in resps if r.tokens.size > 1],
                   .95), 4),
               "blocks_leaked": st["blocks_in_use"]}
        if mode == "on":
            ht = st.get("host_tier") or {}
            row["host_resumes"] = ht.get("hits", 0)
            row["host_misses"] = ht.get("misses", 0)
        starve_rows[mode] = row
        tokens_by_mode[mode] = sorted(
            (r.request_id, tuple(r.tokens.tolist())) for r in resps)
    rows["starvation"] = starve_rows
    if len(modes) == 2:
        rows["token_identical"] = (
            tokens_by_mode[modes[0]] == tokens_by_mode[modes[1]])
        off_oh = starve_rows["off"].get("preempt_overhead_ms_per_cycle")
        on_oh = starve_rows["on"].get("preempt_overhead_ms_per_cycle")
        if off_oh and on_oh:
            # THE GATE: one page-in resume must beat the one prefill
            # replay it displaces
            rows["resume_over_replay_overhead"] = round(
                on_oh / off_oh, 3)
            rows["resume_beats_replay"] = on_oh <= off_oh

    # -- shared-system-prompt trace: cold-prefix page-in -------------
    system = rng.randint(0, 256, (64,))
    shared_reqs = [dict(prompt=np.concatenate(
        [system, rng.randint(0, 256, (8,))]).astype(np.int32),
        max_new_tokens=8) for _ in range(4)]

    def shared_engine(mode):
        return ServingEngine(
            params, cfg, max_slots=2, max_len=96,
            prompt_buckets=(72,), cache_layout="paged", block_size=8,
            chunk_tokens=16, **tier_kw[mode])

    for mode in dict.fromkeys(modes):
        # warmup: chunk ladder + (on) the digest page-in path — the
        # second sequential request is the one that pages in
        weng = shared_engine(mode)
        for r in shared_reqs[:2]:
            drive(weng, [r])
    shared_rows = {}
    for mode in modes:
        eng = shared_engine(mode)
        ttfts, all_tokens = [], []
        # sequential arrivals: the prefix is COLD between requests —
        # exactly the trace where only a parked copy can share it
        for r in shared_reqs:
            resps = drive(eng, [r])
            ttfts += [x.ttft_ms for x in resps]
            all_tokens += [tuple(x.tokens.tolist()) for x in resps]
        st = eng.stats()
        row = {"ttft_ms_p95": round(_pct_of(sorted(ttfts), .95), 4),
               "blocks_leaked": st["blocks_in_use"]}
        if mode == "on":
            ht = st.get("host_tier") or {}
            row["host_hits"] = ht.get("hits", 0)
            row["host_pages_parked"] = ht.get("pages", 0)
        shared_rows[mode] = {**row, "tokens": hash(tuple(all_tokens))}
    rows["shared_prompt"] = shared_rows
    if len(modes) == 2:
        rows["shared_token_identical"] = (
            shared_rows[modes[0]]["tokens"]
            == shared_rows[modes[1]]["tokens"])
        rows["shared_ttft_on_over_off"] = round(
            shared_rows["on"]["ttft_ms_p95"]
            / max(shared_rows["off"]["ttft_ms_p95"], 1e-9), 3)
    for m in shared_rows.values():
        m.pop("tokens", None)
    return rows


def bench_adapter_ablation(platform="cpu", counts=(1, 8, 64)):
    """Multi-tenant LoRA serving ablation (ISSUE 20): one decode
    engine serving ``count`` DISTINCT adapters, three ways at batch
    parity (same prompts, same ``max_slots``):

    - **batched** — the ragged grouped-matmul path: an
      :class:`AdapterPool` smaller than the tenant count (the LRU
      churns), heterogeneous adapter ids across co-resident lanes,
      one engine for the whole mix;
    - **merged** — the classic single-tenant fast path: adapter 1
      folded into the base weights (``merge_lora``), the same batch on
      one engine.  The ISSUE 20 gate is batched >= 0.8x THIS row's
      tokens/s — heterogeneity must cost little vs the best
      homogeneous case;
    - **sequential** — the only way merged weights serve many tenants:
      one merge + one solo run per adapter, summed.  This is the
      baseline that degrades with tenant count (batching is lost), and
      its per-request greedy tokens are the merged-weights REFERENCE
      the batched mix must match token-for-token.

    Every row carries the pool-churn ledger (hits/misses/evictions,
    preemptions, zero pinned refs after drain + a ``census()``
    partition check) and backend/skipped — off-TPU the tokens/s are
    same-backend ratios, not chip rates."""
    import time as _time

    from apex_tpu.models.config import TransformerConfig
    from apex_tpu.models.lora import merge_lora
    from apex_tpu.models.transformer_lm import init_gpt_params
    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.adapter_pool import AdapterPool
    from apex_tpu.serving.cluster.worker import build_adapter_suite

    cfg = TransformerConfig(
        num_layers=2, hidden_size=128, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=256,
        compute_dtype=jnp.float32, remat=False)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    suite = build_adapter_suite(cfg, max(counts), rank=4)
    geometry = dict(max_slots=4, max_len=64, prompt_buckets=(16,),
                    cache_layout="paged", block_size=8,
                    num_blocks=48, reserve_blocks=0)
    # > max_slots so admission never blocks on a pinned-full pool, but
    # far below 64 registered tenants so the LRU actually churns
    POOL_SLOTS = 6

    def trace(count):
        r = np.random.RandomState(1000 + count)
        return [dict(prompt=r.randint(0, 256, (16,)).astype(np.int32),
                     max_new_tokens=8, adapter_id=(i % count) + 1)
                for i in range(count)]

    def drive(eng, reqs, with_adapter):
        return eng.run([
            dict(prompt=r["prompt"].copy(),
                 max_new_tokens=r["max_new_tokens"],
                 **({"adapter_id": r["adapter_id"]}
                    if with_adapter else {}))
            for r in reqs])

    def pooled_engine(count):
        pool = AdapterPool(cfg, slots=POOL_SLOTS)
        for aid in range(1, count + 1):
            pool.register(aid, suite[aid])
        return ServingEngine(params, cfg, adapter_pool=pool,
                             **geometry), pool

    # warmup compiles: the ragged batched-delta decode step and the
    # plain merged step are distinct jit keys
    warm_count = min(2, max(counts))
    weng, _ = pooled_engine(warm_count)
    drive(weng, trace(warm_count), True)
    drive(ServingEngine(merge_lora(params, cfg, suite[1]), cfg,
                        **geometry), trace(warm_count), False)

    rows = {"backend": platform, "skipped": False,
            "counts": list(counts), "pool_slots": POOL_SLOTS,
            "batch_slots": geometry["max_slots"]}
    for count in counts:
        reqs = trace(count)

        # -- batched: heterogeneous lanes through one pooled engine --
        eng, pool = pooled_engine(count)
        t0 = _time.perf_counter()
        resps = drive(eng, reqs, True)
        bwall = _time.perf_counter() - t0
        gen = sum(int(r.tokens.size) for r in resps)
        batched_tokens = [tuple(r.tokens.tolist()) for r in
                          sorted(resps, key=lambda r: r.request_id)]
        pst, est = pool.stats(), eng.stats()
        batched = {"tokens_per_sec": round(gen / max(bwall, 1e-9), 2),
                   "pool_hits": pst["hits"],
                   "pool_misses": pst["misses"],
                   "pool_evictions": pst["evictions"],
                   "pinned_refs_after": pst["pinned_refs"],
                   "preemptions": est["preemptions"],
                   "blocks_leaked": est["blocks_in_use"],
                   "pool_census": pool.census()}

        # -- merged: adapter 1 folded into the weights, same batch ---
        meng = ServingEngine(merge_lora(params, cfg, suite[1]), cfg,
                             **geometry)
        t0 = _time.perf_counter()
        mresps = drive(meng, reqs, False)
        mwall = _time.perf_counter() - t0
        merged = {"tokens_per_sec": round(
            sum(int(r.tokens.size) for r in mresps)
            / max(mwall, 1e-9), 2)}

        # -- sequential: one merge + one solo run per tenant ---------
        seq_tokens = [None] * count
        swall = sgen = 0.0
        for aid in sorted({r["adapter_id"] for r in reqs}):
            idxs = [i for i, r in enumerate(reqs)
                    if r["adapter_id"] == aid]
            t0 = _time.perf_counter()
            seng = ServingEngine(merge_lora(params, cfg, suite[aid]),
                                 cfg, **geometry)
            srs = drive(seng, [reqs[i] for i in idxs], False)
            swall += _time.perf_counter() - t0
            sgen += sum(int(r.tokens.size) for r in srs)
            for i, r in zip(idxs, sorted(
                    srs, key=lambda x: x.request_id)):
                seq_tokens[i] = tuple(r.tokens.tolist())
        sequential = {"tokens_per_sec": round(
            sgen / max(swall, 1e-9), 2)}

        row = {"batched": batched, "merged": merged,
               "sequential": sequential,
               # THE GATE: every heterogeneous greedy stream must
               # match its per-request merged-weights reference
               "token_identical": batched_tokens == seq_tokens,
               "batched_over_merged": round(
                   batched["tokens_per_sec"]
                   / max(merged["tokens_per_sec"], 1e-9), 3),
               "batched_over_sequential": round(
                   batched["tokens_per_sec"]
                   / max(sequential["tokens_per_sec"], 1e-9), 3)}
        rows[f"adapters_{count}"] = row
    return rows


# the controller-trace engine geometry (larger than _TRACE_ENGINE so a
# long prompt + chunking have room)
_CTRL_ENGINE = dict(max_slots=3, max_len=96, block_size=8,
                    chunk_tokens=16)


def _diurnal_trace(rng, vocab, calm=6, crowd=10, tail=5):
    """Diurnal + flash-crowd arrivals (ISSUE 15): a calm morning
    stream, a near-simultaneous crowd volley (with two LONG batch
    prompts riding it — the chunked-prefill stressor), then a long
    calm tail that gives a scale-down its window.  All greedy so every
    topology/knob cell must agree token-for-token."""
    shapes = (("standard", 12, 8), ("interactive", 8, 6),
              ("standard", 16, 6))
    trace = []
    t = 0.0
    for i in range(calm):
        cls, plen, new = shapes[i % len(shapes)]
        trace.append((round(t, 4), dict(
            prompt=rng.randint(0, vocab, (plen,)).tolist(),
            max_new_tokens=new, temperature=0.0, slo_class=cls)))
        t += float(rng.exponential(0.25))
    # flash crowd: everything lands inside ~50 ms
    for i in range(crowd):
        if i % 5 == 4:
            trace.append((round(t, 4), dict(
                prompt=rng.randint(0, vocab, (80,)).tolist(),
                max_new_tokens=6, temperature=0.0, slo_class="batch")))
        else:
            cls, plen, new = shapes[i % len(shapes)]
            trace.append((round(t, 4), dict(
                prompt=rng.randint(0, vocab, (plen,)).tolist(),
                max_new_tokens=new, temperature=0.0, slo_class=cls)))
        t += 0.005
    # calm tail: sparse arrivals — the scale-down window
    for i in range(tail):
        cls, plen, new = shapes[i % len(shapes)]
        t += float(rng.exponential(0.4)) + 0.2
        trace.append((round(t, 4), dict(
            prompt=rng.randint(0, vocab, (plen,)).tolist(),
            max_new_tokens=new, temperature=0.0, slo_class=cls)))
    return trace


def _spawn_ctrl_workers(chunked, n_decode):
    """Spawn 1 prefill + n decode workers with the controller-trace
    geometry; returns (procs, prefill_addr, decode_addrs,
    decode_flags)."""
    from apex_tpu.serving.cluster.worker import spawn_worker

    model_flags = []
    for flag, key in (("--layers", "layers"), ("--hidden", "hidden"),
                      ("--heads", "heads"), ("--vocab", "vocab"),
                      ("--max-pos", "max_pos"), ("--seed", "seed")):
        model_flags += [flag, str(_TRACE_MODEL[key])]
    decode_flags = model_flags + [
        "--max-slots", str(_CTRL_ENGINE["max_slots"]),
        "--max-len", str(_CTRL_ENGINE["max_len"]),
        "--cache-layout", "paged",
        "--block-size", str(_CTRL_ENGINE["block_size"])]
    if chunked:
        decode_flags += ["--chunk-tokens",
                         str(_CTRL_ENGINE["chunk_tokens"])]
    prefill_flags = model_flags + [
        "--max-len", str(_CTRL_ENGINE["max_len"])]
    procs = []
    pf_proc, pf_addr, _ = spawn_worker("prefill",
                                       extra_args=prefill_flags)
    procs.append(pf_proc)
    dc_addrs = []
    for _ in range(n_decode):
        dc_proc, dc_addr, _ = spawn_worker("decode",
                                           extra_args=decode_flags)
        procs.append(dc_proc)
        dc_addrs.append(dc_addr)
    return procs, pf_addr, dc_addrs, decode_flags


_TROUGH_S = 4.0     # the post-crowd diurnal trough both cells serve


def _controller_cell(trace, chunked, controller):
    """One cell of the on/off x on/off ablation: replay the diurnal
    trace against the spawned-process topology, then serve the
    post-crowd TROUGH (``_TROUGH_S`` of near-idle wall — the diurnal
    valley, compressed).  BOTH cells start at peak provisioning (2
    decode workers: what an operator without an autoscaler must run
    all day); the controller cell lets the elastic loop act on
    ``autoscale_signal`` — the sustained idle signal in the trough
    DRAINS one decode worker losslessly and reaps it, so the cell's
    chip-seconds (the integral of live workers over the whole window)
    come in measurably under static provisioning at the same goodput.
    Chip-seconds are honest spend: a draining worker counts until
    reaped."""
    import time as _time

    from apex_tpu.serving.cluster import PoolController, Router
    from apex_tpu.serving.cluster.worker import shutdown_worker

    procs, pf_addr, dc_addrs, decode_flags = _spawn_ctrl_workers(
        chunked, n_decode=2)
    ctrl = None
    router = None
    try:
        router = Router([pf_addr], dc_addrs)
        # warmup: compile both workers' buckets before the clock runs
        for t in trace[:2]:
            router.submit(t[1]["prompt"], max_new_tokens=2)
        router.run(max_wall_s=180)
        on_step = None
        if controller:
            ctrl = PoolController(
                router,
                worker_flags={"decode": decode_flags},
                min_decode=1, max_decode=2, min_prefill=1,
                max_prefill=1, scale_up_after=2, scale_down_after=3,
                cooldown_ticks=2, tick_interval_s=0.25)
            ctrl.tick()          # open the chip-seconds clock at start
            on_step = ctrl.maybe_tick
        t0 = _time.perf_counter()
        out = router.run_trace(trace, max_wall_s=600, on_step=on_step)
        # the trough: sparse-to-zero arrivals.  The controller keeps
        # ticking (this is where the scale-down fires); the static
        # cell just burns its peak fleet.  Anchored at run_trace's
        # RETURN, not the trace span — a loaded box that took longer
        # than the span to drain the crowd must still get its full
        # near-idle window, or the scale-down gate fails spuriously.
        trough_deadline = _time.perf_counter() + _TROUGH_S
        while _time.perf_counter() < trough_deadline:
            out.extend(router.step())
            if on_step is not None:
                on_step()
            # AFTER the tick: a drain fired by on_step banks any
            # completed-but-unpolled responses, and missing them here
            # would fail the zero-lost gate spuriously
            out.extend(router.take_drain_completions())
            _time.sleep(0.02)
        wall = _time.perf_counter() - t0
        if controller:
            ctrl.tick()          # close the accrual window
            out.extend(router.take_drain_completions())
            st = ctrl.stats()
            chip_s = st["chip_seconds"]
            actions = [(a["action"], a["pool"])
                       for a in st["actions"]]
            drained = st["drained_requests"]
        else:
            chip_s = wall * (1 + len(dc_addrs))
            actions, drained = [], 0
        met = sum(1 for r in out if r.slo_met)
        row = {
            "wall_s": round(wall, 3),
            "completed": len(out),
            "submitted": len(trace),
            "zero_lost": len(out) == len(trace),
            "goodput_rate": round(met / max(len(out), 1), 4),
            "chip_seconds": round(chip_s, 3),
            "migrations": sum(r.migrations for r in out),
            "requeues": sum(r.requeues for r in out),
            "actions": actions,
            "drained_requests": drained,
            "slo": _slo_fields(out),
            "tokens": [r.tokens.tolist() for r in sorted(
                out, key=lambda r: r.request_id)],
        }
        return row
    finally:
        if ctrl is not None:
            ctrl.close()
        if router is not None:
            try:
                router.close(shutdown_workers=True)
            except Exception:
                pass
        for proc in procs:
            try:
                shutdown_worker(proc)
            except Exception:
                proc.kill()


def bench_serve_trace_controller(platform="cpu"):
    """THE ISSUE 15 anchor: one diurnal + flash-crowd trace replayed
    against the spawned-process cluster, controller on/off x chunked
    prefill on/off.  Controller-off is static PEAK provisioning held
    through the post-crowd trough (the fleet an operator without an
    autoscaler must run); controller-on starts at the same peak and
    lets the elastic loop act on ``autoscale_signal`` — the trough's
    sustained idle signal drains one decode worker losslessly and
    reaps it.  Gates: controller-on goodput >= off at measurably fewer
    chip-seconds, zero requests lost across scale-down drains, and all
    four cells token-identical (greedy — which subsumes
    migrated-output identity on the raw wire; the deterministic
    mid-flight migration pin lives in
    tests/test_serving_controller.py).

    What the chunked dimension measures HERE, honestly: in the
    disaggregated topology decode pools receive already-prefilled KV
    (``submit_prefilled``), which never takes the chunked path — the
    chunked cells differ from the chunked-off cells only where a
    preemption forces a local resume replay (that replay IS chunked),
    so this axis pins "chunking changes nothing on the cluster path"
    (token identity, no throughput regression), not the interference
    bound.  The interference bound — the ISSUE 15 TPOT gate — is the
    co-located engine's story and is measured by
    ``bench_chunked_starvation`` on the same JSON line."""
    rng = np.random.RandomState(23)
    cfg = _trace_cfg()
    trace = _diurnal_trace(rng, cfg.vocab_size)
    rows = {"backend": platform, "skipped": False,
            "requests": len(trace),
            "trace_span_s": round(trace[-1][0], 3),
            "chunk_tokens": _CTRL_ENGINE["chunk_tokens"],
            # the chunked axis on the CLUSTER path covers only
            # preempt->resume replays (decode pools inject prefilled
            # KV); the TPOT interference gate lives in the
            # chunked_starvation row of this same JSON line
            "chunked_axis_note": "cluster decode pools receive "
            "prefilled KV — chunking engages on resume replays only; "
            "see chunked_starvation for the interference gate"}
    cells = {}
    for chunked in (False, True):
        for controller in (False, True):
            name = (f"chunked_{'on' if chunked else 'off'}"
                    f"_controller_{'on' if controller else 'off'}")
            try:
                cells[name] = _controller_cell(trace, chunked,
                                               controller)
            except Exception as e:
                cells[name] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
    token_sets = [c.pop("tokens") for c in cells.values()
                  if "tokens" in c]
    rows["token_identical_across_cells"] = (
        len(token_sets) == 4
        and all(t == token_sets[0] for t in token_sets[1:]))
    rows.update(cells)
    on = cells.get("chunked_on_controller_on", {})
    off = cells.get("chunked_on_controller_off", {})
    if "goodput_rate" in on and "goodput_rate" in off:
        rows["goodput_ok"] = (on["goodput_rate"]
                              >= off["goodput_rate"])
        rows["chip_seconds_saved_frac"] = round(
            1 - on["chip_seconds"] / max(off["chip_seconds"], 1e-9), 4)
        rows["chip_seconds_ok"] = (on["chip_seconds"]
                                   < off["chip_seconds"])
        rows["zero_lost"] = (on.get("zero_lost", False)
                             and off.get("zero_lost", False))
    return rows


def _spawn_mode_cell(trace, deferred):
    """One cell of the deferred-vs-blocking scale-up ablation: start
    at MIN provisioning (1 decode worker), replay the flash-crowd
    trace, and let the controller scale up mid-crowd.  Blocking mode
    (``defer_spawn=False``) spawns inside the tick — the router loop
    the tick rides stalls for the new worker's entire cold start;
    deferred mode records ``spawn_started`` immediately, polls READY
    non-blocking, and attaches on a later tick.  The max single-tick
    wall is the smoking gun either way."""
    import time as _time

    from apex_tpu.serving.cluster import PoolController, Router
    from apex_tpu.serving.cluster.worker import shutdown_worker

    procs, pf_addr, dc_addrs, decode_flags = _spawn_ctrl_workers(
        False, n_decode=1)
    ctrl = None
    router = None
    tick_walls = []
    try:
        router = Router([pf_addr], dc_addrs)
        # warmup: compile the workers' buckets before the clock runs
        for t in trace[:2]:
            router.submit(t[1]["prompt"], max_new_tokens=2)
        router.run(max_wall_s=180)
        ctrl = PoolController(
            router, worker_flags={"decode": decode_flags},
            defer_spawn=deferred, spawn_timeout_s=240.0,
            min_decode=1, max_decode=2, min_prefill=1, max_prefill=1,
            scale_up_after=2, scale_down_after=10_000,
            cooldown_ticks=2, tick_interval_s=0.25)
        ctrl.tick()          # open the chip-seconds clock at start

        def on_step():
            t0 = _time.perf_counter()
            if ctrl.maybe_tick() is not None:
                tick_walls.append(_time.perf_counter() - t0)

        t0 = _time.perf_counter()
        out = router.run_trace(trace, max_wall_s=600, on_step=on_step)
        # settle window: let an in-flight attach land and the tail
        # drain — bounded, and exits early once everything completed
        # with no spawn still warming
        deadline = _time.perf_counter() + 15.0
        while _time.perf_counter() < deadline:
            out.extend(router.step())
            on_step()
            out.extend(router.take_drain_completions())
            if (len(out) >= len(trace) and not any(
                    ctrl.stats()["pending_spawns"].values())):
                break
            _time.sleep(0.02)
        wall = _time.perf_counter() - t0
        st = ctrl.stats()
        met = sum(1 for r in out if r.slo_met)
        row = {
            "mode": "deferred" if deferred else "blocking",
            "wall_s": round(wall, 3),
            "completed": len(out),
            "submitted": len(trace),
            "zero_lost": len(out) == len(trace),
            "goodput_rate": round(met / max(len(out), 1), 4),
            "max_tick_ms": round(max(tick_walls) * 1e3, 1)
            if tick_walls else 0.0,
            "actions": [(a["action"], a["pool"])
                        for a in st["actions"]],
            "attached_workers": sum(
                1 for a in st["actions"]
                if a["action"] in ("attach", "spawn")),
            "ready_ms": [a["ready_ms"] for a in st["actions"]
                         if "ready_ms" in a],
            "slo": _slo_fields(out),
            "tokens": [r.tokens.tolist() for r in sorted(
                out, key=lambda r: r.request_id)],
        }
        return row
    finally:
        if ctrl is not None:
            ctrl.close()
        if router is not None:
            try:
                router.close(shutdown_workers=True)
            except Exception:
                pass
        for proc in procs:
            try:
                shutdown_worker(proc)
            except Exception:
                proc.kill()


def bench_spawn_mode_ablation(platform="cpu"):
    """ISSUE 17 deferred-attach anchor: the flash-crowd trace replayed
    at MIN provisioning, blocking spawn vs deferred attach.  Gates:
    deferred goodput >= blocking (the crowd keeps being served while
    the new worker warms), zero requests lost in BOTH cells, token
    identity across cells (greedy), and the deferred cell's max tick
    wall a fraction of the blocking cell's (which contains an entire
    worker cold start)."""
    rng = np.random.RandomState(31)
    cfg = _trace_cfg()
    trace = _diurnal_trace(rng, cfg.vocab_size, calm=2, crowd=12,
                           tail=3)
    rows = {"backend": platform, "requests": len(trace),
            "trace_span_s": round(trace[-1][0], 3)}
    cells = {}
    for mode, deferred in (("blocking", False), ("deferred", True)):
        try:
            cells[mode] = _spawn_mode_cell(trace, deferred)
        except Exception as e:
            cells[mode] = {"error": f"{type(e).__name__}: {e}"[:200]}
    token_sets = [c.pop("tokens") for c in cells.values()
                  if "tokens" in c]
    rows["token_identical"] = (len(token_sets) == 2
                               and token_sets[0] == token_sets[1])
    rows.update(cells)
    dfr = cells.get("deferred", {})
    blk = cells.get("blocking", {})
    if "goodput_rate" in dfr and "goodput_rate" in blk:
        rows["goodput_ok"] = (dfr["goodput_rate"]
                              >= blk["goodput_rate"])
        rows["zero_lost"] = (dfr.get("zero_lost", False)
                             and blk.get("zero_lost", False))
        if dfr.get("max_tick_ms"):
            rows["tick_stall_ratio"] = round(
                blk["max_tick_ms"] / max(dfr["max_tick_ms"], 1e-9), 1)
    return rows


def bench_cold_vs_warm_start(platform="cpu"):
    """ISSUE 17 acceptance row: decode-worker READY time with an
    empty compile-cache dir (cold: trace + AOT-compile the whole
    bucket ladder) vs the SAME dir primed (warm: a few
    ``deserialize_and_load``s).  READY is the worker-INTERNAL
    main()→READY span (the ``ready_ms`` field on the READY line), not
    parent wall: the python+jax import tax is identical in both cells
    and no cache can fix it, so counting it would only dilute the
    ratio.  Gate: warm <= 0.4x cold."""
    import os
    import shutil
    import tempfile
    import time as _time

    from apex_tpu.serving.cluster.worker import (
        shutdown_worker, spawn_worker_async)

    m = _TRACE_MODEL
    cache_dir = tempfile.mkdtemp(prefix="apex_compile_cache_")
    # a fuller ladder than the trace geometry (4 prompt buckets +
    # chunked prefill) so the cold cell compiles something worth
    # caching — the shape a real pool's workers actually carry
    flags = ["--layers", str(m["layers"]), "--hidden", str(m["hidden"]),
             "--heads", str(m["heads"]), "--vocab", str(m["vocab"]),
             "--max-pos", "256", "--seed", str(m["seed"]),
             "--max-slots", "2", "--max-len", "128",
             "--cache-layout", "paged", "--block-size", "8",
             "--chunk-tokens", "32", "--compile-cache", cache_dir]
    rows = {"backend": platform}
    try:
        for cell in ("cold", "warm"):
            pw = spawn_worker_async("decode", extra_args=flags,
                                    timeout=600)
            try:
                while pw.poll() is None:
                    _time.sleep(0.1)
                if pw.addr is None:
                    raise RuntimeError(
                        f"{cell} worker died before READY: {pw.error}")
                rows[cell] = {"ready_ms": round(pw.ready_ms, 1),
                              "spawn_wall_s": round(pw.age_s, 3)}
            finally:
                shutdown_worker(pw.proc)
        try:
            with open(os.path.join(cache_dir, "manifest.json")) as f:
                rows["cache_entries"] = len(json.load(f))
        except (OSError, ValueError):
            rows["cache_entries"] = 0
        ratio = (rows["warm"]["ready_ms"]
                 / max(rows["cold"]["ready_ms"], 1e-9))
        rows["warm_over_cold"] = round(ratio, 4)
        rows["gate_warm_le_0p4x_cold"] = ratio <= 0.4
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return rows


def bench_resnet50(on_tpu):
    from apex_tpu.models.resnet import make_resnet_train_step, resnet50

    if on_tpu:
        # b256 measured best on v5e (b64: 1.9k, b128: 2.3k, b256: 2.4k imgs/s)
        batch, iters, hw = 256, 10, 224
        # MLPerf-style space-to-depth stem (models/resnet.py:132): the
        # 7x7x3 stem wastes the MXU's 128-deep input channels; the
        # equivalent 4x4x12 conv on the 2x2 space-to-depth input is the
        # layout the chip wants
        model = resnet50(space_to_depth_stem=True)
    else:
        from apex_tpu.models.resnet import resnet18
        batch, iters, hw = 4, 2, 64
        model = resnet18(num_classes=16)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, hw, hw, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 16, (batch,)), jnp.int32)

    init, step = make_resnet_train_step(
        model, fused_adam(lr=1e-3), "O2", image_shape=(hw, hw, 3))
    state, stats = init(jax.random.PRNGKey(0))

    def one(carry):
        s, st = carry[:2] if carry else (state, stats)
        s, st, m = step(s, st, images, labels)
        return s, st, m["loss"]

    sec = _time_fn(one, iters=iters, name="resnet50")
    imgs_per_s = batch / sec
    # RN50 train ≈ 3 × fwd (4.1 GFLOP/img at 224²) — standard accounting
    mfu = (imgs_per_s * 3 * 4.1e9 / _chip_peak_flops()) if on_tpu else 0.0
    return {
        "imgs_per_sec_per_chip": round(imgs_per_s, 1),
        "step_ms": round(sec * 1e3, 2),
        "mfu": round(mfu, 4),
        "batch": batch,
    }


def bench_bert(on_tpu, seq=512):
    if on_tpu:
        # round 3: s512 (the phase-2 pretraining length where attention
        # cost actually bites); b8 keeps the same 4096
        # tokens/step as the old b32xs128 row
        batch, iters = (8, 10) if seq == 512 else (32, 10)
        cfg = bert_large(max_position_embeddings=seq, remat=False)
    else:
        batch, seq, iters = 2, 64, 2
        cfg = bert_large(num_layers=2, hidden_size=256,
                         num_attention_heads=4, vocab_size=8192,
                         max_position_embeddings=seq)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    mlm = jnp.asarray(
        np.where(rng.rand(batch, seq) < 0.15,
                 rng.randint(0, cfg.vocab_size, (batch, seq)), -1),
        jnp.int32)
    nsp = jnp.asarray(rng.randint(0, 2, (batch,)), jnp.int32)
    tt = jnp.zeros((batch, seq), jnp.int32)
    mask = jnp.zeros((batch, seq), bool)

    init, step = make_bert_train_step(
        cfg, fused_lamb(lr=1e-4, weight_decay=0.01), "O2")
    state = init(jax.random.PRNGKey(0))
    n_params = _param_count(state.master_params)

    def one(carry):
        s = carry[0] if carry else state
        s, m = step(s, tokens, mlm, nsp, tt, mask)
        return s, m["loss"]

    sec = _time_fn(one, iters=iters, name="bert_large")
    tokens_per_s = batch * seq / sec
    flops_per_tok = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = tokens_per_s * flops_per_tok / _chip_peak_flops()
    return {
        "tokens_per_sec_per_chip": round(tokens_per_s, 1),
        "step_ms": round(sec * 1e3, 2),
        "mfu": round(mfu, 4),
        "params": n_params,
        "batch": batch, "seq": seq,
    }


def bench_transducer(on_tpu):
    from apex_tpu.contrib.transducer import transducer_joint, transducer_loss

    if on_tpu:
        B, T, U, H, K, iters = 16, 200, 40, 512, 128, 20
    else:
        B, T, U, H, K, iters = 2, 20, 8, 64, 32, 2
    rng = np.random.RandomState(0)
    f = jnp.asarray(rng.randn(B, T, H), jnp.float32)
    g = jnp.asarray(rng.randn(B, U, H), jnp.float32)
    w = jnp.asarray(rng.randn(H, K) * 0.05, jnp.float32)
    f_len = jnp.full((B,), T, jnp.int32)
    y_len = jnp.full((B,), U - 1, jnp.int32)
    label = jnp.asarray(rng.randint(1, K, (B, U - 1)), jnp.int32)

    @jax.jit
    def train(f, g, w):
        def loss_fn(w):
            h = transducer_joint(f, g, f_len, y_len + 1, relu=True)
            logits = h @ w
            return jnp.mean(transducer_loss(
                logits, label, f_len, y_len))
        l, gw = jax.value_and_grad(loss_fn)(w)
        return l, w - 1e-3 * gw

    def one(carry):
        ww = carry[1] if carry else w
        l, ww = train(f, g, ww)
        return l, ww

    sec = _time_fn(one, iters=iters, name="transducer")
    return {
        "steps_per_sec": round(1.0 / sec, 2),
        "step_ms": round(sec * 1e3, 2),
        "shape": [B, T, U, H, K],
    }


def bench_gpt_moe(on_tpu):
    """GPT-MoE (Switch FFN, 8 experts) — the beyond-reference model
    family; tok/s at matched active-params-per-token vs the dense 125M
    is not apples-to-apples, so this row reports absolute throughput."""
    from apex_tpu.models.config import TransformerConfig

    if on_tpu:
        batch, seq, iters = 8, 512, 10
        cfg = TransformerConfig(
            num_layers=12, hidden_size=768, num_attention_heads=12,
            vocab_size=50304, max_position_embeddings=seq,
            num_experts=8, remat=False, scan_layers=False)
    else:
        batch, seq, iters = 2, 64, 2
        cfg = TransformerConfig(
            num_layers=2, hidden_size=128, num_attention_heads=4,
            vocab_size=1024, max_position_embeddings=seq,
            num_experts=4, remat=False)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = init(jax.random.PRNGKey(0))
    n_params = _param_count(state.master_params)

    def one(carry):
        s = carry[0] if carry else state
        s, m = step(s, tokens, labels)
        return s, m["loss"]

    sec = _time_fn(one, iters=iters, name="gpt_moe")
    return {
        "tokens_per_sec_per_chip": round(batch * seq / sec, 1),
        "step_ms": round(sec * 1e3, 2),
        "params_total": n_params,
        "num_experts": cfg.num_experts,
        "batch": batch, "seq": seq,
    }


def bench_mlp_adam(on_tpu):
    """FusedAdam vs unfused optax Adam on the examples/simple MLP — the
    BASELINE.json north-star 'FusedAdam within 5% of torch Adam'."""
    import optax
    from apex_tpu.amp.frontend import make_train_step

    d, layers = (2048, 4) if on_tpu else (256, 2)
    rng = np.random.RandomState(0)
    params = {
        f"w{i}": jnp.asarray(rng.randn(d, d) * 0.02, jnp.float32)
        for i in range(layers)
    }
    x = jnp.asarray(rng.randn(64, d), jnp.float32)

    def loss_fn(p, x):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"].astype(h.dtype))
        return jnp.mean(h ** 2)

    results = {}
    for name, tx in (("fused", fused_adam(lr=1e-3)),
                     ("unfused", optax.adam(1e-3))):
        init, raw_step = make_train_step(loss_fn, tx, "O1")
        step = jax.jit(raw_step)   # time the compiled step, not dispatch
        state = init(params)

        def one(carry, step=step, state=state):
            s = carry[0] if carry else state
            s, m = step(s, x)
            return s, m["loss"]

        results[name] = _time_fn(one, iters=20 if on_tpu else 2,
                                 name=f"mlp_adam_{name}")
    return {
        "fused_step_ms": round(results["fused"] * 1e3, 3),
        "unfused_step_ms": round(results["unfused"] * 1e3, 3),
        "fused_over_unfused": round(
            results["fused"] / results["unfused"], 3),
    }


def bench_grad_comm(on_tpu, wire_dtypes=("fp32", "bf16", "int8")):
    """Wire-dtype ablation for the compressed gradient collectives
    (``--grad-comm``): the GPT tiny/125M geometry trained through
    ``make_ddp_train_step`` over a dp mesh of every visible device, one
    row per wire dtype, with the trace-time compressed-byte counters
    alongside tokens/s.  On a 1-chip window dp=1 makes the collective a
    no-op — the row exists so the next multi-chip window can run
    ``python bench.py --grad-comm fp32,bf16,int8`` and read the
    crossover directly."""
    from apex_tpu.models.transformer_lm import gpt_loss
    from apex_tpu.observability import metrics as _telemetry
    from apex_tpu.parallel.distributed import make_ddp_train_step
    from apex_tpu.parallel.mesh import create_mesh

    ndev = len(jax.devices())
    if on_tpu:
        batch, seq, iters = 8 * ndev, 1024, 10
        cfg = gpt_125m(max_position_embeddings=seq, remat=False,
                       scan_layers=False, fused_head_ce=True)
    else:
        batch, seq, iters = 2 * ndev, 128, 2
        cfg = gpt_125m(num_layers=2, hidden_size=256,
                       num_attention_heads=4, vocab_size=8192,
                       max_position_embeddings=seq)
    mesh = create_mesh(dp=ndev)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    from apex_tpu.models.gpt import init_gpt_params

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, t, l):
        return gpt_loss(p, t, l, cfg, None)

    rows = {}
    for wire in wire_dtypes:
        init, step = make_ddp_train_step(
            loss_fn, fused_adam(lr=1e-4), "O2", mesh,
            batch_axes=2, grad_comm=wire)
        state = init(params)
        reg = _telemetry.registry()
        base = (reg.counter("collectives.compressed.bytes").value,
                reg.counter("collectives.compressed.raw_bytes").value
                ) if reg is not None else (0, 0)

        def one(carry, step=step, state=state):
            s = carry[0] if carry else state
            s, m = step(s, tokens, labels)
            return s, m["loss"]

        sec = _time_fn(one, iters=iters, name=f"gpt_ddp_comm_{wire}")
        row = {
            "tokens_per_sec": round(batch * seq / sec, 1),
            "step_ms": round(sec * 1e3, 2),
            "dp": ndev,
        }
        if reg is not None:
            row["wire_bytes_per_trace"] = int(
                reg.counter("collectives.compressed.bytes").value - base[0])
            row["raw_bytes_per_trace"] = int(
                reg.counter("collectives.compressed.raw_bytes").value
                - base[1])
        rows[wire] = row
        del state
    return rows


def bench_tp_overlap(on_tpu):
    """Off/on ablation for the ring collective-matmul TP overlap
    (``--tp-overlap``): the GPT geometry trained through
    ``make_gpt_train_step`` over a (dp, tp) mesh of every visible
    device, one row per ``overlap_comm`` setting, with the trace-time
    ``collectives.ring.*`` counters alongside tokens/s.  On a 1-chip
    window tp=1 makes the ring inapplicable (calls stay 0) — the rows
    exist so the next multi-chip window can run
    ``python bench.py --tp-overlap`` and read the crossover directly."""
    import math

    from apex_tpu.observability import metrics as _telemetry
    from apex_tpu.parallel.mesh import create_mesh

    ndev = len(jax.devices())
    if on_tpu:
        batch, seq, iters = 8, 1024, 10
        cfg = gpt_125m(max_position_embeddings=seq, remat=False,
                       scan_layers=False, fused_head_ce=True)
    else:
        batch, seq, iters = 2, 128, 2
        cfg = gpt_125m(num_layers=2, hidden_size=256,
                       num_attention_heads=4, vocab_size=8192,
                       max_position_embeddings=seq)
    # tp must divide the head count; the rest of the devices go to dp
    tp = math.gcd(ndev, cfg.num_attention_heads)
    dp = ndev // tp
    mesh = create_mesh(dp=dp, tp=tp)
    batch = batch * dp
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)

    rows = {}
    for name, overlap in (("off", False), ("on", True)):
        init, step = make_gpt_train_step(
            cfg, fused_adam(lr=1e-4), "O2", mesh, overlap_comm=overlap)
        state = init(jax.random.PRNGKey(0))
        reg = _telemetry.registry()
        base = ((reg.counter("collectives.ring.calls").value,
                 reg.counter("collectives.ring.hops").value,
                 reg.counter("collectives.ring.bytes").value)
                if reg is not None else (0, 0, 0))

        def one(carry, step=step, state=state):
            s = carry[0] if carry else state
            s, m = step(s, tokens, labels)
            return s, m["loss"]

        sec = _time_fn(one, iters=iters, name=f"gpt_tp_overlap_{name}")
        row = {
            "tokens_per_sec": round(batch * seq / sec, 1),
            "step_ms": round(sec * 1e3, 2),
            "tp": tp, "dp": dp,
        }
        if reg is not None:
            row["ring_calls_per_trace"] = int(
                reg.counter("collectives.ring.calls").value - base[0])
            row["ring_hops_per_trace"] = int(
                reg.counter("collectives.ring.hops").value - base[1])
            row["ring_bytes_per_trace"] = int(
                reg.counter("collectives.ring.bytes").value - base[2])
        rows[name] = row
        del state
    if "off" in rows and "on" in rows and rows["off"]["step_ms"]:
        rows["on_over_off"] = round(
            rows["on"]["step_ms"] / rows["off"]["step_ms"], 3)
    return rows


def bench_moe_ablation(on_tpu):
    """Routing x wire-dtype x overlap ablation for the expert-parallel
    MoE fast path (``--moe``, ROADMAP item 5): the GPT-MoE geometry
    trained through ``make_gpt_train_step`` over an (ep, dp) mesh of
    every visible device — one row per (routing, moe_comm, overlap_comm)
    combination with the trace-time ``moe.*`` dispatch/ring counters
    alongside tokens/s — plus the *dense twin at matched active params
    per token* (same hidden/ffn/layers, no experts), the headline
    comparison: a top-1 MoE moves the same per-token FLOPs as its dense
    twin, so ragged tokens/s over dense tokens/s is the routing +
    dispatch overhead the fast path exists to shrink.  On a 1-chip
    window ep=1 keeps the island inapplicable (dispatch bytes stay 0) —
    the rows exist so the next multi-chip window can run
    ``python bench.py --moe`` and read the crossover directly.

    Also sets the ``moe.expert_load_max``/``moe.expert_load_mean``
    gauges host-side from a routing probe (``MoEOutput.expert_load``),
    the load-imbalance signal ``tools/telemetry_report.py``'s MoE
    summary reads."""
    import math

    from apex_tpu.observability import metrics as _telemetry
    from apex_tpu.parallel.mesh import create_mesh

    ndev = len(jax.devices())
    if on_tpu:
        batch, seq, iters, E = 8, 512, 10, 8
        dims = dict(num_layers=12, hidden_size=768,
                    num_attention_heads=12, vocab_size=50304,
                    max_position_embeddings=seq, remat=False,
                    scan_layers=False)
    else:
        batch, seq, iters, E = 2, 64, 2, 4
        dims = dict(num_layers=2, hidden_size=128,
                    num_attention_heads=4, vocab_size=1024,
                    max_position_embeddings=seq, remat=False)
    ep = math.gcd(ndev, E)
    dp = ndev // ep
    # a 1-device window gets the meshless step (the island then falls
    # back to the local ragged math — rows still carry their counters)
    mesh = create_mesh(dp=dp, ep=ep) if ndev > 1 else None
    batch = batch * dp
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, dims["vocab_size"], (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, dims["vocab_size"], (batch, seq)),
                         jnp.int32)

    def run_row(name, cfg, overlap=None):
        init, step = make_gpt_train_step(
            cfg, fused_adam(lr=1e-4), "O2", mesh, overlap_comm=overlap)
        state = init(jax.random.PRNGKey(0))
        reg = _telemetry.registry()
        base = (tuple(reg.counter(f"moe.{c}").value for c in
                      ("dispatch_bytes", "dispatch_raw_bytes",
                       "ring_calls", "ring_hops"))
                if reg is not None else (0, 0, 0, 0))

        def one(carry, step=step, state=state):
            s = carry[0] if carry else state
            s, m = step(s, tokens, labels)
            return s, m["loss"]

        sec = _time_fn(one, iters=iters, name=f"gpt_moe_{name}")
        row = {
            "tokens_per_sec": round(batch * seq / sec, 1),
            "step_ms": round(sec * 1e3, 2),
            "ep": ep, "dp": dp,
        }
        if reg is not None:
            now = tuple(reg.counter(f"moe.{c}").value for c in
                        ("dispatch_bytes", "dispatch_raw_bytes",
                         "ring_calls", "ring_hops"))
            row.update(
                dispatch_bytes_per_trace=int(now[0] - base[0]),
                dispatch_raw_bytes_per_trace=int(now[1] - base[1]),
                ring_calls_per_trace=int(now[2] - base[2]),
                ring_hops_per_trace=int(now[3] - base[3]),
            )
        del state
        return row

    from apex_tpu.models.config import TransformerConfig

    def safe_row(rows, key, *args, **kw):
        try:
            rows[key] = run_row(*args, **kw)
        except Exception as e:        # keep the other ablation rows alive
            rows[key] = {"error": f"{type(e).__name__}: {e}"[:200]}

    rows = {}
    safe_row(rows, "dense", "dense", TransformerConfig(**dims))
    safe_row(rows, "capacity", "capacity",
             TransformerConfig(num_experts=E, **dims))
    for wire in ("fp32", "bf16", "int8"):
        for ov_name, ov in (("off", False), ("on", True)):
            safe_row(
                rows, f"ragged_{wire}_overlap_{ov_name}",
                f"ragged_{wire}_{ov_name}",
                TransformerConfig(num_experts=E, moe_routing="ragged",
                                  moe_comm=wire, **dims),
                overlap=ov)

    # expert-load imbalance gauges from a routing probe: the data-
    # dependent load cannot ride trace-time counters, so bench samples
    # it host-side from MoEOutput.expert_load (no-op when telemetry is
    # unconfigured — module-level gauge helpers fast-path)
    from apex_tpu.transformer.moe import init_moe_params, switch_moe_mlp

    h = dims["hidden_size"]
    probe = switch_moe_mlp(
        init_moe_params(jax.random.PRNGKey(1), h, 4 * h, E),
        jnp.asarray(rng.randn(2, seq, h) * 0.5, jnp.float32),
        ep_axis=None, routing="ragged")
    load = np.asarray(probe.expert_load, np.float64)
    _telemetry.gauge("moe.expert_load_max").set(float(load.max()))
    _telemetry.gauge("moe.expert_load_mean").set(float(load.mean()))
    rows["expert_load"] = {
        "max": float(load.max()), "mean": float(load.mean()),
        "imbalance": round(float(load.max() / max(load.mean(), 1e-9)),
                           3),
    }

    # the headline: MoE tokens/s vs dense at matched active params
    dense_tps = rows["dense"].get("tokens_per_sec", 0.0)
    for key in ("capacity", "ragged_fp32_overlap_off"):
        tps = rows.get(key, {}).get("tokens_per_sec", 0.0)
        if dense_tps and tps:
            rows[f"{key}_over_dense_matched_active"] = round(
                tps / dense_tps, 3)
    return rows


# the inference rows, shared by the full matrix and --decode so the two
# run modes can never report differently-configured rows under one name
_DECODE_ROWS = (
    ("gpt2_125m_decode", bench_decode),
    ("gpt2_125m_gqa4_decode",
     lambda t, **kw: bench_decode(t, query_groups=4, **kw)),
)


def bench_checkpoint(on_tpu, save_every=None):
    """Async sharded-checkpoint overhead on the steady-state train step
    (ISSUE 11 acceptance: < 5% of step time).

    Three timings on the same GPT geometry: the plain AMP-O2 step
    (``ckpt off``), the same step with an ``AsyncCheckpointer.save``
    issued every ``save_every`` timed iterations (the device→host copy
    + manifest commit overlap the following steps), and one
    synchronous ``save_sharded`` for contrast (what the loop would pay
    if it blocked).  The row carries the saver's own telemetry — save
    ms (background), blocking ms (what the loop thread actually paid),
    bytes, overlap ratio — plus ``overhead_frac`` and the
    ``overhead_ok`` verdict against the 5% gate.
    """
    import shutil
    import tempfile
    import time as _time

    from apex_tpu.checkpoint import AsyncCheckpointer, save_sharded

    if on_tpu:
        batch, seq, iters = 16, 1024, 20
        save_every = save_every or 4
        cfg = gpt_125m(max_position_embeddings=seq, remat=False,
                       scan_layers=False, fused_head_ce=True)
    else:
        # CPU smoke: a longer step than the other smoke rows, on
        # purpose — the writer thread shares this host's few cores
        # with XLA (on a chip the step runs off-host and the loop
        # thread is idle), so the overhead ratio is only meaningful
        # when the step is long enough to amortize one snapshot the
        # way a real training step would; the sparser cadence matches
        # (a 900 ms smoke step checkpointed every 8 steps moves the
        # same bytes/second as a chip step every 4)
        batch, seq, iters = 4, 256, 16
        save_every = save_every or 8
        cfg = gpt_125m(num_layers=2, hidden_size=256,
                       num_attention_heads=4, vocab_size=8192,
                       max_position_embeddings=seq)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")

    # each timed run owns a fresh state: the step donates its input,
    # so a state threaded through one timer is dead for the next
    def make_one(state0, on_step=None):
        def one(carry):
            s = carry[0] if carry else state0
            s, m = step(s, tokens, labels)
            if on_step is not None:
                on_step(s)
            return s, m["loss"]

        return one

    state0 = init(jax.random.PRNGKey(0))
    n_params = _param_count(state0.master_params)
    base_s = _time_fn(make_one(state0), iters=iters, name="ckpt_off")
    del state0

    ckpt_dir = tempfile.mkdtemp(prefix="apex_bench_ckpt_")
    try:
        saver = AsyncCheckpointer(ckpt_dir, keep=2)
        counter = {"i": 0}

        def maybe_save(s):
            counter["i"] += 1
            if counter["i"] % save_every == 0:
                saver.save(counter["i"], s)

        # warmup covers one full save interval so the snapshot-copy jit
        # compile lands in warmup, not the timed window
        timer = StepTimer("ckpt_async", warmup=save_every, iters=iters)
        ckpt_s = timer.time(
            make_one(init(jax.random.PRNGKey(0)), on_step=maybe_save))
        saver.wait()
        last = saver.last_result
        saver.close()

        final_state = timer.last[0]
        t0 = _time.perf_counter()
        save_sharded(ckpt_dir, 999999, final_state)
        sync_s = _time.perf_counter() - t0
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    overhead = ckpt_s / base_s - 1.0
    out = {
        "step_ms_ckpt_off": round(base_s * 1e3, 2),
        "step_ms_ckpt_async": round(ckpt_s * 1e3, 2),
        "overhead_frac": round(overhead, 4),
        "overhead_ok": bool(overhead < 0.05),
        "save_every_steps": save_every,
        "sync_save_ms": round(sync_s * 1e3, 2),
        "params": n_params, "batch": batch, "seq": seq,
    }
    if last is not None:
        out.update({
            "save_ms": round(last.save_ms, 2),
            "blocking_ms": round(last.blocking_ms, 3),
            "overlap_ratio": round(last.overlap_ratio, 4),
            "checkpoint_bytes": last.bytes,
        })
    return out


def _require_platform(cpu_smoke: bool) -> str:
    """The platform this run measures on.  A measurement path that
    finds no chip fails: without ``--cpu-smoke`` anything but a TPU
    ends the run non-zero, and ``--cpu-smoke`` (toy sizes, control flow
    only — its rates are never device numbers) refuses a TPU."""
    platform = jax.devices()[0].platform
    if cpu_smoke == (platform == "tpu"):
        raise SystemExit(
            f"bench: JAX reports platform {platform!r}; "
            + ("--cpu-smoke runs on the CPU only" if cpu_smoke else
               "the benchmark needs a TPU (pass --cpu-smoke with "
               "JAX_PLATFORMS=cpu for the toy-size control-flow run)"))
    return platform


def main():
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--cpu-smoke", action="store_true",
        help="run the toy-size CPU rows (control flow only; never a "
             "device number) instead of failing for want of a TPU")
    parser.add_argument(
        "--grad-comm", default=None, metavar="DTYPES",
        help="comma list of gradient wire dtypes (fp32,bf16,int8): run "
             "ONLY the compressed-collective ablation rows "
             "(bench_grad_comm) instead of the full matrix")
    parser.add_argument(
        "--tp-overlap", action="store_true",
        help="run ONLY the ring collective-matmul TP-overlap ablation "
             "rows (bench_tp_overlap, overlap_comm off vs on) instead "
             "of the full matrix")
    parser.add_argument(
        "--moe", action="store_true",
        help="run ONLY the expert-parallel MoE ablation rows "
             "(bench_moe_ablation: routing x wire dtype x overlap, "
             "plus the dense twin at matched active params — the "
             "headline MoE-vs-dense row) instead of the full matrix")
    parser.add_argument(
        "--ckpt", action="store_true",
        help="run ONLY the async-checkpoint overhead row "
             "(bench_checkpoint: steady-state step time with the "
             "sharded AsyncCheckpointer saving inside the timed "
             "window vs without — the ISSUE 11 <5%% overhead gate) "
             "instead of the full matrix")
    parser.add_argument(
        "--decode", action="store_true",
        help="run ONLY the inference rows (prefill/decode split + GQA "
             "variant + the continuous-batching serving mixes) instead "
             "of the full matrix")
    parser.add_argument(
        "--cache-layout", default="contiguous", metavar="LAYOUTS",
        help="comma list of KV cache layouts (contiguous, paged) for "
             "the --decode rows; more than one also emits the "
             "matched-HBM cache_layout_ablation row (ISSUE 6)")
    parser.add_argument(
        "--serve-trace", action="store_true",
        help="run ONLY the cluster serve-trace rows (ISSUE 9): one "
             "bursty open-loop arrival trace replayed against the "
             "single-process engine AND the two-process "
             "prefill/decode disaggregated topology (real sockets, "
             "KV handoff over the wire) on this host, with per-class "
             "TTFT/e2e percentiles + goodput per topology.  "
             "CPU-pinned: this measures topology cost under "
             "identical numerics, not chip rates.  --cache-layout "
             "picks the decode pool layout(s)")
    parser.add_argument(
        "--controller", action="store_true",
        help="with --serve-trace: run ONLY the ISSUE 15 elastic-"
             "controller ablation instead of the disaggregation rows "
             "— the diurnal + flash-crowd trace, controller on/off x "
             "chunked prefill on/off (goodput, p95 TTFT/TPOT, "
             "chip-seconds, zero-lost drains), plus the chunked-"
             "prefill starvation gate (one long prompt co-resident: "
             "decode TPOT p95 with chunking <= 2x the no-long-prompt "
             "baseline)")
    parser.add_argument(
        "--wire-dtype", default="raw", metavar="DTYPES",
        help="comma list of KV handoff wire formats (raw, bf16, int8) "
             "for the --serve-trace rows; raw is the token-identity "
             "form, bf16/int8 trade parity for wire bytes")
    parser.add_argument(
        "--cache-dtype", default=None, metavar="DTYPES",
        help="comma list of paged-pool at-rest forms (bf16, int8): "
             "with --decode, run ONLY the quantized-serving ablation "
             "(bench_cache_dtype_ablation — byte-matched admission "
             "concurrency + preemption rows, the spec-decode "
             "accept-rate delta gate, and the weight-only quantized "
             "matmul rows) instead of the full inference matrix "
             "(ISSUE 14)")
    parser.add_argument(
        "--decode-fused", default=None, metavar="MODES",
        help="comma list of off, on: with --decode, run ONLY the "
             "fused decode-layer ablation (bench_decode_fused — "
             "per-token ms per route plus the per-layer op/launch "
             "structural ledger; ISSUE 17).  Off-TPU the kernel is "
             "timed under the Pallas interpreter, so wall-clock there "
             "is not a fusion win — the op/launch deltas are the "
             "honest CPU column")
    parser.add_argument(
        "--cold-start", action="store_true",
        help="run ONLY the worker cold-vs-warm start row (ISSUE 17): "
             "spawn a decode worker twice against one compile-cache "
             "dir — empty (cold: trace + AOT-compile the bucket "
             "ladder) then primed (warm: deserialize) — and report "
             "the worker-internal READY-ms ratio (gate: warm <= 0.4x "
             "cold).  CPU-pinned like --serve-trace (the spawned "
             "worker could not attach an already-claimed chip)")
    parser.add_argument(
        "--host-tier", default=None, metavar="MODES",
        help="comma list of off, on: with --decode, run ONLY the "
             "hierarchical KV cache ablation (bench_host_tier_ablation "
             "— the preemption starvation mix, resume-from-host-tier "
             "vs prefill-replay overhead + greedy token identity, and "
             "the shared-system-prompt trace where cold prefixes page "
             "back in from host DRAM; ISSUE 18) instead of the full "
             "inference matrix")
    parser.add_argument(
        "--adapters", default=None, metavar="COUNTS",
        help="comma list of distinct-adapter counts (e.g. 1,8,64): "
             "with --decode, run ONLY the multi-tenant LoRA serving "
             "ablation (bench_adapter_ablation — heterogeneous "
             "batched decode via ragged grouped matmul vs the merged-"
             "weights engine at batch parity vs the sequential per-"
             "adapter baseline, plus greedy token identity against "
             "the merged reference and the adapter-pool churn ledger; "
             "ISSUE 20) instead of the full inference matrix")
    parser.add_argument(
        "--spec", default=None, metavar="SPECS",
        help="comma list of speculative-decoding modes (off, ngram): "
             "with --decode, run ONLY the spec ablation rows "
             "(bench_spec_ablation — accept-rate sweep per cache "
             "layout, stderr table with the accept-rate column) "
             "instead of the full inference matrix (ISSUE 8)")
    args = parser.parse_args()
    cache_dtypes = None
    if args.cache_dtype is not None:
        cache_dtypes = tuple(
            w.strip() for w in args.cache_dtype.split(",") if w.strip())
        bad = [w for w in cache_dtypes if w not in ("bf16", "int8")]
        if bad or not cache_dtypes:
            parser.error(f"--cache-dtype {args.cache_dtype!r}: expected "
                         "a comma list of bf16, int8")
        if not args.decode:
            parser.error("--cache-dtype only applies to the --decode "
                         "rows")
        if args.spec is not None:
            parser.error("--cache-dtype and --spec are separate "
                         "ablations; run them as separate invocations")
    fused_modes = None
    if args.decode_fused is not None:
        fused_modes = tuple(
            m.strip() for m in args.decode_fused.split(",")
            if m.strip())
        bad = [m for m in fused_modes if m not in ("off", "on")]
        if bad or not fused_modes:
            parser.error(f"--decode-fused {args.decode_fused!r}: "
                         "expected a comma list of off, on")
        if not args.decode:
            parser.error("--decode-fused only applies to the --decode "
                         "rows")
        if args.spec is not None or args.cache_dtype is not None:
            parser.error("--decode-fused is its own ablation; run "
                         "--spec/--cache-dtype as separate "
                         "invocations")
    host_modes = None
    if args.host_tier is not None:
        host_modes = tuple(
            m.strip() for m in args.host_tier.split(",") if m.strip())
        bad = [m for m in host_modes if m not in ("off", "on")]
        if bad or not host_modes:
            parser.error(f"--host-tier {args.host_tier!r}: expected a "
                         "comma list of off, on")
        if not args.decode:
            parser.error("--host-tier only applies to the --decode "
                         "rows")
        if args.spec is not None or args.cache_dtype is not None:
            parser.error("--host-tier is its own ablation; run "
                         "--spec/--cache-dtype as separate "
                         "invocations")
    adapter_counts = None
    if args.adapters is not None:
        try:
            adapter_counts = tuple(
                int(c.strip()) for c in args.adapters.split(",")
                if c.strip())
        except ValueError:
            adapter_counts = ()
        if not adapter_counts or any(c < 1 for c in adapter_counts):
            parser.error(f"--adapters {args.adapters!r}: expected a "
                         "comma list of positive adapter counts "
                         "(e.g. 1,8,64)")
        if not args.decode:
            parser.error("--adapters only applies to the --decode "
                         "rows")
        if args.spec is not None or args.cache_dtype is not None:
            parser.error("--adapters is its own ablation; run "
                         "--spec/--cache-dtype as separate "
                         "invocations")
    spec_modes = None
    if args.spec is not None:
        spec_modes = tuple(
            s.strip() for s in args.spec.split(",") if s.strip())
        bad = [s for s in spec_modes if s not in ("off", "ngram")]
        if bad or not spec_modes:
            parser.error(f"--spec {args.spec!r}: expected a comma list "
                         "of off, ngram")
        if not args.decode:
            parser.error("--spec only applies to the --decode rows")
    layouts = tuple(
        l.strip() for l in args.cache_layout.split(",") if l.strip())
    bad = [l for l in layouts if l not in ("contiguous", "paged")]
    if bad or not layouts:
        parser.error(f"--cache-layout {args.cache_layout!r}: expected a "
                     "comma list of contiguous, paged")
    wire_dtypes = tuple(
        w.strip() for w in args.wire_dtype.split(",") if w.strip())
    bad = [w for w in wire_dtypes if w not in ("raw", "bf16", "int8")]
    if bad or not wire_dtypes:
        parser.error(f"--wire-dtype {args.wire_dtype!r}: expected a "
                     "comma list of raw, bf16, int8")
    if args.controller and not args.serve_trace:
        parser.error("--controller rides the serve-trace harness; "
                     "pass --serve-trace --controller")
    cpu_pinned = bool(args.serve_trace or args.cold_start)
    if cpu_pinned:
        # the topology rows are CPU-pinned BEFORE backend init, and say
        # so in their output ("cpu_pinned"): both topologies (and the
        # spawned worker processes, which inherit os.environ) must
        # share one platform or neither the latency comparison nor the
        # greedy token-identity pin means anything — and a child
        # process cannot attach to a chip this parent holds
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    # APEX_TPU_TELEMETRY=<path> streams every row's StepTimer span into
    # the shared JSONL schema alongside the headline JSON line
    # (APEX_TPU_TELEMETRY_TRACE=<path> adds the Perfetto timeline).
    configure_from_env()
    # recompile + HBM accounting rides EVERY bench run (standalone —
    # no telemetry required): the tracker counts backend compiles per
    # StepTimer label, and the "runtime" block below lands in the
    # BENCH JSON so published rows carry their compile counts and HBM
    # peaks.  An unexpected `<row>.retrace` entry = a compile in the
    # timed window = the row's number is compile-polluted.
    install_recompile_tracker()
    enable_compile_cache()
    platform = _require_platform(args.cpu_smoke or cpu_pinned)
    on_tpu = platform == "tpu"
    if args.ckpt:
        try:
            row = bench_checkpoint(on_tpu)
        except Exception as e:
            row = {"error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "train_ckpt_async_overhead",
            # headline: the fraction of step time async checkpointing
            # costs (the ISSUE 11 gate is < 0.05)
            "value": row.get("overhead_frac", 0.0),
            "unit": "frac",
            "backend": platform,
            # a row that ERRORED must not read as a 0.0-overhead pass
            # to the machine-readable caveat fields
            "skipped": (f"bench_checkpoint failed: {row['error']}"
                        if "error" in row else False),
            "details": {"checkpoint": row},
            "runtime": runtime_summary(),
        }))
        return
    if args.grad_comm:
        wires = tuple(
            w.strip() for w in args.grad_comm.split(",") if w.strip())
        if not wires:
            parser.error("--grad-comm needs at least one wire dtype "
                         "(fp32, bf16, int8)")
        rows = bench_grad_comm(on_tpu, wires)
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "gpt_ddp_grad_comm_ablation",
            "value": rows.get(wires[0], {}).get("tokens_per_sec", 0.0),
            "unit": "tokens/s",
            "backend": platform,
            "skipped": False,
            "details": rows,
            "runtime": runtime_summary(),
        }))
        return
    if args.moe:
        rows = bench_moe_ablation(on_tpu)
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "gpt_moe_ep_ablation",
            # headline: ragged MoE tokens/s (dense twin + the
            # matched-active-params ratio ride in the details)
            "value": rows.get("ragged_fp32_overlap_off", {}).get(
                "tokens_per_sec", 0.0),
            "unit": "tokens/s",
            "backend": platform,
            "skipped": False,
            "details": rows,
            "runtime": runtime_summary(),
        }))
        return
    if args.tp_overlap:
        rows = bench_tp_overlap(on_tpu)
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "gpt_tp_overlap_ablation",
            "value": rows.get("off", {}).get("tokens_per_sec", 0.0),
            "unit": "tokens/s",
            "backend": platform,
            "skipped": False,
            "details": rows,
            "runtime": runtime_summary(),
        }))
        return
    if args.cold_start:
        try:
            rows = bench_cold_vs_warm_start(platform=platform)
        except Exception as e:
            rows = {"error": f"{type(e).__name__}: {e}"[:200]}
        if "error" in rows:
            skipped = f"cold_vs_warm_start failed: {rows['error']}"
        else:
            skipped = False
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "worker_cold_vs_warm_start",
            "cpu_pinned": True,
            # headline: warm READY ms over cold READY ms (the ISSUE 17
            # gate is <= 0.4)
            "value": rows.get("warm_over_cold", 0.0),
            "unit": "x",
            "backend": platform,
            "skipped": skipped,
            "details": {"cold_vs_warm_start": rows},
            "runtime": runtime_summary(),
        }))
        return
    if args.serve_trace and args.controller:
        details = {}
        try:
            details["chunked_starvation"] = bench_chunked_starvation(
                platform=platform)
        except Exception as e:
            details["chunked_starvation"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
        try:
            details["controller_trace"] = bench_serve_trace_controller(
                platform=platform)
        except Exception as e:
            details["controller_trace"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
        # ISSUE 17: the deferred-attach vs blocking scale-up cells —
        # flash crowd at min provisioning, spawn-driven goodput
        # recovery without stalling the tick loop
        try:
            details["spawn_mode"] = bench_spawn_mode_ablation(
                platform=platform)
        except Exception as e:
            details["spawn_mode"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
        ct = details["controller_trace"]
        if "error" in ct:
            skipped = f"controller trace failed: {ct['error']}"
        elif "chip_seconds_saved_frac" not in ct:
            skipped = "controller cells incomplete: no chip-seconds " \
                      "comparison"
        else:
            skipped = False
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "serve_trace_controller",
            "cpu_pinned": True,
            # headline: the chip-second fraction the elastic loop
            # saved at >= static goodput over the diurnal window
            "value": ct.get("chip_seconds_saved_frac", 0.0),
            "unit": "frac",
            "backend": platform,
            "skipped": skipped,
            "details": details,
            "runtime": runtime_summary(),
        }))
        return
    if args.serve_trace:
        details = {}
        for layout in layouts:
            for wire in wire_dtypes:
                sfx = f"_{layout}_{wire}"
                try:
                    details["serve_trace" + sfx] = bench_serve_trace(
                        cache_layout=layout, wire_dtype=wire)
                except Exception as e:
                    details["serve_trace" + sfx] = {
                        "error": f"{type(e).__name__}: {e}"[:200]}
        head = details.get(
            f"serve_trace_{layouts[0]}_{wire_dtypes[0]}", {})
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "serve_trace_disaggregation",
            "cpu_pinned": True,
            "value": head.get("disaggregated", {}).get(
                "gen_tokens_per_sec", 0.0),
            "unit": "tokens/s",
            "backend": platform,
            "skipped": False,
            "details": details,
            "runtime": runtime_summary(),
        }))
        return
    if args.decode and fused_modes:
        try:
            rows = bench_decode_fused(on_tpu, fused_modes)
        except Exception as e:
            rows = {"error": f"{type(e).__name__}: {e}"[:200]}
        if "error" in rows:
            skipped = f"bench_decode_fused failed: {rows['error']}"
        elif not on_tpu:
            # CPU-smoke honesty: the kernel route timed under the
            # Pallas interpreter measures interpreter overhead — the
            # structural op/launch ledger is the portable signal here
            skipped = ("cpu smoke: kernel timed under the Pallas "
                       "interpreter; use layer_ops (op/launch deltas) "
                       "— ms columns are not fusion wins off-chip")
        else:
            skipped = False
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "gpt2_125m_decode_fused_ablation",
            # headline: fused-route decode rate (the off-route rate
            # and the structural ledger ride in the details)
            "value": rows.get("fused_on", {}).get(
                "decode_tokens_per_sec", 0.0),
            "unit": "tokens/s",
            "backend": platform,
            "skipped": skipped,
            "details": {"decode_fused_ablation": rows},
            "runtime": runtime_summary(),
        }))
        return
    if args.decode and host_modes:
        try:
            rows = bench_host_tier_ablation(platform=platform,
                                            modes=host_modes)
        except Exception as e:
            rows = {"error": f"{type(e).__name__}: {e}"[:200]}
        # a single-mode run measures no resume-vs-replay ratio: the
        # headline carries a machine-readable caveat rather than a
        # 0.0 that reads as "page-in is free"
        if "error" in rows:
            skipped = f"bench_host_tier failed: {rows['error']}"
        elif "resume_over_replay_overhead" not in rows:
            skipped = ("single-mode run: no resume-vs-replay ratio "
                       "(pass --host-tier off,on)")
        else:
            skipped = False
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "host_tier_kv_offload_ablation",
            # headline: preempt-overhead p95 with the tier on over
            # off — the ISSUE 18 gate is <= 1.0 (page-in resume beats
            # the prefill replay it displaces)
            "value": rows.get("resume_over_replay_overhead", 0.0),
            "unit": "x",
            "backend": platform,
            "skipped": skipped,
            "details": {"host_tier_ablation": rows},
            "runtime": runtime_summary(),
        }))
        return
    if args.decode and adapter_counts:
        try:
            rows = bench_adapter_ablation(platform=platform,
                                          counts=adapter_counts)
        except Exception as e:
            rows = {"error": f"{type(e).__name__}: {e}"[:200]}
        if "error" in rows:
            skipped = f"bench_adapter_ablation failed: {rows['error']}"
        elif not on_tpu:
            # CPU-smoke honesty: tokens/s off-chip are same-backend
            # ratios, not chip rates — batched_over_merged, the token-
            # identity column and the pool-churn ledger are the
            # portable signal
            skipped = ("cpu smoke: tokens/s are same-backend ratios, "
                       "not chip rates — use batched_over_merged + "
                       "token_identical + the pool ledger")
        else:
            skipped = False
        head = rows.get(f"adapters_{max(adapter_counts)}", {})
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "multi_tenant_lora_ablation",
            # headline: batched heterogeneous decode over the single-
            # merged-adapter engine at batch parity, at the largest
            # tenant count (the ISSUE 20 >= 0.8x gate)
            "value": head.get("batched_over_merged", 0.0),
            "unit": "x",
            "backend": platform,
            "skipped": skipped,
            "details": {"adapter_ablation": rows},
            "runtime": runtime_summary(),
        }))
        return
    if args.decode and cache_dtypes:
        try:
            rows = bench_cache_dtype_ablation(on_tpu, cache_dtypes,
                                              platform=platform)
        except Exception as e:
            rows = {"error": f"{type(e).__name__}: {e}"[:200]}
        _print_cache_dtype_table(rows)
        # a single-dtype run measures no multiple: the headline must
        # carry a machine-readable caveat, not a 0.0 that reads as a
        # catastrophic regression against the >= 1.8x gate
        if "error" in rows:
            skipped = f"bench_cache_dtype failed: {rows['error']}"
        elif "admitted_concurrency_multiple" not in rows:
            skipped = ("single-dtype run: no concurrency multiple "
                       "(pass --cache-dtype bf16,int8)")
        else:
            skipped = False
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "quantized_serving_cache_dtype_ablation",
            # headline: admitted concurrency at matched pool bytes,
            # int8 over bf16 (the >= 1.8x ISSUE 14 acceptance gate)
            "value": rows.get("admitted_concurrency_multiple", 0.0),
            "unit": "x",
            "backend": platform,
            "skipped": skipped,
            "details": {"cache_dtype_ablation": rows},
            "runtime": runtime_summary(),
        }))
        return
    if args.decode and spec_modes:
        details = {}
        for layout in layouts:
            sfx = "" if layout == "contiguous" else f"_{layout}"
            try:
                details["spec_ablation" + sfx] = bench_spec_ablation(
                    on_tpu, spec_modes, cache_layout=layout)
            except Exception as e:
                details["spec_ablation" + sfx] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
        _print_spec_table(details)
        head_sfx = "" if layouts[0] == "contiguous" else f"_{layouts[0]}"
        head = details.get("spec_ablation" + head_sfx, {})
        head_mode = "ngram" if "ngram" in spec_modes else spec_modes[0]
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "gpt2_125m_decode_spec_ablation",
            "value": head.get("repetition", {}).get(head_mode, {}).get(
                "decode_tokens_per_sec", 0.0),
            "unit": "tokens/s",
            "backend": platform,
            "skipped": False,
            "details": details,
            "runtime": runtime_summary(),
        }))
        return
    if args.decode:
        details = {}
        for layout in layouts:
            # the contiguous rows keep their BENCH-continuity names;
            # other layouts suffix (and every row body carries
            # "cache_layout") so trajectories never mix layouts
            sfx = "" if layout == "contiguous" else f"_{layout}"
            for name, fn in (
                *_DECODE_ROWS,
                ("serving_continuous_batching", bench_serving),
            ):
                try:
                    details[name + sfx] = fn(on_tpu, cache_layout=layout)
                except Exception as e:
                    details[name + sfx] = {
                        "error": f"{type(e).__name__}: {e}"[:200]}
        if len(layouts) > 1:
            try:
                details["cache_layout_ablation"] = (
                    bench_cache_layout_ablation(on_tpu, layouts))
            except Exception as e:
                details["cache_layout_ablation"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]}
        # headline = the first requested layout's decode row (a
        # paged-only run must not report 0.0 just because the
        # unsuffixed contiguous key is absent)
        head_sfx = "" if layouts[0] == "contiguous" else f"_{layouts[0]}"
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "metric": "gpt2_125m_decode_tokens_per_sec",
            "value": details.get("gpt2_125m_decode" + head_sfx, {}).get(
                "decode_tokens_per_sec", 0.0),
            "unit": "tokens/s",
            "backend": platform,
            "skipped": False,
            "details": details,
            "runtime": runtime_summary(),
        }))
        return
    details = {}
    for name, fn in (
        ("gpt2_125m", bench_gpt),
        ("gpt2_350m", lambda t: bench_gpt(t, size="350m")),
        ("gpt2_125m_gqa4",
         lambda t: bench_gpt(t, query_groups=4, baseline=False)),
        ("gpt2_125m_s8192_longctx", bench_gpt_longctx),
        ("gpt2_125m_s8192_cp_ring_vs_ulysses", bench_longctx_cp_compare),
        ("resnet50", bench_resnet50),
        ("bert_large", bench_bert),
        ("rnnt_transducer", bench_transducer),
        # BENCH-continuity decode rows stay in the matrix; the serving
        # mixes run only under --decode (measure_all's bench_decode
        # stage) so the campaign does not pay them twice
        *_DECODE_ROWS,
        ("gpt_moe_8e", bench_gpt_moe),
        ("mlp_fused_adam", bench_mlp_adam),
    ):
        try:
            details[name] = fn(on_tpu)
        except Exception as e:  # keep the headline alive
            details[name] = {"error": f"{type(e).__name__}: {e}"[:200]}

    gpt = details.get("gpt2_125m", {})
    print(json.dumps({
        "schema_version": SCHEMA_VERSION,
        "metric": _HEADLINE,
        "value": gpt.get("tokens_per_sec_per_chip", 0.0),
        "unit": "tokens/s",
        "backend": platform,
        "skipped": False,
        "vs_baseline": gpt.get("speedup_vs_fp32_unfused", 0.0),
        "details": details,
        # compile.{count,ms} per row label + HBM peak: a row whose
        # label shows a `.retrace` compile was polluted; a peak near
        # device capacity explains an MFU cliff (docs/observability.md)
        "runtime": runtime_summary(),
    }))


if __name__ == "__main__":
    main()
