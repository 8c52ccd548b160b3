"""Per-kernel perf ledger: fused kernels vs their XLA-composed equivalents.

The reference's value proposition is per-kernel speed ("optimized for
performance", /root/reference/README.md:3-6).  This microbenchmark times
every fused op in :mod:`apex_tpu.ops` against the plain jnp composition
XLA would produce (autodiff for backward) at the bench-matrix shapes, on
the real chip.  The measured winners justify each op's default backend
(KERNEL_BENCH.json holds the last table; not measured on today's code).

Methodology: each variant is chained through a `lax.fori_loop` *inside*
one jit (the output of iteration i feeds iteration i+1), so the reported
per-iteration time contains no host dispatch and no cross-iteration
parallelism.  For fwd+bwd, the chained value is the gradient (same shape
as the input).  Reported number = best of 5 timed calls / INNER.

Usage:  python bench_kernels.py [--json out]     (needs a TPU)
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

INNER = (64, 256, 1024)  # chained iteration counts; reported time is the
                         # least-squares slope over the points, which
                         # cancels the fixed host<->device round-trip
                         # per call and averages out its jitter
REPS = 5                 # timed outer calls per point; best is used


def _scalarize(tree):
    """Cheap on-device scalar depending on every leaf — only a float
    crosses to the host at sync time."""
    return sum(jnp.ravel(leaf)[0].astype(jnp.float32)
               for leaf in jax.tree_util.tree_leaves(tree))


def _best_of(run, args):
    out = run(*args)          # compile + warmup
    float(np.asarray(out))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = run(*args)
        float(np.asarray(out))
        best = min(best, time.perf_counter() - t0)
    return best


def _time(make_run, args, inner=None):
    points = inner or INNER
    times = [_best_of(make_run(n), args) for n in points]
    slope = np.polyfit(points, times, 1)[0]
    return max(float(slope), 1e-9)


def chain_fwd(op, *args, inner=None):
    """Time op(x, *rest) chained through x (op(x) must have x's shape)."""

    def make_run(n):
        @jax.jit
        def run(x, *rest):
            return _scalarize(jax.lax.fori_loop(
                0, n, lambda i, t: op(t, *rest), x))
        return run

    return _time(make_run, args, inner)


def chain_grad(op, argnums, *args, inner=None):
    """Time jax.grad(sum-of-op) chained through the differentiated args."""
    k = len(argnums)
    g = jax.grad(
        lambda *a: op(*a).astype(jnp.float32).sum(), argnums=argnums)

    def make_run(n):
        @jax.jit
        def run(*a):
            def body(i, diff):
                return g(*diff, *a[k:])

            return _scalarize(jax.lax.fori_loop(0, n, body, a[:k]))
        return run

    return _time(make_run, args, inner)


def _fmt(name, pallas_s, xla_s):
    ratio = pallas_s / xla_s
    win = "pallas" if ratio < 1.0 else "xla"
    print(f"  {name:<44} pallas {pallas_s*1e6:9.1f}us   "
          f"xla {xla_s*1e6:9.1f}us   ratio {ratio:5.3f}  -> {win}")
    return {"pallas_us": round(pallas_s * 1e6, 1),
            "xla_us": round(xla_s * 1e6, 1),
            "pallas_over_xla": round(ratio, 3), "winner": win}


def bench_flash_attention(results):
    from apex_tpu.ops.flash_attention import (
        causal_work_share, flash_attention, mha_reference)

    print("flash_attention (bf16, d=64)")
    rng = np.random.RandomState(0)
    for b, s, h, causal in ((8, 512, 12, True), (16, 1024, 12, True),
                            (4, 2048, 12, True), (8, 512, 12, False)):
        q = jnp.asarray(rng.randn(b, s, h, 64), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, s, h, 64), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, s, h, 64), jnp.bfloat16)
        tag = f"b{b}xs{s}{'_causal' if causal else ''}"
        # share of the score rectangle the kernels compute at this shape
        share = causal_work_share(s, s, causal)
        print(f"  {tag}: causal_work_share {share:.4f}")

        fa = functools.partial(flash_attention, causal=causal)
        ref = functools.partial(mha_reference, causal=causal)
        results[f"flash_fwd_{tag}"] = _fmt(
            f"fwd   {tag}", chain_fwd(fa, q, k, v, inner=(16, 48, 160)),
            chain_fwd(ref, q, k, v, inner=(16, 48, 160)))
        results[f"flash_fwdbwd_{tag}"] = _fmt(
            f"fwd+bwd {tag}",
            chain_grad(fa, (0, 1, 2), q, k, v, inner=(16, 48, 160)),
            chain_grad(ref, (0, 1, 2), q, k, v, inner=(16, 48, 160)))
        for row in (f"flash_fwd_{tag}", f"flash_fwdbwd_{tag}"):
            results[row]["causal_work_share"] = share


def bench_flash_gqa(results):
    """Grouped-K/V flash vs the repeat-then-flash composition a user
    would otherwise write (round-5 GQA-aware kernels): same math, but
    the repeated [b, s, n, d] K/V — written once and re-read by both
    kernel passes — never exists in HBM on the grouped path.  Ratio < 1
    is the measured form of the rep-x traffic claim."""
    from apex_tpu.ops.flash_attention import flash_attention

    print("flash_attention grouped K/V (GQA 12h -> g, bf16, d=64)")
    rng = np.random.RandomState(0)
    for b, s, h, g in ((16, 1024, 12, 4), (8, 512, 12, 4),
                       (16, 1024, 12, 1)):
        q = jnp.asarray(rng.randn(b, s, h, 64), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, s, g, 64), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, s, g, 64), jnp.bfloat16)
        tag = f"b{b}xs{s}_g{g}"
        rep = h // g

        fa = functools.partial(flash_attention, causal=True)

        def repeated(q, k, v, rep=rep):
            return fa(q, jnp.repeat(k, rep, axis=2),
                      jnp.repeat(v, rep, axis=2))

        results[f"flash_gqa_fwd_{tag}"] = _fmt(
            f"gqa fwd   {tag}", chain_fwd(fa, q, k, v, inner=(16, 48, 160)),
            chain_fwd(repeated, q, k, v, inner=(16, 48, 160)))
        results[f"flash_gqa_fwdbwd_{tag}"] = _fmt(
            f"gqa fwd+bwd {tag}",
            chain_grad(fa, (0, 1, 2), q, k, v, inner=(16, 48, 160)),
            chain_grad(repeated, (0, 1, 2), q, k, v, inner=(16, 48, 160)))


def bench_layer_norm(results):
    from apex_tpu.ops.layer_norm import (fused_layer_norm, fused_rms_norm,
                                         layer_norm_ref, rms_norm_ref)

    print("layer_norm / rms_norm")
    rng = np.random.RandomState(0)
    for rows, hidden, dtype in ((16384, 768, jnp.bfloat16),
                                (16384, 1024, jnp.bfloat16),
                                (16384, 768, jnp.float32)):
        x = jnp.asarray(rng.randn(rows, hidden), dtype)
        w = jnp.ones((hidden,), jnp.float32)
        b = jnp.zeros((hidden,), jnp.float32)
        tag = f"{rows}x{hidden}_{jnp.dtype(dtype).name}"

        ln = lambda x, w, b: fused_layer_norm(x, w, b)
        ref = lambda x, w, b: layer_norm_ref(x, w, b)
        results[f"ln_fwd_{tag}"] = _fmt(
            f"LN fwd   {tag}", chain_fwd(ln, x, w, b),
            chain_fwd(ref, x, w, b))
        results[f"ln_fwdbwd_{tag}"] = _fmt(
            f"LN fwd+bwd {tag}",
            chain_grad(ln, (0, 1, 2), x, w, b),
            chain_grad(ref, (0, 1, 2), x, w, b))

    x = jnp.asarray(rng.randn(16384, 768), jnp.bfloat16)
    w = jnp.ones((768,), jnp.float32)
    results["rms_fwdbwd_16384x768_bf16"] = _fmt(
        "RMS fwd+bwd 16384x768_bf16",
        chain_grad(lambda x, w: fused_rms_norm(x, w), (0, 1), x, w),
        chain_grad(lambda x, w: rms_norm_ref(x, w), (0, 1), x, w))


def bench_softmax(results):
    from apex_tpu.ops import softmax as sm

    print("scaled softmax (causal / plain)")
    rng = np.random.RandomState(0)
    for b, h, s in ((16, 12, 1024), (32, 16, 512)):
        x = jnp.asarray(rng.randn(b, h, s, s), jnp.bfloat16)
        tag = f"{b}x{h}x{s}x{s}"
        causal = lambda x: sm.scaled_upper_triang_masked_softmax(x, 0.125)
        causal_ref = lambda x: sm._softmax_fwd_ref(x, 0.125, None, True)
        results[f"softmax_causal_fwd_{tag}"] = _fmt(
            f"causal fwd {tag}", chain_fwd(causal, x),
            chain_fwd(causal_ref, x))
        results[f"softmax_causal_fwdbwd_{tag}"] = _fmt(
            f"causal fwd+bwd {tag}",
            chain_grad(causal, (0,), x),
            chain_grad(causal_ref, (0,), x))


def bench_xentropy(results):
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

    print("xentropy (fused lse-saving vs naive log_softmax)")
    rng = np.random.RandomState(0)
    rows, v = 16384, 50304
    logits = jnp.asarray(rng.randn(rows, v), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, v, (rows,)), jnp.int32)

    def naive(logits, labels):
        ls = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(ls, labels[..., None], axis=-1)[..., 0]
        return -picked

    fused = lambda lg, lb: softmax_cross_entropy_loss(lg, lb, 0.0, -100)
    results[f"xentropy_fwdbwd_{rows}x{v}"] = _fmt(
        f"fwd+bwd {rows}x{v}",
        chain_grad(fused, (0,), logits, labels),
        chain_grad(naive, (0,), logits, labels))


def bench_swiglu(results):
    from apex_tpu.ops.swiglu import bias_swiglu_ref, fused_bias_swiglu

    print("bias_swiglu (custom-vjp recompute vs autodiff)")
    rng = np.random.RandomState(0)
    rows, f2 = 16384, 6144
    x = jnp.asarray(rng.randn(rows, f2), jnp.bfloat16)
    b = jnp.asarray(rng.randn(f2) * 0.01, jnp.float32)
    results[f"swiglu_fwdbwd_{rows}x{f2}"] = _fmt(
        f"fwd+bwd {rows}x{f2}",
        chain_grad(fused_bias_swiglu, (0, 1), x, b),
        chain_grad(bias_swiglu_ref, (0, 1), x, b))


def bench_rope(results):
    from apex_tpu.ops.rope import fused_apply_rotary_pos_emb

    print("rope (custom-vjp adjoint vs autodiff)")
    rng = np.random.RandomState(0)
    s, b, h, d = 1024, 16, 12, 64
    t = jnp.asarray(rng.randn(s, b, h, d), jnp.bfloat16)
    freqs = jnp.asarray(rng.randn(s, 1, 1, d), jnp.float32)

    def naive(t, freqs):
        f32 = freqs.astype(jnp.float32)
        cos, sin = jnp.cos(f32), jnp.sin(f32)
        t32 = t.astype(jnp.float32)
        half = d // 2
        rot = jnp.concatenate([-t32[..., half:], t32[..., :half]], axis=-1)
        return (t32 * cos + rot * sin).astype(t.dtype)

    results[f"rope_fwdbwd_s{s}b{b}"] = _fmt(
        f"fwd+bwd s{s}b{b}h{h}d{d}",
        chain_grad(fused_apply_rotary_pos_emb, (0,), t, freqs),
        chain_grad(naive, (0,), t, freqs))


def bench_packed_attention(results):
    """Padding FLOPs recovered by the varlen (segment-id) kernel: the
    same token stream as right-padded b32xs512 batches (BERT-large
    attention geometry, ~50% fill) vs packed 512-token rows."""
    from apex_tpu.ops.flash_attention import flash_attention

    h, d, s = 16, 64, 512
    rng = np.random.RandomState(0)
    # 32 sequences, lengths ~ U(128, 384): mean 256 -> 8192 real tokens
    lengths = rng.randint(128, 385, size=32)
    total = int(lengths.sum())

    # padded layout: one sequence per 512-row + key-padding mask
    qp = jnp.asarray(rng.randn(32, s, h, d), jnp.bfloat16)
    kpm = jnp.asarray(
        np.arange(s)[None, :] >= lengths[:, None])          # True = pad

    # packed layout: first-fit whole sequences per row (a sequence never
    # spans rows — splitting would silently drop its cross-row attention
    # and inflate the measured speedup)
    rows_fill = []
    assign = []
    for i, L in enumerate(lengths):
        L = int(L)
        for r, used in enumerate(rows_fill):
            if used + L <= s:
                assign.append((r, used, L, i))
                rows_fill[r] += L
                break
        else:
            assign.append((len(rows_fill), 0, L, i))
            rows_fill.append(L)
    n_rows = len(rows_fill)
    seg = np.full((n_rows, s), -1, np.int32)
    for r, start, L, i in assign:
        seg[r, start:start + L] = i
    qk = jnp.asarray(rng.randn(n_rows, s, h, d), jnp.bfloat16)
    seg = jnp.asarray(seg)

    def padded(q):
        return flash_attention(q, q, q, key_padding_mask=kpm)

    def packed(q):
        return flash_attention(q, q, q, segment_ids=seg)

    t_pad = chain_grad(padded, (0,), qp, inner=(16, 48, 160))
    t_pack = chain_grad(packed, (0,), qk, inner=(16, 48, 160))
    tok_pad = total / t_pad
    tok_pack = total / t_pack
    speedup = tok_pack / tok_pad
    print("packed varlen attention (BERT-large geometry, s512)")
    print(f"  padded b32 fwd+bwd {t_pad*1e6:9.1f}us  "
          f"packed b{n_rows} {t_pack*1e6:9.1f}us  "
          f"-> {speedup:.2f}x tokens/s")
    results["packed_vs_padded_s512"] = {
        "padded_us": round(t_pad * 1e6, 1),
        "packed_us": round(t_pack * 1e6, 1),
        "padded_rows": 32, "packed_rows": n_rows,
        "real_tokens": total,
        "tokens_per_s_speedup": round(speedup, 3),
    }


def bench_adam(results):
    """Flat-buffer Adam, absolute time only: the Pallas kernel this row
    used to race was deleted in round 5 (1.82x XLA at its best swept
    block size, its win-or-delete gate), so the row now just
    tracks the XLA fused update the optimizers actually run."""
    from apex_tpu.ops.flat_adam import adam_kernel_flat

    print("flat Adam (88M fp32 buffer, XLA fused update)")
    n = 88_000_000
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(n // 1000, 1000).reshape(-1)[:n] * 1e-3,
                    jnp.float32)
    p = jnp.asarray(rng.randn(n // 1000, 1000).reshape(-1)[:n] * 1e-2,
                    jnp.float32)
    scalars = jnp.asarray([1e-3, 0.9, 0.999, 1e-8, 0.01, 0.9, 0.999],
                          jnp.float32)

    def step(pmv, g, scalars):
        p, m, v = pmv
        u, m, v = adam_kernel_flat(g, p, m, v, scalars)
        return (p + u, m, v)

    zeros = jnp.zeros_like(p)

    def make_run(n):
        @jax.jit
        def run(p, m, v, g, scalars):
            return _scalarize(jax.lax.fori_loop(
                0, n, lambda i, pmv: step(pmv, g, scalars),
                (p, m, v)))
        return run

    t = _time(make_run, (p, zeros, zeros, g, scalars), inner=(16, 48, 160))
    print(f"  update 88M fp32 (xla)                        "
          f"{t*1e6:9.1f}us")
    results["adam_flat_88m"] = {"xla_us": round(t * 1e6, 1),
                                "winner": "xla",
                                "note": "pallas kernel deleted round 5"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="KERNEL_BENCH.json")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    args = ap.parse_args()

    from apex_tpu.utils.jax_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench_kernels: JAX reports platform {dev.platform!r}; the "
            "kernel ledger is a device measurement and needs a TPU")
    print(f"device: {dev.device_kind} ({dev.platform})")
    results = {}
    benches = {
        "flash_attention": bench_flash_attention,
        "flash_gqa": bench_flash_gqa,
        "layer_norm": bench_layer_norm,
        "softmax": bench_softmax,
        "xentropy": bench_xentropy,
        "swiglu": bench_swiglu,
        "rope": bench_rope,
        "packed_attention": bench_packed_attention,
        "adam": bench_adam,
    }
    only = set(args.only.split(",")) if args.only else None
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            fn(results)
        except Exception as e:
            print(f"  {name} FAILED: {type(e).__name__}: {e}")
            results[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
    with open(args.json, "w") as f:
        json.dump({"device": dev.device_kind, "inner": INNER,
                   "results": results}, f, indent=1)
    print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
