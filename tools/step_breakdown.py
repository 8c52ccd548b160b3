"""Attribute GPT train-step time: measured ablations + compiled roofline.

The reference's perf workflow leans on nvprof/NVTX ranges; the TPU
analog here combines three sources into one table:

1. measured ablations on the real chip (full step, fwd+bwd, fwd,
   backbone-only, head+CE, per-layer slope from a 6-vs-12-layer diff);
2. the compiled step's ``cost_analysis()`` (XLA's own flop/byte counts)
   turned into roofline lower bounds at the chip's peak FLOP/s and HBM
   bandwidth;
3. the delta between the two — the "unattributed" time that profiling
   work should chase.

Usage (on the real chip):
    python tools/step_breakdown.py \
        [--batch 16] [--seq 1024] [--fused-head-ce]

jax.named_scope ranges are already in the model (transformer_lm.py) for
xprof sessions; this tool is the numbers-first view for a machine
without an interactive xprof UI.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.config import gpt_125m
from apex_tpu.models.gpt import make_gpt_train_step
from apex_tpu.models.transformer_lm import (
    gpt_loss, init_gpt_params, lm_head_weight, single_device_ctx,
    transformer_backbone)
from apex_tpu.observability import StepTimer, configure_from_env
from apex_tpu.optimizers import fused_adam

_PEAK_FLOPS = 197e12      # v5e bf16 dense
_PEAK_BYTES = 819e9       # v5e HBM GB/s


def timeit(fn, *args, iters=10, name="ablation"):
    # Shared measurement path (ISSUE 1): same StepTimer + fencing
    # semantics as bench.py, so ablation rows compare against BENCH
    # lines apples-to-apples; ms to match the printed tables.
    return StepTimer(name, warmup=1, iters=iters).time_call(fn, *args) * 1e3


def roofline(jitted, *args):
    """(flops, bytes, bound_ms) from the compiled step's cost analysis."""
    compiled = jitted.lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    bound = max(flops / _PEAK_FLOPS, byts / _PEAK_BYTES) * 1e3
    return flops, byts, bound


def resnet_main(args):
    """ResNet-50 step attribution (where do the 106 ms of
    the b256 step go?).  Ablations: full AMP step → loss fwd+bwd → fwd
    only → inference fwd (BN frozen) → stem variant diff, plus XLA's
    cost-analysis roofline on the fwd+bwd graph."""
    from apex_tpu.models.resnet import make_resnet_train_step, resnet50

    B = args.batch
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(B, 224, 224, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, (B,)), jnp.int32)

    results = {}
    for s2d in (True, False):
        model = resnet50(space_to_depth_stem=s2d)
        init, step = make_resnet_train_step(
            model, fused_adam(lr=1e-3), "O2", image_shape=(224, 224, 3))
        state, stats = init(jax.random.PRNGKey(0))

        def one(carry, step=step, state=state, stats=stats):
            s, st = carry[:2] if carry else (state, stats)
            s, st, m = step(s, st, images, labels)
            return s, st, m["loss"]

        timer = StepTimer(f"rn50_full_{'s2d' if s2d else '7x7'}",
                          warmup=1, iters=args.iters)
        t_full = timer.time(one) * 1e3
        state, stats = timer.last[:2]

        params_bf16 = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.bfloat16)
            if v.dtype == jnp.float32 else v, state.master_params)
        imgs_bf16 = images.astype(jnp.bfloat16)

        def loss_f(p, st, im):
            logits, mut = model.apply(
                {"params": p, "batch_stats": st}, im, train=True,
                mutable=["batch_stats"])
            one_hot = jax.nn.one_hot(labels, 1000, dtype=jnp.float32)
            return -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits.astype(jnp.float32)) * one_hot,
                axis=-1))

        grad_j = jax.jit(jax.grad(loss_f))
        t_fwdbwd = timeit(grad_j, params_bf16, stats, imgs_bf16,
                          iters=args.iters, name="rn50_fwdbwd")
        fl, by, bound = roofline(grad_j, params_bf16, stats, imgs_bf16)

        fwd_j = jax.jit(loss_f)
        t_fwd = timeit(fwd_j, params_bf16, stats, imgs_bf16,
                       iters=args.iters, name="rn50_fwd")

        infer_j = jax.jit(lambda p, st, im: model.apply(
            {"params": p, "batch_stats": st}, im,
            train=False).astype(jnp.float32).mean())
        t_infer = timeit(infer_j, params_bf16, stats, imgs_bf16,
                         iters=args.iters, name="rn50_infer")

        results[s2d] = (t_full, t_fwdbwd, t_fwd, t_infer, fl, by, bound)

    for s2d, (t_full, t_fwdbwd, t_fwd, t_infer, fl, by, bound) in \
            results.items():
        # standard accounting: train ≈ 3 × 4.1 GFLOP fwd per image
        mfu = B * 3 * 4.1e9 / (_PEAK_FLOPS * t_full / 1e3)
        tag = "s2d-stem" if s2d else "7x7-stem"
        print(f"[{tag}] full AMP O2 step: {t_full:8.2f} ms  "
              f"({B / (t_full / 1e3):.0f} imgs/s, MFU {mfu:.3f})")
        print(f"  fwd+bwd:          {t_fwdbwd:8.2f} ms   "
              f"-> opt/scaler/BN-update {t_full - t_fwdbwd:6.2f}")
        print(f"  fwd (train):      {t_fwd:8.2f} ms   "
              f"-> bwd {t_fwdbwd - t_fwd:6.2f}")
        print(f"  fwd (inference):  {t_infer:8.2f} ms   "
              f"-> BN-stats cost {t_fwd - t_infer:6.2f}")
        print(f"  roofline(fwd+bwd):{bound:8.2f} ms  "
              f"({fl/1e12:.2f} TFLOP, {by/1e9:.2f} GB compiled)")
        print(f"  unattributed vs roofline: {t_fwdbwd - bound:6.2f} ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt", choices=("gpt", "resnet50"))
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--fused-head-ce", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    # APEX_TPU_TELEMETRY=<path> streams every ablation as step.* spans
    configure_from_env()
    if args.model == "resnet50":
        if args.batch is None:
            args.batch = 256   # the bench-matrix RN50 batch
        resnet_main(args)
        return
    if args.batch is None:
        args.batch = 16        # the bench-matrix GPT batch
    B, S = args.batch, args.seq

    cfg = gpt_125m(max_position_embeddings=S, remat=False,
                   scan_layers=False, fused_head_ce=args.fused_head_ce)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)

    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-4), "O2")
    state = init(jax.random.PRNGKey(0))

    # the step donates its state: thread it through the timing carry
    def one(carry):
        s = carry[0] if carry else state
        s, m = step(s, tokens, labels)
        return s, m["loss"]

    timer = StepTimer("gpt_full_step", warmup=1, iters=args.iters)
    t_full = timer.time(one) * 1e3
    state = timer.last[0]

    params_bf16 = jax.tree_util.tree_map(
        lambda v: v.astype(jnp.bfloat16)
        if v.dtype == jnp.float32 else v, state.master_params)

    loss_f = lambda p: gpt_loss(p, tokens, labels, cfg)   # noqa: E731
    grad_j = jax.jit(jax.grad(loss_f))
    t_fwdbwd = timeit(grad_j, params_bf16, iters=args.iters,
                      name="gpt_fwdbwd")
    fl, by, bound = roofline(grad_j, params_bf16)

    fwd_j = jax.jit(loss_f)
    t_fwd = timeit(fwd_j, params_bf16, iters=args.iters, name="gpt_fwd")

    ctx = single_device_ctx()
    hidden = jnp.asarray(rng.randn(B, S, cfg.hidden_size), jnp.bfloat16)

    def backbone_loss(p, h):
        out, _ = transformer_backbone(p, h, cfg, ctx, with_aux=True)
        return out.astype(jnp.float32).mean()

    t_bb = timeit(jax.jit(jax.grad(backbone_loss)), params_bf16, hidden,
                  iters=args.iters, name="gpt_backbone")

    def head_loss(p, h):
        from apex_tpu.ops.lm_head_ce import lm_head_cross_entropy
        head = lm_head_weight(p, cfg).astype(cfg.compute_dtype)
        if args.fused_head_ce:
            losses = lm_head_cross_entropy(h, head, labels,
                                           chunk=cfg.head_ce_chunk)
        else:
            from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
            logits = jnp.einsum("bsh,vh->bsv", h, head,
                                preferred_element_type=jnp.float32)
            losses = softmax_cross_entropy_loss(
                logits.reshape(-1, logits.shape[-1]),
                labels.reshape(-1), padding_idx=None)
        return losses.mean()

    t_head = timeit(jax.jit(jax.grad(head_loss, argnums=(0, 1))),
                    params_bf16, hidden, iters=args.iters,
                    name="gpt_head_ce")

    cfg6 = dataclasses.replace(cfg, num_layers=6)
    p6 = jax.tree_util.tree_map(
        lambda v: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v,
        init_gpt_params(jax.random.PRNGKey(0), cfg6))

    def backbone6(p, h):
        out, _ = transformer_backbone(p, h, cfg6, ctx, with_aux=True)
        return out.astype(jnp.float32).mean()

    t_bb6 = timeit(jax.jit(jax.grad(backbone6)), p6, hidden,
                   iters=args.iters, name="gpt_backbone_6layer")

    n_params = sum(
        int(np.prod(v.shape))
        for v in jax.tree_util.tree_leaves(state.master_params)
        if hasattr(v, "dtype") and v.dtype == jnp.float32)
    ideal_flops = (6 * n_params * B * S
                   + 12 * cfg.num_layers * cfg.hidden_size * B * S * S)
    ideal_ms = ideal_flops / _PEAK_FLOPS * 1e3
    mfu = ideal_ms / t_full

    print(f"config: b{B}xs{S}, fused_head_ce={args.fused_head_ce}")
    print(f"full AMP O2 step:     {t_full:8.2f} ms   (MFU {mfu:.3f})")
    print(f"  fwd+bwd:            {t_fwdbwd:8.2f} ms   "
          f"-> opt/scaler/casts {t_full - t_fwdbwd:6.2f}")
    print(f"  fwd only:           {t_fwd:8.2f} ms")
    print(f"  backbone fwd+bwd:   {t_bb:8.2f} ms   "
          f"-> embed+head+CE {t_fwdbwd - t_bb:6.2f}")
    print(f"  head+CE fwd+bwd:    {t_head:8.2f} ms")
    print(f"  per-layer fwd+bwd:  {(t_bb - t_bb6) / 6:8.2f} ms "
          f"(12-vs-6-layer slope)")
    print(f"roofline(fwd+bwd):    {bound:8.2f} ms  "
          f"({fl/1e12:.2f} TFLOP, {by/1e9:.2f} GB compiled)")
    print(f"unattributed vs roofline: {t_fwdbwd - bound:6.2f} ms")


if __name__ == "__main__":
    main()
