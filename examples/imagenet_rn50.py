"""examples/imagenet analog: ResNet-50, AMP O2 + DP + SyncBN — full
resumable trainer.

Reference: examples/imagenet/main_amp.py (torchvision resnet50, O0-O3
opt levels, DDP, optional SyncBN, data prefetcher, prec@1/prec@5,
checkpoint save/resume).  Feature parity on TPU:

- AMP opt levels via ``make_resnet_train_step`` (O0-O5; O2 default)
- data-parallel mesh when >1 device (SyncBN stats ride GSPMD pmean)
- background-thread prefetcher (the ``data_prefetcher`` analog,
  main_amp.py:256 — host→device copy overlaps the device step)
- prec@1 / prec@5 on the last batch (main_amp.py ``accuracy`` :439)
- step-decay LR schedule with warmup (``adjust_learning_rate`` :421)
- checkpoint save/restore + ADLR AutoResume requeue
  (utils/checkpoint.py; resume picks up at the saved step)

With ``--data-dir`` the trainer reads a real ImageFolder tree
(``<dir>/<class>/<img>``) through :mod:`apex_tpu.data` — PIL decode +
augmentation in a thread pool, batched by
``MegatronPretrainingRandomSampler`` (per-rank buckets, epoch-seeded
shuffles, ``consumed_samples`` resume — the torch DataLoader +
DistributedSampler analog, main_amp.py:188-218).  Without it, synthetic
batches keep the benchmark path dependency-free.

Run:     python examples/imagenet_rn50.py [--batch 128] [--opt-level O2]
Real:    python examples/imagenet_rn50.py --data-dir /data/imagenet/train
Resume:  python examples/imagenet_rn50.py --ckpt-dir /tmp/rn50ckpt
         (a second run with the same dir continues from the last save,
         and the sampler continues from the same consumed_samples)
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.data import device_prefetch
from apex_tpu.models import make_resnet_train_step
from apex_tpu.optimizers import fused_sgd
from apex_tpu.parallel.mesh import create_mesh
from apex_tpu.utils.checkpoint import (
    AutoResume,
    async_saver,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from apex_tpu.utils.jax_cache import enable_compile_cache


def synthetic_batches(batch, hw=224, classes=1000, seed=0):
    rng = np.random.RandomState(seed)
    while True:
        x = rng.randn(batch, hw, hw, 3).astype(np.float32)
        y = rng.randint(0, classes, (batch,)).astype(np.int32)
        yield x, y


def real_batches(data_dir, batch, hw, start_step):
    """ImageFolder tree → endless resumable batches (see module doc)."""
    from apex_tpu.data import ImageFolderDataset, make_image_loader
    from apex_tpu.transformer._data import MegatronPretrainingRandomSampler

    ds = ImageFolderDataset(data_dir, image_size=hw, train=True)
    if len(ds) < batch:
        raise ValueError(
            f"--batch {batch} exceeds the dataset size {len(ds)}; the "
            f"sampler needs at least one full batch per epoch")
    consumed = start_step * batch
    while True:   # sampler iterates one epoch per pass; loop forever
        sampler = MegatronPretrainingRandomSampler(
            total_samples=len(ds),
            consumed_samples=consumed,
            local_minibatch_size=batch,
            data_parallel_rank=0,
            data_parallel_size=1,
        )
        for x, y in make_image_loader(ds, sampler):
            # the sampler itself drops ragged tails (Megatron's
            # last-batch rule), so every batch arrives full
            assert x.shape[0] == batch, x.shape
            consumed += x.shape[0]
            yield x, y




def accuracy(logits, labels, topk=(1, 5)):
    """prec@k (reference accuracy(), main_amp.py:439)."""
    order = np.argsort(-np.asarray(logits, np.float32), axis=-1)
    labels = np.asarray(labels)
    out = []
    for k in topk:
        hit = (order[:, :k] == labels[:, None]).any(axis=1)
        out.append(100.0 * hit.mean())
    return out


def lr_schedule(base_lr, step, steps_per_epoch):
    """Step decay /10 at epochs 30/60/80 with 5-epoch warmup
    (adjust_learning_rate, main_amp.py:421)."""
    import jax.numpy as jnp

    epoch = step / steps_per_epoch
    factor = ((epoch >= 30).astype(jnp.float32)
              + (epoch >= 60) + (epoch >= 80))
    lr = base_lr * (0.1 ** factor)
    warm = base_lr * (1.0 + step) / (5.0 * steps_per_epoch)
    return jnp.where(epoch < 5, warm, lr)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--opt-level", default="O2")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="enable save/resume in this directory")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--steps-per-epoch", type=int, default=5000)
    ap.add_argument("--data-dir", default=None,
                    help="ImageFolder root (class subdirs); synthetic "
                         "data when omitted")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--arch", default="resnet50",
                    help="resnet18/34/50/101/152 (reference --arch, "
                         "main_amp.py:36)")
    ap.add_argument("--num-classes", type=int, default=1000)
    args = ap.parse_args()

    import apex_tpu.models as _models

    mesh = create_mesh() if len(jax.devices()) > 1 else None
    model = getattr(_models, args.arch)(num_classes=args.num_classes)
    schedule = lambda step: lr_schedule(  # noqa: E731
        args.lr, step, args.steps_per_epoch)
    init, step = make_resnet_train_step(
        model, fused_sgd(lr=schedule, momentum=0.9, weight_decay=1e-4),
        args.opt_level, mesh, image_shape=(args.image_size,
                                           args.image_size, 3))
    state, stats = init(jax.random.PRNGKey(0))

    start = 0
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state, stats = restore_checkpoint(
                args.ckpt_dir, (state, stats))
            start = last
            print(f"resumed from step {start}")

    auto = AutoResume()
    auto.init()

    if args.data_dir:
        source = real_batches(args.data_dir, args.batch,
                              args.image_size, start)
    else:
        source = synthetic_batches(args.batch, hw=args.image_size,
                                   classes=args.num_classes)
    batches = device_prefetch(source)
    # compile-only warmup on a throwaway COPY (the step donates its
    # inputs) and a ZERO batch — drawing a real batch here would drop
    # those samples from the epoch and skew the sampler's
    # consumed_samples accounting across preemption/resume cycles
    x = jnp.zeros((args.batch, args.image_size, args.image_size, 3),
                  jnp.float32)
    y = jnp.zeros((args.batch,), jnp.int32)
    warm = jax.tree_util.tree_map(
        lambda v: jnp.array(v, copy=True) if isinstance(v, jax.Array)
        else v, (state, stats))
    _s, _st, m = step(*warm, x, y)
    float(m["loss"])
    del _s, _st, warm

    t0 = time.perf_counter()
    done = 0
    # periodic saves are async: the snapshot is taken immediately, the
    # disk write overlaps the next training steps (requeue saves stay
    # synchronous — durability before releasing the slot)
    saver = async_saver() if args.ckpt_dir else None
    try:
        for i in range(start, args.steps):
            x, y = next(batches)
            state, stats, m = step(state, stats, x, y)
            done += 1
            saved_here = False
            if saver is not None and (i + 1) % args.ckpt_every == 0:
                saver.save(args.ckpt_dir, i + 1, (state, stats))
                saved_here = True
            if auto.termination_requested():
                # cluster wants the slot back: checkpoint + requeue
                float(m["loss"])
                if saver is not None:
                    saver.wait()
                    if not saved_here:   # async save already covers i+1
                        save_checkpoint(args.ckpt_dir, i + 1,
                                        (state, stats))
                auto.request_resume()
                print(f"AutoResume: checkpointed at step {i + 1}, "
                      "requeued")
                return
    finally:
        if saver is not None:
            saver.close()
    loss = float(m["loss"])                          # device sync
    dt = (time.perf_counter() - t0) / max(done, 1)

    # eval-style metrics on the last batch (prec@k)
    logits = model.apply(
        {"params": state.params, "batch_stats": stats},
        jnp.asarray(x), train=False)
    p1, p5 = accuracy(logits, y)
    print(f"loss {loss:.4f}  prec@1 {p1:.2f}  prec@5 {p5:.2f}  "
          f"{args.batch / dt:.1f} imgs/sec "
          f"({len(jax.devices())} device(s), {args.opt_level})")


if __name__ == "__main__":
    main()
