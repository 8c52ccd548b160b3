"""examples/dcgan analog: DCGAN generator/discriminator under AMP.

Reference: examples/dcgan/main_amp.py — the adversarial workload that
exercises amp with MULTIPLE optimizers and losses (``amp.initialize``
with [netD, netG] and ``scale_loss(..., loss_id=k)`` for errD_real /
errD_fake / errG).  TPU shape: two independent AMP train steps (each
with its own dynamic loss scaler — the loss_id analog), the opposing
network's params riding in the batch slot so no gradients flow through
them.

Runs on synthetic noise/images; swap ``synthetic_images`` for a real
dataset (LSUN/CIFAR in the reference) to train for real.

Run: python examples/dcgan.py [--steps 20] [--opt-level O2]
"""

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.amp.frontend import make_train_step
from apex_tpu.optimizers import fused_adam
from apex_tpu.utils.jax_cache import enable_compile_cache

NZ = 64          # latent dim
NGF = NDF = 32   # feature widths
HW = 32          # image size


class Generator(nn.Module):
    @nn.compact
    def __call__(self, z):
        x = z.reshape(z.shape[0], 1, 1, NZ)
        for i, ch in enumerate((NGF * 4, NGF * 2, NGF)):
            x = nn.ConvTranspose(
                ch, (4, 4), strides=(4, 4) if i == 0 else (2, 2),
                padding="SAME")(x)
            x = nn.GroupNorm(num_groups=8)(x)
            x = nn.relu(x)
        x = nn.ConvTranspose(3, (4, 4), strides=(2, 2), padding="SAME")(x)
        return jnp.tanh(x)


class Discriminator(nn.Module):
    @nn.compact
    def __call__(self, x):
        for i, ch in enumerate((NDF, NDF * 2, NDF * 4)):
            x = nn.Conv(ch, (4, 4), strides=(2, 2), padding="SAME")(x)
            x = nn.leaky_relu(x, 0.2)
        x = nn.Conv(1, (4, 4), strides=(4, 4), padding="VALID")(x)
        return x.reshape(x.shape[0])


def bce_logits(logits, target):
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * target
        + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def synthetic_images(batch, seed=0):
    rng = np.random.RandomState(seed)
    while True:
        yield jnp.asarray(
            np.tanh(rng.randn(batch, HW, HW, 3)), jnp.float32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--opt-level", default="O2")
    ap.add_argument("--lr", type=float, default=2e-4)
    args = ap.parse_args()

    gen, disc = Generator(), Discriminator()
    key = jax.random.PRNGKey(0)
    kg, kd, kz = jax.random.split(key, 3)
    z0 = jnp.zeros((args.batch, NZ), jnp.float32)
    pg = gen.init(kg, z0)["params"]
    pd = disc.init(kd, jnp.zeros((args.batch, HW, HW, 3)))["params"]

    def d_loss(pd_, real, z, pg_const):
        fake = gen.apply({"params": pg_const}, z)
        errD_real = bce_logits(
            disc.apply({"params": pd_}, real), 1.0)
        errD_fake = bce_logits(
            disc.apply({"params": pd_}, fake), 0.0)
        return errD_real + errD_fake

    def g_loss(pg_, z, pd_const):
        fake = gen.apply({"params": pg_}, z)
        return bce_logits(disc.apply({"params": pd_const}, fake), 1.0)

    # two AMP steps, each with its own dynamic scaler (loss_id analog)
    adam = lambda: fused_adam(lr=args.lr, betas=(0.5, 0.999))  # noqa: E731
    init_d, step_d = make_train_step(d_loss, adam(), args.opt_level)
    init_g, step_g = make_train_step(g_loss, adam(), args.opt_level)
    sd, sg = init_d(pd), init_g(pg)

    data = synthetic_images(args.batch)
    t0 = time.perf_counter()
    for i in range(args.steps):
        kz, k1 = jax.random.split(kz)
        z = jax.random.normal(k1, (args.batch, NZ))
        real = next(data)
        sd, md = step_d(sd, real, z, sg.params)
        sg, mg = step_g(sg, z, sd.params)
    d, g = float(md["loss"]), float(mg["loss"])
    dt = (time.perf_counter() - t0) / args.steps
    print(f"errD {d:.4f}  errG {g:.4f}  {1.0 / dt:.2f} it/s "
          f"({args.opt_level}, scales D={float(md['loss_scale'])} "
          f"G={float(mg['loss_scale'])})")


if __name__ == "__main__":
    main()
