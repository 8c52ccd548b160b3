"""examples/simple analog: tiny model + AMP + data parallelism.

Reference: examples/simple/distributed/distributed_data_parallel.py — a
Linear model on fake data under apex.amp + apex.parallel.DDP, launched with
one process per GPU. TPU-native shape: ONE process, a ('pp','dp','sp','tp')
mesh over all chips, the batch sharded along 'dp', and the whole train step
jitted — XLA inserts the gradient all-reduce that apex DDP's bucket hooks
performed by hand.

Run: python examples/simple_ddp.py  (any number of devices, incl. 1)
"""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu.optimizers import fused_adam
from apex_tpu.parallel.mesh import create_mesh, replicate, shard_batch
from apex_tpu.utils.jax_cache import enable_compile_cache


def main():
    enable_compile_cache()
    N, D_in, D_hidden, D_out = 64, 1024, 256, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N, D_in), jnp.float32)
    y = jnp.asarray(rng.randn(N, D_out), jnp.float32)

    params = {
        "w1": jnp.asarray(rng.randn(D_in, D_hidden) * 0.02, jnp.float32),
        "b1": jnp.zeros((D_hidden,), jnp.float32),
        "w2": jnp.asarray(rng.randn(D_hidden, D_out) * 0.02, jnp.float32),
        "b2": jnp.zeros((D_out,), jnp.float32),
    }

    def loss_fn(p, x, y):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        pred = h @ p["w2"] + p["b2"]
        return jnp.mean((pred - y) ** 2)

    mesh = create_mesh()                      # all devices on 'dp'
    init, step = amp.make_train_step(loss_fn, fused_adam(lr=1e-3), "O1")
    state = init(params)
    state = jax.device_put(state, replicate(mesh))
    x = jax.device_put(x, shard_batch(mesh))
    y = jax.device_put(y, shard_batch(mesh))

    jstep = jax.jit(step, donate_argnums=0)
    with jax.set_mesh(mesh):
        for i in range(500):
            state, metrics = jstep(state, x, y)
            if i % 100 == 0 or i == 499:
                print(f"step {i:4d}  loss {float(metrics['loss']):.6f}  "
                      f"scale {float(metrics['loss_scale']):.0f}")


if __name__ == "__main__":
    main()
