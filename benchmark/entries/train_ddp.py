"""The ``train_ddp`` entry: data-parallel training over all the cell's chips
on one host, ``apex_tpu.parallel.make_ddp_train_step`` (the whole AMP step
under ``shard_map`` over ``create_mesh(dp=chips)``, gradients averaged on the
float32 wire) around the loss and the model arguments of the configuration's
one-chip ``program``.  ``entries/train.py``'s ``TrainRun`` drives it: the
same set-up, window, first steps and check; what differs is where things
live.

- The state is replicated over the mesh and the global batch split over it
  by rows (``P("dp")``): each host batch of the pool goes to the chips
  inside the window, a quarter to each.
- The step is wrapped in one donating ``jax.jit``, as
  ``make_gpt_train_step`` wraps its own: ``make_ddp_train_step``'s is not
  donated, and two copies of the state do not fit beside the activations.
- ``check`` hands the float32 reference the same batches split the same
  way, so its step follows its inputs over the four chips (parameters
  replicated): the comparison is on the global batch.
- ``memory_peak_bytes`` is the first chip's; every chip holds the same.
"""

from __future__ import annotations

import time

import jax
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.entries import train
from benchmark.reference import train as ref_train

_MESH = None       # the run's mesh, for make_step (one run a process)


def make_step(cfg, optimizer, opt_level):
    """``make_gpt_train_step``'s signature, data parallel over the run's
    mesh: ``(init, step)``."""
    from apex_tpu.models.transformer_lm import gpt_loss, init_gpt_params
    from apex_tpu.parallel import make_ddp_train_step

    init_state, step = make_ddp_train_step(
        lambda p, t, l: gpt_loss(p, t, l, cfg), optimizer, opt_level,
        _MESH, batch_axes=2)
    return (lambda rng: init_state(init_gpt_params(rng, cfg)),
            jax.jit(step, donate_argnums=0))


def setup(config: dict, cell: dict, seed: int, devices: list, t0: float,
          log) -> "DdpTrainRun":
    from apex_tpu.parallel import create_mesh

    global _MESH
    if cell["batch"] % len(devices):
        raise SystemExit(f"benchmark: {len(devices)} chips do not divide "
                         f"the batch of {cell['batch']}")
    _MESH = create_mesh(dp=len(devices), devices=devices)
    program = dict(config["program"],
                   train_step=f"{__name__}:make_step")
    return DdpTrainRun(dict(config, program=program), cell, seed,
                       devices[0], t0, log)


class DdpTrainRun(train.TrainRun):
    @property
    def key(self):
        return self._key

    @key.setter
    def key(self, value):
        # TrainRun makes the state and the reference's weights from this
        # key with plain jits, which follow their input: a key replicated
        # over the mesh makes both replicated over it
        self._key = jax.device_put(value, NamedSharding(_MESH, P()))

    def _feed_and_step(self):
        """TrainRun's, with the batch split by rows over the mesh."""
        rows = NamedSharding(_MESH, P("dp"))
        with TraceAnnotation("bench:feed"):
            batch = jax.device_put(
                self.pool[self.n_steps % len(self.pool)], rows)
        t = time.perf_counter()
        with TraceAnnotation("bench:dispatch"):
            self.state, metrics = self.step(self.state, *batch)
        self.n_steps += 1
        return metrics, time.perf_counter() - t

    def check(self) -> tuple:
        host = self.pool
        self.pool = jax.device_put(host[:ref_train.N_STEPS],
                                   NamedSharding(_MESH, P("dp")))
        try:
            return super().check()
        finally:
            self.pool = host
