"""The ``train`` entry: one chip, the train step a user calls
(``make_*_train_step`` of the configuration's ``program`` block), unchanged.

``setup`` builds ONE object, the jitted step with its state, from weights the
benchmark makes on the device from the seed (the reference's own generator,
in the program's layout), drives it through its first steps on the pool's
first batches through the same feed-and-call the window uses, and hands that
same object to ``window``.  ``check`` then sets those first steps against
the plain float32 reference (``benchmark/reference``), once the window has
closed and the program's state is freed.
"""

from __future__ import annotations

import importlib
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from benchmark import flops, traffic, trace_reduce
from benchmark.reference import train as ref_train

IN_FLIGHT = 2          # the host runs at most this many steps ahead
TRACED_STEPS = 10


def _load(path: str):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def seed_key(seed: int):
    """A key from any whole number, also one past 32 signed bits."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def build_step(program: dict):
    """``(init, step)`` exactly as a user gets them."""
    cfg = _load(program["model_config"])(**program["model_config_kwargs"])
    optimizer = _load(program["optimizer"])(**program["optimizer_kwargs"])
    return _load(program["train_step"])(cfg, optimizer, program["opt_level"])


def setup(config: dict, cell: dict, seed: int, devices: list, t0: float,
          log) -> "TrainRun":
    if cell["chips"] != 1 or len(devices) != 1:
        raise SystemExit(
            f"benchmark: the train entry drives one chip; the cell asks "
            f"for {cell['chips']} (a data-parallel cell needs an entry of "
            "its own)")
    return TrainRun(config, cell, seed, devices[0], t0, log)


class TrainRun:
    def __init__(self, config, cell, seed, device, t0, log):
        self.config, self.cell, self.device, self.log = (
            config, cell, device, log)
        self.t0 = t0
        self.ref = config["reference"]
        self.model = ref_train.model_module(self.ref["model"])
        self.key = seed_key(seed)
        self.pool = traffic.make_pool(cell, config["vocab_size"], seed)
        self.tokens_per_step = cell["batch"] * cell["seq"]
        init, self.step = build_step(config["program"])

        def make_state(key):
            mine = self.model.init_params(key, config)
            state = init(jax.random.key_data(key))
            theirs = state.master_params
            if (jax.tree_util.tree_structure(mine)
                    != jax.tree_util.tree_structure(theirs)):
                raise SystemExit(
                    "benchmark: the reference's parameter tree is not the "
                    "program's:\n"
                    f"{jax.tree_util.tree_structure(mine)}\n"
                    f"{jax.tree_util.tree_structure(theirs)}")
            for name, a, b in zip(ref_train.leaf_names(mine),
                                  jax.tree_util.tree_leaves(mine),
                                  jax.tree_util.tree_leaves(theirs)):
                if a.shape != b.shape:
                    raise SystemExit(
                        f"benchmark: leaf {name}: reference {a.shape}, "
                        f"program {b.shape}")
            # the user's state, with the benchmark's weights in it: the
            # program's own random ones are never made (dead code)
            return state._replace(
                master_params=mine,
                params=jax.tree_util.tree_map(
                    lambda m, p: m.astype(p.dtype), mine, state.params))

        fresh = jax.jit(lambda key: self.model.init_params(key, config))
        self.make_params = lambda: fresh(self.key)
        log("setup: weights and state on the device")
        self.state = jax.jit(make_state)(self.key)
        jax.block_until_ready(self.state)

        # the first steps: the window's own call and feed, the rows of the
        # pool's first batches; what they leave is what check() compares
        log("setup: first steps (compile or cache load)")
        self.n_steps = 0
        first = []
        for i in range(ref_train.N_STEPS):
            first.append(self._feed_and_step()[0])
            if i == 0:
                moment = jax.device_get(getattr(
                    self.state.opt_state, config["program"]["first_moment"]))
        # the first moment after one step and the masters after the last go
        # to the host as they are; what is compared is worked out once the
        # window has closed, so that set-up holds nothing on the device
        # beside the user's state and memory_peak_bytes is the program's
        self._first = ([m["loss"] for m in first], moment,
                       jax.device_get(self.state.master_params))
        self.first_overflow = [bool(m["overflow"]) for m in first]
        jax.block_until_ready(self.state)
        self.setup_s = time.perf_counter() - t0
        log(f"setup: done in {self.setup_s:.1f} s; first losses "
            f"{[float(x) for x in self._first[0]]}, overflow "
            f"{self.first_overflow}")

    def _feed_and_step(self):
        """One step as the window takes it: the next host batch of the
        pool goes to the device, then the user's step is called.  Returns
        its metrics and the seconds the call took to return (no fence)."""
        with TraceAnnotation("bench:feed"):
            batch = jax.device_put(
                self.pool[self.n_steps % len(self.pool)], self.device)
        t = time.perf_counter()
        with TraceAnnotation("bench:dispatch"):
            self.state, metrics = self.step(self.state, *batch)
        self.n_steps += 1
        return metrics, time.perf_counter() - t

    def _run(self, stop) -> tuple:
        """Steps until ``stop(n_done)``, at most IN_FLIGHT ahead of the
        device; fenced on the last step's outputs."""
        metrics, dispatch, fenced = [], [], []
        while not stop(len(metrics)):
            m, dt = self._feed_and_step()
            metrics.append(m)
            dispatch.append(dt)
            if len(metrics) > IN_FLIGHT:
                with TraceAnnotation("bench:fence"):
                    metrics[-1 - IN_FLIGHT]["loss"].block_until_ready()
                fenced.append(time.perf_counter())
        with TraceAnnotation("bench:fence"):
            jax.block_until_ready((self.state, metrics[-1]))
        return metrics, dispatch, np.diff(fenced if len(fenced) > 1
                                          else [0.0, 0.0])

    def window(self, seconds: float, trace_dir) -> dict:
        self.log(f"window: {seconds} s")
        start = time.perf_counter()
        metrics, dispatch, between = self._run(
            lambda n: time.perf_counter() - start >= seconds)
        elapsed = time.perf_counter() - start
        losses, overflow = jax.device_get(
            ([m["loss"] for m in metrics], [m["overflow"] for m in metrics]))
        steps = len(metrics)
        seq = self.cell["seq"]
        record = {
            "end_to_end": {
                "train_tokens_per_s": steps * self.tokens_per_step / elapsed,
                "setup_s": self.setup_s,
            },
            "attempted": steps,
            "failed": int(sum(not np.isfinite(x) for x in losses)),
            "window_s": elapsed,
            "steps": steps,
            "dispatch_s": dispatch,
            "overflow": [bool(x) for x in overflow],
            "flops_per_token": flops.train_flops_per_token(
                self.config, seq, self.cell.get("head_token_share", 1.0)),
            # None off the chip: the CPU keeps no such count
            "memory_peak_bytes": (self.device.memory_stats() or {}).get(
                "peak_bytes_in_use"),
        }
        # a stalled host shows as one long wait between two fences
        self.log(f"window: {steps} steps in {elapsed:.3f} s, last loss "
                 f"{float(losses[-1]):.4f}, {sum(record['overflow'])} "
                 f"skipped; between fences median "
                 f"{1e3 * float(np.median(between)):.1f} ms, longest "
                 f"{1e3 * float(np.max(between)):.1f} ms")
        if trace_dir is not None:
            # a slice of steady state after the timed window, so that the
            # profiler's cost is in no rate
            self.log(f"trace: {TRACED_STEPS} steps")
            jax.profiler.start_trace(trace_dir)
            try:
                self._run(lambda n: n >= TRACED_STEPS)
            finally:
                jax.profiler.stop_trace()
            record["trace"] = trace_reduce.reduce_trace(trace_dir)
        return record

    def first_readings(self) -> dict:
        """Frees the program's state and gives what its first steps left,
        as ``reference.train.compare`` takes it."""
        self.state = None
        losses, moment, masters = self._first
        return ref_train.readings(
            losses, moment,
            ref_train.tree_difference(masters, self.make_params()))

    def check(self) -> tuple:
        """Run the float32 reference over the same first batches from the
        same seed, and compare the program's first steps with it."""
        self.state = None
        self.log("check: float32 reference, first steps")
        t = time.perf_counter()
        want = ref_train.reference_steps(
            self.ref, self.config, self.make_params,
            self.pool[:ref_train.N_STEPS])
        numbers = ref_train.compare(self.first_readings(), want)
        self.log(f"check: reference losses {want['loss']} "
                 f"({time.perf_counter() - t:.1f} s)")
        return ref_train.judge(numbers, self.cell["check"]["limits"])
