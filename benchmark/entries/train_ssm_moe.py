"""The ``train_ssm_moe`` entry: ``entries/train_moe.py``'s ``MoeTrainRun``
(the counters in the step's outputs, the trace split by the cell file's
``scope_words``) for a configuration with state-space layers, with
``flops_nemotron_h.py``'s count in place of ``flops_moe.py``'s (which knows
``conv`` and ``full_attention`` layers and gated experts only).

After the window it puts into the record ``flops_per_token``, ``moe`` (as
``train_moe`` does, the grouped products not gated) and ``ssm``: the
operations and the least bytes of the step's state-space scans, which
``ssd_scan_roofline`` reads.

A traced run's ``record["trace"]["device_ops"]`` keeps, after its ten rows,
a row for each of the cell file's ``kernel_ops`` that ran and is not among
the ten: the program's kernels by their own names, own time as
``trace_reduce`` counts it.  ``flash_fwd`` takes 4.7 ms of this cell's 267
ms step, and ten rows of XLA's fusions stand before it; without its row
``flash_fwd_ms`` would read nothing in a cell whose step runs the kernel.
"""

from __future__ import annotations

import jax

from benchmark import flops_nemotron_h as flops_ssm
from benchmark import scope_times, trace_reduce
from benchmark.entries import train, train_moe


def setup(config: dict, cell: dict, seed: int, devices: list, t0: float,
          log) -> "SsmMoeTrainRun":
    if cell["chips"] != 1 or len(devices) != 1:
        raise SystemExit("benchmark: the train_ssm_moe entry drives one chip")
    return SsmMoeTrainRun(config, cell, seed, devices[0], t0, log)


def kernel_rows(events: list, names, rows: list) -> list:
    """``[name, own seconds]`` of each of ``names`` that ran on the device
    (this entry drives one chip: one device plane) and has no row in
    ``rows`` yet."""
    own = trace_reduce._self_times(
        [e for e in events if e.line == trace_reduce.OPS_LINE])
    listed = {name for name, _ in rows}
    return [[name, own[name] / 1e9] for name in names
            if name in own and name not in listed]


class SsmMoeTrainRun(train_moe.MoeTrainRun):
    def window(self, seconds: float, trace_dir) -> dict:
        self._counters = []
        record = train.TrainRun.window(self, seconds, trace_dir)
        record["flops_per_token"] = flops_ssm.train_flops_per_token(
            self.config, self.cell["seq"])
        runs = self.cell["forward_runs"]
        flops, nbytes = flops_ssm.scan_step_work(
            self.config, self.tokens_per_step, runs)
        record["ssm"] = {"scan_flops_a_step": flops,
                         "scan_bytes_a_step": nbytes}
        rows = [r for r in self._counters if None not in r]
        if rows:
            sums = [float(sum(col)) for col in zip(*jax.device_get(rows))]
            moe = dict(zip(train_moe.COUNTERS, sums), steps=len(rows))
            per_layer = (moe["moe_assignments_held"] / len(rows)
                         / flops_ssm.layers_of(self.config, "E"))
            (moe["grouped_flops_a_step"],
             moe["grouped_bytes_a_step"]) = flops_ssm.grouped_step_work(
                self.config, per_layer, runs)
            record["moe"] = moe
        if trace_dir is not None and record.get("trace"):
            # as train_moe: the scopes are looked up in check()
            self._events = scope_times.load_events(
                trace_reduce.find_xplane(trace_dir), {})
            record["trace"]["device_ops"] += kernel_rows(
                self._events, self.cell.get("kernel_ops", ()),
                record["trace"]["device_ops"])
            self._batch = train_moe._struct(
                jax.device_put(self.pool[0], self.device))
            self._state = train_moe._struct(self.state)
        self._record = record
        return record
