"""The ``train_mla_moe`` entry: ``entries/train_moe.py``'s ``MoeTrainRun``
(the counters in the step's outputs, the trace split by the cell file's
``scope_words``) for a configuration with latent attention and a
multi-token-prediction module, with ``flops_mla_moe.py``'s count in place
of ``flops_moe.py``'s (which knows ``conv`` and ``full_attention`` layers
and counts the head once).

After the window it puts into the record ``flops_per_token``, ``moe`` (as
``train_moe`` does; the counters sum over the MTP module's expert layer too)
and ``mla``: the least operations and bytes of the step's attention, which
``mla_flash_roofline`` reads.  A traced run's ``device_ops`` gets a row for
each of the cell file's ``kernel_ops`` outside its ten
(``train_ssm_moe.kernel_rows``), and ``check`` adds, beside ``scope_ms``
(an operation's time to the INNERMOST of the cell's ``scope_words``),
``scope_under_ms``: device milliseconds a step of every operation that has
one of the cell's ``under_words`` anywhere in its ``op_name`` -- all of a
latent-attention block's operator, kernels included, and all of the MTP
module, its block and its head included.

A program without the counters or the scopes leaves these out and nothing
here raises: the metrics' readers then return nothing.
"""

from __future__ import annotations

import jax

from benchmark import flops_mla_moe as flops_mla
from benchmark import scope_times, trace_reduce
from benchmark.entries import train, train_moe
from benchmark.entries.train_ssm_moe import kernel_rows


def setup(config: dict, cell: dict, seed: int, devices: list, t0: float,
          log) -> "MlaMoeTrainRun":
    if cell["chips"] != 1 or len(devices) != 1:
        raise SystemExit("benchmark: the train_mla_moe entry drives one chip")
    return MlaMoeTrainRun(config, cell, seed, devices[0], t0, log)


class MlaMoeTrainRun(train_moe.MoeTrainRun):
    def window(self, seconds: float, trace_dir) -> dict:
        self._counters = []
        record = train.TrainRun.window(self, seconds, trace_dir)
        record["flops_per_token"] = flops_mla.train_flops_per_token(
            self.config, self.cell["seq"])
        flops, nbytes = flops_mla.flash_step_work(
            self.config, self.cell["batch"], self.cell["seq"])
        record["mla"] = {"flash_flops_a_step": flops,
                         "flash_bytes_a_step": nbytes}
        rows = [r for r in self._counters if None not in r]
        if rows:
            sums = [float(sum(col)) for col in zip(*jax.device_get(rows))]
            moe = dict(zip(train_moe.COUNTERS, sums), steps=len(rows))
            per_layer = (moe["moe_assignments_held"] / len(rows)
                         / flops_mla.expert_layers(self.config))
            (moe["grouped_flops_a_step"],
             moe["grouped_bytes_a_step"]) = flops_mla.grouped_step_work(
                self.config, per_layer, self.cell["forward_runs"])
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

    def check(self) -> tuple:
        if self._events:
            self.log("check: the step's text, for the scopes")
            names = scope_times.op_names_of(self.step.lower(
                self._state, *self._batch).compile().as_text())
            self._record["scope_ms"] = train_moe.scope_ms(
                self._events, names, self.cell["scope_words"])
            under = {w: train_moe.scope_ms(self._events, names, [w]).get(w)
                     for w in self.cell.get("under_words", ())}
            self._record["scope_under_ms"] = {
                w: ms for w, ms in under.items() if ms is not None}
            self._events = []
        return train.TrainRun.check(self)
