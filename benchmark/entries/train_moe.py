"""The ``train_moe`` entry: ``entries/train.py``'s ``TrainRun``, unchanged,
for a configuration whose expert layers count their assignments
(``apex_tpu/models/hybrid.py`` ``MOE_COUNTERS``, in the step's own outputs).

After the window it puts into the record what the mixture-of-experts
per-layer metrics read:

- ``flops_per_token``: the configuration's own count (``flops_moe.py``;
  ``flops.py`` knows one kind of layer only);
- ``moe``: the step's counters, summed over the window's steps, with the
  steps counted;
- ``scope_ms`` (traced runs): device milliseconds a step under each of the
  cell file's own ``scope_words``, an operation's own time going to the
  innermost of these words in its ``op_name``.  The names come from the
  compiled step's text, which is lowered in ``check``, after the window has
  closed (a lowering inside it would end the run).

A program without the counters or the scopes leaves these out and nothing
here raises: the metrics' readers then return nothing.
"""

from __future__ import annotations

import jax

from benchmark import flops_moe, scope_times, trace_reduce
from benchmark.entries import train

COUNTERS = ("moe_assignments_held", "moe_assignments",
            "moe_held_load_max", "moe_held_load_mean")


def setup(config: dict, cell: dict, seed: int, devices: list, t0: float,
          log) -> "MoeTrainRun":
    if cell["chips"] != 1 or len(devices) != 1:
        raise SystemExit("benchmark: the train_moe entry drives one chip")
    return MoeTrainRun(config, cell, seed, devices[0], t0, log)


def _struct(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), tree)


def scope_ms(events: list, op_names: dict, words: list) -> dict:
    """Own device milliseconds per step under each of ``words``: every
    operation goes to the innermost of them in its ``op_name``."""
    ops = [e for e in events if e.line == trace_reduce.OPS_LINE]
    planes = sorted({e.plane for e in ops})
    steps = sum(e.line == scope_times.MODULES_LINE for e in events)
    if not planes or not steps:
        return {}
    known = set(words)

    def word_of(name: str) -> str:
        for part in reversed(op_names.get(name, "").split("/")):
            word = scope_times._TRANSFORMS.sub("", part)
            if word in known:
                return word
        return ""

    total: dict = {}
    for plane in planes:
        named = [trace_reduce.Event(e.plane, e.line, word_of(e.name),
                                    e.start_ns, e.dur_ns)
                 for e in ops if e.plane == plane]
        for word, ns in trace_reduce._self_times(named).items():
            if word:
                total[word] = total.get(word, 0.0) + ns
    return {w: ns / 1e6 / steps for w, ns in total.items()}


class MoeTrainRun(train.TrainRun):
    def __init__(self, *args):
        self._counters, self._events, self._record = [], [], None
        super().__init__(*args)

    def _feed_and_step(self):
        metrics, seconds = super()._feed_and_step()
        self._counters.append([metrics.get(k) for k in COUNTERS])
        return metrics, seconds

    def window(self, seconds: float, trace_dir) -> dict:
        self._counters = []
        record = super().window(seconds, trace_dir)
        record["flops_per_token"] = flops_moe.train_flops_per_token(
            self.config, self.cell["seq"])
        rows = [r for r in self._counters if None not in r]
        if rows:
            sums = [float(sum(col)) for col in zip(*jax.device_get(rows))]
            moe = dict(zip(COUNTERS, sums), steps=len(rows))
            per_layer = (moe["moe_assignments_held"] / len(rows)
                         / flops_moe.expert_layers(self.config))
            (moe["grouped_flops_a_step"],
             moe["grouped_bytes_a_step"]) = flops_moe.grouped_step_work(
                self.config, per_layer, self.cell["forward_runs"])
            record["moe"] = moe
        if trace_dir is not None and record.get("trace"):
            # the operations, named by instruction; their scopes are
            # looked up in check(), once the window has closed
            self._events = scope_times.load_events(
                trace_reduce.find_xplane(trace_dir), {})
            self._batch = _struct(jax.device_put(self.pool[0], self.device))
            self._state = _struct(self.state)
        self._record = record
        return record

    def check(self) -> tuple:
        if self._events:
            self.log("check: the step's text, for the scopes")
            text = self.step.lower(
                self._state, *self._batch).compile().as_text()
            self._record["scope_ms"] = scope_ms(
                self._events, scope_times.op_names_of(text),
                self.cell["scope_words"])
            self._events = []
        return super().check()
