"""From a profiler trace to device time per ``(pass, phase)`` of the train
step, in the program's own words: the ``jax.named_scope`` vocabulary that
``apex_tpu`` puts over the whole jitted step (``docs/observability.md``).
Kept with the benchmark, beside ``trace_reduce.py``, whose rule for an
operation's own time it uses: an event's span less what is nested in it.

Every HLO instruction carries the scopes it was traced under in its
``op_name``, outermost first
(``jit(step_fn)/transpose(jvp(model))/backbone/while/body/closed_call/
checkpoint/rematted_computation/attention/qkv/dot_general``), through
``jvp``, ``transpose``, ``checkpoint`` and ``scan``.  From it:

- ``pass``: ``recompute`` if it holds ``rematted_computation``, else
  ``backward`` if ``transpose(``, else ``forward`` if ``jvp(``, else
  ``update`` (unscale, optimizer, the add, the casts);
- ``phase``: the innermost word of ``VOCABULARY`` in it.  Where that word is
  ``backbone`` the operation is ``scan_plumbing``: the slices, updates and
  copies that stacking every layer's weights, gradients and residuals brings,
  scanned or unrolled.  A ``while``'s own time, what its body's operations do
  not cover, is ``scan_gaps``.  The rest is ``unscoped`` and listed by the
  instruction's name less its number.

Where ``op_name`` comes from: the events of a device plane's ``XLA Ops`` line
are named by their instruction, and ``jax.profiler.ProfileData`` shows no
``op_name`` with them, so the names are looked up in the compiled step's HLO
text (``step.lower(...).compile().as_text()``: a cache load where the step has
run), which holds ``metadata={op_name="..."}`` for every instruction.

What the table cannot tell apart: a fused instruction has one ``op_name``, its
root's or its matmul's.  The compiler fuses across scopes (fc1's matmul and
GELU are a producer inside fc2's fusion in the forward pass; Adam, the add,
the overflow select and the bf16 cast of one leaf are one fusion), so a
phase's time is that of the instructions that carry its name.  Shares are of
device time, not of FLOPs.

    python3 benchmark/scope_times.py <trace_dir> --hlo step.hlo.txt[.gz]
        [--record benchmark/fixtures/scopes/<name>.json.gz --share 0.2]
    python3 benchmark/scope_times.py --workload <cell> --seed <n> --out <dir>

The second form needs the chip: it sets the cell up as ``run.py`` does, traces
the entry's slice of steps after a short window, writes the step's HLO text
beside the trace and prints the table.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys
from typing import Iterable, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import trace_reduce

MODULES_LINE = "XLA Modules"
PASSES = ("forward", "recompute", "backward", "update")
# the program's scopes, docs/observability.md; ``model`` and ``backbone``
# only hold other scopes
VOCABULARY = frozenset((
    "cast_params", "amp_unscale", "grad_reduce", "amp_scale_update",
    "optimizer", "trust_ratio", "apply_update",
    "embed", "embedding_ln", "ln1", "attention", "qkv", "core_attention",
    "proj", "residual", "ln2", "mlp", "fc1", "fc2", "final_ln",
    "lm_head_ce", "mlm_head", "nsp_head",
    "flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv",
    "layer_norm_fwd", "layer_norm_bwd"))
CONTAINERS = frozenset(("model", "backbone"))
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRANSFORMS = re.compile(r"^(?:\w+\()+|\)+$")


class ScopedEvent(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    op_name: str


def op_names_of(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` ("" where it has none) for every
    instruction of an HLO module's text; the names are unique in a module."""
    found = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            found[m.group(1)] = op.group(1) if op else ""
    return found


def classify(name: str, op_name: str) -> tuple:
    """``(pass, phase)`` of the instruction ``name`` with this ``op_name``."""
    if "rematted_computation" in op_name:
        which = "recompute"
    elif "transpose(" in op_name:
        which = "backward"
    elif "jvp(" in op_name:
        which = "forward"
    else:
        which = "update"
    stem = trace_reduce._stem(name)
    if stem == "while":
        return which, "scan_gaps"
    for part in reversed(op_name.split("/")):
        word = _TRANSFORMS.sub("", part)       # jvp(cast_params)
        if word in VOCABULARY:
            return which, word
        if word == "backbone":
            return which, "scan_plumbing"
    return which, "unscoped:" + stem


def load_events(xplane_path: str, op_names: dict) -> list:
    """The device planes' operations, each with its ``op_name``, and their
    ``XLA Modules`` events (one per executed step)."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name not in (trace_reduce.OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = trace_reduce._short(ev.name)
                events.append(ScopedEvent(
                    plane.name, line.name, name, float(ev.start_ns),
                    float(ev.duration_ns), op_names.get(name, "")))
    return events


def reduce_events(events: Iterable[ScopedEvent]) -> dict:
    """Own device seconds per ``(pass, phase)``, keyed ``"pass/phase"``,
    summed per pass, and per step of the slice; ``{}`` without a device
    plane.  ``busy_s`` is ``trace_reduce``'s, which the times add up to
    where the trace's events nest."""
    events = list(events)
    ops = [e for e in events if e.line == trace_reduce.OPS_LINE]
    planes = sorted({e.plane for e in ops})
    if not planes:
        return {}
    steps = sum(e.line == MODULES_LINE for e in events) / len(planes)
    own: dict = {}
    for plane in planes:
        # an operation's own time by trace_reduce's rule, summed under the
        # name it is handed: here the operation's pass and phase
        named = [trace_reduce.Event(e.plane, e.line,
                                    "/".join(classify(e.name, e.op_name)),
                                    e.start_ns, e.dur_ns)
                 for e in ops if e.plane == plane]
        for key, ns in trace_reduce._self_times(named).items():
            own[key] = own.get(key, 0.0) + ns / len(planes) / 1e9
    busy_s = trace_reduce.reduce_events(
        trace_reduce.Event(*e[:5]) for e in ops)["busy_s"]
    times, unscoped = {}, {}
    for key, s in own.items():
        which, phase = key.split("/", 1)
        if phase.startswith("unscoped:"):
            unscoped[f"{which}/{phase[9:]}"] = s
            key = which + "/unscoped"
        times[key] = times.get(key, 0.0) + s
    by_pass = {p: sum(s for k, s in times.items() if k.startswith(p + "/"))
               for p in PASSES}
    by_phase = dict(sorted(times.items(), key=lambda kv: -kv[1]))

    def ms_per_step(seconds: dict) -> dict:
        return {k: 1e3 * s / steps if steps else None
                for k, s in seconds.items()}

    return {
        "busy_s": busy_s,
        "steps": steps,
        "pass_s": by_pass,
        "pass_ms_per_step": ms_per_step(by_pass),
        "phase_s": by_phase,
        "phase_ms_per_step": ms_per_step(by_phase),
        "unscoped_s": dict(sorted(unscoped.items(), key=lambda kv: -kv[1])),
    }


def read_text(path: str) -> str:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return f.read()


def reduce_trace(trace_dir: str, hlo_text: str) -> dict:
    return reduce_events(load_events(trace_reduce.find_xplane(trace_dir),
                                     op_names_of(hlo_text)))


def table(reduced: dict) -> str:
    """The reduction as lines of text: passes, then phases by time."""
    busy, steps = reduced["busy_s"], reduced["steps"]
    total = sum(reduced["phase_s"].values())
    rows = [f"busy {busy:.6f} s over {steps:g} steps; (pass, phase) times "
            f"add to {total:.6f} s ({100 * (total / busy - 1):+.4f}%)",
            f"{'pass/phase':<34}{'ms a step':>12}{'share %':>10}"]
    for group in ("pass_s", "phase_s", "unscoped_s"):
        rows.append(f"-- {group[:-2]}")
        for key, s in reduced[group].items():
            per = f"{1e3 * s / steps:12.3f}" if steps else f"{'':>12}"
            rows.append(f"{key:<34}{per}{100 * s / busy:10.2f}")
    return "\n".join(rows)


def trace_cell(name: str, seed: int, seconds: float, out_dir: str) -> tuple:
    """``(trace_dir, hlo_path)``: the cell set up as ``run.py`` sets it up,
    a short window, the entry's traced slice, and the step's HLO text
    (outside any timed window).  Needs the chip."""
    import importlib
    import time

    import jax

    from benchmark import run as bench_run

    manifest = bench_run.read_json(bench_run.ROOT, "BENCHMARK.json")
    cell, config = bench_run.load_cell(name, manifest)
    devices = bench_run.pick_devices(cell["chips"], need_chip=True)
    bench_run.use_compile_cache()
    entry = importlib.import_module(f"benchmark.entries.{cell['entry']}")
    run = entry.setup(config, cell, seed, devices, time.perf_counter(),
                      bench_run.log)
    trace_dir = os.path.join(out_dir, f"trace_{name}")
    run.window(seconds, trace_dir)
    batch = jax.device_put(run.pool[0], run.device)
    text = run.step.lower(run.state, *batch).compile().as_text()
    hlo_path = os.path.join(out_dir, f"{name}.hlo.txt.gz")
    with gzip.open(hlo_path, "wt") as f:
        f.write(text)
    return trace_dir, hlo_path


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?")
    ap.add_argument("--hlo", help="the compiled step's HLO text (.gz or not)")
    ap.add_argument("--workload", help="trace this cell on the chip first")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(HERE), ".bench_out"))
    ap.add_argument("--record", help="write a fixture of the slice's "
                    "leading --share of events, with each event's op_name")
    ap.add_argument("--share", type=float, default=0.2)
    ap.add_argument("--json", action="store_true",
                    help="print the reduction as JSON, not as a table")
    args = ap.parse_args(argv)
    if args.workload:
        args.trace_dir, args.hlo = trace_cell(
            args.workload, args.seed, args.seconds, args.out)
    if not (args.trace_dir and args.hlo):
        ap.error("give a trace directory and --hlo, or --workload")
    events = load_events(trace_reduce.find_xplane(args.trace_dir),
                         op_names_of(read_text(args.hlo)))
    reduced = reduce_events(events)
    print(json.dumps(reduced, indent=1) if args.json else table(reduced))
    if args.record:
        t0 = min(e.start_ns for e in events)
        t1 = max(e.start_ns + e.dur_ns for e in events)
        cut = t0 + args.share * (t1 - t0)
        kept = [[e.plane, e.line, e.name, e.start_ns - t0, e.dur_ns,
                 e.op_name] for e in events
                if e.start_ns + e.dur_ns <= cut]
        os.makedirs(os.path.dirname(os.path.abspath(args.record)),
                    exist_ok=True)
        with gzip.open(args.record, "wt") as f:
            json.dump({"events": kept, "expect": reduce_events(
                ScopedEvent(*k) for k in kept)}, f)
        print(f"recorded {len(kept)} events to {args.record}")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
