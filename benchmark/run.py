"""The benchmark's one command::

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration, one entry or one
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
gives it: ``workloads/<cell>.json``, ``configs/<config>.json``,
``entries/<entry>.py``, ``layer_metrics/<metric>.py``.  Nothing in this
file names any of them.

The last line of standard output is the result, one JSON object.  Without a
TPU, with fewer chips than the cell asks for, with a ``device_kind`` that
``peaks.py`` does not know, or with a compilation inside the measured
window, the run exits non-zero and prints no result.  A traced run's
profile goes under ``.bench_out/`` and is deleted once it is reduced.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # set-up is counted from here

import argparse
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# a jit cache miss lowers its function anew, also where the persistent
# cache then spares the compiler: this event counts both
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, manifest: dict, bench_dir: str = HERE) -> tuple:
    """``(cell, config)`` of the manifest's workload ``name``."""
    listed = {w["name"]: w for w in manifest["workloads"]}
    if name not in listed:
        raise SystemExit(f"benchmark: no workload {name!r} in "
                         f"BENCHMARK.json (has: {sorted(listed)})")
    cell = read_json(bench_dir, "workloads", name + ".json")
    for key in ("config", "chips"):
        if cell[key] != listed[name][key]:
            raise SystemExit(
                f"benchmark: workloads/{name}.json says {key}="
                f"{cell[key]!r}, BENCHMARK.json {listed[name][key]!r}")
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    config = read_json(os.path.dirname(bench_dir), files[cell["config"]])
    return cell, config


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The manifest's metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", [cell_name])]


def pick_devices(chips: int, need_chip: bool) -> list:
    import jax

    devices = jax.devices()
    if need_chip and devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX reports platform {devices[0].platform!r}, "
            "not a TPU; nothing was run")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chips, JAX reports "
            f"{len(devices)}; nothing was run")
    return devices[:chips]


def use_compile_cache() -> None:
    """The persistent cache at a fixed place inside the checkout (the path
    is part of its key), unless the environment already names one; every
    program goes in, however quick its compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class LoweringCounter:
    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == _LOWERING_EVENT:
            self.count += 1


def run_cell(manifest: dict, name: str, seed: int, seconds: float,
             trace: bool, *, need_chip: bool = True,
             bench_dir: str = HERE, out_dir: str = "") -> dict:
    """One run of one cell; returns the result object."""
    cell, config = load_cell(name, manifest, bench_dir)
    devices = pick_devices(cell["chips"], need_chip)
    log(f"reached {len(devices)} x {devices[0].device_kind}")
    peaks = None
    if need_chip:
        from benchmark.peaks import peaks_for

        peaks = peaks_for(devices[0].device_kind)
    use_compile_cache()
    lowerings = LoweringCounter()
    entry = importlib.import_module(f"benchmark.entries.{cell['entry']}")

    run = entry.setup(config, cell, seed, devices, _T0, log)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(out_dir or os.path.join(ROOT, ".bench_out"),
                                 f"trace_{name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    before = lowerings.count
    record = run.window(seconds, trace_dir)
    in_window = lowerings.count - before
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if in_window:
        raise SystemExit(
            f"benchmark: {in_window} program(s) were lowered inside the "
            "measured window; every shape has to be warm before it")
    record["peaks"] = peaks
    record["chips"] = len(devices)
    correct, numbers = run.check()

    if trace:
        metrics = {}
        for m in metrics_of(manifest, "per_layer", name):
            reader = importlib.import_module(
                f"benchmark.layer_metrics.{m['name']}")
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {
            m["name"]: {"value": record["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in metrics_of(manifest, "end_to_end", name)}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": bool(correct and not record["failed"]),
              "attempted": record["attempted"], "failed": record["failed"],
              "metrics": metrics, "device": device}
    reduced = record.get("trace") or {}
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = read_json(ROOT, "BENCHMARK.json")
    result = run_cell(manifest, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for number, v in result["check"].items():
        log(f"check: {number} = {v['value']:.6g} (limit {v['limit']})")
    log(f"correct = {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
