"""Read, on the chip at a serving cell's own size, what its rate and its
check's limits are set from (PERF.md, sections 2 and 4)::

    python3 benchmark/calibrate_serve.py --workload <cell> --knee 8,12,16 \\
        --seconds 10 --out out/knee.json
    python3 benchmark/calibrate_serve.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 3 --seconds 10 --out out/serve_calib.json
    python3 benchmark/calibrate_serve.py --workload <cell> --ops \\
        --seconds 4 --out out/ops.json

``--knee``: for each rate in turn, a fresh engine offered the cell's mix at
that rate (seed 0) through the fill and the window: the output tokens
completed a second, the engine's queue when the window opens and when it
closes, its step time and its lanes in use.  The knee is the highest rate
at which the queue does not grow over the window.

``--seeds``: for every seed a whole run of the cell and its check (the
lower readings).  For the first ``--control-seeds`` of them also: the
control, the reference in int8 in the program's place on the rows that
seed's check compared (``reference/serve.py``'s ``control_numbers``, in
int8 and in float8), judged by the cell's limits; the program's own int8 paths, each through
the same window and the same ``check``: its int8 K/V pool
(``cache_wire="int8"``), int8 weights (``models.quantized.quantize_params``)
and both; and the program with each of ``serve_faults.FAULTS`` planted.

``--ops``: one traced window; every device operation's own time, to name
the decode kernel (``decode_kernel.ops`` in the cell file).

One process reads them all.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import run as bench_run          # also puts the checkout on sys.path


def _setup(cell, config, seed, devices, prepare=None):
    from benchmark.entries import serve

    return serve.ServeRun(config, cell, seed, devices[0], bench_run._T0,
                          bench_run.log, prepare=prepare)


def knee(cell, config, devices, rates, seconds) -> list:
    from benchmark.layer_metrics import serve_batch_occupancy, serve_step_ms

    rows = []
    for rate in rates:
        mix = dict(cell["traffic_mix"])
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=rate)
        run = _setup(dict(cell, traffic_mix=mix), config, 0, devices)
        record = run.window(seconds, None)
        serve = record["serve"]
        row = {"rate_per_s": rate,
               "serve_tokens_per_s":
                   record["end_to_end"]["serve_tokens_per_s"],
               "completed": record["attempted"] - record["failed"],
               "queued_at_open": int(serve["queued_after"][0]),
               "queued_at_close": int(serve["queued_after"][-1]),
               "step_ms": serve_step_ms.read(record),
               "occupancy_pct": serve_batch_occupancy.read(record),
               "prefill_ms_median": float(
                   sorted(serve["prefill_ms"])[len(serve["prefill_ms"]) // 2])
               if serve["prefill_ms"] else None,
               "memory_peak_bytes": record["memory_peak_bytes"]}
        rows.append(row)
        bench_run.log(json.dumps(row))
        run.free()
        del run
        gc.collect()
    return rows


def _read(cell, config, seed, devices, seconds, prepare=None,
          control=False, steps=False) -> dict:
    from benchmark.entries import serve
    from benchmark.reference import serve as ref_serve
    from benchmark.reference import train as ref_train

    run = _setup(cell, config, seed, devices, prepare)
    record = run.window(seconds, None)
    rows = run.sample()
    correct, numbers = run.check()
    out = {"correct": bool(correct and not record["failed"]),
           "failed": record["failed"],
           "serve_tokens_per_s": record["end_to_end"]["serve_tokens_per_s"],
           "numbers": {k: v["value"] for k, v in numbers.items()}}
    if steps:
        out["steps"] = {k: [float(x) for x in record["serve"][k]]
                        for k in ("step_start_s", "step_s", "admitted",
                                  "decoded_lanes", "queued_after", "ctx_sum")}
    for fmt in ("int8", "float8") if control else ():
        numbers = ref_serve.control_numbers(
            run.make_reference_params(), config, rows, serve._length(rows),
            fmt)
        limits = {k: v for k, v in cell["check"]["limits"].items()
                  if k in numbers}
        out["control_reference_" + fmt] = {
            "correct": ref_train.judge(numbers, limits)[0],
            "numbers": numbers}
    del run
    gc.collect()
    return out


def readings(cell, config, devices, seeds, control_seeds, seconds,
             reference_only=False) -> list:
    from apex_tpu.models.quantized import quantize_params
    from benchmark import serve_faults

    wire = dict(cell, engine=dict(cell["engine"], cache_wire="int8"))
    rows = []
    for n, seed in enumerate(seeds):
        row = {"seed": seed,
               "program": _read(cell, config, seed, devices, seconds,
                                control=n < control_seeds, steps=True)}
        bench_run.log(json.dumps({k: v for k, v in row["program"].items()
                                  if k != "steps"}))
        if n < control_seeds and not reference_only:
            for name, c, prepare in (
                    ("cache_wire_int8", wire, None),
                    ("weights_int8", cell, quantize_params),
                    ("weights_and_cache_wire_int8", wire, quantize_params)):
                row[name] = _read(c, config, seed, devices, seconds, prepare)
                bench_run.log(json.dumps({name: row[name]}))
            for name, (where, wrap) in serve_faults.FAULTS.items():
                with serve_faults.plant(where, wrap):
                    row[name] = _read(cell, config, seed, devices, seconds)
                bench_run.log(json.dumps({name: row[name]}))
        rows.append(row)
    return rows


def op_times(cell, config, devices, seconds, out_dir) -> dict:
    from benchmark import trace_reduce

    run = _setup(cell, config, 1, devices)
    trace_dir = os.path.join(out_dir, "ops_trace")
    run.window(seconds, trace_dir)
    events = trace_reduce.load_events(trace_reduce.find_xplane(trace_dir))
    own = trace_reduce._self_times(
        [e for e in events if e.line == trace_reduce.OPS_LINE])
    return {name: ns / 1e9 for name, ns in sorted(
        own.items(), key=lambda kv: -kv[1])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--knee")
    ap.add_argument("--seeds")
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--reference-controls-only", action="store_true",
                    help="of the controls, read the reference's alone")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--bench-dir", default=bench_run.HERE)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU at a toy size")
    args = ap.parse_args(argv)

    manifest = bench_run.read_json(
        args.bench_dir, "BENCHMARK.json") if args.bench_dir != bench_run.HERE \
        else bench_run.read_json(bench_run.ROOT, "BENCHMARK.json")
    cell, config = bench_run.load_cell(args.workload, manifest,
                                       args.bench_dir)
    devices = bench_run.pick_devices(cell["chips"], not args.cpu)
    bench_run.use_compile_cache()
    if args.knee:
        out = knee(cell, config, devices,
                   [float(r) for r in args.knee.split(",")], args.seconds)
    elif args.seeds:
        out = readings(cell, config, devices,
                       [int(s) for s in args.seeds.split(",")],
                       args.control_seeds, args.seconds,
                       args.reference_controls_only)
    else:
        out = op_times(cell, config, devices, args.seconds,
                       os.path.dirname(os.path.abspath(args.out)))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
