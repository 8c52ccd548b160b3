"""Read, on the chip at a cell's own size, the two readings each limit of a
training cell's check is set from (PERF.md, section 2)::

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 3 --out chiprun_out/calib.json

For every seed: the timed path's first steps against the float32 reference
(the lower reading).  For the first ``--control-seeds`` of them also the
controls (the reference in int8 and in float8 in the program's place) and
each planted fault against the same reference (the upper readings).  One
process reads them all, since set-up is most of a run.  Not part of a
benchmark run; a TPU only.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax

import run as bench_run          # also puts the checkout on sys.path


CONTROLS = ("int8", "float8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmark.entries import train
    from benchmark.reference import train as ref_train

    manifest = bench_run.read_json(bench_run.ROOT, "BENCHMARK.json")
    cell, config = bench_run.load_cell(args.workload, manifest)
    devices = bench_run.pick_devices(cell["chips"], True)
    bench_run.use_compile_cache()
    # one jitted step for all seeds: tracing an unrolled 24-layer step
    # anew for each would be most of the time
    build, built = train.build_step, {}
    train.build_step = lambda program: built.setdefault(
        json.dumps(program, sort_keys=True), build(program))
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        run = train.setup(config, cell, seed, devices, bench_run._T0,
                          bench_run.log)
        run.state = None
        batches = run.pool[:ref_train.N_STEPS]
        want = ref_train.reference_steps(run.ref, config, run.make_params,
                                         batches)
        row = {"seed": seed, "overflow": run.first_overflow,
               "program": ref_train.compare(run.first_readings(), want,
                                            detail=True)}
        if n < args.control_seeds:
            # the reference's trees wait on the host while another side
            # takes its steps on the device
            want["moment"], want["delta"] = jax.device_get(
                (want["moment"], want["delta"]))
            sides = {"control_" + p: dict(precision=p) for p in CONTROLS}
            sides.update({f: dict(fault=f) for f in ref_train.FAULTS})
            for name, kw in sides.items():
                other = ref_train.first_steps(
                    run.ref, config, run.make_params, batches, **kw)
                row[name] = ref_train.compare(other, want, detail=True)
                del other
        rows.append(row)
        bench_run.log(json.dumps(row))
        del run, want
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
