"""CPU rehearsal of the benchmark, run by hand::

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py

1. ``BENCHMARK.json`` keeps to the contract's limits that can be checked
   here, and every name in it resolves to a file under ``benchmark/``.
2. ``trace_reduce`` gives, on the recorded events in ``fixtures/``, the busy
   time, idle share, top operations and gaps written beside them.
3. ``benchmark/tests``: the entry's control flow at a 2-layer toy size
   (``tests/toy``), with and without a trace, the references against the
   program there, the controls and the planted faults.

It prints no device metric and is not the measurement path: ``run.py``
itself still refuses a CPU.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run          # also puts the checkout on sys.path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_manifest() -> list:
    m = bench_run.read_json(bench_run.ROOT, "BENCHMARK.json")
    bad = []

    def need(ok, what):
        if not ok:
            bad.append(what)

    need(set(m) == {"command", "paths", "run_seconds", "configs",
                    "workloads", "end_to_end", "per_layer"}, "top-level keys")
    need(1 <= m["run_seconds"] <= 51, "run_seconds")
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s to compile,
    # 1200 s spare, inside 43200 s: the longest run_seconds later PRs
    # can still fill all 24 cells with
    need((2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200,
         "run_seconds does not fit a full check of 24 cells")
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        need(NAME.match(c["name"]), f"config name {c['name']}")
        need(os.path.isfile(os.path.join(bench_run.ROOT, c["file"])),
             f"config file {c['file']}")
        cfg = bench_run.read_json(bench_run.ROOT, c["file"])
        need(cfg["source"] == c["source"], f"{c['name']}: source differs")
        need(cfg["reduced"] == c["reduced"], f"{c['name']}: reduced differs")
        need(os.path.isfile(os.path.join(
            bench_run.HERE, "reference", cfg["reference"]["model"] + ".py")),
            f"{c['name']}: reference module")
    cells = {w["name"] for w in m["workloads"]}
    need(sum(w["chips"] == 4 for w in m["workloads"])
         <= max(1, len(cells) // 4), "too many four-chip cells")
    for w in m["workloads"]:
        need(NAME.match(w["name"]) and NAME.match(w["traffic"]),
             f"cell name {w['name']}")
        need(w["config"] in configs, f"{w['name']}: config")
        need(len(w["why"]) <= 200, f"{w['name']}: why over 200 characters")
        cell, _ = bench_run.load_cell(w["name"], m)
        need(cell["traffic"] == w["traffic"], f"{w['name']}: traffic")
        need(os.path.isfile(os.path.join(
            bench_run.HERE, "entries", cell["entry"] + ".py")),
            f"{w['name']}: entry {cell['entry']}")
        need(cell["check"]["limits"], f"{w['name']}: no limits")
    need({c["name"] for c in m["configs"]}
         == {w["config"] for w in m["workloads"]}, "a config has no cell")
    e2e = {x["name"]: x for x in m["end_to_end"]}
    need("setup_s" in e2e, "setup_s missing")
    for x in m["end_to_end"]:
        need(0 < x["bound"] <= 0.1, f"{x['name']}: bound")
        need(x["source"] in ("host_clock", "device_trace"),
             f"{x['name']}: source")
    for x in m["end_to_end"] + m["per_layer"]:
        need(NAME.match(x["name"]) and UNIT.match(x["unit"]),
             f"metric {x['name']}")
        need(x["better"] in ("lower", "higher"), f"{x['name']}: better")
        need(set(x.get("workloads", [])) <= cells, f"{x['name']}: cells")
    for x in m["per_layer"]:
        need(x["moves"] in e2e, f"{x['name']}: moves")
        need(os.path.isfile(os.path.join(
            bench_run.HERE, "layer_metrics", x["name"] + ".py")),
            f"{x['name']}: reader")
    need(len(json.dumps(m)) < 64 * 1024, "manifest over 64 KiB")
    return bad


def check_fixtures() -> list:
    from benchmark import trace_reduce

    bad = []
    paths = sorted(glob.glob(os.path.join(bench_run.HERE, "fixtures",
                                          "*.json.gz")))
    if not paths:
        bad.append("no fixture under benchmark/fixtures")
    for path in paths:
        with gzip.open(path, "rt") as f:
            fixture = json.load(f)
        got = trace_reduce.reduce_events(
            trace_reduce.Event(*e) for e in fixture["events"])
        if json.loads(json.dumps(got)) != fixture["expect"]:
            bad.append(f"{os.path.basename(path)}: reduction differs")
        print(f"fixture {os.path.basename(path)}: "
              f"{len(fixture['events'])} events, "
              f"{len(got['device_ops'])} top operations, "
              f"{len(got['idle_gaps'])} gaps")
    return bad


def check_toy_runs() -> list:
    import pytest

    rc = pytest.main([os.path.join(bench_run.HERE, "tests"), "-q",
                      "-p", "no:cacheprovider"])
    return [f"benchmark/tests: pytest exit code {int(rc)}"] if rc else []


def main() -> int:
    import jax

    if jax.devices()[0].platform != "cpu":
        raise SystemExit("selfcheck is the CPU rehearsal; set "
                         "JAX_PLATFORMS=cpu")
    bad = check_manifest() + check_fixtures() + check_toy_runs()
    for line in bad:
        print("FAIL:", line)
    print("selfcheck:", "FAILED" if bad else "ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
