"""The four set-up metrics on the toy cell: every reader reports, each part
is at least 0, the parts add up to ``setup_s``, and the compiles of the
check's reference, after the window, are not counted.

The toy manifest is the accepted one; the set-up metrics are taken from
``BENCHMARK.json`` and appended to it here."""

import pytest

from apex_tpu import observability
from benchmark import run as bench_run
from benchmark.entries import train
from benchmark.tests.test_check import SEED, TOY

if not hasattr(observability, "startup_summary"):
    pytest.skip("the program keeps no start-up ledger", allow_module_level=True)

SETUP = ("setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s",
         "setup_outside_compile_s")


@pytest.fixture(scope="module")
def manifest():
    toy = bench_run.read_json(TOY, "BENCHMARK.json")
    real = bench_run.read_json(bench_run.ROOT, "BENCHMARK.json")
    toy["per_layer"] += [m for m in real["per_layer"] if m["name"] in SETUP]
    return toy


def test_setup_splits_into_four(manifest, tmp_path, monkeypatch):
    seen = {}
    run_window = train.TrainRun.window

    def window(self, seconds, trace_dir):
        seen["setup_s"] = self.setup_s
        return run_window(self, seconds, trace_dir)

    monkeypatch.setattr(train.TrainRun, "window", window)
    result = bench_run.run_cell(manifest, "gpt2_toy_train", SEED, 0.3, True,
                                need_chip=False, bench_dir=TOY,
                                out_dir=str(tmp_path))
    parts = {m: result["metrics"][m]["value"] for m in SETUP}
    assert all(v >= 0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(seen["setup_s"], abs=1e-6)
    assert all(result["metrics"][m]["unit"] == "s" for m in SETUP)

    # the check compiled (or loaded) the reference's programs after set-up
    # ended: the ledger holds them, the metrics do not
    t0 = bench_run._T0
    whole = observability.startup_summary(since=t0)
    backend = parts["setup_compile_s"] + parts["setup_cache_load_s"]
    assert whole["compile_s"] + whole["load_s"] > backend
    late = observability.startup_summary(since=t0 + seen["setup_s"])
    assert late["compile_s"] + late["load_s"] > 0
