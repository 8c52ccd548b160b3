"""The benchmark's data-parallel entry (``benchmark/entries/train_ddp.py``)
at a toy size on four of the suite's virtual CPU devices: the cell
``gpt2_toy_train_dp4`` of ``benchmark/tests/toy2``, driven by
``benchmark/run.py``'s ``run_cell`` as a chip run is, but for the look for a
chip.  The benchmark's own tests (``benchmark/tests``) run in a process with
one device."""

import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run                         # noqa: E402
from benchmark.entries import train_ddp                        # noqa: E402

TOY = os.path.join(ROOT, "benchmark", "tests", "toy2")
SEED = 2 ** 31 + 3          # past 32 signed bits, as the driver's are


@pytest.fixture(autouse=True)
def _compile_cache_as_it_was():
    """``run_cell`` turns the persistent compile cache on for its process;
    the tests that share this worker get their settings back."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def _half_batch(step):
    def broken(state, *batch):
        half = batch[0].shape[0] // 2
        return step(state, *[x[:half] for x in batch])
    return broken


@pytest.mark.parametrize("fault", [None, _half_batch],
                         ids=["sound", "half_batch"])
def test_data_parallel_entry(fault, tmp_path, monkeypatch):
    """The state replicated over four devices, each batch split by rows:
    correct against the float32 reference on the global batch; with half
    of the rows left out, not correct."""
    if fault is not None:
        make = train_ddp.make_step

        def make_broken(*args):
            init, step = make(*args)
            return init, fault(step)

        monkeypatch.setattr(train_ddp, "make_step", make_broken)
    manifest = bench_run.read_json(TOY, "BENCHMARK.json")
    result = bench_run.run_cell(
        manifest, "gpt2_toy_train_dp4", SEED, 0.3, False, need_chip=False,
        bench_dir=TOY, out_dir=str(tmp_path))
    assert result["device"]["count"] == 4 <= len(jax.devices())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] == (fault is None), result["check"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
