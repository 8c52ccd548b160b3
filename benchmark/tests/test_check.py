"""What decides ``correct`` has been shown to fail.

- The controls: the float32 reference put in the program's place and
  computed in int8 (the precision below the configurations' bfloat16 that
  a TPU v5e has hardware for) or by the fp8 training recipe comes out as
  not correct.
- Each fault a training step can have, planted under the harness with the
  look for a chip skipped and the rest of a run driven as it is: a step that
  returns its state unchanged; half of the batch left out, the mean taken
  over the rest.  ``correct`` comes out false.
- A sound run comes out correct, on the same seeds.

Toy sizes (two layers, hidden 128) with limits of their own, read on the
CPU; the cells' limits are read on the chip at the cells' sizes (PERF.md).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as bench_run
from benchmark.entries import train
from benchmark.reference import train as ref_train

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELLS = ["gpt2_toy_train", "bert_toy_pretrain"]
SEED = 2 ** 31 + 3          # past 32 signed bits, as the driver's are


@pytest.fixture(scope="module")
def manifest():
    return bench_run.read_json(TOY, "BENCHMARK.json")


def _run(manifest, cell, tmp_path, trace=False):
    return bench_run.run_cell(manifest, cell, SEED, 0.3, trace,
                              need_chip=False, bench_dir=TOY,
                              out_dir=str(tmp_path))


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(manifest, cell, trace, tmp_path):
    """The whole of a run but the look for a chip, with and without the
    traced slice (a CPU trace has no device plane: the readers that need
    one return nothing and their metrics are left out)."""
    result = _run(manifest, cell, tmp_path, trace)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    if trace:
        assert {"step_dispatch_ms", "overflow_skipped_share"} <= set(
            result["metrics"])
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("control", ["int8", "float8"])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(manifest, cell, control):
    cell_file, config = bench_run.load_cell(cell, manifest, TOY)
    model = ref_train.model_module(config["reference"]["model"])
    key = train.seed_key(SEED)
    batches = train.traffic.make_pool(
        cell_file, config["vocab_size"], SEED)[:ref_train.N_STEPS]
    args = (config["reference"], config,
            lambda: model.init_params(key, config), batches)
    correct, numbers = ref_train.judge(
        ref_train.compare(ref_train.first_steps(*args, precision=control),
                          ref_train.reference_steps(*args)),
        cell_file["check"]["limits"])
    assert not correct, numbers


def _state_unchanged(step):
    def broken(state, *batch):
        _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), *batch)
        return state, metrics
    return broken


def _half_batch(step):
    def broken(state, *batch):
        half = batch[0].shape[0] // 2
        return step(state, *[x[:half] for x in batch])
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(manifest, cell, fault, tmp_path, monkeypatch):
    build = train.build_step

    def build_broken(program):
        init, step = build(program)
        return init, fault(step)

    monkeypatch.setattr(train, "build_step", build_broken)
    result = _run(manifest, cell, tmp_path)
    assert not result["correct"], result["check"]


def test_lowering_inside_the_window_is_refused(manifest, tmp_path,
                                               monkeypatch):
    """A shape that is not warm when the window opens ends the run."""
    build = train.build_step

    def build_cold(program):
        init, step = build(program)
        calls = []

        def cold(state, *batch):
            calls.append(1)
            if len(calls) == ref_train.N_STEPS + 2:
                jax.jit(lambda x: x * 3 + len(calls))(jnp.ones(7))
            return step(state, *batch)
        return init, cold

    monkeypatch.setattr(train, "build_step", build_cold)
    with pytest.raises(SystemExit, match="lowered inside"):
        _run(manifest, CELLS[0], tmp_path)


def test_no_chip_no_run(manifest):
    with pytest.raises(SystemExit, match="not a TPU"):
        bench_run.run_cell(manifest, CELLS[0], 1, 0.1, False, bench_dir=TOY)
