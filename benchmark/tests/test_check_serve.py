"""The serve entry at a toy size (two layers, hidden 128, ``tests/toy5``):
a whole run but the look for a chip, what decides its ``correct`` shown to
fail, and the traffic shown to repeat.

- A sound run comes out correct, with and without the traced slice, and
  prints its end-to-end metrics, ``attempted`` and ``failed``.
- The control, the float32 reference put in the program's place and
  computed in float8 (e4m3, one scale a row of each operand), comes out as
  not correct on the rows a sound run served; so does the program with
  its own int8 weights and int8 K/V pool switched on, which serves in
  another type than the cell states.
- Each fault a served token can have, planted in the program
  (``serve_faults.py``): a token altered where it is produced, the decode
  step's K/V not written (the state returned unchanged), one layer's K/V
  not written, the decode position off by one.  ``correct`` comes out
  false.
- A program lowered inside the window ends the run.
- A seed gives the same schedule every time, and every seed the same set
  of gaps and sizes.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark import serve_faults, serve_traffic
from benchmark.entries import serve

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy5")
CELL = "gpt2_toy_serve"
SEED = 2 ** 31 + 3          # past 32 signed bits, as a run's seed may be
SECONDS = 1.0


@pytest.fixture(scope="module")
def manifest():
    return bench_run.read_json(TOY, "BENCHMARK.json")


def _run(manifest, tmp_path, trace=False):
    return bench_run.run_cell(manifest, CELL, SEED, SECONDS, trace,
                              need_chip=False, bench_dir=TOY,
                              out_dir=str(tmp_path))


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(manifest, trace, tmp_path):
    result = _run(manifest, tmp_path, trace)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    if trace:
        # a CPU trace has no device plane: the readers that need one
        # return nothing and their metrics are left out
        assert {"serve_step_ms", "serve_prefill_ms",
                "serve_batch_occupancy",
                "setup_outside_compile_s"} <= set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "check"


def test_control_is_not_correct(manifest):
    """The float8 reference in the program's place, judged by the cell's
    limits."""
    from benchmark.reference import serve as ref_serve
    from benchmark.reference import train as ref_train

    cell, config = bench_run.load_cell(CELL, manifest, TOY)
    run = serve.setup(config, cell, SEED, jax.devices()[:1], 0.0,
                      lambda msg: None)
    run.window(SECONDS, None)
    rows = run.sample()
    assert run.check()[0]
    numbers = ref_serve.control_numbers(run.make_reference_params(), config,
                                        rows, serve._length(rows), "float8")
    limits = {k: v for k, v in cell["check"]["limits"].items()
              if k in numbers}
    assert not ref_train.judge(numbers, limits)[0], numbers


def test_program_in_int8_is_not_correct(manifest):
    """The program with its own int8 paths switched on (weights,
    ``models.quantized.quantize_params``; K/V pool, ``cache_wire``), through
    the same window and check."""
    from apex_tpu.models.quantized import quantize_params

    cell, config = bench_run.load_cell(CELL, manifest, TOY)
    cell = dict(cell, engine=dict(cell["engine"], cache_wire="int8"))
    run = serve.ServeRun(config, cell, SEED, jax.devices()[0], 0.0,
                         lambda msg: None, prepare=quantize_params)
    record = run.window(SECONDS, None)
    correct, numbers = run.check()
    assert record["failed"] == 0
    assert not correct and numbers["precision_departures"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(serve_faults.FAULTS))
def test_fault_is_not_correct(manifest, fault, tmp_path):
    with serve_faults.plant(*serve_faults.FAULTS[fault]):
        result = _run(manifest, tmp_path)
    assert result["failed"] == 0
    assert not result["correct"], result["check"]


def test_lowering_inside_the_window_is_refused(manifest, tmp_path):
    """A shape that is not warm when the window opens ends the run."""
    calls = []

    def cold(step, vocab):
        def broken(*args):
            calls.append(1)
            if len(calls) > 60:
                jax.jit(lambda x: x * 3 + len(calls))(jnp.ones(7))
            return step(*args)
        return broken

    with serve_faults.plant("step", cold):
        with pytest.raises(SystemExit, match="lowered inside"):
            _run(manifest, tmp_path)


def test_schedule_repeats_for_a_seed(manifest):
    cell, config = bench_run.load_cell(CELL, manifest, TOY)
    mix, vocab = cell["traffic_mix"], config["vocab_size"]
    one, again = (serve_traffic.make_schedule(mix, vocab, SEED, 10.0)
                  for _ in range(2))
    other = serve_traffic.make_schedule(mix, vocab, SEED + 1, 10.0)
    assert np.array_equal(one.due_s, again.due_s)
    assert np.array_equal(one.output_tokens, again.output_tokens)
    assert all(np.array_equal(a, b)
               for a, b in zip(one.prompts, again.prompts))
    # another seed: the same gaps and sizes in another order
    assert np.allclose(np.sort(np.diff(one.due_s[8:])),
                       np.sort(np.diff(other.due_s[8:])))
    assert sorted(map(len, one.prompts)) == sorted(map(len, other.prompts))
    assert sorted(one.output_tokens) == sorted(other.output_tokens)
    assert not np.array_equal(one.due_s, other.due_s)
    fill, rate = mix["fill"], mix["arrivals"]["rate_per_s"]
    k = fill["requests"]
    # the fill first (submitted one a step, no due time), then the arrivals
    # from the fill's seconds before the window to its close
    assert np.isneginf(one.due_s[:k]).all()
    assert len(one.due_s) == k + round(rate * (fill["seconds"] + 10.0))
    assert one.due_s[k] == -fill["seconds"]
    assert np.all(np.diff(one.due_s[k:]) >= 0) and one.due_s[-1] < 10.0
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(p) <= hi for p in one.prompts)
    assert all(0 <= p.min() and p.max() < vocab for p in one.prompts)


def test_lognormal_lengths_follow_their_spec():
    """A spread lognormal: the median at the median, clipped at both ends,
    every seed the same multiset; sigma 0 gives the median to all."""
    spec = {"median": 64, "sigma": 0.7, "min": 16, "max": 256}
    lengths = serve_traffic.lognormal_lengths(spec, 401)
    assert np.median(lengths) == 64
    assert lengths.min() == 16 and lengths.max() == 256
    fixed = serve_traffic.lognormal_lengths(dict(spec, sigma=0.0), 9)
    assert (fixed == 64).all()


def test_no_chip_no_run(manifest):
    with pytest.raises(SystemExit, match="not a TPU"):
        bench_run.run_cell(manifest, CELL, 1, 0.1, False, bench_dir=TOY)
