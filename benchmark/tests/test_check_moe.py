"""The ``train_moe`` entry and the LFM2-MoE reference at a toy size on the
CPU (``tests/toy2``: the cell's pattern, 1 dense + 4 expert layers, 4 of
16 experts held): a sound run comes out correct and carries the counters'
metrics; the int8 and float8 controls and both planted faults come out not
correct.  ``test_check.py`` says what each of these is.  (The ``train_ddp``
entry's toy cell, ``gpt2_toy_train_dp4`` in the same manifest, needs four
devices: ``tests/test_benchmark_entries.py``, tier 1, runs it on the
suite's virtual CPU devices.)"""

import os

import pytest

from benchmark import flops_moe
from benchmark import run as bench_run
from benchmark.entries import train, train_moe
from benchmark.reference import train as ref_train
from test_check import SEED, _half_batch, _state_unchanged

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy2")
CELL = "lfm2_toy_train"


@pytest.fixture(scope="module")
def manifest():
    return bench_run.read_json(TOY, "BENCHMARK.json")


def _run(manifest, tmp_path, trace=False, cell=CELL):
    return bench_run.run_cell(manifest, cell, SEED, 0.3, trace,
                              need_chip=False, bench_dir=TOY,
                              out_dir=str(tmp_path))


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(manifest, trace, tmp_path):
    result = _run(manifest, tmp_path, trace)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    if trace:
        # the counters need no device plane; the device-trace readers find
        # none on the CPU, return nothing and are left out
        got = result["metrics"]
        assert {"moe_held_assignment_share",
                "moe_load_max_over_mean"} <= set(got)
        assert 15.0 < got["moe_held_assignment_share"]["value"] < 35.0
        assert got["moe_load_max_over_mean"]["value"] >= 1.0
        assert not {"expert_ffn_ms", "grouped_matmul_roofline"} & set(got)
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("control", ["int8", "float8"])
def test_control_is_not_correct(manifest, control):
    cell_file, config = bench_run.load_cell(CELL, manifest, TOY)
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


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_fault_is_not_correct(manifest, fault, tmp_path, monkeypatch):
    build = train.build_step

    def build_broken(program):
        init, step = build(program)
        return init, fault(step)

    monkeypatch.setattr(train, "build_step", build_broken)
    result = _run(manifest, tmp_path)
    assert not result["correct"], result["check"]


def test_scope_ms_splits_own_time_by_the_innermost_word():
    from benchmark import scope_times, trace_reduce

    ops, mods = trace_reduce.OPS_LINE, scope_times.MODULES_LINE
    ev = scope_times.ScopedEvent
    plane = trace_reduce.DEVICE_PLANE + "0"
    events = [ev(plane, mods, "jit_step", 0.0, 100e6, ""),
              ev(plane, mods, "jit_step", 100e6, 100e6, ""),
              ev(plane, ops, "gmm_fwd.1", 0.0, 4e6, ""),
              ev(plane, ops, "fusion.2", 10e6, 6e6, ""),
              ev(plane, ops, "fusion.3", 20e6, 2e6, ""),
              ev(plane, ops, "fusion.4", 30e6, 8e6, "")]
    names = {"gmm_fwd.1": "jit(f)/jvp(model)/mlp/expert_ffn/gmm_fwd/call",
             "fusion.2": "jit(f)/transpose(jvp(model))/mlp/expert_ffn/mul",
             "fusion.3": "jit(f)/jvp(model)/short_conv/conv_gate/mul",
             "fusion.4": "jit(f)/jvp(model)/attention/qkv/dot_general"}
    words = ["short_conv", "conv_gate", "expert_ffn", "gmm_fwd"]
    assert train_moe.scope_ms(events, names, words) == {
        "gmm_fwd": 2.0, "expert_ffn": 3.0, "conv_gate": 1.0}
    assert train_moe.scope_ms([], names, words) == {}


def test_flops_of_the_published_configuration():
    """The count the issue wrote out: 186.1 M weights a token, 1.318
    GFLOP a token at s8192."""
    config = bench_run.read_json(bench_run.HERE, "configs",
                                 "lfm2_24b_a2b_ep8.json")
    assert flops_moe.matmul_weights(config) == pytest.approx(
        186.1e6, rel=1e-3)
    assert flops_moe.train_flops_per_token(config, 8192) == pytest.approx(
        1.318e9, rel=1e-3)
    flops, nbytes = flops_moe.grouped_step_work(config, 8192.0, 2)
    # 4 layers x (2 forward runs + 2 gradients) x 2 products
    assert flops == pytest.approx(
        4 * 4 * 2 * 8192 * (2048 * 3072 + 1536 * 2048), rel=1e-9)
    assert nbytes > 0
