"""The ``train_ssm_moe`` entry and the Nemotron-H reference at a toy size on
the CPU (``tests/toy3``: the cell's pattern ``EMEMEM*``, 4 of 16 experts
held): a sound run comes out correct and carries the counters' metrics; the
int8 and float8 controls and both planted faults come out not correct.
``test_check.py`` says what each of these is."""

import os

import pytest

from benchmark import flops_nemotron_h as flops_ssm
from benchmark import run as bench_run
from benchmark.entries import train
from benchmark.reference import train as ref_train
from test_check import SEED, _half_batch, _state_unchanged

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy3")
CELL = "nemotron_toy_train"
PUBLISHED = "nemotron_twotower_30b_a3b_ep16"


@pytest.fixture(scope="module")
def manifest():
    return bench_run.read_json(TOY, "BENCHMARK.json")


def _run(manifest, tmp_path, trace=False):
    return bench_run.run_cell(manifest, CELL, SEED, 0.3, trace,
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
        assert not {"ssd_scan_ms", "ssd_scan_roofline", "mamba_mixer_ms",
                    "shared_expert_ms", "expert_ffn_ms"} & set(got)
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


def test_readers_return_nothing_where_the_program_has_no_such_scope():
    """On a parent that lacks the scopes and the entry's ``ssm`` block
    (or with no trace) every new reader returns ``None`` and raises
    nothing."""
    from benchmark.layer_metrics import (
        mamba_mixer_ms, shared_expert_ms, ssd_scan_ms, ssd_scan_roofline)

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for record in ({}, {"scope_ms": {"router": 1.0}, "peaks": peaks},
                   {"scope_ms": {"ssd_scan": 2.0}, "peaks": peaks}):
        assert mamba_mixer_ms.read(record) == record.get(
            "scope_ms", {}).get("ssd_scan")
        assert shared_expert_ms.read(record) is None
        assert ssd_scan_roofline.read(record) is None
    work = {"scan_flops_a_step": 197e12 * 1e-3,
            "scan_bytes_a_step": 819e9 * 2e-3}
    record = {"scope_ms": {"ssd_scan": 8.0, "ssm_in": 4.0,
                           "shared_expert": 3.0},
              "peaks": peaks, "ssm": work}
    assert ssd_scan_ms.read(record) == 8.0
    assert mamba_mixer_ms.read(record) == 12.0
    assert shared_expert_ms.read(record) == 3.0
    # the bytes bound it: 2 ms of 8
    assert ssd_scan_roofline.read(record) == pytest.approx(25.0)


def test_flops_of_the_published_configuration():
    """The count written out in PERF.md: 255.7 M weights a token, 1.967
    GFLOP a token at s8192; the scan 3.408 MFLOP and 20,736 bytes a token
    a layer a pass."""
    config = bench_run.read_json(bench_run.HERE, "configs",
                                 PUBLISHED + ".json")
    assert flops_ssm.layer_weights(config, "M") == 2688 * 10304 + 4096 * 2688
    assert flops_ssm.layer_weights(config, "*") == 2688 * 4608 + 4096 * 2688
    assert flops_ssm.layer_weights(config, "E") == pytest.approx(
        2688 * 128 + 0.375 * 2 * 2688 * 1856 + 2 * 2688 * 3712)
    assert flops_ssm.matmul_weights(config) == pytest.approx(
        255.68e6, rel=1e-4)
    assert flops_ssm.scan_flops_per_token(config) == 2 * (
        128 * 128 * 8 + 128 * 64 * 64 + 2 * 64 * 128 * 64)
    assert flops_ssm.scan_bytes_per_token(config) == 20736
    assert flops_ssm.train_flops_per_token(config, 8192) == pytest.approx(
        6 * 255.68e6 + 12 * 4096 * 8192 + 9 * 3.407872e6, rel=1e-4)
    flops, nbytes = flops_ssm.scan_step_work(config, 8192, 2)
    assert flops == 3 * 4 * 8192 * 3407872 and nbytes == 3 * 4 * 8192 * 20736
    flops, nbytes = flops_ssm.grouped_step_work(config, 3072.0, 2)
    # 3 layers x (2 forward runs + 2 gradients) x 2 products, not gated
    assert flops == pytest.approx(
        3 * 4 * 2 * 3072 * (2688 * 1856 + 1856 * 2688), rel=1e-9)
    assert nbytes > 0


def test_the_published_file_keeps_the_catalogs_numbers():
    """Every key the source's config.json has is in the file under the
    same name; only the four ``reduced`` keys differ; the program's
    keywords are the file's own values, the router at its published
    width."""
    config = bench_run.read_json(bench_run.HERE, "configs",
                                 PUBLISHED + ".json")
    assert config["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    dep, kw = config["deployment"], config["program"]["model_config_kwargs"]
    assert config["hybrid_override_pattern"] == dep[
        "hybrid_override_pattern_published"][6:13] == "EMEMEM*"
    assert len(dep["hybrid_override_pattern_published"]) == dep[
        "num_hidden_layers_published"] == 52
    assert kw["n_routed_experts"] == dep["num_experts_published"] == 128
    assert kw["experts_held"] == dep["experts_held"] == [
        0, config["n_routed_experts"]]
    assert dep["chips_per_layer"] * config["n_routed_experts"] == 128
    assert config["vocab_size"] * 8 == dep["vocab_size_published"]
    for key, value in kw.items():
        if key in config and key != "n_routed_experts":
            assert config[key] == value, key
    assert set(config["not_held"]["left_out"]) >= {"denoiser tower", "adaLN"}


def test_kernel_rows_name_the_kernels_outside_the_ten():
    """The entry adds a row for a named kernel that ran and has none among
    the trace's ten: its own time, the instances summed; a kernel with a
    row already, or one that did not run, gets none."""
    from benchmark import scope_times, trace_reduce
    from benchmark.entries import train_ssm_moe

    plane = trace_reduce.DEVICE_PLANE + "0"
    events = [scope_times.ScopedEvent(plane, line, name, start, dur, "")
              for line, name, start, dur in [
                  (trace_reduce.OPS_LINE, "fusion.1", 0.0, 100.0),
                  (trace_reduce.OPS_LINE, "flash_fwd.3", 100.0, 40.0),
                  (trace_reduce.OPS_LINE, "flash_fwd.7", 200.0, 10.0),
                  (trace_reduce.OPS_LINE, "flash_bwd_dq.2", 300.0, 30.0),
                  (scope_times.MODULES_LINE, "jit_step", 0.0, 330.0)]]
    rows = [["fusion", 1e-7], ["flash_bwd_dq", 3e-8]]
    got = train_ssm_moe.kernel_rows(
        events, ["flash_fwd", "flash_bwd", "flash_bwd_dq"], rows)
    assert got == [["flash_fwd", pytest.approx(5e-8)]]
    assert train_ssm_moe.kernel_rows([], ["flash_fwd"], rows) == []


def test_the_draw_is_levelled():
    """Output projections are centred over their input index, the
    router's columns and ``b`` within each group of held experts; every
    leaf keeps its spread."""
    import jax
    import numpy as np

    config = bench_run.read_json(TOY, "configs", "nemotron_toy.json")
    model = ref_train.model_module(config["reference"]["model"])
    params = model.init_params(jax.random.key(5), config)
    count = config["deployment"]["experts_held"][1]
    seen = set()
    for kind, lp in zip(config["hybrid_override_pattern"], params["layers"]):
        seen.add(kind)
        if kind == "M":
            w = np.asarray(lp["ssm_out_kernel"])
            assert np.abs(w.mean(0)).max() < 1e-8 and w.std() > 0
        if kind == "E":
            assert np.abs(np.asarray(lp["moe_fc2"]).mean(1)).max() < 1e-8
            assert np.abs(
                np.asarray(lp["shared_fc2_kernel"]).mean(0)).max() < 1e-8
            for name in ("router_kernel", "router_bias"):
                w = np.asarray(lp[name])
                groups = w.reshape(w.shape[:-1] + (-1, count))
                assert np.abs(groups.sum(-1)).max() < 1e-6, name
            assert 0.015 < np.asarray(lp["router_kernel"]).std() < 0.025
    assert {"M", "E"} <= seen
