"""The ``train_mla_moe`` entry and the JoyAI-LLM-Flash reference at a toy
size on the CPU (``tests/toy4``: one dense layer, two expert layers and the
MTP module, 4 of 16 experts held, three inputs): a sound run comes out
correct and carries the counters' metrics; the int8 and float8 controls and
both planted faults come out not correct.  ``test_check.py`` says what each
of these is."""

import os

import pytest

from benchmark import flops_mla_moe as flops_mla
from benchmark import run as bench_run
from benchmark.entries import train
from benchmark.reference import train as ref_train
from test_check import SEED, _half_batch, _state_unchanged

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy4")
CELL = "joyai_toy_train"
PUBLISHED = "joyai_llm_flash_ep32"


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
        assert not {"mla_attention_ms", "mla_latent_proj_ms", "mla_rope_ms",
                    "mtp_ms", "mla_flash_roofline", "flash_fwd_ms",
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
    assert len(batches[0]) == 3
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
    """On a parent that lacks the scopes and the entry's ``mla`` block and
    ``scope_under_ms`` (or with no trace) every new reader returns ``None``
    and raises nothing."""
    from benchmark.layer_metrics import (
        mla_attention_ms, mla_flash_roofline, mla_latent_proj_ms,
        mla_rope_ms, mtp_ms)

    readers = (mla_attention_ms, mla_flash_roofline, mla_latent_proj_ms,
               mla_rope_ms, mtp_ms)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"window_s": 1.0, "device_ops": [["fusion", 0.5],
                                             ["flash_fwd", 0.02]]}
    for record in ({}, {"scope_ms": {"router": 1.0}, "peaks": peaks},
                   {"scope_ms": {"router": 1.0}, "peaks": peaks,
                    "trace": trace, "window_s": 10.0, "steps": 20}):
        for reader in readers:
            assert reader.read(record) is None, reader.__name__
    work = {"flash_flops_a_step": 197e12 * 30e-3,
            "flash_bytes_a_step": 819e9 * 2e-3}
    record = {"scope_ms": {"mla_q_latent": 4.0, "mla_out": 3.0,
                           "mla_rope": 2.0, "mla_attention": 50.0},
              "scope_under_ms": {"mla_attention": 80.0, "mtp": 30.0},
              "peaks": peaks, "mla": work, "window_s": 10.0, "steps": 20,
              "trace": {"window_s": 5.0, "device_ops": [
                  ["fusion", 2.0], ["flash_fwd", 0.1],
                  ["flash_bwd_dq", 0.15], ["flash_bwd_dkv", 0.25]]}}
    assert mla_attention_ms.read(record) == 80.0
    assert mla_latent_proj_ms.read(record) == 7.0
    assert mla_rope_ms.read(record) == 2.0
    assert mtp_ms.read(record) == 30.0
    # the kernels take 0.5 of 5 s traced: a tenth of a 500 ms step, 50 ms;
    # the operations bound the least time: 30 ms of them
    assert mla_flash_roofline.read(record) == pytest.approx(60.0)


def test_flops_of_the_published_configuration():
    """The count written out in PERF.md: 309.2 M weights a token, 4.875
    GFLOP a token at s8192; the attention's least work 14.85 TFLOP and
    4.04 GB a step."""
    config = bench_run.read_json(bench_run.HERE, "configs",
                                 PUBLISHED + ".json")
    assert flops_mla.mla_weights(config) == (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 4096 * 2048)
    assert flops_mla.expert_block_weights(config) == pytest.approx(
        2048 * 256 + (0.25 + 1) * 3 * 2048 * 768)
    assert flops_mla.blocks(config) == 6
    assert flops_mla.expert_layers(config) == 5
    assert flops_mla.matmul_weights(config) == pytest.approx(
        309.2e6, rel=1e-4)
    assert flops_mla.train_flops_per_token(config, 8192) == pytest.approx(
        6 * 309.198848e6 + 6 * 6 * 8192 * 32 * 320, rel=1e-9)
    flops, nbytes = flops_mla.flash_step_work(config, 1, 8192)
    pairs = 8192 * 8193 // 2 * 32 * 6
    assert flops == pairs * (640 + 1664)
    assert nbytes == 2 * 8192 * 6 * (
        (6144 + 4160 + 3 * 4096) + (4096 + 6144 + 4160 + 4096))
    flops, nbytes = flops_mla.grouped_step_work(config, 2048.0, 2)
    # 5 expert blocks x (2 forward runs + 2 gradients) x 2 products, gated
    assert flops == pytest.approx(
        5 * 4 * 2 * 2048 * (2048 * 1536 + 768 * 2048), rel=1e-9)
    assert nbytes > 0


def test_the_published_file_keeps_the_catalogs_numbers():
    """Only the three ``reduced`` keys differ from the source; the
    program's keywords are the file's own values, the router at its
    published width; the deployment is 32 chips a layer."""
    config = bench_run.read_json(bench_run.HERE, "configs",
                                 PUBLISHED + ".json")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    dep, kw = config["deployment"], config["program"]["model_config_kwargs"]
    assert kw["n_routed_experts"] == dep["num_experts_published"] == 256
    assert kw["experts_held"] == dep["experts_held"] == [
        0, config["n_routed_experts"]]
    assert dep["chips_per_layer"] * config["n_routed_experts"] == 256
    assert config["vocab_size"] * 8 == dep["vocab_size_padded"] == 130048
    assert dep["vocab_size_published"] == 129280
    assert (config["qk_head_dim"] == config["qk_nope_head_dim"]
            + config["qk_rope_head_dim"] == 192)
    assert config["head_dim"] == config["qk_rope_head_dim"] == 64
    for key, value in kw.items():
        if key in config and key != "n_routed_experts":
            assert config[key] == value, key
    state = config["state_bytes"]
    assert state["parameters"] == (
        sum(state["per_layer"]) + state["mtp_module"]
        + state["embedding_and_head"] + state["final_norm"]) == 492090624
