"""``benchmark/scope_times.py``: the classification of an ``op_name``, the
reduction of a recorded chip slice, and the vocabulary it shares with the
program."""

import glob
import gzip
import json
import os
import re

import pytest

from benchmark import scope_times, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = sorted(glob.glob(os.path.join(BENCH, "fixtures", "scopes",
                                         "*.json.gz")))
PLANE, OPS = "/device:TPU:0", trace_reduce.OPS_LINE
BODY = "jit(step_fn)/jvp(model)/backbone/while/body/"
REMAT = ("jit(step_fn)/transpose(jvp(model))/backbone/while/body/closed_call/"
         "checkpoint/rematted_computation/")


def _fixture(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_six_events_classify_as_the_docstring_says():
    us = 1e3
    events = [scope_times.ScopedEvent(PLANE, OPS, *e) for e in (
        # a scan of 100 us whose body's operations cover 70
        ("while.2", 0, 100 * us, "jit(step_fn)/jvp(model)/backbone/while"),
        ("convolution_add_fusion.15", 5 * us, 40 * us,
         BODY + "closed_call/attention/qkv/dot_general"),
        ("bitcast_dynamic-update-slice_fusion.18", 50 * us, 30 * us,
         BODY + "dynamic_update_slice"),
        ("flash_fwd.15", 100 * us, 60 * us,
         REMAT + "attention/core_attention/flash_fwd/pallas_call"),
        ("fusion.9", 160 * us, 20 * us, "jit(step_fn)/optimizer/mul"),
        ("copy-done.3", 190 * us, 10 * us, ""),          # 10 us idle before
    )] + [scope_times.ScopedEvent(PLANE, scope_times.MODULES_LINE,
                                  "jit_step_fn(1)", 0, 200 * us, "")]
    got = scope_times.reduce_events(events)
    assert got["steps"] == 1
    assert got["busy_s"] == pytest.approx(190e-6)
    assert got["phase_s"] == pytest.approx({
        "forward/qkv": 40e-6, "forward/scan_plumbing": 30e-6,
        "forward/scan_gaps": 30e-6, "recompute/flash_fwd": 60e-6,
        "update/optimizer": 20e-6, "update/unscoped": 10e-6})
    assert got["pass_s"] == pytest.approx({
        "forward": 100e-6, "recompute": 60e-6, "backward": 0.0,
        "update": 30e-6})
    assert got["unscoped_s"] == pytest.approx({"update/copy-done": 10e-6})
    assert got["phase_ms_per_step"]["forward/qkv"] == pytest.approx(0.04)
    assert "forward/qkv" in scope_times.table(got)


@pytest.mark.parametrize("name, op_name, want", [
    ("fusion.3", "jit(step_fn)/transpose(jvp(model))/backbone/while/body/"
     "closed_call/checkpoint/mlp/fc1/dot_general", ("backward", "fc1")),
    ("convert_element_type.85", "jit(step_fn)/jvp(cast_params)/"
     "convert_element_type", ("forward", "cast_params")),
    ("layer_norm_bwd.21", "jit(step_fn)/transpose(jvp(model))/final_ln/"
     "layer_norm_bwd/pallas_call", ("backward", "layer_norm_bwd")),
    ("multiply_reduce_fusion.4", "jit(step_fn)/optimizer/trust_ratio/"
     "reduce_sum", ("update", "trust_ratio")),
    ("mul.7", "jit(step_fn)/transpose(jvp())/mul",
     ("backward", "unscoped:mul")),
])
def test_classify(name, op_name, want):
    assert scope_times.classify(name, op_name) == want


def test_op_names_of_hlo_text():
    text = """
ENTRY %main {
  %p.1 = f32[4]{0} parameter(0), metadata={op_name="state.step"}
  %fusion.3 = (f32[4]{0}, f32[4]{0}) fusion(%p.1), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/optimizer/mul" stack_frame_id=2}
  ROOT %copy.2 = f32[4]{0} copy(%p.1)
}"""
    assert scope_times.op_names_of(text) == {
        "p.1": "state.step", "fusion.3": "jit(step_fn)/optimizer/mul",
        "copy.2": ""}


def test_there_is_a_recorded_slice():
    assert FIXTURES


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_recorded_slice_reduces_to_its_expect(path):
    fixture = _fixture(path)
    events = [scope_times.ScopedEvent(*e) for e in fixture["events"]]
    got = scope_times.reduce_events(events)
    assert json.loads(json.dumps(got)) == fixture["expect"]
    # the (pass, phase) times are the slice's busy time, split
    assert sum(got["phase_s"].values()) == pytest.approx(
        got["busy_s"], rel=1e-3)
    assert sum(got["pass_s"].values()) == pytest.approx(
        got["busy_s"], rel=1e-3)
    # every event was given its op_name when recorded, and the kernels
    # carry names of their own
    assert any(k.endswith("/flash_fwd") for k in got["phase_s"])
    assert sum(got["unscoped_s"].values()) < 0.05 * got["busy_s"]
    # the GPT cell remats its layers, the BERT cell does not
    remat = os.path.basename(path).startswith("gpt2")
    assert (got["pass_s"]["recompute"] > 0) == remat


def test_vocabulary_is_the_programs():
    """Every ``jax.named_scope`` of the files that make up the train step is
    a word the reduction knows, and the other way round."""
    root = os.path.dirname(BENCH)
    written = set()
    for rel in ("amp/frontend.py", "models/transformer_lm.py",
                "models/bert.py", "ops/flash_attention.py",
                "ops/layer_norm.py", "optimizers/fused_lamb.py"):
        with open(os.path.join(root, "apex_tpu", rel)) as f:
            written |= set(re.findall(r'named_scope\("(\w+)"\)', f.read()))
    assert written == scope_times.VOCABULARY | scope_times.CONTAINERS
