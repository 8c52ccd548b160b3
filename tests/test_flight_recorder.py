"""ISSUE 4 tier-1 coverage: trace export, flight recorder, detectors,
recompile + HBM accounting, env validation, and the health-report tool.

The acceptance scenarios live here: a run that produces span + step +
serving-request rows in a schema-valid Chrome trace; an injected-NaN
train loop whose flight-recorder post-mortem names the first anomalous
step; and a forced shape-change retrace that increments
``compile.count``.
"""

import contextlib
import importlib.util
import io
import json
import logging
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.observability as obs
from apex_tpu.observability import detectors as det

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    obs.shutdown()


@contextlib.contextmanager
def _capture_warnings():
    """The apex_tpu logger is propagate=False (its own stderr handler),
    so caplog never sees it — attach a capturing handler directly."""
    records = []

    class _H(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = _H(level=logging.WARNING)
    logger = logging.getLogger("apex_tpu")
    logger.addHandler(h)
    try:
        yield records
    finally:
        logger.removeHandler(h)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------


# every Chrome trace event must carry these (the schema check the
# acceptance criterion names)
_REQUIRED_BY_PH = {
    "X": ("name", "pid", "tid", "ts", "dur"),
    "C": ("name", "pid", "ts"),
    "M": ("name", "pid"),
    "i": ("name", "pid", "tid", "ts"),
    "b": ("name", "pid", "tid", "ts", "id"),
    "e": ("name", "pid", "tid", "ts", "id"),
}


def _assert_valid_trace(events):
    assert events, "empty trace"
    for ev in events:
        assert isinstance(ev, dict)
        ph = ev.get("ph")
        assert ph in _REQUIRED_BY_PH, f"unknown phase {ph!r}: {ev}"
        for field in _REQUIRED_BY_PH[ph]:
            assert field in ev, f"{ph!r} event missing {field!r}: {ev}"
        if ph == "X":
            assert ev["dur"] >= 0


class TestTraceExport:
    def test_trace_file_is_valid_chrome_trace_json(self, tmp_path):
        path = tmp_path / "trace.json"
        obs.configure(trace_path=str(path))
        with obs.span("train_step"):
            pass
        obs.gauge("train.loss").set(1.5)
        obs.event("amp.loss_scale_change", old=2.0, new=1.0)
        obs.shutdown()
        events = json.load(open(path))     # plain json.load must work
        assert isinstance(events, list)
        _assert_valid_trace(events)
        assert {e["ph"] for e in events} >= {"X", "C", "M", "i"}

    def test_span_step_and_serving_rows(self, tmp_path):
        """The acceptance-criterion row kinds from one run: a span row,
        a StepTimer ``step.*`` row, and serving-request async rows."""
        from apex_tpu.models.config import TransformerConfig
        from apex_tpu.models.transformer_lm import init_gpt_params
        from apex_tpu.serving import ServingEngine

        path = tmp_path / "trace.json"
        obs.configure(trace_path=str(path))
        with obs.span("train_step"):
            jnp.ones((2,)).block_until_ready()
        obs.StepTimer("gpt2", warmup=1, iters=2).time(
            lambda c: (0, jnp.asarray(1.0)))
        cfg = TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=2,
            vocab_size=64, max_position_embeddings=32, remat=False,
            compute_dtype=jnp.float32)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        engine = ServingEngine(params, cfg, max_slots=2, max_len=16,
                               prompt_buckets=(8,))
        engine.run([dict(prompt=np.asarray([1, 2, 3]),
                         max_new_tokens=2) for _ in range(2)])
        obs.shutdown()
        events = obs.load_trace(str(path))
        _assert_valid_trace(events)
        slices = {e["name"] for e in events if e["ph"] == "X"}
        assert "train_step" in slices            # span row
        assert "step.gpt2" in slices             # StepTimer row
        assert "serving.prefill" in slices       # serving span row
        begins = [e for e in events
                  if e["ph"] == "b" and e["name"] == "serving.request"]
        ends = [e for e in events
                if e["ph"] == "e" and e["name"] == "serving.request"]
        assert {e["id"] for e in begins} == {0, 1}   # per-request rows
        assert {e["id"] for e in ends} == {0, 1}
        # counter tracks from the gauges
        counters = {e["name"] for e in events if e["ph"] == "C"}
        assert "serving.queue_depth" in counters

    def test_truncated_trace_still_loads(self, tmp_path):
        """Crash robustness: the array form loads with the tail
        missing (the file of a process that died mid-write)."""
        path = tmp_path / "trace.json"
        obs.configure(trace_path=str(path))
        with obs.span("s1"):
            pass
        with obs.span("s2"):
            pass
        obs.registry().flush()
        # simulate the crash: no close; chop the final line in half
        full = open(path).read().rstrip()
        (tmp_path / "cut.json").write_text(full[: -10])
        events = obs.load_trace(str(tmp_path / "cut.json"))
        assert any(e.get("name") == "s1" for e in events)
        obs.shutdown()

    def test_nonfinite_values_stay_strict_json(self, tmp_path):
        """A NaN loss is the flagship incident: Perfetto's strict
        JSON.parse rejects bare NaN/Infinity tokens, so the trace of
        exactly the run being debugged must never contain them."""
        path = tmp_path / "trace.json"
        obs.configure(trace_path=str(path))
        obs.gauge("train.loss").set(float("nan"))
        obs.gauge("train.grad_norm").set(float("inf"))
        obs.event("anomaly.nan_inf", value=float("nan"))
        obs.shutdown()
        text = open(path).read()
        import re

        assert not re.search(r"\bNaN\b|\bInfinity\b", text), text
        events = json.loads(text)       # and still fully parseable
        assert any(e.get("name") == "train.loss" for e in events)

    def test_user_host_tag_is_not_assumed_numeric(self, tmp_path):
        # tags={"host": hostname} is a natural user tag; it must not
        # kill configure() even though the registry's own rank tag is
        # an int
        path = tmp_path / "trace.json"
        obs.configure(trace_path=str(path), tags={"host": "gpu-node-1"})
        with obs.span("s"):
            pass
        obs.shutdown()
        events = obs.load_trace(str(path))
        assert any(e["ph"] == "X" and e["pid"] == 0 for e in events)

    def test_spans_land_on_family_thread_rows(self, tmp_path):
        path = tmp_path / "trace.json"
        obs.configure(trace_path=str(path))
        with obs.span("serving.prefill"):
            pass
        with obs.span("step.bench"):
            pass
        obs.shutdown()
        events = obs.load_trace(str(path))
        names = {e["args"]["name"]: e["tid"] for e in events
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        tid_of = {e["name"]: e["tid"] for e in events if e["ph"] == "X"}
        assert tid_of["serving.prefill"] == names["serving"]
        assert tid_of["step.bench"] == names["step"]
        assert names["serving"] != names["step"]


@pytest.mark.slow
def test_bench_decode_run_produces_valid_trace(tmp_path, monkeypatch,
                                               capsys):
    """The acceptance criterion end-to-end: one real ``bench.py
    --decode`` run (StepTimer rows + the serving mixes) with
    APEX_TPU_TELEMETRY_TRACE set produces a schema-valid trace
    containing span, step, and serving-request rows, and a BENCH JSON
    line carrying the runtime (compile/hbm) block.  Runs bench.main()
    in-process, on the conftest's CPU, with ``--cpu-smoke``."""
    trace_path = tmp_path / "bench_trace.json"
    monkeypatch.setenv("APEX_TPU_TELEMETRY_TRACE", str(trace_path))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--decode", "--cpu-smoke"])
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    bench_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_mod)
    bench_mod.main()
    obs.shutdown()                       # close/finalize the trace file
    stdout = capsys.readouterr().out
    line = next(ln for ln in stdout.splitlines() if ln.startswith("{"))
    bench = json.loads(line)
    for row in bench["details"].values():
        assert "error" not in row, row
    assert "runtime" in bench and "compile" in bench["runtime"]
    assert bench["runtime"]["compile"]["count"] > 0
    events = obs.load_trace(str(trace_path))
    _assert_valid_trace(events)
    slices = {e["name"] for e in events if e["ph"] == "X"}
    assert any(n.startswith("step.") for n in slices)        # StepTimer
    assert "serving.prefill" in slices                       # span row
    assert any(e["ph"] == "b" and e["name"] == "serving.request"
               for e in events)                              # request rows


# ---------------------------------------------------------------------------
# detectors (unit level)
# ---------------------------------------------------------------------------


class TestDetectors:
    def test_loss_spike_zscore(self):
        d = det.ZScoreDetector("loss", "loss_spike", threshold=6.0,
                               min_points=8)
        for i in range(20):
            assert d.feed(i, {"loss": 1.0 + 0.01 * (i % 3)}) is None
        a = d.feed(20, {"loss": 50.0})
        assert a is not None and a.kind == "loss_spike"
        assert a.step == 20

    def test_zscore_ignores_constant_series(self):
        # std ~ 0 on a constant series must not make 1.0001 a "spike"
        d = det.ZScoreDetector("loss", "loss_spike", min_points=4)
        for i in range(10):
            d.feed(i, {"loss": 1.0})
        assert d.feed(10, {"loss": 1.0001}) is None

    def test_nan_first_seen_fires_once_with_keys(self):
        d = det.NanInfDetector()
        assert d.feed(0, {"loss": 1.0, "grad_norm": 2.0}) is None
        a = d.feed(1, {"loss": 1.0, "grad_norm": float("inf")})
        assert a is not None and a.kind == "nan_inf"
        assert a.detail["keys"] == ["grad_norm"]
        assert a.step == 1
        # poisoned steps after the first do not re-fire
        assert d.feed(2, {"loss": float("nan")}) is None

    def test_scaler_thrash_rate_window_with_hysteresis(self):
        d = det.ScalerThrashDetector(window=16, rate_threshold=0.5,
                                     min_points=8)
        fired = [d.feed(i, i % 2 == 0) for i in range(40)]
        hits = [a for a in fired if a is not None]
        assert len(hits) == 1                      # hysteresis: one incident
        assert hits[0].kind == "scaler_thrash"
        d2 = det.ScalerThrashDetector(window=16, rate_threshold=0.5)
        assert all(d2.feed(i, False) is None for i in range(40))

    def test_throughput_regression(self):
        d = det.ThroughputRegressionDetector(baseline_points=4,
                                             recent=3, ratio=1.5)
        for i in range(6):
            assert d.feed("step.gpt2", 0.100) is None
        fired = [a for a in (d.feed("step.gpt2", 0.300, step=i)
                             for i in range(3)) if a is not None]
        assert len(fired) == 1          # hysteresis: one incident
        assert fired[0].kind == "throughput_regression"
        # an unrelated series keeps its own baseline
        assert d.feed("step.other", 0.300) is None

    def test_queue_stall_detector(self):
        d = det.QueueStallDetector(patience=4)
        fired = [d.feed(queue_depth=3, occupancy=0.5) for _ in range(6)]
        assert any(a is not None
                   and a.kind == "serving_admission_stall"
                   for a in fired)
        d2 = det.QueueStallDetector(patience=4)
        assert all(d2.feed(queue_depth=3, occupancy=1.0) is None
                   for _ in range(6))

    def test_step_time_samples_containing_compiles_are_dropped(self):
        """A timing that contained a backend compile (fresh serving
        bucket, legitimate retrace) is not a steady-state sample: the
        bank must drop it instead of poisoning the baseline or firing
        a false regression — the compile is already first-class signal
        via compile.{count,ms}."""
        from apex_tpu.observability import device as dev

        reg = obs.configure()
        bank = reg.detectors
        tracker = dev.recompile_tracker()
        bank.feed_step_time("serving.prefill", 0.010)   # may be dropped
        for _ in range(6):                              # clean baseline
            bank.feed_step_time("serving.prefill", 0.010)
        # a compile lands inside the next (10x slower) observation:
        tracker.on_compile(0.090, "serving.prefill")
        bank.feed_step_time("serving.prefill", 0.100)
        assert not any(a.kind == "throughput_regression"
                       for a in bank.anomalies)
        # compile-free slowness STILL fires
        for _ in range(3):
            bank.feed_step_time("serving.prefill", 0.100)
        assert any(a.kind == "throughput_regression"
                   for a in bank.anomalies)

    def test_bank_fires_events_and_counter(self, tmp_path):
        path = tmp_path / "t.jsonl"
        reg = obs.configure(jsonl_path=str(path))
        for i in range(10):
            obs.record_step_metrics({"loss": 1.0, "step": i})
        obs.record_step_metrics({"loss": float("nan"), "step": 10})
        assert reg.counter("anomaly.count").value == 1
        obs.shutdown()
        recs = [json.loads(line) for line in open(path)]
        evs = [r for r in recs if r["type"] == "event"
               and r["name"] == "anomaly.nan_inf"]
        assert len(evs) == 1 and evs[0]["data"]["step"] == 10


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_injected_nan_train_loop_postmortem(self, tmp_path):
        """The acceptance scenario: a real amp.frontend train loop, a
        NaN injected mid-run, and a dump that names the first anomalous
        step."""
        from apex_tpu.amp.frontend import initialize, make_train_step
        from apex_tpu.amp.scaler import record_scaler_step
        from apex_tpu.optimizers import fused_adam

        dump_path = tmp_path / "flight.json"
        obs.configure(flight_recorder=str(dump_path), flight_steps=64)
        params = {"w": jnp.ones((8, 8), jnp.float32)}
        x = jnp.ones((2, 8), jnp.float32)
        # static loss scale: no settle-phase overflow skips, so
        # TrainState.step == loop index and the post-mortem step is
        # exact (dynamic-scale skip semantics are pinned elsewhere)
        init, step = make_train_step(
            lambda p, xx: jnp.mean((xx @ p["w"]) ** 2),
            fused_adam(lr=1e-3), initialize("O2", loss_scale=1.0),
            norm_telemetry=True)
        state = init(params)
        for i in range(8):
            if i == 5:
                # poison the params: every later loss/norm is non-finite
                state = state._replace(
                    master_params={"w": state.master_params["w"]
                                   * float("nan")})
            state, metrics = step(state, x)
            record_scaler_step(metrics)
            obs.record_step_metrics(metrics)
        assert dump_path.exists(), "no post-mortem dumped on anomaly"
        # strict JSON: jq / JSON.parse reject bare NaN tokens, and the
        # NaN incident is exactly the dump that must stay readable
        import re

        assert not re.search(r"\bNaN\b|\bInfinity\b",
                             open(dump_path).read())
        dump = json.load(open(dump_path))
        assert dump["reason"].startswith("anomaly:nan_inf")
        assert dump["first_anomaly"]["kind"] == "nan_inf"
        # steps 0..4 were clean; the poisoned step is the 6th (index 5)
        assert dump["first_anomalous_step"] == 5
        bad_keys = dump["first_anomaly"]["detail"]["keys"]
        assert "loss" in bad_keys or "grad_norm" in bad_keys
        steps = dump["steps"]
        assert steps and steps[-1]["step"] == 5
        # the ring holds the healthy history too (non-finite values
        # are stringified for strict-JSON dumps)
        assert any(isinstance(s["loss"], float)
                   and math.isfinite(s["loss"]) for s in steps)
        assert not any(isinstance(s["loss"], float)
                       and math.isnan(s["loss"]) for s in steps)

    def test_ring_buffer_is_bounded(self, tmp_path):
        obs.configure(flight_recorder=str(tmp_path / "f.json"),
                      flight_steps=16)
        for i in range(100):
            obs.record_step_metrics({"loss": 1.0, "step": i})
        rec = obs.registry().recorder
        assert len(rec.steps) == 16
        assert rec.steps[0]["step"] == 84 and rec.steps[-1]["step"] == 99

    def test_on_demand_dump_and_health_report(self, tmp_path):
        dump_path = tmp_path / "f.json"
        obs.configure(flight_recorder=str(dump_path))
        for i in range(4):
            obs.record_step_metrics(
                {"loss": 1.0 + i, "loss_scale": 1024.0, "step": i})
        rec = obs.registry().recorder
        out = rec.dump(reason="unit_test")
        assert out == str(dump_path)
        doc = json.load(open(dump_path))
        assert doc["reason"] == "unit_test"
        assert doc["dump_schema_version"] == 1
        assert [s["step"] for s in doc["steps"]] == [0, 1, 2, 3]
        assert "metrics_summary" in doc

        health = _load_tool("health_report")
        buf = io.StringIO()
        health.render_dump(doc, out=buf)
        text = buf.getvalue()
        assert "incident summary" in text
        assert "no anomalies recorded" in text
        assert "loss" in text

    def test_crash_excepthook_dumps(self, tmp_path):
        dump_path = tmp_path / "f.json"
        prev_hook = sys.excepthook
        obs.configure(flight_recorder=str(dump_path))
        obs.record_step_metrics({"loss": 2.5, "step": 7})
        assert sys.excepthook is not prev_hook
        try:
            sys.excepthook(RuntimeError, RuntimeError("boom"), None)
            doc = json.load(open(dump_path))
            assert doc["reason"] == "crash"
            assert doc["error"] == "RuntimeError: boom"
            assert doc["steps"][-1]["loss"] == 2.5
        finally:
            obs.shutdown()
        # shutdown restores the hook that configure() replaced
        assert sys.excepthook is prev_hook

    def test_shutdown_preserves_the_incident_dump(self, tmp_path):
        """The anomaly-time dump brackets the incident; a run that
        outlives it must not have that window overwritten by the
        shutdown dump — the aftermath goes to a sibling .final file."""
        dump_path = tmp_path / "flight.json"
        obs.configure(flight_recorder=str(dump_path), flight_steps=8)
        for i in range(5):
            obs.record_step_metrics({"loss": 1.0, "step": i})
        obs.record_step_metrics({"loss": float("nan"), "step": 5})
        # the run survives the anomaly far past the ring size
        for i in range(6, 30):
            obs.record_step_metrics({"loss": 1.0, "step": i})
        obs.shutdown()
        incident = json.load(open(dump_path))
        assert incident["reason"] == "anomaly:nan_inf"
        assert incident["steps"][-1]["step"] == 5    # window preserved
        final = json.load(open(tmp_path / "flight.final.json"))
        assert final["reason"] == "shutdown_with_anomalies"
        assert final["steps"][-1]["step"] == 29

    def test_quiet_run_leaves_no_artifact(self, tmp_path):
        dump_path = tmp_path / "f.json"
        obs.configure(flight_recorder=str(dump_path))
        for i in range(5):
            obs.record_step_metrics({"loss": 1.0, "step": i})
        obs.shutdown()
        assert not dump_path.exists()


# ---------------------------------------------------------------------------
# recompilation + HBM accounting
# ---------------------------------------------------------------------------


class TestRuntimeAccounting:
    def test_forced_retrace_increments_compile_count(self):
        """The acceptance scenario: an intentional shape-change retrace
        shows up in compile.{count,ms} under the active label."""
        from apex_tpu.observability import device as dev

        reg = obs.configure()
        tracker = dev.recompile_tracker()
        assert tracker is not None, "configure() must install the tracker"
        f = jax.jit(lambda x: x * 2 + 1)
        # build inputs OUTSIDE the label: jnp.ones itself compiles a
        # tiny fill program and would pollute the labeled count
        a, b = jnp.ones((4,)), jnp.ones((9,))
        base = reg.counter("compile.count").value
        with dev.compile_label("retrace_unit"):
            f(a)
            f(a)      # cache hit: no compile
            f(b)      # shape change: forced retrace
        delta = reg.counter("compile.count").value - base
        assert delta == 2, f"expected 2 compiles (initial+retrace), {delta}"
        assert reg.counter("compile.retrace_unit.count").value == 2
        assert reg.counter("compile.ms").value >= 0
        row = tracker.summary()["by_label"]["retrace_unit"]
        assert row["count"] == 2 and row["ms"] > 0

    def test_compile_labels_nest_and_unlabeled_falls_back(self):
        from apex_tpu.observability import device as dev

        assert dev.current_compile_label() is None
        with dev.compile_label("outer"):
            assert dev.current_compile_label() == "outer"
            with dev.compile_label("inner"):
                assert dev.current_compile_label() == "inner"
            assert dev.current_compile_label() == "outer"
        assert dev.current_compile_label() is None

    def test_steptimer_attributes_warmup_compiles(self):
        from apex_tpu.observability import device as dev

        reg = obs.configure()

        @jax.jit
        def step(x):
            return x + 1

        x = jnp.zeros((3, 3))
        obs.StepTimer("unit_row", warmup=1, iters=2).time_call(step, x)
        assert reg.counter("compile.unit_row.count").value >= 1
        # nothing compiled inside the timed window
        assert reg.counter("compile.unit_row.retrace.count").value == 0
        assert dev.runtime_summary()["compile"]["by_label"][
            "unit_row"]["count"] >= 1

    def test_sample_device_memory_cpu_degrades_to_none(self):
        # CPU backends report no memory_stats: the helper returns None
        # and sets no gauges rather than exploding
        reg = obs.configure()
        out = obs.sample_device_memory()
        if out is None:
            assert reg.gauge("hbm.bytes_in_use").value is None
        else:       # a real accelerator in the loop: gauges must agree
            assert reg.gauge("hbm.bytes_in_use").value == pytest.approx(
                out["bytes_in_use"])

    def test_runtime_summary_shape(self):
        from apex_tpu.observability import device as dev

        dev.install_recompile_tracker()
        out = obs.runtime_summary()
        assert "compile" in out
        assert {"count", "ms", "by_label"} <= set(out["compile"])


# ---------------------------------------------------------------------------
# configure_from_env validation (ISSUE 4 satellite)
# ---------------------------------------------------------------------------


class TestEnvConfiguration:
    def test_all_documented_vars_round_trip(self, tmp_path):
        env = {
            "APEX_TPU_TELEMETRY": str(tmp_path / "t.jsonl"),
            "APEX_TPU_TELEMETRY_TRACE": str(tmp_path / "trace.json"),
            "APEX_TPU_TELEMETRY_FLIGHT": str(tmp_path / "f.json"),
            "APEX_TPU_TELEMETRY_FLIGHT_STEPS": "32",
            "APEX_TPU_TELEMETRY_DETECTORS": "1",
            "APEX_TPU_TELEMETRY_STDERR": "0",
            "APEX_TPU_TELEMETRY_PROFILER": "0",
        }
        reg = obs.configure_from_env(env)
        assert reg is not None
        assert reg.detectors is not None
        assert reg.recorder is not None
        assert reg.recorder.max_steps == 32
        kinds = {type(s).__name__ for s in reg.sinks}
        assert {"JsonlSink", "TraceSink"} <= kinds

    def test_nothing_set_stays_disabled(self):
        assert obs.configure_from_env({}) is None
        assert not obs.enabled()

    def test_malformed_bool_warns_with_var_name(self):
        with _capture_warnings() as warnings:
            reg = obs.configure_from_env(
                {"APEX_TPU_TELEMETRY_STDERR": "maybe"})
        assert reg is None      # malformed value falls back to default
        assert any("APEX_TPU_TELEMETRY_STDERR" in w for w in warnings)

    def test_malformed_int_warns_but_still_configures(self, tmp_path):
        with _capture_warnings() as warnings:
            reg = obs.configure_from_env({
                "APEX_TPU_TELEMETRY_FLIGHT": str(tmp_path / "f.json"),
                "APEX_TPU_TELEMETRY_FLIGHT_STEPS": "lots",
            })
        assert reg is not None          # the typo cost the option,
        assert reg.recorder is not None  # not the whole config
        assert reg.recorder.max_steps == 256
        assert any("APEX_TPU_TELEMETRY_FLIGHT_STEPS" in w
                   for w in warnings)

    def test_unknown_var_warns_with_var_name(self, tmp_path):
        with _capture_warnings() as warnings:
            obs.configure_from_env({
                "APEX_TPU_TELEMETRY": str(tmp_path / "t.jsonl"),
                "APEX_TPU_TELEMETRY_TRACEPATH": "typo.json",
            })
        assert any("APEX_TPU_TELEMETRY_TRACEPATH" in w for w in warnings)

    def test_detectors_can_be_disabled(self, tmp_path):
        reg = obs.configure_from_env({
            "APEX_TPU_TELEMETRY": str(tmp_path / "t.jsonl"),
            "APEX_TPU_TELEMETRY_DETECTORS": "0",
        })
        assert reg is not None and reg.detectors is None

    def test_env_table_documents_every_var(self):
        """docs/observability.md must mention every ENV_VARS entry —
        the 'document in one place' satellite is enforceable."""
        from apex_tpu.observability.metrics import ENV_PREFIX, ENV_VARS

        doc = open(os.path.join(REPO, "docs", "observability.md")).read()
        for suffix in ENV_VARS:
            assert ENV_PREFIX + suffix in doc, (
                f"{ENV_PREFIX + suffix} missing from docs/observability.md")
