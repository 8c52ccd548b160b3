"""The start-up ledger (``observability/device.py``): a backend step that
loads from the persistent compilation cache is a load, not a compile; nested
spans are booked once; a warm call fires nothing; ``compile.count`` counts
misses only."""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

import apex_tpu.observability as obs
from apex_tpu.observability import device as dev


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    obs.shutdown()


@pytest.fixture
def persistent_cache(tmp_path):
    """Every program into a fresh persistent cache, as the benchmark sets
    it; the process's settings come back afterwards."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0.0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _row(name):
    return dict(dev.recompile_tracker().rows.get(name, dev._new_row()))


def ledger_unit_step(x):
    return jnp.sin(x) @ x.T + jnp.cos(x).sum()


def test_a_cache_hit_is_a_load_not_a_compile(persistent_cache):
    f = jax.jit(ledger_unit_step)
    x = jnp.ones((4, 4))
    before = _row("ledger_unit_step")
    f(x).block_until_ready()
    first = _row("ledger_unit_step")
    assert first["n_compiled"] - before["n_compiled"] == 1
    assert first["n_loaded"] == before["n_loaded"]
    assert first["trace_s"] > before["trace_s"]
    assert first["lower_s"] > before["lower_s"]

    jax.clear_caches()
    f(x).block_until_ready()
    second = _row("ledger_unit_step")
    assert second["n_compiled"] == first["n_compiled"]
    assert second["n_loaded"] - first["n_loaded"] == 1
    assert second["load_s"] > first["load_s"]
    assert second["compile_s"] == first["compile_s"]


def test_nested_spans_are_booked_once():
    """``sin``, ``cos`` and the sum are jitted functions of their own: their
    traces open inside the step's.  The booked seconds are the union, no
    more than the wall time, and the rows add up to the phases."""
    def nested_unit_step(x):
        return jnp.sin(jnp.cos(x) @ x) + jnp.tanh(x).sum()

    x = jnp.ones((8, 8))
    x.block_until_ready()
    t0 = time.perf_counter()
    jax.jit(nested_unit_step)(x).block_until_ready()
    t1 = time.perf_counter()
    ledger = obs.startup_summary(until=t1, since=t0)
    rows = ledger["rows"]
    assert {"nested_unit_step", "sin", "cos"} <= set(rows)
    phases = ("trace_s", "lower_s", "compile_s", "load_s")
    booked = sum(ledger[p] for p in phases)
    assert 0 < booked <= t1 - t0
    assert sum(r[p] for r in rows.values() for p in phases) == pytest.approx(
        booked, rel=1e-6)
    # the step's own trace excludes the nested ones
    inner = sum(rows[k]["trace_s"] for k in rows if k != "nested_unit_step")
    assert inner > 0 and rows["nested_unit_step"]["trace_s"] > 0
    assert ledger["trace_s"] == pytest.approx(
        inner + rows["nested_unit_step"]["trace_s"], rel=1e-6)


def test_warm_calls_fire_no_callback():
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((16,))
    f(x).block_until_ready()
    tracker = dev.recompile_tracker()
    before = tracker.n_events
    for _ in range(100):
        y = f(x)
    y.block_until_ready()
    assert tracker.n_events == before


def test_compile_count_counts_misses_only(persistent_cache):
    reg = obs.configure()
    f = jax.jit(lambda x: x * 5 - 2)
    x = jnp.ones((6,))
    x.block_until_ready()
    counts = ("compile.count", "compile.load.count")
    base = [reg.counter(c).value for c in counts]
    f(x).block_until_ready()
    assert [reg.counter(c).value - b for c, b in zip(counts, base)] == [1, 0]
    jax.clear_caches()
    f(x).block_until_ready()
    assert [reg.counter(c).value - b for c, b in zip(counts, base)] == [1, 1]
    assert reg.counter("compile.load.ms").value >= 0
    assert reg.counter("compile.trace_lower.ms").value >= 0
    out = obs.runtime_summary()["compile"]
    assert {"count", "ms", "load", "trace_lower", "by_label"} <= set(out)
    assert out["load"]["count"] >= 1


def test_the_ledger_is_on_without_configure():
    """Importing the package installs it; building a train step keeps it."""
    from apex_tpu import amp
    from apex_tpu.optimizers import fused_adam

    assert dev.recompile_tracker() is not None
    amp.make_train_step(lambda p, x: (p * x).sum(), fused_adam(lr=1e-3), "O2")
    assert dev.recompile_tracker() is not None
