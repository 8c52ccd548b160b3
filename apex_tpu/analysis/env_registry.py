"""The authoritative table of ``APEX_TPU_*`` environment variables.

PR 4 established the pattern for telemetry: one registered, validated,
documented table (``observability.metrics.ENV_VARS``) with warn-by-name
on anything unknown.  This module generalizes it to the whole repo: any
``os.environ`` read of an ``APEX_TPU_*`` name must appear here by its
exact name, name the module that owns its validated parser, and point
at the doc file that describes it.  The
linter enforces all three:

- APX201 (``unregistered-env-var``): an env read whose literal name is
  not in this table;
- APX202 (``undocumented-env-var``): a registered variable whose name
  does not appear in its declared doc file;
- APX203 (``env-table-sync``): the telemetry rows here must exactly
  mirror ``observability.metrics.ENV_VARS`` (statically parsed from the
  source, so this module never has to import the package).

Stdlib-only by contract (Tier-A modules run without jax).
"""

from __future__ import annotations

from typing import Dict, NamedTuple

__all__ = ["EnvVar", "ENV_REGISTRY", "lookup", "telemetry_names"]


class EnvVar(NamedTuple):
    name: str          # exact name
    owner: str         # module whose parser validates it
    doc: str           # repo-relative doc file that describes it
    help: str


def _v(name, owner, doc, help):
    return (name, EnvVar(name, owner, doc, help))


# One row per variable.  Keep sorted by name within each group;
# docs/static_analysis.md renders the consolidated table and the
# docs-sync rule holds each row to its declared file.
ENV_REGISTRY: Dict[str, EnvVar] = dict([
    # ---- telemetry (must mirror observability.metrics.ENV_VARS) ----
    _v("APEX_TPU_TELEMETRY", "apex_tpu.observability.metrics",
       "docs/observability.md", "JSONL record-stream file"),
    _v("APEX_TPU_TELEMETRY_STDERR", "apex_tpu.observability.metrics",
       "docs/observability.md", "per-metric summary table at shutdown"),
    _v("APEX_TPU_TELEMETRY_PROFILER", "apex_tpu.observability.metrics",
       "docs/observability.md", "jax.profiler span annotations (xprof)"),
    _v("APEX_TPU_TELEMETRY_TRACE", "apex_tpu.observability.metrics",
       "docs/observability.md", "Chrome trace_events JSON timeline"),
    _v("APEX_TPU_TELEMETRY_FLIGHT", "apex_tpu.observability.metrics",
       "docs/observability.md", "flight-recorder post-mortem dump path"),
    _v("APEX_TPU_TELEMETRY_FLIGHT_STEPS", "apex_tpu.observability.metrics",
       "docs/observability.md", "flight-recorder ring size (steps)"),
    _v("APEX_TPU_TELEMETRY_DETECTORS", "apex_tpu.observability.metrics",
       "docs/observability.md", "step-boundary anomaly detectors"),
    _v("APEX_TPU_TELEMETRY_PORT", "apex_tpu.observability.metrics",
       "docs/observability.md", "serve /metrics + /healthz on this port"),
    # ---- kernel/backend routing --------------------------------------
    _v("APEX_TPU_PALLAS_INTERPRET", "apex_tpu.ops._pallas_utils",
       "docs/inference.md",
       "run Pallas kernels in interpret mode (CPU testing)"),
    _v("APEX_TPU_DISABLE_NATIVE", "apex_tpu.contrib.sparsity",
       "docs/static_analysis.md",
       "sparsity permutation search: force the python path"),
    # ---- serving knobs -----------------------------------------------
    _v("APEX_TPU_CHUNK_TOKENS", "apex_tpu.serving.engine",
       "docs/serving.md",
       "chunked-prefill chunk size override (positive int; off/0 "
       "forces monolithic prefill)"),
    _v("APEX_TPU_COMPILE_CACHE", "apex_tpu.serving.compile_cache",
       "docs/serving.md",
       "persistent AOT compile-cache directory (engine default when "
       "compile_cache_dir is not passed)"),
    _v("APEX_TPU_HOST_TIER_BYTES", "apex_tpu.serving.host_tier",
       "docs/serving.md",
       "host-DRAM KV offload tier capacity (bytes, 256m/2g suffixes; "
       "off/0 disables)"),
    _v("APEX_TPU_HOST_TIER_WIRE", "apex_tpu.serving.host_tier",
       "docs/serving.md",
       "host-tier at-rest codec (raw|int8; raw keeps digest parking "
       "bitwise)"),
    _v("APEX_TPU_ADAPTER_POOL_BYTES", "apex_tpu.serving.adapter_pool",
       "docs/serving.md",
       "HBM budget for the LoRA adapter slab pool (bytes, 256m/2g "
       "suffixes; admission blocks when a request's adapter cannot "
       "fit)"),
    # ---- training / parallel knobs -----------------------------------
    _v("APEX_TPU_ALLOW_FP16", "apex_tpu.amp.policy",
       "docs/amp.md", "permit raw fp16 on TPU (default maps to bf16)"),
    _v("APEX_TPU_CP_STRICT", "apex_tpu.models.transformer_lm",
       "docs/parallelism.md",
       "context parallel: error instead of falling back"),
    _v("APEX_TPU_TERMINATION_FILE", "apex_tpu.utils.checkpoint",
       "docs/static_analysis.md",
       "AutoResume: scheduler's checkpoint-and-requeue request file"),
    # ---- test / harness ----------------------------------------------
    _v("APEX_TPU_SKIP_FLAKY_TEST", "apex_tpu.testing.common_utils",
       "docs/static_analysis.md",
       "skip tests marked flaky (reference-parity harness knob)"),
    _v("APEX_TPU_TEST_ON_TPU", "tests.conftest",
       "docs/static_analysis.md",
       "keep the real chip attached for the tpu-marked kernel tests"),
    _v("APEX_TPU_DRYRUN_PHASE", "__graft_entry__",
       "docs/static_analysis.md",
       "pin the dryrun gate to one parity phase"),
    _v("APEX_TPU_DRYRUN_CHILD", "__graft_entry__",
       "docs/static_analysis.md",
       "internal: marks a re-exec'd virtual-CPU dryrun child"),
])


def telemetry_names() -> tuple:
    """The registered telemetry variables (APX203 checks these against
    a static parse of ``observability.metrics.ENV_VARS``)."""
    return tuple(sorted(n for n in ENV_REGISTRY
                        if n.startswith("APEX_TPU_TELEMETRY")))


def lookup(name: str):
    """The :class:`EnvVar` row of ``name``, or ``None`` (unregistered;
    a name built at run time has no row, so its read is refused)."""
    return ENV_REGISTRY.get(name)
