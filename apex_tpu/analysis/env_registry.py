"""The authoritative table of ``APEX_TPU_*`` environment variables.

PR 4 established the pattern for telemetry: one registered, validated,
documented table (``observability.metrics.ENV_VARS``) with warn-by-name
on anything unknown.  This module generalizes it to the whole repo: any
``os.environ`` read of an ``APEX_TPU_*`` name must appear here (exact
name or a ``*``-suffixed family), name the module that owns its
validated parser, and point at the doc file that describes it.  The
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
    name: str          # exact name, or a family ending in "*"
    owner: str         # module whose parser validates it
    doc: str           # repo-relative doc file that describes it
    help: str


def _v(name, owner, doc, help):
    return (name, EnvVar(name, owner, doc, help))


# One row per variable (or per dynamic family, "*"-suffixed).  Keep
# sorted by name within each group; docs/static_analysis.md renders the
# consolidated table and the docs-sync rule holds each row to its
# declared file.
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
    _v("APEX_TPU_BACKEND", "apex_tpu.utils.registry",
       "docs/static_analysis.md",
       "force the op registry's backend (pallas|xla)"),
    _v("APEX_TPU_PALLAS_INTERPRET", "apex_tpu.utils.registry",
       "docs/inference.md",
       "run Pallas kernels in interpret mode (CPU testing)"),
    _v("APEX_TPU_DISABLE_*", "apex_tpu.utils.registry",
       "docs/static_analysis.md",
       "disable one registered op by name (fall back to XLA)"),
    _v("APEX_TPU_DISABLE_NATIVE", "apex_tpu.contrib.sparsity",
       "docs/static_analysis.md",
       "sparsity permutation search: force the python path"),
    _v("APEX_TPU_FLASH_BWD", "apex_tpu.ops.flash_attention",
       "docs/static_analysis.md",
       "flash-attention backward mode (auto|fused|split)"),
    _v("APEX_TPU_FLASH_BWD_FUSED_MAX", "apex_tpu.ops.flash_attention",
       "docs/static_analysis.md",
       "auto mode's fused/split seq-length crossover (default 512)"),
    _v("APEX_TPU_FLASH_FUSED_BQ", "apex_tpu.ops.flash_attention",
       "docs/static_analysis.md",
       "fused flash backward query-block size override"),
    _v("APEX_TPU_LN_BWD", "apex_tpu.ops.layer_norm",
       "docs/static_analysis.md",
       "layer-norm backward routing (pallas|xla)"),
    _v("APEX_TPU_SOFTMAX", "apex_tpu.ops.softmax",
       "docs/static_analysis.md",
       "softmax family routing (pallas forces the kernel)"),
    _v("APEX_TPU_FUSED_SAMPLING", "apex_tpu.ops.fused_sampling",
       "docs/inference.md",
       "fused sampling kernel routing (kernel|reference|auto)"),
    _v("APEX_TPU_PAGED_ATTENTION", "apex_tpu.ops.paged_attention",
       "docs/inference.md",
       "paged-attention kernel routing (kernel|reference|auto)"),
    _v("APEX_TPU_GROUPED_MATMUL", "apex_tpu.ops.grouped_matmul",
       "docs/parallelism.md",
       "grouped (ragged expert) matmul routing (kernel|reference|auto)"),
    _v("APEX_TPU_DECODE_FUSED", "apex_tpu.ops.decode_step",
       "docs/inference.md",
       "fused decode-layer megakernel routing "
       "(kernel|reference|auto)"),
    _v("APEX_TPU_QUANT_MATMUL", "apex_tpu.ops.dense",
       "docs/inference.md",
       "weight-only int8 dense/grouped matmul routing "
       "(kernel|reference|auto)"),
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
    """Resolve an env-var name against the table: exact match first,
    then the longest matching ``*`` family.  Returns the
    :class:`EnvVar` row or ``None`` (unregistered)."""
    hit = ENV_REGISTRY.get(name)
    if hit is not None:
        return hit
    best = None
    for key, row in ENV_REGISTRY.items():
        if key.endswith("*") and name.startswith(key[:-1]):
            if best is None or len(key) > len(best[0]):
                best = (key, row)
    return best[1] if best else None
