"""Generate the API reference (docs/api/*.md) from live docstrings.

The reference ships Sphinx RST covering every public class
(/root/reference/docs/source/*.rst); apex_tpu generates the equivalent
from the package itself so the reference can never drift from the code:

    JAX_PLATFORMS=cpu python docs/gen_api.py

Walks the public surface (every name in each module's ``__all__``, or
its public functions/classes when ``__all__`` is absent), emits one
markdown file per module group with signatures + docstrings, and an
index.  CI can diff the output to catch undocumented additions.
"""

from __future__ import annotations

import importlib
import re
import inspect
import os
import textwrap

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "api")

# module path -> (page, section title)
MODULES = [
    # amp
    ("apex_tpu.amp", "amp", "apex_tpu.amp — mixed precision"),
    ("apex_tpu.amp.frontend", "amp", "amp.frontend — train-step factory"),
    ("apex_tpu.amp.scaler", "amp", "amp.scaler — dynamic loss scaling"),
    ("apex_tpu.amp.policy", "amp", "amp.policy — opt-level policies"),
    ("apex_tpu.amp.patch", "amp", "amp.patch — O1 per-op cast engine"),
    # optimizers
    ("apex_tpu.optimizers", "optimizers",
     "apex_tpu.optimizers — fused optimizers"),
    ("apex_tpu.contrib.optimizers.distributed_fused_adam", "optimizers",
     "contrib.optimizers — ZeRO DistributedFusedAdam"),
    ("apex_tpu.contrib.optimizers.distributed_fused_lamb", "optimizers",
     "contrib.optimizers — ZeRO DistributedFusedLAMB"),
    # ops
    ("apex_tpu.ops._pallas_utils", "ops",
     "ops._pallas_utils — which implementation runs, shared helpers"),
    ("apex_tpu.ops.flash_attention", "ops",
     "ops.flash_attention — FlashAttention-2 kernels"),
    ("apex_tpu.ops.layer_norm", "ops", "ops.layer_norm — LN/RMSNorm"),
    ("apex_tpu.ops.softmax", "ops", "ops.softmax — scaled softmax family"),
    ("apex_tpu.ops.xentropy", "ops", "ops.xentropy — fused CE"),
    ("apex_tpu.ops.lm_head_ce", "ops",
     "ops.lm_head_ce — chunked head+CE fusion"),
    ("apex_tpu.ops.swiglu", "ops", "ops.swiglu — fused bias-SwiGLU"),
    ("apex_tpu.ops.rope", "ops", "ops.rope — rotary embeddings"),
    ("apex_tpu.ops.dense", "ops", "ops.dense — fused dense epilogues"),
    ("apex_tpu.ops.flat_adam", "ops", "ops.flat_adam — flat Adam"),
    ("apex_tpu.ops.collective_matmul", "ops",
     "ops.collective_matmul — overlapped ring TP collectives"),
    ("apex_tpu.ops.grouped_matmul", "ops",
     "ops.grouped_matmul — ragged expert segment matmul"),
    ("apex_tpu.ops.ssd_scan", "ops",
     "ops.ssd_scan — chunked state-space scan (Mamba-2)"),
    ("apex_tpu.ops.paged_attention", "ops",
     "ops.paged_attention — ragged paged-attention decode kernel"),
    ("apex_tpu.ops.fused_sampling", "ops",
     "ops.fused_sampling — fused temperature/top-k/top-p/sample kernel"),
    ("apex_tpu.ops.decode_step", "ops",
     "ops.decode_step — fused decode-layer megakernel "
     "(rope + paged attention + projection)"),
    # comm
    ("apex_tpu.comm", "comm",
     "apex_tpu.comm — compressed gradient collectives"),
    ("apex_tpu.comm.config", "comm",
     "comm.config — grad_comm spec (wire dtype / error feedback / buckets)"),
    ("apex_tpu.comm.quantize", "comm",
     "comm.quantize — block-scaled int8 / bf16 wire formats"),
    ("apex_tpu.comm.bucketing", "comm",
     "comm.bucketing — greedy dtype-segregated buckets"),
    ("apex_tpu.comm.reduce", "comm",
     "comm.reduce — compressed all-reduce / reduce-scatter + telemetry"),
    # checkpoint
    ("apex_tpu.checkpoint", "checkpoint",
     "apex_tpu.checkpoint — elastic fault-tolerant training state"),
    ("apex_tpu.checkpoint.sharded", "checkpoint",
     "checkpoint.sharded — per-process shards + atomic manifest"),
    ("apex_tpu.checkpoint.async_saver", "checkpoint",
     "checkpoint.async_saver — overlapped zero-stall saves"),
    ("apex_tpu.checkpoint.recovery", "checkpoint",
     "checkpoint.recovery — detector-driven rollback + LR re-warm"),
    # analysis (apexlint)
    ("apex_tpu.analysis.rules", "analysis",
     "analysis.rules — Tier-A AST rules (the invariant table)"),
    ("apex_tpu.analysis.linter", "analysis",
     "analysis.linter — rule driver, suppressions, baseline diff"),
    ("apex_tpu.analysis.env_registry", "analysis",
     "analysis.env_registry — the authoritative APEX_TPU_* table"),
    ("apex_tpu.analysis.callgraph", "analysis",
     "analysis.callgraph — traced-code reachability heuristic"),
    ("apex_tpu.analysis.jaxpr_audit", "analysis",
     "analysis.jaxpr_audit — Tier-B trace auditor (census, overlap, "
     "upcasts, donation)"),
    ("apex_tpu.analysis.concurrency", "analysis",
     "analysis.concurrency — Tier-C thread-escape graph + guarded-by "
     "discipline (APX501-503)"),
    ("apex_tpu.analysis.lifecycle", "analysis",
     "analysis.lifecycle — Tier-C thread/server lifecycle + paired "
     "acquire/release (APX504-505)"),
    ("apex_tpu.analysis.stress", "analysis",
     "analysis.stress — seeded concurrency stress smoke (the "
     "concurrency_audit gate's dynamic half)"),
    # parallel
    ("apex_tpu.parallel.mesh", "parallel", "parallel.mesh — device mesh"),
    ("apex_tpu.parallel.launch", "parallel",
     "parallel.launch — multi-host bootstrap"),
    ("apex_tpu.parallel.distributed", "parallel",
     "parallel.distributed — DDP"),
    ("apex_tpu.parallel.sync_batchnorm", "parallel",
     "parallel.sync_batchnorm — SyncBN"),
    ("apex_tpu.parallel.fsdp", "parallel", "parallel.fsdp — ZeRO-3"),
    ("apex_tpu.parallel.ring_attention", "parallel",
     "parallel.ring_attention — context parallelism (ring)"),
    ("apex_tpu.parallel.ulysses", "parallel",
     "parallel.ulysses — context parallelism (all-to-all)"),
    ("apex_tpu.parallel.LARC", "parallel", "parallel.LARC"),
    ("apex_tpu.parallel.clip_grad", "parallel", "parallel.clip_grad"),
    # transformer (Megatron layer)
    ("apex_tpu.transformer.parallel_state", "transformer",
     "transformer.parallel_state — process groups"),
    ("apex_tpu.transformer.tensor_parallel.layers", "transformer",
     "tensor_parallel.layers — Vocab/Column/Row"),
    ("apex_tpu.transformer.tensor_parallel.mappings", "transformer",
     "tensor_parallel.mappings — collectives"),
    ("apex_tpu.transformer.tensor_parallel.cross_entropy", "transformer",
     "tensor_parallel.cross_entropy"),
    ("apex_tpu.transformer.tensor_parallel.random", "transformer",
     "tensor_parallel.random — RNG streams"),
    ("apex_tpu.transformer.pipeline_parallel.schedules", "transformer",
     "pipeline_parallel.schedules — 1F1B / interleaved"),
    ("apex_tpu.transformer.pipeline_parallel.p2p_communication",
     "transformer", "pipeline_parallel.p2p_communication"),
    ("apex_tpu.transformer.microbatches", "transformer",
     "transformer.microbatches"),
    ("apex_tpu.transformer.moe", "transformer",
     "transformer.moe — Switch MoE"),
    ("apex_tpu.transformer._data", "transformer",
     "transformer._data — batch samplers"),
    # models
    ("apex_tpu.models.config", "models", "models.config"),
    ("apex_tpu.models.transformer_lm", "models",
     "models.transformer_lm — decoder backbone"),
    ("apex_tpu.models.gpt", "models", "models.gpt — GPT wiring"),
    ("apex_tpu.models.generate", "models",
     "models.generate — flash prefill + ragged KV-cache decoding"),
    ("apex_tpu.models.speculative", "models",
     "models.speculative — n-gram drafting + batched verification"),
    ("apex_tpu.models.quantized", "models",
     "models.quantized — weight-only int8 serving conversion"),
    ("apex_tpu.models.lora", "models",
     "models.lora — LoRA adapters: merged weights or ragged batched "
     "deltas"),
    ("apex_tpu.models.bert", "models", "models.bert"),
    ("apex_tpu.models.resnet", "models", "models.resnet"),
    # serving
    ("apex_tpu.serving", "serving",
     "apex_tpu.serving — continuous-batching inference engine"),
    ("apex_tpu.serving.engine", "serving",
     "serving.engine — ServingEngine + Request/Response"),
    ("apex_tpu.serving.batching", "serving",
     "serving.batching — prompt buckets + slot pool"),
    ("apex_tpu.serving.paged_cache", "serving",
     "serving.paged_cache — block pool, block tables, prefix sharing"),
    ("apex_tpu.serving.slo", "serving",
     "serving.slo — SLO classes, TTFT/TPOT deadlines, goodput judge"),
    ("apex_tpu.serving.compile_cache", "serving",
     "serving.compile_cache — persistent AOT executables + warmup "
     "ladder"),
    ("apex_tpu.serving.adapter_pool", "serving",
     "serving.adapter_pool — refcounted HBM LoRA slab pool"),
    ("apex_tpu.serving.cluster", "serving",
     "serving.cluster — disaggregated prefill/decode tier"),
    ("apex_tpu.serving.cluster.protocol", "serving",
     "serving.cluster.protocol — length-prefixed socket frames"),
    ("apex_tpu.serving.cluster.handoff", "serving",
     "serving.cluster.handoff — KV wire format (raw/bf16/int8)"),
    ("apex_tpu.serving.cluster.worker", "serving",
     "serving.cluster.worker — prefill/decode pool members"),
    ("apex_tpu.serving.cluster.router", "serving",
     "serving.cluster.router — SLO-aware dispatch + requeue"),
    ("apex_tpu.serving.cluster.controller", "serving",
     "serving.cluster.controller — elastic pool controller "
     "(spawn/drain on autoscale_signal)"),
    # data
    ("apex_tpu.data.image_folder", "data",
     "data.image_folder — file-backed input pipeline"),
    ("apex_tpu.data.prefetch", "data",
     "data.prefetch — device prefetch (data_prefetcher analog)"),
    # contrib
    ("apex_tpu.contrib.multihead_attn", "contrib",
     "contrib.multihead_attn"),
    ("apex_tpu.contrib.transducer", "contrib", "contrib.transducer"),
    ("apex_tpu.contrib.sparsity", "contrib", "contrib.sparsity — ASP"),
    ("apex_tpu.contrib.focal_loss", "contrib", "contrib.focal_loss"),
    ("apex_tpu.contrib.index_mul_2d", "contrib", "contrib.index_mul_2d"),
    ("apex_tpu.contrib.conv_bias_relu", "contrib",
     "contrib.conv_bias_relu"),
    ("apex_tpu.contrib.peer_memory", "contrib",
     "contrib.peer_memory — halo exchange"),
    ("apex_tpu.contrib.bottleneck", "contrib", "contrib.bottleneck"),
    # observability
    ("apex_tpu.observability", "observability",
     "apex_tpu.observability — telemetry"),
    ("apex_tpu.observability.metrics", "observability",
     "observability.metrics — registry, counters/gauges/histograms"),
    ("apex_tpu.observability.spans", "observability",
     "observability.spans — span API + StepTimer"),
    ("apex_tpu.observability.sinks", "observability",
     "observability.sinks — JSONL / stderr-summary sinks"),
    ("apex_tpu.observability.trace", "observability",
     "observability.trace — Chrome trace_events / Perfetto export"),
    ("apex_tpu.observability.recorder", "observability",
     "observability.recorder — flight recorder / crash post-mortem"),
    ("apex_tpu.observability.detectors", "observability",
     "observability.detectors — step-boundary anomaly detectors"),
    ("apex_tpu.observability.device", "observability",
     "observability.device — recompile tracking + HBM gauges"),
    ("apex_tpu.observability.sketches", "observability",
     "observability.sketches — mergeable log-bucket histogram sketch"),
    ("apex_tpu.observability.openmetrics", "observability",
     "observability.openmetrics — OpenMetrics text render/parse"),
    ("apex_tpu.observability.exporter", "observability",
     "observability.exporter — live /metrics + /healthz HTTP endpoint"),
    # misc
    ("apex_tpu.normalization", "misc", "apex_tpu.normalization"),
    ("apex_tpu.fused_dense", "misc", "apex_tpu.fused_dense"),
    ("apex_tpu.mlp", "misc", "apex_tpu.mlp"),
    ("apex_tpu.RNN", "misc", "apex_tpu.RNN"),
    ("apex_tpu.fp16_utils", "misc", "apex_tpu.fp16_utils"),
    ("apex_tpu.multi_tensor", "misc", "apex_tpu.multi_tensor"),
    ("apex_tpu.utils.checkpoint", "misc",
     "utils.checkpoint — save/resume + AutoResume"),
    ("apex_tpu.utils.collectives", "misc", "utils.collectives"),
    ("apex_tpu.testing", "misc", "apex_tpu.testing"),
]


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [n for n, obj in vars(mod).items()
            if not n.startswith("_")
            and (inspect.isfunction(obj) or inspect.isclass(obj))
            and getattr(obj, "__module__", "").startswith("apex_tpu")]


def _sig(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default-value reprs can embed memory addresses (<function f at
    # 0x7f...>) — strip them so regeneration is deterministic
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _doc(obj, indent="") -> str:
    doc = inspect.getdoc(obj) or "*(no docstring)*"
    # docstrings can embed object reprs with process-local addresses
    doc = re.sub(r" at 0x[0-9a-f]+", "", doc)
    return textwrap.indent(doc, indent)


def _emit_entry(lines, name, obj):
    if inspect.isclass(obj):
        lines.append(f"### class `{name}{_sig(obj)}`\n")
        lines.append(_doc(obj) + "\n")
        for mname in sorted(vars(obj)):
            if mname.startswith("_"):
                continue
            raw = inspect.getattr_static(obj, mname)
            if isinstance(raw, property):
                m, kind = raw.fget, "property "
            elif isinstance(raw, (staticmethod, classmethod)):
                m, kind = raw.__func__, ""
            elif inspect.isroutine(raw):
                m, kind = raw, ""
            else:
                continue
            if m is not None and inspect.getdoc(m):
                sig = "" if kind else _sig(m)
                lines.append(f"- **{kind}`{mname}{sig}`** — "
                             f"{(inspect.getdoc(m) or '').splitlines()[0]}")
        lines.append("")
    elif callable(obj):
        lines.append(f"### `{name}{_sig(obj)}`\n")
        lines.append(_doc(obj) + "\n")
    else:
        lines.append(f"### `{name}`\n")
        lines.append(f"*(constant — {type(obj).__name__})*\n")


def main(out_dir: str = OUT):
    os.makedirs(out_dir, exist_ok=True)
    pages: dict = {}
    skipped = []
    for mod_path, page, title in MODULES:
        try:
            mod = importlib.import_module(mod_path)
        except Exception as e:
            skipped.append((mod_path, str(e)))
            continue
        lines = pages.setdefault(page, [])
        lines.append(f"\n## {title}\n")
        head = (inspect.getdoc(mod) or "").strip()
        if head:
            lines.append(head.split("\n\n")[0] + "\n")
        for name in _public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None:
                continue
            _emit_entry(lines, name, obj)

    index = ["# apex_tpu API reference",
             "",
             "Generated from docstrings by `docs/gen_api.py` "
             "(regenerate after API changes).", ""]
    for page in sorted(pages):
        path = os.path.join(out_dir, f"{page}.md")
        with open(path, "w") as f:
            f.write(f"# apex_tpu API — {page}\n")
            f.write("\n".join(pages[page]) + "\n")
        index.append(f"- [{page}]({page}.md)")
        print(f"wrote {path}")
    with open(os.path.join(out_dir, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    if skipped:
        print("skipped:", skipped)
    return skipped


if __name__ == "__main__":
    main()
