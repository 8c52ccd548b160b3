"""Tier-A lint rules: the repo's implicit invariants as AST checks.

Each rule encodes one invariant a past PR established (the rule table
with rationale lives in docs/static_analysis.md); every rule has
positive+negative fixtures in tests/test_lint.py and the grep-guard
families keep their tier-1 names in tests/test_observability_guard.py,
now thin runners over these rules.

Rule ids are stable (baselines and suppression comments reference
them):

- ``APX101`` chained-registry-call          (PR 1/4 zero-overhead path)
- ``APX102`` direct-registry-construction   (one registry, via configure)
- ``APX103`` private-registry-global        (_REGISTRY is owner-private)
- ``APX104`` module-level-exporter-import   (PR 7 lazy HTTP machinery)
- ``APX105`` metric-prefix-helper           (moe./checkpoint./generate.spec.
  /serving.compile_cache./worker.ready_ms accounting rides the module
  helpers on the same statement)
- ``APX106`` ungated-memory-sample          (hot paths gate HBM sampling)
- ``APX201`` unregistered-env-var           (PR 4 warn-by-name pattern,
  generalized: every APEX_TPU_* read is in analysis/env_registry.py)
- ``APX202`` undocumented-env-var           (docs-sync per registry row)
- ``APX203`` env-table-sync                 (registry mirrors
  observability.metrics.ENV_VARS, statically parsed)
- ``APX301`` host-sync-in-traced-code       (.item()/float()/np.asarray/
  device_get under a jax trace — heuristic call graph, see callgraph.py)
- ``APX302`` nondeterminism-in-traced-code  (time.*/stdlib random/
  np.random under a trace; jax.random is fine)
- ``APX401`` use-after-donation             (a buffer passed at a
  donate_argnums/argnames position is dead after the call)

Tier C (the APX5xx concurrency & lifecycle family) lives in the
sibling :mod:`~apex_tpu.analysis.concurrency` and
:mod:`~apex_tpu.analysis.lifecycle` modules and registers through
:func:`all_rules`; it shares this module's Finding/fingerprint/
suppression machinery unchanged.

Suppression: ``# apexlint: disable=APX301`` (comma list or ``all``) on
the offending line, or ``# apexlint: skip-file`` in a file's first ten
lines.  Grandfathered findings live in LINT_BASELINE.json with a
justification (tools/lint.py --write-baseline).

Stdlib-only by contract: no jax, no apex_tpu imports beyond the
sibling analysis modules.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from apex_tpu.analysis import env_registry
from apex_tpu.analysis.callgraph import traced_functions

__all__ = [
    "Finding",
    "ModuleInfo",
    "Rule",
    "ALL_RULES",
    "TIER_A_RULES",
    "all_rules",
    "rules_by_id",
    "module_from_source",
]


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str        # repo-relative, "/"-separated
    line: int
    col: int
    message: str
    severity: str
    snippet: str

    def location(self) -> str:
        return f"{self.path}:{self.line}"

    def fingerprint(self, ordinal: int = 0) -> str:
        """Line-number-free identity so baselines survive unrelated
        edits: rule + path + the offending source text, plus an ordinal
        distinguishing identical snippets in one file."""
        norm = " ".join(self.snippet.split())
        h = hashlib.sha256(
            f"{self.rule}|{self.path}|{norm}".encode()).hexdigest()[:16]
        return f"{self.rule}:{h}:{ordinal}"


class ModuleInfo:
    """One parsed target file plus the derived context rules key on."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source)
        self.in_pkg = self.relpath.startswith("apex_tpu/")
        self.is_obs = self.relpath.startswith("apex_tpu/observability/")
        self.is_analysis = self.relpath.startswith("apex_tpu/analysis/")
        self.basename = os.path.basename(self.relpath)
        self._parents: Optional[Dict[ast.AST, ast.AST]] = None

    def parents(self) -> Dict[ast.AST, ast.AST]:
        if self._parents is None:
            self._parents = {}
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    self._parents[child] = node
        return self._parents

    def segment(self, node: ast.AST) -> str:
        return ast.get_source_segment(self.source, node) or ""

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""


def module_from_source(source: str, relpath: str = "apex_tpu/_fixture.py",
                       ) -> ModuleInfo:
    """Build a ModuleInfo from an in-memory snippet (fixture tests)."""
    return ModuleInfo(path=relpath, relpath=relpath, source=source)


class Rule:
    """One invariant as a check: per-module rules implement
    :meth:`check`; repo-level rules (docs-sync, table-sync, the
    donation rule's cross-module pass) implement :meth:`check_repo`
    and run once over the parsed module set."""

    id: str = ""
    name: str = ""
    severity: str = "error"
    description: str = ""
    # "A" = AST repo rules (this module); "C" = the concurrency/
    # lifecycle auditor (analysis/concurrency.py + lifecycle.py).
    # Tier B (the jaxpr auditor) is not a Rule — it needs jax.
    tier: str = "A"
    # repo-level rules run once over the module set instead of per file
    repo_level: bool = False

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        return iter(())

    def check_repo(self, modules: List[ModuleInfo],
                   root: str) -> Iterator[Finding]:
        return iter(())

    def finding(self, mod: ModuleInfo, node: ast.AST,
                message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(rule=self.id, path=mod.relpath, line=line,
                       col=getattr(node, "col_offset", 0) + 1,
                       message=message, severity=self.severity,
                       snippet=mod.line_text(line))


def _dotted(node: ast.AST) -> Optional[str]:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# APX10x — the telemetry fast-path families (PR 1/4/7/8/10/11 guards)
# ---------------------------------------------------------------------------

METRIC_METHODS = {
    "counter", "gauge", "histogram", "sketch", "event", "observe_span",
    "set_step", "summary", "snapshot",
}


class ChainedRegistryRule(Rule):
    id = "APX101"
    name = "chained-registry-call"
    description = ("unconditional registry().<metric>() bypasses the "
                   "no-op fast path — bind-and-check or use the "
                   "module-level helpers")

    def check(self, mod):
        if not mod.in_pkg or mod.is_obs:
            return
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in METRIC_METHODS
                    and isinstance(node.func.value, ast.Call)):
                inner = node.func.value.func
                if (isinstance(inner, ast.Name) and inner.id == "registry"
                        ) or (isinstance(inner, ast.Attribute)
                              and inner.attr == "registry"):
                    yield self.finding(
                        mod, node,
                        f"chained registry().{node.func.attr}(...) — "
                        "bind-and-check (reg = registry(); if reg is "
                        "None: ...) or use the module-level helper")


class DirectRegistryRule(Rule):
    id = "APX102"
    name = "direct-registry-construction"
    description = ("a second MetricsRegistry() dodges configure/"
                   "shutdown and the module-level fast path")

    def check(self, mod):
        if not mod.in_pkg or mod.is_obs:
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                fn = node.func
                name = (fn.id if isinstance(fn, ast.Name)
                        else fn.attr if isinstance(fn, ast.Attribute)
                        else None)
                if name == "MetricsRegistry":
                    yield self.finding(
                        mod, node,
                        "direct MetricsRegistry() construction — go "
                        "through observability.configure()")


class PrivateGlobalRule(Rule):
    id = "APX103"
    name = "private-registry-global"
    description = ("_REGISTRY is private to observability.metrics; go "
                   "through registry()/enabled()")

    def check(self, mod):
        if not mod.in_pkg:
            return
        if mod.is_obs and mod.basename == "metrics.py":
            return   # the owner
        for node in ast.walk(mod.tree):
            hit = (
                (isinstance(node, ast.Name) and node.id == "_REGISTRY")
                or (isinstance(node, ast.Attribute)
                    and node.attr == "_REGISTRY")
                or (isinstance(node, ast.ImportFrom)
                    and any(a.name == "_REGISTRY" for a in node.names)))
            if hit:
                yield self.finding(
                    mod, node,
                    "_REGISTRY access outside its owner — use "
                    "registry()/enabled()")


class ExporterImportRule(Rule):
    id = "APX104"
    name = "module-level-exporter-import"
    description = ("the exporter must only load lazily inside "
                   "configure(export_port=...) — a module-level import "
                   "pays for HTTP machinery on every unconfigured "
                   "import apex_tpu")

    _TARGET = "apex_tpu.observability.exporter"

    def check(self, mod):
        if not mod.in_pkg:
            return
        # AST beats the old ^-anchored grep here: an import nested in a
        # module-level if/try still runs at import time and is flagged;
        # only imports inside a function body are lazy.
        func_spans: List[Tuple[int, int]] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                func_spans.append((node.lineno, node.end_lineno or
                                   node.lineno))
        for node in ast.walk(mod.tree):
            hit = (
                (isinstance(node, ast.Import)
                 and any(a.name == self._TARGET for a in node.names))
                or (isinstance(node, ast.ImportFrom)
                    and node.module == self._TARGET))
            if not hit:
                continue
            ln = node.lineno
            if any(lo < ln <= hi for lo, hi in func_spans):
                continue   # inside a function: the lazy form
            yield self.finding(
                mod, node,
                "module-level import of the telemetry exporter — "
                "configure(export_port=...) imports it lazily")


class MetricPrefixRule(Rule):
    id = "APX105"
    name = "metric-prefix-helper"
    description = ("moe.* / checkpoint.* / generate.spec.* / "
                   "serving.compile_cache.* / serving.host_tier.* / "
                   "serving.adapter.* / cluster.prefix_affinity_* / "
                   "cluster.adapter_affinity_* / worker.ready_ms "
                   "metric touches must ride the _telemetry helpers "
                   "on the same statement — a second access idiom "
                   "forks the accounting telemetry_report and the "
                   "dryrun gates read")

    _CKPT = ("saves", "bytes", "restores", "rollbacks", "overlap_ratio")
    # prefix -> allowed _telemetry helper attributes
    PREFIXES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
        ("generate.spec.", ("counter",)),
        ("moe.", ("counter", "gauge")),
        # ISSUE 17: the compile-cache hit/miss/load ledger and the
        # worker READY gauge feed telemetry_report's
        # compile_cache_summary — same one-accounting-path contract
        ("serving.compile_cache.", ("counter", "histogram", "event")),
        ("worker.ready_ms", ("gauge",)),
        # ISSUE 18: the hierarchical-KV ledger (hit/miss/eviction
        # counters, bytes/pages gauges, page-in/out sketches) and the
        # router's prefix-affinity counter feed telemetry_report's
        # host_tier_summary — same one-accounting-path contract
        ("serving.host_tier.", ("counter", "gauge", "sketch")),
        ("cluster.prefix_affinity_", ("counter",)),
        # ISSUE 20: the adapter-pool ledger (hit/miss/eviction
        # counters, residency gauges) and the router's
        # adapter-affinity counter feed telemetry_report's
        # adapter_summary — same one-accounting-path contract
        ("serving.adapter.", ("counter", "gauge")),
        ("cluster.adapter_affinity_", ("counter",)),
    ) + tuple((f"checkpoint.{n}", ("counter", "gauge")) for n in _CKPT)

    def _match(self, value: str) -> Optional[Tuple[str, Tuple[str, ...]]]:
        for prefix, helpers in self.PREFIXES:
            if value.startswith(prefix):
                return prefix, helpers
        return None

    def check(self, mod):
        # the observability package owns the registry internals; the
        # analysis package READS these counters by name to diff them
        # against the jaxpr census (Tier B) — neither emits a second
        # accounting path
        if not mod.in_pkg or mod.is_obs or mod.is_analysis:
            return
        parents = mod.parents()
        for node in ast.walk(mod.tree):
            value = None
            if isinstance(node, ast.Constant) and isinstance(node.value,
                                                             str):
                # a Constant inside an f-string is covered by its
                # JoinedStr match — reporting both would double-count
                # one violation
                if isinstance(parents.get(node), ast.JoinedStr):
                    continue
                value = node.value
            elif (isinstance(node, ast.JoinedStr) and node.values
                  and isinstance(node.values[0], ast.Constant)
                  and isinstance(node.values[0].value, str)):
                value = node.values[0].value
            if value is None:
                continue
            m = self._match(value)
            if m is None:
                continue
            prefix, helpers = m
            cur = parents.get(node)
            ok = False
            while cur is not None:
                if isinstance(cur, ast.Call):
                    fn = _dotted(cur.func)
                    if fn in tuple(f"_telemetry.{h}" for h in helpers):
                        ok = True
                        break
                if isinstance(cur, ast.stmt):
                    break
                cur = parents.get(cur)
            if not ok:
                yield self.finding(
                    mod, node,
                    f"{value!r} touched outside "
                    + "/".join(f"_telemetry.{h}(...)" for h in helpers)
                    + " on the same statement")


class GatedMemorySampleRule(Rule):
    id = "APX106"
    name = "ungated-memory-sample"
    description = ("sample_device_memory() is a real runtime query per "
                   "call — hot paths gate it on enabled() / "
                   "bind-and-check (or pass emit=False)")

    _GATE = re.compile(r"enabled\(\)|is not None|is None|emit=False")

    def check(self, mod):
        if not mod.in_pkg or mod.is_obs:
            return
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and ((isinstance(node.func, ast.Name)
                          and node.func.id == "sample_device_memory")
                         or (isinstance(node.func, ast.Attribute)
                             and node.func.attr
                             == "sample_device_memory"))):
                continue
            if any(kw.arg == "emit" for kw in node.keywords):
                continue   # caller-owns-it form (checked by regex too,
                           # but the AST keyword is the precise signal)
            lo = max(0, node.lineno - 3)
            context = "\n".join(mod.lines[lo:node.lineno])
            if not self._GATE.search(context):
                yield self.finding(
                    mod, node,
                    "ungated sample_device_memory() — gate on "
                    "enabled() within two lines or pass emit=False")


# ---------------------------------------------------------------------------
# APX20x — env-var discipline (the PR-4 pattern, repo-wide)
# ---------------------------------------------------------------------------


def _env_name_from_arg(arg: ast.AST) -> Optional[str]:
    """A literal (or f-string static prefix) env-var name, if the
    expression names one."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if (isinstance(arg, ast.JoinedStr) and arg.values
            and isinstance(arg.values[0], ast.Constant)
            and isinstance(arg.values[0].value, str)):
        return arg.values[0].value
    return None


class UnregisteredEnvVarRule(Rule):
    id = "APX201"
    name = "unregistered-env-var"
    description = ("every APEX_TPU_* env read must be registered in "
                   "analysis/env_registry.py (owner + doc pointer) — "
                   "the generalized warn-by-name table")

    def check(self, mod):
        for node in ast.walk(mod.tree):
            name = None
            if isinstance(node, ast.Call):
                fn = node.func
                is_get = (isinstance(fn, ast.Attribute)
                          and fn.attr == "get")
                is_getenv = (_dotted(fn) or "").endswith("os.getenv") \
                    or _dotted(fn) == "getenv"
                if (is_get or is_getenv) and node.args:
                    name = _env_name_from_arg(node.args[0])
            elif isinstance(node, ast.Subscript):
                tgt = _dotted(node.value) or ""
                if tgt.endswith("environ") or tgt == "env":
                    name = _env_name_from_arg(node.slice)
            if not name or not name.startswith("APEX_TPU_"):
                continue
            if env_registry.lookup(name) is None:
                yield self.finding(
                    mod, node,
                    f"env read of unregistered {name} — add a row to "
                    "apex_tpu/analysis/env_registry.py (owner module + "
                    "doc file) and document it there")


class UndocumentedEnvVarRule(Rule):
    id = "APX202"
    name = "undocumented-env-var"
    repo_level = True
    description = ("each registered APEX_TPU_* variable must appear in "
                   "its declared doc file (docs-sync)")

    def check_repo(self, modules, root):
        cache: Dict[str, str] = {}
        for name, row in sorted(env_registry.ENV_REGISTRY.items()):
            doc = row.doc
            if doc not in cache:
                path = os.path.join(root, doc)
                try:
                    with open(path) as f:
                        cache[doc] = f.read()
                except OSError:
                    cache[doc] = ""
            if name not in cache[doc]:
                yield Finding(
                    rule=self.id, path=doc, line=1, col=1,
                    message=(f"registered env var {name} is not "
                             f"mentioned in its declared doc file "
                             f"{doc}"),
                    severity=self.severity, snippet=name)


class EnvTableSyncRule(Rule):
    id = "APX203"
    name = "env-table-sync"
    repo_level = True
    description = ("the registry's telemetry rows must exactly mirror "
                   "observability.metrics.ENV_VARS (statically parsed "
                   "— the linter never imports the package)")

    _METRICS = "apex_tpu/observability/metrics.py"

    def check_repo(self, modules, root):
        mod = next((m for m in modules if m.relpath == self._METRICS),
                   None)
        if mod is None:
            return
        prefix, suffixes = None, None
        for node in mod.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
            if "ENV_PREFIX" in targets and isinstance(node.value,
                                                      ast.Constant):
                prefix = node.value.value
            if "ENV_VARS" in targets and isinstance(node.value, ast.Dict):
                suffixes = [k.value for k in node.value.keys
                            if isinstance(k, ast.Constant)]
        if prefix is None or suffixes is None:
            yield Finding(
                rule=self.id, path=self._METRICS, line=1, col=1,
                message=("could not statically parse ENV_PREFIX/"
                         "ENV_VARS from metrics.py — the sync rule "
                         "needs the literal table"),
                severity=self.severity, snippet="ENV_VARS")
            return
        expected = sorted(prefix + s for s in suffixes)
        got = sorted(env_registry.telemetry_names())
        if expected != got:
            missing = sorted(set(expected) - set(got))
            stale = sorted(set(got) - set(expected))
            yield Finding(
                rule=self.id, path="apex_tpu/analysis/env_registry.py",
                line=1, col=1,
                message=("telemetry env rows out of sync with "
                         f"metrics.ENV_VARS: missing={missing} "
                         f"stale={stale}"),
                severity=self.severity, snippet="ENV_REGISTRY")


# ---------------------------------------------------------------------------
# APX30x — host syncs / nondeterminism under a jax trace
# ---------------------------------------------------------------------------

# attribute reads that are static at trace time (shapes live on the
# aval, not the buffer) — int(x.shape[0]) is not a host sync
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize",
                 "sharding", "aval", "weak_type"}


def _module_aliases(tree: ast.Module) -> Tuple[Dict[str, str],
                                               Dict[str, str]]:
    """(import aliases, from-imports): ``import numpy as np`` →
    aliases["np"] == "numpy"; ``from time import time`` →
    fromimports["time"] == "time.time"."""
    aliases: Dict[str, str] = {}
    fromimports: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                fromimports[a.asname or a.name] = \
                    f"{node.module}.{a.name}"
    return aliases, fromimports


def _contains_dynamic_param(node: ast.AST, params: Set[str]) -> bool:
    """Does the expression reference a function parameter other than
    through a static attribute (.shape/.dtype/...) or inside a
    ``math.*`` call?  (stdlib math raises on tracers immediately, so
    ``int(math.prod(shape))`` cannot be a *silent* host sync — but the
    exemption covers only the math call's own subtree, so
    ``float(math.sqrt(2.0) * x)`` still flags on ``x``.)"""
    exempt: Set[int] = set()
    for sub in ast.walk(node):
        if (isinstance(sub, ast.Call)
                and (_dotted(sub.func) or "").startswith("math.")):
            for inner in ast.walk(sub):
                exempt.add(id(inner))
    for sub in ast.walk(node):
        if id(sub) in exempt:
            continue
        if (isinstance(sub, ast.Name) and sub.id in params
                and not _under_static_attr(node, sub)):
            return True
    return False


def _under_static_attr(root: ast.AST, target: ast.Name) -> bool:
    """True when ``target`` appears only as the base of a
    ``.shape``-like access inside ``root`` (best effort: checks the
    innermost attribute wrapping it)."""
    for sub in ast.walk(root):
        if (isinstance(sub, ast.Attribute)
                and sub.attr in _STATIC_ATTRS):
            for inner in ast.walk(sub.value):
                if inner is target:
                    return True
    return False


class _TracedCodeRule(Rule):
    """Shared machinery: locate traced functions and walk their bodies
    (excluding nested defs, which are visited as their own traced
    entries).  The call-graph fixpoint and the qualname index are
    computed once per module and memoized on the ModuleInfo — APX301
    and APX302 share them instead of re-running the visitor."""

    @staticmethod
    def _traced_index(mod: ModuleInfo):
        cached = getattr(mod, "_traced_index_cache", None)
        if cached is not None:
            return cached
        traced = traced_functions(mod.tree)
        index: Dict[str, ast.AST] = {}

        class V(ast.NodeVisitor):
            def __init__(self):
                self.stack = []

            def _f(self, node):
                qual = ".".join([*self.stack, node.name])
                index[qual] = node
                self.stack.append(node.name)
                self.generic_visit(node)
                self.stack.pop()

            visit_FunctionDef = _f
            visit_AsyncFunctionDef = _f

            def visit_ClassDef(self, node):
                self.stack.append(node.name)
                self.generic_visit(node)
                self.stack.pop()

        if traced:
            V().visit(mod.tree)
        mod._traced_index_cache = (traced, index)
        return mod._traced_index_cache

    def _iter_traced_bodies(self, mod: ModuleInfo):
        traced, index = self._traced_index(mod)
        for qual, reason in traced.items():
            node = index.get(qual)
            if node is None:
                continue
            # params annotated as host scalars (int/float/bool/str)
            # are static by contract — int(msg_nbytes) on an
            # `msg_nbytes: int` parameter is not a host sync
            _HOST_ANNOT = {"int", "float", "bool", "str"}
            params = {
                a.arg for a in (node.args.posonlyargs + node.args.args
                                + node.args.kwonlyargs)
                if not (isinstance(a.annotation, ast.Name)
                        and a.annotation.id in _HOST_ANNOT)}
            yield qual, reason, node, params

    @staticmethod
    def _walk_body(func_node):
        """Walk a function body without descending into nested defs."""
        stack = list(func_node.body)
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    continue
                stack.append(child)


class HostSyncRule(_TracedCodeRule):
    id = "APX301"
    name = "host-sync-in-traced-code"
    description = (".item()/float()/int() on traced values, "
                   "np.asarray, device_get, block_until_ready inside "
                   "code reachable from jit/scan/while_loop/shard_map "
                   "— a host round-trip per trace (or a tracer error)")

    _SYNC_ATTRS = {"item", "block_until_ready", "copy_to_host_async"}
    _CASTS = {"float", "int", "bool"}

    def check(self, mod):
        if not mod.in_pkg:
            return
        aliases, _ = _module_aliases(mod.tree)
        np_names = {a for a, m in aliases.items() if m == "numpy"}
        for qual, reason, node, params in self._iter_traced_bodies(mod):
            for sub in self._walk_body(node):
                if not isinstance(sub, ast.Call):
                    continue
                fn = sub.func
                # x.item() / x.block_until_ready()
                if (isinstance(fn, ast.Attribute)
                        and fn.attr in self._SYNC_ATTRS):
                    yield self.finding(
                        mod, sub,
                        f".{fn.attr}() inside traced {qual} "
                        f"({reason}) — a host sync per trace")
                    continue
                dotted = _dotted(fn) or ""
                # np.asarray / np.array / jax.device_get on dynamic args
                root = dotted.split(".", 1)[0]
                is_np_mat = (root in np_names and dotted.endswith(
                    (".asarray", ".array")))
                is_devget = dotted.endswith("device_get")
                if (is_np_mat or is_devget) and sub.args and any(
                        _contains_dynamic_param(a, params)
                        for a in sub.args):
                    yield self.finding(
                        mod, sub,
                        f"{dotted}(...) on a traced value inside "
                        f"{qual} ({reason}) — materializes to host")
                    continue
                # float(x)/int(x)/bool(x) on a traced parameter value
                if (isinstance(fn, ast.Name) and fn.id in self._CASTS
                        and len(sub.args) == 1
                        and _contains_dynamic_param(sub.args[0],
                                                    params)):
                    yield self.finding(
                        mod, sub,
                        f"{fn.id}(...) on a traced value inside "
                        f"{qual} ({reason}) — concretization error or "
                        "silent host sync")


class NondeterminismRule(_TracedCodeRule):
    id = "APX302"
    name = "nondeterminism-in-traced-code"
    description = ("time.* / stdlib random / np.random inside traced "
                   "code bakes one host value into the compiled "
                   "program (a silent per-trace constant); use "
                   "jax.random with explicit keys or hoist to the "
                   "host loop")

    def check(self, mod):
        if not mod.in_pkg:
            return
        aliases, fromimports = _module_aliases(mod.tree)
        time_names = {a for a, m in aliases.items() if m == "time"}
        rand_names = {a for a, m in aliases.items() if m == "random"}
        np_names = {a for a, m in aliases.items() if m == "numpy"}
        _TIME_FNS = {"time", "perf_counter", "monotonic", "time_ns",
                     "perf_counter_ns", "monotonic_ns"}
        for qual, reason, node, _params in self._iter_traced_bodies(mod):
            for sub in self._walk_body(node):
                if not isinstance(sub, ast.Call):
                    continue
                dotted = _dotted(sub.func) or ""
                parts = dotted.split(".")
                bad = None
                if parts[0] in time_names and len(parts) > 1:
                    bad = f"{dotted}() reads the host clock"
                elif parts[0] in rand_names and len(parts) > 1:
                    bad = f"{dotted}() draws from host RNG state"
                elif (parts[0] in np_names and len(parts) > 2
                      and parts[1] == "random"):
                    bad = f"{dotted}() draws from numpy RNG state"
                elif (isinstance(sub.func, ast.Name)
                      and fromimports.get(sub.func.id, "").startswith(
                          "time.")
                      and fromimports[sub.func.id].split(".")[-1]
                      in _TIME_FNS):
                    bad = (f"{sub.func.id}() (from time import ...) "
                           "reads the host clock")
                if bad:
                    yield self.finding(
                        mod, sub,
                        f"{bad} inside traced {qual} ({reason}) — the "
                        "value freezes at trace time")


# ---------------------------------------------------------------------------
# APX401 — donation safety
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _DonatingDef:
    positions: Set[int]
    kwnames: Set[str]
    where: str


def _literal_positions(node: ast.AST) -> Optional[Set[int]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)):
                return None
            out.add(elt.value)
        return out
    return None


def _literal_names(node: ast.AST) -> Optional[Set[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, str)):
                return None
            out.add(elt.value)
        return out
    return None


def _donation_kwargs(call: ast.Call):
    """(positions, names) from a call that mentions donate_argnums/
    donate_argnames literally; (None, None) when absent/dynamic."""
    pos = names = None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            pos = _literal_positions(kw.value)
        elif kw.arg == "donate_argnames":
            names = _literal_names(kw.value)
    return pos, names


def _is_jit_call(call: ast.Call) -> bool:
    name = _dotted(call.func) or ""
    if name.rsplit(".", 1)[-1] in ("jit", "pjit"):
        return True
    # functools.partial(jax.jit, ...) decorator form
    if name.rsplit(".", 1)[-1] == "partial" and call.args:
        first = _dotted(call.args[0]) or ""
        return first.rsplit(".", 1)[-1] in ("jit", "pjit")
    return False


class DonationRule(Rule):
    id = "APX401"
    name = "use-after-donation"
    repo_level = True
    description = ("an argument passed at a donate_argnums/"
                   "donate_argnames position is deleted by the call — "
                   "reading it afterwards is a runtime error on "
                   "hardware (and silently fine on CPU, where tests "
                   "run)")

    def check_repo(self, modules, root):
        # pass 1: donating callables — decorated defs (by function
        # name, repo-global: call sites import them) and local
        # `name = jax.jit(f, donate_argnums=...)` bindings (per module)
        global_defs: Dict[str, _DonatingDef] = {}
        local_defs: Dict[Tuple[str, str], _DonatingDef] = {}
        for mod in modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        if not (isinstance(dec, ast.Call)
                                and _is_jit_call(dec)):
                            continue
                        pos, names = _donation_kwargs(dec)
                        if pos is None and names is None:
                            continue
                        argnames = [a.arg for a in
                                    node.args.posonlyargs
                                    + node.args.args]
                        pos = set(pos or ())
                        for nm in names or ():
                            if nm in argnames:
                                pos.add(argnames.index(nm))
                        global_defs[node.name] = _DonatingDef(
                            positions=pos, kwnames=set(names or ()),
                            where=f"{mod.relpath}:{node.lineno}")
                elif isinstance(node, ast.Assign):
                    if not (isinstance(node.value, ast.Call)
                            and _is_jit_call(node.value)):
                        continue
                    pos, names = _donation_kwargs(node.value)
                    if pos is None and names is None:
                        continue
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            local_defs[(mod.relpath, tgt.id)] = \
                                _DonatingDef(
                                    positions=set(pos or ()),
                                    kwnames=set(names or ()),
                                    where=(f"{mod.relpath}:"
                                           f"{node.lineno}"))
        if not (global_defs or local_defs):
            return
        # pass 2: call sites + use-after scan
        for mod in modules:
            if not mod.in_pkg:
                continue
            yield from self._check_module(mod, global_defs, local_defs)

    def _check_module(self, mod, global_defs, local_defs):
        scopes = [mod.tree]
        scopes += [n for n in ast.walk(mod.tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
        for scope in scopes:
            body = getattr(scope, "body", [])
            for call in self._scope_calls(scope):
                callee = _dotted(call.func)
                if callee is None:
                    continue
                term = callee.rsplit(".", 1)[-1]
                dd = (local_defs.get((mod.relpath, term))
                      or global_defs.get(term))
                if dd is None:
                    continue
                for path in self._donated_paths(mod, call, dd):
                    use = self._first_use_after(mod, scope, call, path)
                    if use is not None:
                        yield self.finding(
                            mod, use,
                            f"{path!r} was donated to {term}(...) at "
                            f"line {call.lineno} (donating jit defined "
                            f"at {dd.where}) and read afterwards — "
                            "the buffer is deleted on hardware")

    @staticmethod
    def _scope_calls(scope):
        """Call nodes belonging to this scope (not nested functions)."""
        stack = list(getattr(scope, "body", []))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(node, ast.Call):
                yield node
            stack.extend(ast.iter_child_nodes(node))

    def _donated_paths(self, mod, call, dd):
        exprs = []
        for i, arg in enumerate(call.args):
            if i in dd.positions:
                exprs.append(arg)
        for kw in call.keywords:
            if kw.arg in dd.kwnames:
                exprs.append(kw.value)
        out = []
        for e in exprs:
            if isinstance(e, (ast.Name, ast.Attribute, ast.Subscript)):
                seg = mod.segment(e)
                if seg:
                    out.append(seg)
        return out

    @staticmethod
    def _rebinds(target_seg: str, path: str) -> bool:
        """Assigning to ``state`` also rebinds ``state.x`` /
        ``state["k"]`` — a prefix rebind kills the whole path."""
        return (target_seg == path
                or path.startswith(target_seg + "[")
                or path.startswith(target_seg + "."))

    def _first_use_after(self, mod, scope, call, path):
        """A Load of ``path`` after the call (its last line — donated
        args on continuation lines of a multi-line call are part of the
        call, not uses after it) with no intervening rebind (an
        assignment whose target is ``path`` or a prefix of it,
        including the statement wrapping the call itself)."""
        call_end = getattr(call, "end_lineno", None) or call.lineno
        rebind_lines = []
        uses = []
        stack = list(getattr(scope, "body", []))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    for el in ([t.elts] if isinstance(
                            t, (ast.Tuple, ast.List)) else [[t]]):
                        for sub in el:
                            if self._rebinds(mod.segment(sub), path):
                                rebind_lines.append(node.lineno)
            elif isinstance(node, ast.For):
                if self._rebinds(mod.segment(node.target), path):
                    rebind_lines.append(node.lineno)
            elif (isinstance(node, (ast.Name, ast.Attribute,
                                    ast.Subscript))
                  and isinstance(getattr(node, "ctx", None), ast.Load)
                  and mod.segment(node) == path
                  and node.lineno > call_end):
                uses.append(node)
            stack.extend(ast.iter_child_nodes(node))
        for use in sorted(uses, key=lambda n: n.lineno):
            if not any(call.lineno <= rl <= use.lineno
                       for rl in rebind_lines):
                return use
        return None


TIER_A_RULES: Tuple[Rule, ...] = (
    ChainedRegistryRule(),
    DirectRegistryRule(),
    PrivateGlobalRule(),
    ExporterImportRule(),
    MetricPrefixRule(),
    GatedMemorySampleRule(),
    UnregisteredEnvVarRule(),
    UndocumentedEnvVarRule(),
    EnvTableSyncRule(),
    HostSyncRule(),
    NondeterminismRule(),
    DonationRule(),
)


def all_rules() -> Tuple[Rule, ...]:
    """Every registered rule across tiers (A + C).  The Tier-C modules
    import :class:`Rule` from here, so their registration is resolved
    lazily — at call time both modules are fully initialized whichever
    one was imported first."""
    from apex_tpu.analysis.concurrency import CONCURRENCY_RULES
    from apex_tpu.analysis.lifecycle import LIFECYCLE_RULES

    return TIER_A_RULES + CONCURRENCY_RULES + LIFECYCLE_RULES


def __getattr__(name):
    # ALL_RULES predates the tiers and is part of the public surface;
    # keep it resolving to the full cross-tier set without a circular
    # import at module load.
    if name == "ALL_RULES":
        return all_rules()
    raise AttributeError(name)


def rules_by_id() -> Dict[str, Rule]:
    """id -> rule instance (the guard test and fixtures key on ids)."""
    return {r.id: r for r in all_rules()}
