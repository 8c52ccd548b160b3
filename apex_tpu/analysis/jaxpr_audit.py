"""Tier-B auditor: trace the canonical entry points and walk the jaxpr.

Tier A reads source; this module reads what jax actually emitted.  For
each entry in :data:`ENTRY_POINTS` it traces the function once
(``jax.make_jaxpr`` — tracing only, nothing compiles or runs), walks
the ClosedJaxpr recursively (pjit/scan/while/cond/custom_vjp/shard_map
sub-jaxprs included) and checks:

- **Collective census vs trace-time counters** (the accounting-drift
  detector).  ``utils/collectives`` wrappers count each collective as
  it is *emitted*; the census counts the equations that actually landed
  in the jaxpr.  ``census > counters`` means a collective was emitted
  around the counted wrappers — a hole in the accounting every
  downstream consumer (telemetry_report ring/MoE summaries, the moe_ep
  and tp_overlap dryrun assertions) silently inherits; always an
  error.  ``counters > census`` happens legitimately when autodiff
  re-traces a ``custom_vjp`` primal whose fwd jaxpr replaces it, so
  entries declare ``counter_policy="exact"`` only where equality is
  structural.
- **No monolithic collectives inside an overlap region.**  An entry
  marked ``overlap_region=True`` is traced entirely under
  ``overlap_scope`` semantics: its census must contain only
  ``ppermute`` rings — an ``all_gather``/``psum``/``all_to_all``
  equation means a code path fell back to the serialized collective
  while claiming overlap.
- **No unexplained bf16→f32 upcasts** in bf16 compute regions:
  ``convert_element_type``→float32 equations whose user-frame
  attribution matches none of :data:`UPCAST_ALLOWLIST` (softmax, norms,
  accumulators, scales, losses — the places fp32 is the design).
- **Donation landed**: entries carrying a jitted step with
  ``donate_argnums`` lower it and require the aliasing annotation in
  the StableHLO — a refactor that breaks donation (e.g. an operand
  captured as a constant) silently doubles peak HBM.
- **No dead equations**: a jaxpr equation whose outputs reach neither
  the outvars nor an effect is compute the author thinks is happening
  but XLA will DCE — usually a dropped return value.

jax is imported lazily inside functions (Tier-A tooling must load this
package without an accelerator stack); entry builders construct tiny
models on whatever backend is active (the 8-virtual-device CPU mesh in
tests and the dryrun gate).

Telemetry: when a registry is configured, each audited entry emits
``audit.census.<kind>{entry=...}`` and ``audit.counted.<kind>{entry=...}``
counters — ``tools/telemetry_report.py``'s ``audit_summary`` renders
the per-entry deltas, so accounting drift is visible in reports, not
just in CI.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "AuditReport",
    "ENTRY_POINTS",
    "COLLECTIVE_KINDS",
    "MONOLITHIC_PRIMS",
    "UPCAST_ALLOWLIST",
    "collective_census",
    "kind_tallies",
    "audit_overlap_trace",
    "audit_entry",
    "run_audit",
]

# jaxpr primitive name -> collectives.* counter kind (the counted
# wrapper families in utils/collectives + the psum/pmean/pmin/pmax
# helpers).  pmean lowers to psum + div, so it lands in the psum row of
# the census; the wrapper counts it as pmean — compare_kinds merges.
COLLECTIVE_KINDS: Dict[str, str] = {
    "psum": "psum",
    "pmin": "pmin",
    "pmax": "pmax",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
    "reduce_scatter": "psum_scatter",
}

# jax 0.9 emits the collectives whose result is typed axis-invariant
# under their own primitive names; the census files them with their
# family
_PRIM_ALIASES = {"psum_invariant": "psum",
                 "all_gather_invariant": "all_gather"}

# anything serialized: inside an overlap region only ppermute rings may
# appear (the whole point of the ring decomposition)
MONOLITHIC_PRIMS = ("psum", "all_gather", "all_to_all", "reduce_scatter",
                    "pmin", "pmax")

# user-frame substrings that explain a bf16→f32 convert: fp32 softmax
# statistics, norm moments, loss reductions, fp32 accumulators, scale
# arithmetic, rotary tables, router/aux math
UPCAST_ALLOWLIST = (
    "softmax", "norm", "loss", "xent", "scale", "rope", "accum",
    "_aux", "router", "logits", "moment", "adam", "lamb", "sketch",
    "probs", "mean",
    # fp32 attention statistics (the online-softmax accumulator class)
    "attention",
    # _mlp's fp32 GELU: bit-comparable HF checkpoint imports need the
    # reference ecosystem's fp32 tanh approximation (transformer_lm.py)
    "_mlp",
)


@dataclasses.dataclass
class AuditReport:
    name: str
    census: Dict[str, int]
    counted: Dict[str, float]
    findings: List[str]
    notes: List[str]

    @property
    def ok(self) -> bool:
        return not self.findings


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(value):
    """Yield every Jaxpr reachable from one eqn param value."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    for v in vals:
        # ClosedJaxpr first: it also duck-types .eqns, but dead-eqn
        # liveness needs the raw Jaxpr's outvars
        if hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr", None),
                                           "eqns"):
            yield v.jaxpr                       # ClosedJaxpr
        elif hasattr(v, "eqns"):                # Jaxpr
            yield v


def iter_eqns(jaxpr):
    """Depth-first over every equation, descending into sub-jaxprs
    (pjit bodies, scan/while/cond branches, shard_map, custom_vjp)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)      # accept ClosedJaxpr
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            yield eqn
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs(v))


def collective_census(jaxpr) -> Dict[str, int]:
    """Count of every collective primitive equation in the trace."""
    out: Dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = _PRIM_ALIASES.get(eqn.primitive.name, eqn.primitive.name)
        if name in COLLECTIVE_KINDS:
            out[name] = out.get(name, 0) + 1
    return out


# ---------------------------------------------------------------------------
# counter plumbing
# ---------------------------------------------------------------------------


def _registry():
    from apex_tpu.observability import metrics as _telemetry

    return _telemetry.registry()


def _ensure_registry():
    """(registry, owned): configure a sink-less registry when telemetry
    is off so the trace-time counters have somewhere to land."""
    reg = _registry()
    if reg is not None:
        return reg, False
    from apex_tpu.observability import configure

    configure(stderr_summary=False)
    return _registry(), True


def _counter_values(reg, prefix: str = "collectives.") -> Dict[str, float]:
    return {k: v for k, v in reg.summary()["counters"].items()
            if k.startswith(prefix)}


def _deltas(before: Dict[str, float],
            after: Dict[str, float]) -> Dict[str, float]:
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0.0)
        if d:
            out[k] = d
    return out


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def kind_tallies(census: Dict[str, int], counted: Dict[str, float],
                 kinds: Tuple[str, ...]) -> Dict[str, Tuple[int, float]]:
    """kind -> (equations in the jaxpr, wrapper-counted calls) — THE
    one fold from primitive census + counter deltas to comparable
    rows, shared by the gate check and the telemetry emission so the
    two can never diverge.  The pmean wrapper emits a psum equation,
    so its count folds into the psum row."""
    out = {}
    for kind in kinds:
        prims = [p for p, k in COLLECTIVE_KINDS.items() if k == kind]
        n_census = sum(census.get(p, 0) for p in prims)
        n_counted = counted.get(f"collectives.{kind}.calls", 0.0)
        if kind == "psum":
            n_counted += counted.get("collectives.pmean.calls", 0.0)
        out[kind] = (n_census, n_counted)
    return out


def check_census_vs_counters(census: Dict[str, int],
                             counted: Dict[str, float],
                             kinds: Tuple[str, ...],
                             policy: str = "at_most") -> List[str]:
    """Accounting drift per collective kind.

    ``census > counters`` (an uncounted collective on a counted kind)
    is always a finding.  ``counters > census`` is a finding only under
    ``policy="exact"`` — autodiff legitimately re-traces custom_vjp
    primals, over-counting relative to the final jaxpr.
    """
    findings = []
    for kind, (n_census, n_counted) in kind_tallies(
            census, counted, kinds).items():
        if n_census > n_counted:
            findings.append(
                f"accounting drift ({kind}): {n_census} equation(s) in "
                f"the jaxpr but only {n_counted:g} counted — a "
                "collective was emitted around the counted wrappers")
        elif policy == "exact" and n_counted > n_census:
            findings.append(
                f"accounting drift ({kind}): counted {n_counted:g} but "
                f"only {n_census} equation(s) landed in the jaxpr")
    return findings


def check_overlap_region(census: Dict[str, int]) -> List[str]:
    """Inside an overlap region only ppermute rings may appear."""
    findings = []
    for prim in MONOLITHIC_PRIMS:
        if census.get(prim, 0):
            findings.append(
                f"monolithic {prim} ({census[prim]} equation(s)) "
                "inside an active overlap_scope region — only "
                "ppermute rings belong here")
    return findings


# how many of an equation's innermost non-jax frames attribute it: the
# function that emitted it and its nearest callers.  The whole stack
# would explain anything (every trace sits under some "loss", and a test
# runner's "<lambda>" frames read as "lamb").
_ATTRIBUTION_DEPTH = 4


def _user_frames(eqn) -> List[str]:
    import itertools

    import jax._src.source_info_util as siu

    frames = siu.user_frames(eqn.source_info.traceback)
    return [f"{fr.file_name}:{fr.function_name}"
            for fr in itertools.islice(frames, _ATTRIBUTION_DEPTH)]


def check_upcasts(jaxpr,
                  allowlist: Tuple[str, ...] = UPCAST_ALLOWLIST,
                  ) -> Tuple[List[str], List[str]]:
    """(findings, notes): bf16→f32 ``convert_element_type`` equations
    whose user-frame attribution matches nothing in the allowlist.
    Converts with *no* user frames (jax-internal synthesis, e.g. the
    transpose machinery) are notes, not findings — they cannot be
    attributed to repo code."""
    import numpy as np

    findings, notes = [], []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = eqn.params.get("new_dtype")
        if new is None or np.dtype(new) != np.dtype("float32"):
            continue
        src = getattr(eqn.invars[0], "aval", None)
        if src is None or np.dtype(src.dtype) != np.dtype("bfloat16"):
            continue
        frames = _user_frames(eqn)
        blob = " ".join(frames).lower()
        if any(tok in blob for tok in allowlist):
            continue
        where = frames[0] if frames else None
        if where is None:
            notes.append("unattributed bf16->f32 convert "
                         "(no user frames; jax-internal)")
        else:
            findings.append(
                f"unexplained bf16->f32 upcast at {where} — allowlist "
                "it in UPCAST_ALLOWLIST if fp32 is the design, else "
                "keep the compute in bf16")
    return findings, notes


# dead compute worth failing CI over: a dropped matmul/scan/collective
# is real work the author believes is happening.  Dead *cheap*
# equations (a mul whose product only fed the unused half of a
# multi-output helper) are normal trace noise jax leaves for XLA's DCE
# — reported as one aggregate note, not findings.
_EXPENSIVE_PRIMS = frozenset(
    ("dot_general", "conv_general_dilated", "scan", "while",
     "pallas_call") + tuple(COLLECTIVE_KINDS))


def _eqn_is_expensive(eqn) -> bool:
    if eqn.primitive.name in _EXPENSIVE_PRIMS:
        return True
    # call-like wrappers (pjit/custom_vjp/remat) are expensive iff
    # their body is
    for v in eqn.params.values():
        for sub in _sub_jaxprs(v):
            for inner in sub.eqns:
                if _eqn_is_expensive(inner):
                    return True
    return False


def check_dead_eqns(jaxpr) -> Tuple[List[str], List[str]]:
    """(findings, notes): equations none of whose outputs reach their
    jaxpr's outvars (or an effect).  Expensive dead compute is a
    finding; cheap dead equations aggregate into one note.  Pallas
    kernel bodies are skipped — they compute through Ref mutation,
    which this liveness does not model."""
    findings: List[str] = []
    dead_cheap = 0
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        live = {id(v) for v in jx.outvars}
        for eqn in reversed(jx.eqns):
            outs_live = any(id(v) in live for v in eqn.outvars)
            has_effect = bool(getattr(eqn, "effects", None))
            if outs_live or has_effect:
                for v in eqn.invars:
                    live.add(id(v))
            elif _eqn_is_expensive(eqn):
                findings.append(
                    f"dead equation: {eqn.primitive.name} at "
                    f"{(_user_frames(eqn) or ['?'])[0]} — its outputs "
                    "reach no jaxpr output (dropped return value?)")
            else:
                dead_cheap += 1
            if eqn.primitive.name != "pallas_call":
                for v in eqn.params.values():
                    stack.extend(_sub_jaxprs(v))
    notes = []
    if dead_cheap:
        notes.append(f"{dead_cheap} cheap dead equation(s) — "
                     "partially-used multi-output helpers; XLA DCEs "
                     "them")
    return findings, notes


def check_donation(jitted, args, kwargs=None) -> List[str]:
    """Lower a jit carrying donate_argnums/argnames and require the
    input/output aliasing annotation in the StableHLO text."""
    kwargs = kwargs or {}
    try:
        text = jitted.lower(*args, **kwargs).as_text()
    except Exception as e:   # lowering needs a live backend
        return [f"donation check could not lower: {e!r}"]
    if ("tf.aliasing_output" not in text
            and "jax.buffer_donor" not in text):
        return ["donated arguments did not lower to aliased buffers "
                "(no tf.aliasing_output/jax.buffer_donor in the "
                "StableHLO) — donation was dropped"]
    return []


def audit_overlap_trace(fn: Callable, *args) -> AuditReport:
    """Trace ``fn`` — assumed to run entirely inside an overlap region
    — and apply the monolithic-collective census check.  The unit test
    plants a ``lax.psum`` here and asserts the finding."""
    import jax

    from apex_tpu.ops.collective_matmul import overlap_scope

    reg, owned = _ensure_registry()
    try:
        before = _counter_values(reg)
        with overlap_scope(True):
            jaxpr = jax.make_jaxpr(fn)(*args)
        counted = _deltas(before, _counter_values(reg))
    finally:
        if owned:
            from apex_tpu.observability import shutdown

            shutdown()
    census = collective_census(jaxpr)
    return AuditReport(name="overlap_trace", census=census,
                       counted=counted,
                       findings=check_overlap_region(census), notes=[])


# ---------------------------------------------------------------------------
# the entry-point matrix
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EntrySpec:
    fn: Callable                      # traced via make_jaxpr
    args: tuple
    compare_kinds: Tuple[str, ...] = ()
    counter_policy: str = "at_most"   # "exact" where structural
    overlap_region: bool = False
    bf16_region: bool = False
    donate: Optional[Tuple] = None    # (jitted, args) for check_donation
    expect_collectives: bool = False  # census must be non-empty
    notes: Tuple[str, ...] = ()


def _tiny_cfg(**kw):
    import jax.numpy as jnp

    from apex_tpu.models.config import TransformerConfig

    kw.setdefault("num_layers", 2)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("max_position_embeddings", 16)
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


def _build_train_amp() -> EntrySpec:
    """The AMP train step on the tiny GPT (O2: bf16 compute, fp32
    masters) — single-device, so the census must be collective-free;
    the jitted step donates its state, so donation must lower."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.gpt import make_gpt_train_step
    from apex_tpu.optimizers import fused_adam

    cfg = _tiny_cfg(compute_dtype=jnp.bfloat16)
    init, step = make_gpt_train_step(cfg, fused_adam(lr=1e-3), "O2")
    state = init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 8)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 8)),
                         jnp.int32)
    return EntrySpec(
        fn=step, args=(state, tokens, labels),
        compare_kinds=("psum", "all_gather", "all_to_all",
                       "ppermute", "psum_scatter"),
        counter_policy="exact",   # zero == zero on one device
        bf16_region=True,
        donate=(step, (state, tokens, labels)),
        notes=("single-device AMP: census and counters must both be "
               "empty",))


def _build_train_ddp_int8() -> EntrySpec:
    """The DDP train step with int8 compressed grad comm on the dp
    mesh — the counted all_to_all/all_gather wire and the found-inf
    psum/pmin/pmax family all land here."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.parallel.distributed import make_ddp_train_step
    from apex_tpu.parallel.mesh import create_mesh

    n = min(8, len(jax.devices()))
    mesh = create_mesh(dp=n)

    def loss_fn(params, x, y):
        h = jnp.tanh(x @ params["w1"])
        p = h @ params["w2"]
        return jnp.mean((p - y) ** 2)

    from apex_tpu.optimizers import fused_adam

    init, step = make_ddp_train_step(loss_fn, fused_adam(lr=1e-3),
                                     "O0", mesh, grad_comm="int8",
                                     batch_axes=2)
    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(16, 32) * 0.1, jnp.float32),
              "w2": jnp.asarray(rng.randn(32, 4) * 0.1, jnp.float32)}
    state = init(params)
    x = jnp.asarray(rng.randn(n * 2, 16), jnp.float32)
    y = jnp.asarray(rng.randn(n * 2, 4), jnp.float32)
    return EntrySpec(
        fn=step, args=(state, x, y),
        compare_kinds=("all_to_all", "all_gather", "psum_scatter",
                       "ppermute"),
        expect_collectives=True,
        notes=("grad wire: quantize -> all_to_all -> dequant-sum -> "
               "requant -> all_gather (comm/reduce.py)",))


def _build_decode(layout: str) -> EntrySpec:
    """decode_step through one cache layout — the serving hot path.
    Single device: collective-free census, and (layout='paged') the
    paged insert path's donation partner is audited separately by the
    serving tests; here the census + dead-eqn checks pin the step."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.generate import decode_step, init_kv_cache

    cfg = _tiny_cfg(position_embedding_type="rope",
                    compute_dtype=jnp.bfloat16)
    from apex_tpu.models.transformer_lm import init_gpt_params

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    cache = init_kv_cache(cfg, 2, 16, cache_layout=layout,
                          block_size=8)
    token = jnp.ones((2,), jnp.int32)

    def fn(p, t, c):
        return decode_step(p, t, c, cfg)

    return EntrySpec(
        fn=fn, args=(params, token, cache),
        compare_kinds=("psum", "all_gather", "all_to_all",
                       "ppermute", "psum_scatter"),
        counter_policy="exact",
        bf16_region=True)


def _build_spec_verify() -> EntrySpec:
    """decode_verify — the speculative-decoding batched verification
    forward (contiguous layout; the paged twin shares every layer
    body already audited by _build_decode('paged'))."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.generate import decode_verify, init_kv_cache
    from apex_tpu.models.transformer_lm import init_gpt_params

    cfg = _tiny_cfg(position_embedding_type="rope",
                    compute_dtype=jnp.bfloat16)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    cache = init_kv_cache(cfg, 2, 16)
    tokens = jnp.ones((2, 4), jnp.int32)

    def fn(p, t, c):
        return decode_verify(p, t, c, cfg)

    return EntrySpec(
        fn=fn, args=(params, tokens, cache),
        compare_kinds=("psum", "all_gather", "all_to_all",
                       "ppermute", "psum_scatter"),
        counter_policy="exact",
        bf16_region=True)


def _build_moe_ragged() -> EntrySpec:
    """The capacity-free ragged MoE through the explicit EP island on
    the ep mesh: the counted all_to_all dispatch/combine is exactly
    what moe.*/collectives.* accounting and the moe_ep dryrun gate
    read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.parallel.mesh import create_mesh
    from apex_tpu.transformer.moe import init_moe_params, switch_moe_mlp

    n = min(8, len(jax.devices()))
    mesh = create_mesh(ep=n)
    h, f, E = 16, 32, 2 * n
    params = init_moe_params(jax.random.PRNGKey(2), h, f, E)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, n, h) * 0.5, jnp.float32)

    def fn(p, xx):
        return switch_moe_mlp(p, xx, top_k=2, routing="ragged",
                              ep_mesh=mesh).out

    return EntrySpec(
        fn=fn, args=(params, x),
        compare_kinds=("all_to_all", "all_gather", "ppermute",
                       "psum_scatter"),
        expect_collectives=True,
        notes=("forward-only trace: the fwd-side counted all_to_all "
               "family must match the census exactly; psum is the "
               "island's load/aux reduction (helpers count it as "
               "grad_sum only under grad, so it is not compared)",))


def _build_tp_ring_overlap() -> EntrySpec:
    """The ring collective-matmul under an active overlap_scope: the
    census may contain ONLY ppermute equations, and the ring-hop
    counters must agree with them — the zero-monolithic-collectives
    acceptance gate."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.ops.collective_matmul import (
        all_gather_matmul,
        matmul_reduce_scatter,
        overlap_scope,
    )

    n = min(8, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n]), ("tp",))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n * 2, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 8) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.randn(8, n * 4) * 0.1, jnp.float32)

    def island(xs, ww, ww2):
        y = all_gather_matmul(xs, ww, axis_name="tp")
        return matmul_reduce_scatter(y, ww2, axis_name="tp")

    sm = jax.shard_map(island, mesh=mesh, in_specs=(P("tp"), P(), P()),
                       out_specs=P("tp"))

    def fn(xs, ww, ww2):
        with overlap_scope(True):
            return sm(xs, ww, ww2)

    return EntrySpec(
        fn=fn, args=(x, w, w2),
        compare_kinds=("ppermute",),
        counter_policy="exact",
        overlap_region=True,
        expect_collectives=True,
        notes=("hops == (tp-1) x calls is asserted via the ppermute "
               "census matching collectives.ppermute.calls",))


ENTRY_POINTS: Dict[str, Callable[[], EntrySpec]] = {
    "train_amp": _build_train_amp,
    "train_ddp_int8": _build_train_ddp_int8,
    "decode_contiguous": lambda: _build_decode("contiguous"),
    "decode_paged": lambda: _build_decode("paged"),
    "spec_verify": _build_spec_verify,
    "moe_ragged": _build_moe_ragged,
    "tp_ring_overlap": _build_tp_ring_overlap,
}


def _emit_audit_counters(reg, name: str, census: Dict[str, int],
                         counted: Dict[str, float],
                         kinds: Tuple[str, ...]) -> None:
    """Mirror exactly what the gate compared: only the entry's
    ``compare_kinds`` land in the report stream, so telemetry_report's
    audit_summary can never show 'drift' on a kind the entry's policy
    deliberately leaves uncompared (e.g. the MoE island's load/aux
    psum, counted only under grad)."""
    if reg is None:
        return
    for kind, (n_census, n_counted) in kind_tallies(
            census, counted, kinds).items():
        if not (n_census or n_counted):
            continue
        reg.counter(f"audit.census.{kind}",
                    tags={"entry": name}).inc(int(n_census))
        reg.counter(f"audit.counted.{kind}",
                    tags={"entry": name}).inc(int(n_counted))


def audit_entry(name: str) -> AuditReport:
    """Build, trace and check one entry point."""
    import jax

    spec = ENTRY_POINTS[name]()
    reg, owned = _ensure_registry()
    try:
        before = _counter_values(reg)
        jaxpr = jax.make_jaxpr(spec.fn)(*spec.args)
        counted = _deltas(before, _counter_values(reg))
        census = collective_census(jaxpr)
        findings: List[str] = []
        notes = list(spec.notes)
        findings += check_census_vs_counters(
            census, counted, spec.compare_kinds, spec.counter_policy)
        if spec.overlap_region:
            findings += check_overlap_region(census)
        if spec.expect_collectives and not census:
            findings.append(
                "expected collectives in the census but the trace "
                "emitted none — the entry no longer exercises its "
                "comm path")
        if spec.bf16_region:
            up, up_notes = check_upcasts(jaxpr)
            findings += up
            notes += up_notes
        dead, dead_notes = check_dead_eqns(jaxpr)
        findings += dead
        notes += dead_notes
        if spec.donate is not None:
            jitted, dargs = spec.donate
            findings += check_donation(jitted, dargs)
        _emit_audit_counters(None if owned else reg, name, census,
                             counted, spec.compare_kinds)
    finally:
        if owned:
            from apex_tpu.observability import shutdown

            shutdown()
    return AuditReport(name=name, census=census, counted=counted,
                       findings=findings, notes=notes)


def run_audit(names: Optional[Tuple[str, ...]] = None,
              ) -> List[AuditReport]:
    """Audit the requested entries (default: all).  Builder or trace
    failures become findings, not crashes — the CI wrapper needs the
    full matrix even when one entry regresses."""
    out = []
    for name in names or tuple(ENTRY_POINTS):
        try:
            out.append(audit_entry(name))
        except Exception as e:
            out.append(AuditReport(
                name=name, census={}, counted={},
                findings=[f"entry failed to build/trace: {e!r}"],
                notes=[]))
    return out
