"""The continuous-batching serving engine (slot or paged KV layout).

Lifecycle (docs/inference.md has the full walkthrough)::

    engine = ServingEngine(params, cfg, max_slots=8, max_len=1024,
                           cache_layout="paged")
    rid = engine.submit([1, 2, 3], max_new_tokens=32, eos_token_id=50256)
    while True:
        for resp in engine.step():       # 0+ completed Responses
            ...
        if engine.idle:
            break
    # or simply: responses = engine.run(requests)

Each :meth:`ServingEngine.step`:

1. **admit** — while a decode lane is free, the queue is non-empty and
   the KV budget covers the next request, pop it, pad its prompt to the
   smallest compile bucket, run ONE batched flash
   :func:`~apex_tpu.models.generate.prefill` into a bucket-sized cache,
   scatter that into the request's KV storage, and sample the first
   token from the prefill logits.  A request can therefore enter the
   batch *mid-flight*, the moment an earlier one frees its lane — the
   continuous-batching property that keeps decode utilization flat
   under mixed-length traffic.
2. **decode** — one batched :func:`~apex_tpu.models.generate.decode_step`
   over ALL lanes (the batch stays rectangular; inactive lanes ride
   along masked, their cache positions frozen), then a vectorized
   sample with per-slot temperatures.  One host sync per step reads the
   new tokens for EOS / length bookkeeping.  With ``spec=`` (ISSUE 8)
   the step is instead one speculative draft→verify→accept round and
   each live lane emits 1..k+1 tokens per poll — same single host
   sync, several tokens of progress.
3. **complete** — lanes whose token hit ``eos_token_id`` or whose
   budget ran out are converted to :class:`Response` and released.

Two KV layouts (``cache_layout=``, ISSUE 6):

- ``"contiguous"`` (PR 3) — one ``max_len`` cache stripe per slot.
  Admission is slot-count-based; every admitted request reserves
  worst-case HBM for its whole lifetime.
- ``"paged"`` — a global block pool (``serving/paged_cache.py``) with
  per-request block tables and the fused ragged-paged-attention decode
  kernel (``ops/paged_attention.py``).  Admission is **block-budget**
  based: a request enters while the free blocks cover its prompt plus
  ``reserve_blocks``, so HBM commits per allocated block, not per
  ``max_slots × max_len``.  Identical full prompt blocks are
  **prefix-shared** (refcounted, copy-on-write discipline — the shared
  blocks are immutable by construction).  When decode needs a tail
  block and the pool is dry, the **youngest** live request is
  preempted — its blocks free instantly (fixed-size blocks, nothing to
  defragment), the request requeues with its progress, and resume
  replays prompt+generated through the batched flash prefill path.
  Greedy outputs are token-identical across a preempt→resume cycle
  (tests/test_serving_paged.py pins it).

Static-shape discipline: exactly one decode compile for the engine's
lifetime (shape ``[max_slots]``), one prefill compile per prompt
bucket, one KV-insert compile per bucket — the bucketed compile cache
that bounds recompiles under production traffic, same budget in both
layouts.

Telemetry (no-op unless ``observability.configure`` ran):
``serving.prefill_ms`` (histogram, per admission),
``serving.decode_tokens_per_sec`` (gauge, per step),
``serving.slot_occupancy`` / ``serving.queue_depth`` (gauges), the
``serving.{requests,prefill_calls,decode_steps,tokens_generated}``
counters the trace-count tests pin against, and — paged layout —
``serving.blocks_in_use`` / ``serving.blocks_free`` /
``serving.prefix_shared_blocks`` (gauges) + ``serving.preemptions``
(counter), the signals the PR 4 HBM accounting and admission-stall
detector read.

SLO accounting (ISSUE 7, same no-op contract): every request carries
lifecycle stamps (submit → first admission → first token → finish,
with preemption cycles clocked separately) that land at completion in
per-class mergeable sketches
``serving.{queue_wait_ms,ttft_ms,tpot_ms,e2e_ms,preempt_overhead_ms}``
(tagged ``slo_class=``), the ``serving.goodput.{met,missed}`` counters
(judged against the per-class TTFT/TPOT deadlines of
``serving/slo.py``), and the SLO-violation detector.  The same numbers
ride on each :class:`Response`, and the
``serving.request.{begin,first_token,end}`` events let a trace/JSONL
consumer reconstruct TTFT/TPOT independently of the engine's
arithmetic (the soak test pins the two derivations against each
other).

Diagnostics (ISSUE 4, same no-op contract): each request emits paired
``serving.request.begin`` / ``serving.request.end`` events (submit →
completion, queue time included) that the Perfetto trace sink renders
as per-request async rows — a preemption adds a ``serving.request.
preempt`` event in between — plus a ``serving.request_ms`` latency
histogram tagged with the finish reason; the queue/occupancy gauges
feed the admission-stall/backlog anomaly detector; prefill and decode
compiles are labeled for the recompile tracker
(``compile.serving.{prefill,decode}.*`` — a bucketed engine should
stop compiling once traffic has touched every bucket); HBM gauges are
sampled at admission and every 64 decode steps.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
import warnings
from collections import deque
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.config import TransformerConfig
from apex_tpu.models.generate import (
    _check_decode_cfg, decode_step, decode_verify, extract_kv,
    init_kv_cache, prefill, sample_logits)
from apex_tpu.ops.fused_sampling import apply_token_mask
from apex_tpu.models.speculative import resolve_spec, spec_round
from apex_tpu.observability import metrics as _telemetry
from apex_tpu.observability import span
from apex_tpu.observability.device import (
    compile_label, sample_device_memory)
from apex_tpu.ops.decode_step import route_decode_fused
from apex_tpu.serving.batching import (
    SlotPool, default_buckets, pad_prompt, pick_bucket)
from apex_tpu.serving.compile_cache import CompileCache
from apex_tpu.serving.host_tier import (
    DIGEST_INVENTORY_N, HostTier, resolve_host_tier_bytes,
    resolve_host_tier_wire)
from apex_tpu.serving.paged_cache import (
    BlockManager, blocks_for, chunk_salt, dequantize_kv,
    gather_block_kv, gather_block_scales, init_paged_pool,
    paged_insert_prefill, paged_insert_prefill_q, prefix_block_hashes,
    resolve_cache_wire)
from apex_tpu.serving.slo import judge as _judge_slo
from apex_tpu.serving.slo import resolve_slo_targets
from apex_tpu.serving.slo import tpot_ms as _tpot_ms

__all__ = ["Request", "Response", "ServingEngine"]


@dataclasses.dataclass
class Request:
    """One generation request. ``prompt`` is a 1-D int token array."""

    prompt: np.ndarray
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    request_id: Optional[int] = None
    # SLO class (ISSUE 7): keys the engine's per-class deadline table
    # (``slo_targets=``) and labels the request's latency sketches and
    # goodput verdict.  Any string is a valid class; classes without a
    # configured target carry no deadline.
    slo_class: str = "default"
    # stamped by ServingEngine.submit; end-to-end latency (queue time
    # included) is measured from here
    submitted_t: float = 0.0
    # SLO lifecycle stamps (perf_counter seconds; 0.0 = not yet):
    # queue_wait ends at the first admission's start, TTFT at the first
    # prefill-sampled token.  preempted_t is live only between a
    # preemption and its resume; the requeue-wait + replay-prefill cost
    # of every such cycle accumulates into preempt_overhead_s.
    admitted_t: float = 0.0
    first_token_t: float = 0.0
    queue_wait_s: float = 0.0
    preempted_t: float = 0.0
    preempt_overhead_s: float = 0.0
    # tokens generated before a preemption (paged layout): resume
    # replays prompt+resume_tokens through prefill and keeps counting
    # its budget from where it left off
    resume_tokens: List[int] = dataclasses.field(
        default_factory=list, repr=False)
    # times this request was preempted (paged layout).  Each admission
    # (initial or resume) samples one token from prefill logits, not a
    # decode poll
    preemptions: int = 0
    # decode polls accumulated BEFORE the latest preemption, so the
    # poll count survives preempt→resume (the resumed slot continues
    # counting from here); Response.decode_steps reports the total
    resume_polls: int = 0
    # memoized (token_count, salt, full_tokens, prefix_block_hashes)
    # for the paged admission path: populated ONCE at submit (ISSUE 18
    # — a fresh submit used to recompute the digests on every
    # admission retry) and invalidated only by resume growth or a
    # namespace flip (a resume can cross the chunked threshold).
    # _blocks_needed polls this every step() while the head request
    # waits on the block budget, _claim_blocks reuses it at admission,
    # and the host tier keys its digest entries off the same chain.
    _hash_cache: Optional[tuple] = dataclasses.field(
        default=None, repr=False)
    # cluster KV handoff (ISSUE 9): ``(k, v, first_token, prefill_ms)``
    # from a remote prefill worker — admission INJECTS this K/V instead
    # of running prefill.  Dropped on preemption (the blocks are gone;
    # resume replays prompt+generated through the local prefill path,
    # which reproduces the same K/V bit-for-bit for a raw-wire handoff).
    handoff: Optional[tuple] = dataclasses.field(
        default=None, repr=False)
    # ISSUE 18: a raw-wire handoff of FRESH prefill pages is bitwise
    # identical to local flash prefill, so its blocks may map and
    # publish flash-namespace digests; every other handoff (compressed
    # wire, drain-migration records carrying decode-written tokens)
    # keeps the no-alias rule and claims fresh unpublished blocks.
    handoff_shareable: bool = False
    # multi-tenant LoRA (ISSUE 20): id of the adapter this request
    # decodes through, 0 = base model.  The id indexes the engine's
    # AdapterPool; admission pins a slab lane for the request's whole
    # residency and the decode step folds the lane's low-rank delta in
    # via ragged grouped matmuls — the base weights never change.
    adapter_id: int = 0
    # constrained decoding (ISSUE 20 satellite): boolean [vocab] mask,
    # True = token allowed.  Applied to the logits BEFORE temperature /
    # top-k / top-p in every sampling site (prefill sample, decode
    # step, spec draft+verify), so greedy and sampled paths agree.
    token_mask: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    # the 1-based AdapterPool lane acquire() pinned for this request
    # (0 = no ref held) — release paths key off it, never off
    # adapter_id alone, so double-release is structurally impossible
    _lane: int = dataclasses.field(default=0, repr=False)

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={self.max_new_tokens} must be >= 1")
        if self.temperature < 0:
            raise ValueError(
                f"temperature={self.temperature}: negative temperatures "
                "would silently invert the distribution; pass 0 for "
                "greedy or a positive value")
        if self.adapter_id < 0:
            raise ValueError(
                f"adapter_id={self.adapter_id} must be >= 0 (0 = base)")
        if self.token_mask is not None:
            self.token_mask = np.asarray(self.token_mask,
                                         bool).reshape(-1)
            if not self.token_mask.any():
                raise ValueError(
                    "token_mask allows no tokens — sampling would "
                    "degenerate to argmax over -inf")


@dataclasses.dataclass
class Response:
    """A completed request: generated tokens (prompt excluded) plus
    its SLO accounting (ISSUE 7) — the same numbers the engine's
    per-class sketches aggregate, carried per request so callers
    (``bench_serving``, a router) can bucket them their own way."""

    request_id: int
    prompt: np.ndarray
    tokens: np.ndarray
    finish_reason: str            # 'eos' | 'length'
    prefill_ms: float
    decode_steps: int
    slo_class: str = "default"
    queue_wait_ms: float = 0.0    # submit -> first admission start
    ttft_ms: float = 0.0          # submit -> first sampled token
    # mean inter-token interval after the first token (0.0 for a
    # one-token response — no interval exists)
    tpot_ms: float = 0.0
    e2e_ms: float = 0.0           # submit -> completion
    preemptions: int = 0
    preempt_overhead_ms: float = 0.0
    slo_met: bool = True          # against the class's deadlines


@dataclasses.dataclass
class _Slot:
    """Host bookkeeping for one live decode lane."""

    request: Request
    tokens: List[int]
    prefill_ms: float
    # paged layout only:
    blocks: List[int] = dataclasses.field(default_factory=list)
    cache_len: int = 0            # tokens materialized in the KV cache
    shared_blocks: int = 0        # prefix blocks mapped, not allocated
    # engine polls this lane was live for — under speculative decoding
    # (ISSUE 8) one poll emits several tokens, so polls and tokens are
    # DIFFERENT numbers and Response.decode_steps reports this one
    decode_polls: int = 0
    # chunked prefill (ISSUE 15): a lane admitted for a long prompt
    # streams its prefill across polls — one chunk_tokens forward per
    # step(), interleaved with everyone else's decode — and only joins
    # the decode batch when the last chunk lands.  While prefilling,
    # cache_len is the prefill progress (tokens written so far).
    prefilling: bool = False
    chunks_done: int = 0
    chunks_total: int = 0
    prefill_tokens: Optional[np.ndarray] = None
    # chunk-aligned digest publication (ISSUE 18): the full prompt's
    # chunk-namespace chain digests, and how many leading blocks have
    # been published so far (shared/page-in blocks count as published
    # at admission; computed blocks publish as their chunk lands)
    digests: Optional[List[bytes]] = None
    published_upto: int = 0


def _resolve_chunk_tokens(value: Optional[int]) -> Optional[int]:
    """The chunked-prefill knob: ``APEX_TPU_CHUNK_TOKENS`` beats the
    caller's ``chunk_tokens=`` (positive int = chunk size, ``off``/``0``
    = force monolithic); malformed values warn BY NAME and fall back to
    the caller's value."""
    raw = os.environ.get("APEX_TPU_CHUNK_TOKENS")
    if raw is not None:
        if raw.strip().lower() in ("off", "0"):
            return None
        try:
            n = int(raw)
            if n < 1:
                raise ValueError(raw)
            return n
        except ValueError:
            warnings.warn(
                f"APEX_TPU_CHUNK_TOKENS={raw!r} is malformed (expected "
                "a positive int, or off/0 to disable); using the "
                "caller's chunk_tokens", stacklevel=3)
    if value is not None and int(value) < 1:
        raise ValueError(
            f"chunk_tokens={value} must be >= 1 (or None for "
            "monolithic prefill)")
    return None if value is None else int(value)


class ServingEngine:
    """Continuous-batching engine over a fixed pool of decode lanes.

    ``max_len`` bounds prompt + generation per request.
    ``cache_layout`` picks the KV storage: ``"contiguous"`` reserves a
    ``max_len`` stripe per slot; ``"paged"`` commits HBM per allocated
    ``block_size``-token block from a ``num_blocks`` pool (default
    ``max_slots × ceil(max_len/block_size)`` — byte-parity with the
    slot layout; size it smaller to overcommit, the engine preempts on
    exhaustion).  ``reserve_blocks`` is the paged admission margin: a
    request is admitted only while the free pool covers its prompt
    blocks PLUS this many, which keeps a little decode headroom and
    damps admit→instant-preempt thrash.

    ``cache_dtype`` (e.g. ``jnp.bfloat16``) shrinks the resident cache
    under an fp32 compute config.  ``top_k`` / ``top_p`` /
    ``vocab_limit`` are engine-wide static sampling knobs (a jit
    recompile each — per-request values would retrace); temperature is
    per-request (a traced ``[max_slots]`` vector).

    ``chunk_tokens`` (ISSUE 15) turns long-prompt admission into
    CHUNKED prefill: a prompt longer than one chunk claims its lane
    and blocks immediately, then streams its prefill one
    ``chunk_tokens``-sized forward per :meth:`step`, interleaved with
    the other lanes' decode (Sarathi-style mixed batching —
    ``step_tokens = decode_lanes + chunk_tokens``), so one 32k prompt
    bounds its co-residents' TPOT interference to one chunk forward
    per poll instead of one monolithic prefill.  The first token is
    sampled from the final chunk's last-token logits
    (greedy-identical to monolithic prefill); a mid-prefill lane can
    be preempted between chunks through the normal block-ledger path
    (nothing delivered yet, so resume just replays the chunks);
    chunk-written blocks are never prefix-shared (see
    :meth:`_blocks_needed`).  ``APEX_TPU_CHUNK_TOKENS`` overrides the
    knob at deploy time.  Composes with ``spec``: the lane joins the
    speculative decode batch once its last chunk lands.

    ``spec`` (ISSUE 8) turns each poll into a speculative round
    (``"ngram"`` or a ``models.speculative.SpecConfig``): every live
    lane drafts ``spec.k`` tokens from its own history, ONE batched
    verify forward scores all lanes' drafts, and each lane emits its
    accepted prefix plus the correction token — up to ``k+1`` tokens
    per poll for one forward.  Greedy lanes stay token-identical to a
    spec-off engine (incl. across preempt→resume — tests/
    test_speculative.py), sampled lanes distribution-identical;
    ``Response.decode_steps`` counts POLLS, the SLO TPOT divides by
    tokens delivered, and the ``generate.spec.*`` counters carry the
    realized accept rate.
    """

    def __init__(self, params: dict, cfg: TransformerConfig, *,
                 max_slots: int = 8, max_len: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 cache_dtype=None, cache_layout: str = "contiguous",
                 cache_wire=None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 reserve_blocks: int = 1,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 vocab_limit: Optional[int] = None,
                 slo_targets: Optional[dict] = None,
                 spec=None,
                 chunk_tokens: Optional[int] = None,
                 host_tier_bytes: Optional[int] = None,
                 host_tier_wire: Optional[str] = None,
                 compile_cache_dir: Optional[str] = None,
                 adapter_pool=None,
                 token_masks: bool = False,
                 rng: Optional[jax.Array] = None):
        _check_decode_cfg(cfg)
        if cache_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"cache_layout={cache_layout!r}: expected 'contiguous' "
                "or 'paged'")
        self.cache_wire = resolve_cache_wire(cache_wire)
        if self.cache_wire != "native" and cache_layout != "paged":
            raise ValueError(
                f"cache_wire={cache_wire!r} needs cache_layout='paged' "
                "— int8 at rest is a block-pool form (ISSUE 14)")
        self.params = params
        self.cfg = cfg
        self.max_slots = int(max_slots)
        self.max_len = int(max_len or cfg.max_position_embeddings)
        # speculative decoding (ISSUE 8): each poll drafts spec.k
        # tokens per lane, verifies them in ONE batched forward, and
        # emits the accepted prefix + correction — several tokens per
        # poll.  _spec_ahead is the KV write horizon a poll may touch
        # past a lane's materialized length (the pending token plus k
        # drafts), which sizes paged tail-block pre-allocation and the
        # admission worst case.
        self._spec = resolve_spec(spec)
        self._spec_ahead = 1 if self._spec is None else self._spec.k + 1
        # chunked prefill (ISSUE 15): prompts longer than chunk_tokens
        # stream their prefill across polls — one fixed-size chunk
        # forward per step(), interleaved with the resident lanes'
        # decode (Sarathi-style: step_tokens = decode_lanes +
        # chunk_tokens) — so a long prompt admits immediately without
        # stalling every co-resident TPOT for its whole prefill.
        # APEX_TPU_CHUNK_TOKENS overrides the caller (deploy-time
        # retuning without a code change); None/off = monolithic.
        self.chunk_tokens = _resolve_chunk_tokens(chunk_tokens)
        if (cfg.position_embedding_type == "learned"
                and self.max_len > cfg.max_position_embeddings):
            raise ValueError(
                f"max_len={self.max_len} exceeds the learned position "
                f"table ({cfg.max_position_embeddings})")
        self.buckets = tuple(sorted(prompt_buckets
                                    or default_buckets(self.max_len)))
        if self.buckets[-1] > self.max_len:
            raise ValueError(
                f"largest prompt bucket {self.buckets[-1]} exceeds "
                f"max_len {self.max_len}")
        # submit validates raw prompts against the CALLER's ladder in
        # both layouts — the resume extension below must not silently
        # widen the configured prompt-size gate
        self._submit_buckets = self.buckets
        if cache_layout == "paged" and self.buckets[-1] < self.max_len:
            # preempt→resume replays prompt+generated through prefill,
            # and that can be ANY length up to max_len — extend the
            # admission ladder so a resume always has a bucket
            self.buckets = tuple(sorted(
                set(self.buckets)
                | {b for b in default_buckets(self.max_len)
                   if b > self.buckets[-1]}))
        self.cache_layout = cache_layout
        # the dtype K/V are COMPUTED and handled in (prefill buckets,
        # handoff padding); the pool may store a different wire form
        self._cache_dtype = jnp.dtype(cache_dtype or cfg.compute_dtype)
        if cache_layout == "paged":
            self.block_size = int(block_size)
            mb = blocks_for(self.max_len, self.block_size)
            if num_blocks:
                self.num_blocks = int(num_blocks)
            elif self.cache_wire == "int8":
                # byte-parity default at the WIRE form (ISSUE 14): the
                # same HBM the native pool would commit buys
                # native_bytes/int8_bytes ≈ itemsize/(1 + 4/dh) times
                # the blocks — the admission-concurrency multiple the
                # --cache-dtype bench ablation measures
                cell = self.block_size * cfg.kv_groups
                native_b = cell * cfg.kv_channels * \
                    self._cache_dtype.itemsize
                int8_b = cell * cfg.kv_channels + 4 * cell
                self.num_blocks = max(
                    mb, self.max_slots * mb * native_b // int8_b)
            else:
                self.num_blocks = self.max_slots * mb
            if reserve_blocks < 0:
                raise ValueError(
                    f"reserve_blocks={reserve_blocks} must be >= 0")
            self.reserve_blocks = int(reserve_blocks)
            pool = init_paged_pool(cfg, self.num_blocks, self.block_size,
                                   cache_dtype=cache_dtype,
                                   cache_wire=self.cache_wire)
            self.cache = dict(
                pool, pos=jnp.zeros((self.max_slots,), jnp.int32))
            self._mgr = BlockManager(self.num_blocks, self.block_size)
            # per-lane block tables, host-mirrored; num_blocks is the
            # UNMAPPED sentinel (reads clamp+mask, writes drop), so a
            # released lane can never touch a reassigned block
            self._tables = np.full((self.max_slots, mb), self.num_blocks,
                                   np.int32)
            # hierarchical KV (ISSUE 18): the bounded host-DRAM page
            # store behind the BlockManager — preempted requests park
            # their pages here (resume = page-in, not prefill replay)
            # and cold published prefixes park by chain digest on
            # their last HBM decref.  APEX_TPU_HOST_TIER_BYTES /
            # APEX_TPU_HOST_TIER_WIRE override the caller.
            hb = resolve_host_tier_bytes(host_tier_bytes)
            self._host = (HostTier(
                hb, wire=resolve_host_tier_wire(host_tier_wire),
                block_size=self.block_size) if hb else None)
        else:
            if resolve_host_tier_bytes(host_tier_bytes):
                raise ValueError(
                    "host_tier_bytes needs cache_layout='paged' — the "
                    "offload tier parks paged blocks (ISSUE 18)")
            self._host = None
            self.cache = init_kv_cache(cfg, self.max_slots, self.max_len,
                                       cache_dtype=cache_dtype)
            self._mgr = None
            self._tables = None
        # resident cache bytes at the wire form (scale pools included)
        # — the serving.cache_bytes{dtype=} gauge and the bench
        # matched-bytes ablation both read this number
        self._cache_bytes = int(sum(
            v.size * v.dtype.itemsize for k, v in self.cache.items()
            if k != "pos"))
        self._wire_dtype_name = ("int8" if self.cache_wire == "int8"
                                 else jnp.dtype(self._cache_dtype).name)
        self._capacity_tokens = (
            self.num_blocks * self.block_size if self._mgr is not None
            else self.max_slots * self.max_len)
        self._blocks_hw = 0
        self._pool = SlotPool(self.max_slots)
        self._slots: List[Optional[_Slot]] = [None] * self.max_slots
        self._queue: deque = deque()
        self._key = rng if rng is not None else jax.random.PRNGKey(0)
        # decode lane state, host-side mirrors of the device batch
        self._pending = np.zeros((self.max_slots,), np.int32)
        self._temps = np.zeros((self.max_slots,), np.float32)
        # spec only: per-lane emitted-token history (prompt+generated,
        # pending token included), the n-gram drafter's haystack.  It
        # LIVES ON DEVICE and is donated through the decode step like
        # the KV cache — the step itself appends each poll's delivered
        # tokens, so steady-state polls pay no host→device re-upload;
        # only admissions/resumes write a row from the host.
        if self._spec is not None:
            self._history = jnp.zeros(
                (self.max_slots, self.max_len), jnp.int32)
            self._hist_len = jnp.zeros((self.max_slots,), jnp.int32)
        else:
            self._history = self._hist_len = None
        # multi-tenant LoRA (ISSUE 20): the refcounted HBM slab pool
        # adapters page through, and the per-lane slab index mirror
        # (0 = base) jnp.asarray'd into the traced step each poll —
        # the SAME host-mirror pattern _pending/_temps use, so compile
        # keys never fork per adapter.
        self._adapters = adapter_pool
        self._lane_slab = np.zeros((self.max_slots,), np.int32)
        # constrained decoding (ISSUE 20 satellite): per-lane boolean
        # vocab masks, all-True for unconstrained lanes.  Allocated
        # only when the caller opts in — an extra [slots, vocab] host
        # array plus one more traced operand is not free.
        self._masks = (np.ones((self.max_slots, cfg.vocab_size), bool)
                       if token_masks else None)
        self._next_id = 0
        self._decode_count = 0
        self._preempt_count = 0
        self._sampling = dict(top_k=top_k, top_p=top_p,
                              vocab_limit=vocab_limit)
        # per-class TTFT/TPOT deadlines (serving/slo.py): defaults
        # overlaid with the caller's overrides; completions are judged
        # into serving.goodput.{met,missed} and the SLO detector
        self._slo_targets = resolve_slo_targets(slo_targets)
        # fused decode-layer routing (ISSUE 17) is resolved ONCE here
        # and threaded as a static into the memoized step builders: an
        # env flip mid-lifetime must never silently replay a stale
        # trace compiled for the other path
        self._decode_fused = route_decode_fused(None)
        self._decode_fn = _make_decode_fn(cfg, top_k, top_p, vocab_limit,
                                          cache_layout == "paged",
                                          self._spec, self._decode_fused)
        self._sample_fn = _make_sample_fn(top_k, top_p, vocab_limit)
        self._chunk_fn = (_make_chunk_fn(cfg, cache_layout == "paged")
                          if self.chunk_tokens else None)
        # persistent compile cache (ISSUE 17): executables load from
        # disk instead of tracing; APEX_TPU_COMPILE_CACHE is the
        # deploy-time default when the caller passes no directory
        cc_dir = (compile_cache_dir
                  or os.environ.get("APEX_TPU_COMPILE_CACHE") or None)
        self._compile_cache = CompileCache(cc_dir) if cc_dir else None

    # -- public API --------------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: int = 32,
               temperature: float = 0.0,
               eos_token_id: Optional[int] = None,
               slo_class: str = "default",
               adapter_id: int = 0,
               token_mask_fn=None) -> int:
        """Queue one request; returns its request id.  ``slo_class``
        keys the engine's deadline table (``slo_targets=``) and labels
        the request's latency sketches + goodput verdict.

        ``adapter_id`` (ISSUE 20) selects a LoRA adapter previously
        :meth:`AdapterPool.register`-ed on the engine's pool; 0 = base
        model.  ``token_mask_fn`` (constrained decoding) is called once
        with the vocab size and must return either a boolean ``[vocab]``
        allow-mask or an iterable of allowed token ids; the mask is
        applied before temperature/top-k/top-p at every sampling site."""
        if adapter_id:
            if self._adapters is None:
                raise ValueError(
                    f"adapter_id={adapter_id} but the engine has no "
                    "adapter_pool — pass adapter_pool= at construction")
            if not self._adapters.registered(adapter_id):
                raise ValueError(
                    f"adapter_id={adapter_id} is not registered on the "
                    "engine's adapter pool")
        token_mask = None
        if token_mask_fn is not None:
            if self._masks is None:
                raise ValueError(
                    "token_mask_fn= needs token_masks=True at engine "
                    "construction (the traced step gains a mask operand)")
            m = token_mask_fn(self.cfg.vocab_size)
            m = np.asarray(m)
            if m.dtype != np.bool_:
                ids = m.astype(np.int64).reshape(-1)
                m = np.zeros((self.cfg.vocab_size,), bool)
                m[ids] = True
            if m.shape != (self.cfg.vocab_size,):
                raise ValueError(
                    f"token_mask_fn returned shape {m.shape}; expected "
                    f"({self.cfg.vocab_size},) or a list of token ids")
            token_mask = m
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, eos_token_id=eos_token_id,
                      request_id=self._next_id, slo_class=str(slo_class),
                      adapter_id=int(adapter_id), token_mask=token_mask)
        if req.prompt.size + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({req.prompt.size}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds the engine max_len "
                f"({self.max_len}); raise max_len or shorten the request")
        pick_bucket(req.prompt.size, self._submit_buckets)  # validate early
        self._check_pool_budget(req)
        if self._mgr is not None:
            # digests once, at submit (ISSUE 18): the admission loop,
            # the claim path and the host tier all reuse this chain —
            # a budget-blocked head request must never rehash per poll
            self._admission_state(req)
        self._next_id += 1
        req.submitted_t = time.perf_counter()
        self._queue.append(req)
        _telemetry.counter("serving.requests").inc()
        if req.adapter_id:
            _telemetry.counter(
                "serving.adapter.requests",
                {"adapter": str(req.adapter_id)}).inc()
        # paired with serving.request.end at completion: the trace sink
        # renders the pair as one async per-request latency row
        _telemetry.event("serving.request.begin", id=req.request_id,
                         prompt_tokens=int(req.prompt.size),
                         max_new_tokens=req.max_new_tokens,
                         slo_class=req.slo_class)
        self._set_gauges()
        return req.request_id

    def submit_prefilled(self, prompt, k, v, first_token: int, *,
                         max_new_tokens: int = 32,
                         temperature: float = 0.0,
                         eos_token_id: Optional[int] = None,
                         slo_class: str = "default",
                         prefill_ms: float = 0.0,
                         shareable: bool = False,
                         adapter_id: int = 0) -> int:
        """Queue a request whose prefill already happened ELSEWHERE —
        the decode half of prefill/decode disaggregation (ISSUE 9).

        ``k``/``v`` are the prompt's per-token K/V ``[L, len(prompt),
        kv_groups, dh]`` (a decoded cluster handoff —
        ``serving/cluster/handoff.py``) and ``first_token`` the token
        the prefill worker sampled from its prefill logits.  Admission
        injects the K/V into this engine's cache (paged: freshly
        allocated blocks, written through the same whole-page scatter
        prefill uses; contiguous: the slot stripe) and the lane decodes
        on — for a raw-wire handoff between same-dtype caches, greedy
        continuation is token-identical to having prefilled here
        (tests/test_serving_handoff.py pins it).  ``prefill_ms`` is
        the remote measurement, carried onto the Response so per-request
        accounting stays meaningful.

        Injected blocks are never prefix-shared or published by
        default: their content is wire-derived (possibly quantized), so
        the chained content digests of locally computed pages must not
        alias them.  ``shareable=True`` (ISSUE 18) opts a handoff INTO
        the flash digest namespace — valid ONLY for raw-wire handoffs
        of fresh prefill pages, which round-trip bit-exactly and are
        therefore bitwise identical to local flash prefill; the caller
        (the cluster decode worker, reading the handoff header) owns
        that judgment.  A shareable handoff maps already-published
        prefix blocks instead of rewriting them and publishes its own
        full prompt blocks for later sharers.  If the request is later
        preempted the handoff is dropped and resume replays through
        the local prefill path.

        ``adapter_id`` (ISSUE 20): the adapter the remote prefill ran
        through — decode must fold the SAME adapter's delta or the
        continuation forks from the prefill distribution.  Adapter
        handoffs are never shareable: the K/V is adapter-specific."""
        if adapter_id:
            if self._adapters is None:
                raise ValueError(
                    f"adapter_id={adapter_id} but the engine has no "
                    "adapter_pool — pass adapter_pool= at construction")
            if not self._adapters.registered(adapter_id):
                raise ValueError(
                    f"adapter_id={adapter_id} is not registered on the "
                    "engine's adapter pool")
            shareable = False
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, eos_token_id=eos_token_id,
                      request_id=self._next_id, slo_class=str(slo_class),
                      adapter_id=int(adapter_id))
        if req.prompt.size + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({req.prompt.size}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds the engine max_len "
                f"({self.max_len}); raise max_len or shorten the request")
        pick_bucket(req.prompt.size, self._submit_buckets)
        self._check_pool_budget(req)
        k = np.asarray(k)
        v = np.asarray(v)
        want = (self.cfg.num_layers, req.prompt.size,
                self.cfg.kv_groups, self.cfg.kv_channels)
        if k.shape != want or v.shape != want:
            raise ValueError(
                f"handoff K/V shape {k.shape}/{v.shape} does not match "
                f"this engine's cache geometry {want} — refusing to "
                "reinterpret a foreign handoff")
        req.handoff = (k, v, int(first_token), float(prefill_ms))
        req.handoff_shareable = bool(shareable)
        if self._mgr is not None and req.handoff_shareable:
            self._admission_state(req)      # digests once, at submit
        self._next_id += 1
        req.submitted_t = time.perf_counter()
        self._queue.append(req)
        _telemetry.counter("serving.requests").inc()
        if req.adapter_id:
            _telemetry.counter(
                "serving.adapter.requests",
                {"adapter": str(req.adapter_id)}).inc()
        _telemetry.event("serving.request.begin", id=req.request_id,
                         prompt_tokens=int(req.prompt.size),
                         max_new_tokens=req.max_new_tokens,
                         slo_class=req.slo_class, injected=True)
        self._set_gauges()
        return req.request_id

    def _check_pool_budget(self, req: Request) -> None:
        """Reject a request that could never complete even alone
        (paged layout: its worst-case block need exceeds the pool)."""
        if self._mgr is None:
            return
        # spec adds a write horizon: a verify block touches up to
        # spec.k cells past the materialized length before its
        # rejected tail rolls back, so the solo worst case must
        # cover those blocks too (clamped to the table reach)
        horizon = min(
            req.prompt.size + req.max_new_tokens
            + (self._spec_ahead - 1),
            blocks_for(self.max_len, self.block_size)
            * self.block_size)
        worst = (blocks_for(horizon, self.block_size)
                 + self.reserve_blocks)
        if worst > self.num_blocks:
            raise ValueError(
                f"request needs up to {worst} blocks (prompt "
                f"{req.prompt.size} + max_new_tokens "
                f"{req.max_new_tokens} at block_size "
                f"{self.block_size}, + {self.reserve_blocks} "
                f"reserve) but the pool holds {self.num_blocks}; "
                "it could never run to completion even alone — "
                "raise num_blocks or shorten the request")

    @property
    def idle(self) -> bool:
        """True when no request is queued or in flight."""
        return not self._queue and self._pool.n_active == 0

    def step(self) -> List[Response]:
        """Admit what fits, run one prefill chunk if a lane is
        mid-prefill (ISSUE 15), decode one token for every live lane;
        returns the requests completed by this step.  The per-step
        token budget is therefore ``decode_lanes + chunk_tokens``
        (Sarathi-style mixed batching): a long prompt streams its
        prefill across polls while everyone else keeps decoding."""
        completed = self._admit()
        # feed the stall detector HERE — after admission, before
        # decode.  This is the only point in the cycle where "queued
        # work alongside free slots" is abnormal: after _decode_once,
        # completions legitimately free slots while the backlog waits
        # for the NEXT step's admission (healthy continuous batching),
        # and before the first step a submit burst is just a queue.
        self._feed_queue_detector()
        if self.chunk_tokens:
            completed.extend(self._prefill_chunk_once())
        if any(st is not None and not st.prefilling
               for st in self._slots):
            completed.extend(self._decode_once())
        self._set_gauges()
        return completed

    def run(self, requests: Sequence[dict] = (),
            max_steps: Optional[int] = None) -> List[Response]:
        """Submit ``requests`` (dicts of :meth:`submit` kwargs), drive
        :meth:`step` until drained, return responses sorted by request
        id."""
        for kw in requests:
            self.submit(**kw)
        out: List[Response] = []
        steps = 0
        while not self.idle:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return sorted(out, key=lambda r: r.request_id)

    def stats(self) -> dict:
        """Engine state snapshot.  Beyond the flat keys (kept stable
        for existing consumers), ``queued_by_class`` and
        ``free_block_headroom`` are the per-SLO-class admission signals
        a cluster router reads (ISSUE 9): how much of each class is
        waiting here, and how many blocks the engine could commit to a
        NEW request without eating its decode reserve (contiguous
        layout: free lanes, each worth one request)."""
        by_class: dict = {}
        for req in self._queue:
            by_class[req.slo_class] = by_class.get(req.slo_class, 0) + 1
        out = {
            "queued": len(self._queue),
            "queued_by_class": by_class,
            "active": self._pool.n_active,
            "free_slots": self._pool.n_free,
            "max_slots": self.max_slots,
            "max_len": self.max_len,
            "buckets": self.buckets,
            "cache_layout": self.cache_layout,
            "cache_wire": self.cache_wire,
            "cache_bytes": self._cache_bytes,
            "sampling": dict(self._sampling),
            "spec_k": None if self._spec is None else self._spec.k,
            "chunk_tokens": self.chunk_tokens,
            "prefilling": sum(1 for st in self._slots
                              if st is not None and st.prefilling),
            "decode_fused": self._decode_fused,
            "compile_cache": (None if self._compile_cache is None
                              else self._compile_cache.stats()),
        }
        if self._mgr is not None:
            free_blocks = max(0, self._mgr.n_free - self.reserve_blocks)
            out.update({
                "block_size": self.block_size,
                "num_blocks": self.num_blocks,
                "blocks_free": self._mgr.n_free,
                "blocks_in_use": self._mgr.n_in_use,
                "prefix_shared_blocks": self._mgr.n_shared,
                "preemptions": self._preempt_count,
                "free_block_headroom": free_blocks,
                # the capacity signal in TOKENS ADMITTABLE under the
                # ACTIVE cache_wire form (ISSUE 15 satellite): an int8
                # pool holds ~1.88x the blocks of a byte-matched native
                # pool, and a consumer comparing pools by bytes (or by
                # a block count at a different block_size) would
                # systematically over-spawn on quantized fleets.
                # Tokens are the one unit every pool form shares.
                "headroom_tokens": free_blocks * self.block_size,
                # the count-bounded digest-inventory summary (ISSUE
                # 18): newest-N chain heads per tier as 64-bit hex
                # prefixes — enough for the router's longest-prefix
                # affinity scoring (collision-rare suffices: the score
                # only picks a worker, it never maps a page)
                "digest_inventory": {
                    "block_size": self.block_size,
                    "chunk_tokens": self.chunk_tokens,
                    "hbm": [h.hex()[:16] for h in
                            self._mgr.newest_digests(
                                DIGEST_INVENTORY_N)],
                    "host": ([h.hex()[:16] for h in
                              self._host.newest_digests()]
                             if self._host is not None else []),
                },
            })
            if self._host is not None:
                out["host_tier"] = self._host.stats()
        else:
            out["free_block_headroom"] = self._pool.n_free
            # contiguous admission reserves a whole stripe per request
            out["headroom_tokens"] = self._pool.n_free * self.max_len
        if self._adapters is not None:
            # rides the cluster poll reply for free (ISSUE 20): the
            # router folds resident_ids into adapter-affinity routing
            out["adapter_pool"] = self._adapters.stats()
        return out

    def drain(self) -> Tuple[List[dict], List[Request]]:
        """Lossless scale-down support (ISSUE 15): pop EVERY request
        out of the engine → ``(live, requeue)``, leaving it idle.

        ``live`` holds one record per decoding lane — everything a
        survivor engine needs to continue the request EXACTLY where it
        stopped: the token sequence the cache materialized (original
        prompt + generated-so-far minus the pending token) as the
        survivor's "prompt", the pending token as its ``first_token``,
        the remaining generation budget, and the per-token K/V pulled
        through :func:`~apex_tpu.models.generate.extract_kv` (block
        tables dereferenced / stripe sliced; int8 pools dequantize to
        float — the wire layer owns its own compression).  Feeding a
        record into another engine's :meth:`submit_prefilled` (the
        cluster drain path does it through the raw KV wire) continues
        greedy token-identically to never having drained
        (tests/test_serving_controller.py pins it).

        ``requeue`` holds the requests with nothing to migrate — the
        engine queue, plus lanes still mid-chunked-prefill (no token
        delivered yet; replaying their prefill elsewhere loses
        nothing) — as plain :class:`Request` objects ready for
        re-submission."""
        live: List[dict] = []
        requeue: List[Request] = []
        if self._mgr is not None:
            # one host->device table upload for the whole drain — the
            # ledger doesn't change until after extraction
            cache = dict(self.cache,
                         block_tables=jnp.asarray(self._tables))
        else:
            cache = self.cache
        for slot in sorted(
                self._pool.active,
                key=lambda s: self._slots[s].request.request_id):
            st = self._slots[slot]
            req = st.request
            if st.prefilling or not st.tokens:
                requeue.append(req)
            else:
                k, v = extract_kv(cache, st.cache_len, row=slot)
                live.append({
                    "engine_rid": req.request_id,
                    "prompt": np.concatenate(
                        [req.prompt,
                         np.asarray(st.tokens[:-1], np.int32)]),
                    "orig_prompt_len": int(req.prompt.size),
                    "done_tokens": list(st.tokens),
                    "first_token": int(st.tokens[-1]),
                    "max_new_tokens": (req.max_new_tokens
                                       - len(st.tokens) + 1),
                    "temperature": req.temperature,
                    "eos_token_id": req.eos_token_id,
                    "slo_class": req.slo_class,
                    "preemptions": req.preemptions,
                    "decode_polls": st.decode_polls,
                    "prefill_ms": st.prefill_ms,
                    "adapter_id": req.adapter_id,
                    "k": np.asarray(k),
                    "v": np.asarray(v),
                })
            self._release_adapter(req)
            self._slots[slot] = None
            self._pending[slot] = 0
            self._temps[slot] = 0.0
            self._lane_slab[slot] = 0
            if self._mgr is not None:
                self._tables[slot, :] = self.num_blocks
                self._mgr.free_all(st.blocks)
            self._pool.release(slot)
            _telemetry.counter("serving.drained").inc()
            _telemetry.event("serving.request.drained",
                             id=req.request_id,
                             migrated=bool(not st.prefilling
                                           and st.tokens))
        while self._queue:
            req = self._queue.popleft()
            req.handoff = None     # its wire pages die with this engine
            requeue.append(req)
            _telemetry.counter("serving.drained").inc()
        self._set_gauges()
        return live, requeue

    # -- internals ---------------------------------------------------------

    def _set_gauges(self) -> None:
        _telemetry.gauge("serving.slot_occupancy").set(
            self._pool.n_active / self.max_slots)
        _telemetry.gauge("serving.queue_depth").set(len(self._queue))
        # quantized-cache accounting (ISSUE 14): pool bytes at the wire
        # form and capacity in tokens, tagged by the at-rest dtype so a
        # stream holding both ends of the --cache-dtype ablation keeps
        # the engines separable (tools/telemetry_report.py derives
        # bytes-per-resident-token and the admission multiple)
        tags = {"dtype": self._wire_dtype_name}
        _telemetry.gauge("serving.cache_bytes", tags).set(
            self._cache_bytes)
        _telemetry.gauge("serving.cache_capacity_tokens", tags).set(
            self._capacity_tokens)
        if self._mgr is not None:
            self._blocks_hw = max(self._blocks_hw, self._mgr.n_in_use)
            _telemetry.gauge("serving.blocks_in_use").set(
                self._mgr.n_in_use)
            _telemetry.gauge("serving.blocks_free").set(self._mgr.n_free)
            _telemetry.gauge("serving.prefix_shared_blocks").set(
                self._mgr.n_shared)
            _telemetry.gauge("serving.cache_blocks_hw", tags).set(
                self._blocks_hw)
        if self.chunk_tokens:
            # chunked-prefill progress (ISSUE 15): aggregate over the
            # in-flight prefilling lanes — serve_dash renders the
            # chunks-done/total column only when these gauges exist.
            # ("progress" naming keeps the OpenMetrics render clear of
            # the serving.prefill_chunks counter's `_total` suffix.)
            pre = [st for st in self._slots
                   if st is not None and st.prefilling]
            _telemetry.gauge("serving.prefilling").set(len(pre))
            _telemetry.gauge("serving.prefill_progress_done").set(
                sum(st.chunks_done for st in pre))
            _telemetry.gauge("serving.prefill_progress_total").set(
                sum(st.chunks_total for st in pre))

    def _feed_queue_detector(self) -> None:
        """Anomaly feed for the queue detector (see step() for why the
        post-admission instant is the only valid sampling point)."""
        reg = _telemetry.registry()
        if reg is not None and reg.detectors is not None:
            reg.detectors.feed_serving(
                len(self._queue), self._pool.n_active / self.max_slots)

    # -- admission ---------------------------------------------------------

    def _admission_state(self, req: Request):
        """(full token array, prefix digests) for the request's current
        resume state, memoized on the Request — populated at submit,
        invalidated only by resume growth or a digest-namespace flip
        (a resume can cross the chunked threshold, and chunk-written
        pages hash under :func:`~apex_tpu.serving.paged_cache.
        chunk_salt`).  _blocks_needed polls this every step() while the
        head request waits on the block budget, so neither the
        prompt+resume concatenation nor the digests may be per-poll
        work."""
        n = req.prompt.size + len(req.resume_tokens)
        salt = (chunk_salt(self.chunk_tokens) if self._chunked(req)
                else b"")
        if (req._hash_cache is None or req._hash_cache[0] != n
                or req._hash_cache[1] != salt):
            tokens = self._full_tokens(req)
            full = n // self.block_size
            req._hash_cache = (n, salt, tokens, prefix_block_hashes(
                tokens[: full * self.block_size], self.block_size,
                salt=salt))
        return req._hash_cache[2], req._hash_cache[3]

    def _chunked(self, req: Request) -> bool:
        """Does this request admit through the chunked-prefill path?
        Only prompts longer than one chunk (a short prompt IS one
        chunk — the monolithic path is strictly better for it) and
        never KV handoffs (their pages come off the wire, not from a
        prefill).  Adapter requests (ISSUE 20) also skip it: their
        prefill runs the LoRA-capable verify forward in one shot, and
        their adapter-specific pages must never publish into the
        chunk digest namespace anyway."""
        if (not self.chunk_tokens or req.handoff is not None
                or req.adapter_id):
            return False
        return (req.prompt.size + len(req.resume_tokens)
                > self.chunk_tokens)

    def _host_resumable(self, req: Request) -> bool:
        """Can this admission skip prefill entirely and page its K/V
        back in from the host tier?  True for a preempted request whose
        materialized pages (``cache_len = prompt + generated - 1`` — the
        pending token's KV was never written) are still parked."""
        return (self._host is not None and req.handoff is None
                and bool(req.resume_tokens)
                and self._host.has_request(
                    req.request_id,
                    req.prompt.size + len(req.resume_tokens) - 1))

    def _chunk_share_plan(self, n: int, hashes: List[bytes]) -> int:
        """How many LEADING full blocks of a chunked admission can map
        (HBM) or page in (host tier) published chunk-namespace digests
        instead of running their chunks.  Sharing is whole-chunk
        granular: the chunk forward writes contiguous ``[lo, hi)``
        spans, so a partially shared chunk would still have to run —
        and every sharer must start its chunk grid at the same aligned
        ``lo`` the producer used, or the flash accumulation phase (and
        hence the page bits) would differ.  Requires ``chunk_tokens %
        block_size == 0`` (otherwise chunk boundaries cut blocks and no
        aligned grid exists), and always leaves the FINAL chunk to run:
        its last-real-token logits sample the first token."""
        ct, bs = self.chunk_tokens, self.block_size
        if ct % bs:
            return 0
        bpc = ct // bs
        max_chunks = min(n // ct, -(-n // ct) - 1)
        lead = 0
        for c in range(max_chunks):
            chunk_hashes = hashes[c * bpc:(c + 1) * bpc]
            if len(chunk_hashes) < bpc:
                break
            if not all(self._mgr.lookup_prefix(h) is not None
                       or (self._host is not None
                           and self._host.has_block(h))
                       for h in chunk_hashes):
                break
            lead += bpc
        return lead

    def _blocks_needed(self, req: Request) -> int:
        """NEW blocks the request must allocate at admission (prefix
        hits against the published HBM block table are free — they map,
        not allocate; host-tier digest hits still allocate, their bytes
        just arrive by page-in scatter instead of compute).  A page-in
        resume covers its materialized ``n - 1`` tokens fresh; so does
        a KV handoff, UNLESS the worker marked it shareable (raw wire,
        fresh prefill pages — bitwise identical to local flash prefill,
        so the flash-namespace digests apply).  A CHUNKED admission
        shares only leading whole chunks in the chunk namespace
        (:meth:`_chunk_share_plan`): chunk-written K/V can differ from
        a monolithic writer's in low-order bits (flash accumulation
        phase), and the content digests guarantee bit-identical
        physical pages only within a writer class."""
        n = req.prompt.size + len(req.resume_tokens)
        bs = self.block_size
        if self._host_resumable(req):
            return blocks_for(n - 1, bs)
        if req.handoff is not None and not req.handoff_shareable:
            return blocks_for(n, bs)
        tokens, hashes = self._admission_state(req)
        need = blocks_for(n, bs)
        if self._chunked(req):
            hashes = hashes[: self._chunk_share_plan(n, hashes)]
        for h in hashes:
            if self._mgr.lookup_prefix(h) is not None:
                need -= 1
        return need

    @staticmethod
    def _full_tokens(req: Request) -> np.ndarray:
        """Prompt plus any pre-preemption progress — the token sequence
        a (re-)admission prefills over."""
        if not req.resume_tokens:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(req.resume_tokens, np.int32)])

    def _admit(self) -> List[Response]:
        """Prefill queued requests into free lanes (continuous
        batching's entry edge).  Contiguous layout: admit while a slot
        is free.  Paged layout: ALSO require the free block pool to
        cover the request's prompt plus ``reserve_blocks`` — the
        block-budget admission that replaces slot-count reservation.
        Returns requests that completed at admission (first token hit
        EOS, or a one-token budget)."""
        completed = []
        while self._queue and self._pool.n_free:
            req = self._queue[0]
            if (self._mgr is not None
                    and self._mgr.n_free < (self._blocks_needed(req)
                                            + self.reserve_blocks)):
                # budget miss: wait for completions (or a preemption)
                # to return blocks — lanes alone don't admit.  Use the
                # wait: decode the head request's parked host-tier
                # pages into a staging copy NOW (the
                # copy_to_host_async-style overlap) so the eventual
                # page-in resume never waits on the wire decode.
                if (self._host is not None and req.resume_tokens
                        and req.handoff is None):
                    self._host.prefetch_request(
                        req.request_id,
                        req.prompt.size + len(req.resume_tokens) - 1)
                break
            if req.adapter_id and not req._lane:
                # pin the adapter's slab lane for the request's whole
                # residency BEFORE claiming the slot (ISSUE 20).  None
                # = every pool lane is pinned by live requests — wait
                # for a completion to unpin one, exactly like the
                # block-budget wait above.  Admission order stays FIFO:
                # a later base-model request must not jump a blocked
                # adapter head (it would starve the adapter class).
                lane = self._adapters.acquire(req.adapter_id)
                if lane is None:
                    break
                req._lane = lane
            self._queue.popleft()
            slot = self._pool.claim()
            try:
                completed.extend(self._admit_one(req, slot))
            except Exception:
                # a transient prefill failure (device OOM, XLA error)
                # must not leak the slot/blocks or drop the request:
                # restore both so the engine stays drainable and a
                # retry can succeed, then surface the error.  Unwind
                # ONLY the pre-handoff state — if the failure struck
                # after the slot was handed over (or after _complete
                # already served and released it), releasing again
                # would double-free and requeueing would serve the
                # request twice.  (_admit_one unwinds its own block
                # allocations; is_active is the O(1) membership check,
                # not a scan over the sorted active tuple.)
                if (self._slots[slot] is None
                        and self._pool.is_active(slot)):
                    self._release_adapter(req)
                    self._pool.release(slot)
                    self._queue.appendleft(req)
                    self._set_gauges()
                raise
        return completed

    def _release_adapter(self, req: Request) -> None:
        """Drop the request's adapter-pool pin, if it holds one.  Every
        slot-teardown edge (complete, preempt, drain, admission unwind)
        funnels through here so the pool ledger stays a true partition
        — ``req._lane`` being 1-based-or-zero makes double-release
        structurally impossible."""
        if self._adapters is not None and req._lane:
            self._adapters.release(req.adapter_id)
            req._lane = 0

    def _mask_arg(self, req: Request) -> tuple:
        """Constrained-decoding operand for one request's sampling
        sites: ``()`` when masks are off (existing call avals — and
        therefore compile-cache keys — stay untouched), else a 1-tuple
        holding the request's 1-D boolean allow-mask (all-True for
        unconstrained requests, so one trace serves both)."""
        if self._masks is None:
            return ()
        m = (req.token_mask if req.token_mask is not None
             else np.ones((self.cfg.vocab_size,), bool))
        return (jnp.asarray(m),)

    def _bind_slot_lane(self, req: Request, slot: int) -> None:
        """Stamp the lane-local traced-operand mirrors at slot handoff
        (ISSUE 20): the adapter slab index and, when constrained
        decoding is on, the request's vocab mask row.  Every teardown
        edge resets both."""
        self._lane_slab[slot] = req._lane
        if self._masks is not None:
            self._masks[slot, :] = (req.token_mask
                                    if req.token_mask is not None
                                    else True)

    def _claim_blocks(self, tokens: np.ndarray, hashes: List[bytes]):
        """Map/allocate the block list for ``tokens`` (``hashes`` =
        its full-block prefix digests): full blocks come from the
        prefix-hash table when published (refcounted share — their
        pages are NOT rewritten); a digest that misses HBM but is
        parked in the host tier allocates fresh, publishes, and rides
        back in by page-in scatter (also excluded from the prefill
        write — the raw host wire restores bitwise what the prefill
        would have written); everything else allocates fresh.  Returns
        (blocks, write_ids, shared_count, page_ins) where ``page_ins``
        is ``[(block, (k, v)), ...]`` for :meth:`_page_in_blocks`;
        raises RuntimeError on pool exhaustion with everything already
        unwound."""
        n = tokens.size
        bs = self.block_size
        blocks: List[int] = []
        write_ids: List[int] = []
        page_ins: List[tuple] = []
        shared = 0
        try:
            for h in hashes:
                blk = self._mgr.share_prefix(h)
                if blk is not None:
                    blocks.append(blk)
                    write_ids.append(self.num_blocks)   # don't rewrite
                    shared += 1
                    continue
                hit = None
                if self._host is not None and self._host.has_block(h):
                    # has_block first so peek's hit/miss accounting
                    # only sees digests that were actually parked
                    hit = self._host.peek_block(h)
                blk = self._mgr.alloc()
                if blk is None:
                    raise RuntimeError("block pool exhausted mid-admit")
                self._mgr.publish_prefix(h, blk)
                blocks.append(blk)
                if hit is not None:
                    write_ids.append(self.num_blocks)   # page-in writes
                    page_ins.append((blk, hit))
                else:
                    write_ids.append(blk)
            if n % bs:
                blk = self._mgr.alloc()                 # private tail
                if blk is None:
                    raise RuntimeError("block pool exhausted mid-admit")
                blocks.append(blk)
                write_ids.append(blk)
        except Exception:
            self._mgr.free_all(blocks)
            raise
        return blocks, write_ids, shared, page_ins

    def _claim_blocks_fresh(self, n_tokens: int):
        """Allocate ``blocks_for(n_tokens)`` fresh blocks (no prefix
        mapping, no publishing) — the admission form for wire-derived
        pages that must never alias the digest namespace (non-shareable
        KV handoffs, page-in resumes whose pages carry decode-written
        tokens).  Same unwind contract as :meth:`_claim_blocks`."""
        blocks: List[int] = []
        try:
            for _ in range(blocks_for(n_tokens, self.block_size)):
                blk = self._mgr.alloc()
                if blk is None:
                    raise RuntimeError("block pool exhausted mid-admit")
                blocks.append(blk)
        except Exception:
            self._mgr.free_all(blocks)
            raise
        return blocks, list(blocks), 0, []

    def _claim_blocks_chunked(self, n: int, hashes: List[bytes]):
        """Block claim for a chunked admission: the leading whole-chunk
        run of published chunk-namespace digests maps (HBM share) or
        pages in (host tier); everything after allocates fresh and
        publishes one block at a time as its chunk lands
        (:meth:`_publish_chunk_blocks`).  Returns (blocks, shared,
        page_ins, lo) where ``lo`` is the chunk-aligned prefill start
        (shared chunks are skipped entirely — the compute win chunked
        sharing exists for).  Same unwind contract as
        :meth:`_claim_blocks`."""
        bs = self.block_size
        lead = self._chunk_share_plan(n, hashes)
        blocks: List[int] = []
        page_ins: List[tuple] = []
        shared = 0
        try:
            for h in hashes[:lead]:
                blk = self._mgr.share_prefix(h)
                if blk is not None:
                    blocks.append(blk)
                    shared += 1
                    continue
                hit = (self._host.peek_block(h)
                       if self._host is not None else None)
                if hit is None:
                    # the plan saw this digest moments ago and nothing
                    # mutates either tier between plan and claim
                    # (engine-loop confined) — unwind loudly rather
                    # than page garbage in
                    raise RuntimeError(
                        "host-tier digest vanished mid-claim")
                blk = self._mgr.alloc()
                if blk is None:
                    raise RuntimeError("block pool exhausted mid-admit")
                self._mgr.publish_prefix(h, blk)
                blocks.append(blk)
                page_ins.append((blk, hit))
            for _ in range(len(blocks), blocks_for(n, bs)):
                blk = self._mgr.alloc()
                if blk is None:
                    raise RuntimeError("block pool exhausted mid-admit")
                blocks.append(blk)
        except Exception:
            self._mgr.free_all(blocks)
            raise
        return blocks, shared, page_ins, lead * bs

    def _page_in_blocks(self, slot: int, page_ins: List[tuple]) -> None:
        """Scatter host-tier digest pages into their freshly published
        HBM blocks through THE one insert edge at
        ``bucket=block_size`` — one compile covers every page-in, and
        int8 pools requantize through the same write path prefill uses
        (requantization is idempotent, so the pool bytes match a
        prefill-written page exactly).  The transient ``pos`` stamp the
        insert leaves is harmless: every caller re-stamps the lane
        position afterward."""
        if not page_ins:
            return
        t0 = time.perf_counter()
        bs = self.block_size
        L, g, dh = (self.cfg.num_layers, self.cfg.kv_groups,
                    self.cfg.kv_channels)
        # ONE batched scatter for every paged-in block: the insert
        # maps token-chunk i to write_ids[i] and each page carries
        # exactly its own block's tokens, so HBM-shared blocks
        # interleaved in token space don't split the batch.  The
        # bucket pads to the next power-of-two block count — a
        # logarithmic compile ladder instead of one dispatch per page
        # (n masks the padding; write_ids pads with UNMAPPED).
        m = len(page_ins)
        cap = 1
        while cap < m:
            cap *= 2
        bucket = cap * bs
        ks = np.zeros((L, 1, bucket, g, dh), dtype=self._cache_dtype)
        vs = np.zeros_like(ks)
        for i, (_blk, (k, v)) in enumerate(page_ins):
            ks[:, 0, i * bs:(i + 1) * bs] = np.asarray(
                k, dtype=self._cache_dtype).reshape(L, bs, g, dh)
            vs[:, 0, i * bs:(i + 1) * bs] = np.asarray(
                v, dtype=self._cache_dtype).reshape(L, bs, g, dh)
        self._insert_prefill_kv(slot, bucket,
                                [blk for blk, _kv in page_ins],
                                jnp.asarray(ks), jnp.asarray(vs),
                                m * bs)
        _telemetry.counter("serving.host_tier.page_ins").inc(m)
        _telemetry.sketch("serving.host_tier.page_in_ms").observe(
            (time.perf_counter() - t0) * 1e3)

    # -- persistent compile cache routing (ISSUE 17) -----------------------

    def _cc_parts(self, **extra) -> dict:
        """The engine-level static identity every persistent-compile-
        cache key carries: wire/layout/spec/chunk/fusion knobs plus
        per-site extras (the prompt bucket).  Mesh geometry and the
        code-version digest are appended by ``CompileCache`` itself."""
        return dict(cache_wire=self.cache_wire,
                    cache_layout=self.cache_layout, spec=self._spec,
                    chunk_tokens=self.chunk_tokens,
                    decode_fused=self._decode_fused,
                    lora=self._adapters is not None,
                    masked=self._masks is not None, **extra)

    def _cc(self, name: str, jitfn, args: tuple, static=None, **parts):
        """Route one jitted call through the persistent compile cache
        when one is configured.  ``args`` are the dynamic positionals
        (what the AOT executable is called with); ``static`` holds
        keyword-only ``static_argnames`` that exist at lowering but are
        baked in at call time.  Without a cache — or when the loaded
        executable rejects the arguments before running (an aval drift
        the key missed; donation has not happened yet at that point) —
        the plain jit call runs and hits jax's in-memory cache."""
        static = static or {}
        if self._compile_cache is not None:
            fn = self._compile_cache.load_or_compile(
                name, jitfn, args, static,
                key_parts=self._cc_parts(**parts))
            if fn is not None:
                try:
                    return fn(*args)
                except Exception:
                    pass
        return jitfn(*args, **static)

    def _cc_prefill(self, padded, lens, bucket: int):
        """The prefill edge's cache routing — special-cased because its
        static ``cfg`` rides in a POSITIONAL slot, so the AOT call
        drops it while the jit fallback keeps it."""
        if self._compile_cache is not None:
            fn = self._compile_cache.load_or_compile(
                "prefill", prefill, (self.params, padded, self.cfg),
                dict(prompt_lens=lens, max_len=bucket,
                     cache_dtype=self._cache_dtype),
                key_parts=self._cc_parts(bucket=bucket))
            if fn is not None:
                try:
                    return fn(self.params, padded, prompt_lens=lens)
                except Exception:
                    pass
        return prefill(self.params, padded, self.cfg, prompt_lens=lens,
                       max_len=bucket, cache_dtype=self._cache_dtype)

    def _insert_prefill_kv(self, slot: int, bucket: int,
                           write_ids: List[int], ks, vs, n: int) -> None:
        """THE one insert edge for a freshly admitted request's K/V
        ``[L, 1, bucket, g, dh]`` — used by both the prefill path and
        the handoff-injection path, so the two can never drift apart
        (the cross-process token-identity pin depends on injection
        writing exactly what prefill would have)."""
        if self._mgr is not None:
            wid = np.full((blocks_for(bucket, self.block_size),),
                          self.num_blocks, np.int32)
            wid[: len(write_ids)] = write_ids
            if self.cache_wire == "int8":
                k, v, sk, sv = self._cc(
                    "paged_insert_prefill_q", paged_insert_prefill_q,
                    (self.cache["k"], self.cache["v"],
                     self.cache["k_scale"], self.cache["v_scale"],
                     ks, vs, jnp.asarray(wid), jnp.int32(n)),
                    dict(block_size=self.block_size), bucket=bucket)
                self.cache = {
                    "k": k, "v": v, "k_scale": sk, "v_scale": sv,
                    "pos": self.cache["pos"].at[slot].set(n),
                }
            else:
                k, v = self._cc(
                    "paged_insert_prefill", paged_insert_prefill,
                    (self.cache["k"], self.cache["v"], ks, vs,
                     jnp.asarray(wid), jnp.int32(n)),
                    dict(block_size=self.block_size), bucket=bucket)
                self.cache = {
                    "k": k, "v": v,
                    "pos": self.cache["pos"].at[slot].set(n),
                }
        else:
            self.cache = self._cc(
                "_insert_slot", _insert_slot,
                (self.cache, ks, vs, jnp.int32(slot), jnp.int32(n)),
                bucket=bucket)

    def _inject_handoff(self, req: Request, slot: int, bucket: int,
                        write_ids: List[int], n: int) -> int:
        """Write a decoded KV handoff into this lane's cache through
        the SAME jitted inserts prefill uses (bucket-shaped, so the
        compile cache is shared with the prefill path) and return the
        remotely sampled first token."""
        k, v, tok, _ms = req.handoff
        shape = (self.cfg.num_layers, 1, bucket,
                 self.cfg.kv_groups, self.cfg.kv_channels)
        k_pad = np.zeros(shape, dtype=self._cache_dtype)
        v_pad = np.zeros(shape, dtype=self._cache_dtype)
        k_pad[:, 0, :n] = np.asarray(k, dtype=self._cache_dtype)
        v_pad[:, 0, :n] = np.asarray(v, dtype=self._cache_dtype)
        self._insert_prefill_kv(slot, bucket, write_ids,
                                jnp.asarray(k_pad), jnp.asarray(v_pad),
                                n)
        return int(tok)

    def _admit_one(self, req: Request, slot: int) -> List[Response]:
        """Prefill one claimed request into its lane (split out so
        :meth:`_admit` can unwind slot + queue state on failure; block
        allocations unwind HERE, closest to where they happen).  A
        request carrying a KV handoff (``submit_prefilled``) skips the
        prefill forward entirely: its cache pages come off the wire,
        its first token from the remote sampler.  A preempted request
        whose pages are still parked in the host tier skips it too —
        resume becomes a page-in (:meth:`_admit_one_paged_in`), even
        for prompts that would otherwise replay chunked."""
        if (self._host is not None and req.handoff is None
                and req.resume_tokens):
            n_kv = req.prompt.size + len(req.resume_tokens) - 1
            kv = self._host.take_request(req.request_id, n_kv)
            if kv is not None:
                return self._admit_one_paged_in(req, slot, *kv)
            # parked pages evicted (or never fit): fall through to a
            # prefill replay.  take_request counted the miss; this
            # counter is the replay half of the resume-vs-replay ratio
            _telemetry.counter("serving.host_tier.replays").inc()
        if self._chunked(req):
            return self._admit_one_chunked(req, slot)
        if req.adapter_id and req.handoff is None:
            # adapter prefill (ISSUE 20) runs the LoRA-capable verify
            # forward — the flash prefill kernel has no delta hook.
            # Handoff admissions stay below: their pages come off the
            # wire and only DECODE needs the adapter.
            return self._admit_one_adapter(req, slot)
        completed: List[Response] = []
        hashes: List[bytes] = []
        page_ins: List[tuple] = []
        shareable = (self._mgr is not None
                     and (req.handoff is None or req.handoff_shareable))
        if shareable:
            tokens, hashes = self._admission_state(req)
        else:
            tokens = self._full_tokens(req)
        n = int(tokens.size)
        bucket = pick_bucket(n, self.buckets)
        blocks: List[int] = []
        write_ids: List[int] = []
        shared = 0
        if self._mgr is not None:
            if shareable:
                # prefill admissions AND shareable raw-wire handoffs
                # map/publish flash-namespace digests (their pages are
                # bitwise what local flash prefill writes)
                blocks, write_ids, shared, page_ins = \
                    self._claim_blocks(tokens, hashes)
            else:
                blocks, write_ids, shared, page_ins = \
                    self._claim_blocks_fresh(n)
        t0 = time.perf_counter()
        if req.admitted_t == 0.0:
            # first admission only: queue wait ends the moment the
            # engine starts working the request (a post-preemption
            # resume is overhead, not queue wait).  The stamp survives
            # a failed-admission unwind on purpose — a retry's queue
            # wait still ends at the first attempt.
            req.admitted_t = t0
            req.queue_wait_s = t0 - req.submitted_t
        try:
            if page_ins:
                # restore host-parked digest pages first (disjoint
                # blocks from every write below; the final insert
                # re-stamps pos)
                with span("serving.host_page_in"), \
                        compile_label("serving.prefill"):
                    self._page_in_blocks(slot, page_ins)
            if req.handoff is not None:
                with span("serving.kv_inject"), \
                        compile_label("serving.prefill"):
                    # same label: the bucket-shaped insert compile is
                    # shared with (and indistinguishable from) the
                    # prefill path's
                    tok = self._inject_handoff(req, slot, bucket,
                                               write_ids, n)
            else:
                with span("serving.prefill"), \
                        compile_label("serving.prefill"):
                    padded = jnp.asarray(pad_prompt(tokens, bucket)[None])
                    lens = jnp.asarray([n], jnp.int32)
                    logits, small = self._cc_prefill(padded, lens,
                                                     bucket)
                    self._insert_prefill_kv(slot, bucket, write_ids,
                                            small["k"], small["v"], n)
                    self._key, sub = jax.random.split(self._key)
                    first = self._cc(
                        "sample", self._sample_fn,
                        (logits,
                         jnp.asarray([req.temperature], jnp.float32),
                         sub) + self._mask_arg(req))
                    tok = int(np.asarray(first)[0])      # host sync
            if self._mgr is not None:
                self._tables[slot, :] = self.num_blocks
                self._tables[slot, : len(blocks)] = blocks
                # high-water at the claim edge, not the gauge edge — a
                # request that admits and completes within one step
                # must still register its pool footprint
                self._blocks_hw = max(self._blocks_hw,
                                      self._mgr.n_in_use)
            now = time.perf_counter()
            ms = (now - t0) * 1e3
            if req.first_token_t == 0.0:
                # TTFT ends here: the first sampled token exists on the
                # host.  The paired event lets a trace/JSONL consumer
                # reconstruct TTFT independently of the engine's own
                # arithmetic (the soak test pins the two against each
                # other).
                req.first_token_t = now
                _telemetry.event("serving.request.first_token",
                                 id=req.request_id,
                                 slo_class=req.slo_class)
            if req.preempted_t:
                # resume complete: the preemption cycle's cost (requeue
                # wait + this replay prefill) is now fully realized
                req.preempt_overhead_s += now - req.preempted_t
                req.preempted_t = 0.0
            if req.handoff is not None:
                # the prefill happened remotely: count the injection,
                # keep serving.prefill_{calls,ms} honest (no forward
                # ran here), and carry the REMOTE prefill cost onto
                # the Response so per-request accounting holds up
                _telemetry.counter("serving.kv_injected").inc()
                _telemetry.histogram("serving.kv_inject_ms").observe(ms)
                ms = req.handoff[3]
            else:
                _telemetry.counter("serving.prefill_calls").inc()
                _telemetry.histogram("serving.prefill_ms").observe(ms)
            _telemetry.counter("serving.tokens_generated").inc()
            if _telemetry.enabled():
                sample_device_memory()   # admission = cache growth edge
            st = _Slot(request=req,
                       tokens=list(req.resume_tokens) + [tok],
                       prefill_ms=ms, blocks=blocks, cache_len=n,
                       shared_blocks=shared,
                       decode_polls=req.resume_polls)
        except Exception:
            # everything before the slot handoff below can raise (the
            # prefill itself, but also a telemetry sink or the HBM
            # sample) — the claimed blocks must unwind HERE or they
            # leak: _admit's unwind restores only slot + queue state
            if self._mgr is not None:
                self._mgr.free_all(blocks)
                self._tables[slot, :] = self.num_blocks
            raise
        self._slots[slot] = st
        self._pending[slot] = tok
        self._temps[slot] = req.temperature
        self._bind_slot_lane(req, slot)
        if self._spec is not None:
            # the drafter's haystack: everything emitted so far,
            # pending token included.  Padded host-side so the device
            # row write is ONE fixed-shape op regardless of length.
            row = np.zeros((self.max_len,), np.int32)
            row[: n] = tokens
            row[n] = tok
            self._history = self._history.at[slot].set(jnp.asarray(row))
            self._hist_len = self._hist_len.at[slot].set(n + 1)
        done = self._finish_reason(st, tok)
        if done:
            completed.append(self._complete(slot, done))
        return completed

    def _admit_one_adapter(self, req: Request, slot: int
                           ) -> List[Response]:
        """Admit one LoRA request (ISSUE 20): prefill the whole prompt
        through the verify forward with the request's adapter delta
        folded in — the same traced family the cluster prefill worker
        uses, so a raw-wire handoff continues bit-exactly.  Blocks are
        always claimed FRESH and never published: adapter K/V is
        adapter-specific, and aliasing it into the base-model digest
        namespace would serve one tenant another tenant's attention
        state."""
        completed: List[Response] = []
        tokens = self._full_tokens(req)
        n = int(tokens.size)
        bucket = pick_bucket(n, self.buckets)
        blocks: List[int] = []
        if self._mgr is not None:
            blocks, _wids, _shared, _pi = self._claim_blocks_fresh(n)
        t0 = time.perf_counter()
        if req.admitted_t == 0.0:
            req.admitted_t = t0
            req.queue_wait_s = t0 - req.submitted_t
        try:
            if self._mgr is not None:
                # the verify forward writes THROUGH the block tables,
                # so the lane's table must exist before the call (the
                # monolithic path stamps it after its row-insert)
                self._tables[slot, :] = self.num_blocks
                self._tables[slot, : len(blocks)] = blocks
                self._blocks_hw = max(self._blocks_hw,
                                      self._mgr.n_in_use)
            with span("serving.lora_prefill"), \
                    compile_label("serving.prefill"):
                padded = pad_prompt(tokens, bucket)
                slabs = self._adapters.slabs()
                args = (self.params, self.cache,
                        jnp.asarray(padded[None]), jnp.int32(n),
                        jnp.int32(slot),
                        jnp.asarray([req._lane], jnp.int32), slabs)
                if self._mgr is not None:
                    args += (jnp.asarray(self._tables[slot]),)
                logits, self.cache = self._cc(
                    "lora_prefill",
                    _make_lora_prefill_fn(self.cfg,
                                          self._mgr is not None),
                    args, bucket=bucket)
                self._key, sub = jax.random.split(self._key)
                first = self._cc(
                    "sample", self._sample_fn,
                    (logits[:, n - 1],
                     jnp.asarray([req.temperature], jnp.float32),
                     sub) + self._mask_arg(req))
                tok = int(np.asarray(first)[0])          # host sync
            now = time.perf_counter()
            ms = (now - t0) * 1e3
            if req.first_token_t == 0.0:
                req.first_token_t = now
                _telemetry.event("serving.request.first_token",
                                 id=req.request_id,
                                 slo_class=req.slo_class)
            if req.preempted_t:
                req.preempt_overhead_s += now - req.preempted_t
                req.preempted_t = 0.0
            _telemetry.counter("serving.prefill_calls").inc()
            _telemetry.histogram("serving.prefill_ms").observe(ms)
            _telemetry.counter("serving.tokens_generated").inc()
            if _telemetry.enabled():
                sample_device_memory()
            st = _Slot(request=req,
                       tokens=list(req.resume_tokens) + [tok],
                       prefill_ms=ms, blocks=blocks, cache_len=n,
                       shared_blocks=0,
                       decode_polls=req.resume_polls)
        except Exception:
            if self._mgr is not None:
                self._mgr.free_all(blocks)
                self._tables[slot, :] = self.num_blocks
            raise
        self._slots[slot] = st
        self._pending[slot] = tok
        self._temps[slot] = req.temperature
        self._bind_slot_lane(req, slot)
        if self._spec is not None:
            row = np.zeros((self.max_len,), np.int32)
            row[: n] = tokens
            row[n] = tok
            self._history = self._history.at[slot].set(jnp.asarray(row))
            self._hist_len = self._hist_len.at[slot].set(n + 1)
        done = self._finish_reason(st, tok)
        if done:
            completed.append(self._complete(slot, done))
        return completed

    def _admit_one_paged_in(self, req: Request, slot: int,
                            k: np.ndarray, v: np.ndarray
                            ) -> List[Response]:
        """Re-admit a preempted request from its host-tier parked pages:
        claim fresh blocks (the pages carry decode-written tokens —
        never digest-shareable, the handoff no-alias rule), scatter the
        parked K/V back through THE one insert edge, and put the lane
        straight back into decode behind its pending token.  NO prefill
        forward runs and NO token is sampled: the preempted lane
        already held its pending token (``resume_tokens[-1]``), whose
        KV the next decode step writes — exactly the state the lane was
        preempted in.  For the raw host wire the round trip is bitwise,
        so greedy continuation is token-identical to the never-preempted
        run (the kv_tier dryrun phase pins this)."""
        n_kv = req.prompt.size + len(req.resume_tokens) - 1
        bucket = pick_bucket(n_kv, self.buckets)
        blocks, write_ids, _sh, _pi = self._claim_blocks_fresh(n_kv)
        t0 = time.perf_counter()
        try:
            with span("serving.host_page_in"), \
                    compile_label("serving.prefill"):
                shape = (self.cfg.num_layers, 1, bucket,
                         self.cfg.kv_groups, self.cfg.kv_channels)
                k_pad = np.zeros(shape, dtype=self._cache_dtype)
                v_pad = np.zeros(shape, dtype=self._cache_dtype)
                k_pad[:, 0, :n_kv] = np.asarray(
                    k, dtype=self._cache_dtype)
                v_pad[:, 0, :n_kv] = np.asarray(
                    v, dtype=self._cache_dtype)
                self._insert_prefill_kv(slot, bucket, write_ids,
                                        jnp.asarray(k_pad),
                                        jnp.asarray(v_pad), n_kv)
            self._tables[slot, :] = self.num_blocks
            self._tables[slot, : len(blocks)] = blocks
            self._blocks_hw = max(self._blocks_hw,
                                  self._mgr.n_in_use)
            now = time.perf_counter()
            ms = (now - t0) * 1e3
            if req.preempted_t:
                # the preemption cycle closes here — no replay ran, so
                # its whole cost is requeue wait + this page-in
                req.preempt_overhead_s += now - req.preempted_t
                req.preempted_t = 0.0
            _telemetry.counter("serving.host_tier.resumes").inc()
            _telemetry.sketch("serving.host_tier.page_in_ms").observe(
                ms)
            if _telemetry.enabled():
                sample_device_memory()
            st = _Slot(request=req, tokens=list(req.resume_tokens),
                       prefill_ms=ms, blocks=blocks, cache_len=n_kv,
                       decode_polls=req.resume_polls)
        except Exception:
            self._mgr.free_all(blocks)
            self._tables[slot, :] = self.num_blocks
            raise
        self._slots[slot] = st
        tok = int(req.resume_tokens[-1])
        self._pending[slot] = tok
        self._temps[slot] = req.temperature
        self._bind_slot_lane(req, slot)
        if self._spec is not None:
            tokens = self._full_tokens(req)
            n = int(tokens.size)
            row = np.zeros((self.max_len,), np.int32)
            row[: n] = tokens
            self._history = self._history.at[slot].set(jnp.asarray(row))
            self._hist_len = self._hist_len.at[slot].set(n)
        return []

    # -- chunked prefill (ISSUE 15) ----------------------------------------

    def _admit_one_chunked(self, req: Request, slot: int
                           ) -> List[Response]:
        """Admit a long prompt WITHOUT running its prefill: claim the
        lane and (paged) every block the full prompt needs — the same
        admission budget the monolithic path commits, so the
        block-ledger arithmetic is unchanged — then mark the lane
        ``prefilling``.  The prefill itself streams one chunk per
        :meth:`step` (:meth:`_prefill_chunk_once`), interleaved with
        the other lanes' decode; the first token is sampled from the
        FINAL chunk's last-token logits, which are greedy-identical to
        the monolithic prefill's (tests/test_serving_chunked.py).

        Chunk-namespace digest sharing (ISSUE 18): leading whole-chunk
        runs whose chain digests are already published map from HBM or
        page in from the host tier (:meth:`_claim_blocks_chunked`) and
        their chunks never run; every other full block publishes its
        digest as its chunk lands (:meth:`_publish_chunk_blocks`)."""
        tokens = self._full_tokens(req)
        n = int(tokens.size)
        blocks: List[int] = []
        hashes: List[bytes] = []
        page_ins: List[tuple] = []
        shared = 0
        lo = 0
        if self._mgr is not None:
            _tok, hashes = self._admission_state(req)
            blocks, shared, page_ins, lo = self._claim_blocks_chunked(
                n, hashes)
        t0 = time.perf_counter()
        if req.admitted_t == 0.0:
            req.admitted_t = t0
            req.queue_wait_s = t0 - req.submitted_t
        try:
            if self._mgr is not None:
                self._tables[slot, :] = self.num_blocks
                self._tables[slot, : len(blocks)] = blocks
                self._blocks_hw = max(self._blocks_hw,
                                      self._mgr.n_in_use)
                self._page_in_blocks(slot, page_ins)
            # park the lane's device position at the share boundary so
            # the masked decode rides it inertly until the first chunk
            # stamps real progress (a stale position from the lane's
            # previous occupant must not outlive the handover)
            self.cache = dict(
                self.cache, pos=self.cache["pos"].at[slot].set(lo))
            _telemetry.event("serving.request.chunk_admit",
                             id=req.request_id, prompt_tokens=n,
                             chunks=-(-(n - lo) // self.chunk_tokens),
                             shared_blocks=shared,
                             paged_in_blocks=len(page_ins))
        except Exception:
            if self._mgr is not None:
                self._mgr.free_all(blocks)
                self._tables[slot, :] = self.num_blocks
            raise
        self._slots[slot] = _Slot(
            request=req, tokens=[], prefill_ms=0.0, blocks=blocks,
            cache_len=lo, shared_blocks=shared,
            decode_polls=req.resume_polls,
            prefilling=True, chunks_done=0,
            chunks_total=-(-(n - lo) // self.chunk_tokens),
            prefill_tokens=tokens,
            digests=(hashes if self._mgr is not None else None),
            published_upto=(lo // self.block_size
                            if self._mgr is not None else 0))
        self._pending[slot] = 0
        self._temps[slot] = 0.0
        self._bind_slot_lane(req, slot)
        return []

    def _prefill_chunk_once(self) -> List[Response]:
        """Run ONE prefill chunk for the oldest prefilling lane — the
        chunk half of the mixed step budget (``step_tokens =
        decode_lanes + chunk_tokens``).  Oldest-first keeps chunk
        completion FIFO, so a second long prompt queues its chunks
        behind the first instead of both starving.  On the final chunk
        the lane transitions to decoding: first token sampled from the
        chunk's last-token logits, TTFT stamped, history row written
        (spec), and the completion edges handled exactly as a
        monolithic admission would."""
        slots = [s for s in self._pool.active
                 if self._slots[s] is not None
                 and self._slots[s].prefilling]
        if not slots:
            return []
        slot = min(slots,
                   key=lambda s: self._slots[s].request.request_id)
        st = self._slots[slot]
        req = st.request
        tokens = st.prefill_tokens
        n = int(tokens.size)
        lo = st.cache_len
        hi = min(n, lo + self.chunk_tokens)
        # ONE chunk shape for the engine's lifetime: tail chunks pad up
        # (their padding writes drop past the table reach / sit past
        # `new_pos`, invisible to every masked read)
        chunk = pad_prompt(tokens[lo:hi], self.chunk_tokens)
        t0 = time.perf_counter()
        with span("serving.prefill_chunk"), \
                compile_label("serving.prefill_chunk"):
            if self._mgr is not None:
                logits, self.cache = self._cc(
                    "chunk", self._chunk_fn,
                    (self.params, self.cache,
                     jnp.asarray(self._tables[slot]),
                     jnp.asarray(chunk), jnp.int32(lo), jnp.int32(hi),
                     jnp.int32(slot)))
            else:
                logits, self.cache = self._cc(
                    "chunk", self._chunk_fn,
                    (self.params, self.cache, jnp.asarray(chunk),
                     jnp.int32(lo), jnp.int32(hi), jnp.int32(slot)))
            if hi >= n:
                # final chunk: its last-REAL-token logits are the
                # first-token logits (greedy-identical to monolithic
                # prefill); sample while still inside the span so
                # prefill cost accounting covers the whole admission
                self._key, sub = jax.random.split(self._key)
                first = self._cc(
                    "sample", self._sample_fn,
                    (logits[:, n - 1 - lo],
                     jnp.asarray([req.temperature], jnp.float32), sub)
                    + self._mask_arg(req))
                tok = int(np.asarray(first)[0])      # host sync
        now = time.perf_counter()
        st.prefill_ms += (now - t0) * 1e3
        st.cache_len = hi
        st.chunks_done += 1
        if self._mgr is not None and st.digests is not None:
            self._publish_chunk_blocks(st, hi)
        _telemetry.counter("serving.prefill_chunks").inc()
        if hi < n:
            return []
        # -- transition to decoding ------------------------------------
        if req.first_token_t == 0.0:
            req.first_token_t = now
            _telemetry.event("serving.request.first_token",
                             id=req.request_id, slo_class=req.slo_class)
        if req.preempted_t:
            req.preempt_overhead_s += now - req.preempted_t
            req.preempted_t = 0.0
        _telemetry.counter("serving.prefill_calls").inc()
        _telemetry.histogram("serving.prefill_ms").observe(st.prefill_ms)
        _telemetry.counter("serving.tokens_generated").inc()
        if _telemetry.enabled():
            sample_device_memory()
        st.prefilling = False
        st.prefill_tokens = None
        st.tokens = list(req.resume_tokens) + [tok]
        self._pending[slot] = tok
        self._temps[slot] = req.temperature
        if self._spec is not None:
            row = np.zeros((self.max_len,), np.int32)
            row[: n] = tokens
            row[n] = tok
            self._history = self._history.at[slot].set(jnp.asarray(row))
            self._hist_len = self._hist_len.at[slot].set(n + 1)
        done = self._finish_reason(st, tok)
        if done:
            return [self._complete(slot, done)]
        return []

    def _publish_chunk_blocks(self, st: _Slot, hi: int) -> None:
        """Publish every newly FULL block's chunk-namespace digest the
        moment its chunk lands (ISSUE 18 — chunked prefill used to
        publish nothing, so the hottest shared prefixes arriving
        chunked never shared).  First publisher wins: a digest another
        lane already published keeps pointing at that lane's block and
        this lane's copy stays private — re-publishing under
        last-writer-wins would orphan the other block's entry while
        both are live.  Publication happens AFTER the chunk's device
        write (the pages are materialized), so a digest can never name
        a garbage page."""
        full = min(hi // self.block_size, len(st.digests))
        for b in range(st.published_upto, full):
            if self._mgr.lookup_prefix(st.digests[b]) is None:
                self._mgr.publish_prefix(st.digests[b], st.blocks[b])
        st.published_upto = max(st.published_upto, full)

    # -- decode ------------------------------------------------------------

    def _youngest_slot(self) -> int:
        """The preemption victim: the most recently submitted live
        request — it has the least sunk prefill+decode work and the
        shortest replay."""
        return max(self._pool.active,
                   key=lambda s: self._slots[s].request.request_id)

    def _host_park_digests(self, blocks: List[int]) -> None:
        """Cold-prefix eviction edge (ISSUE 18): gather and park —
        digest-keyed — every block in ``blocks`` that is published and
        about to DIE with this release (refcount 1; blocks other
        tables still share stay HBM-resident and need no parking).
        One batched gather covers all victims; raw host wire only
        (``put_block`` refuses otherwise — a digest hit maps pages
        with no token re-check, so only a bit-exact wire may alias the
        digest namespace).  Must run BEFORE ``free_all``: it needs the
        refcounts and the pool pages intact."""
        if self._host is None or self._host.wire != "raw":
            return
        victims = []
        for blk in blocks:
            h = self._mgr.digest_of(blk)
            if h is None or self._mgr.refcount(blk) != 1:
                continue
            if self._host.has_block(h):
                continue      # already parked; content is immutable
            victims.append((h, blk))
        if not victims:
            return
        ids = [blk for _, blk in victims]
        k, v = gather_block_kv(self.cache["k"], self.cache["v"], ids)
        if "k_scale" in self.cache:
            # int8 pool: park the dequantized float pages — page-in
            # requantizes through the one insert edge, and
            # requantization idempotence makes the pool bytes match
            sk = gather_block_scales(self.cache["k_scale"], ids)
            sv = gather_block_scales(self.cache["v_scale"], ids)
            k = dequantize_kv(k, sk)
            v = dequantize_kv(v, sv)
        k = np.asarray(k)
        v = np.asarray(v)
        bs = self.block_size
        for i, (h, _blk) in enumerate(victims):
            self._host.put_block(h, k[:, i * bs:(i + 1) * bs],
                                 v[:, i * bs:(i + 1) * bs])

    def _host_park(self, slot: int, st: _Slot) -> None:
        """Page the preemption victim out to the host tier BEFORE its
        blocks are freed: dying published blocks keyed by chain digest
        (cold-prefix eviction), plus — for a decoding lane — the
        request's materialized tokens keyed by (request, token count)
        so re-admission is a page-in, not a prefill replay.  A
        mid-prefill lane has no pending token to resume behind;
        re-admission restarts its chunk stream, where the digests
        parked here let the finished chunks page back in."""
        self._host_park_digests(st.blocks)
        if st.prefilling or st.cache_len < 1:
            return
        k, v = extract_kv(
            dict(self.cache, block_tables=jnp.asarray(self._tables)),
            st.cache_len, row=slot)
        self._host.put_request(st.request.request_id, st.cache_len,
                               np.asarray(k), np.asarray(v))

    def _preempt(self, slot: int) -> None:
        """Evict one live request: park its pages in the host tier when
        one is configured (resume becomes a page-in), free its blocks
        (decref — shared prefix blocks survive under their other
        owners), park its progress on the Request, requeue it at the
        FRONT (it resumes as soon as the budget allows, replaying
        prompt+generated through the batched flash prefill if its
        parked pages were evicted), release the lane."""
        st = self._slots[slot]
        if self._host is not None:
            self._host_park(slot, st)
        self._slots[slot] = None
        self._pending[slot] = 0
        self._temps[slot] = 0.0
        self._lane_slab[slot] = 0
        self._tables[slot, :] = self.num_blocks
        self._mgr.free_all(st.blocks)
        self._pool.release(slot)
        req = st.request
        # drop the adapter pin across the requeue wait: a preempted
        # tenant must not hold a slab lane hostage while it has no
        # cache pages either (re-admission re-acquires, possibly
        # paging the adapter back in — churn the pool counters see)
        self._release_adapter(req)
        req.resume_tokens = list(st.tokens)
        # an injected handoff dies with its blocks: resume pages the
        # parked copy back in, or replays prompt+generated through the
        # LOCAL prefill path (bit-identical K/V for a raw-wire handoff,
        # so greedy parity survives)
        req.handoff = None
        req.handoff_shareable = False
        req.preemptions += 1
        req.resume_polls = st.decode_polls
        # the overhead clock: runs from here until the resume prefill
        # completes (closed out in _admit_one)
        req.preempted_t = time.perf_counter()
        self._queue.appendleft(req)
        self._preempt_count += 1
        _telemetry.counter("serving.preemptions").inc()
        _telemetry.event("serving.request.preempt",
                         id=req.request_id, tokens=len(st.tokens),
                         blocks_freed=len(st.blocks))

    def _ensure_tail_blocks(self) -> None:
        """Paged pre-decode edge: every live lane gets blocks mapped to
        cover its next write horizon NOW (the jitted step cannot
        allocate) — one token on the plain path, the pending token plus
        ``spec.k`` drafts under speculative decoding (writes past the
        table reach drop; they are beyond every budget by
        construction).  On pool exhaustion the youngest live request is
        preempted — repeatedly, until the allocation succeeds or the
        needy lane itself was evicted — instead of stalling the whole
        batch."""
        mb = self._tables.shape[1]
        for slot in list(self._pool.active):
            st = self._slots[slot]
            if st is None or st.prefilling:    # preempted this pass /
                continue                       # blocks pre-claimed
            need = min(-(-(st.cache_len + self._spec_ahead)
                         // self.block_size), mb)
            while self._slots[slot] is st and len(st.blocks) < need:
                blk = self._mgr.alloc()
                if blk is not None:
                    self._tables[slot, len(st.blocks)] = blk
                    st.blocks.append(blk)
                    self._blocks_hw = max(self._blocks_hw,
                                          self._mgr.n_in_use)
                    continue
                self._preempt(self._youngest_slot())

    def _decode_once(self) -> List[Response]:
        """One batched decode step over every lane (live ones advance,
        free ones ride along masked).  Under speculative decoding the
        step is one draft→verify→accept round and each live lane
        delivers 1..k+1 tokens — multi-token emission per poll; EOS and
        budget truncation stay host-side (a truncated lane completes
        this poll, so no continuing lane ever diverges from its device
        cache position)."""
        if self._mgr is not None:
            self._ensure_tail_blocks()
            if not self._pool.n_active:        # everything preempted
                return []
        # prefilling lanes (ISSUE 15) ride the batch masked: position
        # frozen, no emission — they join once their last chunk lands
        active = np.zeros((self.max_slots,), bool)
        for i, st in enumerate(self._slots):
            active[i] = st is not None and not st.prefilling
        if not active.any():                   # only prefilling lanes
            return []
        t0 = time.perf_counter()
        self._key, sub = jax.random.split(self._key)
        em_host = acc_host = nxt_host = None
        # LoRA / constrained-decoding operands (ISSUE 20): appended
        # ONLY when the engine was built with them, so a plain engine's
        # call avals — and its persistent compile-cache keys — never
        # change.  The lane vector and mask rows are host mirrors
        # uploaded per step (same pattern as _pending/_temps); the
        # slabs are fetched fresh each poll so an eviction between
        # polls is always visible to the next step.
        extra = ()
        if self._adapters is not None or self._masks is not None:
            extra = ((jnp.asarray(self._lane_slab),
                      self._adapters.slabs())
                     if self._adapters is not None else (None, None))
            extra += ((jnp.asarray(self._masks),)
                      if self._masks is not None else (None,))
        with compile_label("serving.decode"):
            # exactly ONE compile should ever land on this label; a
            # second is the static-shape discipline breaking
            if self._spec is not None:
                args = [self.params, self.cache]
                if self._mgr is not None:
                    args.append(jnp.asarray(self._tables))
                args += [self._history, self._hist_len,
                         jnp.asarray(self._pending),
                         jnp.asarray(self._temps),
                         jnp.asarray(active), sub]
                (em, n_acc, self.cache, self._history,
                 self._hist_len) = self._cc("decode", self._decode_fn,
                                            tuple(args) + extra)
                em_host = np.asarray(em)             # host sync
                acc_host = np.asarray(n_acc)
            elif self._mgr is not None:
                nxt, self.cache = self._cc(
                    "decode", self._decode_fn,
                    (self.params, self.cache, jnp.asarray(self._tables),
                     jnp.asarray(self._pending),
                     jnp.asarray(self._temps), jnp.asarray(active),
                     sub) + extra)
                nxt_host = np.asarray(nxt)           # host sync
            else:
                nxt, self.cache = self._cc(
                    "decode", self._decode_fn,
                    (self.params, self.cache, jnp.asarray(self._pending),
                     jnp.asarray(self._temps), jnp.asarray(active),
                     sub) + extra)
                nxt_host = np.asarray(nxt)           # host sync
        dt = time.perf_counter() - t0
        _telemetry.counter("serving.decode_steps").inc()
        self._decode_count += 1
        if self._decode_count % 64 == 0 and _telemetry.enabled():
            sample_device_memory()   # HBM creep shows on the decode cadence
        completed = []
        emitted = 0
        accepted = 0
        live = 0
        for slot, st in enumerate(self._slots):
            if st is None or st.prefilling:
                continue
            live += 1
            st.decode_polls += 1
            if self._spec is None:
                n_raw = 1
                toks = [int(nxt_host[slot])]
            else:
                n_raw = int(acc_host[slot]) + 1
                accepted += n_raw - 1
                toks = [int(t) for t in em_host[slot, :n_raw]]
            # the device wrote and committed n_raw entries; the host
            # delivers them in order, stopping at EOS / budget — a lane
            # that truncates here always completes below, so cache_len
            # only ever drifts on a lane being released anyway
            st.cache_len += n_raw
            done = None
            for tok in toks:
                st.tokens.append(tok)
                self._pending[slot] = tok
                emitted += 1
                done = self._finish_reason(st, tok)
                if done:
                    break
            if done:
                completed.append(self._complete(slot, done))
        _telemetry.counter("serving.tokens_generated").inc(emitted)
        if self._spec is not None and live:
            # the same realized counters generate(spec=...) emits, so
            # one report/dashboard path serves both entry points;
            # verify_calls counts per-sequence verify passes (the
            # amortization denominator), not batched forwards
            _telemetry.counter("generate.spec.draft_tokens").inc(
                self._spec.k * live)
            _telemetry.counter("generate.spec.accepted_tokens").inc(
                accepted)
            _telemetry.counter("generate.spec.verify_calls").inc(live)
        if dt > 0:
            _telemetry.gauge("serving.decode_tokens_per_sec").set(
                emitted / dt)
        return completed

    def _finish_reason(self, st: _Slot, tok: int) -> Optional[str]:
        eos = st.request.eos_token_id
        if eos is not None and tok == eos:
            return "eos"
        if len(st.tokens) >= st.request.max_new_tokens:
            return "length"
        return None

    def _complete(self, slot: int, reason: str) -> Response:
        st = self._slots[slot]
        self._slots[slot] = None
        self._temps[slot] = 0.0
        self._lane_slab[slot] = 0
        if self._mgr is not None:
            if self._host is not None:
                # completion is the other cold-prefix eviction edge: a
                # published block whose last sharer finishes would be
                # gone — park it digest-keyed first
                self._host_park_digests(st.blocks)
            self._tables[slot, :] = self.num_blocks
            self._mgr.free_all(st.blocks)
        self._pool.release(slot)
        req = st.request
        self._release_adapter(req)
        now = time.perf_counter()
        # -- SLO accounting (ISSUE 7): the per-request measurements,
        # their per-class sketches, and the goodput verdict ------------
        latency_ms = (now - req.submitted_t) * 1e3
        queue_wait_ms = req.queue_wait_s * 1e3
        ttft_ms = (req.first_token_t - req.submitted_t) * 1e3
        # mean inter-token interval AFTER the first token, preemption
        # stalls included — what streaming feels like.  The divisor is
        # TOKENS DELIVERED, never polls (serving/slo.py:tpot_ms):
        # under speculative decoding one poll emits several tokens and
        # the per-poll interval would overstate TPOT by the emission
        # factor.  None for a one-token response: no interval exists,
        # so no TPOT verdict.
        tpot_ms = _tpot_ms(req.first_token_t, now, len(st.tokens))
        overhead_ms = req.preempt_overhead_s * 1e3
        tags = {"slo_class": req.slo_class}
        _telemetry.sketch("serving.queue_wait_ms", tags).observe(
            queue_wait_ms)
        _telemetry.sketch("serving.ttft_ms", tags).observe(ttft_ms)
        if tpot_ms is not None:
            _telemetry.sketch("serving.tpot_ms", tags).observe(tpot_ms)
        _telemetry.sketch("serving.e2e_ms", tags).observe(latency_ms)
        if req.preemptions:
            # only preempted requests land here: the sketch answers
            # "what does a preemption cost when it happens", not a
            # zero-diluted average over the whole fleet
            _telemetry.sketch("serving.preempt_overhead_ms",
                              tags).observe(overhead_ms)
        met = _judge_slo(self._slo_targets.get(req.slo_class),
                         ttft_ms, tpot_ms)
        _telemetry.counter(
            "serving.goodput.met" if met else "serving.goodput.missed",
            tags).inc()
        reg = _telemetry.registry()
        if reg is not None and reg.detectors is not None:
            reg.detectors.feed_slo(req.slo_class, met)
        _telemetry.histogram("serving.request_ms").observe(
            latency_ms, rid=req.request_id, finish_reason=reason,
            tokens=len(st.tokens))
        end_data = dict(
            id=req.request_id, finish_reason=reason,
            tokens=len(st.tokens),
            latency_ms=round(latency_ms, 3),
            slo_class=req.slo_class,
            queue_wait_ms=round(queue_wait_ms, 3),
            ttft_ms=round(ttft_ms, 3),
            preemptions=req.preemptions,
            preempt_overhead_ms=round(overhead_ms, 3),
            slo_met=met)
        if tpot_ms is not None:
            # a one-token response HAS no TPOT — omitting the key (not
            # stamping 0.0) keeps trace-side reconstructions from
            # counting a fake 0 ms interval into their percentiles
            end_data["tpot_ms"] = round(tpot_ms, 4)
        _telemetry.event("serving.request.end", **end_data)
        return Response(
            request_id=req.request_id,
            prompt=req.prompt,
            tokens=np.asarray(st.tokens, np.int32),
            finish_reason=reason,
            prefill_ms=st.prefill_ms,
            # the engine polls this request was live for (accumulated
            # across preempt→resume).  Without spec this equals
            # len(tokens) - 1 - preemptions (every admission samples
            # one prefill token, every poll adds one); with spec on,
            # polls < tokens - 1 is exactly the amortization win and
            # the two stay coherent via tokens = 1 + preemptions +
            # sum(per-poll emissions)
            decode_steps=st.decode_polls,
            slo_class=req.slo_class,
            queue_wait_ms=queue_wait_ms,
            ttft_ms=ttft_ms,
            tpot_ms=tpot_ms or 0.0,
            e2e_ms=latency_ms,
            preemptions=req.preemptions,
            preempt_overhead_ms=overhead_ms,
            slo_met=met,
        )


# -- jitted pieces ----------------------------------------------------------


def _mixed_sample(logits, temps, key, token_mask=None, *,
                  top_k, top_p, vocab_limit):
    """Per-row temperature sampling: greedy rows (temp == 0) take the
    argmax, the rest sample at temperature 1 over pre-scaled logits —
    one traced [b] vector, no recompile per request mix.

    ``token_mask`` (constrained decoding, ISSUE 20 satellite) is a
    boolean allow-mask ([vocab] or [b, vocab]) applied BEFORE the
    temperature/top-k/top-p chain, so greedy and sampled rows see the
    same restricted support.  It is a POSITIONAL arg (default None =
    no extra traced operand) so unconstrained engines keep their
    existing call avals and compile-cache keys."""
    logits = apply_token_mask(logits, token_mask)
    greedy = sample_logits(logits, key, temperature=0.0,
                           vocab_limit=vocab_limit)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = sample_logits(scaled, key, temperature=1.0, top_k=top_k,
                            top_p=top_p, vocab_limit=vocab_limit)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _make_sample_fn(top_k, top_p, vocab_limit):
    return jax.jit(functools.partial(
        _mixed_sample, top_k=top_k, top_p=top_p, vocab_limit=vocab_limit))


@functools.lru_cache(maxsize=None)
def _make_decode_fn(cfg, top_k, top_p, vocab_limit, paged, spec=None,
                    decode_fused: str = "reference"):
    """One compiled decode+sample step for the engine's lifetime —
    memoized on the static knobs so engines sharing a config (tests,
    multi-engine processes) share the XLA compile too.

    The cache is donated: the slot/pool buffers are updated in place on
    device rather than copied per token (on CPU test platforms the
    donation degrades to a copy with a one-time warning).  Paged
    engines pass the block tables SEPARATELY (not donated): the host
    mutates its table mirror between steps (tail allocation,
    preemption), so a fresh device copy rides in each step while the
    big pool stays put.

    With ``spec`` set the step is one speculative round
    (``models.speculative.spec_round``): draft from the lanes' token
    history, verify k+1 tokens in one forward, return the candidate
    emission matrix + accepted counts; live lanes commit
    ``pos += n_acc + 1`` (the pending token and the accepted drafts),
    frozen lanes keep their position and — paged — their sentinel
    table rows, so a parked lane can never corrupt live blocks."""

    if spec is not None:
        def _spec_step(params, cache, tables, history, hist_lens,
                       tokens, temps, active, key,
                       lane=None, slabs=None, masks=None):
            prev_pos = cache["pos"]
            full = cache if tables is None else dict(
                cache, block_tables=tables)
            lora = (None if lane is None
                    else {"idx": lane, "slabs": slabs})
            em, n_acc, _y, new, _prev = spec_round(
                params, cfg, full, tokens, history, hist_lens, key,
                spec=spec, temperature=temps, top_k=top_k, top_p=top_p,
                vocab_limit=vocab_limit, token_mask=masks, lora=lora)
            n_raw = n_acc + 1
            # key-generic rebuild: an int8 pool carries k_scale/v_scale
            # alongside k/v — whatever the layout stores rides through
            cache = {kk: vv for kk, vv in new.items()
                     if kk not in ("pos", "block_tables")}
            cache["pos"] = jnp.where(active, prev_pos + n_raw, prev_pos)
            # device-side history append: this poll's delivered tokens
            # scatter in at each live lane's length (frozen lanes and
            # past-the-buffer columns drop) — the steady-state poll
            # never re-uploads the haystack from the host
            b, max_len = history.shape
            k1 = em.shape[1]
            cols = hist_lens[:, None] + jnp.arange(k1,
                                                   dtype=jnp.int32)[None]
            keep = ((jnp.arange(k1)[None] < n_raw[:, None])
                    & active[:, None])
            cols = jnp.where(keep, cols, max_len)
            history = history.at[jnp.arange(b)[:, None], cols].set(
                em, mode="drop")
            hist_lens = jnp.where(
                active, jnp.minimum(hist_lens + n_raw, max_len),
                hist_lens)
            return em, n_acc, cache, history, hist_lens

        if paged:
            @functools.partial(jax.jit, donate_argnames=(
                "cache", "history", "hist_lens"))
            def step_fn(params, cache, tables, history, hist_lens,
                        tokens, temps, active, key,
                        lane=None, slabs=None, masks=None):
                return _spec_step(params, cache, tables, history,
                                  hist_lens, tokens, temps, active, key,
                                  lane, slabs, masks)

            return step_fn

        @functools.partial(jax.jit, donate_argnames=(
            "cache", "history", "hist_lens"))
        def step_fn(params, cache, history, hist_lens, tokens, temps,
                    active, key, lane=None, slabs=None, masks=None):
            return _spec_step(params, cache, None, history, hist_lens,
                              tokens, temps, active, key,
                              lane, slabs, masks)

        return step_fn

    if paged:
        @functools.partial(jax.jit, donate_argnames=("cache",))
        def step_fn(params, cache, tables, tokens, temps, active, key,
                    lane=None, slabs=None, masks=None):
            prev_pos = cache["pos"]
            logits, new = decode_step(
                params, tokens, dict(cache, block_tables=tables), cfg,
                decode_fused=decode_fused,
                lora=(None if lane is None
                      else {"idx": lane, "slabs": slabs}))
            # free lanes ride along: frozen position + sentinel table
            # rows (writes drop), so they can't corrupt live blocks.
            # Key-generic rebuild so the int8 pool's scale arrays ride
            # through the donation untouched.
            cache = {kk: vv for kk, vv in new.items()
                     if kk not in ("pos", "block_tables")}
            cache["pos"] = jnp.where(active, new["pos"], prev_pos)
            nxt = _mixed_sample(logits, temps, key, masks, top_k=top_k,
                                top_p=top_p, vocab_limit=vocab_limit)
            return nxt, cache

        return step_fn

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def step_fn(params, cache, tokens, temps, active, key,
                lane=None, slabs=None, masks=None):
        prev_pos = cache["pos"]
        logits, cache = decode_step(params, tokens, cache, cfg,
                                    decode_fused=decode_fused,
                                    lora=(None if lane is None
                                          else {"idx": lane,
                                                "slabs": slabs}))
        # free slots ride along; freezing their position keeps their
        # lane from walking off the cache during long droughts
        cache = dict(cache, pos=jnp.where(active, cache["pos"], prev_pos))
        nxt = _mixed_sample(logits, temps, key, masks, top_k=top_k,
                            top_p=top_p, vocab_limit=vocab_limit)
        return nxt, cache

    return step_fn


@functools.lru_cache(maxsize=None)
def _make_chunk_fn(cfg, paged):
    """One compiled chunked-prefill step (ISSUE 15), memoized on the
    static knobs like :func:`_make_decode_fn`.  The chunk ``[m]``
    appends at ``pos`` of lane ``slot`` and attends to the lane's
    already-written KV prefix plus itself causally — the verification
    forward (:func:`~apex_tpu.models.generate.decode_verify`) run
    b=1 against the engine's cache, which reuses the existing write
    edges in both layouts (paged: the table scatter, int8 scale cells
    included; contiguous: the stripe scatter).  The engine pins the
    chunk shape to ONE bucket (``chunk_tokens``, tail chunks padded),
    so this is exactly one compile per engine lifetime.

    ``new_pos`` is the host-known progress after this chunk (the real
    token count, excluding tail padding): the lane's device position is
    stamped here so the masked decode step, the dashboard, and the
    eventual decode transition all see a consistent cache."""

    if paged:
        @functools.partial(jax.jit, donate_argnames=("cache",))
        def chunk_fn(params, cache, table_row, chunk, pos, new_pos,
                     slot):
            sub = {kk: vv for kk, vv in cache.items() if kk != "pos"}
            sub["pos"] = pos[None]
            sub["block_tables"] = table_row[None]
            logits, new = decode_verify(params, chunk[None], sub, cfg)
            out = {kk: vv for kk, vv in new.items()
                   if kk not in ("pos", "block_tables")}
            out["pos"] = cache["pos"].at[slot].set(new_pos)
            return logits, out

        return chunk_fn

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def chunk_fn(params, cache, chunk, pos, new_pos, slot):
        k_row = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
        v_row = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)
        sub = {"k": k_row, "v": v_row, "pos": pos[None]}
        logits, new = decode_verify(params, chunk[None], sub, cfg)
        return logits, {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], new["k"], slot, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], new["v"], slot, axis=1),
            "pos": cache["pos"].at[slot].set(new_pos),
        }

    return chunk_fn


@functools.lru_cache(maxsize=None)
def _make_lora_prefill_fn(cfg, paged):
    """One compiled LoRA prefill (ISSUE 20), memoized like
    :func:`_make_chunk_fn`.  The whole bucket-padded prompt runs as a
    single b=1 verification forward at position 0 with the request's
    adapter delta folded in — :func:`~apex_tpu.models.generate.
    decode_verify` is the one forward that threads the ragged-grouped-
    matmul delta, so adapter prefill reuses its machinery instead of
    growing a second flash-prefill variant.  The cluster prefill
    worker runs the SAME traced family, which is what makes a raw-wire
    adapter handoff continue bit-exactly on the decode worker."""

    if paged:
        @functools.partial(jax.jit, donate_argnames=("cache",))
        def lora_prefill_fn(params, cache, prompt, n, slot, lane,
                            slabs, table_row):
            sub = {kk: vv for kk, vv in cache.items() if kk != "pos"}
            sub["pos"] = jnp.zeros((1,), jnp.int32)
            sub["block_tables"] = table_row[None]
            logits, new = decode_verify(
                params, prompt, sub, cfg,
                lora={"idx": lane, "slabs": slabs})
            out = {kk: vv for kk, vv in new.items()
                   if kk not in ("pos", "block_tables")}
            out["pos"] = cache["pos"].at[slot].set(n)
            return logits, out

        return lora_prefill_fn

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def lora_prefill_fn(params, cache, prompt, n, slot, lane, slabs):
        k_row = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
        v_row = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)
        sub = {"k": k_row, "v": v_row,
               "pos": jnp.zeros((1,), jnp.int32)}
        logits, new = decode_verify(
            params, prompt, sub, cfg,
            lora={"idx": lane, "slabs": slabs})
        return logits, {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], new["k"], slot, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], new["v"], slot, axis=1),
            "pos": cache["pos"].at[slot].set(n),
        }

    return lora_prefill_fn


@functools.partial(jax.jit, donate_argnames=("cache",))
def _insert_slot(cache, ks, vs, slot, length):
    """Scatter a bucket-sized prefill cache [L, 1, S, g, dh] into row
    ``slot`` of the big cache and set its position counter.  The big
    cache is donated — admission updates the slot row in place instead
    of copying the whole multi-slot buffer per request."""
    k = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype),
        (jnp.int32(0), slot, jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    v = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype),
        (jnp.int32(0), slot, jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    pos = cache["pos"].at[slot].set(length)
    return {"k": k, "v": v, "pos": pos}
