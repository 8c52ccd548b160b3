"""Fused sampling — logits → temperature → top-k/top-p → sample, one op.

The decode hot path pays a chain of separate sampling ops per token
(temperature scale → ``lax.top_k``/sort → cumulative-sum nucleus mask →
``jax.random.categorical``), each a full ``[b, vocab]`` HBM round trip.
Following "LLM Inference Acceleration via Efficient Operation Fusion"
(PAPERS.md, ROADMAP item 2), :func:`fused_sample` collapses the chain
into ONE kernel over ``[b, vocab]``: each grid step owns a row, applies
the vocab limit, scales by that row's temperature, resolves the top-k
and nucleus cutoffs by in-register bisection (no sort, no materialized
sorted copy), and draws the token by Gumbel-max over the filtered
logits — the row is read from HBM once and the only write is one token
id.

Two execution paths, routed like ``flash_attention`` /
``paged_attention``:

- **reference** (always available, the numerics oracle): the exact
  ``sample_logits`` op sequence — *bit-identical* to the historical
  sampler given the same PRNG key, which is what lets
  ``models.generate.sample_logits`` become a thin wrapper without
  perturbing any seeded test;
- **kernel**: the fused Pallas kernel.  Its filter cutoffs converge to
  the same values (bisection over row values is exact at fp32
  resolution), but the Gumbel draw uses an in-kernel counter-based
  generator (seeded from the caller's key), so kernel-path parity is
  *distributional* (χ² in tests/test_fused_sampling.py) while greedy
  rows are exact.

``backend=None`` picks the kernel on TPU or under
``APEX_TPU_PALLAS_INTERPRET=1`` (the 8-virtual-device CI path) and the
reference elsewhere (``_pallas_utils.resolve_backend``);
``"kernel"``/``"reference"`` pin, anything else raises.

``temperature`` may be a per-sequence ``[b]`` vector (traced — the
serving engine's mixed-temperature contract): rows at temperature 0
take the argmax, the rest sample at temperature 1 over their pre-scaled
logits, exactly the engine's historical ``_mixed_sample`` composition.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas_utils import LANES as _LANES
from apex_tpu.ops._pallas_utils import on_tpu, resolve_backend

__all__ = ["fused_sample", "filter_logits", "sample_reference",
           "apply_token_mask"]

_NEG_INF = -1e30
# bisection trip count: each iteration halves the value interval, so 64
# collapses any fp32 row range below one ulp — the cutoff the loop
# converges to IS the row's k-th value / nucleus boundary exactly
_BISECT_ITERS = 64


def filter_logits(logits, *, top_k: Optional[int] = None,
                  top_p: Optional[float] = None):
    """Apply the top-k / nucleus cutoffs to ``logits`` ``[b, v]``
    (already temperature-scaled), returning filtered logits with
    dropped tokens at ``-1e30`` — the exact op sequence the historical
    ``sample_logits`` used, factored out so the fused reference path,
    the thin ``sample_logits`` wrapper, and speculative decoding's
    rejection-sampling distributions all share ONE implementation.

    Without ``top_p`` the top-k cutoff uses ``jax.lax.top_k``
    (O(v·log k)) instead of a full descending sort; the single-sort
    path survives only where the nucleus mass genuinely needs the
    sorted cumulative sum."""
    if top_p is None:
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits < kth, _NEG_INF, logits)
        return logits
    # one descending sort serves both cutoffs (the nucleus mass below
    # needs the sorted cumulative sum anyway)
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k is not None:
        kth = sorted_l[:, top_k - 1][:, None]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
        # reflect the cutoff in sorted space so the nucleus mass
        # below is computed over the top_k-filtered distribution
        rank = jnp.arange(sorted_l.shape[-1])[None]
        sorted_l = jnp.where(rank >= top_k, _NEG_INF, sorted_l)
    # nucleus: drop tokens outside the smallest prob-sorted prefix
    # reaching mass top_p; n_keep clamps to 1 so the head token always
    # stays (top_p<=0 means near-greedy, not a silent no-op)
    probs = jax.nn.softmax(sorted_l, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (csum - probs) < top_p
    n_keep = jnp.maximum(jnp.sum(keep_sorted, axis=-1), 1)
    cutoff = jnp.take_along_axis(sorted_l, (n_keep - 1)[:, None], axis=-1)
    return jnp.where(logits < cutoff, _NEG_INF, logits)


def _mask_vocab(logits, vocab_limit):
    if vocab_limit is None:
        return logits
    over = jnp.arange(logits.shape[-1]) >= vocab_limit
    return jnp.where(over[None], _NEG_INF, logits)


def apply_token_mask(logits, token_mask):
    """Constrained decoding (ISSUE 20): zero out disallowed tokens
    BEFORE any temperature/top-k/top-p work.  ``token_mask`` is a bool
    ``[v]`` (one constraint for the whole batch) or ``[b, v]``
    (per-row, the serving engine's per-request JSON-mode masks), True =
    allowed.  Masking ahead of the filters is what keeps the filtered
    distribution a proper renormalization of the allowed set — masking
    after top-k could leave fewer than k live tokens of the ALLOWED
    set and silently sharpen the draw."""
    if token_mask is None:
        return logits
    mask = token_mask
    if mask.ndim == 1:
        mask = mask[None]
    return jnp.where(mask, logits, _NEG_INF)


def sample_reference(logits, key, *, temperature=0.0,
                     top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     vocab_limit: Optional[int] = None,
                     token_mask=None):
    """The XLA composition (numerics oracle): bit-identical to the
    historical ``sample_logits`` for a scalar ``temperature`` and to
    the serving engine's mixed-temperature sampler for a ``[b]``
    vector, given the same key (and, with ``token_mask=None``, to the
    pre-constrained-decoding sampler exactly)."""
    logits = apply_token_mask(_mask_vocab(logits, vocab_limit),
                              token_mask)
    if not (hasattr(temperature, "ndim") and temperature.ndim):
        # static scalar: greedy short-circuits ALL filtering work — the
        # cutoffs cannot change the argmax (tests pin the equivalence)
        if float(temperature) == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = filter_logits(logits / float(temperature),
                               top_k=top_k, top_p=top_p)
        return jax.random.categorical(key, scaled).astype(jnp.int32)
    # per-sequence [b] temperatures (traced): greedy rows take the
    # argmax, the rest sample at temperature 1 over pre-scaled logits —
    # one traced vector, no recompile per request mix
    temps = temperature.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = filter_logits(logits / jnp.maximum(temps, 1e-6)[:, None],
                           top_k=top_k, top_p=top_p)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused Pallas kernel.
# ---------------------------------------------------------------------------


def _uniform_bits(col_u32, row, s0, s1):
    """Counter-based per-(row, column) uniform draw in (0, 1): a
    murmur3-style finalizer over (column, row, key words).  Chosen over
    ``pltpu.prng_*`` because it lowers identically on hardware AND the
    interpret path (the CI route), and it is a pure function of the
    caller's PRNG key — same key, same draw."""
    x = col_u32 ^ (s0 + row.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    x = x + s1
    x = x * jnp.uint32(0x27D4EB2F)
    x = x ^ (x >> 15)
    # 24 high bits -> exact multiples of 2^-24 in [0, 1 - 2^-24] (every
    # such multiple is fp32-representable, so u can never round UP to
    # 1.0 and blow the double log into +inf); clamp the bottom so it
    # never sees exactly 0 either
    # (the 24 bits go through int32: Mosaic has no uint32 -> f32 cast)
    u24 = jax.lax.bitcast_convert_type(x >> 8, jnp.int32)
    u = u24.astype(jnp.float32) * (1.0 / (1 << 24))
    return jnp.maximum(u, 1.0 / (1 << 24))


def _sampling_kernel(top_k, top_p, n_valid, *refs):
    """Grid (b,): one row per step.  The row is read once; the filters
    resolve their cutoffs by value-space bisection (64 halvings of the
    row's own range collapse below one fp32 ulp, so the converged bound
    IS the k-th value / nucleus boundary), and the draw is Gumbel-max —
    no sort, no second HBM pass, one int32 out."""
    seed_ref, temp_ref, x_ref, o_ref = refs
    i = pl.program_id(0)
    x = x_ref[0].astype(jnp.float32)                      # (1, V)
    V = x.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, V), 1)
    valid = col < n_valid          # vocab limit + lane padding together
    x = jnp.where(valid, x, _NEG_INF)

    # greedy argmax (also the nucleus filter's forced-keep head token)
    m = jnp.max(x)
    greedy = jnp.min(jnp.where((x == m) & valid, col, V))

    temp = temp_ref[i]
    y = jnp.where(valid, x / jnp.maximum(temp, 1e-6), _NEG_INF)

    if top_k is not None and top_k < n_valid:
        # k-th largest by bisection: the largest t with
        # count(y >= t) >= k is exactly the k-th value.  The range must
        # span only LIVE entries (the nucleus branch's discipline): a
        # token mask leaves -1e30 holes inside the vocab window, and a
        # range that wide turns 64 halvings into a useless resolution —
        # the cutoff would never resolve between finite logits and the
        # filter silently keeps the whole allowed set
        hi0 = jnp.max(y)
        lo0 = jnp.min(jnp.where(y > _NEG_INF / 2, y, hi0))

        def kth_body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            cnt = jnp.sum((y >= mid).astype(jnp.int32))
            ok = cnt >= top_k
            return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

        kth, _ = jax.lax.fori_loop(0, _BISECT_ITERS, kth_body, (lo0, hi0))
        y = jnp.where(y < kth, _NEG_INF, y)

    if top_p is not None:
        # nucleus boundary by bisection on UNNORMALIZED mass: drop v
        # iff the mass strictly above it reaches top_p — the same keep
        # set as the sorted-prefix form (ties at the cutoff included)
        m2 = jnp.max(y)
        live = y > _NEG_INF / 2
        e = jnp.where(live, jnp.exp(y - m2), 0.0)
        target = jnp.float32(top_p) * jnp.sum(e)
        # the bisection range must span only LIVE entries: a prior
        # top-k filter left -1e30 holes inside the vocab window, and a
        # range that wide turns 64 halvings into a useless resolution
        lo0 = jnp.min(jnp.where(live, y, m2)) - 1.0

        def nuc_body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            mass = jnp.sum(jnp.where(y > mid, e, 0.0))
            ok = mass >= target
            return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

        theta, _ = jax.lax.fori_loop(0, _BISECT_ITERS, nuc_body, (lo0, m2))
        y = jnp.where((y > theta) | (col == greedy), y, _NEG_INF)

    u = _uniform_bits(col.astype(jnp.uint32), i,
                      seed_ref[0].astype(jnp.uint32),
                      seed_ref[1].astype(jnp.uint32))
    z = y + (-jnp.log(-jnp.log(u)))                       # Gumbel-max
    ms = jnp.max(z)
    sampled = jnp.min(jnp.where(z == ms, col, V))
    out = jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)
    o_ref[0] = jnp.full((1, _LANES), out, jnp.int32)


def _key_words(key) -> jax.Array:
    """Two int32 words from a PRNG key (typed or raw uint32 pair)."""
    data = key
    if not jnp.issubdtype(jnp.result_type(key), jnp.integer):
        data = jax.random.key_data(key)
    data = data.reshape(-1)
    words = jnp.stack([data[0], data[-1]]).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _fused_pallas(logits, key, temps, top_k, top_p, vocab_limit,
                  interpret):
    b, v = logits.shape
    n_valid = v if vocab_limit is None else min(int(vocab_limit), v)
    pad = (-v) % _LANES
    if pad:
        logits = jnp.pad(logits, ((0, 0), (0, pad)),
                         constant_values=_NEG_INF)
    top_k = None if top_k is None else min(int(top_k), n_valid)
    call = pl.pallas_call(
        functools.partial(_sampling_kernel, top_k, top_p, n_valid),
        grid_spec=_grid_spec(b, logits.shape[1]),
        out_shape=jax.ShapeDtypeStruct((b, 1, _LANES), jnp.int32),
        interpret=interpret,
    )
    out = call(_key_words(key), temps.astype(jnp.float32),
               logits[:, None, :])
    return out[:, 0, 0]


def _grid_spec(b, v_padded):
    from jax.experimental.pallas import tpu as pltpu

    # rows ride a unit MIDDLE axis ([b, 1, v]): Mosaic only takes a
    # unit second-last block dim when it equals the array's
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec(
            (1, 1, v_padded), lambda i, seed_ref, temp_ref: (i, 0, 0))],
        out_specs=pl.BlockSpec(
            (1, 1, _LANES), lambda i, seed_ref, temp_ref: (i, 0, 0)),
    )


def fused_sample(
    logits: jax.Array,
    key: jax.Array,
    *,
    temperature=0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    vocab_limit: Optional[int] = None,
    token_mask=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Sample next tokens ``[b]`` from ``logits`` ``[b, v]`` with the
    whole temperature → top-k → top-p → draw chain fused into one op.

    ``temperature``: a static float (0 = greedy, every filter skipped —
    the cutoffs cannot change the argmax) or a traced ``[b]`` vector of
    per-sequence temperatures (rows at 0 are greedy).  ``top_k`` /
    ``top_p`` / ``vocab_limit`` are static.  ``backend``: ``None``
    routes automatically (fused Pallas kernel on TPU or under
    ``APEX_TPU_PALLAS_INTERPRET=1``; XLA reference otherwise),
    ``"kernel"`` / ``"reference"`` pin a path — the parity suite
    compares the two.

    Distribution contract: the reference path is bit-identical to the
    historical ``sample_logits`` given the same key; the kernel path
    selects the same support (greedy rows exactly) but draws through an
    in-kernel counter-based generator, so its parity is distributional
    (χ² — tests/test_fused_sampling.py).

    ``token_mask``: optional bool ``[v]`` / ``[b, v]`` allowed-token
    mask (constrained decoding, e.g. a JSON-mode token set), applied
    before every filter on BOTH paths — the kernel sees pre-masked
    logits, so its bisection cutoffs resolve over the allowed set."""
    if top_k is not None and top_k < 1:
        raise ValueError(
            f"top_k={top_k}: pass None (not 0) to disable the cutoff")
    logits = apply_token_mask(logits, token_mask)
    static_temp = not (hasattr(temperature, "ndim")
                      and getattr(temperature, "ndim", 0))
    if static_temp and float(temperature) < 0:
        raise ValueError(
            f"temperature={temperature}: negative temperatures would "
            "silently invert the distribution; pass 0 for greedy or a "
            "positive value")
    if resolve_backend("fused sampling", backend) == "reference":
        return sample_reference(logits, key, temperature=temperature,
                                top_k=top_k, top_p=top_p,
                                vocab_limit=vocab_limit)
    if static_temp and float(temperature) == 0.0:
        # pure argmax — not worth a kernel launch, and it keeps greedy
        # bit-identical across every backend
        return jnp.argmax(_mask_vocab(logits, vocab_limit),
                          axis=-1).astype(jnp.int32)
    temps = (jnp.full((logits.shape[0],), float(temperature), jnp.float32)
             if static_temp else temperature.astype(jnp.float32))
    return _fused_pallas(logits, key, temps, top_k, top_p, vocab_limit,
                         interpret=not on_tpu())
