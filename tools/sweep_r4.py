"""Round-4 on-chip kernel sweeps — the four kernels that lost to XLA
in the round-3 per-kernel ledger, measured with the same
chained-fori_loop methodology as bench_kernels.py.

Each knob is read at trace time, so one process sweeps every variant:

- flash s512 fwd+bwd: split (round-3 default) vs the new fused
  single-pass backward (``APEX_TPU_FLASH_BWD``) x fused q-block size
  (``APEX_TPU_FLASH_FUSED_BQ`` 128/256/512);
- flat Adam 88M: decided round 5 (kernel deleted — see the tombstone
  note at sweep_flat_adam's former site);
- LN bwd 16384x768 bf16: the revisit-accumulator kernel
  (``APEX_TPU_LN_BWD=pallas``, the round-5 default — it wins on chip)
  vs the XLA composition (``=xla``); the round-4 per-block-partials
  variant was deleted in round 5 (Mosaic rejects its block spec);
- softmax causal 512^2: confirms the grad path now routes to XLA
  (expected ratio ~1.0) while fwd-only keeps the Pallas win.

Usage:  python tools/sweep_r4.py [--json f]     (needs a TPU)
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench_kernels import _fmt, chain_fwd, chain_grad


def _report(results, key, name, pallas_s, xla_s):
    results[key] = _fmt(name, pallas_s, xla_s)


@contextlib.contextmanager
def _knobs(**env):
    """Set APEX_TPU_* sweep knobs, restoring prior values even when a
    variant raises — a mid-sweep exception must not leak a knob into the
    later sweeps of the same process (ADVICE r4)."""
    saved = {k: os.environ.get(k) for k in env}
    try:
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        yield
    finally:
        for k, old in saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


def sweep_flash_s512(results):
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    print("flash s512 bwd: split vs fused single-pass", flush=True)
    rng = np.random.RandomState(0)
    b, s, h, d = 8, 512, 12, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    for causal in (True, False):
        tag = f"b{b}xs{s}{'_causal' if causal else ''}"
        ref = functools.partial(mha_reference, causal=causal)
        xla = chain_grad(ref, (0, 1, 2), q, k, v, inner=(16, 48, 160))
        fa = functools.partial(flash_attention, causal=causal)
        for mode, bq in (("split", 0), ("fused", 128), ("fused", 256),
                         ("fused", 512)):
            with _knobs(APEX_TPU_FLASH_BWD=mode,
                        APEX_TPU_FLASH_FUSED_BQ=bq or None):
                got = chain_grad(fa, (0, 1, 2), q, k, v,
                                 inner=(16, 48, 160))
            label = mode if mode == "split" else f"{mode}_bq{bq}"
            _report(results, f"flash_fwdbwd_{tag}_{label}",
                    f"fwd+bwd {tag} {label}", got, xla)


# (sweep_flat_adam was removed in round 5: the decision it existed to
# make fired on first chip contact — rows=512 → 1.82x, rows=1024 →
# 1.85x the XLA fused update, rows≥2048 failed to compile — so the
# Pallas flat kernel and APEX_TPU_ADAM_BLOCK_ROWS were deleted and the
# optimizers keep the XLA flat path.  bench_kernels.py's adam row now
# tracks the XLA update's absolute time.)


def sweep_ln_bwd(results):
    from apex_tpu.ops.layer_norm import fused_layer_norm, layer_norm_ref

    print("LN fwd+bwd 16384x768 bf16: Pallas bwd vs XLA bwd", flush=True)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16384, 768), jnp.bfloat16)
    w = jnp.ones((768,), jnp.float32)
    b = jnp.zeros((768,), jnp.float32)
    ln = lambda x, w, b: fused_layer_norm(x, w, b)
    ref = lambda x, w, b: layer_norm_ref(x, w, b)
    xla_chain = chain_grad(ref, (0, 1, 2), x, w, b)
    for mode in ("pallas", "xla"):
        with _knobs(APEX_TPU_LN_BWD=mode):
            got = chain_grad(ln, (0, 1, 2), x, w, b)
        tag = mode
        _report(results, f"ln_fwdbwd_{tag}", f"LN fwd+bwd {tag}",
                got, xla_chain)


def sweep_softmax(results):
    from apex_tpu.ops import softmax as sm

    print("softmax causal 512^2: grad path now XLA-routed", flush=True)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 16, 512, 512), jnp.bfloat16)
    op = lambda x: sm.scaled_upper_triang_masked_softmax(x, 0.125)
    ref = lambda x: sm._softmax_fwd_ref(x, 0.125, None, True)
    _report(results, "softmax_causal_fwd_512", "causal fwd 512^2",
            chain_fwd(op, x), chain_fwd(ref, x))
    _report(results, "softmax_causal_fwdbwd_512", "causal fwd+bwd 512^2",
            chain_grad(op, (0,), x), chain_grad(ref, (0,), x))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: flash,adam,ln,softmax")
    args = ap.parse_args()
    print(f"devices: {jax.devices()}", flush=True)
    results = {}
    sweeps = {"flash": sweep_flash_s512,
              "ln": sweep_ln_bwd, "softmax": sweep_softmax}
    only = set(args.only.split(",")) if args.only else set(sweeps)
    for name, fn in sweeps.items():
        if name in only:
            fn(results)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    print(json.dumps(
        {k: v["pallas_over_xla"] for k, v in results.items()}))


if __name__ == "__main__":
    main()
