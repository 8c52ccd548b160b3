"""Round-5 crossover sweeps — the measurements the round-4 sweep left
open (none of it measured on today's code):

- flash fused-vs-split backward at s1024: the s512 sweep showed every
  fused q-block beating the split pair; FUSED_MAX (the ``auto``
  crossover) needs the next seqlen class measured before it moves.
- flash fwd s512 re-measure at larger chained iteration counts: the
  ledger run produced a zero slope for the XLA side (noise swamped the
  64/256/1024 points at this small shape), which rendered the ratio
  meaningless.

Usage:  python tools/sweep_r5.py [--json f]     (needs a TPU)
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench_kernels import chain_fwd, chain_grad
from tools.sweep_r4 import _knobs, _report


def sweep_flash_crossover(results):
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    print("flash s1024 bwd: split vs fused single-pass", flush=True)
    rng = np.random.RandomState(0)
    b, s, h, d = 16, 1024, 12, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    for causal in (True, False):
        tag = f"b{b}xs{s}{'_causal' if causal else ''}"
        ref = functools.partial(mha_reference, causal=causal)
        xla = chain_grad(ref, (0, 1, 2), q, k, v, inner=(8, 24, 80))
        fa = functools.partial(flash_attention, causal=causal)
        for mode, bq in (("split", 0), ("fused", 256), ("fused", 512),
                         ("fused", 1024)):
            with _knobs(APEX_TPU_FLASH_BWD=mode,
                        APEX_TPU_FLASH_FUSED_BQ=bq or None):
                try:
                    got = chain_grad(fa, (0, 1, 2), q, k, v,
                                     inner=(8, 24, 80))
                except Exception as e:
                    print(f"  {mode}_bq{bq}: {type(e).__name__}: "
                          f"{e}"[:120], flush=True)
                    continue
            label = mode if mode == "split" else f"{mode}_bq{bq}"
            _report(results, f"flash_fwdbwd_{tag}_{label}",
                    f"fwd+bwd {tag} {label}", got, xla)


def sweep_flash_fwd_s512(results):
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    print("flash fwd s512: re-measure at larger inner counts", flush=True)
    rng = np.random.RandomState(0)
    b, s, h, d = 8, 512, 12, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    for causal in (True, False):
        tag = f"b{b}xs{s}{'_causal' if causal else ''}"
        fa = functools.partial(flash_attention, causal=causal)
        ref = functools.partial(mha_reference, causal=causal)
        got = chain_fwd(fa, q, k, v, inner=(256, 1024, 4096))
        xla = chain_fwd(ref, q, k, v, inner=(256, 1024, 4096))
        _report(results, f"flash_fwd_{tag}_remeasure",
                f"fwd {tag} (remeasured)", got, xla)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: crossover,fwd512")
    args = ap.parse_args()
    print(f"devices: {jax.devices()}", flush=True)
    results = {}
    sweeps = {"crossover": sweep_flash_crossover,
              "fwd512": sweep_flash_fwd_s512}
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
