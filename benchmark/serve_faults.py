"""Faults a served token can have, planted in the program, for the checks
that show the serve entry's ``correct`` catches them
(``tests/test_check_serve.py`` at a toy size, ``calibrate_serve.py`` at the
cell's).  ``token_altered`` wraps the engine's compiled step
(``_make_decode_fn``'s result, ``(nxt, cache) = step(...)``); the others
wrap ``models.generate.decode_step``, the one decode step that both the
engine's step and the check's reading of the program's log-probabilities
call.  ``plant`` makes every engine and every such reading built inside it
go through the fault.
"""

from __future__ import annotations

import contextlib
import importlib

import jax.numpy as jnp


def token_altered(step, vocab):
    """A token altered where it is produced."""
    def broken(*args):
        nxt, cache = step(*args)
        return (nxt + 1) % vocab, cache
    return broken


def kv_not_written(decode_step, layers=slice(None)):
    """The step returns its K/V state unchanged."""
    def broken(params, token, cache, cfg, **kw):
        logits, new = decode_step(params, token, cache, cfg, **kw)
        return logits, dict(new, **{k: new[k].at[layers].set(
            cache[k][layers]) for k in ("k", "v", "k_scale", "v_scale")
            if k in cache})
    return broken


def layer_kv_not_written(decode_step):
    """One layer's K/V not written."""
    return kv_not_written(decode_step, layers=0)


def position_off_by_one(decode_step):
    """The decode position one past where it is."""
    def broken(params, token, cache, cfg, **kw):
        logits, new = decode_step(params, token,
                                  dict(cache, pos=cache["pos"] + 1), cfg,
                                  **kw)
        return logits, dict(new, pos=new["pos"] - 1)
    return broken


FAULTS = {"token_altered": ("step", token_altered),
          "kv_not_written": ("decode_step", kv_not_written),
          "layer_kv_not_written": ("decode_step", layer_kv_not_written),
          "position_off_by_one": ("decode_step", position_off_by_one)}


def _clear(make):
    """Forgets the steps built before: they hold the step they were
    traced with."""
    from benchmark.entries import serve

    make.cache_clear()
    serve._forced_step.cache_clear()


@contextlib.contextmanager
def plant(where: str, wrap):
    """Every engine and every reading of the program built inside runs
    with ``wrap`` around ``where``: ``"step"``, the engine's compiled step
    (``wrap(step, vocab)``), or ``"decode_step"``
    (``wrap(decode_step)``)."""
    generate = importlib.import_module("apex_tpu.models.generate")
    from apex_tpu.serving import engine as engine_mod

    make, step = engine_mod._make_decode_fn, generate.decode_step
    if where == "step":
        def make_broken(cfg, *args, **kw):
            return wrap(make(cfg, *args, **kw), cfg.vocab_size)
        engine_mod._make_decode_fn = make_broken
    else:
        engine_mod.decode_step = generate.decode_step = wrap(step)
    _clear(make)
    try:
        yield
    finally:
        engine_mod._make_decode_fn = make
        engine_mod.decode_step = generate.decode_step = step
        _clear(make)
